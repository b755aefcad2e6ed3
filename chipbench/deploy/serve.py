"""A fleet of decoding sessions that snapshots through
`SessionService.save_session`.

Built as `repro.launch.serve` builds it: `init_model_params`,
`make_decode_step`, one `SessionService` over one store with one pool
slot per session, prompts that share a prefix, greedy decoding with the
tokens read back each step, and a snapshot of `{"cache", "cursor"}`
every few tokens.  The configuration file gives the model, the fleet
(sessions, requests per session, cache positions) and the settings the
launcher passes; the traffic mix gives the prompts and the cadence.

Traffic mix keys: `shared_prefix_tokens`, `own_prompt_tokens`,
`snapshot_every_tokens`, `warm_snapshots` (per session after its first,
in set-up).
"""
from __future__ import annotations

import os
from typing import Any, Dict, List

import numpy as np

from . import common

#: the launcher's cap on pool slots
MAX_POOL = 4


class _Session:
    def __init__(self, sid: str, prompts: np.ndarray, cache: Any) -> None:
        self.sid = sid
        self.prompts = prompts
        self.cache = cache
        self.logits = None
        self.tokens = 0


class Deployment:
    def __init__(self, cfg: Dict[str, Any], mix: Dict[str, Any], seed: int,
                 workdir: str, log, annotate) -> None:
        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.dep = cfg["deployment"]
        self.store_dir = os.path.join(workdir, "store")
        self.log = log
        self.annotate = annotate
        self.chunk_bytes = self.dep["chunk_bytes"]
        self.streams = [f"s{i}" for i in range(cfg["sessions"])]
        self.steps = 0
        #: rounds from one snapshot to the next; a window holds whole cycles
        self.cycle = mix["snapshot_every_tokens"]
        self._reader = None

    def setup(self) -> None:
        import jax
        from repro.models.model import api, init_model_params
        from repro.sessions import SessionService
        from repro.train.serve_step import make_decode_step

        arch = common.arch_config(self.cfg)
        self.arch = arch
        self.params = jax.jit(lambda k: init_model_params(arch, k))(
            common.jax_key(self.seed, common.WEIGHTS))
        step = make_decode_step(arch)

        def chipbench_step(params, cache, tokens):
            return step(params, cache, tokens)

        self.step = jax.jit(chipbench_step)
        n_req = self.cfg["requests_per_session"]
        self.svc = SessionService(
            common.store_of(self.dep, self.store_dir),
            pool_size=min(len(self.streams), MAX_POOL),
            policy=common.policy_of(self.dep),
            chunk_bytes=self.chunk_bytes,
            async_mode=self.dep["async_mode"])
        for ck in self.svc.pool:
            self.log.watch(ck)
        rng = np.random.default_rng(common.child_seed(self.seed, common.DATA))
        shared = rng.integers(0, arch.vocab,
                              size=(n_req, self.mix["shared_prefix_tokens"]))
        init_cache = jax.jit(lambda: api(arch).init_cache(
            arch, n_req, self.cfg["cache_positions"]))
        self.sessions: List[_Session] = []
        for sid in self.streams:
            own = rng.integers(0, arch.vocab,
                               size=(n_req, self.mix["own_prompt_tokens"]))
            prompts = np.concatenate([shared, own], axis=1).astype(np.int32)
            self.svc.open_session(sid)
            self.sessions.append(_Session(sid, prompts, init_cache()))
        self.cache_bytes = sum(int(v.nbytes) for v in jax.tree.leaves(
            self.sessions[0].cache))
        every = self.mix["snapshot_every_tokens"]
        n_snaps = 1 + self.mix["warm_snapshots"]
        while min(s.tokens for s in self.sessions) < max(
                n_snaps * every, self.sessions[0].prompts.shape[1] + every):
            self.round()
        self.drain()

    def round(self) -> None:
        import jax.numpy as jnp
        every = self.mix["snapshot_every_tokens"]
        with self.annotate("chipbench.step"):
            for sess in self.sessions:
                if sess.tokens < sess.prompts.shape[1]:
                    tok = jnp.asarray(sess.prompts[:, sess.tokens:sess.tokens + 1])
                else:
                    tok = jnp.argmax(sess.logits, axis=-1)[:, None].astype(
                        jnp.int32)
                    np.asarray(tok)           # streamed to the client
                sess.logits, sess.cache = self.step(self.params, sess.cache,
                                                    tok)
                sess.tokens += 1
                if sess.tokens % every == 0:
                    snap = {"cache": sess.cache,
                            "cursor": {"pos": sess.tokens}}
                    self.log.save(sess.sid, snap,
                                  lambda s, sid=sess.sid:
                                  self.svc.save_session(sid, s),
                                  self.cache_bytes)
        self.steps += 1

    def drain(self) -> None:
        for ck in self.svc.pool:
            ck.wait()

    def logical_bytes(self) -> int:
        return self.cache_bytes

    def traffic_faults(self) -> int:
        return 0

    def release(self) -> None:
        errors = self.svc.close()
        if errors:
            raise errors[0]
        del self.params, self.step, self.svc
        self.sessions = []

    # -- what the check reads: a new service on the same store ----------
    def _fresh(self):
        if self._reader is None:
            from repro.sessions import SessionService
            self._reader = SessionService(
                common.store_of(self.dep, self.store_dir),
                chunk_bytes=self.chunk_bytes)
        return self._reader

    def restore_last(self, stream: str, tid: int, like: Any) -> Any:
        """The migration path: the new service adopts the session's
        branch and restores its tip."""
        return self._fresh().resume_session(stream)

    def restore(self, tid: int) -> Any:
        return self._fresh().pool[0].load(time_id=tid)

    def manifest(self, tid: int) -> Dict[str, Any]:
        return self._fresh().store.get_manifest(tid)

    def close_reader(self) -> None:
        if self._reader is not None:
            self._reader.close()
            self._reader = None
