"""A training job that checkpoints through `Chipmink.save`.

Built as `repro.launch.train` builds it: `init_model_params`,
`init_train_state`, `make_train_step`, `TokenPipeline`, the ASCC's
read-only leaves and `snapshot_of`; the step donates its input except
right after a save.  The configuration file gives the model and the
deployment settings the launcher passes (chunk size, podding policy,
async saves, store); the traffic mix gives which layers train and how
often the job saves.

Traffic mix keys: `train_top_layers` (the top layers that train),
`train_also` (other top-level parameter groups that train),
`save_every_steps`, `warm_saves` (saves after the first, in set-up).
"""
from __future__ import annotations

import os
from typing import Any, Dict, List

import numpy as np

from . import common


class Deployment:
    def __init__(self, cfg: Dict[str, Any], mix: Dict[str, Any], seed: int,
                 workdir: str, log, annotate) -> None:
        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.dep = cfg["deployment"]
        self.store_dir = os.path.join(workdir, "store")
        self.log = log
        self.annotate = annotate
        self.chunk_bytes = self.dep["chunk_bytes"]
        self.streams = ["train"]
        self.steps = 0
        #: rounds from one save to the next; a window holds whole cycles
        self.cycle = mix["save_every_steps"]
        self.losses: List[float] = []
        self._reader = None

    # -- set-up -----------------------------------------------------------
    def setup(self) -> None:
        import jax
        import jax.numpy as jnp
        from repro.core import Chipmink
        from repro.core.ascc import readonly_state_leaves
        from repro.models.model import init_model_params
        from repro.train.data import TokenPipeline
        from repro.train.optimizer import OptConfig
        from repro.train.train_step import (init_train_state,
                                            make_train_step,
                                            touched_prefixes_from_metrics)

        arch = common.arch_config(self.cfg)
        opt = OptConfig(**self.dep["optimizer"])
        self.arch = arch
        self.state = jax.jit(lambda k: init_train_state(
            arch, init_model_params(arch, k), opt))(
                common.jax_key(self.seed, common.WEIGHTS))
        self.pipeline = TokenPipeline(
            arch.vocab, self.dep["global_batch"], self.dep["seq_len"],
            seed=common.child_seed(self.seed, common.DATA))
        top = self.mix["train_top_layers"]
        trained = {f"layers/{i}" for i in range(arch.n_layers - top,
                                                arch.n_layers)}
        trained |= set(self.mix["train_also"])
        groups = [f"layers/{i}" for i in range(arch.n_layers)] + [
            k for k in self.state["params"] if k != "layers"]
        self.frozen = tuple(f"params/{g}" for g in groups if g not in trained)
        step = make_train_step(arch, opt, frozen=self.frozen, remat=False)

        def chipbench_step(state, batch):
            return step(state, batch)

        self.step_keep = jax.jit(chipbench_step)
        self.step_donate = jax.jit(chipbench_step, donate_argnums=0)
        self.touched = lambda metrics: touched_prefixes_from_metrics(
            arch, metrics, self.frozen)
        self.ck = Chipmink(common.store_of(self.dep, self.store_dir),
                           common.policy_of(self.dep),
                           chunk_bytes=self.chunk_bytes,
                           async_mode=self.dep["async_mode"])
        self.log.watch(self.ck)

        example = {k: jnp.asarray(v)
                   for k, v in self.pipeline.next_batch().items()}
        self.pipeline.restore({**self.pipeline.cursor(), "step": 0})
        readonly = readonly_state_leaves(self.step_keep, self.state, example)
        self.readonly = {"params/" + p if not p.startswith(
            ("params", "opt", "step")) else p for p in readonly}
        self.active_bytes = sum(
            int(v.nbytes) for p, v in _device_leaves(self.state).items()
            if p not in self.readonly)
        self.saved = False
        n_saves = 1 + self.mix["warm_saves"]
        while len(self.log.records) < n_saves:
            self.round()
        self.ck.wait()

    # -- traffic ----------------------------------------------------------
    def round(self) -> None:
        import jax.numpy as jnp
        from repro.launch.train import snapshot_of
        batch = {k: jnp.asarray(v)
                 for k, v in self.pipeline.next_batch().items()}
        step = self.step_keep if self.saved else self.step_donate
        with self.annotate("chipbench.step"):
            self.state, metrics = step(self.state, batch)
            self.losses.append(float(metrics["nll"]))
        self.steps += 1
        self.saved = self.steps % self.mix["save_every_steps"] == 0
        if self.saved:
            touched = self.touched(metrics)
            snap = snapshot_of(self.state, self.pipeline)
            self.log.save("train", snap, lambda s: self.ck.save(
                s, touched_prefixes=touched, readonly_paths=self.readonly),
                self.active_bytes)

    def drain(self) -> None:
        self.ck.wait()

    def logical_bytes(self) -> int:
        return sum(int(v.nbytes) for v in _device_leaves(self.state).values())

    def traffic_faults(self) -> int:
        return int(sum(not np.isfinite(x) for x in self.losses))

    def release(self) -> None:
        """Free what the check does not need (the live state; the last
        snapshot stays with the save log)."""
        errors = self.ck.close()
        if errors:
            raise errors[0]
        del self.state, self.step_keep, self.step_donate, self.ck

    # -- what the check reads: a fresh instance on the same store --------
    def _fresh(self):
        if self._reader is None:
            from repro.core import Chipmink
            self._reader = Chipmink(common.store_of(self.dep, self.store_dir),
                                    chunk_bytes=self.chunk_bytes)
        return self._reader

    def restore_last(self, stream: str, tid: int, like: Any) -> Any:
        return self._fresh().load(time_id=tid, like=like)

    def restore(self, tid: int) -> Any:
        return self._fresh().load(time_id=tid)

    def manifest(self, tid: int) -> Dict[str, Any]:
        return self._fresh().store.get_manifest(tid)

    def close_reader(self) -> None:
        if self._reader is not None:
            self._reader.close()
            self._reader = None


def _device_leaves(state: Any) -> Dict[str, Any]:
    from ..reference import array_leaves
    snap = {"params": state["params"], "opt": state["opt"]}
    return array_leaves(snap)
