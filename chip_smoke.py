"""Smoke run of the Chipmink save path on one TPU chip.

    python3 chip_smoke.py

Drives the main path once, through the entry points a user calls, at the
published widths of qwen1.5-0.5b (24 layers, d_model 1024, vocab 151936;
random weights from seed 0):

  * train phase — `repro.launch.train.train(reduced=False)` takes a few
    AdamW steps and saves every few steps with async saves into a
    `FileStore`.  Checks: the first save digested every chunk of the
    state through the compiled kernel; sampled kernel digests equal the
    numpy digest twin (`fingerprint_words_np`) of the same bytes; warm
    saves fetch digests with one device sync; a fresh `Chipmink` on the
    store loads the last commit bit-identical to the live state.
  * serve phase — `repro.launch.serve.serve(reduced=False,
    n_sessions=2)` decodes a few tokens per session with snapshots into
    one `FileStore`; a new `SessionService` resumes one session, and its
    cache must be bit-identical to the live one.

Earlier lines report, for information, each save's host timers from
`Chipmink.save_stats` and each phase's wall time, compile time and peak
device memory.  The last line is one JSON object naming
the device.  Exits non-zero, printing no such line, when JAX finds no
TPU or any check fails.  One process; stores live in a git-ignored
directory of the checkout and are removed at the end.
"""
from __future__ import annotations

import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
WORK_DIR = os.path.join(ROOT, ".smoke_stores")
ARCH = "qwen1.5-0.5b"
#: train phase: steps, save cadence, batch shape
STEPS, SAVE_EVERY, BATCH, SEQ = 4, 2, 8, 128
#: serve phase: requests per session, cache length, tokens, save cadence
REQUESTS, CACHE_LEN, GEN_TOKENS, SERVE_SAVE_EVERY = 4, 128, 8, 8
#: leaves whose kernel digests are cross-checked on the host
N_SAMPLED_LEAVES = 6


class SmokeCheckFailed(RuntimeError):
    pass


def _check(ok: bool, msg: str) -> None:
    """A smoke check: raises (unlike `assert`, also under ``python -O``)."""
    if not ok:
        raise SmokeCheckFailed(msg)


class _CompileClock:
    """Sums JAX's backend-compile durations (seconds) as they happen."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        import jax
        self.total = 0.0

        def listen(event, duration, **_):
            if event == self.EVENT:
                self.total += duration

        jax.monitoring.register_event_duration_secs_listener(listen)


def _flat(tree) -> dict:
    """{path: array or scalar} over Chipmink's own pytree walk."""
    from repro.core.graph import SCALAR, build_graph
    g = build_graph(tree, chunk_bytes=1 << 40)
    out = dict(g.arrays)
    out.update({n.key: n.value for n in g.nodes.values()
                if n.kind == SCALAR})
    return out


def _check_bit_identical(live, restored, what: str) -> int:
    """Every leaf of `restored` equals `live` in dtype, shape and bytes;
    returns the array bytes compared."""
    import numpy as np
    a, b = _flat(live), _flat(restored)
    _check(sorted(a) == sorted(b), f"{what}: leaf sets differ")
    n_bytes = 0
    for key in sorted(a):
        x, y = a[key], b[key]
        if not hasattr(x, "dtype"):
            _check(x == y, f"{what}: scalar {key} {x!r} != {y!r}")
            continue
        x, y = np.asarray(x), np.asarray(y)
        _check(x.dtype == y.dtype and x.shape == y.shape,
               f"{what}: {key} is {y.dtype}{y.shape}, "
               f"live {x.dtype}{x.shape}")
        _check(x.tobytes() == y.tobytes(), f"{what}: {key} bytes differ")
        n_bytes += x.nbytes
    return n_bytes


def _print_saves(phase: str, stats) -> None:
    """One informational line per save: dirty chunks and host timers."""
    for st in stats:
        print(f"{phase} save {st['time_id']}: "
              f"{st['n_dirty_chunks']}/{st['n_chunks']} chunks dirty, "
              f"t_digest {st['t_digest']:.3f} s, "
              f"t_gather {st['t_gather']:.3f} s, "
              f"t_write {st['t_write']:.3f} s, "
              f"{st['bytes_written']} bytes written", flush=True)


def _check_kernel_compiled() -> None:
    """The fingerprint kernel lowers to a Mosaic custom call, not to the
    Pallas interpreter."""
    import functools

    import jax
    import jax.numpy as jnp
    from repro.kernels.fingerprint import (fingerprint_words_cmp,
                                           pallas_interpret)
    _check(pallas_interpret() is False, "kernel mode is interpret")
    u32 = jnp.uint32
    text = jax.jit(functools.partial(fingerprint_words_cmp, rows=64)).lower(
        jax.ShapeDtypeStruct((64, 4096), u32),
        jax.ShapeDtypeStruct((64,), u32),
        jax.ShapeDtypeStruct((64, 4), u32)).as_text()
    _check("tpu_custom_call" in text, "kernel is not a compiled TPU call")


def train_phase(store_dir: str, *, reduced: bool = False) -> dict:
    import jax
    import numpy as np
    from repro.core import Chipmink, FileStore
    from repro.core.graph import build_graph, chunk_grid
    from repro.kernels.ops import leaf_fingerprint_np
    from repro.launch.train import snapshot_of, train

    out = train(ARCH, steps=STEPS, save_every=SAVE_EVERY,
                store_dir=store_dir, reduced=reduced, global_batch=BATCH,
                seq_len=SEQ)
    ck = out["chipmink"]
    ck.close()
    _check(np.isfinite(out["losses"]).all(), "non-finite loss")
    live = snapshot_of(out["state"], out["pipeline"])
    graph = build_graph(live, chunk_bytes=ck.chunk_bytes)
    n_chunks = {kind: sum(
        chunk_grid(a.shape, np.dtype(a.dtype), ck.chunk_bytes)[1]
        for a in graph.arrays.values()
        if isinstance(a, jax.Array) == (kind == "device"))
        for kind in ("device", "host")}
    n_all = n_chunks["device"] + n_chunks["host"]

    stats = ck.save_stats
    _print_saves("train", stats)
    _check(len(stats) == STEPS // SAVE_EVERY, f"{len(stats)} saves landed")
    first = stats[0]
    _check(first["n_chunks"] == first["n_dirty_chunks"] == n_all,
           f"first save digested {first['n_chunks']} of {n_all} chunks, "
           f"{first['n_dirty_chunks']} dirty")
    _check(first["n_fused_rows"] == n_chunks["device"],
           f"{first['n_fused_rows']} of {n_chunks['device']} device "
           "chunks went through the kernel")
    for st in stats[1:]:
        _check(st["n_digest_syncs"] == 1,
               f"warm save {st['time_id']}: {st['n_digest_syncs']} "
               "digest syncs")

    # kernel digests of the last save (the live state) against the numpy
    # twin over the same bytes: zero tolerance.  The largest leaf is
    # always sampled; the rest are drawn from seed 0.
    table = ck.detector.export_table()
    keys = sorted(graph.arrays, key=lambda k: -graph.arrays[k].size)
    rng = np.random.default_rng(0)
    picks = [keys[0]] + list(rng.choice(keys[1:], size=min(
        N_SAMPLED_LEAVES - 1, len(keys) - 1), replace=False))
    n_checked = 0
    for key in picks:
        host = leaf_fingerprint_np(np.asarray(graph.arrays[key]),
                                   chunk_bytes=ck.chunk_bytes,
                                   seed=ck.detector.seed)
        for ci in range(host.shape[0]):
            _check(table[f"{key}#[{ci}]"] == host[ci].tobytes(),
                   f"kernel digest of {key}#[{ci}] differs from the "
                   "numpy twin")
        n_checked += host.shape[0]

    restored = Chipmink(FileStore(store_dir),
                        chunk_bytes=ck.chunk_bytes).load(like=live)
    n_bytes = _check_bit_identical(live, restored, "restored train state")
    return {"saves": len(stats), "device_chunks": n_chunks["device"],
            "digests_cross_checked": n_checked, "state_bytes": n_bytes,
            "loss_first": out["losses"][0], "loss_last": out["losses"][-1]}


def serve_phase(store_dir: str, *, reduced: bool = False) -> dict:
    from repro.core import FileStore
    from repro.launch.serve import serve
    from repro.sessions import SessionService

    out = serve(ARCH, n_requests=REQUESTS, gen_tokens=GEN_TOKENS,
                cache_len=CACHE_LEN, save_every=SERVE_SAVE_EVERY,
                reduced=reduced, n_sessions=2, store="file",
                store_dir=store_dir)
    out["service"].close()
    _print_saves("serve", out["snap_stats"])
    n_tokens = out["tokens"].shape[1]
    _check(n_tokens == GEN_TOKENS, f"{n_tokens} tokens generated")
    # serve evicts the last session; s0's last snapshot is its final token
    svc = SessionService(FileStore(store_dir),
                         chunk_bytes=out["chipmink"].chunk_bytes)
    state = svc.resume_session("s0")
    svc.close()
    n_bytes = _check_bit_identical(out["caches"]["s0"], state["cache"],
                                   "resumed session cache")
    return {"snapshots": len(out["snap_stats"]), "tokens": n_tokens,
            "cache_bytes": n_bytes}


def main() -> int:
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    _check_kernel_compiled()

    clock = _CompileClock()
    shutil.rmtree(WORK_DIR, ignore_errors=True)
    try:
        for name, phase in (("train", train_phase), ("serve", serve_phase)):
            t0, c0 = time.perf_counter(), clock.total
            info = phase(os.path.join(WORK_DIR, name))
            wall = time.perf_counter() - t0
            mem = dev.memory_stats() or {}
            gib = {k: mem.get(k, 0) / 2**30
                   for k in ("peak_bytes_in_use", "bytes_limit")}
            print(f"{name} phase: wall {wall:.1f} s, compile "
                  f"{clock.total - c0:.1f} s, peak device memory since "
                  f"start {gib['peak_bytes_in_use']:.3f} of "
                  f"{gib['bytes_limit']:.3f} GiB; {info}", flush=True)
    finally:
        shutil.rmtree(WORK_DIR, ignore_errors=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
