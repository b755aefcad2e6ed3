"""Single-sync save benchmark: fused on-device diff + speculative gather
vs the two-sync baseline (digest fetch + payload gather).

    PYTHONPATH=src python -m benchmarks.bench_singlesync [--quick]

Workload: the sparse-update regime on *device* (jnp) state — host numpy
leaves digest on the host and would hide the sync count under test.  Two
`Chipmink` instances replay the same mutate-then-save trajectory, one
`fused=True` and one `fused=False` (the PR 1 two-sync baseline), with
`jax.device_get` wrapped by a counting shim; reported per row:

  * blocking `device_get` calls per warm save for both paths
    (acceptance: fused == 1 on warm speculated sparse saves, ≤ 2
    always; baseline == 2 on dirty saves),
  * speculation hit rate (`n_spec_hits / (hits + misses)`) and
    corrective-sync count,
  * median warm save latency for both paths,
  * bit-identity of manifests/pods between the two paths.

The trajectory dumps to ``experiments/bench/BENCH_singlesync.json`` so
CI can diff sync-count or latency regressions per PR.
"""
from __future__ import annotations

import json
import os
import time
from typing import Any, Dict, List, Tuple

import numpy as np

OUT_JSON = os.path.join(os.path.dirname(__file__), "..", "experiments",
                        "bench", "BENCH_singlesync.json")

#: (rows, d, dirty rows/save, saves, chunk_bytes)
FULL_CFG = (8192, 64, 8, 10, 1 << 12)
QUICK_CFG = (2048, 32, 4, 8, 1 << 12)


def _trajectory(rows: int, d: int, dirty_rows: int, n_saves: int,
                seed: int = 0):
    """Deterministic mutate-then-save trajectory on device arrays.

    A fixed *hot* row set mutates every save (the skewed-access regime
    the flip-EMA speculator targets — frequent tokens, optimizer slots);
    one late save additionally touches a cold row, forcing a speculation
    miss so the corrective path shows up in the trajectory.
    """
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    emb = rng.standard_normal((rows, d)).astype(np.float32)
    mu = np.zeros_like(emb)
    hot = rng.integers(0, rows, size=dirty_rows)
    for step in range(n_saves):
        if step:
            emb[hot] += 1e-2
            mu[hot] = 0.9 * mu[hot] + 1e-2
        if step == n_saves - 2:           # one cold-row mispredict
            emb[(hot[0] + rows // 2) % rows] -= 1e-2
        yield {"params": {"emb": jnp.asarray(emb)},
               "opt": {"mu": jnp.asarray(mu)}, "step": step}


class _SyncCounter:
    """Wraps `jax.device_get` to count blocking fetches per save."""

    def __init__(self):
        import jax
        self._jax = jax
        self._real = jax.device_get
        self.n = 0

    def __enter__(self):
        def counted(x):
            self.n += 1
            return self._real(x)
        self._jax.device_get = counted
        return self

    def __exit__(self, *exc):
        self._jax.device_get = self._real
        return False

    def take(self) -> int:
        n, self.n = self.n, 0
        return n


def _replay(fused: bool, cfg: Tuple[int, ...]):
    from repro.core import Chipmink, MemoryStore
    rows, d, dirty, n_saves, chunk = cfg
    ck = Chipmink(MemoryStore(), chunk_bytes=chunk, fused=fused)
    syncs: List[int] = []
    t_total: List[float] = []
    with _SyncCounter() as counter:
        for state in _trajectory(rows, d, dirty, n_saves):
            t0 = time.perf_counter()
            ck.save(state)
            t_total.append(time.perf_counter() - t0)
            syncs.append(counter.take())
    return ck, syncs, t_total


def _strip(manifest: Dict) -> Dict:
    return {k: v for k, v in manifest.items() if k != "stats"}


def bench_singlesync(quick: bool = False) -> List[Dict]:
    cfg = QUICK_CFG if quick else FULL_CFG
    rows, d, dirty_rows, n_saves, chunk = cfg

    fus, fus_syncs, fus_total = _replay(True, cfg)
    ref, ref_syncs, ref_total = _replay(False, cfg)

    identical = True
    for tid in fus.store.list_time_ids():
        mf, mr = fus.store.get_manifest(tid), ref.store.get_manifest(tid)
        if _strip(mf) != _strip(mr):
            identical = False
        for meta in mf["pods"].values():
            dg = meta["d"]
            if not (fus.store.has_pod(dg) and ref.store.has_pod(dg)):
                identical = False
            elif fus.store.get_pod(dg) != ref.store.get_pod(dg):
                identical = False

    # warm saves: skip the all-dirty bootstrap and the EMA-settling
    # prefix (cold chunks decay below the speculation threshold after
    # four clean observations; the set shrink also recompiles the
    # padded gather once).
    warm = slice(5, None)
    hits = sum(s["n_spec_hits"] for s in fus.save_stats[warm])
    misses = sum(s["n_spec_misses"] for s in fus.save_stats[warm])
    hit_rate = hits / max(hits + misses, 1)
    corrective = [s["n_corrective_syncs"] for s in fus.save_stats[warm]]

    row = {
        "bench": "singlesync", "workload": "sparse_update_device",
        "syncs_per_warm_save_fused": float(np.median(fus_syncs[warm])),
        "syncs_per_warm_save_twosync": float(np.median(ref_syncs[warm])),
        "max_syncs_any_save_fused": int(max(fus_syncs)),
        "single_sync_warm": bool(np.median(fus_syncs[warm]) == 1.0),
        "le_two_syncs_always": bool(max(fus_syncs) <= 2),
        "spec_hit_rate": round(hit_rate, 4),
        "n_corrective_syncs_warm": int(sum(corrective)),
        "t_save_ms_fused_p50": round(1e3 * float(np.median(fus_total[warm])),
                                     3),
        "t_save_ms_twosync_p50": round(1e3 * float(np.median(ref_total[warm])),
                                       3),
        "artifacts_identical": bool(identical),
    }

    os.makedirs(os.path.dirname(OUT_JSON), exist_ok=True)
    traj = {
        "config": {"rows": rows, "d": d, "dirty_rows": dirty_rows,
                   "n_saves": n_saves, "chunk_bytes": chunk, "quick": quick},
        "fused": [_traj_row(s, n) for s, n in zip(fus.save_stats, fus_syncs)],
        "twosync": [_traj_row(s, n) for s, n in zip(ref.save_stats,
                                                    ref_syncs)],
        "summary": [row],
    }
    with open(OUT_JSON, "w") as f:
        json.dump(traj, f, indent=2, sort_keys=True)
    return [row]


def _traj_row(s: Dict[str, Any], n_syncs: int) -> Dict[str, Any]:
    keys = ("time_id", "t_digest", "t_gather", "t_write", "n_dirty_chunks",
            "n_digest_syncs", "n_gather_syncs", "n_corrective_syncs",
            "n_spec_predicted", "n_spec_hits", "n_spec_misses",
            "n_fused_rows")
    out = {k: s[k] for k in keys if k in s}
    out["device_get_calls"] = n_syncs
    return out


def main() -> None:
    import argparse
    p = argparse.ArgumentParser()
    p.add_argument("--quick", action="store_true",
                   help="small config for CI smoke runs")
    args = p.parse_args()
    for row in bench_singlesync(quick=args.quick):
        print(",".join(f"{k}={v}" for k, v in row.items()))


if __name__ == "__main__":
    main()
