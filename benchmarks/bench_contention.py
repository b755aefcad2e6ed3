"""Contention benchmark: N writer processes + a collector on ONE store.

    PYTHONPATH=src python -m benchmarks.bench_contention [--quick]

N separate Python processes (spawned, each its own Chipmink in
``multi_writer`` mode) save disjoint branches against one FileStore
while a GC process mark-and-sweeps in a loop.  Each writer also creates
and deletes a throwaway branch, so the collector has real garbage to
reclaim *while* saves are in flight — the sweep fence and save intents
are doing live work, not idling.

Measured:

  * **save latency** p50 / p99 per writer (the cost of lease traffic +
    CAS contention on the hot path);
  * **lost-race retries** — refs CAS races (`CommitDAG.n_cas_races`),
    lease blob races (`LeaseManager.n_blob_cas_races`), and store-level
    CAS conflicts (`StoreStats.meta_cas_conflicts`);
  * **GC under contention** — runs, mark restarts (refs moved mid-mark),
    intent-pinned pods (the sweep fence firing), bytes reclaimed;
  * **correctness** — zero lost commits: every recorded TimeID loads
    bit-identical to its formulaic oracle after the dust settles, and
    only the deleted throwaway branches were collected.

Rows land in ``experiments/bench/BENCH_contention.json``; CI runs the
--quick config as a smoke check.
"""
from __future__ import annotations

import argparse
import json
import multiprocessing as mp
import os
import shutil
import tempfile
import time
from typing import Any, Dict, List

import numpy as np

OUT_JSON = os.path.join(os.path.dirname(__file__), "..", "experiments",
                        "bench", "BENCH_contention.json")

#: (n_writers, saves_per_writer, rows, tmp_branch_saves)
FULL_CFG = (4, 12, 512, 3)
QUICK_CFG = (4, 4, 128, 2)

LEASE_TTL_S = 5.0


def _fill(idx: int, i: int) -> float:
    return 10_000.0 * (idx + 1) + i


def _state(rows: int, fill: float) -> Dict[str, Any]:
    return {"w": np.full((rows, 16), np.float32(fill)),
            "b": np.arange(64, dtype=np.float32) + np.float32(fill),
            "step": int(fill)}


def _open(root: str):
    from repro.core import Chipmink, FileStore
    return Chipmink(store=FileStore(root), use_kernel=False,
                    multi_writer=True, lease_ttl_s=LEASE_TTL_S,
                    fsck_on_open=False)


def _writer_proc(root: str, idx: int, n_saves: int, rows: int,
                 tmp_saves: int, out_q) -> None:
    ck = _open(root)
    ck.checkout("main")
    ck.branch(f"w{idx}")
    lat: List[float] = []
    tids: List[int] = []
    for i in range(n_saves):
        s = _state(rows, _fill(idx, i))
        t0 = time.perf_counter()
        tids.append(ck.save(s))
        lat.append(time.perf_counter() - t0)
    # garbage production: a throwaway branch the collector must reclaim
    # (and must reclaim ONLY this) while peers keep saving.
    ck.branch(f"tmp{idx}")
    doomed = [ck.save(_state(rows, -_fill(idx, i)))
              for i in range(tmp_saves)]
    ck.checkout(f"w{idx}")
    ck.delete_branch(f"tmp{idx}")
    ck.close()
    out_q.put({
        "idx": idx, "tids": tids, "doomed": doomed, "lat": lat,
        "refs_cas_races": ck.versions.n_cas_races,
        "lease_cas_races": ck.leases.n_blob_cas_races,
        "meta_cas_conflicts": ck.store.stats.meta_cas_conflicts,
        "alias_rewrites": sum(s.get("n_alias_rewrites", 0)
                              for s in ck.save_stats),
    })


def _gc_proc(root: str, stop_path: str, out_q) -> None:
    from repro.core import LeaseHeld
    ck = _open(root)
    agg = {"gc_runs": 0, "gc_mark_restarts": 0, "gc_mark_aborts": 0,
           "pods_pinned": 0, "commits_pinned": 0, "bytes_reclaimed": 0,
           "gc_errors": 0}
    while not os.path.exists(stop_path):
        try:
            st = ck.gc()
            agg["gc_runs"] += 1
            agg["gc_mark_restarts"] += st.n_mark_restarts
            agg["pods_pinned"] += st.n_pods_pinned
            agg["commits_pinned"] += st.n_commits_pinned
            agg["bytes_reclaimed"] += st.bytes_reclaimed
        except LeaseHeld:
            agg["gc_errors"] += 1
        except RuntimeError:
            # refs kept moving through every re-mark: writers saturate
            # the store and this gc cycle yields — expected under peak
            # contention, the next cycle tries again.
            agg["gc_mark_aborts"] += 1
        time.sleep(0.02)
    ck.close()
    out_q.put(agg)


def _start_on_cpu(procs) -> None:
    """Start spawned children with JAX pinned to the CPU: their states
    are numpy (``use_kernel=False``), and an accelerator belongs to one
    process, so none of them may reach for it."""
    prev = os.environ.get("JAX_PLATFORMS")
    os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        for p in procs:
            p.start()
    finally:
        if prev is None:
            del os.environ["JAX_PLATFORMS"]
        else:
            os.environ["JAX_PLATFORMS"] = prev


def _pct(xs: List[float], q: float) -> float:
    return float(np.percentile(np.asarray(xs), q)) if xs else 0.0


def run(cfg, verbose: bool = True) -> Dict[str, Any]:
    n_writers, n_saves, rows, tmp_saves = cfg
    root = tempfile.mkdtemp(prefix="chipmink-contend-")
    stop_path = os.path.join(root, "GC_STOP")
    try:
        boot = _open(root)
        boot.save(_state(rows, 0.0))           # shared root on main
        boot.close()

        ctx = mp.get_context("spawn")
        q = ctx.Queue()
        gq = ctx.Queue()
        gc = ctx.Process(target=_gc_proc, args=(root, stop_path, gq))
        _start_on_cpu([gc])
        t_wall = time.perf_counter()
        procs = [ctx.Process(target=_writer_proc,
                             args=(root, i, n_saves, rows, tmp_saves, q))
                 for i in range(n_writers)]
        _start_on_cpu(procs)
        writers = [q.get(timeout=600) for _ in procs]
        for p in procs:
            p.join()
        t_wall = time.perf_counter() - t_wall
        open(stop_path, "w").close()
        gc_agg = gq.get(timeout=600)
        gc.join()
        assert all(p.exitcode == 0 for p in procs), "a writer crashed"
        assert gc.exitcode == 0, "the collector crashed"

        # ---- serialized verification: zero lost commits ----
        ver = _open(root)
        final = ver.gc()                        # reclaim remaining garbage
        lost = 0
        for w in writers:
            for i, tid in enumerate(w["tids"]):
                loaded = ver.load(time_id=tid)
                want = _state(rows, _fill(w["idx"], i))
                if not (loaded["step"] == want["step"]
                        and np.array_equal(loaded["w"], want["w"])
                        and np.array_equal(loaded["b"], want["b"])):
                    lost += 1
        all_tids = [t for w in writers for t in w["tids"]]
        doomed = {t for w in writers for t in w["doomed"]}
        survivors = set(ver.store.list_time_ids())
        rep = ver.fsck()
        ver.close()

        lat = [x for w in writers for x in w["lat"]]
        summary = {
            "n_writers": n_writers,
            "saves_per_writer": n_saves,
            "wall_s": round(t_wall, 3),
            "zero_lost_commits": lost == 0
            and len(set(all_tids)) == len(all_tids),
            "gc_swept_only_garbage":
                set(all_tids) <= survivors
                and not (doomed & survivors),
            "save_p50_ms": round(_pct(lat, 50) * 1e3, 3),
            "save_p99_ms": round(_pct(lat, 99) * 1e3, 3),
            "refs_cas_races": sum(w["refs_cas_races"] for w in writers),
            "lease_cas_races": sum(w["lease_cas_races"] for w in writers),
            "meta_cas_conflicts": sum(w["meta_cas_conflicts"]
                                      for w in writers),
            "alias_rewrites": sum(w["alias_rewrites"] for w in writers),
            "bytes_reclaimed": gc_agg["bytes_reclaimed"]
            + final.bytes_reclaimed,
            "fsck_clean_after": rep.clean,
            **{k: v for k, v in gc_agg.items() if k != "bytes_reclaimed"},
        }
        if verbose:
            for k, v in summary.items():
                print(f"  {k:>22}: {v}")
        assert summary["zero_lost_commits"], "a committed save was lost"
        assert summary["gc_swept_only_garbage"], "GC swept live data"
        return summary
    finally:
        shutil.rmtree(root, ignore_errors=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small config (CI smoke)")
    args = ap.parse_args()
    cfg = QUICK_CFG if args.quick else FULL_CFG
    print(f"contention bench: {cfg[0]} writers x {cfg[1]} saves "
          f"(rows={cfg[2]}, quick={args.quick})")
    summary = run(cfg)
    payload = {
        "bench": "contention",
        "quick": args.quick,
        "config": {"n_writers": cfg[0], "saves_per_writer": cfg[1],
                   "rows": cfg[2], "tmp_branch_saves": cfg[3],
                   "lease_ttl_s": LEASE_TTL_S},
        "summary": summary,
    }
    os.makedirs(os.path.dirname(OUT_JSON), exist_ok=True)
    with open(OUT_JSON, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
    print(f"wrote {os.path.normpath(OUT_JSON)}")


if __name__ == "__main__":
    main()
