"""Benchmark harness — one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only NAME] [--quick]

Prints the contract CSV ``name,us_per_call,derived`` (one line per
benchmark row) and writes full row dumps to experiments/bench/*.csv.
A bench that raises prints an ``ERROR`` row, the rest still run, and
the harness then exits non-zero.
The ``incremental`` bench additionally dumps its per-save trajectory
(t_graph, t_podding, t_total, reuse counters, for both the incremental
and the from-scratch pipeline) to
``experiments/bench/BENCH_incremental.json`` for per-PR regression
diffing.
"""
from __future__ import annotations

import argparse
import csv
import os
import sys
import time
from typing import Callable, Dict, List

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

from . import (bench_core, bench_fingerprint, bench_incremental,  # noqa: E402
               bench_serve_fleet)

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "experiments",
                       "bench")

BENCHES: Dict[str, Callable[[], List[Dict]]] = {
    "storage_fig8": bench_core.bench_storage,
    "latency_fig9": bench_core.bench_latency,
    "breakdown_fig10": bench_core.bench_breakdown,
    "compression_fig11": bench_core.bench_compression,
    "loading_fig12": bench_core.bench_loading,
    "mutation_fig13": bench_core.bench_mutation_sweep,
    "scaling_fig14": bench_core.bench_scaling,
    "podding_fig15": bench_core.bench_podding_optimizers,
    "ablation_fig16": bench_core.bench_cd_avf,
    "async_fig17": bench_core.bench_async,
    "thesaurus_fig19": bench_core.bench_thesaurus,
    "ascc_table3": bench_core.bench_ascc,
    "kernel_fingerprint": bench_core.bench_kernel,
    "fingerprint_batch": bench_fingerprint.bench_fingerprint,
    "incremental": bench_incremental.bench_incremental,
    "serve_fleet": bench_serve_fleet.bench_serve_fleet,
}


def _derived_of(row: Dict) -> str:
    skip = {"bench"}
    parts = [f"{k}={v}" for k, v in row.items() if k not in skip]
    return ";".join(parts)


def main() -> None:
    enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--only", default=None)
    args = p.parse_args()
    failed: List[str] = []

    os.makedirs(OUT_DIR, exist_ok=True)
    print("name,us_per_call,derived")
    for name, fn in BENCHES.items():
        if args.only and args.only != name:
            continue
        t0 = time.perf_counter()
        try:
            rows = fn()
        except Exception as e:  # noqa: BLE001
            print(f"{name},0,ERROR:{type(e).__name__}:{e}")
            failed.append(name)
            continue
        us = (time.perf_counter() - t0) * 1e6 / max(len(rows), 1)
        with open(os.path.join(OUT_DIR, f"{name}.csv"), "w", newline="") as f:
            keys: List[str] = sorted({k for r in rows for k in r})
            w = csv.DictWriter(f, fieldnames=keys)
            w.writeheader()
            w.writerows(rows)
        for row in rows:
            print(f"{name},{us:.1f},{_derived_of(row)}")
    if failed:
        sys.exit(f"benches failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
