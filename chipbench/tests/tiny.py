"""Cells of the benchmark cut to a size the CPU runs in seconds: the
same configuration files and mixes, with the model's and the fleet's
sizes made small.  For the tests only; the benchmark never runs these."""
from __future__ import annotations

import time

from chipbench import harness

TINY_MODEL = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=3,
                  num_attention_heads=2, num_key_value_heads=2,
                  vocab_size=512)
CELLS = {"train": "train-finetune-top2", "serve": "serve-decode-snap2"}
#: the serving deployment's files, which no cell of BENCHMARK.json runs
#: yet, entered as a cell would enter them
SERVE_CONFIG = {"name": "qwen1.5-0.5b-serve",
                "file": "chipbench/configs/qwen1.5-0.5b-serve.json"}
SERVE_CELL = {"name": "serve-decode-snap2", "config": "qwen1.5-0.5b-serve",
              "traffic": "decode-snap2", "chips": 1}


def tiny_spec(kind: str) -> harness.CellSpec:
    bench = harness.load_benchmark()
    if kind == "serve":
        bench = dict(bench, configs=bench["configs"] + [SERVE_CONFIG],
                     workloads=bench["workloads"] + [SERVE_CELL])
    spec = harness.cell_spec(bench, CELLS[kind])
    spec.cfg.update(TINY_MODEL)
    if kind == "train":
        spec.cfg["deployment"].update(chunk_bytes=1024, global_batch=2,
                                      seq_len=16)
    else:
        spec.cfg.update(cache_positions=16, requests_per_session=2)
        spec.cfg["deployment"].update(chunk_bytes=256)
    return spec


def tiny_run(kind: str, work_dir, *, seed: int = 2**31 + 7,
             seconds: float = 1.0, plant=None) -> harness.Outcome:
    return harness.run(tiny_spec(kind), seed, seconds, False,
                       t_start=time.perf_counter(), require_tpu=False,
                       plant=plant, work_dir=str(work_dir))
