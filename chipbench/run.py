"""Benchmark of the Chipmink save path on the chip.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Builds the cell's deployment from its files (see `chipbench/harness.py`),
warms every shape it uses, drives its closed loop for `--seconds`, checks
that what was saved restores bit-identical, and prints one JSON result as
the last line of standard output; the numbers compared, each with its
limit, are the last lines of standard error.  With `--trace 1` the
metrics are the per-layer ones, read from a profiler trace of the window
and from the program's per-save timers.  Exits non-zero, printing no
result, when JAX finds no TPU or fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = p.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

    from chipbench import harness
    spec = harness.cell_spec(harness.load_benchmark(ROOT), a.workload)
    try:
        harness.devices(spec.chips)
    except harness.NoAccelerator as exc:
        print(f"chipbench: {exc}", file=sys.stderr)
        return 2
    print(f"compile cache: {harness.use_compile_cache()}", flush=True)

    out = harness.run(spec, a.seed, a.seconds, bool(a.trace),
                      t_start=T_START)
    for line in out.info:
        print(line, flush=True)
    for name, value in out.numbers.items():
        limit = out.result["checks"][name]["limit"]
        print(f"check {name}: {value} (limit {limit})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out.result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
