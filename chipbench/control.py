"""Runs of a cell with the save path broken, to read what the check
compares when the program is wrong.

    python3 chipbench/control.py --workload <cell> --mode control \
        --seeds 1,2,3 --seconds 10

`--mode` is one of `chipbench.faults.MODES`, or `none` for the sound
program.  All seeds run in one process.  One line per seed: the mode,
the seed, `correct` and every number compared.  Never part of a
benchmark run.
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
    p.add_argument("--mode", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, required=True)
    a = p.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

    from chipbench import faults, harness
    spec = harness.cell_spec(harness.load_benchmark(ROOT), a.workload)
    harness.devices(spec.chips)
    harness.use_compile_cache()
    plant = None if a.mode == "none" else faults.MODES[a.mode]
    for seed in (int(s) for s in a.seeds.split(",")):
        t0 = time.perf_counter()
        try:
            out = harness.run(spec, seed, a.seconds, False, t_start=t0,
                              plant=plant)
            line = {"correct": out.result["correct"], "numbers": out.numbers}
        except Exception as exc:     # a crash is a control that failed
            line = {"correct": False,
                    "crashed": f"{type(exc).__name__}: {exc}"[:500]}
        print(json.dumps({"mode": a.mode, "seed": seed, **line,
                          "wall_s": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
