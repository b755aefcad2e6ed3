"""The control and the planted model faults that the set-up comparison of
`train-moe-touched` (`deploy/train_esft.py`) must catch.

    python3 chipbench/faults_esft.py --mode control --seeds 1,2 --seconds 1

Never used by a benchmark run: the command drives them on the chip,
`chipbench/tests/test_chipbench_esft.py` on the CPU.  As in
`chipbench.faults`, each `install(log)` breaks one run before its set-up
and returns a function that undoes it; the command prints one line per
seed: the mode, the seed, `correct`, every number compared and the
model check's readings.

* `control`: the step's readings are the reference's in the nearest
  precision below the configuration's bfloat16 (`reference.LOW`, every
  matmul operand and the stored parameters).
* `unmasked`: the train step's expert mask passes every row through, so
  the frozen experts train.
* `no_bias_correction`: AdamW leaves the bias correction out of its
  update.
* `half_batch`: the step trains on half of each batch.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Callable

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if __name__ == "__main__":
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench import reference_deepseek_v2 as reference  # noqa: E402
from chipbench.deploy import train_esft  # noqa: E402
from chipbench.faults import _patch  # noqa: E402


def control(log) -> Callable[[], None]:
    def make(_step_readings):
        def low_readings(dep, batch):
            loss, out, routing = train_esft.reference_step(
                dep, dep.state["params"], batch, low=reference.LOW)
            return {"loss": float(loss), "mu": out["mu"], "nu": out["nu"],
                    "params": out["params"], "choices": routing["choice"],
                    "frozen_moved": 0}
        return low_readings
    return _patch("chipbench.deploy.train_esft", None, "step_readings", make)


def unmasked(log) -> Callable[[], None]:
    return _patch("repro.train.train_step", None, "_select_rows",
                  lambda _orig: lambda new, old, masks, path=(): new)


def no_bias_correction(log) -> Callable[[], None]:
    # AdamW's update as at a step so late that its bias correction is 1
    return _patch("repro.train.optimizer", None, "adamw_update",
                  lambda update: lambda grads, opt, params, step, cfg: update(
                      grads, opt, params, step + 10 ** 6, cfg))


def half_batch(log) -> Callable[[], None]:
    def make(make_train_step):
        def make_half(*args, **kwargs):
            step = make_train_step(*args, **kwargs)
            return lambda state, batch: step(state, {
                k: v[:v.shape[0] // 2] for k, v in batch.items()})
        return make_half
    return _patch("repro.train.train_step", None, "make_train_step", make)


MODES = {"control": control, "unmasked": unmasked,
         "no_bias_correction": no_bias_correction, "half_batch": half_batch}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--mode", required=True, choices=sorted(MODES))
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, required=True)
    a = p.parse_args(argv)

    from chipbench import harness
    spec = harness.cell_spec(harness.load_benchmark(ROOT),
                             "train-moe-touched")
    harness.devices(spec.chips)
    harness.use_compile_cache()
    for seed in (int(s) for s in a.seeds.split(",")):
        t0 = time.perf_counter()
        out = harness.run(spec, seed, a.seconds, False, t_start=t0,
                          plant=MODES[a.mode])
        print(json.dumps({"mode": a.mode, "seed": seed,
                          "correct": out.result["correct"],
                          "numbers": out.numbers,
                          "wall_s": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
