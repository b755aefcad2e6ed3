"""The reduction from a profiler trace to busy time, the save's device
time, top operations and idle gaps: on hand-made traces with known
answers, and on a small trace recorded on a TPU v5e."""
import os

import pytest

from chipbench import tracing
from chipbench.tracing import Trace

DATA = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")
MS = 1_000_000


def test_interval_arithmetic():
    assert tracing.union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)]) == \
        [(0, 3), (5, 8)]
    assert tracing.clip([(0, 4), (6, 10)], 2, 8) == [(2, 4), (6, 8)]
    assert tracing.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert tracing.subtract([(0, 2), (4, 6)], []) == [(0, 2), (4, 6)]
    assert tracing.total([(0, 2), (4, 7)]) == 5


def _synthetic() -> Trace:
    ops = [("fusion.1", 10 * MS, 30 * MS),      # inside the step module
           ("fingerprint", 40 * MS, 50 * MS),
           ("copy.2", 45 * MS, 60 * MS),        # overlaps the kernel
           ("fusion.1", 90 * MS, 120 * MS)]     # runs past the window
    modules = [("jit_chipbench_step(12)", 10 * MS, 30 * MS),
               ("jit_digest(3)", 40 * MS, 60 * MS),
               ("jit_chipbench_step(12)", 90 * MS, 120 * MS)]
    host = [(tracing.WINDOW_SPAN, 0, 100 * MS),
            ("chipbench.step", 5 * MS, 35 * MS),
            ("chipbench.write", 60 * MS, 90 * MS),
            ("chipbench.serialize", 62 * MS, 70 * MS)]
    return Trace(devices={"/device:TPU:0": {tracing.OPS_LINE: ops,
                                            tracing.MODULES_LINE: modules}},
                 host=host)


def test_reduction_with_known_answer():
    s = tracing.reduce_trace(_synthetic())
    assert s.window_s == pytest.approx(0.1)
    # busy: [10,30) + [40,60) + [90,100) inside the window
    assert s.busy_s == pytest.approx(0.05)
    # save: the busy time outside the step module's intervals
    assert s.save_busy_s == pytest.approx(0.02)
    assert s.device_ops[0] == ["fusion.1", pytest.approx(0.03)]
    assert dict((k, v) for k, v in s.device_ops)["copy.2"] == \
        pytest.approx(0.015)
    # gaps [0,10), [30,40), [60,90): the step span covers [5,10) and
    # [30,35), the serialize span [62,70) inside the write span [60,90)
    assert dict(s.idle_gaps) == pytest.approx({
        "chipbench.write": 0.022, "chipbench.serialize": 0.008,
        "chipbench.step": 0.01, "unannotated": 0.01})
    assert s.idle_gaps[0][0] == "chipbench.write"


def test_reduction_refuses_a_trace_without_window_or_device():
    t = _synthetic()
    with pytest.raises(ValueError):
        tracing.reduce_trace(Trace(devices=t.devices, host=[]))
    with pytest.raises(ValueError):
        tracing.reduce_trace(Trace(devices={}, host=t.host))


def test_reduction_of_a_recorded_tpu_trace():
    """Three rounds of a matmul step (`chipbench_step`), the compiled
    fingerprint kernel and a reduction, with 10 ms of sleep between, on
    one v5e.  The device's operations never overlap there, so busy time
    is the sum of their durations inside the window."""
    trace = tracing.load_trace(DATA)
    assert list(trace.devices) == ["/device:TPU:0"]
    s = tracing.reduce_trace(trace)
    lines = trace.devices["/device:TPU:0"]
    lo, hi = [(a, b) for n, a, b in trace.host
              if n == tracing.WINDOW_SPAN][0]
    inside = [(n, a, b) for n, a, b in lines[tracing.OPS_LINE]
              if lo <= a and b <= hi]
    steps = [(a, b) for n, a, b in lines[tracing.MODULES_LINE]
             if "chipbench_step" in n]
    in_step = [(a, min(b, max(sb for sa, sb in steps if sa <= a < sb)))
               for n, a, b in inside if any(sa <= a < sb for sa, sb in steps)]
    assert s.window_s == pytest.approx((hi - lo) / 1e9)
    assert s.busy_s == pytest.approx(sum(b - a for _, a, b in inside) / 1e9)
    assert s.save_busy_s == pytest.approx(
        s.busy_s - sum(b - a for a, b in in_step) / 1e9)
    assert 0 < s.save_busy_s < s.busy_s < s.window_s
    names = [n for n, _ in s.device_ops]
    assert "fingerprint_words_cmp" in names
    assert "convolution_tanh_fusion" in names
    assert sum(v for _, v in s.idle_gaps) == pytest.approx(
        s.window_s - s.busy_s)
    assert dict(s.idle_gaps)["unannotated"] > 0.02     # the sleeps
