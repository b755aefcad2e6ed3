"""The serving deployment, driven on the CPU at a tiny size: a sound run
is correct, and the control and every planted fault make it not
correct."""
import pytest

from chipbench import faults
from chipbench.tests.tiny import tiny_run


def test_sound_serve_run_is_correct(tmp_path):
    out = tiny_run("serve", tmp_path)
    assert out.result["correct"], out.numbers
    assert out.result["failed"] == 0 and out.result["attempted"] > 2
    assert set(out.result["metrics"]) == {
        "step_ms", "stall_ms", "commit_ms", "stored_bytes_ratio",
        "setup_s"}
    assert list(out.result)[-1] == "checks"


@pytest.mark.parametrize("mode", ["control", "stale", "half", "alter"])
def test_broken_snapshot_path_is_not_correct(tmp_path, mode):
    out = tiny_run("serve", tmp_path, plant=faults.MODES[mode])
    assert not out.result["correct"], (mode, out.numbers)
