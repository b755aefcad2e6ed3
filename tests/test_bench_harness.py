"""The benchmark harness reports a raising bench and fails the run."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import run  # noqa: E402


def test_exits_nonzero_when_a_bench_raises(monkeypatch, capsys, tmp_path):
    def boom():
        raise RuntimeError("boom")

    monkeypatch.setattr(run, "enable_compile_cache", lambda: None)
    monkeypatch.setattr(run, "OUT_DIR", str(tmp_path))
    monkeypatch.setattr(run, "BENCHES", {"bad": boom,
                                         "good": lambda: [{"x": 1}]})
    monkeypatch.setattr(sys, "argv", ["run"])
    with pytest.raises(SystemExit) as exc:
        run.main()
    assert exc.value.code not in (0, None)
    out = capsys.readouterr().out
    assert "bad,0,ERROR:RuntimeError:boom" in out
    assert "good," in out                 # later benches still ran


BENCH_MODULES = sorted(
    f[:-3] for f in os.listdir(os.path.dirname(run.__file__))
    if f.endswith(".py") and f != "__init__.py")


@pytest.mark.parametrize("name", BENCH_MODULES)
def test_bench_module_imports(name):
    """Every benchmark module imports: a deleted helper module cannot
    leave a bench behind that fails only when it is run."""
    import importlib
    importlib.import_module(f"benchmarks.{name}")


def test_singlesync_quick_runs(monkeypatch, tmp_path):
    """The quick single-sync bench runs end to end, reports measured
    quantities only, and keeps its sync and identity verdicts."""
    from benchmarks import bench_singlesync
    monkeypatch.setattr(bench_singlesync, "OUT_JSON",
                        str(tmp_path / "BENCH_singlesync.json"))
    (row,) = bench_singlesync.bench_singlesync(quick=True)
    assert row["artifacts_identical"] and row["single_sync_warm"]
    assert not any("floor" in k or "GBps" in k for k in row)
    assert (tmp_path / "BENCH_singlesync.json").exists()
