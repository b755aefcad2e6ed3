"""BENCHMARK.json and the files it names: every configuration, traffic
mix and per-layer metric is found by its name."""
import json
import os
import re

import pytest

from chipbench import harness, tracing
from chipbench.harness import View

BENCH = harness.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench"]
    assert 1 <= BENCH["run_seconds"] <= 51


@pytest.mark.parametrize("cell", [c["name"] for c in BENCH["workloads"]])
def test_every_cell_resolves(cell):
    spec = harness.cell_spec(BENCH, cell)
    assert spec.mix["loop"] == spec.cfg["kind"]
    assert harness.deployment_class(spec.cfg["kind"]) is not None
    names = {m["name"] for m in spec.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert spec.per_layer


def test_configs_name_their_files_and_cuts():
    for c in BENCH["configs"]:
        cfg = json.load(open(os.path.join(harness.ROOT, c["file"])))
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        for key in c["reduced"]:
            assert key in cfg and not key.endswith(("_dim", "_rank"))
        assert cfg["assumed"] and cfg["guarantees"]


def test_names_units_and_sources():
    seen = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in BENCH[group]:
            assert NAME.match(entry["name"]), entry["name"]
            assert entry["name"] not in seen
            seen.add(entry["name"])
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    layers = {m["layer"] for m in BENCH["per_layer"]}
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and m["layer"] in layers


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_every_per_layer_metric_has_a_reader(metric):
    read = harness.metric_reader(metric)
    assert read(View(saves=[], stalls=[], trace=None, peaks={}, window_s=1.0)) is None


def test_traffic_and_metrics_found_by_file_name():
    for cell in BENCH["workloads"]:
        assert os.path.exists(os.path.join(
            harness.PKG, "traffic", cell["traffic"] + ".json"))
    with pytest.raises(FileNotFoundError):
        harness.traffic("no-such-mix")
    with pytest.raises(KeyError):
        harness.cell_spec(BENCH, "no-such-cell")


def test_peaks_table():
    p = harness.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks("TPU v99")


class _Rec:
    def __init__(self, nbytes):
        self.active_device_bytes = nbytes
        self.stats = {"t_graph": 0.5, "t_digest": 1.0, "t_podding": 0.25,
                      "t_decide": 0.25, "t_gather": 2.0, "t_write": 4.0}


def _summary(busy, save_busy, window=10.0):
    return tracing.TraceSummary(window_s=window, busy_s=busy,
                                save_busy_s=save_busy, device_ops=[],
                                idle_gaps=[])


def test_readers_arithmetic():
    peaks = {"hbm_bytes_per_s": 819e9}
    view = View(saves=[_Rec(819e9 * 0.1), _Rec(819e9 * 0.1)],
                stalls=[0.5 + i / 10 for i in range(11)],
                trace=_summary(busy=2.5, save_busy=0.8), peaks=peaks,
                window_s=10.0)
    got = {m: harness.metric_reader(m)(view) for m in (
        "graph_ms", "digest_ms", "podding_ms", "gather_ms", "write_ms",
        "device_idle_share", "save_hbm_roofline", "stall_p95_ms")}
    assert got == pytest.approx({
        "graph_ms": 500.0, "digest_ms": 1000.0, "podding_ms": 500.0,
        "gather_ms": 2000.0, "write_ms": 4000.0, "device_idle_share": 75.0,
        "save_hbm_roofline": 25.0, "stall_p95_ms": 1450.0})


def test_roofline_above_100_percent_is_an_error():
    view = View(saves=[_Rec(819e9)], stalls=[1.0],
                trace=_summary(busy=1, save_busy=0.5),
                peaks={"hbm_bytes_per_s": 819e9}, window_s=10.0)
    with pytest.raises(ValueError):
        harness.metric_reader("save_hbm_roofline")(view)
