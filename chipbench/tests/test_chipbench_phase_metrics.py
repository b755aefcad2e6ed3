"""The per-layer metrics read from the program's phase timers and byte
counters: known answers on a hand-made view, and nothing to read from a
program that has no such timer."""
import pytest

from chipbench import harness
from chipbench.harness import View

PHASE_METRICS = {
    "submit_wait_ms": "t_submit_wait",
    "queue_wait_ms": "t_queue",
    "digest_fetch_ms": "t_digest_fetch",
    "serialize_ms": "t_serialize",
    "store_put_ms": "t_store_put",
    "commit_phase_ms": "t_commit",
}


class _Rec:
    def __init__(self, stats):
        self.stats = stats


def _view(*stats):
    return View(saves=[_Rec(s) for s in stats], stalls=[1.0], trace=None,
                peaks={}, window_s=10.0)


@pytest.mark.parametrize("metric", sorted(PHASE_METRICS))
def test_phase_metric_is_the_mean_in_ms(metric):
    key = PHASE_METRICS[metric]
    view = _view({key: 0.5, "t_graph": 9.0}, {key: 1.5}, {key: 2.5})
    assert harness.metric_reader(metric)(view) == pytest.approx(1500.0)


@pytest.mark.parametrize("metric", sorted(PHASE_METRICS) +
                         ["fetched_per_dirty_byte"])
def test_a_program_without_the_timers_reads_nothing(metric):
    # the stats of a program that had only the older timers
    view = _view({"t_graph": 0.1, "t_digest": 0.2, "t_write": 1.0})
    assert harness.metric_reader(metric)(view) is None


def test_fetched_per_dirty_byte_sums_before_dividing():
    view = _view({"bytes_fetched": 300, "bytes_dirty": 100},
                 {"bytes_fetched": 100, "bytes_dirty": 100},
                 {"t_graph": 0.1})
    got = harness.metric_reader("fetched_per_dirty_byte")(view)
    assert got == pytest.approx(2.0)


def test_fetched_per_dirty_byte_with_nothing_dirty_reads_nothing():
    view = _view({"bytes_fetched": 4096, "bytes_dirty": 0})
    assert harness.metric_reader("fetched_per_dirty_byte")(view) is None


def test_the_new_metrics_are_registered_for_the_cell():
    bench = harness.load_benchmark()
    spec = harness.cell_spec(bench, "train-finetune-top2")
    names = {m["name"] for m in spec.per_layer}
    assert set(PHASE_METRICS) | {"fetched_per_dirty_byte"} <= names
