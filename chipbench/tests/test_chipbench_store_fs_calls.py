"""`store_fs_calls_per_pod`, read from the store's path-resolving call
counter: known answers on a hand-made view, and nothing to read from a
program that has no such counter or wrote no pod."""
import pytest

from chipbench import harness
from chipbench.harness import View

METRIC = "store_fs_calls_per_pod"


class _Rec:
    def __init__(self, stats):
        self.stats = stats


def _view(*stats):
    return View(saves=[_Rec(s) for s in stats], stalls=[1.0], trace=None,
                peaks={}, window_s=10.0)


@pytest.mark.parametrize("saves, want", [
    # a fresh pod in a known shard costs 4 calls
    ([{"n_store_fs_calls": 400, "pods_written": 100}], 4.0),
    # dedup hits probe without writing; a new shard adds its mkdir
    ([{"n_store_fs_calls": 402, "pods_written": 100},
      {"n_store_fs_calls": 205, "pods_written": 50},
      {"t_graph": 0.1}], 4.046666666666667),
    # a program with only the older timers
    ([{"t_graph": 0.1, "t_digest": 0.2, "t_write": 1.0}], None),
    # a window that wrote no pod
    ([{"n_store_fs_calls": 6, "pods_written": 0}], None),
], ids=["fresh", "sums-before-dividing", "no-counter", "nothing-written"])
def test_store_fs_calls_per_pod_known_answers(saves, want):
    got = harness.metric_reader(METRIC)(_view(*saves))
    assert got == (None if want is None else pytest.approx(want))


def test_the_metric_is_registered_for_the_cell():
    bench = harness.load_benchmark()
    spec = harness.cell_spec(bench, "train-finetune-top2")
    assert METRIC in {m["name"] for m in spec.per_layer}
