"""`store_put_concurrency`, read from the program's put timers: known
answers on a hand-made view, and nothing to read from a program that
has no `t_put_wall`."""
import pytest

from chipbench import harness
from chipbench.harness import View

METRIC = "store_put_concurrency"


class _Rec:
    def __init__(self, stats):
        self.stats = stats


def _view(*stats):
    return View(saves=[_Rec(s) for s in stats], stalls=[1.0], trace=None,
                peaks={}, window_s=10.0)


@pytest.mark.parametrize("saves, want", [
    # four puts always in flight
    ([{"t_store_put": 0.8, "t_put_wall": 0.2}], 4.0),
    # puts in order: the latencies fill the wall time less the collection
    ([{"t_store_put": 0.09, "t_put_wall": 0.1}], 0.9),
    # sums before dividing; a save of the older program is left out
    ([{"t_store_put": 0.6, "t_put_wall": 0.2},
      {"t_store_put": 0.3, "t_put_wall": 0.1},
      {"t_store_put": 0.5, "t_write": 0.6}], 3.0),
    # a program with only the older timers
    ([{"t_graph": 0.1, "t_store_put": 0.5, "t_write": 0.6}], None),
    # a window whose saves put nothing
    ([{"t_store_put": 0.0, "t_put_wall": 0.0}], None),
], ids=["overlapped", "serial", "sums-before-dividing", "no-timer",
        "nothing-put"])
def test_store_put_concurrency_known_answers(saves, want):
    got = harness.metric_reader(METRIC)(_view(*saves))
    assert got == (None if want is None else pytest.approx(want))


@pytest.mark.parametrize("cell", ["train-finetune-top2", "train-moe-touched"])
def test_the_metric_is_registered_for_the_cell(cell):
    bench = harness.load_benchmark()
    spec = harness.cell_spec(bench, cell)
    assert METRIC in {m["name"] for m in spec.per_layer}
