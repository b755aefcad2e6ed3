"""`submit_wait_ms`: the caller's time blocked in the save call until
the save queue has a free slot, in ms: mean `t_submit_wait` of the
saves committed in the window (the program's own phase timer)."""
from __future__ import annotations

from chipbench.phase_stats import mean_ms


def read(view):
    return mean_ms(view, "t_submit_wait")
