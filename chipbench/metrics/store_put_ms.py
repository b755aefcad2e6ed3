"""`store_put_ms`: the store's pod puts in the write phase, summed over
the pods a save writes, the wait for the namespace lock before each put
left out, in ms: mean `t_store_put` of the saves committed in the window
(the program's own phase timer)."""
from __future__ import annotations

from chipbench.phase_stats import mean_ms


def read(view):
    return mean_ms(view, "t_store_put")
