"""`store_put_concurrency`: the mean number of pod puts in flight while a
save puts its pods, Σ`t_store_put` / Σ`t_put_wall` over the saves
committed in the window (the program's own phase timers: the pods' put
latencies summed, over the wall time from the first pod's dispatch to
the last pod's result).  A put that waits for the interpreter lock or
for a store that serializes calls counts as in flight too: overlap shows
only where the per-pod latency, `store_put_ms` / pods, stays near the
serial one.  A program without `t_put_wall`, or a window that spent no
time putting, gives nothing to read."""
from __future__ import annotations


def read(view):
    recs = [r.stats for r in view.saves
            if "t_put_wall" in r.stats and "t_store_put" in r.stats]
    wall = sum(s["t_put_wall"] for s in recs)
    if not wall:
        return None
    return sum(s["t_store_put"] for s in recs) / wall
