"""`gather_ms`: mean `t_gather` of the saves committed in the window, in ms
(the program's own host timer, from `save_stats`)."""
from __future__ import annotations


def read(view):
    xs = [r.stats["t_gather"] for r in view.saves if "t_gather" in r.stats]
    return sum(xs) / len(xs) * 1e3 if xs else None
