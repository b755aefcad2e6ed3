"""`graph_ms`: mean `t_graph` of the saves committed in the window, in ms
(the program's own host timer, from `save_stats`)."""
from __future__ import annotations


def read(view):
    xs = [r.stats["t_graph"] for r in view.saves if "t_graph" in r.stats]
    return sum(xs) / len(xs) * 1e3 if xs else None
