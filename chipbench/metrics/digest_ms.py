"""`digest_ms`: mean `t_digest` of the saves committed in the window, in ms
(the program's own host timer, from `save_stats`)."""
from __future__ import annotations


def read(view):
    xs = [r.stats["t_digest"] for r in view.saves if "t_digest" in r.stats]
    return sum(xs) / len(xs) * 1e3 if xs else None
