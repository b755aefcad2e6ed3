"""`podding_ms`: mean `t_podding + t_decide` of the saves committed in the
window, in ms (the program's own host timers, from `save_stats`)."""
from __future__ import annotations


def read(view):
    xs = [r.stats["t_podding"] + r.stats["t_decide"] for r in view.saves
          if "t_podding" in r.stats and "t_decide" in r.stats]
    return sum(xs) / len(xs) * 1e3 if xs else None
