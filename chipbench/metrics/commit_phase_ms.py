"""`commit_phase_ms`: the commit phase (manifest put, refcount, refs
compare-and-swap with its retries, release of the intent), in ms: mean
`t_commit` of the saves committed in the window (the program's own
phase timer)."""
from __future__ import annotations

from chipbench.phase_stats import mean_ms


def read(view):
    return mean_ms(view, "t_commit")
