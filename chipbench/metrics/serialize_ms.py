"""`serialize_ms`: pod serialization in the write phase, summed over
the pods a save writes, in ms: mean `t_serialize` of the saves
committed in the window (the program's own phase timer)."""
from __future__ import annotations

from chipbench.phase_stats import mean_ms


def read(view):
    return mean_ms(view, "t_serialize")
