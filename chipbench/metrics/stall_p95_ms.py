"""`stall_p95_ms`: 95th percentile (linear between ranks) of the caller's
time inside the save call, over every save issued in the traced window,
in ms.  A tail of a few tens of saves: for reading, not for a bound."""
from __future__ import annotations

from chipbench import stats


def read(view):
    return stats.percentile(view.stalls, 95) * 1e3 if view.stalls else None
