"""`partly_dirty_share`: of the leaves a save found partly dirty (some
chunks dirty, some clean), the share of their bytes that was dirty:
Σ`bytes_partly_dirty` / Σ`bytes_in_partly_dirty` over the saves
committed in the window (the program's own counters, from
`save_stats`).  With expert slabs that fill whole chunks and exact
detection it is the share of experts that train; above it, chunks of a
frozen expert were written.  A program without the counters, or a window
with no partly dirty leaf, gives nothing to read."""
from __future__ import annotations


def read(view):
    recs = [r.stats for r in view.saves
            if "bytes_partly_dirty" in r.stats
            and "bytes_in_partly_dirty" in r.stats]
    whole = sum(s["bytes_in_partly_dirty"] for s in recs)
    if not whole:
        return None
    return sum(s["bytes_partly_dirty"] for s in recs) / whole
