"""`fetched_per_dirty_byte`: bytes brought from the device to the host
(digest fetch and corrective gather, `bytes_fetched`) over the true
lengths of the dirty chunks (`bytes_dirty`), summed over the saves
committed in the window (the program's own counters, from `save_stats`).
1 is the least a save that reads every dirty byte once can fetch; whole
leaves sent for a few dirty chunks raise it.  A program without the
counters, or a window with no dirty byte, gives nothing to read."""
from __future__ import annotations


def read(view):
    recs = [r.stats for r in view.saves
            if "bytes_fetched" in r.stats and "bytes_dirty" in r.stats]
    dirty = sum(s["bytes_dirty"] for s in recs)
    if not dirty:
        return None
    return sum(s["bytes_fetched"] for s in recs) / dirty
