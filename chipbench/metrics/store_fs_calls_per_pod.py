"""`store_fs_calls_per_pod`: path-resolving filesystem calls the store
made in the write phase (`n_store_fs_calls`: pod probes, shard mkdirs,
creates, renames) per pod written (`pods_written`), summed over the
saves committed in the window (the program's own counters, from
`save_stats`).  A program without the counter, or a window that wrote no
pod, gives nothing to read."""
from __future__ import annotations


def read(view):
    recs = [r.stats for r in view.saves
            if "n_store_fs_calls" in r.stats and "pods_written" in r.stats]
    pods = sum(s["pods_written"] for s in recs)
    if not pods:
        return None
    return sum(s["n_store_fs_calls"] for s in recs) / pods
