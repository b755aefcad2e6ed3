"""What the harness records about every save it issues.

A save's stall is the caller's time inside the public save call.  Its
commit is the moment the program appends the save's entry to the
instance's public `save_stats` list, which it does right after the refs
compare-and-swap that publishes the commit: `watch` swaps that list for
a `StampedStats`, which stamps `perf_counter()` on each append.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from . import reference


@dataclasses.dataclass
class SaveRecord:
    stream: str
    in_window: bool
    active_device_bytes: int
    t_call: float
    stall_s: float = 0.0
    tid: Optional[int] = None
    stats: Optional[Dict[str, Any]] = None
    t_commit: Optional[float] = None
    #: {leaf path: device (n_chunks, 2) checksums} when sampled
    checksums: Optional[Dict[str, Any]] = None

    @property
    def commit_s(self) -> Optional[float]:
        if self.t_commit is None:
            return None
        return self.t_commit - self.t_call


class StampedStats(list):
    """A `save_stats` list that reports each append, with its time."""

    def __init__(self, items, on_append: Callable[[Dict, float], None]):
        super().__init__(items)
        self._on_append = on_append

    def append(self, stats: Dict[str, Any]) -> None:
        self._on_append(stats, time.perf_counter())
        super().append(stats)


class SaveLog:
    """Issues saves for a deployment and keeps their records.

    While `sampling` is on (after the window has closed), the first two
    saves of the `sample` stream record on-device chunk checksums of the
    snapshot they were given, for the dirty-set and earlier-commit
    comparisons; `n_sampled` counts that stream's saves since.
    `transform` maps the snapshot handed to the program (identity in a
    benchmark run; a planted fault or the control elsewhere)."""

    def __init__(self, chunk_bytes: int, sample: str,
                 annotate: Callable[[str], Any]):
        self.chunk_bytes = chunk_bytes
        self.sample = sample
        self.annotate = annotate
        self.transform: Callable[[str, Any], Any] = lambda stream, snap: snap
        self.records: List[SaveRecord] = []
        self.by_tid: Dict[int, SaveRecord] = {}
        self.last: Dict[str, Tuple[int, Any]] = {}
        self.in_window = False
        self.sampling = False
        self.n_sampled = 0
        self._early: Dict[int, Tuple[Dict, float]] = {}
        self._lock = threading.Lock()

    def watch(self, instance: Any) -> None:
        """Stamp the commits of one program instance (a `Chipmink`)."""
        instance.save_stats = StampedStats(instance.save_stats,
                                           self._committed)

    def _committed(self, stats: Dict[str, Any], t: float) -> None:
        with self._lock:
            rec = self.by_tid.get(stats["time_id"])
            if rec is None:           # a synchronous save commits first
                self._early[stats["time_id"]] = (stats, t)
            else:
                rec.stats, rec.t_commit = stats, t

    def save(self, stream: str, snapshot: Any,
             call: Callable[[Any], int], active_device_bytes: int) -> int:
        rec = SaveRecord(stream=stream, in_window=self.in_window,
                         active_device_bytes=active_device_bytes,
                         t_call=0.0)
        if self.sampling and stream == self.sample:
            if self.n_sampled < 2:
                rec.checksums = reference.tree_chunk_checksums(
                    reference.array_leaves(snapshot), self.chunk_bytes)
            self.n_sampled += 1
        given = self.transform(stream, snapshot)
        with self.annotate("chipbench.save"):
            rec.t_call = time.perf_counter()
            tid = call(given)
            rec.stall_s = time.perf_counter() - rec.t_call
        with self._lock:
            rec.tid = tid
            self.by_tid[tid] = rec
            early = self._early.pop(tid, None)
            if early is not None:
                rec.stats, rec.t_commit = early
        self.records.append(rec)
        self.last[stream] = (tid, snapshot)
        return tid

    def window(self) -> List[SaveRecord]:
        return [r for r in self.records if r.in_window]

    def sampled_pair(self) -> Optional[Tuple[SaveRecord, SaveRecord]]:
        recs = [r for r in self.records if r.checksums is not None]
        return (recs[0], recs[1]) if len(recs) == 2 else None

    def fetch_checksums(self) -> None:
        """Bring sampled checksums to the host."""
        for r in self.records:
            if r.checksums is not None:
                r.checksums = {k: np.asarray(v)
                               for k, v in r.checksums.items()}
