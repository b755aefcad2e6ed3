"""Phase timers, profiler spans and counters of one save.

Every phase of a save is timed on the host clock into the save's
``save_stats`` entry (``t_<phase>``, summed over repeats, so a per-pod
phase reports its total) and opens a ``jax.profiler.TraceAnnotation``
named ``chipmink.<phase>`` that carries the save's TimeID.  A profiler
trace therefore shows the phases of one save, on every thread that works
on it, on the same clock as the device's operations.  With no profiler
running a span costs under a microsecond, so the spans are always on.

Phases of one save (``save_stats`` key in brackets):

  caller thread   ``save`` containing ``graph`` (``t_graph``) and
                  ``submit_wait`` (``t_submit_wait``, stamped by
                  `AsyncSaver`; 0 in sync mode); ``t_queue`` is the
                  body's time between its enqueue and its start
  podding thread  ``body`` (``t_body``), tiled by ``avf``, ``digest``,
                  ``observe``, ``podding``, ``decide``, ``intent``,
                  ``gather``, ``write`` and ``commit``; ``digest``
                  holds ``digest.dispatch``, ``digest.fetch``,
                  ``digest.unpack`` and ``digest.diff``; ``write`` holds
                  ``write.puts`` (``t_put_wall``: from the first pod's
                  dispatch to the last pod's result; the wait for the
                  namespace lock before each pod's thesaurus entry is
                  ``t_ns_wait``); ``commit`` (``t_commit``)
  put threads     per pod ``write.serialize`` (``t_serialize``),
  (or podding)    ``write.delta_encode`` (``t_delta_encode``) and
                  ``write.store_put`` (``t_store_put``), each summed
                  over the save's pods, so overlapping pods add up past
                  ``t_put_wall``; on the podding thread where the store
                  keeps its puts in order (`BaseStore.concurrent_puts`)

Counters: ``bytes_fetched`` (every byte brought from device to host),
``bytes_dirty`` (true lengths of the dirty chunks),
``bytes_partly_dirty`` and ``bytes_in_partly_dirty`` (of the leaves with
some chunks dirty and some clean: their dirty bytes, and their whole
size), ``bytes_serialized`` (pod bytes serialized),
``n_store_fs_calls`` (the store's ``pod_fs_calls`` across the write
phase: path-resolving filesystem calls of its pod probes and writes).
"""
from __future__ import annotations

import contextlib
import time
from typing import Any, Dict, Optional

from jax.profiler import TraceAnnotation

SPAN_PREFIX = "chipmink."


class Phases:
    """Records the phases and counters of one save into its stats dict."""

    __slots__ = ("stats", "time_id")

    def __init__(self, stats: Dict[str, Any], time_id: int) -> None:
        self.stats = stats
        self.time_id = time_id

    def __call__(self, name: str, key: Optional[str] = None) -> "_Phase":
        """Span ``chipmink.<name>`` timed into `key` (default
        ``t_<name>`` with dots as underscores)."""
        return _Phase(self, name, key or "t_" + name.replace(".", "_"))

    def span(self, name: str) -> TraceAnnotation:
        """Span only, for a phase whose time another thread stamps."""
        return TraceAnnotation(SPAN_PREFIX + name, time_id=self.time_id)

    def add(self, key: str, value: float) -> None:
        self.stats[key] = self.stats.get(key, 0) + value


class _Phase:
    __slots__ = ("phases", "name", "key", "annotation", "t0")

    def __init__(self, phases: Phases, name: str, key: str) -> None:
        self.phases = phases
        self.name = name
        self.key = key

    # the span's own cost lies inside the phase's time, so that phases
    # that follow one another add up to the phase that holds them
    def __enter__(self) -> "_Phase":
        self.t0 = time.perf_counter()
        self.annotation = self.phases.span(self.name)
        self.annotation.__enter__()
        return self

    def __exit__(self, *exc: Any) -> None:
        self.annotation.__exit__(*exc)
        self.phases.add(self.key, time.perf_counter() - self.t0)


class _NoPhases:
    """Stands in for `Phases` where a phase runs outside any save."""

    def __call__(self, name: str, key: Optional[str] = None):
        return contextlib.nullcontext()

    def add(self, key: str, value: float) -> None:
        pass


NO_PHASES = _NoPhases()
