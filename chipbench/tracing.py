"""Traced runs: host spans around the calls into each layer, and the
reduction of a profiler trace to device busy time, the save's share of
it, the top device operations and the idle gaps by host activity.

Spans are set from this package only.  `layer_spans` wraps the program's
layer entry points in `jax.profiler.TraceAnnotation` for the duration of
a traced run and restores them afterwards; behaviour is unchanged.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import glob
import importlib
import os
import re
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

#: (module, class or None, attribute, span name)
LAYER_ENTRY_POINTS = (
    ("repro.core.graph_cache", "GraphCache", "build", "chipbench.graph"),
    ("repro.core.active_filter", "ActiveVariableFilter", "active_leaves",
     "chipbench.avf"),
    ("repro.core.change_detector", "ChangeDetector", "detect",
     "chipbench.digest"),
    ("repro.core.checkpoint", None, "pod_graph", "chipbench.podding"),
    ("repro.core.checkpoint", None, "fused_chunk_fetch", "chipbench.gather"),
    ("repro.core.checkpoint", None, "batched_chunk_fetch", "chipbench.gather"),
    ("repro.core.checkpoint", None, "serialize_pod", "chipbench.serialize"),
    ("repro.core.store", "FileStore", "_write_atomic", "chipbench.store_put"),
    ("repro.version.commit_graph", "CommitDAG", "record", "chipbench.commit"),
)

#: jitted functions of the benchmark itself, excluded from a save's
#: device time: the traffic's step (the reference runs after the window)
BENCH_MODULE_MARKERS = ("chipbench_step",)

WINDOW_SPAN = "chipbench.window"


def annotation(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def _wrap(fn, name: str):
    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with annotation(name):
            return fn(*args, **kwargs)
    return wrapped


@contextlib.contextmanager
def layer_spans() -> Iterator[None]:
    undo: List[Tuple[Any, str, Any]] = []
    try:
        for mod_name, cls_name, attr, span in LAYER_ENTRY_POINTS:
            owner = importlib.import_module(mod_name)
            if cls_name is not None:
                owner = getattr(owner, cls_name)
            orig = owner.__dict__[attr]
            undo.append((owner, attr, orig))
            setattr(owner, attr, _wrap(orig, span))
        yield
    finally:
        for owner, attr, orig in reversed(undo):
            setattr(owner, attr, orig)


# -- the trace ------------------------------------------------------------

Interval = Tuple[int, int]              # [start_ns, end_ns)


@dataclasses.dataclass
class Trace:
    """What the reduction reads from a profiler trace."""

    #: per device plane: {line name: [(name, start_ns, end_ns)]}
    devices: Dict[str, Dict[str, List[Tuple[str, int, int]]]]
    #: host spans of this package: [(name, start_ns, end_ns)]
    host: List[Tuple[str, int, int]]


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float
    #: busy time of operations outside the benchmark's own programs
    save_busy_s: float
    device_ops: List[List[Any]]
    idle_gaps: List[List[Any]]


_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
#: an operation's HLO text starts with its instruction name: `%name.N = `
_OP_NAME = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)*\s*=")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def load_trace(path: str) -> Trace:
    """Read an `.xplane.pb`, or the newest one a profiler wrote under the
    directory `path`."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(
            path, "plugins", "profile", "*", "*.xplane.pb")))
        if not found:
            raise FileNotFoundError(f"no xplane.pb under {path}")
        path = found[-1]
    data = ProfileData.from_file(path)
    devices: Dict[str, Dict[str, List[Tuple[str, int, int]]]] = {}
    host: List[Tuple[str, int, int]] = []
    for plane in data.planes:
        if _DEVICE_PLANE.match(plane.name):
            devices[plane.name] = {
                line.name: [(e.name, int(e.start_ns), int(e.end_ns))
                            for e in line.events]
                for line in plane.lines
                if line.name in (OPS_LINE, MODULES_LINE)}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, int(e.start_ns), int(e.end_ns))
                            for e in line.events
                            if e.name.startswith("chipbench."))
    return Trace(devices=devices, host=host)


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Parts of the disjoint sorted intervals `a` outside those of `b`."""
    out: List[Interval] = []
    b = list(b)
    j = 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        cur, k = s, j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def total(intervals: Sequence[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def op_name(hlo: str) -> str:
    """`%fingerprint_words_cmp.1 = (u32[...]) custom-call(...)` ->
    `fingerprint_words_cmp`; any other text is kept whole."""
    m = _OP_NAME.match(hlo)
    return m.group(1) if m else hlo


def reduce_trace(trace: Trace, top: int = 10) -> TraceSummary:
    """Busy time is the union of operation intervals inside the window,
    averaged over the device planes.  The save's share leaves out every
    operation that runs inside a module of the benchmark's own jitted
    functions.  Idle gaps inside the window are attributed to the
    innermost host span of this package that overlaps them most."""
    spans = [h for h in trace.host if h[0] == WINDOW_SPAN]
    if not spans:
        raise ValueError("trace holds no window span")
    lo, hi = spans[0][1], spans[0][2]
    if not trace.devices:
        raise ValueError("trace holds no device plane")
    busy_ns = save_ns = 0
    op_time: Dict[str, int] = {}
    gaps: List[Interval] = []
    for lines in trace.devices.values():
        ops = lines.get(OPS_LINE, [])
        busy = union(clip([(s, e) for _, s, e in ops], lo, hi))
        bench = union(clip([(s, e) for name, s, e in lines.get(
            MODULES_LINE, []) if any(m in name for m in BENCH_MODULE_MARKERS)],
            lo, hi))
        busy_ns += total(busy)
        save_ns += total(subtract(busy, bench))
        for name, s, e in ops:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                short = op_name(name)
                op_time[short] = op_time.get(short, 0) + d
        gaps.extend(subtract([(lo, hi)], busy))
    n = len(trace.devices)
    idle = _attribute_gaps(gaps, [h for h in trace.host
                                  if h[0] != WINDOW_SPAN])
    return TraceSummary(
        window_s=(hi - lo) / 1e9, busy_s=busy_ns / n / 1e9,
        save_busy_s=save_ns / n / 1e9,
        device_ops=[[k, v / n / 1e9] for k, v in sorted(
            op_time.items(), key=lambda kv: -kv[1])[:top]],
        idle_gaps=[[k, v / n / 1e9] for k, v in sorted(
            idle.items(), key=lambda kv: -kv[1])[:top]])


def _attribute_gaps(gaps: Sequence[Interval],
                    host: Sequence[Tuple[str, int, int]]) -> Dict[str, int]:
    """{span name: idle ns}: each part of a gap goes to the innermost
    (shortest) host span of this package that covers it, or to
    `unannotated`."""
    import bisect
    out: Dict[str, int] = {}
    spans = sorted(host, key=lambda h: h[1])
    starts = [h[1] for h in spans]
    longest = max((e - s for _, s, e in spans), default=0)
    for s, e in gaps:
        lo = bisect.bisect_left(starts, s - longest)
        hi = bisect.bisect_left(starts, e)
        near = [h for h in spans[lo:hi] if h[2] > s]
        cuts = sorted({s, e} | {t for _, hs, he in near for t in (hs, he)
                                if s < t < e})
        for a, b in zip(cuts, cuts[1:]):
            cover = [h for h in near if h[1] <= a and h[2] >= b]
            name = min(cover, key=lambda h: h[2] - h[1])[0] if cover \
                else "unannotated"
            out[name] = out.get(name, 0) + (b - a)
    return out
