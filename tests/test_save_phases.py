"""Phase timers, spans and counters of a save (`core/phases.py`): the
caller's wait for a queue slot, the body's wait in the queue, the
phases that tile the body, the byte counters, and the profiler spans
that carry each save's TimeID.  Structure and counts, not speed."""
import glob
import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import Chipmink, MemoryStore
from repro.core.async_saver import AsyncSaver, QueueTimes

#: the top-level phases of a save body, in order
BODY_PHASES = ("avf", "digest", "observe", "podding", "decide", "intent",
               "gather", "write", "commit")
NEW_KEYS = ({"t_submit_wait", "t_queue", "t_body", "t_digest_dispatch",
             "t_digest_fetch", "t_digest_unpack", "t_digest_diff",
             "t_serialize", "t_store_put", "t_ns_wait", "bytes_fetched",
             "bytes_dirty", "bytes_serialized", "n_store_fs_calls",
             "bytes_partly_dirty", "bytes_in_partly_dirty"}
            | {f"t_{p}" for p in BODY_PHASES})


def _state(step, n=1 << 15, leaves=4):
    base = np.arange(n, dtype=np.float32)
    out = {f"l{j}": jnp.asarray(base + step + j) for j in range(leaves)}
    out["step"] = step
    return out


def test_submit_wait_times_the_wait_for_a_slot():
    s = AsyncSaver(depth=1)
    first, second = QueueTimes(), QueueTimes()
    s.submit(lambda: time.sleep(0.2), first)
    s.submit(lambda: None, second)          # the one slot is taken
    s.wait()
    assert first.submit_wait == 0.0
    assert second.submit_wait >= 0.15
    assert first.queue >= 0.0 and second.queue >= 0.0
    assert s.n_stalls == 1


def test_queue_wait_times_the_body_behind_another():
    s = AsyncSaver(depth=2)
    gate = threading.Event()
    behind = QueueTimes()
    s.submit(lambda: gate.wait(5.0))
    s.submit(lambda: None, behind)          # queued behind the gate
    time.sleep(0.2)
    gate.set()
    s.wait()
    assert behind.submit_wait == 0.0
    assert behind.queue >= 0.15


def test_queue_times_are_stamped_before_each_body_starts():
    # many submitters, a tiny switch interval: every body must find its
    # own stamps already written when it starts
    s = AsyncSaver(depth=2)
    seen = []
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def submitter():
            for _ in range(100):
                times = QueueTimes()
                s.submit(lambda t=times: seen.append(
                    (t.enqueued, t.queue, t.submit_wait,
                     time.perf_counter())), times)

        workers = [threading.Thread(target=submitter) for _ in range(8)]
        for w in workers:
            w.start()
        for w in workers:
            w.join(timeout=60)
        assert not any(w.is_alive() for w in workers)
        s.wait()
    finally:
        sys.setswitchinterval(old)
    assert len(seen) == 800
    # an unwritten stamp reads 0; a written one ends before the body
    assert all(enq > 0 and queue > 0 and wait >= 0 and enq + queue <= start
               for enq, queue, wait, start in seen)


def test_sync_save_has_no_waits():
    ck = Chipmink(MemoryStore(), chunk_bytes=1 << 12)
    ck.save(_state(0))
    ck.save(_state(1))
    for st in ck.save_stats:
        assert st["t_submit_wait"] == 0.0 and st["t_queue"] == 0.0


def test_async_save_records_every_phase_and_counter():
    ck = Chipmink(MemoryStore(), chunk_bytes=1 << 12, async_mode=True)
    for step in range(3):
        ck.save(_state(step))
    ck.wait()
    for st in ck.save_stats:
        missing = NEW_KEYS - set(st)
        assert not missing, missing
        assert all(st[k] >= 0 for k in NEW_KEYS)
    manifest = ck.store.get_manifest(ck.save_stats[-1]["time_id"])
    assert "t_queue" in manifest["stats"]
    assert "bytes_fetched" in manifest["stats"]


def test_top_level_phases_tile_the_body():
    # a compressing store makes the body long enough that the few
    # microseconds between two phases stay far below 1%
    ck = Chipmink(MemoryStore(compress=True), chunk_bytes=1 << 15,
                  async_mode=True)
    for step in range(4):
        ck.save(_state(step, n=1 << 17))
    ck.wait()
    for st in ck.save_stats:
        total = sum(st[f"t_{p}"] for p in BODY_PHASES)
        assert abs(total - st["t_body"]) <= 0.01 * st["t_body"], st
        parts = (st["t_serialize"] + st["t_delta_encode"]
                 + st["t_store_put"] + st["t_ns_wait"])
        assert parts <= st["t_write"]


def test_byte_counters():
    n, chunk = 1000, 1 << 10        # 256 floats a chunk; the last 232
    ck = Chipmink(MemoryStore(), chunk_bytes=chunk)
    payloads = []                   # payload bytes as detect returns
    detect = ck.detector.detect

    def spy(*args, **kwargs):
        report = detect(*args, **kwargs)
        payloads.append(sum(len(b) for b in report.payload.values()))
        return report

    ck.detector.detect = spy
    w = np.arange(n, dtype=np.float32)
    ck.save({"w": jnp.asarray(w)})
    first = ck.save_stats[-1]
    assert first["bytes_dirty"] == n * 4            # every chunk is new
    assert first["bytes_fetched"] >= n * 4           # digests + gather
    assert first["bytes_serialized"] >= n * 4
    for step in range(1, 4):
        w = w.copy()
        w[900] += 1.0                                # the short last chunk
        ck.save({"w": jnp.asarray(w)})
        st = ck.save_stats[-1]
        assert st["bytes_dirty"] == (n - 3 * 256) * 4
        assert st["bytes_fetched"] >= payloads[-1]
    # a warm save whose dirty chunk was speculated fetches it with the
    # digests: the payload is not empty and nothing more is gathered
    assert st["n_spec_hits"] == 1 and st["n_gather_syncs"] == 0
    assert payloads[-1] > 0


def _profile_events(trace_dir):
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        # each line of a host plane is one thread
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("chipmink."):
                    tid = dict(e.stats).get("time_id")
                    out.append((e.name, (plane.name, i), int(e.start_ns),
                                int(e.start_ns + e.duration_ns), tid))
    return out


def test_profiler_trace_holds_the_spans_of_each_save(tmp_path):
    ck = Chipmink(MemoryStore(), chunk_bytes=1 << 12, async_mode=True)
    ck.save(_state(0))                   # compiles outside the trace
    ck.wait()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        tids = [ck.save(_state(step)) for step in (1, 2)]
        ck.wait()
    finally:
        jax.profiler.stop_trace()
    events = _profile_events(str(tmp_path))
    for tid in tids:
        mine = [e for e in events if e[4] == tid]
        names = {e[0] for e in mine}
        assert {"chipmink.save", "chipmink.graph", "chipmink.submit_wait",
                "chipmink.body", "chipmink.digest.fetch",
                "chipmink.write.serialize",
                "chipmink.write.store_put"} <= names
        assert {f"chipmink.{p}" for p in BODY_PHASES} <= names
        (body,) = [e for e in mine if e[0] == "chipmink.body"]
        (call,) = [e for e in mine if e[0] == "chipmink.save"]
        assert call[1] != body[1]        # the caller's and the worker's
        caller_side = {"chipmink.save", "chipmink.graph",
                       "chipmink.submit_wait"}
        for name, line, start, end, _ in mine:
            if name in caller_side:
                assert line == call[1]
                assert call[2] <= start and end <= call[3]
            elif name != "chipmink.body":
                assert line == body[1], name
                assert body[2] <= start and end <= body[3], name
