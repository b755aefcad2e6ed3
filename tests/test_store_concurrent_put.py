"""A save's pods put from a few threads (`Chipmink._pod_writes`) on a
store that lets its puts overlap (`BaseStore.concurrent_puts`): the trial
that decides whether the threads pay, the same pods, bytes and counters
as the serial loop, failures that stop the save before its manifest, and
a `FileStore` whose puts are safe on many threads at once.  Tests of the
threaded path set the trial's outcome on the instance."""
import contextlib
import os
import random
import sys
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (LGA, Chipmink, FaultyStore, FileStore,
                        InjectedCrash, MemoryStore, RetryPolicy, lga1)
from repro.core import checkpoint

CHUNK = 1 << 12


def _state(step, n=1 << 14, leaves=4):
    # odd leaves change in two chunks a step, so a pod may be a delta
    out = {}
    for j in range(leaves):
        w = np.arange(n, dtype=np.float32) + j
        if j % 2:
            w[[5, n // 2]] += step
        out[f"l{j}"] = jnp.asarray(w)
    out["step"] = step
    return out


def _saves(ck, steps=3):
    tids = [ck.save(_state(step)) for step in range(steps)]
    ck.wait()
    return tids


def _pods(store):
    return {d: store.get_pod(d) for d in store.list_pods()}


def _tmp_files(root):
    return [f for _, _, files in os.walk(root) for f in files
            if f.endswith(".tmp")]


@pytest.mark.parametrize("delta_chains", [False, True],
                         ids=["whole", "delta-chains"])
def test_pool_save_matches_the_serial_twin(tmp_path, delta_chains):
    # lga1 bundles a leaf's chunks, so a pod changes in part
    kw = dict(chunk_bytes=CHUNK, async_mode=True, delta_chains=delta_chains,
              policy=lga1() if delta_chains else LGA())
    pool = Chipmink(FileStore(str(tmp_path)), **kw)
    pool._puts_overlap = True
    serial = Chipmink(MemoryStore(), **kw)
    tids = _saves(pool)
    assert _saves(serial) == tids
    assert pool._put_pool is not None and serial._put_pool is None
    assert pool.save_stats[0]["pods_written"] >= 2
    assert _pods(pool.store) == _pods(serial.store)
    for tid in tids:
        got, want = pool.load(time_id=tid), serial.load(time_id=tid)
        assert got.keys() == want.keys()
        for k in got:
            assert np.array_equal(np.asarray(got[k]), np.asarray(want[k]))
        man_pool = pool.store.get_manifest(tid)
        man_serial = serial.store.get_manifest(tid)
        assert man_pool["pods"] == man_serial["pods"]
    keys = ("pods_written", "pods_aliased", "n_delta_pods",
            "chain_depth_max", "bytes_serialized", "bytes_written")
    for a, b in zip(pool.save_stats, serial.save_stats):
        assert {k: a[k] for k in keys} == {k: b[k] for k in keys}
        assert 0 < a["t_put_wall"] <= a["t_write"]
    if delta_chains:
        assert sum(st["n_delta_pods"] for st in pool.save_stats) > 0
    assert _tmp_files(str(tmp_path)) == []
    pool.close()
    assert pool._put_pool is None


@pytest.mark.parametrize("make", [
    lambda root: MemoryStore(),
    lambda root: FaultyStore(FileStore(root)),
], ids=["memory", "faulty-file"])
def test_stores_that_keep_puts_in_order_take_the_serial_path(tmp_path,
                                                             make):
    ck = Chipmink(make(str(tmp_path)), chunk_bytes=CHUNK)
    _saves(ck, steps=2)
    assert not ck.store.concurrent_puts
    assert ck._put_pool is None
    assert ck.save_stats[0]["pods_written"] >= 2


class _CountingFileStore(FileStore):
    """Counts each digest's puts; `fail` may raise in a put."""

    def __init__(self, root, fail=None):
        super().__init__(root)
        self.fail = fail
        self.puts = {}
        self.lock = threading.Lock()

    def put_pod(self, digest_hex, data):
        with self.lock:
            self.puts[digest_hex] = self.puts.get(digest_hex, 0) + 1
            n = sum(self.puts.values())
        if self.fail is not None:
            self.fail(n, digest_hex)
        return super().put_pod(digest_hex, data)


class _SlowFileStore(FileStore):
    """Each put waits `delay` seconds first, as on a filesystem whose
    calls wait; with `one_at_a_time` the waits do not overlap, as on one
    that serializes concurrent calls."""

    def __init__(self, root, delay, one_at_a_time):
        super().__init__(root)
        self.delay = delay
        self.gate = (threading.Lock() if one_at_a_time
                     else contextlib.nullcontext())

    def put_pod(self, digest_hex, data):
        with self.gate:
            time.sleep(self.delay)
        return super().put_pod(digest_hex, data)


@pytest.mark.parametrize("one_at_a_time", [False, True],
                         ids=["waits-overlap", "waits-serialized"])
def test_the_trial_keeps_the_threads_only_where_they_pay(tmp_path,
                                                         monkeypatch,
                                                         one_at_a_time):
    monkeypatch.setattr(checkpoint, "PUT_TRIAL_PODS", 8)
    store = _SlowFileStore(str(tmp_path), 0.004, one_at_a_time)
    ck = Chipmink(store, chunk_bytes=CHUNK)
    # a chunk a pod: fewer pods than twice the trial's are put in order
    ck.save(_state(0, n=1 << 10, leaves=8))
    assert ck.save_stats[-1]["pods_written"] < 16
    assert ck._puts_overlap is None and ck._put_pool is None
    ck.save(_state(1, n=1 << 13, leaves=8))
    assert ck.save_stats[-1]["pods_written"] >= 16
    assert ck._puts_overlap is (not one_at_a_time)
    # the outcome holds for the instance's later saves of two pods or more
    used = ck._put_pool
    ck._put_pool = None
    ck.save(_state(2, n=1 << 13, leaves=8))
    assert 2 <= ck.save_stats[-1]["pods_written"] < 16
    assert (ck._put_pool is not None) is (not one_at_a_time)
    got = ck.load()
    for k, v in _state(2, n=1 << 13, leaves=8).items():
        assert np.array_equal(np.asarray(got[k]), np.asarray(v))
    used.shutdown()
    ck.close()


def test_a_transient_error_in_one_task_is_retried(tmp_path):
    seen = []

    def once(n, digest_hex):
        if n == 3 and not seen:
            seen.append(digest_hex)
            raise OSError("transient")

    store = _CountingFileStore(str(tmp_path), fail=once)
    ck = Chipmink(store, chunk_bytes=CHUNK,
                  retry_policy=RetryPolicy(backoff_s=0.0))
    ck._puts_overlap = True
    tid = ck.save(_state(0))
    assert seen and store.puts[seen[0]] == 2
    assert ck.save_stats[-1]["n_retries"] == 1
    assert ck._put_pool is not None
    got = ck.load(time_id=tid)
    assert np.array_equal(np.asarray(got["l1"]),
                          np.asarray(_state(0)["l1"]))


@pytest.mark.parametrize("fault", ["crash", "exhausted"])
def test_a_failed_task_stops_the_save_before_its_manifest(tmp_path, fault):
    from_put = [None]

    def fail(n, digest_hex):
        if from_put[0] is not None and n >= from_put[0]:
            if fault == "crash":
                raise InjectedCrash("put")
            raise OSError("disk gone")

    store = _CountingFileStore(str(tmp_path), fail=fail)
    ck = Chipmink(store, chunk_bytes=CHUNK,
                  retry_policy=RetryPolicy(max_retries=2, backoff_s=0.0))
    ck._puts_overlap = True
    first = ck.save(_state(0))
    from_put[0] = sum(store.puts.values()) + 3
    with pytest.raises(InjectedCrash if fault == "crash" else OSError):
        ck.save(_state(1))
    # every task has ended: no put lands after the save has raised
    after = dict(store.puts)
    time.sleep(0.1)
    assert dict(store.puts) == after
    assert store.list_time_ids() == [first]
    assert ck.versions.head_commit() == first
    from_put[0] = None
    third = ck.save(_state(2))
    assert store.list_time_ids() == [first, third]
    assert ck.versions.head_commit() == third
    got = ck.load(time_id=third)
    for k, v in _state(2).items():
        assert np.array_equal(np.asarray(got[k]), np.asarray(v))


def test_two_stores_put_one_digest_from_many_threads(tmp_path):
    root = str(tmp_path)
    stores = [FileStore(root), FileStore(root)]
    blobs = {os.urandom(16).hex(): os.urandom(3000 + i) for i in range(40)}
    errors = []

    def worker(store, seed):
        order = list(blobs)
        random.Random(seed).shuffle(order)
        try:
            for d in order:
                store.put_pod(d, blobs[d])
        except BaseException as exc:       # reported below
            errors.append(exc)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=worker, args=(stores[i % 2], i))
                   for i in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert errors == []
    fresh = FileStore(root)
    assert fresh.list_pods() == sorted(blobs)
    for d, data in blobs.items():
        assert fresh.get_pod(d) == data
    assert _tmp_files(root) == []


def test_sweep_removes_an_orphan_of_the_unique_name(tmp_path):
    store = FileStore(str(tmp_path))
    d = "ab" + "0" * 30
    store.put_pod(d, b"pod")
    shard = os.path.join(str(tmp_path), "pods", "ab")
    orphan = os.path.join(shard, f"{d}.pod.0123456789ab7.tmp")
    with open(orphan, "wb") as f:
        f.write(b"po")
    assert store.sweep_tmp() == 1
    assert not os.path.exists(orphan)
    assert store.get_pod(d) == b"pod"


def test_fs_call_and_byte_counters_are_exact_under_threads(tmp_path):
    store = FileStore(str(tmp_path))
    shards = [f"{i:02x}" for i in range(16)]
    digests = [shards[i % 16] + os.urandom(15).hex() for i in range(480)]
    before = store.stats.pod_fs_calls
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(
            target=lambda part: [store.put_pod(d, d.encode()) for d in part],
            args=(digests[i::8],)) for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    # 4 a fresh pod, and each shard made once
    assert store.stats.pod_fs_calls - before == 4 * len(digests) + 16
    assert store.stats.pods_written == len(digests)
    assert store.stats.pod_bytes_written == sum(len(d) for d in digests)
    again = store.stats.pod_fs_calls
    for d in digests[:8]:
        assert not store.put_pod(d, d.encode())
    assert store.stats.pods_deduped == 8
    assert store.stats.pod_fs_calls - again == 8
