"""Cross-writer contention: concurrent Chipmink instances on ONE store.

The fast half runs two instances (their own threads) plus a concurrent
collector inside one process against a shared FileStore — real CAS
traffic, real lease fencing, no subprocess overhead.  The @slow half is
the real thing: separate Python processes race saves and branch
mutations on one directory, and both histories must come back
bit-identical to a serialized oracle.
"""
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import repro
from repro.core import Chipmink, FileStore, LeaseManager

SRC_DIR = os.path.dirname(os.path.dirname(os.path.abspath(repro.__file__)))


def _state(fill: float):
    return {"w": np.full((64, 8), np.float32(fill)), "step": int(fill)}


def _check(loaded, fill: float):
    assert loaded["step"] == int(fill)
    assert np.array_equal(loaded["w"], np.full((64, 8), np.float32(fill)))


def _open(root, **kw):
    kw.setdefault("fsck_on_open", False)
    return Chipmink(store=FileStore(root), use_kernel=False,
                    multi_writer=True, lease_heartbeat=False, **kw)


def test_two_writers_and_gc_in_threads(tmp_path):
    """Two instances save on disjoint branches while a third collects.
    Zero lost commits; GC never sweeps a committed pod."""
    root = str(tmp_path)
    boot = _open(root)
    boot.save(_state(0.0))            # root commit on main
    boot.close()

    n_each = 5
    oracle = {}                        # tid -> fill
    errors = []
    lock = threading.Lock()

    def writer(idx):
        try:
            ck = _open(root)
            ck.checkout("main")
            ck.branch(f"w{idx}")
            for i in range(n_each):
                fill = 100.0 * (idx + 1) + i
                tid = ck.save(_state(fill))
                with lock:
                    oracle[tid] = fill
            ck.close()
        except BaseException as e:     # surfaced after join
            errors.append((idx, e))

    stop = threading.Event()
    gc_stats = {"runs": 0, "pinned": 0, "restarts": 0}

    def collector():
        try:
            ck = _open(root)
            while not stop.is_set():
                st = ck.gc()
                gc_stats["runs"] += 1
                gc_stats["pinned"] += st.n_pods_pinned
                gc_stats["restarts"] += st.n_mark_restarts
                time.sleep(0.01)
            ck.close()
        except BaseException as e:
            errors.append(("gc", e))

    threads = [threading.Thread(target=writer, args=(i,)) for i in (0, 1)]
    gc_thread = threading.Thread(target=collector)
    for t in threads:
        t.start()
    gc_thread.start()
    for t in threads:
        t.join()
    stop.set()
    gc_thread.join()

    assert not errors, errors
    assert len(oracle) == 2 * n_each   # no tid collisions, no lost saves
    assert gc_stats["runs"] >= 1

    # serialized verification: every commit loads bit-identical
    ver = _open(root)
    for tid, fill in sorted(oracle.items()):
        _check(ver.load(time_id=tid), fill)
    for idx in (0, 1):
        tip = ver.versions.resolve(f"w{idx}")
        _check(ver.load(time_id=tip), 100.0 * (idx + 1) + n_each - 1)
    rep = ver.fsck()
    assert not rep.incomplete and not rep.refs_rolled_back
    assert LeaseManager(ver.store).live_leases() == []
    ver.close()


WORKER = r"""
import json, sys
import numpy as np
from repro.core import Chipmink, FileStore

root, idx, n_saves = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
ck = Chipmink(store=FileStore(root), use_kernel=False, multi_writer=True,
              lease_ttl_s=5.0, fsck_on_open=False)
ck.checkout("main")
ck.branch(f"w{idx}")
tids = []
for i in range(n_saves):
    fill = 1000.0 * (idx + 1) + i
    s = {"w": np.full((64, 8), np.float32(fill)), "step": int(fill)}
    tids.append(ck.save(s))
ck.tag(f"t{idx}", at=tids[-1])
ck.close()
with open(f"{root}/out{idx}.json", "w") as f:
    json.dump({"tids": tids,
               "refs_races": ck.versions.n_cas_races,
               "lease_races": ck.leases.n_blob_cas_races}, f)
"""


@pytest.mark.slow
def test_two_processes_race_saves_and_branches(tmp_path):
    """The satellite contract: two separate Chipmink PROCESSES race
    saves + branch/tag mutations against one FileStore; afterwards both
    histories are bit-identical to the serialized oracle."""
    root = str(tmp_path)
    boot = _open(root)
    boot.save(_state(0.0))
    boot.close()

    n_saves = 4
    env = dict(os.environ, PYTHONPATH=SRC_DIR, JAX_PLATFORMS="cpu")
    procs = [subprocess.Popen(
        [sys.executable, "-c", WORKER, root, str(idx), str(n_saves)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE)
        for idx in (0, 1)]
    for p in procs:
        out, err = p.communicate(timeout=300)
        assert p.returncode == 0, err.decode()

    results = {}
    for idx in (0, 1):
        with open(os.path.join(root, f"out{idx}.json")) as f:
            results[idx] = json.load(f)
    all_tids = results[0]["tids"] + results[1]["tids"]
    assert len(set(all_tids)) == 2 * n_saves   # CAS tid counter held

    ver = _open(root)
    for idx in (0, 1):
        for i, tid in enumerate(results[idx]["tids"]):
            _check(ver.load(time_id=tid), 1000.0 * (idx + 1) + i)
        # both the branch tip and the tag survived the refs races
        assert ver.versions.resolve(f"w{idx}") == results[idx]["tids"][-1]
        assert ver.versions.resolve(f"t{idx}") == results[idx]["tids"][-1]
    rep = ver.fsck()
    assert not rep.incomplete and not rep.refs_rolled_back
    assert rep.leases_reaped == []     # close() released every lease
    # GC reclaims nothing: every commit is reachable from a branch/tag
    st = ver.gc()
    assert st.n_commits_deleted == 0
    for idx in (0, 1):
        for i, tid in enumerate(results[idx]["tids"]):
            _check(ver.load(time_id=tid), 1000.0 * (idx + 1) + i)
    ver.close()
