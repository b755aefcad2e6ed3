"""FileStore pod I/O through one held descriptor of ``pods/``: the same
files, names and bytes as full paths, dedup read from disk, fsync of
file and shard directory, a ``pods/`` replaced underneath a live store,
the descriptors a store holds, the one `_write_atomic` per pod put that
the benchmark wraps, and the `pod_fs_calls` counter."""
import gc
import os
import shutil
import stat

import jax.numpy as jnp
import msgpack
import numpy as np
import pytest

from repro.core import Chipmink, FileStore
from repro.core import store as store_mod
from repro.core.delta import encode_pod_delta

BASE = "aa" * 16
NEW = "ab" * 16


def _blob(tag):
    entries = [{"k": f"leaf/{i}", "t": 2, "r": 0, "d": tag * 64}
               for i in range(4)]
    return msgpack.packb({"pid": 1, "e": entries}, use_bin_type=True)


def _new_blob():
    doc = msgpack.unpackb(_blob(b"v"), raw=False)
    doc["e"][1]["d"] = b"x" * 64
    return msgpack.packb(doc, use_bin_type=True)


def _put(store, form):
    """Put NEW in `form` over a whole BASE; returns what the put said."""
    if form == "whole":
        return store.put_pod(NEW, _new_blob())
    return store.put_pod_delta(NEW, encode_pod_delta(_new_blob(), BASE, [1]))


def _files(root):
    return sorted(os.path.relpath(os.path.join(d, f), root)
                  for d, _, fs in os.walk(os.path.join(root, "pods"))
                  for f in fs)


@pytest.mark.parametrize("relative", [True, False],
                         ids=["held-descriptor", "full-paths"])
@pytest.mark.parametrize("form", ["whole", "delta"])
def test_pod_round_trip_dedup_and_delete(tmp_path, monkeypatch, form,
                                         relative):
    monkeypatch.setattr(store_mod, "_RELATIVE_IO", relative)
    root = str(tmp_path)
    store = FileStore(root)
    assert bool(store._pods_fds) == relative
    assert store.put_pod(BASE, _blob(b"v"))
    assert _put(store, form)
    # dedup against either form, read from disk
    assert not store.put_pod(NEW, _new_blob())
    assert not store.put_pod_delta(NEW, b"unused")
    assert store.stats.pods_written == 2 and store.stats.pods_deduped == 2

    suffix = ".pod" if form == "whole" else ".dpod"
    assert _files(root) == [f"pods/aa/{BASE}.pod", f"pods/ab/{NEW}{suffix}"]
    on_disk = os.path.getsize(os.path.join(root, "pods", "ab", NEW + suffix))
    assert store.has_pod(NEW) and store.pod_nbytes(NEW) == on_disk
    assert store.get_pod(NEW) == _new_blob()
    assert store.list_pods() == [BASE, NEW]
    assert store.list_delta_pods() == ([NEW] if form == "delta" else [])
    assert FileStore(root).get_pod(NEW) == _new_blob()

    assert store.delete_pod(NEW) == on_disk
    assert not store.has_pod(NEW) and store.list_pods() == [BASE]
    assert _files(root) == [f"pods/aa/{BASE}.pod"]


@pytest.mark.parametrize("form", ["whole", "delta"])
def test_fsync_syncs_the_file_and_its_shard_directory(tmp_path, monkeypatch,
                                                      form):
    store = FileStore(str(tmp_path), fsync=True)
    store.put_pod(BASE, _blob(b"v"))
    synced = []
    real_fsync = os.fsync

    def counting_fsync(fd):
        synced.append(os.fstat(fd))
        real_fsync(fd)

    monkeypatch.setattr(os, "fsync", counting_fsync)
    assert _put(store, form)
    assert not _put(store, form)                # a dedup hit writes nothing
    shard = os.stat(os.path.join(str(tmp_path), "pods", "ab"))
    assert len(synced) == 2
    assert stat.S_ISREG(synced[0].st_mode)
    assert stat.S_ISDIR(synced[1].st_mode)
    assert synced[1].st_ino == shard.st_ino


@pytest.mark.parametrize("remade", [True, False],
                         ids=["removed-and-remade", "removed"])
def test_a_pods_directory_replaced_underneath_still_lands_the_put(
        tmp_path, remade):
    root = str(tmp_path)
    store = FileStore(root)
    assert store.put_pod(BASE, _blob(b"v"))
    pods = os.path.join(root, "pods")
    shutil.rmtree(pods)
    if remade:
        os.mkdir(pods)
    assert not store.has_pod(BASE)              # gone with the directory
    assert store.put_pod(NEW, _new_blob())
    assert _files(root) == [f"pods/ab/{NEW}.pod"]
    assert store.get_pod(NEW) == _new_blob()
    assert FileStore(root).get_pod(NEW) == _new_blob()
    # the shard of the first pod is made again in the new directory
    assert store.put_pod(BASE, _blob(b"v"))
    assert FileStore(root).list_pods() == [BASE, NEW]


def test_a_store_holds_few_descriptors_and_drops_them(tmp_path):
    def n_open():
        return len(os.listdir("/proc/self/fd"))

    before = n_open()
    store = FileStore(str(tmp_path))
    for i in range(256):
        store.put_pod(f"{i:02x}" + "0" * 30, _blob(bytes([i])))
    assert len(os.listdir(os.path.join(str(tmp_path), "pods"))) == 256
    assert n_open() <= before + 3
    del store
    gc.collect()
    assert n_open() <= before


@pytest.mark.parametrize("form", ["whole", "delta"])
def test_every_pod_put_passes_through_write_atomic_once(tmp_path,
                                                        monkeypatch, form):
    # wrapped by name, as the benchmark's traced run wraps it
    orig = FileStore.__dict__["_write_atomic"]
    calls = []

    def wrapped(self, *args, **kwargs):
        calls.append(args[0])
        return orig(self, *args, **kwargs)

    store = FileStore(str(tmp_path))
    store.put_pod(BASE, _blob(b"v"))
    monkeypatch.setattr(FileStore, "_write_atomic", wrapped)
    assert _put(store, form)
    assert not _put(store, form)
    suffix = ".pod" if form == "whole" else ".dpod"
    assert len(calls) == 1 and calls[0].endswith(NEW + suffix)


@pytest.mark.parametrize("case, want", [
    ("fresh pod, known shard", 4),
    ("fresh pod, new shard", 5),
    ("dedup hit, whole form", 1),
    ("dedup hit, delta form", 2),
    ("fresh pod, known shard, fsync", 5),
])
def test_pod_fs_calls_counts_path_resolving_calls(tmp_path, case, want):
    store = FileStore(str(tmp_path), fsync="fsync" in case)
    store.put_pod(BASE, _blob(b"v"))            # shard "aa" now known
    if "delta" in case:
        store.put_pod_delta(NEW, encode_pod_delta(_new_blob(), BASE, [1]))
    elif "dedup" in case:
        store.put_pod(NEW, _new_blob())
    digest = NEW if "dedup" in case or "new shard" in case else "aa" + "1" * 30
    before = store.stats.pod_fs_calls
    store.put_pod(digest, _new_blob())
    assert store.stats.pod_fs_calls - before == want


def test_a_save_records_its_store_fs_calls(tmp_path):
    store = FileStore(str(tmp_path))
    ck = Chipmink(store, chunk_bytes=1 << 12)
    w = np.arange(1 << 14, dtype=np.float32)
    for step in range(3):
        w = w.copy()
        w[step * 1024] += 1.0
        before = store.stats.pod_fs_calls
        ck.save({"w": jnp.asarray(w), "step": step})
        st = ck.save_stats[-1]
        assert st["n_store_fs_calls"] == store.stats.pod_fs_calls - before
        shards = st["pods_written"] if step == 0 else 0
        assert (4 * st["pods_written"] <= st["n_store_fs_calls"]
                <= 4 * st["pods_written"] + shards + 2 * st["pods_aliased"])
