"""The comparison that decides `correct`.

Every number is exact, so every limit is 0:

* `saves_lost`: saves issued in the window that never committed.
* `restore_last_mismatch`: leaves of each stream's last commit that a
  fresh instance on the same store restores differently from the live
  snapshot that was saved (dtype, shape, bytes or scalar value; a leaf
  present on one side only counts too).
* `digest_mismatch`: chunks of sampled leaves of that commit whose
  digest in the manifest differs from the numpy twin of the digest.
* `dirty_mismatch`: for the sampled pair (two consecutive saves that the
  traffic issues right after the window), chunks on which the store's
  dirty set (the manifest digest tables) and the reference's (chunk
  checksums of the two snapshots) disagree, plus the gap between the
  reference's count and the save's own `n_dirty_chunks`.
* `restore_earlier_mismatch`: chunks of the pair's later commit (not the
  last one) whose restored bytes do not give the checksum recorded at
  its save.
* `sample_missing`: 1 when the pair was not recorded.
* `traffic_faults`: non-finite losses of the traffic's own steps (set by
  the harness).
"""
from __future__ import annotations

import contextlib
import time
from typing import Any, Dict, Optional, Set

import numpy as np

from . import reference
from .savelog import SaveLog

LIMITS = {"saves_lost": 0, "restore_last_mismatch": 0,
          "digest_mismatch": 0, "dirty_mismatch": 0,
          "restore_earlier_mismatch": 0, "sample_missing": 0,
          "traffic_faults": 0}

#: leaves digested on the host per run: the largest and a seeded sample
N_DIGEST_LEAVES = 6


def _host(x: Any) -> np.ndarray:
    return np.ascontiguousarray(np.asarray(x))


def _same_array(x: Any, y: Any) -> bool:
    a, b = _host(x), _host(y)
    return (a.dtype == b.dtype and a.shape == b.shape
            and np.array_equal(a.reshape(-1).view(np.uint8),
                               b.reshape(-1).view(np.uint8)))


def leaf_mismatches(live: Any, restored: Any) -> int:
    a, b = reference.flatten(live), reference.flatten(restored)
    n = len(set(a) ^ set(b))
    for key in set(a) & set(b):
        x, y = a[key], b[key]
        if reference._is_array(x) or reference._is_array(y):
            n += not (reference._is_array(x) and reference._is_array(y)
                      and _same_array(x, y))
        else:
            n += x != y
    return int(n)


def digest_mismatches(restored: Any, table: Dict[str, bytes],
                      chunk_bytes: int, rng: np.random.Generator,
                      seed: int = 0) -> int:
    leaves = reference.array_leaves(restored)
    keys = sorted(leaves, key=lambda k: (-int(leaves[k].nbytes), k))
    picks = keys[:1] + [keys[1:][i] for i in rng.choice(
        len(keys) - 1, size=min(N_DIGEST_LEAVES - 1, len(keys) - 1),
        replace=False)] if keys else []
    bad = 0
    for path in picks:
        dig = reference.fingerprint_chunks_np(_host(leaves[path]),
                                              chunk_bytes, seed=seed)
        for ci, row in enumerate(dig):
            bad += table.get(f"{path}#[{ci}]") != row.tobytes()
    return bad


def reference_dirty(before: Dict[str, np.ndarray],
                    after: Dict[str, np.ndarray]) -> Set[str]:
    out: Set[str] = set()
    for path, cs in after.items():
        prev = before.get(path)
        if prev is None or prev.shape != cs.shape:
            rows = range(cs.shape[0])
        else:
            rows = np.nonzero((prev != cs).any(axis=1))[0]
        out.update(f"{path}#[{int(ci)}]" for ci in rows)
    return out


def store_dirty(before: Dict[str, bytes], after: Dict[str, bytes]) -> Set[str]:
    return {k for k, d in after.items() if before.get(k) != d}


def checksum_mismatches(restored: Any, recorded: Dict[str, np.ndarray],
                        chunk_bytes: int) -> int:
    import jax
    fn = jax.jit(reference.chunk_checksums, static_argnums=1)
    leaves = reference.array_leaves(restored)
    bad = 0
    for path, cs in recorded.items():
        if path not in leaves:
            bad += cs.shape[0]
            continue
        got = np.asarray(fn(jax.numpy.asarray(leaves[path]), chunk_bytes))
        bad += int((got != cs).any(axis=1).sum()) if got.shape == cs.shape \
            else cs.shape[0]
    return bad


def compare(log: SaveLog, deployment: Any, rng: np.random.Generator,
            timings: Optional[Dict[str, float]] = None,
            digest_seed: int = 0) -> Dict[str, float]:
    """The numbers compared, in `LIMITS` order.  `deployment` restores
    (`restore_last(stream, tid, like)`, `restore(tid)`) and reads
    manifests (`manifest(tid)`) through a fresh program instance on the
    deployment's store.  `timings` collects the seconds of each part."""
    timings = {} if timings is None else timings

    @contextlib.contextmanager
    def timed(part):
        t0 = time.perf_counter()
        yield
        timings[part] = timings.get(part, 0.0) + time.perf_counter() - t0

    cb = log.chunk_bytes
    window = log.window()
    out: Dict[str, float] = {"saves_lost": sum(
        1 for r in window if r.stats is None)}

    restore_bad = digest_bad = 0
    digest_stream = log.sample
    for stream, (tid, live) in sorted(log.last.items()):
        with timed("restore_last"):
            restored = deployment.restore_last(stream, tid, live)
        with timed("compare_last"):
            restore_bad += leaf_mismatches(live, restored)
        if stream == digest_stream:
            with timed("digests"):
                table = reference.unpack_digest_table(
                    deployment.manifest(tid)["chunks"])
                digest_bad += digest_mismatches(restored, table, cb, rng,
                                                seed=digest_seed)
        del restored
    out["restore_last_mismatch"] = restore_bad
    out["digest_mismatch"] = digest_bad

    pair = log.sampled_pair()
    if pair is None:
        out.update(dirty_mismatch=0, restore_earlier_mismatch=0,
                   sample_missing=1)
        return out
    first, second = pair
    with timed("dirty"):
        ref = reference_dirty(first.checksums, second.checksums)
        man = store_dirty(*(reference.unpack_digest_table(
            deployment.manifest(r.tid)["chunks"]) for r in pair))
        n_dirty = second.stats["n_dirty_chunks"] if second.stats else -1
        out["dirty_mismatch"] = len(ref ^ man) + abs(len(ref) - n_dirty)
    with timed("restore_earlier"):
        out["restore_earlier_mismatch"] = checksum_mismatches(
            deployment.restore(second.tid), second.checksums, cb)
    out["sample_missing"] = 0
    return out


def verdict(numbers: Dict[str, float]) -> bool:
    return all(numbers.get(k, 1) <= lim for k, lim in LIMITS.items())
