"""jit'd wrappers around the fingerprint kernel: arrays & pytrees → digests.

`leaf_fingerprint` converts an array of any dtype into the canonical uint32
word stream, splits it on the ObjectGraph's deterministic row-block grid,
and returns one 128-bit digest per chunk.  `tree_fingerprint` maps the graph
of a state pytree to a {chunk key → digest bytes} table — the device half of
the change detector (§4.2).

Fingerprint pipeline
--------------------
The per-leaf functions here are the **parity oracle**; the save hot path
runs the batched engine in `batch.py`.  Layout and contract:

  * Bucket layout: every chunk of every leaf is a row of exactly one
    power-of-two word-width bucket (`pow2ceil(words_per_chunk)`, min 128
    words).  Rows are bucket-major: buckets ascend by width, a leaf's
    chunks are consecutive rows within its bucket.  Row counts are padded
    to a multiple of the kernel's row block; (C, W) bucket shapes depend
    only on the leaf specs, so they repeat across saves and the kernel
    jit cache stops recompiling; padded rows carry zero words and a zero
    folded length and are sliced off on the host.
  * Kernel mode: compiled on a TPU, interpreted on the CPU, decided in
    one place from the platform (`fingerprint.pallas_interpret`).
  * Digest-neutral padding: zero words contribute nothing to the
    weighted sums and each row folds its own true byte length (ref.py),
    so a 2048-word chunk digests bit-identically whether it sits in a
    (1, 2048) per-leaf call or a (512, 2048) bucket row.
  * Single-sync invariant: a save issues one `pallas_call` per bucket
    and fetches digests, the on-device dirty bitmask, and speculated
    payload rows with **one** `jax.device_get` total.  The fused bucket
    kernel (`fingerprint.fingerprint_words_cmp`) compares each completed
    digest against the device-resident previous table
    (`batch.DeviceTable` — in the steady state the previous save's own
    kernel output, zero table traffic) and emits a per-row dirty flag;
    rows without a trusted previous digest are forced dirty on the host.
  * Speculation semantics: chunks whose flip EMA exceeds the store's
    ``spec_threshold`` (`core.volatility.FlipTracker.predicted`) —
    expanded to pod granularity, plus the pods of changed scalars — have
    their packed word rows compacted into the digest fetch.  Chunk
    boundaries are 4-byte aligned and rows are little-endian bitcasts,
    so a fetched row's first true-length bytes ARE the chunk payload.
    A dirty chunk in the payload is a speculation *hit* (its bytes
    already crossed the link); a dirty chunk outside it is a *miss* and
    joins one corrective `batched_chunk_fetch` — so a warm sparse save
    costs exactly 1 blocking sync, any save at most 2 (digest fetch +
    ≤ 1 corrective gather), and manifests are bit-identical to the
    two-sync path either way.
  * Fallback ladder: ``fused=True`` (default) → on-device compare +
    speculative payload, 1–2 syncs; ``fused=False`` → batched two-sync
    path (digest fetch + payload gather, host compare); ``batched=False``
    → the per-leaf oracle here (one sync per device leaf).  Host (numpy)
    leaves always digest on the host (numpy twin, zero syncs) and are
    dirty-resolved by the host compare at every rung.
  * Incremental host half (see `core.checkpoint`): the digest keys this
    engine emits are *chunk keys*, which the incremental pipeline relies
    on being stable — `GraphCache` keeps node ids and keys fixed for
    unchanged subtrees, so the persistent digest table, the reused
    `PodAssignment` (memo locals preserved), and the pod-digest cache
    all index the same rows across saves.  Overlapped async saves are
    sound because the graph built at `save()` call time snapshots device
    array references (immutable) and host scalars before the device
    digest/gather work is enqueued behind the previous save.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.graph import ObjectGraph, chunk_grid
from .fingerprint import fingerprint_words
from .ref import fingerprint_words_np, fingerprint_words_ref


#: lane count of a TPU vreg row: sub-word dtypes are packed in rows of
#: this many words, so no intermediate has a narrow minor dimension
_LANES = 128


def to_words(arr: jnp.ndarray) -> jnp.ndarray:
    """Bitcast any-dtype array to a flat uint32 word stream (device-side).

    itemsize 4 → direct bitcast; 2 → pack pairs little-endian; 1 → pack
    quads; 8 → bitcast to 2×uint32.  Trailing bytes are zero-padded (the
    digest folds true lengths separately).

    Sub-word dtypes are packed lane-dense: the stream is laid out in rows
    of 128 words' worth of elements, and element j of each word is the
    strided lane slice ``[:, j::per_word]``.  A ``(-1, 2)`` or ``(-1, 4)``
    layout would be tiled out to 128 lanes on a TPU — a 64x (resp. 32x)
    temporary of the leaf."""
    if arr.dtype == jnp.bool_:
        arr = arr.astype(jnp.uint8)
    flat = arr.reshape(-1)
    isz = np.dtype(arr.dtype).itemsize
    if isz == 4:
        return jax.lax.bitcast_convert_type(flat, jnp.uint32)
    if isz not in (1, 2, 8):
        raise ValueError(f"unsupported itemsize {isz}")
    n_words = -(-flat.shape[0] * isz // 4)
    per = max(1, 4 // isz)              # elements per word (1 for 8-byte)
    row = _LANES * per if isz < 4 else _LANES
    pad = (-flat.shape[0]) % row
    if pad:
        flat = jnp.pad(flat, (0, pad))
    if isz == 8:
        words = jax.lax.bitcast_convert_type(flat.reshape(-1, row),
                                             jnp.uint32)
        return words.reshape(-1)[:n_words]
    u = jax.lax.bitcast_convert_type(
        flat, jnp.uint16 if isz == 2 else jnp.uint8).reshape(-1, row)
    # slice before widening: the strided slices of the narrow dtype are
    # the only temporaries (together the size of the leaf)
    words = u[:, 0::per].astype(jnp.uint32)
    for j in range(1, per):
        words = words | (u[:, j::per].astype(jnp.uint32)
                         << jnp.uint32(8 * isz * j))
    return words.reshape(-1)[:n_words]


def to_words_np(arr: np.ndarray) -> np.ndarray:
    """Host (numpy) twin of to_words — bit-identical."""
    a = np.asarray(arr)
    if a.dtype == np.bool_:
        a = a.astype(np.uint8)
    raw = a.tobytes()
    pad = (-len(raw)) % 4
    if pad:
        raw += b"\0" * pad
    return np.frombuffer(raw, dtype="<u4").copy()


def leaf_fingerprint(arr: Any, *, chunk_bytes: int = 1 << 22, seed: int = 0,
                     use_kernel: bool = True) -> np.ndarray:
    """Digest one array on its flat-range chunk grid → uint32 (n_chunks, 4)."""
    arr = jnp.asarray(arr)
    shape = tuple(int(d) for d in arr.shape)
    dtype = np.dtype(arr.dtype)
    elems, n_chunks = chunk_grid(shape, dtype, chunk_bytes)
    total = int(np.prod(shape, dtype=np.int64)) if shape else 1
    nbytes = total * dtype.itemsize

    if n_chunks == 1:
        words = to_words(arr)[None, :]
        lengths = jnp.asarray([nbytes], jnp.uint32)
    else:
        flat = arr.reshape(-1)
        pad = n_chunks * elems - total
        if pad:
            flat = jnp.pad(flat, (0, pad))
        words = to_words(flat)
        words = words.reshape(n_chunks, words.shape[0] // n_chunks)
        lens = np.full((n_chunks,), elems * dtype.itemsize, dtype=np.uint32)
        lens[-1] = nbytes - (n_chunks - 1) * elems * dtype.itemsize
        lengths = jnp.asarray(lens)

    if use_kernel:
        dig = fingerprint_words(words, lengths, seed=seed)
    else:
        dig = fingerprint_words_ref(words, lengths, seed=seed)
    return np.asarray(jax.device_get(dig))


def leaf_fingerprint_np(arr: np.ndarray, *, chunk_bytes: int = 1 << 22,
                        seed: int = 0) -> np.ndarray:
    """Pure-host twin for numpy state (data-pipeline cursors etc.)."""
    a = np.asarray(arr)
    shape = a.shape
    dtype = a.dtype
    elems, n_chunks = chunk_grid(shape, dtype, chunk_bytes)
    total = int(np.prod(shape, dtype=np.int64)) if shape else 1
    nbytes = int(a.nbytes)
    if n_chunks == 1:
        words = to_words_np(a)[None, :]
        lengths = np.asarray([nbytes], np.uint32)
    else:
        flat = a.reshape(-1)
        pad = n_chunks * elems - total
        if pad:
            flat = np.pad(flat, (0, pad))
        words = to_words_np(flat)
        words = words.reshape(n_chunks, words.shape[0] // n_chunks)
        lengths = np.full((n_chunks,), elems * dtype.itemsize, dtype=np.uint32)
        lengths[-1] = nbytes - (n_chunks - 1) * elems * dtype.itemsize
    return fingerprint_words_np(words, lengths, seed=seed)


def digest_to_bytes(row: np.ndarray) -> bytes:
    return np.asarray(row, np.uint32).tobytes()


def tree_fingerprint(graph: ObjectGraph, *, active_leaf_paths=None,
                     chunk_bytes: int = 1 << 22, seed: int = 0,
                     use_kernel: bool = True) -> Dict[str, bytes]:
    """Digest every chunk of (active) leaves → {chunk key: 16-byte digest}."""
    out: Dict[str, bytes] = {}
    for leaf in graph.leaf_nodes():
        lkey = leaf.key
        if active_leaf_paths is not None and lkey not in active_leaf_paths:
            continue
        arr = graph.arrays[lkey]
        if isinstance(arr, np.ndarray):
            dig = leaf_fingerprint_np(arr, chunk_bytes=chunk_bytes, seed=seed)
        else:
            dig = leaf_fingerprint(arr, chunk_bytes=chunk_bytes, seed=seed,
                                   use_kernel=use_kernel)
        for ci in range(dig.shape[0]):
            out[f"{lkey}#[{ci}]"] = digest_to_bytes(dig[ci])
    return out
