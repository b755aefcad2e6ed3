"""Batched, size-bucketed fingerprint dispatch (§4.2 hot path, amortized).

The per-leaf digest path (`ops.leaf_fingerprint`) pays one Pallas dispatch,
one jit-cache entry per distinct (C, W) shape, and one blocking
`jax.device_get` *per leaf per save*.  For a real training pytree with
hundreds of leaves that dispatch/sync overhead dominates the actual
memory-bound hashing.  This module amortizes all of it across the whole
object graph:

  * **Planner** — every chunk of every leaf is assigned a slot
    (bucket, row) where the bucket is the power-of-two word width
    ``pow2ceil(words_per_chunk)`` clamped to ``MIN_BUCKET_WORDS``.  Mixed
    dtypes and ragged leaves land in the same bucket as long as their
    chunk word-widths round to the same power of two; per-row true byte
    lengths are folded into the digest exactly as in the per-leaf path,
    so bucket padding is digest-neutral.
  * **Packer** — a jit'd function (cached on the plan) bitcasts every
    leaf to its uint32 word stream, reshapes it onto the chunk grid, and
    writes it in place into its rows of one (C_bucket, W_bucket) matrix
    per bucket.  Row counts are padded up to a multiple of the kernel's
    row block; the plan depends only on the leaf specs, so bucket shapes
    repeat across saves of one pytree and the kernel's jit cache stops
    recompiling.
  * **Dispatch** — one `pallas_call` per bucket, row-blocked
    (`fingerprint.fingerprint_words(rows=...)`): a grid cell digests up
    to ``fingerprint.MAX_BLOCK_ROWS`` chunks at once, so small chunks
    cost a fraction of a dispatch instead of one each.
  * **Fetch** — all (C, 4) digest rows of all buckets leave the device in
    a **single** `jax.device_get` at the end of the save (the
    single-sync contract; `DigestResult.n_syncs` reports it).

The **fused single-sync** path (`digest_leaves_fused`) goes one step
further: the previous save's digest table stays *resident on device*
(`DeviceTable` — per-bucket (padded_rows, 4) arrays in slot order), the
compare-against-previous runs inside the bucket kernel
(`fingerprint.fingerprint_words_cmp` emits digests **plus** a dirty
bitmask per bucket), and a speculative compaction gathers the packed
word rows of likely-dirty chunks into dense per-bucket payload buffers —
so digests, bitmask, and dirty-chunk payload all come back in **one**
`jax.device_get`.  Because rows are pre-packed uint32 word streams and
chunk boundaries are 4-byte aligned (`core.graph.chunk_grid`), a fetched
row's first `true_length` bytes ARE the chunk's payload bytes — no second
gather for speculated chunks.  In the steady state the device table is
the previous save's own kernel output (zero host↔device table traffic);
when the plan changes or the table was imported (post-checkout), it is
re-seeded from the host table via one async H2D upload — never a
blocking fetch.

Host (numpy) leaves run through the same planner with the numpy digest
twin — batching there amortizes the per-call weight-stream computation of
`ref.fingerprint_words_np` across every row of a bucket.

The per-leaf functions in ops.py remain the parity oracle: batched
digests are bit-identical (see tests/test_batch_plan.py).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.graph import ObjectGraph, chunk_grid, fetch_whole_leaf
from .fingerprint import (TILE, block_rows, fingerprint_words,
                          fingerprint_words_cmp, pow2ceil)
from .ref import (fingerprint_words_cmp_ref, fingerprint_words_np,
                  fingerprint_words_ref)

#: smallest bucket word width (512 B) — tiny leaves share one bucket
MIN_BUCKET_WORDS = 128
#: chunks per piece when the packer cuts a large leaf along its rows
PACK_PIECE_CHUNKS = 32


@dataclasses.dataclass(frozen=True)
class LeafSlot:
    """Where one leaf's chunks land: rows [row0, row0+n_chunks) of a bucket."""
    key: str
    shape: Tuple[int, ...]
    dtype: str
    n_chunks: int
    elems: int               # elements per full chunk (flat-range grid)
    words_per_chunk: int     # uint32 word width of a full chunk
    nbytes: int              # total leaf payload bytes
    bucket: int              # bucket word width (power of two)
    row0: int                # first row within the bucket


@dataclasses.dataclass(frozen=True)
class BucketSpec:
    width: int               # words per row (power of two)
    n_rows: int              # real chunk rows
    padded_rows: int         # n_rows up to a multiple of block_rows
    block_rows: int          # rows per kernel grid cell
    tile: int                # inner tile width for the kernel


@dataclasses.dataclass(frozen=True)
class BatchPlan:
    leaves: Tuple[LeafSlot, ...]
    buckets: Tuple[BucketSpec, ...]   # ascending width; rows bucket-major
    chunk_bytes: int

    @property
    def n_chunks(self) -> int:
        return sum(b.n_rows for b in self.buckets)


@functools.lru_cache(maxsize=512)
def plan_leaves(specs: Tuple[Tuple[str, Tuple[int, ...], str], ...],
                chunk_bytes: int) -> BatchPlan:
    """Pack chunk slots of the given (key, shape, dtype) leaves into
    power-of-two word-width buckets.  Deterministic: slots depend only on
    the spec sequence and chunk_bytes, so plans (and the jit'd packers
    keyed on them) are shared across saves."""
    slots: List[LeafSlot] = []
    rows_in_bucket: Dict[int, int] = {}
    for key, shape, dtype in specs:
        dt = np.dtype(dtype)
        elems, n_chunks = chunk_grid(shape, dt, chunk_bytes)
        total = int(np.prod(shape, dtype=np.int64)) if shape else 1
        nbytes = total * dt.itemsize
        wpc = max(1, -(-(elems * dt.itemsize) // 4))
        bucket = max(MIN_BUCKET_WORDS, pow2ceil(wpc))
        row0 = rows_in_bucket.get(bucket, 0)
        rows_in_bucket[bucket] = row0 + n_chunks
        slots.append(LeafSlot(key=key, shape=tuple(shape), dtype=str(dtype),
                              n_chunks=n_chunks, elems=elems,
                              words_per_chunk=wpc, nbytes=nbytes,
                              bucket=bucket, row0=row0))
    buckets = []
    for width in sorted(rows_in_bucket):
        n_rows = rows_in_bucket[width]
        block = block_rows(n_rows)
        padded = -(-n_rows // block) * block
        buckets.append(BucketSpec(width=width, n_rows=n_rows,
                                  padded_rows=padded, block_rows=block,
                                  tile=min(TILE, width)))
    return BatchPlan(leaves=tuple(slots), buckets=tuple(buckets),
                     chunk_bytes=chunk_bytes)


@functools.lru_cache(maxsize=512)
def _plan_slots(plan: BatchPlan) -> Tuple[Tuple[str, ...],
                                          Tuple[Tuple[str, int], ...]]:
    """(chunk keys in slot order, (leaf key, global row offset) pairs).

    Slot order is bucket-major (ascending width), then row order within
    the bucket.  Cached per plan so steady-state saves rebuild nothing.
    """
    base: Dict[int, int] = {}
    off = 0
    for b in plan.buckets:
        base[b.width] = off
        off += b.n_rows
    ordered = sorted(plan.leaves, key=lambda s: (s.bucket, s.row0))
    keys: List[str] = []
    leaf_offsets: List[Tuple[str, int]] = []
    for s in ordered:
        row = base[s.bucket] + s.row0
        leaf_offsets.append((s.key, row))
        keys.extend(f"{s.key}#[{ci}]" for ci in range(s.n_chunks))
    return tuple(keys), tuple(leaf_offsets)


@functools.lru_cache(maxsize=512)
def _plan_lengths(plan: BatchPlan) -> Tuple[np.ndarray, ...]:
    """Per-bucket true-byte-length columns (padded rows fold length 0)."""
    out = {b.width: np.zeros((b.padded_rows,), np.uint32)
           for b in plan.buckets}
    for s in plan.leaves:
        lens = np.full((s.n_chunks,), s.elems * np.dtype(s.dtype).itemsize,
                       np.uint32)
        lens[-1] = s.nbytes - (s.n_chunks - 1) * s.elems * \
            np.dtype(s.dtype).itemsize
        out[s.bucket][s.row0:s.row0 + s.n_chunks] = lens
    return tuple(out[b.width] for b in plan.buckets)


def _words_matrix(slot: LeafSlot, arr: Any, n_chunks: int) -> jnp.ndarray:
    """uint32 (n_chunks, bucket) word rows of `arr`, the leaf's chunks
    from a chunk boundary on (zero words pad the last chunk and each
    row's width)."""
    from .ops import to_words
    w = to_words(arr)
    need = n_chunks * slot.words_per_chunk
    if int(w.shape[0]) != need:
        w = jnp.pad(w, (0, need - int(w.shape[0])))
    mat = w.reshape(n_chunks, slot.words_per_chunk)
    if slot.words_per_chunk != slot.bucket:
        mat = jnp.pad(mat, ((0, 0), (0, slot.bucket - slot.words_per_chunk)))
    return mat


def _leaf_pieces(slot: LeafSlot, arr: Any):
    """(first chunk, word rows) pieces of one leaf.

    A leaf whose chunks are whole rows of its leading axis is cut along
    that axis, `PACK_PIECE_CHUNKS` chunks at a time: laying a piece out
    as word rows is a temporary of that piece only, where the whole leaf
    at once costs a temporary of the leaf (on a TPU, a reshape that
    changes the minor dimension moves the data)."""
    row = int(np.prod(slot.shape[1:], dtype=np.int64))
    if (len(slot.shape) < 2 or slot.elems % row
            or slot.n_chunks <= PACK_PIECE_CHUNKS):
        yield 0, _words_matrix(slot, arr, slot.n_chunks)
        return
    rows_per_chunk = slot.elems // row
    for c0 in range(0, slot.n_chunks, PACK_PIECE_CHUNKS):
        n = min(PACK_PIECE_CHUNKS, slot.n_chunks - c0)
        part = arr[c0 * rows_per_chunk:(c0 + n) * rows_per_chunk]
        yield c0, _words_matrix(slot, part, n)


@functools.lru_cache(maxsize=512)
def _packer_for(plan: BatchPlan):
    """jit'd: leaf arrays (plan order) -> per-bucket (padded_rows, width)
    uint32 word matrices.  One dispatch packs the whole pytree.  Each
    leaf's rows are written in place into a zeroed bucket matrix, piece
    by piece, so the program holds no concatenated temporary the size of
    the state beside its output."""
    def pack(*arrays):
        out = {b.width: jnp.zeros((b.padded_rows, b.width), jnp.uint32)
               for b in plan.buckets}
        for slot, arr in zip(plan.leaves, arrays):
            for c0, mat in _leaf_pieces(slot, arr):
                out[slot.bucket] = jax.lax.dynamic_update_slice(
                    out[slot.bucket], mat, (slot.row0 + c0, 0))
        return tuple(out[b.width] for b in plan.buckets)

    return jax.jit(pack)


@functools.lru_cache(maxsize=512)
def _gather_for(rows: int, width: int, n: int):
    """Compiled: `n` rows of a packed bucket of `rows` x `width` words,
    by their int32 indices."""
    words = jax.ShapeDtypeStruct((rows, width), jnp.uint32)
    idx = jax.ShapeDtypeStruct((n,), jnp.int32)
    return jax.jit(lambda w, i: w[i]).lower(words, idx).compile()


@functools.lru_cache(maxsize=512)
def _gathers_for(plan: BatchPlan) -> Tuple[Dict[int, Any], ...]:
    """Per bucket, {n: the compiled gather of n packed rows} for every
    power of two n up to the most rows the bucket can send sparse.

    Built for every bucket the first time the plan speculates: a set of
    speculated rows is gathered in one call, padded to a power of two,
    so no later speculation set compiles a program."""
    out = []
    for b in plan.buckets:
        # a leaf crosses sparse with fewer than half its chunks wanted
        # (`fetch_whole_leaf`)
        most = sum((s.n_chunks - 1) // 2 for s in plan.leaves
                   if s.bucket == b.width)
        sizes = pow2ceil(most).bit_length() if most else 0
        out.append({1 << k: _gather_for(b.padded_rows, b.width, 1 << k)
                    for k in range(sizes)})
    return tuple(out)


def _pack_leaf_words_np(slot: LeafSlot, arr: np.ndarray) -> np.ndarray:
    from .ops import to_words_np
    w = to_words_np(arr)
    need = slot.n_chunks * slot.words_per_chunk
    if w.shape[0] != need:
        w = np.pad(w, (0, need - w.shape[0]))
    mat = w.reshape(slot.n_chunks, slot.words_per_chunk)
    if slot.words_per_chunk != slot.bucket:
        mat = np.pad(mat, ((0, 0), (0, slot.bucket - slot.words_per_chunk)))
    return mat


@dataclasses.dataclass
class DigestResult:
    """Digests of a leaf set in slot order (device buckets first)."""
    keys: List[str]                    # chunk keys, aligned with mat rows
    mat: np.ndarray                    # uint32 (C, 4)
    n_syncs: int                       # device_get calls issued (0 or 1)
    leaf_rows: Dict[str, int]          # leaf key -> first row of its chunks

    def row_of(self, leaf_key: str, chunk_index: int) -> int:
        return self.leaf_rows[leaf_key] + chunk_index


@dataclasses.dataclass
class FusedDigestResult(DigestResult):
    """DigestResult plus the fused-pass extras.

    `dirty` is int8 per slot row: 1 dirty, 0 clean (kernel-compared
    against a trusted previous digest), -1 unknown (host-group rows — the
    caller falls back to its host compare for those).  `payload` maps
    chunk keys of speculatively compacted rows to their exact payload
    bytes (what `serialize_pod` would have gathered).
    """
    dirty: Optional[np.ndarray] = None
    payload: Dict[str, bytes] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class DeviceTable:
    """Previous digest table resident on device, in bucket-slot order.

    In the steady state `digs` are the previous save's own kernel output
    arrays (never re-uploaded); `valid` flags the rows whose previous
    digest is real — a row seeded without a host entry compares against
    zeros and is forced dirty by the caller.
    """
    plan: BatchPlan
    digs: List[Any]                    # per bucket: uint32 (padded_rows, 4)
    valid: List[np.ndarray]            # per bucket: bool (n_rows,)


def seed_device_table(plan: BatchPlan,
                      lookup) -> DeviceTable:
    """Build the device-resident previous-digest table for `plan` from a
    host digest lookup (chunk key -> 16-byte digest, or None when never
    seen).  One async H2D upload per bucket — no blocking sync."""
    keys, _ = _plan_slots(plan)
    digs: List[Any] = []
    valid: List[np.ndarray] = []
    off = 0
    for b in plan.buckets:
        mat = np.zeros((b.padded_rows, 4), np.uint32)
        v = np.zeros((b.n_rows,), bool)
        for r in range(b.n_rows):
            d = lookup(keys[off + r])
            if d is not None:
                mat[r] = np.frombuffer(d, np.uint32)
                v[r] = True
        digs.append(jnp.asarray(mat))
        valid.append(v)
        off += b.n_rows
    return DeviceTable(plan=plan, digs=digs, valid=valid)


@dataclasses.dataclass
class PendingDigest:
    """A digest whose device work is dispatched and not yet fetched.

    `fetch` brings every device output to the host in ONE
    `jax.device_get` (none when there are no device leaves) and returns
    the bytes it brought; `finish` unpacks them into the result.  The
    split lets a caller time the launch, the fetch and the unpack apart.
    """
    handles: Any                       # device outputs, None when none
    unpack: Callable[[Any], Any]       # fetched outputs -> result
    fetched: Any = None

    def fetch(self) -> int:
        if self.handles is None:
            return 0
        self.fetched = jax.device_get(self.handles)   # the ONE sync
        self.handles = None
        return sum(int(x.nbytes)
                   for x in jax.tree_util.tree_leaves(self.fetched))

    def finish(self) -> Any:
        fetched, self.fetched = self.fetched, None
        return self.unpack(fetched)

    def result(self) -> Any:
        """Fetch and finish."""
        self.fetch()
        return self.finish()


def _plan_groups(items: Sequence[Tuple[str, Any]], chunk_bytes: int):
    """(device group, host group) of the (leaf key, array) pairs, each
    as (plan, arrays), or None when the group is empty."""
    dev: List[Tuple[str, Any]] = []
    host: List[Tuple[str, Any]] = []
    for key, arr in items:
        (host if isinstance(arr, np.ndarray) else dev).append((key, arr))
    out = []
    for group in (dev, host):
        if not group:
            out.append(None)
            continue
        specs = tuple(
            (k, tuple(int(d) for d in a.shape), str(np.dtype(a.dtype)))
            for k, a in group)
        out.append((plan_leaves(specs, chunk_bytes), [a for _, a in group]))
    return out


def _assemble(groups: Sequence[Tuple[BatchPlan, List[np.ndarray]]]):
    """(plan, per-bucket digest mats) groups -> (chunk keys, (C, 4) digest
    matrix, leaf key -> first row), in slot order."""
    keys: List[str] = []
    mats: List[np.ndarray] = []
    leaf_rows: Dict[str, int] = {}
    offset = 0
    for plan, digs in groups:
        plan_keys, plan_offsets = _plan_slots(plan)
        keys.extend(plan_keys)
        mats.extend(digs)
        for lkey, row in plan_offsets:
            leaf_rows[lkey] = offset + row
        offset += plan.n_chunks
    mat = (np.concatenate(mats, axis=0) if mats
           else np.zeros((0, 4), np.uint32))
    return keys, mat, leaf_rows


def _bucket_rows(plan: BatchPlan, fetched: Sequence[Any],
                 dtype) -> List[np.ndarray]:
    """Per-bucket fetched outputs without their padding rows."""
    return [np.asarray(h, dtype)[:b.n_rows]
            for b, h in zip(plan.buckets, fetched)]


def _dispatch_device(plan: BatchPlan, arrays: Sequence[Any], *, seed: int,
                     use_kernel: bool) -> List[Any]:
    packed = _packer_for(plan)(*arrays)
    lengths = _plan_lengths(plan)
    digs = []
    for b, words, lens in zip(plan.buckets, packed, lengths):
        if use_kernel:
            d = fingerprint_words(words, jnp.asarray(lens), seed=seed,
                                  tile=b.tile, rows=b.block_rows)
        else:
            d = fingerprint_words_ref(words, jnp.asarray(lens), seed=seed)
        digs.append(d)
    return digs


def _digest_host(plan: BatchPlan, arrays: Sequence[np.ndarray], *,
                 seed: int) -> List[np.ndarray]:
    lengths = _plan_lengths(plan)
    by_bucket: Dict[int, List[Tuple[int, np.ndarray]]] = {
        b.width: [] for b in plan.buckets}
    for slot, arr in zip(plan.leaves, arrays):
        by_bucket[slot.bucket].append((slot.row0,
                                       _pack_leaf_words_np(slot, arr)))
    out = []
    for b, lens in zip(plan.buckets, lengths):
        mats = [m for _, m in sorted(by_bucket[b.width], key=lambda t: t[0])]
        words = np.concatenate(mats, axis=0) if len(mats) > 1 else mats[0]
        out.append(fingerprint_words_np(words, lens[:b.n_rows], seed=seed))
    return out


def dispatch_leaves(items: Sequence[Tuple[str, Any]], *, chunk_bytes: int,
                    seed: int = 0, use_kernel: bool = True) -> PendingDigest:
    """Launch the digest of every chunk of the given (leaf key, array)
    pairs; the pending digest finishes as a `DigestResult`.

    Device (jax) leaves go through the bucketed Pallas path and cost one
    `jax.device_get` total, at `PendingDigest.fetch`; host (numpy) leaves
    go through the bucketed numpy twin here and cost zero.  Result rows
    are bucket-major with all device buckets first.
    """
    dev, host = _plan_groups(items, chunk_bytes)
    handles = None
    if dev is not None:
        handles = _dispatch_device(*dev, seed=seed, use_kernel=use_kernel)
    host_digs = _digest_host(*host, seed=seed) if host is not None else None

    def unpack(fetched) -> DigestResult:
        groups = []
        if dev is not None:
            groups.append((dev[0], _bucket_rows(dev[0], fetched, np.uint32)))
        if host is not None:
            groups.append((host[0], host_digs))
        keys, mat, leaf_rows = _assemble(groups)
        return DigestResult(keys=keys, mat=mat, n_syncs=int(dev is not None),
                            leaf_rows=leaf_rows)

    return PendingDigest(handles, unpack)


def digest_leaves(items: Sequence[Tuple[str, Any]], *, chunk_bytes: int,
                  seed: int = 0, use_kernel: bool = True) -> DigestResult:
    """`dispatch_leaves`, fetched and finished."""
    return dispatch_leaves(items, chunk_bytes=chunk_bytes, seed=seed,
                           use_kernel=use_kernel).result()


def _dispatch_device_fused(plan: BatchPlan, arrays: Sequence[Any], *,
                           seed: int, use_kernel: bool,
                           table: DeviceTable,
                           spec_rows: Dict[int, np.ndarray]):
    """Fused per-bucket digest+compare plus the speculated payload.

    `spec_rows` maps a bucket index to the sorted bucket rows whose
    payload to fetch.  Each leaf with speculated rows crosses whole or
    sparse, as `fetch_whole_leaf` decides.  The rows of sparse leaves are
    gathered from the packed bucket into one dense buffer — rows are the
    chunks' little-endian word streams, so a row's first true-length
    bytes ARE the payload — padded to a power-of-two row count
    (repeating the first row) by a gather compiled ahead for that count
    (`_gathers_for`), so a changing speculation set compiles nothing.

    Returns (device outputs for the ONE `jax.device_get`, unpack: fetched
    outputs -> (digest mats, dirty masks, {chunk key: payload bytes}, new
    DeviceTable)).
    """
    keys, _ = _plan_slots(plan)
    lengths = _plan_lengths(plan)
    base = np.cumsum([0] + [b.n_rows for b in plan.buckets])
    packed = list(_packer_for(plan)(*arrays))
    gather = _gathers_for(plan) if spec_rows else ()
    digs_dev: List[Any] = []
    masks_dev: List[Any] = []
    # (bucket index, gathered rows) and the gathered device buffers
    gathers: List[Tuple[int, np.ndarray]] = []
    gathered: List[Any] = []
    # (bucket index, slot, speculated chunk indices) and the device leaves
    whole: List[Tuple[int, LeafSlot, np.ndarray]] = []
    whole_dev: List[Any] = []
    for bi, b in enumerate(plan.buckets):
        # only the kernel and the gather below read the packed bucket:
        # holding no reference past them frees it as soon as they finish,
        # not after the fetch
        words, packed[bi] = packed[bi], None
        lens = jnp.asarray(lengths[bi])
        if use_kernel:
            d, m = fingerprint_words_cmp(words, lens, table.digs[bi],
                                         seed=seed, tile=b.tile,
                                         rows=b.block_rows)
        else:
            d, m = fingerprint_words_cmp_ref(words, lens, table.digs[bi],
                                             seed=seed)
        digs_dev.append(d)
        masks_dev.append(m)
        rows = spec_rows.get(bi)
        if rows is None:
            continue
        sparse: List[np.ndarray] = []
        for slot, arr in zip(plan.leaves, arrays):
            if slot.bucket != b.width:
                continue
            lo, hi = np.searchsorted(rows, [slot.row0,
                                            slot.row0 + slot.n_chunks])
            if hi == lo:
                continue
            if fetch_whole_leaf(int(hi - lo), slot.n_chunks):
                whole.append((bi, slot, rows[lo:hi] - slot.row0))
                whole_dev.append(arr)
            else:
                sparse.append(rows[lo:hi])
        if sparse:
            rows = np.concatenate(sparse)
            n = pow2ceil(len(rows))
            pad = np.full(n - len(rows), rows[0])
            # int32 on the host: a cast on the device would compile per size
            idx = jnp.asarray(np.concatenate([rows, pad]).astype(np.int32))
            gathers.append((bi, rows))
            gathered.append(gather[bi][n](words, idx))
    del words
    # padded digest rows stay on device as the next save's prev table:
    # every digested row is trusted once fetched.
    new_table = DeviceTable(
        plan=plan, digs=digs_dev,
        valid=[np.ones((b.n_rows,), bool) for b in plan.buckets])

    def unpack(host):
        digs, masks, gathered_host, whole_host = host
        payload: Dict[str, bytes] = {}
        for (bi, rows), fetched in zip(gathers, gathered_host):
            for i, r in enumerate(rows):
                payload[keys[base[bi] + r]] = \
                    fetched[i].tobytes()[:int(lengths[bi][r])]
        for (bi, slot, chunk_ids), leaf in zip(whole, whole_host):
            flat = np.asarray(leaf).reshape(-1)
            for ci in chunk_ids:
                lo = ci * slot.elems
                payload[keys[base[bi] + slot.row0 + ci]] = \
                    flat[lo:lo + slot.elems].tobytes()
        return (_bucket_rows(plan, digs, np.uint32),
                _bucket_rows(plan, masks, np.uint8), payload, new_table)

    return [digs_dev, masks_dev, gathered, whole_dev], unpack


def _spec_rows(plan: BatchPlan, spec_keys: set) -> Dict[int, np.ndarray]:
    """Speculated chunk keys -> sorted local rows per bucket index."""
    spec_rows: Dict[int, List[int]] = {}
    if spec_keys:
        row_of = {k: r for r, k in enumerate(_plan_slots(plan)[0])}
        base = np.cumsum([0] + [b.n_rows for b in plan.buckets])
        for k in spec_keys:
            r = row_of.get(k)
            if r is not None:
                bi = int(np.searchsorted(base, r, side="right")) - 1
                spec_rows.setdefault(bi, []).append(r - int(base[bi]))
    return {bi: np.asarray(sorted(r), np.int64)
            for bi, r in spec_rows.items()}


def dispatch_leaves_fused(items: Sequence[Tuple[str, Any]], *,
                          chunk_bytes: int, seed: int = 0,
                          use_kernel: bool = True,
                          table: Optional[DeviceTable] = None,
                          lookup=None,
                          spec_keys: Optional[set] = None) -> PendingDigest:
    """Launch the fused single-sync digest of the given (leaf key, array)
    pairs.

    Device leaves run the fused digest+compare kernel against the
    device-resident previous table (`table` when its plan matches this
    call's leaf specs, else re-seeded from `lookup`), with the packed
    rows of `spec_keys` chunks compacted into the same fetch — ONE
    blocking `jax.device_get` total, at `PendingDigest.fetch`.  Host
    leaves take the numpy twin here (dirty = -1: the caller's host
    compare decides).

    The pending digest finishes as (result, new device table to carry to
    the next save).
    """
    dev, host = _plan_groups(items, chunk_bytes)
    handles = unpack_dev = None
    if dev is not None:
        plan, arrays = dev
        if table is None or table.plan is not plan:
            # plan changed (or table imported/never built): re-seed from
            # the host table; rows it has never seen compare against
            # zeros and are forced dirty below.
            table = seed_device_table(
                plan, lookup if lookup is not None else lambda k: None)
        handles, unpack_dev = _dispatch_device_fused(
            plan, arrays, seed=seed, use_kernel=use_kernel, table=table,
            spec_rows=_spec_rows(plan, spec_keys or set()))
    host_digs = _digest_host(*host, seed=seed) if host is not None else None

    def unpack(fetched) -> Tuple[FusedDigestResult, Optional[DeviceTable]]:
        groups = []
        dirty_parts: List[np.ndarray] = []
        payload: Dict[str, bytes] = {}
        new_table = table              # preserved when no device leaves
        if dev is not None:
            dig_mats, masks, payload, new_table = unpack_dev(fetched)
            for bi, m in enumerate(masks):
                d = m.astype(np.int8)
                d[~table.valid[bi]] = 1      # no trusted prev: dirty
                dirty_parts.append(d)
            groups.append((dev[0], dig_mats))
        if host is not None:
            groups.append((host[0], host_digs))
            dirty_parts.append(np.full((host[0].n_chunks,), -1, np.int8))
        keys, mat, leaf_rows = _assemble(groups)
        dirty = (np.concatenate(dirty_parts) if dirty_parts
                 else np.zeros((0,), np.int8))
        res = FusedDigestResult(keys=keys, mat=mat,
                                n_syncs=int(dev is not None),
                                leaf_rows=leaf_rows, dirty=dirty,
                                payload=payload)
        return res, new_table

    return PendingDigest(handles, unpack)


def digest_leaves_fused(items: Sequence[Tuple[str, Any]], *,
                        chunk_bytes: int, seed: int = 0,
                        use_kernel: bool = True,
                        table: Optional[DeviceTable] = None,
                        lookup=None,
                        spec_keys: Optional[set] = None
                        ) -> Tuple[FusedDigestResult,
                                   Optional[DeviceTable]]:
    """`dispatch_leaves_fused`, fetched and finished: (result, new device
    table to carry to the next save)."""
    return dispatch_leaves_fused(
        items, chunk_bytes=chunk_bytes, seed=seed, use_kernel=use_kernel,
        table=table, lookup=lookup, spec_keys=spec_keys).result()


def tree_fingerprint_batched(graph: ObjectGraph, *, active_leaf_paths=None,
                             chunk_bytes: int = 1 << 22, seed: int = 0,
                             use_kernel: bool = True
                             ) -> Tuple[Dict[str, bytes], int]:
    """Batched drop-in for `ops.tree_fingerprint`: {chunk key: 16-byte
    digest} for every (active) leaf, plus the number of device syncs paid
    (≤ 1)."""
    items = []
    for leaf in graph.leaf_nodes():
        if active_leaf_paths is not None and leaf.key not in active_leaf_paths:
            continue
        items.append((leaf.key, graph.arrays[leaf.key]))
    res = digest_leaves(items, chunk_bytes=chunk_bytes, seed=seed,
                        use_kernel=use_kernel)
    buf = res.mat.tobytes()
    out = {k: buf[16 * i:16 * (i + 1)] for i, k in enumerate(res.keys)}
    return out, res.n_syncs
