"""Batch planner properties + batched-vs-per-leaf digest parity.

The planner must be a partition: every chunk of every leaf lands in
exactly one (bucket, row) slot, widths are powers of two that fit the
chunk, and true byte lengths survive packing.  The batched engine must be
bit-identical to the per-leaf oracle (`leaf_fingerprint` /
`leaf_fingerprint_np`) across mixed dtypes and ragged sizes, and must pay
at most one device sync per save.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.graph import build_graph, chunk_grid
from repro.kernels.batch import (MIN_BUCKET_WORDS, digest_leaves,
                                 plan_leaves, pow2ceil,
                                 tree_fingerprint_batched)
from repro.kernels.ops import (digest_to_bytes, leaf_fingerprint,
                               leaf_fingerprint_np, tree_fingerprint)

from proptest import given, integers, sampled_from

DTYPES = ["float32", "bfloat16", "int8", "bool", "float16", "int32"]


def _rand_leaf(rng, rows, cols, dt):
    x = rng.standard_normal((rows, cols))
    if dt == "bool":
        return x > 0
    if dt == "bfloat16":
        return np.asarray(jnp.asarray(x, jnp.bfloat16))
    if dt in ("int8", "int32"):
        return (x * 50).astype(dt)
    return x.astype(dt)


@given(n_leaves=integers(1, 6), seed=integers(0, 10_000),
       chunk=sampled_from([64, 256, 1024, 4096]))
def test_plan_partitions_every_chunk(n_leaves, seed, chunk):
    rng = np.random.default_rng(seed)
    specs = []
    expected = {}
    for i in range(n_leaves):
        dt = DTYPES[int(rng.integers(0, len(DTYPES)))]
        shape = (int(rng.integers(1, 300)), int(rng.integers(1, 9)))
        specs.append((f"l{i}", shape, dt))
        _, n_chunks = chunk_grid(shape, np.dtype(dt), chunk)
        expected[f"l{i}"] = n_chunks
    plan = plan_leaves(tuple(specs), chunk)

    # every chunk in exactly one slot; rows within a bucket are disjoint
    seen = {}
    for s in plan.leaves:
        assert s.n_chunks == expected[s.key]
        # width is the smallest allowed power of two that fits the chunk
        assert s.bucket == max(MIN_BUCKET_WORDS, pow2ceil(s.words_per_chunk))
        assert s.bucket & (s.bucket - 1) == 0
        for ci in range(s.n_chunks):
            slot = (s.bucket, s.row0 + ci)
            assert slot not in seen, f"slot collision: {slot}"
            seen[slot] = f"{s.key}#[{ci}]"
    assert len(seen) == sum(expected.values()) == plan.n_chunks
    # bucket row counts cover exactly the assigned slots
    for b in plan.buckets:
        rows = {r for (w, r) in seen if w == b.width}
        assert rows == set(range(b.n_rows))
        # rows pad to the next multiple of the block, not a power of two
        assert b.padded_rows % b.block_rows == 0
        assert 0 <= b.padded_rows - b.n_rows < b.block_rows

    # true byte lengths preserved: sum of folded lengths == payload bytes
    from repro.kernels.batch import _plan_lengths
    total = sum(int(lens.sum()) for lens in _plan_lengths(plan))
    expected_bytes = sum(
        (int(np.prod(sh, dtype=np.int64)) if sh else 1) * np.dtype(dt).itemsize
        for _, sh, dt in specs)
    assert total == expected_bytes


@given(rows=integers(1, 400), cols=integers(1, 9),
       dt=sampled_from(DTYPES), chunk=sampled_from([64, 1024, 1 << 20]))
def test_batched_matches_per_leaf_oracle_np(rows, cols, dt, chunk):
    rng = np.random.default_rng(rows * 131 + cols)
    x = _rand_leaf(rng, rows, cols, dt)
    res = digest_leaves([("x", x)], chunk_bytes=chunk, seed=7)
    oracle = leaf_fingerprint_np(x, chunk_bytes=chunk, seed=7)
    assert res.n_syncs == 0           # pure-host leaves: no device traffic
    assert res.mat.shape == oracle.shape
    assert (res.mat == oracle).all()
    assert res.keys == [f"x#[{ci}]" for ci in range(oracle.shape[0])]


@pytest.mark.parametrize("dt", ["float32", "bfloat16", "int8", "bool"])
def test_batched_matches_per_leaf_oracle_device(dt):
    rng = np.random.default_rng(hash(dt) & 0xFFFF)
    arrs = [jnp.asarray(_rand_leaf(rng, r, c, dt))
            for r, c in [(57, 3), (300, 8), (1, 1)]]
    items = [(f"l{i}", a) for i, a in enumerate(arrs)]
    res = digest_leaves(items, chunk_bytes=512, seed=5)
    assert res.n_syncs == 1           # single end-of-save digest fetch
    for i, a in enumerate(arrs):
        oracle = leaf_fingerprint(a, chunk_bytes=512, seed=5)
        r0 = res.leaf_rows[f"l{i}"]
        got = res.mat[r0:r0 + oracle.shape[0]]
        assert (got == oracle).all()


def test_mixed_device_host_tree_parity():
    rng = np.random.default_rng(0)
    state = {
        "emb": rng.standard_normal((500, 16)).astype(np.float32),
        "w": jnp.asarray(rng.standard_normal((64, 64)), jnp.bfloat16),
        "flags": rng.standard_normal(33) > 0,
        "q": jnp.asarray(rng.integers(-100, 100, size=(777,)), jnp.int8),
        "s": np.float32(1.25),
    }
    g = build_graph(state, chunk_bytes=1 << 10)
    ref = tree_fingerprint(g, chunk_bytes=1 << 10, seed=3)
    got, n_syncs = tree_fingerprint_batched(g, chunk_bytes=1 << 10, seed=3)
    assert n_syncs == 1
    assert got == ref


def test_bucket_shapes_stable_across_saves():
    """Same leaf specs → the same plan object (lru-cached), so jit'd
    packers and kernel shapes are reused save-over-save."""
    specs = (("a", (128, 4), "float32"), ("b", (9, 9), "bfloat16"))
    assert plan_leaves(specs, 1 << 10) is plan_leaves(specs, 1 << 10)


@pytest.mark.parametrize("n_rows", [1, 3, 64, 65, 17695])
def test_padded_rows_follow_block_rows(n_rows):
    """A bucket pads its rows to the next multiple of its row block (not
    to a power of two), and the device-resident digest table takes that
    padded shape."""
    from repro.kernels.batch import seed_device_table
    plan = plan_leaves((("w", (n_rows, 128), "float32"),), 512)
    (b,) = plan.buckets
    assert b.n_rows == n_rows
    assert b.block_rows == min(64, pow2ceil(n_rows))
    assert b.padded_rows == -(-n_rows // b.block_rows) * b.block_rows
    table = seed_device_table(plan, lambda key: None)
    assert table.digs[0].shape == (b.padded_rows, 4)
    assert table.valid[0].shape == (n_rows,)


def test_fused_table_keeps_padded_shape():
    from repro.kernels.batch import digest_leaves_fused
    items = [("w", jnp.ones((65, 128), jnp.float32))]
    res, table = digest_leaves_fused(items, chunk_bytes=512)
    (b,) = table.plan.buckets
    assert (b.n_rows, b.padded_rows) == (65, 128)
    assert table.digs[0].shape == (128, 4)
    assert res.mat.shape == (65, 4) and (res.dirty == 1).all()


@pytest.mark.parametrize("shape,dt", [((1000, 8), "float32"),
                                      ((1000, 16), "bfloat16"),
                                      ((700, 4, 8), "int8"),
                                      ((5000,), "float32")])
def test_packer_pieces_match_host_twin(shape, dt):
    """Leaves cut into row pieces (more than PACK_PIECE_CHUNKS chunks of
    whole rows, last chunk partial) pack to the host twin's exact words."""
    from repro.kernels.batch import (PACK_PIECE_CHUNKS, _pack_leaf_words_np,
                                     _packer_for)
    rng = np.random.default_rng(len(shape))
    host = _rand_leaf(rng, int(np.prod(shape[:-1])), shape[-1], dt)
    host = host.reshape(shape)
    plan = plan_leaves((("x", shape, dt), ("y", (5, 3), "float32")), 512)
    slot = plan.leaves[0]
    assert slot.n_chunks > PACK_PIECE_CHUNKS
    packed = _packer_for(plan)(jnp.asarray(host),
                               jnp.ones((5, 3), jnp.float32))
    bi = [b.width for b in plan.buckets].index(slot.bucket)
    got = np.asarray(packed[bi])[slot.row0:slot.row0 + slot.n_chunks]
    assert np.array_equal(got, _pack_leaf_words_np(slot, host))


def test_detector_single_sync_per_save():
    from repro.core.change_detector import ChangeDetector
    rng = np.random.default_rng(4)
    state = {f"l{i}": jnp.asarray(rng.standard_normal((100, 8)), jnp.float32)
             for i in range(5)}
    cd = ChangeDetector(chunk_bytes=1 << 10)
    r = cd.detect(build_graph(state, chunk_bytes=1 << 10))
    assert r.n_syncs == 1             # 5 device leaves, ONE digest fetch
    r2 = cd.detect(build_graph(state, chunk_bytes=1 << 10))
    assert r2.n_syncs == 1 and not r2.dirty
