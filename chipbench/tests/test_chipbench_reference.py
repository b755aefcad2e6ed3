"""The plain reference agrees with the store's formats, and its
checksums see every change."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import reference

SHAPES = [(), (1,), (7,), (3, 5), (130, 17), (4, 16, 2, 32), (1000,)]
DTYPES = [np.float32, jnp.bfloat16, np.int8, np.int32, np.float64, np.bool_,
          np.uint16]


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("chunk_bytes", [4, 6, 64, 1000, 1 << 18])
def test_chunk_grid_matches_store(dtype, chunk_bytes):
    from repro.core.graph import chunk_grid
    for shape in SHAPES:
        assert reference.chunk_grid(shape, np.dtype(dtype), chunk_bytes) == \
            chunk_grid(shape, np.dtype(dtype), chunk_bytes)


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16, np.int8,
                                   np.float64])
@pytest.mark.parametrize("chunk_bytes", [64, 256, 4096])
def test_numpy_digest_twin_matches_store(dtype, chunk_bytes):
    from repro.kernels.ops import leaf_fingerprint_np
    rng = np.random.default_rng(0)
    for shape in [(1,), (3, 5), (130, 17), (64, 64)]:
        a = (rng.standard_normal(shape) * 100).astype(dtype)
        for seed in (0, 5):
            np.testing.assert_array_equal(
                reference.fingerprint_chunks_np(a, chunk_bytes, seed=seed),
                leaf_fingerprint_np(a, chunk_bytes=chunk_bytes, seed=seed))


def test_flatten_matches_store_keys():
    from repro.core.graph import build_graph
    tree = {"b": {"x": jnp.ones(3), "y": [jnp.zeros(2), 5]}, "a": 1.5,
            "c": (jnp.ones((2, 2)),)}
    g = build_graph(tree, chunk_bytes=8)
    assert set(reference.array_leaves(tree)) == set(g.arrays)
    assert list(reference.flatten(tree)) == ["b/x", "b/y/0", "b/y/1", "a",
                                             "c/0"]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int8,
                                   jnp.int32])
def test_checksums_see_every_single_change(dtype):
    x = jnp.asarray(np.random.default_rng(1).integers(0, 50, (37, 9)), dtype)
    chunk_bytes = 40
    base = np.asarray(reference.chunk_checksums(x, chunk_bytes))
    elems, n = reference.chunk_grid(x.shape, x.dtype, chunk_bytes)
    assert base.shape == (n, 2)
    flat = x.reshape(-1)
    for i in range(0, flat.shape[0], 7):
        y = flat.at[i].set(flat[i] + 1).reshape(x.shape)
        got = np.asarray(reference.chunk_checksums(y, chunk_bytes))
        changed = np.nonzero((got != base).any(axis=1))[0].tolist()
        assert changed == [i // elems]


def test_tree_checksums_equal_leaf_checksums():
    leaves = {"a": jnp.arange(100, dtype=jnp.float32),
              "b": jnp.ones((5, 7), jnp.bfloat16)}
    tree = reference.tree_chunk_checksums(leaves, 64)
    for k, v in leaves.items():
        np.testing.assert_array_equal(
            np.asarray(tree[k]), np.asarray(reference.chunk_checksums(v, 64)))


def test_unpack_digest_table_matches_store():
    from repro.core.change_detector import pack_digest_table
    table = {f"a/b#[{i}]": bytes([i]) * 16 for i in range(3)}
    table["c#[0]"] = b"\x07" * 16
    assert reference.unpack_digest_table(pack_digest_table(table)) == table
