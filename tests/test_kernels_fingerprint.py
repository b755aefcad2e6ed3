"""Pallas fingerprint kernel: shape/dtype sweeps vs the pure-jnp oracle
(exact integer equality), numpy twin parity, sensitivity, chunk-grid
consistency with the ObjectGraph."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.graph import chunk_grid
from repro.kernels.fingerprint import fingerprint_words
from repro.kernels.ops import (leaf_fingerprint, leaf_fingerprint_np,
                               to_words, to_words_np)
from repro.kernels.ref import (fingerprint_words_np, fingerprint_words_ref,
                               mix32, mix32_np)

from proptest import given, integers, sampled_from

DTYPES = ["float32", "float16", "bfloat16", "int32", "int8", "uint8",
          "bool"]


def test_mix32_matches_numpy():
    xs = np.arange(0, 2**32, 2**27, dtype=np.uint32)
    a = np.asarray(mix32(jnp.asarray(xs)))
    b = mix32_np(xs)
    assert (a == b).all()


@pytest.mark.parametrize("C,W", [(1, 1), (1, 4096), (3, 4096), (2, 5000),
                                 (7, 1), (1, 9000)])
def test_kernel_matches_oracle(C, W):
    rng = np.random.default_rng(C * 31 + W)
    words = rng.integers(0, 2**32, size=(C, W), dtype=np.uint32)
    lens = rng.integers(1, W * 4 + 1, size=(C,)).astype(np.uint32)
    a = np.asarray(fingerprint_words(jnp.asarray(words), jnp.asarray(lens),
                                     seed=5, interpret=True))
    b = np.asarray(fingerprint_words_ref(jnp.asarray(words),
                                         jnp.asarray(lens), seed=5))
    c = fingerprint_words_np(words, lens, seed=5)
    assert (a == b).all() and (b == c).all()


@pytest.mark.parametrize("dt", DTYPES)
def test_words_conversion_device_host_parity(dt):
    rng = np.random.default_rng(hash(dt) & 0xFFFF)
    x = rng.standard_normal((37, 19))
    if dt == "bool":
        x = x > 0
    elif dt == "bfloat16":
        x = jnp.asarray(x, jnp.bfloat16)
        w1 = np.asarray(to_words(x))
        w2 = to_words_np(np.asarray(x))
        assert (w1 == w2).all()
        return
    else:
        x = x.astype(dt)
    w1 = np.asarray(to_words(jnp.asarray(x)))
    w2 = to_words_np(x)
    assert (w1 == w2).all()


@pytest.mark.parametrize("n", [1, 3, 255, 257, 1001])
@pytest.mark.parametrize("dt", ["bool", "int8", "uint8", "bfloat16",
                                "float16", "float32", "float64"])
def test_lane_dense_words_bit_identical(dt, n):
    """The lane-dense device packing equals the host twin byte for byte
    at odd lengths and at lengths that are not multiples of 256."""
    rng = np.random.default_rng(n)
    x = rng.standard_normal(n) * 1000
    x = x > 0 if dt == "bool" else x.astype(
        jnp.bfloat16 if dt == "bfloat16" else dt)
    with jax.enable_x64(dt == "float64"):
        dev = jnp.asarray(x)
        assert dev.dtype == x.dtype
        got = np.asarray(to_words(dev))
    want = to_words_np(x)
    assert got.dtype == np.uint32 and got.shape == want.shape
    assert (got == want).all()


@pytest.mark.slow
@given(rows=integers(1, 700), cols=integers(1, 9),
       dt=sampled_from(["float32", "float16", "int8"]),
       chunk=sampled_from([64, 256, 4096, 1 << 20]))
def test_leaf_fingerprint_device_host_parity(rows, cols, dt, chunk):
    rng = np.random.default_rng(rows * 31 + cols)
    x = rng.standard_normal((rows, cols)).astype(dt)
    d_dev = leaf_fingerprint(jnp.asarray(x), chunk_bytes=chunk, seed=3)
    d_host = leaf_fingerprint_np(x, chunk_bytes=chunk, seed=3)
    assert d_dev.shape == d_host.shape
    assert (d_dev == d_host).all()
    r, n = chunk_grid(x.shape, np.dtype(dt), chunk)
    assert d_dev.shape == (n, 4)


def test_sensitivity_single_chunk_changes():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((1024, 32)).astype(np.float32)
    d0 = leaf_fingerprint_np(x, chunk_bytes=1 << 14)
    x2 = x.copy()
    x2[777, 3] = 42.0
    d1 = leaf_fingerprint_np(x2, chunk_bytes=1 << 14)
    e, n = chunk_grid(x.shape, np.dtype(np.float32), 1 << 14)
    diff = (d0 != d1).any(axis=1)
    assert diff.sum() == 1
    assert diff[(777 * 32 + 3) // e]


def test_position_sensitivity():
    """Swapping two words must change the digest (weighted, not plain sum)."""
    w = np.zeros((1, 8), np.uint32)
    w[0, 0], w[0, 1] = 1, 2
    w2 = np.zeros((1, 8), np.uint32)
    w2[0, 0], w2[0, 1] = 2, 1
    lens = np.asarray([32], np.uint32)
    assert (fingerprint_words_np(w, lens) != fingerprint_words_np(w2, lens)).any()


def test_length_fold_distinguishes_padding():
    """Trailing-zero content vs shorter content: digests differ via length."""
    w = np.zeros((2, 4), np.uint32)
    w[:, 0] = 7
    lens = np.asarray([16, 8], np.uint32)   # same words, different true length
    d = fingerprint_words_np(w, lens)
    assert (d[0] != d[1]).any()


def test_seed_changes_digest():
    w = np.arange(16, dtype=np.uint32).reshape(1, 16)
    lens = np.asarray([64], np.uint32)
    assert (fingerprint_words_np(w, lens, seed=0)
            != fingerprint_words_np(w, lens, seed=1)).any()


def test_zero_d_and_scalar_arrays():
    d1 = leaf_fingerprint(jnp.float32(3.5), chunk_bytes=64)
    d2 = leaf_fingerprint_np(np.float32(3.5), chunk_bytes=64)
    assert (d1 == d2).all() and d1.shape == (1, 4)


def test_collision_smoke():
    """1k random 64-byte chunks → no digest collisions (128-bit space)."""
    rng = np.random.default_rng(1)
    words = rng.integers(0, 2**32, size=(1000, 16), dtype=np.uint32)
    lens = np.full((1000,), 64, np.uint32)
    d = fingerprint_words_np(words, lens)
    keys = {d[i].tobytes() for i in range(1000)}
    assert len(keys) == 1000
