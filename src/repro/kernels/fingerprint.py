"""Pallas TPU kernel: chunk fingerprint digest at HBM bandwidth.

The paper's change detector hashes pod bytes with xxhash on the host CPU
(§4.2).  On a TPU fleet that design would force every byte of training
state across the device→host link each save.  The TPU-native adaptation
computes the 128-bit digest *on device*:

  * the word stream of each chunk is tiled into (rows, TILE) uint32 VMEM
    blocks (TILE = 4096 words = 16 KiB; last-dim multiple of 128 lanes),
  * per block, four weighted sums are accumulated on the VPU (integer
    multiply-add only; no MXU) — arithmetic intensity ≈ 1 op/byte, so the
    kernel is memory-bound by construction: its ceiling is the HBM peak
    (published for v5e as 819 GB/s; not a measured rate),
  * only 16 bytes per chunk leave the device; clean chunks never move.

The kernel is *row-blocked*: a grid cell digests `rows` chunks at once
(each digest lane is a per-row weighted reduction over the tile), so the
grid of a batched (C, W) bucket is (C / rows, W / TILE) instead of
(C, W / TILE).  Grid-cell dispatch is the dominant overhead both in
interpret mode and for small chunks on hardware (a 2048-word chunk is a
single 8 KiB DMA; blocking 64 of them turns it into a 512 KiB DMA), so
the batched planner in batch.py always calls with rows > 1.

The digest spec (and the oracle) live in ref.py; weighted sums are
order-independent, so the sequential TPU grid can accumulate partial tile
sums into the (rows, 4) output block, which is revisited across the inner
grid dimension.

The *fused* variant (`fingerprint_words_cmp`) additionally takes the
previous save's digest block as an input and emits a per-row dirty flag
alongside the digests: at the final inner grid step — when the (rows, 4)
accumulator holds the complete digest — each row is compared against its
previous digest and the (rows, 1) dirty block is written.  That moves the
change *compare* on-device, so the host never needs the previous table to
decide dirtiness (the single-sync save contract in batch.py/ops.py).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ref import DIGEST_WORDS, LANE_PRIMES, PHI32, STREAM_SALT, mix32

TILE = 4096  # uint32 words per VMEM block (16 KiB); multiple of 128 lanes
#: chunk rows digested per grid cell (block row count, power of two)
MAX_BLOCK_ROWS = 64


def pow2ceil(n: int) -> int:
    return 1 << max(0, int(n - 1).bit_length())


def block_rows(n_rows: int) -> int:
    """Rows per grid cell for a matrix of `n_rows` chunk rows.

    Rows are padded up to a multiple of the block, so a block of fewer
    than 8 rows always spans the whole row dimension — the only case in
    which the TPU's (8, 128) tiling rule admits it."""
    return min(MAX_BLOCK_ROWS, pow2ceil(n_rows))


def pallas_interpret() -> bool:
    """Pallas mode for the default backend: compiled on a TPU, the
    interpreter on the CPU.  Any other backend has no kernel."""
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"the fingerprint kernel runs on 'tpu' (compiled) or 'cpu' "
        f"(interpreted), not on {platform!r}")


def _sum_u32(x):
    """Wrap-around uint32 sum along axis 1.  The TPU compiler reduces no
    unsigned integers, so the sum runs in int32: two's-complement
    addition is the same mod 2^32."""
    s = jnp.sum(jax.lax.bitcast_convert_type(x, jnp.int32), axis=1,
                dtype=jnp.int32)
    return jax.lax.bitcast_convert_type(s, jnp.uint32)


def _fingerprint_kernel(words_ref, lengths_ref, out_ref, *, seed: int,
                        tile: int):
    """Grid = (C // rows, W // tile).  Block shapes: words (rows, tile),
    lengths (rows, 1), out (rows, DIGEST_WORDS) revisited along the inner
    grid dim."""
    j = pl.program_id(1)
    base = (j * tile).astype(jnp.uint32)
    pos = base + jax.lax.broadcasted_iota(jnp.uint32, (1, tile), 1)
    x = words_ref[...].astype(jnp.uint32)          # (rows, tile)

    partial = []
    for d in range(DIGEST_WORDS):
        w = mix32(pos * jnp.uint32(LANE_PRIMES[d]) + jnp.uint32(seed)
                  + jnp.uint32((d * STREAM_SALT) & 0xFFFFFFFF))
        partial.append(_sum_u32(x * w))
    part = jnp.stack(partial, axis=1)              # (rows, DIGEST_WORDS)

    @pl.when(j == 0)
    def _init():
        length = lengths_ref[...].astype(jnp.uint32)[:, 0]   # (rows,)
        folds = []
        for d in range(DIGEST_WORDS):
            folds.append(mix32(length ^ jnp.uint32(((d + 1) * PHI32) & 0xFFFFFFFF))
                         + jnp.uint32(seed))
        out_ref[...] = jnp.stack(folds, axis=1)

    out_ref[...] += part


def _fingerprint_cmp_kernel(words_ref, lengths_ref, prev_ref, out_ref,
                            dirty_ref, *, seed: int, tile: int):
    """Fused digest + compare.  Same grid/blocks as `_fingerprint_kernel`
    plus a prev-digest input block (rows, DIGEST_WORDS) and a dirty output
    block (rows, 1), both revisited along the inner grid dim.  The dirty
    flag is written once, at the final inner step, when the accumulator
    holds the full digest."""
    _fingerprint_kernel(words_ref, lengths_ref, out_ref, seed=seed,
                        tile=tile)

    @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
    def _compare():
        diff = (out_ref[...] != prev_ref[...]).astype(jnp.int32)
        dirty_ref[...] = jnp.max(diff, axis=1,
                                 keepdims=True).astype(jnp.uint32)


def _pad_grid(words, lengths, tile, rows):
    """Pad (C, W) words to the (rows, tile) grid; returns padded arrays
    plus the original C (padding rows are digest-garbage, sliced off)."""
    words = jnp.asarray(words, jnp.uint32)
    C, W = words.shape
    Wp = max(tile, -(-W // tile) * tile)
    Cp = max(rows, -(-C // rows) * rows)
    if Wp != W or Cp != C:
        words = jnp.pad(words, ((0, Cp - C), (0, Wp - W)))
    lengths2d = jnp.asarray(lengths, jnp.uint32).reshape(C, 1)
    if Cp != C:
        lengths2d = jnp.pad(lengths2d, ((0, Cp - C), (0, 0)))
    return words, lengths2d, C, Cp, Wp


@functools.partial(jax.jit,
                   static_argnames=("seed", "interpret", "tile", "rows"))
def fingerprint_words_cmp(words, lengths, prev, *, seed: int = 0,
                          interpret: Optional[bool] = None,
                          tile: int = TILE, rows: Optional[int] = None):
    """Fused digest-and-compare: uint32 words (C, W) + previous digests
    (C, 4) -> (digests uint32 (C, 4), dirty uint32 (C,)).

    dirty[c] == 1 iff digest[c] differs from prev[c] in any lane.  Rows
    whose previous digest is unknown must be forced dirty by the caller
    (the kernel compares against whatever sentinel was supplied).
    `interpret` defaults to the platform's mode (`pallas_interpret`),
    `rows` to `block_rows(C)`.
    """
    if interpret is None:
        interpret = pallas_interpret()
    if rows is None:
        rows = block_rows(words.shape[0])
    words, lengths2d, C, Cp, Wp = _pad_grid(words, lengths, tile, rows)
    prev = jnp.asarray(prev, jnp.uint32)
    if Cp != C:
        prev = jnp.pad(prev, ((0, Cp - C), (0, 0)))

    grid = (Cp // rows, Wp // tile)
    out, dirty = pl.pallas_call(
        functools.partial(_fingerprint_cmp_kernel, seed=seed, tile=tile),
        grid=grid,
        in_specs=[
            pl.BlockSpec((rows, tile), lambda i, j: (i, j)),
            pl.BlockSpec((rows, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((rows, DIGEST_WORDS), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((rows, DIGEST_WORDS), lambda i, j: (i, 0)),
            pl.BlockSpec((rows, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Cp, DIGEST_WORDS), jnp.uint32),
            jax.ShapeDtypeStruct((Cp, 1), jnp.uint32),
        ],
        interpret=interpret,
    )(words, lengths2d, prev)
    return out[:C], dirty[:C, 0]


@functools.partial(jax.jit,
                   static_argnames=("seed", "interpret", "tile", "rows"))
def fingerprint_words(words, lengths, *, seed: int = 0,
                      interpret: Optional[bool] = None, tile: int = TILE,
                      rows: Optional[int] = None) -> jnp.ndarray:
    """Digest uint32 words (C, W) -> uint32 (C, 4) via the Pallas kernel.

    W is padded to a multiple of `tile` and C to a multiple of `rows`
    (zero words are digest-neutral; true byte lengths are folded
    separately — see ref.py; padding rows are sliced off the output).
    `rows` chunks share one grid cell — the batched planner uses this to
    amortize dispatch across every chunk of every leaf in a bucket.
    `interpret` defaults to the platform's mode (`pallas_interpret`),
    `rows` to `block_rows(C)`.
    """
    if interpret is None:
        interpret = pallas_interpret()
    if rows is None:
        rows = block_rows(words.shape[0])
    words, lengths2d, C, Cp, Wp = _pad_grid(words, lengths, tile, rows)

    grid = (Cp // rows, Wp // tile)
    out = pl.pallas_call(
        functools.partial(_fingerprint_kernel, seed=seed, tile=tile),
        grid=grid,
        in_specs=[
            pl.BlockSpec((rows, tile), lambda i, j: (i, j)),
            pl.BlockSpec((rows, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((rows, DIGEST_WORDS), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Cp, DIGEST_WORDS), jnp.uint32),
        interpret=interpret,
    )(words, lengths2d)
    return out[:C]
