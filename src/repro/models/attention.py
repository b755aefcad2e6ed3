"""Attention: grouped-query (GQA/MQA), causal / sliding-window, with a
memory-bounded q-chunked path for long prefill, plus single-token decode
attention over (optionally ring-buffered) KV caches.

Layout conventions:
    q        (B, S, Hq,  D)
    k, v     (B, T, Hkv, D)      Hq = Hkv * G
Scores are computed grouped — KV heads are never materialized Hq-wide —
which keeps decode reads at the true KV-cache footprint.

The q-chunked path unrolls a *python* loop (static trip count) rather than
`lax.scan`, so `compiled.cost_analysis()` attributes the full FLOP count
(while-loop bodies are counted once by HLO cost analysis — an accounting
choice that matters for the roofline harness).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _grouped_scores(q: jax.Array, k: jax.Array) -> jax.Array:
    """(B,S,Hkv,G,D) x (B,T,Hkv,D) -> (B,Hkv,G,S,T)"""
    return jnp.einsum("bsngd,btnd->bngst", q, k)


def _mask_bias(q_pos: jax.Array, k_pos: jax.Array, *, causal: bool,
               window: Optional[int]) -> jax.Array:
    """(S, T) additive bias: 0 allowed / NEG_INF masked."""
    diff = q_pos[:, None] - k_pos[None, :]
    ok = jnp.ones(diff.shape, jnp.bool_)
    if causal:
        ok &= diff >= 0
    if window is not None:
        ok &= diff < window
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True, window: Optional[int] = None,
              q_chunk: Optional[int] = None,
              q_offset: int = 0, mixed: bool = False,
              scale: Optional[float] = None) -> jax.Array:
    """Full (or q-chunked) grouped attention.

    q, k: (B, S|T, Hq|Hkv, D); v: (B, T, Hkv, Dv).  Returns (B, S, Hq,
    Dv).  `scale` multiplies the scores (default D ** -0.5).
    `q_offset` positions the queries within the key timeline (prefill
    continuation).  `q_chunk` bounds the per-step score materialization to
    (B, Hq, q_chunk, T) — the long-context memory lever.  `mixed=True`
    keeps Q/K operands bf16 with f32 accumulation (MXU-native), which
    makes the backward dK/dV (all-reduced under replicated-KV sharding)
    bf16 — half the wire bytes of the f32-cast baseline.
    """
    B, S, Hq, D = q.shape
    _, T, Hkv, _ = k.shape
    G = Hq // Hkv
    Dv = v.shape[-1]
    qg = q.reshape(B, S, Hkv, G, D)
    scale = D ** -0.5 if scale is None else scale
    k_pos = jnp.arange(T)

    def block(q_blk: jax.Array, offset: int) -> jax.Array:
        s = q_blk.shape[1]
        if mixed:
            scores = jnp.einsum("bsngd,btnd->bngst",
                                q_blk * jnp.asarray(scale, q_blk.dtype), k,
                                preferred_element_type=jnp.float32)
        else:
            scores = _grouped_scores(q_blk.astype(jnp.float32) * scale,
                                     k.astype(jnp.float32))
        q_pos = jnp.arange(s) + (q_offset + offset)
        bias = _mask_bias(q_pos, k_pos, causal=causal, window=window)
        scores = scores + bias[None, None, None, :, :]
        w = jax.nn.softmax(scores, axis=-1)
        o = jnp.einsum("bngst,btnd->bsngd", w.astype(v.dtype), v)
        return o.reshape(B, s, Hq, Dv)

    if q_chunk is None or S <= q_chunk:
        return block(qg, 0)

    # python-unrolled q chunks (uneven tail allowed): static trip count,
    # exact HLO cost accounting, bounded (B,Hq,chunk,T) score buffers
    from ..parallel.sharding import constrain  # late import: optional mesh
    outs = []
    off = 0
    while off < S:
        size = min(q_chunk, S - off)
        blk = jax.lax.dynamic_slice_in_dim(qg, off, size, axis=1)
        # re-pin sequence-parallel sharding on the chunk (the slice loses
        # the constraint and GSPMD may otherwise pick a head split that
        # forces involuntary full rematerialization)
        blk = constrain(blk, ("batch", "seq_model", None, None, None))
        outs.append(block(blk, off))
        off += size
    return jnp.concatenate(outs, axis=1)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     lengths: jax.Array, *,
                     window: Optional[int] = None,
                     scale: Optional[float] = None) -> jax.Array:
    """One-token attention over a KV cache.

    q: (B, 1, Hq, D); caches: (B, T, Hkv, D); lengths: (B,) valid entries.
    For ring-buffered sliding-window caches, T == window and `lengths`
    saturates at T (positions are implicit — softmax is order-invariant
    given causal validity, so ring rotation needs no unrotation here;
    decode RoPE is applied before insertion).
    """
    B, _, Hq, D = q.shape
    _, T, Hkv, _ = k_cache.shape
    G = Hq // Hkv
    qg = q.reshape(B, Hkv, G, D)
    scale = D ** -0.5 if scale is None else scale
    scores = jnp.einsum("bngd,btnd->bngt", qg.astype(jnp.float32) * scale,
                        k_cache.astype(jnp.float32))
    idx = jnp.arange(T)[None, :]                       # (1, T)
    valid = idx < lengths[:, None]                     # (B, T)
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bngt,btnd->bngd", w.astype(v_cache.dtype), v_cache)
    return o.reshape(B, 1, Hq, v_cache.shape[-1])
