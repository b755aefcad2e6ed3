"""Mixture-of-Experts FFN, two ways.

`moe_ffn`: top-k routing with capacity-factor dispatch (GShard-style
dense einsum formulation) + optional shared expert; it computes every
expert and drops tokens over capacity (granite, kimi).

`routed_experts`: DeepSeekMoE routing over all experts, computed for the
experts this chip holds only, with no capacity and no drop: the routed
rows are sorted by expert and run through grouped matmuls
(`jax.lax.ragged_dot`), sized for the most rows a token can send.

Expert weights carry an `experts` leading logical axis (expert parallelism:
sharded over the `model` mesh axis); tokens are grouped along the data
axis, so the dispatch/combine einsums lower to the expert all-to-all
pattern under GSPMD.

The dense one-hot dispatch is the *paper-faithful-baseline* choice — exact,
shardable, MXU-friendly — and its overhead is visible in the roofline
(dispatch ≈ expert FLOPs for very-many-expert models like kimi-k2); the
§Perf hillclimb replaces it per-cell where it dominates.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from .layers import dense, swiglu


#: name of the routing decisions that a rematerialized layer keeps
ROUTING = "moe_routing"


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    expert_ff: int
    n_shared: int = 0          # shared experts (always-on), DeepSeek/K2 style
    capacity_factor: float = 1.25
    n_groups: int = 16         # token groups (≈ data-parallel shards)
    ep_logical: str = "experts"  # logical axis of the expert dim


def _capacity(n_tokens_per_group: int, cfg: MoEConfig) -> int:
    c = int(n_tokens_per_group * cfg.top_k * cfg.capacity_factor
            / cfg.n_experts) + 1
    return max(8, -(-c // 8) * 8)  # round up to 8


def router_dispatch(logits: jax.Array, cfg: MoEConfig
                    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """logits: (G, n, X) → dispatch (G, n, X, C) bf16 one-hot,
    combine (G, n, X, C) weights, aux load-balancing loss (scalar)."""
    G, n, X = logits.shape
    C = _capacity(n, cfg)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top_w, top_idx = jax.lax.top_k(probs, cfg.top_k)       # (G, n, k)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)

    counts = jnp.zeros((G, X), jnp.int32)
    dispatch = jnp.zeros((G, n, X, C), jnp.bfloat16)
    combine = jnp.zeros((G, n, X, C), jnp.float32)
    for j in range(cfg.top_k):
        idx_j = top_idx[:, :, j]                           # (G, n)
        oh = jax.nn.one_hot(idx_j, X, dtype=jnp.int32)     # (G, n, X)
        pos_in_expert = jnp.cumsum(oh, axis=1) - oh + counts[:, None, :]
        pos = jnp.sum(oh * pos_in_expert, axis=-1)         # (G, n)
        keep = pos < C
        pos_oh = jax.nn.one_hot(pos, C, dtype=jnp.float32) \
            * keep[..., None].astype(jnp.float32)          # (G, n, C)
        d_j = oh.astype(jnp.float32)[..., None] * pos_oh[:, :, None, :]
        dispatch = dispatch + d_j.astype(jnp.bfloat16)
        combine = combine + d_j * top_w[:, :, j][..., None, None]
        counts = counts + jnp.sum(oh, axis=1)

    # GShard aux loss: mean(fraction routed * mean prob) * X
    frac = jnp.mean(jax.nn.one_hot(top_idx[:, :, 0], X, dtype=jnp.float32),
                    axis=1)                                # (G, X)
    aux = jnp.mean(frac * jnp.mean(probs, axis=1)) * X * X
    return dispatch, combine.astype(jnp.bfloat16), aux


def moe_ffn(x: jax.Array, params: Dict, cfg: MoEConfig
            ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: (B, S, E) → (y, aux_loss, expert_token_counts (X,)).

    The per-expert token counts are the step's touch report.  An expert
    that got no token this step got a zero gradient, but it is not
    clean: AdamW still moves its rows (weight decay, and the decayed
    moments of earlier steps), so only a train-step mask
    (`make_train_step(expert_mask=...)`) keeps an expert's rows as they
    were.
    """
    B, S, E = x.shape
    G = min(cfg.n_groups, B * S)
    tokens = x.reshape(G, (B * S) // G, E)
    logits = dense(tokens, params["router"])               # (G, n, X)
    dispatch, combine, aux = router_dispatch(logits, cfg)

    # dispatch: (G, n, X, C) × (G, n, E) -> (X, G, C, E); the X-dim
    # constraint turns the reshard into the expert all-to-all under GSPMD
    from ..parallel.sharding import constrain
    expert_in = jnp.einsum("gnxc,gne->xgce", dispatch,
                           tokens.astype(jnp.bfloat16))
    expert_in = constrain(expert_in, (cfg.ep_logical, None, None, None))
    Xn, Gn, Cn, En = expert_in.shape
    ein = expert_in.reshape(Xn, Gn * Cn, En)
    g = jnp.einsum("xte,xef->xtf", ein, params["w_gate"])
    u = jnp.einsum("xte,xef->xtf", ein, params["w_up"])
    h = jax.nn.silu(g) * u
    eout = jnp.einsum("xtf,xfe->xte", h, params["w_down"])
    eout = eout.reshape(Xn, Gn, Cn, En)
    y = jnp.einsum("xgce,gnxc->gne", eout, combine)
    y = y.reshape(B, S, E).astype(x.dtype)

    if cfg.n_shared:
        y = y + swiglu(x, params["shared_gate"], params["shared_up"],
                       params["shared_down"])

    counts = jnp.sum(dispatch.astype(jnp.float32), axis=(0, 1, 3))  # (X,)
    return y, aux, counts


def routed_experts(x: jax.Array, params: Dict,
                   spec) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """DeepSeekMoE's routed experts (DeepSeek-V2, arXiv:2405.04434 §2.2),
    the part of them this chip holds.

    x: (B, S, E); params: `router` (E, X) over all X = `spec.n_experts`
    experts, and the held experts' `w_gate`, `w_up` (H, E, F) and
    `w_down` (H, F, E), experts 0 .. H - 1.  The router
    is a softmax over X in fp32, greedy top-k, weights not renormalized
    (DeepSeek-V2-Lite's routed scaling factor is 1).  Each (token,
    choice) that names a held expert is computed; the rest are what the
    other chips add, and are left out.  Returns (y (B, S, E), routed rows
    per held expert (H,), the experts each token chose (B, S, k)).
    """
    B, S, E = x.shape
    N, k, H = B * S, spec.top_k, spec.held
    tokens = x.reshape(N, E)
    with jax.named_scope("moe_router"):
        logits = jnp.einsum("ne,ex->nx", tokens.astype(jnp.float32),
                            params["router"].astype(jnp.float32))
        weight, choice = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
        weight = checkpoint_name(weight, ROUTING)
        choice = checkpoint_name(choice, ROUTING)
    with jax.named_scope("moe_experts"):
        local = choice.reshape(-1)                             # (N k,)
        held = local < H
        slot = jnp.where(held, local, H)      # the others sort last
        order = jnp.argsort(slot, stable=True)
        sizes = jnp.sum(jax.nn.one_hot(slot, H, dtype=jnp.int32), axis=0)
        # ragged_dot leaves the rows past the last group unspecified (on a
        # TPU they keep what the buffer held, NaN included): each operand
        # and product is selected to the held rows, so such a value reaches
        # neither the output nor, through the selects' transposes, a
        # gradient
        valid = (jnp.arange(N * k) < jnp.sum(sizes))[:, None]

        def grouped(a, w):
            return jnp.where(valid, jax.lax.ragged_dot(a, w, sizes), 0)

        rows = jnp.where(valid, jnp.take(tokens, order // k, axis=0), 0)
        g = grouped(rows, params["w_gate"])
        u = grouped(rows, params["w_up"])
        out = grouped(jax.nn.silu(g) * u, params["w_down"])
        out = jnp.take(out, jnp.argsort(order), axis=0).reshape(N, k, E)
        w = jnp.where(held.reshape(N, k), weight, 0.0)
        y = jnp.einsum("nke,nk->ne", out.astype(jnp.float32), w)
    return (y.reshape(B, S, E).astype(x.dtype), sizes,
            choice.reshape(B, S, k))
