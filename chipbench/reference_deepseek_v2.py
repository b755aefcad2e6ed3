"""Plain reference of DeepSeek-V2's training step at one chip's share:
the forward pass, the loss and the gradients, in float32, from the
equations of DeepSeek-V2 (arXiv:2405.04434 §2.1 MLA, §2.2 DeepSeekMoE)
and YaRN (arXiv:2309.00071).  It imports nothing of the program; it reads
the program's parameter tree by its names.  Attention runs in blocks of
queries and the experts one at a time over every token, each weighted by
its gate (zero for a token that did not choose it), each under
`jax.lax.map` or `scan`.

Departures from the published model, each shared with the program:

1. RMSNorm weights are stored as weight − 1: a norm multiplies by
   1 + `scale` (the program's parametrization of every norm).
2. The token embeddings are multiplied by sqrt(hidden) (the program's
   decoder stack does this for every model; DeepSeek-V2 does not).  With
   random weights it is a rescaling of the table.
3. Rotary pairs are (i, i + rope/2).  DeepSeek-V2's checkpoint stores the
   rope lanes interleaved, and its `apply_rotary_pos_emb` de-interleaves
   them first; loading a checkpoint would permute those 64 columns of
   `q_proj` and `kv_a_proj_with_mqa`.
4. One chip's share of a layer-sharing deployment: the held heads, the
   held routed experts (the router still scores every expert and picks
   its top-k of them), slices of the dense and shared MLPs' widths and of
   the vocabulary.  What the other chips would add to a layer's output
   is left out, so each layer's attention and MLP outputs are partial
   sums; the loss is over the vocabulary slice.
5. No auxiliary balance loss (the router does not train).

`LOW` names the precision of the control: with `low` given, every matmul
operand is rounded to it (products still sum in float32), and so are the
parameters a step stores.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

#: the nearest precision below the configuration's bfloat16
LOW = jnp.float8_e4m3fn
EPS = 1e-6
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


@dataclasses.dataclass(frozen=True)
class Spec:
    """The sizes the parameter tree does not carry."""
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float
    yarn_factor: float
    yarn_original_max_position: int
    yarn_beta_fast: float
    yarn_beta_slow: float
    yarn_mscale: float
    yarn_mscale_all_dim: float
    top_k: int
    routed_scale: float = 1.0

    @classmethod
    def from_file(cls, cfg: Dict[str, Any]) -> "Spec":
        """From a Hugging Face `deepseek_v2` config's keys, for the chip
        that holds the first experts."""
        y = cfg["rope_scaling"]
        return cls(qk_nope_head_dim=cfg["qk_nope_head_dim"],
                   qk_rope_head_dim=cfg["qk_rope_head_dim"],
                   v_head_dim=cfg["v_head_dim"],
                   rope_theta=float(cfg["rope_theta"]),
                   yarn_factor=float(y["factor"]),
                   yarn_original_max_position=y[
                       "original_max_position_embeddings"],
                   yarn_beta_fast=float(y["beta_fast"]),
                   yarn_beta_slow=float(y["beta_slow"]),
                   yarn_mscale=float(y["mscale"]),
                   yarn_mscale_all_dim=float(y["mscale_all_dim"]),
                   top_k=cfg["num_experts_per_tok"],
                   routed_scale=float(cfg["routed_scaling_factor"]))


def _mm(spec: str, a, b, low=None):
    if low is not None:
        a = a.astype(low).astype(jnp.float32)
        b = b.astype(low).astype(jnp.float32)
    return jnp.einsum(spec, a, b)


def _rms(x, scale):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS) * (
        1.0 + scale)


def _get_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn(spec: Spec) -> Tuple[np.ndarray, float, float]:
    """(rotary frequencies, cos/sin scale, softmax scale)."""
    d, base = spec.qk_rope_head_dim, spec.rope_theta

    def lane(rotations):
        return d * math.log(spec.yarn_original_max_position
                            / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(lane(spec.yarn_beta_fast)), 0)
    high = min(math.ceil(lane(spec.yarn_beta_slow)), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    extra = 1.0 / base ** (np.arange(0, d, 2) / d)
    inv = extra / spec.yarn_factor * ramp + extra * (1.0 - ramp)
    cos_scale = (_get_mscale(spec.yarn_factor, spec.yarn_mscale)
                 / _get_mscale(spec.yarn_factor, spec.yarn_mscale_all_dim))
    softmax_scale = ((spec.qk_nope_head_dim + d) ** -0.5
                     * _get_mscale(spec.yarn_factor,
                                   spec.yarn_mscale_all_dim) ** 2)
    return inv.astype(np.float32), cos_scale, softmax_scale


def _rope(x, inv, cos_scale):
    """x: (B, S, ..., d) rotated by position along S."""
    S = x.shape[1]
    ang = np.arange(S, dtype=np.float32)[:, None] * inv[None, :]
    shape = (1, S) + (1,) * (x.ndim - 3) + (inv.shape[0],)
    cos = (cos_scale * np.cos(ang)).reshape(shape)
    sin = (cos_scale * np.sin(ang)).reshape(shape)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def mla(p: Dict, x, spec: Spec, low=None, block: int = 512):
    """Latent attention over the heads `p` holds (no q-LoRA)."""
    B, S, _ = x.shape
    nope, rope, dv = (spec.qk_nope_head_dim, spec.qk_rope_head_dim,
                      spec.v_head_dim)
    H = p["q_proj"].shape[1] // (nope + rope)
    R = p["kv_a_proj_with_mqa"].shape[1] - rope
    inv, cos_scale, softmax_scale = yarn(spec)
    q = _mm("bse,ef->bsf", x, p["q_proj"], low).reshape(B, S, H, nope + rope)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], inv, cos_scale)],
                        -1)
    a = _mm("bse,ef->bsf", x, p["kv_a_proj_with_mqa"], low)
    latent = _rms(a[..., :R], p["kv_a_layernorm"]["scale"])
    k_pe = _rope(a[..., R:], inv, cos_scale)                 # (B, S, rope)
    kv = _mm("bsr,rf->bsf", latent, p["kv_b_proj"], low).reshape(
        B, S, H, nope + dv)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        k_pe[:, :, None, :], (B, S, H, rope))], -1)
    v = kv[..., nope:]
    block = min(block, S)
    assert S % block == 0, (S, block)

    def rows(q0):
        """Attention of the queries q0 .. q0 + block to every key."""
        qb = jax.lax.dynamic_slice_in_dim(q, q0, block, axis=1)
        s = _mm("bshd,bthd->bhst", qb, k, low) * softmax_scale
        causal = (q0 + jnp.arange(block))[:, None] >= jnp.arange(S)[None, :]
        w = jax.nn.softmax(jnp.where(causal[None, None], s, -jnp.inf), -1)
        return _mm("bhst,bthd->bshd", w, v, low)

    o = jax.lax.map(jax.checkpoint(rows), jnp.arange(0, S, block))
    o = jnp.moveaxis(o, 0, 1).reshape(B, S, H * dv)
    return _mm("bsf,fe->bse", o, p["o_proj"], low)


def mlp(p: Dict, x, low=None, names=("w_gate", "w_up", "w_down")):
    g = _mm("bse,ef->bsf", x, p[names[0]], low)
    u = _mm("bse,ef->bsf", x, p[names[1]], low)
    return _mm("bsf,fe->bse", jax.nn.silu(g) * u, p[names[2]], low)


def route(router, x, spec: Spec, low=None, choice=None):
    """(gate weights (B, S, k), chosen experts (B, S, k), margin (B, S)):
    a softmax over every expert, greedy top-k, not renormalized.  Given
    `choice` (another computation's picks), the weights are this
    softmax's at those picks, and the margin is the least chosen
    probability less the greatest unchosen one: negative where `choice`
    is not a top-k of this softmax."""
    probs = jax.nn.softmax(_mm("bse,ex->bsx", x, router, low), axis=-1)
    if choice is None:
        choice = jax.lax.top_k(probs, spec.top_k)[1]
    w = jnp.take_along_axis(probs, choice, -1)
    picked = jnp.any(choice[..., None] == jnp.arange(probs.shape[-1]), -2)
    margin = jnp.min(w, -1) - jnp.max(jnp.where(picked, -1.0, probs), -1)
    return w * spec.routed_scale, choice, margin


def moe(p: Dict, x, spec: Spec, low=None, choice=None):
    """The held routed experts' part of a DeepSeekMoE layer (they are the
    router's first experts), plus the shared experts' slice → (output, (routing margin (B, S), choice))."""
    w, idx, margin = route(p["router"], x, spec, low, choice)

    def expert(y, e):
        """Adds expert e's output, weighted by each token's gate for it."""
        gate = jnp.sum(jnp.where(idx == e[0], w, 0.0), -1)
        return y + gate[..., None] * mlp(dict(zip(EXPERT_LEAVES, e[1:])), x,
                                         low), None

    y, _ = jax.lax.scan(jax.checkpoint(expert), jnp.zeros_like(x), (
        jnp.arange(p["w_gate"].shape[0]),) + tuple(
            p[n] for n in EXPERT_LEAVES))
    if "shared_gate" in p:
        y = y + mlp(p, x, low, ("shared_gate", "shared_up", "shared_down"))
    return y, (margin, idx)


def layer(p: Dict, x, spec: Spec, low=None, choice=None):
    """→ (output, the routing of `moe`, or None for a dense layer)."""
    x = x + mla(p["attn"], _rms(x, p["norm1"]["scale"]), spec, low)
    h = _rms(x, p["norm2"]["scale"])
    if "router" not in p["ffn"]:
        return x + mlp(p["ffn"], h, low), None
    y, routing = moe(p["ffn"], h, spec, low, choice)
    return x + y, routing


def logits(params: Dict, tokens, spec: Spec, low=None,
           choices: Optional[Dict[str, Any]] = None):
    """→ (logits over the vocabulary slice, {"margin": {MoE layer:
    (B, S)}, "choice": {MoE layer: (B, S, k)}}).  `choices` ({MoE layer:
    (B, S, k)}) routes each layer by another computation's picks."""
    E = params["embed"].shape[1]
    x = jnp.take(params["embed"], tokens, axis=0) * math.sqrt(E)
    routing = {"margin": {}, "choice": {}}
    for i in sorted(params["layers"], key=int):
        choice = None if choices is None else choices.get(i)
        x, r = jax.checkpoint(lambda p, y, c: layer(p, y, spec, low, c))(
            params["layers"][i], x, choice)
        if r is not None:
            routing["margin"][i], routing["choice"][i] = r
    x = _rms(x, params["final_norm"]["scale"])
    return _mm("bse,ev->bsv", x, params["lm_head"], low), routing


def loss(params: Dict, batch: Dict, spec: Spec, low=None, choices=None):
    """(mean next-token cross-entropy over the vocabulary slice, the
    routing of `logits`)."""
    lg, routing = logits(params, batch["tokens"], spec, low, choices)
    ll = jnp.take_along_axis(lg, batch["labels"][..., None], -1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(lg, axis=-1) - ll), routing


def adamw_params(p, m, v, count: int, *, lr: float, b1: float, b2: float,
                 eps: float, weight_decay: float):
    """AdamW's new parameters after its `count`-th step, from the
    moments that step left (`m`, `v`): p − lr·(m̂ / (√v̂ + eps) +
    weight_decay·p), with m̂ and v̂ the bias-corrected moments.  float32,
    on numpy or jax arrays alike."""
    p = p.astype(np.float32)
    m_hat = m / (1.0 - b1 ** count)
    v_hat = v / (1.0 - b2 ** count)
    return p - lr * (m_hat / (v_hat ** 0.5 + eps) + weight_decay * p)


def adamw_first_step(params: Dict, batch: Dict, spec: Spec,
                     trained: Dict[str, Sequence[int]], *, lr: float,
                     b1: float, b2: float, eps: float, weight_decay: float,
                     clip_norm: float, low=None, choices=None):
    """One AdamW step from zero moments, for the trained expert rows.

    `trained` maps a MoE layer to the held experts that train; every
    other parameter is frozen, so its gradient is zero.  The gradient of
    the trained rows is clipped to `clip_norm` by its global norm; the
    moments become (1 − b1)·g and (1 − b2)·g², and the rows move as
    `adamw_params` says.  `choices` routes by another computation's
    picks (see `route`).  With `low`, every matmul operand and the new
    parameters are rounded to it.  Returns (loss, {"mu", "nu", "params":
    {layer: {leaf: (n_trained, ...)}}}, the routing of `logits`), in
    float32 under the highest matmul precision."""
    f32 = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    pick = {i: np.asarray(list(e), int) for i, e in trained.items()}
    experts = {i: {n: f32["layers"][i]["ffn"][n] for n in EXPERT_LEAVES}
               for i in trained}

    def of_experts(ex):
        layers = {i: dict(lp, ffn=dict(lp["ffn"], **ex[i])) if i in ex
                  else lp for i, lp in f32["layers"].items()}
        return loss(dict(f32, layers=layers), batch, spec, low, choices)

    with jax.default_matmul_precision("highest"):
        (value, routing), grads = jax.value_and_grad(
            of_experts, has_aux=True)(experts)
    g = {i: {n: grads[i][n][pick[i]] for n in EXPERT_LEAVES} for i in pick}
    norm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
    g = jax.tree.map(lambda x: x * jnp.minimum(1.0, clip_norm / (norm + 1e-9)),
                     g)
    mu = jax.tree.map(lambda x: (1.0 - b1) * x, g)
    nu = jax.tree.map(lambda x: (1.0 - b2) * x * x, g)
    new = {i: {n: adamw_params(experts[i][n][pick[i]], mu[i][n], nu[i][n],
                               1, lr=lr, b1=b1, b2=b2, eps=eps,
                               weight_decay=weight_decay)
               for n in EXPERT_LEAVES} for i in pick}
    if low is not None:
        new = jax.tree.map(lambda x: x.astype(low).astype(jnp.float32), new)
    return value, {"mu": mu, "nu": nu, "params": new}, routing


def relative_error(got, want) -> float:
    """‖got − want‖ / ‖want‖ (Frobenius), in float64 on the host."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want),
                                                  1e-30))
