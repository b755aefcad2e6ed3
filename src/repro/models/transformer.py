"""Unified decoder stack covering the dense / moe / ssm / hybrid / vlm
families (whisper's enc-dec lives in whisper.py and reuses these pieces).

Each layer is (mixer, ffn) from `cfg.layer_plan()`:
    mixer ∈ {attn, attn_local, mla, mamba, rglru}
    ffn   ∈ {swiglu, gelu, moe, dense_first, none}

Parameters are declared once in `param_defs` (shape + dtype + logical axes
+ init), which drives real init (smoke/examples) and ShapeDtypeStruct
construction (dry-run).  Forward passes apply divisibility-aware sharding
constraints (parallel/sharding.py): batch over (pod, data); attention
scores sequence-parallel over `model`; decode KV-cache time over `model`;
experts / fused head / ffn dims over `model`.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ArchConfig
from ..parallel.sharding import constrain
from .attention import attention, decode_attention
from .layers import (ParamDef, ParamDefs, apply_mrope, apply_rope, dense,
                     gelu_mlp, layer_norm, rms_norm, swiglu, yarn_freqs,
                     yarn_mscale)
from .moe import ROUTING, MoEConfig, moe_ffn, routed_experts
from .rglru import (RGLRUConfig, recurrent_block, recurrent_block_decode)
from .ssm import SSMConfig, mamba_block, mamba_decode_step

# ---------------------------------------------------------------------------
# parameter declaration
# ---------------------------------------------------------------------------


def _norm_defs(path: Tuple[str, ...], cfg: ArchConfig) -> ParamDefs:
    E = cfg.d_model
    defs: ParamDefs = {path + ("scale",): ParamDef((E,), jnp.float32, (None,),
                                                   "zeros" if cfg.norm == "rms" else "ones")}
    if cfg.norm == "ln":
        defs[path + ("bias",)] = ParamDef((E,), jnp.float32, (None,), "zeros")
    return defs


def _attn_defs(p: Tuple[str, ...], cfg: ArchConfig) -> ParamDefs:
    E, Hq, Hkv, D = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    defs: ParamDefs = {
        p + ("wq",): ParamDef((E, Hq * D), None, ("embed", "heads")),
        p + ("wk",): ParamDef((E, Hkv * D), None, ("embed", "kv")),
        p + ("wv",): ParamDef((E, Hkv * D), None, ("embed", "kv")),
        p + ("wo",): ParamDef((Hq * D, E), None, ("heads", "embed")),
    }
    if cfg.qkv_bias:
        defs[p + ("bq",)] = ParamDef((Hq * D,), None, ("heads",), "zeros")
        defs[p + ("bk",)] = ParamDef((Hkv * D,), None, ("kv",), "zeros")
        defs[p + ("bv",)] = ParamDef((Hkv * D,), None, ("kv",), "zeros")
    return defs


def _mla_defs(p: Tuple[str, ...], cfg: ArchConfig) -> ParamDefs:
    a = cfg.mla
    if a.q_lora_rank is not None:
        raise NotImplementedError("MLA with a q-LoRA projection")
    E, H, R = cfg.d_model, cfg.n_heads, a.kv_lora_rank
    return {
        p + ("q_proj",): ParamDef((E, H * a.qk_head_dim), None,
                                  ("embed", "heads")),
        p + ("kv_a_proj_with_mqa",): ParamDef(
            (E, R + a.qk_rope_head_dim), None, ("embed", None)),
        p + ("kv_a_layernorm", "scale"): ParamDef((R,), jnp.float32, (None,),
                                                  "zeros"),
        p + ("kv_b_proj",): ParamDef(
            (R, H * (a.qk_nope_head_dim + a.v_head_dim)), None,
            (None, "heads")),
        p + ("o_proj",): ParamDef((H * a.v_head_dim, E), None,
                                  ("heads", "embed")),
    }


def _ffn_defs(p: Tuple[str, ...], cfg: ArchConfig, kind: str) -> ParamDefs:
    E = cfg.d_model
    if kind == "swiglu" or kind == "dense_first":
        F = cfg.first_dense_ff if kind == "dense_first" else cfg.d_ff
        return {
            p + ("w_gate",): ParamDef((E, F), None, ("embed", "ffn")),
            p + ("w_up",): ParamDef((E, F), None, ("embed", "ffn")),
            p + ("w_down",): ParamDef((F, E), None, ("ffn", "embed")),
        }
    if kind == "gelu":
        F = cfg.d_ff
        return {
            p + ("w_up",): ParamDef((E, F), None, ("embed", "ffn")),
            p + ("b_up",): ParamDef((F,), None, ("ffn",), "zeros"),
            p + ("w_down",): ParamDef((F, E), None, ("ffn", "embed")),
            p + ("b_down",): ParamDef((E,), None, (None,), "zeros"),
        }
    if kind == "moe" and cfg.moe.dropless:
        m = cfg.moe
        X, H, F, Fs = m.n_experts, m.held, m.expert_ff, m.shared_width
        defs = {
            p + ("router",): ParamDef((E, X), None, ("embed", None)),
            p + ("w_gate",): ParamDef((H, E, F), None, ("experts", "embed", None)),
            p + ("w_up",): ParamDef((H, E, F), None, ("experts", "embed", None)),
            p + ("w_down",): ParamDef((H, F, E), None, ("experts", None, "embed")),
        }
        if m.n_shared:
            defs[p + ("shared_gate",)] = ParamDef((E, Fs), None, ("embed", "ffn"))
            defs[p + ("shared_up",)] = ParamDef((E, Fs), None, ("embed", "ffn"))
            defs[p + ("shared_down",)] = ParamDef((Fs, E), None, ("ffn", "embed"))
        return defs
    if kind == "moe":
        m = cfg.moe
        assert m is not None
        X, F = m.n_experts, m.expert_ff
        if cfg.ep_axis == "data":
            # EP over data + TP(ffn) over model: fully sharded weights
            # with NO per-use FSDP regather (tokens all-to-all instead)
            ax_in = ("experts_dp", None, "ffn")
            ax_out = ("experts_dp", "ffn", None)
        else:
            ax_in = ("experts", "embed", None)
            ax_out = ("experts", None, "embed")
        defs: ParamDefs = {
            p + ("router",): ParamDef((E, X), jnp.float32, ("embed", None)),
            p + ("w_gate",): ParamDef((X, E, F), None, ax_in),
            p + ("w_up",): ParamDef((X, E, F), None, ax_in),
            p + ("w_down",): ParamDef((X, F, E), None, ax_out),
        }
        if m.n_shared:
            Fs = F * m.n_shared
            defs[p + ("shared_gate",)] = ParamDef((E, Fs), None, ("embed", "ffn"))
            defs[p + ("shared_up",)] = ParamDef((E, Fs), None, ("embed", "ffn"))
            defs[p + ("shared_down",)] = ParamDef((Fs, E), None, ("ffn", "embed"))
        return defs
    if kind == "none":
        return {}
    raise ValueError(kind)


def _mamba_defs(p: Tuple[str, ...], cfg: ArchConfig) -> ParamDefs:
    s = cfg.ssm
    assert s is not None
    E = cfg.d_model
    Ei = s.expand * E
    K, N, R = s.d_conv, s.d_state, s.dt_rank
    return {
        p + ("in_proj",): ParamDef((E, 2 * Ei), None, ("embed", "inner")),
        p + ("conv_w",): ParamDef((K, Ei), None, (None, "inner")),
        p + ("conv_b",): ParamDef((Ei,), None, ("inner",), "zeros"),
        p + ("x_proj",): ParamDef((Ei, R + 2 * N), None, ("inner", None)),
        p + ("dt_proj",): ParamDef((R, Ei), None, (None, "inner")),
        p + ("dt_bias",): ParamDef((Ei,), jnp.float32, ("inner",), "zeros"),
        p + ("A_log",): ParamDef((Ei, N), jnp.float32, ("inner", None), "ones"),
        p + ("D",): ParamDef((Ei,), jnp.float32, ("inner",), "ones"),
        p + ("out_proj",): ParamDef((Ei, E), None, ("inner", "embed")),
    }


def _rglru_defs(p: Tuple[str, ...], cfg: ArchConfig) -> ParamDefs:
    r = cfg.rglru
    assert r is not None
    E = cfg.d_model
    W = r.lru_width or E
    H = 16 if W % 16 == 0 else 1          # block-diagonal gate blocks
    K = r.d_conv
    return {
        p + ("in_gate",): ParamDef((E, W), None, ("embed", "lru_heads")),
        p + ("in_rec",): ParamDef((E, W), None, ("embed", "lru_heads")),
        p + ("conv_w",): ParamDef((K, W), None, (None, "lru_heads")),
        p + ("conv_b",): ParamDef((W,), None, ("lru_heads",), "zeros"),
        p + ("gate_a",): ParamDef((H, W // H, W // H), None,
                                  ("lru_heads", None, None)),
        p + ("gate_x",): ParamDef((H, W // H, W // H), None,
                                  ("lru_heads", None, None)),
        p + ("lambda",): ParamDef((W,), jnp.float32, ("lru_heads",), "ones"),
        p + ("out_proj",): ParamDef((W, E), None, ("lru_heads", "embed")),
    }


def param_defs(cfg: ArchConfig) -> ParamDefs:
    E, V = cfg.d_model, cfg.vocab
    defs: ParamDefs = {
        ("embed",): ParamDef((V, E), None, ("vocab", "embed"), "embed"),
    }
    if not cfg.tie_embeddings:
        defs[("lm_head",)] = ParamDef((E, V), None, ("embed", "vocab"))
    defs.update(_norm_defs(("final_norm",), cfg))
    if cfg.vlm is not None:
        defs[("patch_proj",)] = ParamDef((E, E), None, ("embed", None))
    for i, (mixer, ffn) in enumerate(cfg.layer_plan()):
        p = ("layers", str(i))
        defs.update(_norm_defs(p + ("norm1",), cfg))
        if mixer in ("attn", "attn_local"):
            defs.update(_attn_defs(p + ("attn",), cfg))
        elif mixer == "mla":
            defs.update(_mla_defs(p + ("attn",), cfg))
        elif mixer == "mamba":
            defs.update(_mamba_defs(p + ("mamba",), cfg))
        elif mixer == "rglru":
            defs.update(_rglru_defs(p + ("rec",), cfg))
        if ffn != "none":
            defs.update(_norm_defs(p + ("norm2",), cfg))
            defs.update(_ffn_defs(p + ("ffn",), cfg, ffn))
    return defs


# ---------------------------------------------------------------------------
# forward pieces
# ---------------------------------------------------------------------------


def _norm(x: jax.Array, params: Dict, cfg: ArchConfig) -> jax.Array:
    if cfg.norm == "rms":
        return rms_norm(x, params["scale"])
    return layer_norm(x, params["scale"], params["bias"])


def _ssm_cfg(cfg: ArchConfig) -> SSMConfig:
    s = cfg.ssm
    return SSMConfig(d_inner=s.expand * cfg.d_model, d_state=s.d_state,
                     d_conv=s.d_conv, dt_rank=s.dt_rank, chunk=s.chunk)


def _rglru_cfg(cfg: ArchConfig) -> RGLRUConfig:
    r = cfg.rglru
    return RGLRUConfig(lru_width=r.lru_width or cfg.d_model, d_conv=r.d_conv)


def _moe_cfg(cfg: ArchConfig, n_tokens: int) -> MoEConfig:
    m = cfg.moe
    groups = math.gcd(n_tokens, 1024)
    return MoEConfig(n_experts=m.n_experts, top_k=m.top_k,
                     expert_ff=m.expert_ff, n_shared=m.n_shared,
                     capacity_factor=m.capacity_factor, n_groups=groups,
                     ep_logical="experts_dp" if cfg.ep_axis == "data"
                     else "experts")


def _rglru_gates_blockdiag(params: Dict) -> Dict:
    """Adapt block-diagonal gate params to rglru.py's dense(x, w) calls by
    exposing callables; instead we inline the block einsum here."""
    return params


def _apply_block_gate(x: jax.Array, w: jax.Array) -> jax.Array:
    """x: (B, L, W); w: (H, W/H, W/H) block-diagonal gate."""
    B, L, W = x.shape
    H = w.shape[0]
    xh = x.reshape(B, L, H, W // H)
    y = jnp.einsum("blhi,hij->blhj", xh, w)
    return y.reshape(B, L, W)


def _rec_params_view(params: Dict) -> Dict:
    """rglru.py expects w_a/w_x as dense mats; wrap block-diagonal ones."""
    return params


def _attn_apply(params: Dict, x: jax.Array, cfg: ArchConfig, *,
                positions: jax.Array, window: Optional[int],
                q_chunk: Optional[int],
                mrope_positions: Optional[jax.Array] = None) -> jax.Array:
    B, S, E = x.shape
    Hq, Hkv, D = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = dense(x, params["wq"], params.get("bq")).reshape(B, S, Hq, D)
    k = dense(x, params["wk"], params.get("bk")).reshape(B, S, Hkv, D)
    v = dense(x, params["wv"], params.get("bv")).reshape(B, S, Hkv, D)
    if mrope_positions is not None:
        sections = cfg.vlm.mrope_sections
        q = apply_mrope(q, mrope_positions, sections, cfg.rope_theta)
        k = apply_mrope(k, mrope_positions, sections, cfg.rope_theta)
    else:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    # sequence-parallel attention: queries' S over `model`, kv replicated
    q = constrain(q, ("batch", "seq_model", None, None))
    k = constrain(k, ("batch", None, None, None))
    v = constrain(v, ("batch", None, None, None))
    o = attention(q, k, v, causal=True, window=window, q_chunk=q_chunk,
                  mixed=cfg.mixed_attn)
    o = constrain(o, ("batch", "seq_model", None, None))
    return dense(o.reshape(B, S, Hq * D), params["wo"])


def _mla_rope(cfg: ArchConfig) -> Tuple[jax.Array, float, float]:
    """(rotary frequencies, cos/sin scale, softmax scale) of an MLA layer:
    YaRN over the rope head size, and DeepSeek-V2's softmax scale
    qk_head_dim^-1/2 · mscale(factor, mscale_all_dim)^2."""
    a = cfg.mla
    inv = yarn_freqs(a.qk_rope_head_dim, cfg.rope_theta, a.yarn_factor,
                     a.yarn_original_max_position, a.yarn_beta_fast,
                     a.yarn_beta_slow)
    cos_scale = (yarn_mscale(a.yarn_factor, a.yarn_mscale)
                 / yarn_mscale(a.yarn_factor, a.yarn_mscale_all_dim))
    softmax_scale = (a.qk_head_dim ** -0.5
                     * yarn_mscale(a.yarn_factor, a.yarn_mscale_all_dim) ** 2)
    return inv, cos_scale, softmax_scale


def _mla_qkv(params: Dict, x: jax.Array, cfg: ArchConfig,
             positions: jax.Array):
    """Queries (B, S, H, qk) and the latent cache entries of `x`: the
    latent before its norm (B, S, R) and the rotated shared rope key
    (B, S, rope)."""
    a = cfg.mla
    B, S, _ = x.shape
    inv, cos_scale, _ = _mla_rope(cfg)
    q = dense(x, params["q_proj"]).reshape(B, S, cfg.n_heads, a.qk_head_dim)
    q_nope, q_pe = jnp.split(q, [a.qk_nope_head_dim], axis=-1)
    q_pe = apply_rope(q_pe, positions, inv_freq=inv, mscale=cos_scale)
    ckv, k_pe = jnp.split(dense(x, params["kv_a_proj_with_mqa"]),
                          [a.kv_lora_rank], axis=-1)
    k_pe = apply_rope(k_pe[:, :, None, :], positions, inv_freq=inv,
                      mscale=cos_scale)[:, :, 0]
    return jnp.concatenate([q_nope, q_pe], axis=-1), ckv, k_pe


def _mla_kv(params: Dict, ckv: jax.Array, k_pe: jax.Array,
            cfg: ArchConfig) -> Tuple[jax.Array, jax.Array]:
    """Per-head keys (B, T, H, qk) and values (B, T, H, v) from the
    latent: its norm, the up-projection `kv_b_proj`, and the one rope key
    every head shares."""
    a = cfg.mla
    B, T, _ = ckv.shape
    kv = dense(rms_norm(ckv, params["kv_a_layernorm"]["scale"]),
               params["kv_b_proj"])
    kv = kv.reshape(B, T, cfg.n_heads, a.qk_nope_head_dim + a.v_head_dim)
    k_nope, v = jnp.split(kv, [a.qk_nope_head_dim], axis=-1)
    k_pe = jnp.broadcast_to(k_pe[:, :, None, :].astype(k_nope.dtype),
                            (B, T, cfg.n_heads, a.qk_rope_head_dim))
    return jnp.concatenate([k_nope, k_pe], axis=-1), v


def _mla_apply(params: Dict, x: jax.Array, cfg: ArchConfig, *,
               positions: jax.Array, q_chunk: Optional[int]) -> jax.Array:
    """Multi-head latent attention (DeepSeek-V2 §2.1) without q-LoRA, over
    the heads this chip holds; rotary pairs (i, i + rope/2)."""
    B, S, _ = x.shape
    with jax.named_scope("mla"):
        q, ckv, k_pe = _mla_qkv(params, x, cfg, positions)
        k, v = _mla_kv(params, ckv, k_pe, cfg)
        o = attention(q, k, v, causal=True, q_chunk=q_chunk,
                      scale=_mla_rope(cfg)[2])
        return dense(o.reshape(B, S, -1), params["o_proj"])


def _ffn_apply(params: Dict, h: jax.Array, cfg: ArchConfig, ffn: str):
    """A layer's feed-forward part → (output, touch report)."""
    if ffn in ("swiglu", "dense_first"):
        return swiglu(h, params["w_gate"], params["w_up"],
                      params["w_down"]), {}
    if ffn == "gelu":
        return gelu_mlp(h, params["w_up"], params["b_up"],
                        params["w_down"], params["b_down"]), {}
    assert ffn == "moe", ffn
    B, S, _ = h.shape
    if not cfg.moe.dropless:
        y, aux_loss, counts = moe_ffn(h, params, _moe_cfg(cfg, B * S))
        return y, {"moe_aux": aux_loss, "expert_counts": counts}
    y, counts, choice = routed_experts(h, params, cfg.moe)
    if cfg.moe.n_shared:
        with jax.named_scope("moe_shared"):
            y = y + swiglu(h, params["shared_gate"], params["shared_up"],
                           params["shared_down"])
    return y, {"expert_counts": counts, "expert_choice": choice}


def _layer_apply(params: Dict, x: jax.Array, cfg: ArchConfig, mixer: str,
                 ffn: str, *, positions, q_chunk, mrope_positions):
    aux = {}
    h = _norm(x, params["norm1"], cfg)
    if mixer in ("attn", "attn_local"):
        window = cfg.sliding_window
        if mixer == "attn_local":
            window = cfg.rglru.attn_window
        h = _attn_apply(params["attn"], h, cfg, positions=positions,
                        window=window, q_chunk=q_chunk,
                        mrope_positions=mrope_positions)
    elif mixer == "mla":
        h = _mla_apply(params["attn"], h, cfg, positions=positions,
                       q_chunk=q_chunk)
    elif mixer == "mamba":
        h = mamba_block(params["mamba"], h, _ssm_cfg(cfg))
    elif mixer == "rglru":
        h = _recurrent_apply(params["rec"], h, cfg)
    x = x + h
    if ffn != "none":
        h, aux = _ffn_apply(params["ffn"], _norm(x, params["norm2"], cfg),
                            cfg, ffn)
        x = x + h
    x = constrain(x, ("batch", "seq_model" if cfg.seq_sp else None, None))
    return x, aux


def _recurrent_apply(params: Dict, x: jax.Array, cfg: ArchConfig) -> jax.Array:
    """Griffin recurrent block with block-diagonal RG-LRU gates."""
    from .rglru import rg_lru
    from .ssm import causal_conv1d
    gate = jax.nn.gelu(dense(x, params["in_gate"]), approximate=True)
    rec = dense(x, params["in_rec"])
    rec = causal_conv1d(rec, params["conv_w"], params["conv_b"])
    lru_params = {
        "w_a": params["gate_a"], "w_x": params["gate_x"],
        "lambda": params["lambda"],
    }
    rec = _rg_lru_blockdiag(lru_params, rec)
    return dense(rec * gate, params["out_proj"])


def _rg_lru_blockdiag(params: Dict, x: jax.Array) -> jax.Array:
    r = jax.nn.sigmoid(_apply_block_gate(x, params["w_a"]).astype(jnp.float32))
    i = jax.nn.sigmoid(_apply_block_gate(x, params["w_x"]).astype(jnp.float32))
    lam = jax.nn.softplus(params["lambda"].astype(jnp.float32))
    a = jnp.exp(-8.0 * lam[None, None, :] * r)
    b = jnp.sqrt(jnp.maximum(1.0 - a * a, 1e-12)) * i * x.astype(jnp.float32)

    def combine(c1, c2):
        a1, b1 = c1
        a2, b2 = c2
        return a2 * a1, a2 * b1 + b2

    _, h = jax.lax.associative_scan(combine, (a, b), axis=1)
    return h.astype(x.dtype)


def _rg_lru_blockdiag_step(params: Dict, x: jax.Array, state: jax.Array):
    """x: (B, 1, W), state (B, W) → (y (B,1,W), new_state)."""
    r = jax.nn.sigmoid(_apply_block_gate(x, params["w_a"]).astype(jnp.float32))
    i = jax.nn.sigmoid(_apply_block_gate(x, params["w_x"]).astype(jnp.float32))
    lam = jax.nn.softplus(params["lambda"].astype(jnp.float32))
    a = jnp.exp(-8.0 * lam[None, None, :] * r)[:, 0]
    b = (jnp.sqrt(jnp.maximum(1.0 - a * a, 1e-12))
         * i[:, 0] * x[:, 0].astype(jnp.float32))
    new = a * state + b
    return new[:, None].astype(x.dtype), new


# ---------------------------------------------------------------------------
# embeddings / logits / positions
# ---------------------------------------------------------------------------


def embed_tokens(params: Dict, tokens: jax.Array, cfg: ArchConfig) -> jax.Array:
    emb = params["embed"]
    x = jnp.take(emb, tokens, axis=0).astype(jnp.bfloat16)
    return x * jnp.asarray(math.sqrt(cfg.d_model), jnp.bfloat16)


def logits_from(params: Dict, x: jax.Array, cfg: ArchConfig) -> jax.Array:
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return jnp.einsum("...e,ev->...v", x, head)


def mrope_positions_for(cfg: ArchConfig, B: int, S: int) -> jax.Array:
    """(3, B, S) t/h/w position streams: patch grid first, then text."""
    v = cfg.vlm
    P = min(v.n_patches, S)
    gh, gw = v.grid
    idx = jnp.arange(S)
    patch_h = (idx // gw) % gh
    patch_w = idx % gw
    text = jnp.maximum(idx - P, 0) + (gh + gw)
    is_text = idx >= P
    t = jnp.where(is_text, text, 0)
    h = jnp.where(is_text, text, patch_h)
    w = jnp.where(is_text, text, patch_w)
    pos = jnp.stack([t, h, w], axis=0)                  # (3, S)
    return jnp.broadcast_to(pos[:, None, :], (3, B, S))


# ---------------------------------------------------------------------------
# top-level: loss / prefill / decode
# ---------------------------------------------------------------------------


def forward(params: Dict, tokens: jax.Array, cfg: ArchConfig, *,
            patch_embeds: Optional[jax.Array] = None,
            q_chunk: Optional[int] = None,
            remat: bool = False) -> Tuple[jax.Array, Dict]:
    """Full-sequence forward → (hidden (B,S,E), aux)."""
    B, S = tokens.shape
    x = embed_tokens(params, tokens, cfg)
    mrope_pos = None
    if cfg.vlm is not None:
        assert patch_embeds is not None
        P = patch_embeds.shape[1]
        patches = dense(patch_embeds.astype(jnp.bfloat16),
                        params["patch_proj"])
        x = jnp.concatenate([patches, x[:, P:]], axis=1)
        mrope_pos = mrope_positions_for(cfg, B, S)
    x = constrain(x, ("batch", "seq_model" if cfg.seq_sp else None, None))
    positions = jnp.arange(S)
    aux_all: Dict[str, List] = {}
    plan = cfg.layer_plan()
    for i, (mixer, ffn) in enumerate(plan):
        layer_fn = lambda p, y: _layer_apply(
            p, y, cfg, mixer, ffn, positions=positions, q_chunk=q_chunk,
            mrope_positions=mrope_pos)
        if remat:
            # the backward pass recomputes a layer but keeps its routing:
            # a recomputed top-k could pick other experts than the loss's
            layer_fn = jax.checkpoint(
                layer_fn, policy=jax.checkpoint_policies.save_only_these_names(
                    ROUTING))
        x, aux = layer_fn(params["layers"][str(i)], x)
        for k, v in aux.items():
            aux_all.setdefault(k, []).append(v)
    x = _norm(x, params["final_norm"], cfg)
    return x, aux_all


def sharded_cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Vocab-sharding-friendly CE: logsumexp + one-hot einsum.  Never
    gathers the full vocab to one device (take_along_axis over a
    model-sharded vocab would all-gather (B,S,V) — tens of GiB/device at
    150k-vocab scale)."""
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)                       # (B, S)
    oh = jax.nn.one_hot(labels, logits.shape[-1], dtype=lf.dtype)
    ll = jnp.einsum("bsv,bsv->bs", lf, oh)                    # (B, S)
    return jnp.mean(lse - ll)


def loss_fn(params: Dict, batch: Dict, cfg: ArchConfig, *,
            q_chunk: Optional[int] = None,
            remat: bool = True) -> Tuple[jax.Array, Dict]:
    """Next-token cross-entropy (+ MoE aux).  batch: tokens, labels
    [, patch_embeds]."""
    x, aux = forward(params, batch["tokens"], cfg,
                     patch_embeds=batch.get("patch_embeds"),
                     q_chunk=q_chunk, remat=remat)
    logits = logits_from(params, x, cfg)
    logits = constrain(logits, ("batch", None, "vocab"))
    loss = sharded_cross_entropy(logits, batch["labels"])
    metrics = {"nll": loss}
    if "moe_aux" in aux:
        moe_loss = 1e-2 * jnp.mean(jnp.stack(aux["moe_aux"]))
        loss = loss + moe_loss
        metrics["moe_aux"] = moe_loss
    for name in ("expert_counts", "expert_choice"):
        if name in aux:
            metrics[name] = jnp.stack(aux[name])
    return loss, metrics


# -- serving ---------------------------------------------------------------


def cache_spec(cfg: ArchConfig, B: int, T: int) -> Dict:
    """Abstract KV/state cache tree (dry-run & allocation).  Windowed
    attention caches are ring buffers of min(T, window)."""
    layers: Dict[str, Dict] = {}
    Hkv, D = cfg.n_kv_heads, cfg.hd
    for i, (mixer, _ffn) in enumerate(cfg.layer_plan()):
        if mixer == "attn":
            Tw = T if cfg.sliding_window is None else min(T, cfg.sliding_window)
            layers[str(i)] = {
                "k": jax.ShapeDtypeStruct((B, Tw, Hkv, D), jnp.bfloat16),
                "v": jax.ShapeDtypeStruct((B, Tw, Hkv, D), jnp.bfloat16),
            }
        elif mixer == "mla":
            a = cfg.mla
            layers[str(i)] = {
                "ckv": jax.ShapeDtypeStruct((B, T, a.kv_lora_rank),
                                            jnp.bfloat16),
                "k_pe": jax.ShapeDtypeStruct((B, T, a.qk_rope_head_dim),
                                             jnp.bfloat16),
            }
        elif mixer == "attn_local":
            Tw = min(T, cfg.rglru.attn_window)
            layers[str(i)] = {
                "k": jax.ShapeDtypeStruct((B, Tw, Hkv, D), jnp.bfloat16),
                "v": jax.ShapeDtypeStruct((B, Tw, Hkv, D), jnp.bfloat16),
            }
        elif mixer == "mamba":
            s = cfg.ssm
            Ei = s.expand * cfg.d_model
            layers[str(i)] = {
                "conv": jax.ShapeDtypeStruct((B, s.d_conv - 1, Ei), jnp.bfloat16),
                "ssm": jax.ShapeDtypeStruct((B, Ei, s.d_state), jnp.float32),
            }
        elif mixer == "rglru":
            W = (cfg.rglru.lru_width or cfg.d_model)
            layers[str(i)] = {
                "conv": jax.ShapeDtypeStruct((B, cfg.rglru.d_conv - 1, W),
                                             jnp.bfloat16),
                "lru": jax.ShapeDtypeStruct((B, W), jnp.float32),
            }
    spec = {"layers": layers, "pos": jax.ShapeDtypeStruct((), jnp.int32)}
    if cfg.encoder is not None:
        from .whisper import encoder_cache_spec
        spec["cross"] = encoder_cache_spec(cfg, B)
    return spec


def cache_axes(cfg: ArchConfig) -> Dict:
    """Logical-axes tree matching cache_spec (decode sharding: cache time
    over `model`, state inner dims over `model`)."""
    layers: Dict[str, Dict] = {}
    for i, (mixer, _ffn) in enumerate(cfg.layer_plan()):
        if mixer in ("attn", "attn_local"):
            layers[str(i)] = {"k": ("batch", "cache_t", None, None),
                              "v": ("batch", "cache_t", None, None)}
        elif mixer == "mla":
            layers[str(i)] = {"ckv": ("batch", "cache_t", None),
                              "k_pe": ("batch", "cache_t", None)}
        elif mixer == "mamba":
            layers[str(i)] = {"conv": ("batch", None, "inner"),
                              "ssm": ("batch", "inner", None)}
        elif mixer == "rglru":
            layers[str(i)] = {"conv": ("batch", None, "lru_heads"),
                              "lru": ("batch", "lru_heads")}
    axes = {"layers": layers, "pos": ()}
    if cfg.encoder is not None:
        from .whisper import encoder_cache_axes
        axes["cross"] = encoder_cache_axes(cfg)
    return axes


def init_cache(cfg: ArchConfig, B: int, T: int) -> Dict:
    spec = cache_spec(cfg, B, T)
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), spec,
                        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def decode_step(params: Dict, cache: Dict, tokens: jax.Array,
                cfg: ArchConfig) -> Tuple[jax.Array, Dict]:
    """One decode step.  tokens: (B, 1) → (logits (B, V), new cache)."""
    B = tokens.shape[0]
    pos = cache["pos"]
    x = embed_tokens(params, tokens, cfg)
    x = constrain(x, ("batch", None, None))
    new_layers: Dict[str, Dict] = {}
    for i, (mixer, ffn) in enumerate(cfg.layer_plan()):
        lp = params["layers"][str(i)]
        lcache = cache["layers"].get(str(i), {})
        h = _norm(x, lp["norm1"], cfg)
        if mixer in ("attn", "attn_local"):
            h, new_lc = _decode_attn(lp["attn"], h, lcache, pos, cfg, mixer)
        elif mixer == "mla":
            h, new_lc = _decode_mla(lp["attn"], h, lcache, pos, cfg)
        elif mixer == "mamba":
            h, conv_s, ssm_s = mamba_decode_step(
                lp["mamba"], h, lcache["conv"], lcache["ssm"], _ssm_cfg(cfg))
            new_lc = {"conv": conv_s, "ssm": ssm_s}
        elif mixer == "rglru":
            h, new_lc = _decode_recurrent(lp["rec"], h, lcache, cfg)
        x = x + h
        if ffn != "none":
            h, _aux = _ffn_apply(lp["ffn"], _norm(x, lp["norm2"], cfg), cfg,
                                 ffn)
            x = x + h
        new_layers[str(i)] = new_lc
    x = _norm(x, params["final_norm"], cfg)
    logits = logits_from(params, x[:, 0], cfg)
    new_cache = {"layers": new_layers, "pos": pos + 1}
    if "cross" in cache:
        new_cache["cross"] = cache["cross"]
    return logits, new_cache


def _decode_attn(params: Dict, x: jax.Array, lcache: Dict, pos: jax.Array,
                 cfg: ArchConfig, mixer: str):
    B, _, E = x.shape
    Hq, Hkv, D = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = dense(x, params["wq"], params.get("bq")).reshape(B, 1, Hq, D)
    k = dense(x, params["wk"], params.get("bk")).reshape(B, 1, Hkv, D)
    v = dense(x, params["wv"], params.get("bv")).reshape(B, 1, Hkv, D)
    if cfg.vlm is not None:
        # text regime in decode: all three streams share the position
        p3 = jnp.broadcast_to(pos[None, None], (3, B, 1))
        q = apply_mrope(q, p3, cfg.vlm.mrope_sections, cfg.rope_theta)
        k = apply_mrope(k, p3, cfg.vlm.mrope_sections, cfg.rope_theta)
    else:
        p = jnp.broadcast_to(pos[None, None], (B, 1))
        q = apply_rope(q, p, cfg.rope_theta)
        k = apply_rope(k, p, cfg.rope_theta)
    k_cache, v_cache = lcache["k"], lcache["v"]
    T = k_cache.shape[1]
    slot = jnp.mod(pos, T)          # ring buffer for windowed caches
    k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k.astype(
        k_cache.dtype), slot, axis=1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v.astype(
        v_cache.dtype), slot, axis=1)
    k_cache = constrain(k_cache, ("batch", "cache_t", None, None))
    v_cache = constrain(v_cache, ("batch", "cache_t", None, None))
    lengths = jnp.minimum(pos + 1, T) * jnp.ones((B,), jnp.int32)
    o = decode_attention(q, k_cache, v_cache, lengths)
    o = dense(o.reshape(B, 1, Hq * D), params["wo"])
    return o, {"k": k_cache, "v": v_cache}


def _decode_mla(params: Dict, x: jax.Array, lcache: Dict, pos: jax.Array,
                cfg: ArchConfig):
    """One MLA decode step: the cache holds the latent and the rotated
    rope key per position; keys and values are re-expanded from it."""
    B = x.shape[0]
    p = jnp.broadcast_to(pos[None, None], (B, 1))
    q, ckv, k_pe = _mla_qkv(params, x, cfg, p)
    caches = {}
    for name, new in (("ckv", ckv), ("k_pe", k_pe)):
        caches[name] = jax.lax.dynamic_update_slice_in_dim(
            lcache[name], new.astype(lcache[name].dtype), pos, axis=1)
    k, v = _mla_kv(params, caches["ckv"], caches["k_pe"], cfg)
    T = k.shape[1]
    lengths = jnp.minimum(pos + 1, T) * jnp.ones((B,), jnp.int32)
    o = decode_attention(q, k, v, lengths, scale=_mla_rope(cfg)[2])
    return dense(o.reshape(B, 1, -1), params["o_proj"]), caches


def _decode_recurrent(params: Dict, x: jax.Array, lcache: Dict,
                      cfg: ArchConfig):
    from .ssm import causal_conv1d  # noqa: F401 (shape parity w/ prefill)
    gate = jax.nn.gelu(dense(x, params["in_gate"]), approximate=True)
    rec = dense(x, params["in_rec"])                     # (B,1,W)
    conv_state = lcache["conv"]
    window = jnp.concatenate([conv_state, rec], axis=1)  # (B,K,W)
    w = params["conv_w"]
    conv = jnp.einsum("bkw,kw->bw", window.astype(jnp.float32),
                      w.astype(jnp.float32))
    rec = jax.nn.silu(conv + params["conv_b"].astype(jnp.float32))
    rec = rec[:, None].astype(x.dtype)
    lru_params = {"w_a": params["gate_a"], "w_x": params["gate_x"],
                  "lambda": params["lambda"]}
    y, new_lru = _rg_lru_blockdiag_step(lru_params, rec, lcache["lru"])
    out = dense(y * gate, params["out_proj"])
    return out, {"conv": window[:, 1:], "lru": new_lru}


def prefill(params: Dict, batch: Dict, cfg: ArchConfig, *,
            q_chunk: Optional[int] = None) -> Tuple[jax.Array, Dict]:
    """Process a full prompt → (last-position logits (B, V), cache).

    Builds the decode cache: full KV for global-attention layers, ring
    window for local layers, final states for SSM/LRU layers.
    """
    tokens = batch["tokens"]
    B, S = tokens.shape
    x, _aux = forward(params, tokens, cfg,
                      patch_embeds=batch.get("patch_embeds"),
                      q_chunk=q_chunk, remat=False)
    logits = logits_from(params, x[:, -1], cfg)
    # a cache primed by re-running mixers in cache mode would duplicate
    # compute; instead caches are filled by the serve loop decode-first
    # pattern or via prefill_cache below.
    return logits, {}


