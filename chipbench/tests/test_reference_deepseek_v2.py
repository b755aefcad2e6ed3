"""The program's DeepSeek-V2 (latent attention with YaRN, DeepSeekMoE's
dropless routed experts, the chip's share) against the plain reference
`chipbench/reference_deepseek_v2.py`, at a reduced size with seeded
random weights."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import reference_deepseek_v2 as R
from repro.configs import get_config
from repro.models import transformer as T
from repro.models.model import api, init_model_params

BASE = get_config("deepseek-v2-lite")
GROUP = 8


def uncut(n_layers: int = 3):
    """DeepSeek-V2-Lite's shape at small widths: 16 heads, 64 routed
    experts (top-6) and 2 shared, YaRN as published."""
    return dataclasses.replace(
        BASE, n_layers=n_layers, d_model=64, d_ff=256, first_dense_ff=256,
        vocab=1024,
        mla=dataclasses.replace(BASE.mla, kv_lora_rank=32,
                                qk_nope_head_dim=16, qk_rope_head_dim=8,
                                v_head_dim=16),
        moe=dataclasses.replace(BASE.moe, expert_ff=16))


def spec_of(cfg) -> R.Spec:
    a = cfg.mla
    return R.Spec(qk_nope_head_dim=a.qk_nope_head_dim,
                  qk_rope_head_dim=a.qk_rope_head_dim,
                  v_head_dim=a.v_head_dim, rope_theta=cfg.rope_theta,
                  yarn_factor=a.yarn_factor,
                  yarn_original_max_position=a.yarn_original_max_position,
                  yarn_beta_fast=a.yarn_beta_fast,
                  yarn_beta_slow=a.yarn_beta_slow,
                  yarn_mscale=a.yarn_mscale,
                  yarn_mscale_all_dim=a.yarn_mscale_all_dim,
                  top_k=cfg.moe.top_k)


def rel(got, want) -> float:
    return R.relative_error(got, want)


def hidden(B=2, S=32, E=64, seed=0):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(
        (B, S, E)), jnp.float32)


def test_yarn_constants_at_published_widths():
    inv, cos_scale, softmax_scale = T._mla_rope(BASE)
    mscale = 0.1 * 0.707 * math.log(40) + 1
    assert mscale == pytest.approx(1.2608, abs=1e-4)
    assert softmax_scale == pytest.approx(192 ** -0.5 * mscale ** 2,
                                          rel=1e-12)
    assert cos_scale == 1.0
    ref_inv, ref_cos, ref_softmax = R.yarn(spec_of(BASE))
    np.testing.assert_allclose(np.asarray(inv), ref_inv, rtol=1e-6)
    assert (ref_cos, ref_softmax) == pytest.approx((cos_scale,
                                                    softmax_scale))
    # the fastest lanes keep their frequency, the slowest are divided by 40
    plain = 1e4 ** -(np.arange(0, 64, 2) / 64)
    assert inv[0] == pytest.approx(plain[0])
    assert inv[-1] == pytest.approx(plain[-1] / 40, rel=1e-6)


@pytest.mark.parametrize("seed", [0, 5])
def test_sublayers_match_reference_in_float32(seed):
    """Latent attention, the routed experts a chip holds, the shared and
    dense MLP slices, at full float32."""
    cfg = uncut().chip_share(GROUP, n_layers=3)
    spec = spec_of(cfg)
    p = init_model_params(cfg, jax.random.key(seed), dtype=jnp.float32)
    h = hidden()
    S = h.shape[1]
    attn, moe, dense = (p["layers"]["1"]["attn"], p["layers"]["1"]["ffn"],
                        p["layers"]["0"]["ffn"])

    def program(h):
        return (T._mla_apply(attn, h, cfg, positions=jnp.arange(S),
                             q_chunk=None),
                T._ffn_apply(moe, h, cfg, "moe"),
                T._ffn_apply(dense, h, cfg, "dense_first")[0])

    def reference(h):
        return R.mla(attn, h, spec), R.moe(moe, h, spec), R.mlp(dense, h)

    with jax.default_matmul_precision("highest"):
        got, (y, aux), got_dense = jax.jit(program)(h)
        want, (y_ref, (_margin, choice)), want_dense = jax.jit(reference)(h)
    assert rel(got, want) < 1e-5
    np.testing.assert_array_equal(np.asarray(aux["expert_choice"]),
                                  np.asarray(choice))
    assert rel(y, y_ref) < 1e-5
    assert rel(got_dense, want_dense) < 1e-5
    # the touch report counts the routed rows of each held expert
    held = np.asarray(choice)
    want_counts = [(held == e).sum() for e in range(cfg.moe.held)]
    np.testing.assert_array_equal(np.asarray(aux["expert_counts"]),
                                  want_counts)


def test_model_loss_logits_and_gradients_match_reference():
    """The whole share in the program's own precision (bf16 weights and
    activations) against the float32 reference, routed alike."""
    cfg = uncut().chip_share(GROUP, n_layers=3)
    spec = spec_of(cfg)
    params = init_model_params(cfg, jax.random.key(3))
    rng = np.random.default_rng(3)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (2, 64)), jnp.int32)
    batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, axis=1)}

    def program_loss(p):
        return api(cfg).loss_fn(p, batch, cfg, remat=False)

    (loss, metrics), grads = jax.jit(jax.value_and_grad(
        program_loss, has_aux=True))(params)
    logits = jax.jit(lambda p: T.logits_from(
        p, T.forward(p, tokens, cfg)[0], cfg))(params)
    choices = {str(i + 1): c for i, c in enumerate(metrics["expert_choice"])}

    f32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        (ref_loss, routing), ref_grads = jax.jit(jax.value_and_grad(
            lambda p: R.loss(p, batch, spec, choices=choices),
            has_aux=True))(f32)
        ref_logits, _ = jax.jit(lambda p: R.logits(
            p, tokens, spec, choices=choices))(f32)
    # the program's picks are a top-6 of the reference's router
    assert min(float(np.min(m)) for m in routing["margin"].values()) > -5e-3
    assert abs(float(loss) - float(ref_loss)) / float(ref_loss) < 2e-3
    assert rel(logits, ref_logits) < 3e-2
    flat = dict(jax.tree_util.tree_leaves_with_path(grads))
    ref_flat = dict(jax.tree_util.tree_leaves_with_path(ref_grads))
    errors = {jax.tree_util.keystr(k): rel(flat[k], ref_flat[k])
              for k in ref_flat if np.any(np.asarray(ref_flat[k]))}
    # bf16 gradients: good to a few percent a leaf
    assert max(errors.values()) < 0.06, errors
    expert = [v for k, v in errors.items() if "w_" in k and "shared" not in k]
    assert max(expert) < 0.05


def _slice(a, axis, part, n):
    size = a.shape[axis] // n
    return jax.lax.slice_in_dim(a, part * size, (part + 1) * size, axis=axis)


def _heads(a, axis, rank, width, H):
    """Head `rank`'s share of a (.., H * width, ..) projection."""
    shape = a.shape[:axis] + (H, width) + a.shape[axis + 1:]
    a = _slice(a.reshape(shape), axis, rank, GROUP)
    return a.reshape(a.shape[:axis] + (-1,) + a.shape[axis + 2:])


def share_of(p_layer, full, rank):
    """Chip `rank`'s share of one layer's parameters of `full`, laid out
    as the first chip's: its experts come first in the router's columns
    (a softmax and its top-k do not depend on the experts' order)."""
    a, H = full.mla, full.n_heads
    attn = p_layer["attn"]
    out = {"attn": {
        "q_proj": _heads(attn["q_proj"], 1, rank, a.qk_head_dim, H),
        "kv_a_proj_with_mqa": attn["kv_a_proj_with_mqa"],
        "kv_a_layernorm": attn["kv_a_layernorm"],
        "kv_b_proj": _heads(attn["kv_b_proj"], 1, rank,
                            a.qk_nope_head_dim + a.v_head_dim, H),
        "o_proj": _heads(attn["o_proj"], 0, rank, a.v_head_dim, H)}}
    f = p_layer["ffn"]
    if "router" in f:
        held = f["w_gate"].shape[0] // GROUP
        out["ffn"] = {"router": jnp.roll(f["router"], -rank * held, axis=1)}
        out["ffn"].update({n: _slice(f[n], 0, rank, GROUP)
                           for n in ("w_gate", "w_up", "w_down")})
        names = ("shared_gate", "shared_up", "shared_down")
    else:
        out["ffn"] = {}
        names = ("w_gate", "w_up", "w_down")
    out["ffn"].update({names[0]: _slice(f[names[0]], 1, rank, GROUP),
                       names[1]: _slice(f[names[1]], 1, rank, GROUP),
                       names[2]: _slice(f[names[2]], 0, rank, GROUP)})
    return out


@pytest.mark.parametrize("layer", ["0", "1"])
def test_eight_chip_shares_add_up_to_the_uncut_layer(layer):
    """The 8 chips' partial outputs of a layer's attention and MLP (held
    heads, held experts, width slices), with the replicated residual and
    norms counted once, sum to the reference's uncut layer."""
    full = uncut(n_layers=2)
    params = init_model_params(full, jax.random.key(7), dtype=jnp.float32)
    p = params["layers"][layer]
    kind = full.layer_plan()[int(layer)][1]
    share = full.chip_share(GROUP, n_layers=2)
    x = hidden(seed=int(layer))
    S = x.shape[1]

    def composed(p, x):
        h = T._norm(x, p["norm1"], full)
        x1 = x + sum(T._mla_apply(share_of(p, full, r)["attn"], h, share,
                                  positions=jnp.arange(S), q_chunk=None)
                     for r in range(GROUP))
        h = T._norm(x1, p["norm2"], full)
        return x1 + sum(T._ffn_apply(share_of(p, full, r)["ffn"], h, share,
                                     kind)[0]
                        for r in range(GROUP))

    with jax.default_matmul_precision("highest"):
        y = jax.jit(composed)(p, x)
        want, _ = jax.jit(lambda p, x: R.layer(p, x, spec_of(full)))(p, x)
    assert rel(y, want) < 1e-5
