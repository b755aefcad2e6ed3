"""Optimizer units, MoE dispatch invariants, whisper enc-dec parity."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.models.model import api, init_model_params
from repro.models.moe import (MoEConfig, _capacity, moe_ffn, routed_experts,
                              router_dispatch)
from repro.train.optimizer import (OptConfig, adafactor_init,
                                   adafactor_update, adamw_init,
                                   adamw_update, clip_by_global_norm,
                                   opt_axes)

from proptest import given, integers, floats


# ---------------------------------------------------------------------------
# optimizers
# ---------------------------------------------------------------------------

def test_adamw_first_step_matches_closed_form():
    cfg = OptConfig(lr=0.1, b1=0.9, b2=0.99, eps=1e-8, weight_decay=0.0)
    params = {"w": jnp.asarray([1.0, -2.0], jnp.float32)}
    grads = {"w": jnp.asarray([0.5, -0.5], jnp.float32)}
    opt = adamw_init(params)
    new_p, _ = adamw_update(grads, opt, params, jnp.zeros((), jnp.int32), cfg)
    # bias-corrected m̂ = g, v̂ = g² → update = g/(|g|+eps) = sign(g)
    np.testing.assert_allclose(np.asarray(new_p["w"]),
                               [1.0 - 0.1, -2.0 + 0.1], rtol=1e-5)


def test_adamw_weight_decay_shrinks():
    cfg = OptConfig(lr=0.1, weight_decay=0.1)
    params = {"w": jnp.asarray([10.0], jnp.float32)}
    grads = {"w": jnp.asarray([0.0], jnp.float32)}
    opt = adamw_init(params)
    new_p, _ = adamw_update(grads, opt, params, jnp.zeros((), jnp.int32), cfg)
    assert float(new_p["w"][0]) < 10.0


def test_adafactor_factored_state_shapes():
    params = {"big": jnp.zeros((64, 32)), "vec": jnp.zeros((16,))}
    opt = adafactor_init(params)
    assert opt["v"]["big"]["vr"].shape == (64,)
    assert opt["v"]["big"]["vc"].shape == (32,)
    assert opt["v"]["vec"]["v"].shape == (16,)
    # memory claim: factored state ≪ full second moment
    assert (opt["v"]["big"]["vr"].size + opt["v"]["big"]["vc"].size
            < params["big"].size)


def test_adafactor_update_moves_params():
    cfg = OptConfig(name="adafactor", lr=0.01, weight_decay=0.0)
    rng = np.random.default_rng(0)
    params = {"w": jnp.asarray(rng.standard_normal((8, 4)), jnp.float32)}
    grads = {"w": jnp.asarray(rng.standard_normal((8, 4)), jnp.float32)}
    opt = adafactor_init(params)
    new_p, new_s = adafactor_update(grads, opt, params,
                                    jnp.zeros((), jnp.int32), cfg)
    assert not np.array_equal(np.asarray(new_p["w"]), np.asarray(params["w"]))
    assert np.isfinite(np.asarray(new_p["w"])).all()


def test_opt_axes_mirror_params():
    params_abs = {"w": jax.ShapeDtypeStruct((64, 32), jnp.float32)}
    axes = {"w": ("embed", "ffn")}
    a = opt_axes(axes, params_abs, OptConfig(name="adamw"))
    assert a["mu"]["w"] == ("embed", "ffn")
    f = opt_axes(axes, params_abs, OptConfig(name="adafactor"))
    assert f["v"]["w"]["vr"] == ("embed",)
    assert f["v"]["w"]["vc"] == ("ffn",)


@given(norm=floats(0.1, 100.0))
def test_clip_by_global_norm(norm):
    g = {"a": jnp.full((10,), 3.0)}
    clipped, gn = clip_by_global_norm(g, norm)
    got = float(jnp.linalg.norm(clipped["a"]))
    assert got <= norm * 1.001 + 1e-6
    if float(gn) <= norm:
        np.testing.assert_allclose(np.asarray(clipped["a"]),
                                   np.asarray(g["a"]), rtol=1e-5)


# ---------------------------------------------------------------------------
# MoE dispatch invariants
# ---------------------------------------------------------------------------

@pytest.mark.slow
@given(n=integers(8, 64), X=integers(4, 16), k=integers(1, 4))
def test_router_dispatch_invariants(n, X, k):
    cfg = MoEConfig(n_experts=X, top_k=min(k, X), expert_ff=8, n_groups=2)
    rng = np.random.default_rng(n * 31 + X)
    logits = jnp.asarray(rng.standard_normal((2, n, X)), jnp.float32)
    dispatch, combine, aux = router_dispatch(logits, cfg)
    C = _capacity(n, cfg)
    d = np.asarray(dispatch, np.float32)
    # each (group, expert, slot) holds at most one token
    assert d.sum(axis=1).max() <= 1.0 + 1e-5
    # each token occupies at most top_k slots
    assert d.sum(axis=(2, 3)).max() <= cfg.top_k + 1e-5
    # combine weights are nonnegative and ≤ 1 per token
    c = np.asarray(combine, np.float32)
    assert (c >= -1e-6).all()
    assert c.sum(axis=(2, 3)).max() <= 1.0 + 5e-3  # bf16 combine rounding
    assert np.isfinite(float(aux))


def test_moe_ffn_no_drop_identity_path():
    """With huge capacity every token is routed; output is finite and
    expert counts sum to tokens × top_k."""
    cfg = MoEConfig(n_experts=4, top_k=2, expert_ff=16,
                    capacity_factor=8.0, n_groups=2)
    rng = np.random.default_rng(0)
    E = 8
    params = {
        "router": jnp.asarray(rng.standard_normal((E, 4)), jnp.float32),
        "w_gate": jnp.asarray(rng.standard_normal((4, E, 16)) * 0.1, jnp.bfloat16),
        "w_up": jnp.asarray(rng.standard_normal((4, E, 16)) * 0.1, jnp.bfloat16),
        "w_down": jnp.asarray(rng.standard_normal((4, 16, E)) * 0.1, jnp.bfloat16),
    }
    x = jnp.asarray(rng.standard_normal((2, 8, E)), jnp.bfloat16)
    y, aux, counts = moe_ffn(x, params, cfg)
    assert y.shape == x.shape
    assert np.isfinite(np.asarray(y, np.float32)).all()
    assert float(np.asarray(counts).sum()) == 2 * 8 * 2  # B*S*top_k


def test_moe_expert_counts_feed_avf():
    """Touch report: experts with zero routed tokens must show count 0."""
    cfg = MoEConfig(n_experts=8, top_k=1, expert_ff=8, capacity_factor=4.0,
                    n_groups=1)
    E = 4
    # router strongly prefers expert 0
    router = np.zeros((E, 8), np.float32)
    router[:, 0] = 10.0
    params = {
        "router": jnp.asarray(router),
        "w_gate": jnp.zeros((8, E, 8), jnp.bfloat16),
        "w_up": jnp.zeros((8, E, 8), jnp.bfloat16),
        "w_down": jnp.zeros((8, 8, E), jnp.bfloat16),
    }
    x = jnp.ones((1, 4, E), jnp.bfloat16)
    _y, _aux, counts = moe_ffn(x, params, cfg)
    c = np.asarray(counts)
    assert c[0] > 0 and (c[1:] == 0).all()


def test_routed_experts_ignore_rows_past_the_held_groups(monkeypatch):
    """`ragged_dot` leaves the rows past its last group unspecified (a TPU
    leaves what the buffer held): with those rows NaN, the held experts'
    output and every gradient are finite and unchanged."""
    spec = dataclasses.replace(
        ARCHS["deepseek-v2-lite"].moe, n_experts=16, top_k=4, expert_ff=8,
        n_held=4)
    rng = np.random.default_rng(0)
    E, H = 8, spec.held

    def arr(*shape):
        return jnp.asarray(rng.standard_normal(shape) * 0.3, jnp.float32)

    params = {"router": arr(E, 16), "w_gate": arr(H, E, 8),
              "w_up": arr(H, E, 8), "w_down": arr(H, 8, E)}
    x = arr(2, 6, E)

    def loss(p, x):
        y, _counts, _choice = routed_experts(x, p, spec)
        return jnp.sum(y * y), y

    want = jax.grad(loss, argnums=(0, 1), has_aux=True)(params, x)
    real = jax.lax.ragged_dot

    def nan_past(a, sizes):
        past = jnp.arange(a.shape[0]) >= jnp.sum(sizes)
        return jnp.where(past[:, None], jnp.nan, a)

    # NaN past the groups in the product and in the rows' cotangent
    @jax.custom_vjp
    def poisoned(lhs, rhs, group_sizes):
        return nan_past(real(lhs, rhs, group_sizes), group_sizes)

    def fwd(lhs, rhs, group_sizes):
        return poisoned(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)

    def bwd(res, g):
        lhs, rhs, sizes = res
        d_lhs, d_rhs = jax.vjp(lambda a, b: real(a, b, sizes), lhs, rhs)[1](g)
        return nan_past(d_lhs, sizes), d_rhs, None

    poisoned.defvjp(fwd, bwd)
    monkeypatch.setattr(jax.lax, "ragged_dot", poisoned)
    got = jax.grad(loss, argnums=(0, 1), has_aux=True)(params, x)
    assert int(np.asarray(routed_experts(x, params, spec)[1]).sum()) < 48
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert np.isfinite(np.asarray(g)).all()
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# ---------------------------------------------------------------------------
# whisper enc-dec parity
# ---------------------------------------------------------------------------

def test_whisper_prefill_decode_parity():
    from repro.models import whisper
    cfg = ARCHS["whisper-base"].reduced()
    params = init_model_params(cfg, jax.random.key(0))
    B, S = 2, 5
    rng = np.random.default_rng(0)
    frames = jnp.asarray(rng.standard_normal(
        (B, cfg.encoder.n_frames, cfg.d_model)), jnp.bfloat16)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (B, S)), jnp.int32)
    full_logits, _ = whisper.prefill(params, {"frames": frames,
                                              "tokens": tokens}, cfg)
    m = api(cfg)
    cache = m.init_cache(cfg, B, 16)
    enc = whisper.encode(params, frames, cfg)
    cache["cross"] = whisper.build_cross_cache(params, enc, cfg)
    step = jax.jit(lambda p, c, t: whisper.decode_step(p, c, t, cfg))
    for i in range(S):
        logits, cache = step(params, cache, tokens[:, i:i + 1])
    np.testing.assert_allclose(np.asarray(logits, np.float32),
                               np.asarray(full_logits, np.float32),
                               rtol=0.15, atol=0.25)
