"""DeepSeek-V2-Lite at one chip's share, trained by expert-specialized
fine-tuning (ESFT): the cut, the train step's expert-row mask, and the
chunks a Chipmink save finds dirty."""
import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import Chipmink, LGA, MemoryStore
from repro.models.model import init_model_params
from repro.models.transformer import param_defs
from repro.train.optimizer import OptConfig
from repro.train.train_step import (EXPERT_LEAVES, expert_row_masks,
                                    init_train_state, make_train_step,
                                    touched_prefixes_from_metrics)

BASE = get_config("deepseek-v2-lite")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def small_share():
    """One chip of 8 of DeepSeek-V2-Lite's shape at small widths: 2 of 16
    heads, experts 0-7 of 64 (top-6), slices of the MLPs."""
    full = dataclasses.replace(
        BASE, d_model=64, d_ff=256, first_dense_ff=256, vocab=1024,
        mla=dataclasses.replace(BASE.mla, kv_lora_rank=32,
                                qk_nope_head_dim=16, qk_rope_head_dim=8,
                                v_head_dim=16),
        moe=dataclasses.replace(BASE.moe, expert_ff=16))
    return full.chip_share(8, n_layers=3)


def frozen_but_experts(params):
    """Every parameter group but the stacked routed-expert leaves."""
    out = [f"params/{k}" for k in params if k != "layers"]
    for i, lp in params["layers"].items():
        for part, sub in lp.items():
            if part == "ffn" and "router" in sub:
                out += [f"params/layers/{i}/ffn/{n}" for n in sub
                        if n not in EXPERT_LEAVES]
            else:
                out.append(f"params/layers/{i}/{part}")
    return out


def batch_of(cfg, seed, B=2, S=32):
    tokens = np.random.default_rng(seed).integers(0, cfg.vocab, (B, S))
    tokens = jnp.asarray(tokens, jnp.int32)
    return {"tokens": tokens, "labels": jnp.roll(tokens, -1, axis=1)}


def flat(tree, prefix=()):
    if isinstance(tree, dict):
        out = {}
        for k, v in tree.items():
            out.update(flat(v, prefix + (k,)))
        return out
    return {"/".join(prefix): tree}


def test_chip_share_holds_the_stated_parameters():
    """The cut keeps every width: per chip of 8, layers 0-4."""
    cfg = BASE.chip_share(8, n_layers=5)
    sizes = {"/".join(p): int(np.prod(d.shape))
             for p, d in param_defs(cfg).items()}

    def total(pred):
        return sum(n for p, n in sizes.items() if pred(p))

    assert total(lambda p: p in ("embed", "lm_head")) == 2 * 12800 * 2048
    assert total(lambda p: p.startswith("layers/0/attn/")) == 2_753_024
    assert total(lambda p: p.startswith("layers/0/ffn/")) == 3 * 2048 * 1368
    assert total(lambda p: p.startswith("layers/1/ffn/shared")) == (
        3 * 2048 * 352)
    assert total(lambda p: p.startswith("layers/1/ffn/w_")) == (
        8 * 3 * 2048 * 1408)
    assert sizes["layers/1/ffn/router"] == 2048 * 64
    assert sum(sizes.values()) == 360_620_544
    assert (cfg.mla, cfg.d_model, cfg.moe.expert_ff) == (
        BASE.mla, 2048, 1408)
    with pytest.raises(ValueError):
        BASE.chip_share(6, n_layers=5)


def test_expert_mask_names_moe_layers_only():
    cfg = small_share()
    masks = expert_row_masks(cfg, {"1": [0, 3]})
    assert sorted(masks) == ["1", "2"]
    assert masks["1"].tolist() == [True, False, False, True] + [False] * 4
    assert not masks["2"].any()
    with pytest.raises(ValueError):
        expert_row_masks(cfg, {"0": [1]})


def test_esft_keeps_frozen_rows_and_a_save_dirties_the_trained_slabs():
    """After a few ESFT steps every frozen leaf, and every frozen expert
    row of the stacked leaves, is bit-identical in the parameters and
    both moments; a Chipmink save then finds exactly the trained slabs'
    chunks dirty."""
    cfg = small_share()
    opt = OptConfig(lr=1e-3)
    state0 = init_train_state(cfg, init_model_params(cfg, jax.random.key(1)),
                              opt)
    trained = {"1": [0, 3], "2": [5, 6]}
    frozen = frozen_but_experts(state0["params"])
    step = jax.jit(make_train_step(cfg, opt, frozen=frozen,
                                   expert_mask=trained, remat=True))
    state = state0
    for i in range(3):
        state, metrics = step(state, batch_of(cfg, i))
    assert np.isfinite(float(metrics["nll"]))

    def trees(s):
        return {"params": s["params"], "mu": s["opt"]["mu"],
                "nu": s["opt"]["nu"]}

    before, after = flat(trees(state0)), flat(trees(state))
    n_moved = 0
    for path, a in before.items():
        a, b = np.asarray(a), np.asarray(after[path])
        parts = path.split("/")
        if parts[-1] in EXPERT_LEAVES and parts[-2] == "ffn":
            rows = trained.get(parts[2], [])
            for e in range(a.shape[0]):
                same = np.array_equal(a[e], b[e])
                assert same != (e in rows), (path, e)
                n_moved += not same
        else:
            assert np.array_equal(a, b), path
    assert n_moved == 3 * 3 * 4        # 3 trees x 3 leaves x 4 slabs

    # slabs of 64 x 16: 2 chunks of bf16 parameters, 4 of fp32 moments
    ck = Chipmink(MemoryStore(), LGA(), chunk_bytes=1024, async_mode=False)
    reports = []
    detect = ck.detector.detect
    ck.detector.detect = lambda *a, **k: reports.append(detect(*a, **k)) \
        or reports[-1]
    ck.save(trees(state0))
    ck.save(trees(state))
    want = set()
    for path, a in after.items():
        parts = path.split("/")
        if parts[-1] in EXPERT_LEAVES and parts[2] in trained:
            per = 64 * 16 * a.dtype.itemsize // 1024
            for e in trained[parts[2]]:
                want |= {f"{path}#[{e * per + c}]" for c in range(per)}
    assert reports[-1].dirty == want
    stats = ck.save_stats[-1]
    assert stats["bytes_partly_dirty"] == stats["bytes_dirty"] == (
        1024 * len(want))
    assert stats["bytes_partly_dirty"] / stats["bytes_in_partly_dirty"] == (
        0.25)


def test_adamw_moves_an_expert_that_got_no_token_unless_masked():
    """The touch report does not prove an expert clean: an expert that
    routed tokens at one step and none at the next still moves at the
    next (its moments decay and AdamW applies them), unless the ESFT mask
    freezes its rows."""
    cfg = small_share()
    opt = OptConfig(lr=1e-3)
    state0 = init_train_state(cfg, init_model_params(cfg, jax.random.key(2)),
                              opt)
    step = jax.jit(make_train_step(cfg, opt, remat=False))
    # two tokens a step: some held experts route none of them
    found = None
    for seed in range(40):
        b1 = batch_of(cfg, seed, B=1, S=2)
        s1, m1 = step(state0, b1)
        for seed2 in range(40, 80):
            b2 = batch_of(cfg, seed2, B=1, S=2)
            s2, m2 = step(s1, b2)
            idle = np.flatnonzero((np.asarray(m1["expert_counts"][0]) > 0)
                                  & (np.asarray(m2["expert_counts"][0]) == 0))
            if idle.size:
                found = (b1, b2, int(idle[0]))
                break
        if found is not None:
            break
    assert found is not None
    b1, b2, e = found

    def leaves(s):
        return [s["params"]["layers"]["1"]["ffn"]["w_up"],
                s["opt"]["mu"]["layers"]["1"]["ffn"]["w_up"],
                s["opt"]["nu"]["layers"]["1"]["ffn"]["w_up"]]

    for a, b in zip(leaves(s1), leaves(s2)):
        assert not np.array_equal(np.asarray(a[e]), np.asarray(b[e]))
    # the report marks every expert leaf touched, used or not
    touched = touched_prefixes_from_metrics(cfg, m2)
    assert "params/layers/1/ffn" in touched

    others = [x for x in range(cfg.moe.held) if x != e]
    masked = jax.jit(make_train_step(cfg, opt, remat=False,
                                     expert_mask={"1": others, "2": others}))
    t1, _ = masked(state0, b1)
    t2, _ = masked(t1, b2)
    for a, b in zip(leaves(state0), leaves(t2)):
        np.testing.assert_array_equal(np.asarray(a[e]), np.asarray(b[e]))


def test_launcher_trains_deepseek_v2_lite(tmp_path):
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch",
         "deepseek-v2-lite", "--steps", "4", "--save-every", "2",
         "--batch", "2", "--seq", "32", "--store", str(tmp_path / "store")],
        env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "saved TimeID=2" in out.stdout
    assert "done: 4 steps" in out.stdout
