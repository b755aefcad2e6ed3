"""Expert-specialized fine-tuning (ESFT, arXiv:2407.01906) of one chip's
share of a DeepSeek-V2 model, checkpointed through `Chipmink.save`.

The job of `deploy/train.py`, with the model built from the file's
DeepSeek-V2 keys (`arch_config`) and other parameters trained: in each
MoE layer the held experts with the largest share of the first batch's
routed tokens train (ESFT-Token), and nothing else does.  The rest of
the model is frozen through the train step's prefix masks (the ASCC
proves it read-only); the other experts' rows inside the stacked expert
leaves through its expert mask (bit-identical parameters and moments),
so a save's delta is a few expert slabs inside every expert leaf.

At set-up, before the window, one step of the timed `chipbench_step`
from the seeded initial state on the first batch is read
(`step_readings`), and the initial parameters and the batch are copied
to the host.  After the window the plain reference
(`chipbench/reference_deepseek_v2.py`) takes the same step, and
`traffic_faults()` counts, besides the traffic's non-finite losses, the
compared quantities outside their tolerance (`TOLERANCE`): the loss; the
first and second AdamW moments of every trained expert slab; the slabs'
parameter change against AdamW's update of the step's own moments; the
routing; and the frozen parameters and moments, bit for bit.

Traffic mix keys: `trained_experts` (experts that train in each MoE
layer), `save_every_steps`, `warm_saves`.  Configuration keys beyond the
model's: `published` (the source's counts of the keys the chip's share
changes), `deployment_share` (`chips`: the chips that share every
layer), `deployment` as in `deploy/train.py`.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List

import numpy as np

from . import common
from . import train
from .. import reference_deepseek_v2 as reference

#: tolerances of the comparison with the reference: the program's
#: readings at the cell's size on a v5e, and those of the reference run
#: in float8_e4m3fn (the `control` of `chipbench/faults_esft.py`), are in
#: PERF.md; each lies between the two
TOLERANCE = {
    # relative: the program reads 2.9e-6 to 1.1e-5, the control 1.6e-4
    # and more
    "loss": 1e-4,
    # relative, the worst trained slab (Frobenius): a bf16 gradient is
    # good to ~1% a slab; in float8 the gradients underflow (~100%)
    "m": 0.05,
    # as `m`, for (1 - b2) g², twice g's relative error
    "nu": 0.1,
    # relative, the worst slab: the parameters' change against AdamW's
    # update of the step's own moments, rounded as the parameters are
    # stored; the same arithmetic reads ~0, an update without its bias
    # correction 0.55
    "update": 0.02,
    # how far (in probability) a token's picks may fall short of the
    # reference's top-k: bf16 activations move a router probability by
    # ~1e-3 at most
    "route": 5e-3,
}

#: DeepSeek-V2 settings the program implements; another value is refused
SUPPORTED = {"q_lora_rank": None, "scoring_func": "softmax",
             "topk_method": "greedy", "norm_topk_prob": False,
             "hidden_act": "silu", "n_group": 1, "topk_group": 1,
             "moe_layer_freq": 1, "attention_bias": False,
             "rms_norm_eps": 1e-06, "routed_scaling_factor": 1}


def arch_config(cfg: Dict[str, Any]):
    """The program's `ArchConfig` of the file: the published model from
    its DeepSeek-V2 keys (with `published`'s counts), cut to the chip's
    share of `deployment_share` and to the file's depth."""
    from repro.configs import get_config
    from repro.configs.base import MLASpec, MoESpec
    for key, want in SUPPORTED.items():
        if cfg[key] != want:
            raise NotImplementedError(f"{key} = {cfg[key]!r}: the program "
                                      f"implements {want!r} only")
    if cfg["rope_scaling"]["type"] != "yarn":
        raise NotImplementedError("rope scaling other than YaRN")
    pub, yarn = cfg["published"], cfg["rope_scaling"]
    full = dataclasses.replace(
        get_config(cfg["arch"]),
        n_layers=pub["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=pub["num_attention_heads"],
        n_kv_heads=pub["num_key_value_heads"], vocab=pub["vocab_size"],
        d_ff=cfg["intermediate_size"], head_dim=cfg["v_head_dim"],
        first_dense_layers=cfg["first_k_dense_replace"],
        first_dense_ff=cfg["intermediate_size"],
        rope_theta=float(cfg["rope_theta"]),
        tie_embeddings=cfg["tie_word_embeddings"],
        mla=MLASpec(kv_lora_rank=cfg["kv_lora_rank"],
                    qk_nope_head_dim=cfg["qk_nope_head_dim"],
                    qk_rope_head_dim=cfg["qk_rope_head_dim"],
                    v_head_dim=cfg["v_head_dim"],
                    q_lora_rank=cfg["q_lora_rank"],
                    yarn_factor=float(yarn["factor"]),
                    yarn_original_max_position=yarn[
                        "original_max_position_embeddings"],
                    yarn_beta_fast=float(yarn["beta_fast"]),
                    yarn_beta_slow=float(yarn["beta_slow"]),
                    yarn_mscale=float(yarn["mscale"]),
                    yarn_mscale_all_dim=float(yarn["mscale_all_dim"])),
        moe=MoESpec(n_experts=pub["n_routed_experts"],
                    top_k=cfg["num_experts_per_tok"],
                    expert_ff=cfg["moe_intermediate_size"],
                    n_shared=cfg["n_shared_experts"], dropless=True))
    arch = full.chip_share(cfg["deployment_share"]["chips"],
                           n_layers=cfg["num_hidden_layers"])
    held = {"n_routed_experts": arch.moe.held,
            "num_attention_heads": arch.n_heads,
            "num_key_value_heads": arch.n_kv_heads,
            "vocab_size": arch.vocab}
    for key, n in held.items():
        if cfg[key] != n:
            raise ValueError(f"{key} is {cfg[key]}, but one chip of "
                             f"{cfg['deployment_share']['chips']} holds {n}")
    return arch


def frozen_groups(params: Dict[str, Any]) -> List[str]:
    """Every parameter group but the stacked routed-expert leaves."""
    out = [f"params/{k}" for k in params if k != "layers"]
    for i, lp in params["layers"].items():
        for part, sub in lp.items():
            if part == "ffn" and "router" in sub:
                out += [f"params/layers/{i}/ffn/{n}" for n in sub
                        if n not in reference.EXPERT_LEAVES]
            else:
                out.append(f"params/layers/{i}/{part}")
    return out


def esft_token_selection(counts: np.ndarray, moe_layers: List[int],
                         n: int) -> Dict[str, List[int]]:
    """{MoE layer: the `n` held experts with the most routed tokens},
    ties to the lower index."""
    return {str(layer): sorted(int(e) for e in
                               np.argsort(-counts[li], kind="stable")[:n])
            for li, layer in enumerate(moe_layers)}


def frozen_mismatches(before: Dict[str, Any], after: Dict[str, Any],
                      frozen: List[str],
                      trained: Dict[str, List[int]]) -> int:
    """Frozen leaves (params and both moments), and frozen expert rows of
    the stacked leaves, that are not bit-identical after the step."""
    import jax.numpy as jnp
    from repro.train.optimizer import is_frozen
    from ..reference import array_leaves
    bad = 0
    trees = [(before["params"], after["params"])] + [
        (before["opt"][k], after["opt"][k]) for k in before["opt"]]
    for old, new in trees:
        new_leaves = array_leaves(new)
        for path, a in array_leaves(old).items():
            b = new_leaves[path]
            parts = tuple(path.split("/"))
            if is_frozen(("params",) + parts, frozen):
                bad += not bool(jnp.array_equal(a, b))
            elif parts[-1] in reference.EXPERT_LEAVES:
                keep = np.ones(a.shape[0], bool)
                keep[trained[parts[1]]] = False
                same = jnp.all(a == b, axis=tuple(range(1, a.ndim)))
                bad += int(np.sum(keep & ~np.asarray(same)))
    return bad


def _trained_rows(tree: Dict[str, Any], trained: Dict[str, List[int]]):
    """{layer: {leaf: host copy of the trained experts' rows}} of a
    params-shaped tree."""
    return {i: {n: np.asarray(tree["layers"][i]["ffn"][n][np.asarray(e)])
                for n in reference.EXPERT_LEAVES}
            for i, e in trained.items()}


def step_readings(dep: "Deployment", batch: Dict[str, Any]) -> Dict[str, Any]:
    """One timed `chipbench_step` from `dep`'s state on `batch`: host
    copies of the loss, the trained rows' moments and new parameters,
    every MoE layer's picks, and the count of frozen leaves and rows that
    moved."""
    after, metrics = dep.step_keep(dep.state, batch)
    out = {"loss": float(metrics["nll"]),
           "mu": _trained_rows(after["opt"]["mu"], dep.trained),
           "nu": _trained_rows(after["opt"]["nu"], dep.trained),
           "params": _trained_rows(after["params"], dep.trained),
           "choices": {str(layer): np.asarray(metrics["expert_choice"][li])
                       for li, layer in enumerate(sorted(
                           int(i) for i in dep.trained))},
           "frozen_moved": frozen_mismatches(dep.state, after,
                                             list(dep.frozen), dep.trained)}
    del after, metrics
    return out


def reference_step(dep: "Deployment", params: Dict[str, Any],
                   batch: Dict[str, Any], low=None, choices=None):
    """The reference's version of `step_readings`' step (see
    `reference.adamw_first_step`), on the host: (loss, moments and new
    parameters of the trained rows, routing)."""
    import jax
    spec = reference.Spec.from_file(dep.cfg)
    o = dep.opt
    return jax.device_get(jax.jit(
        lambda p, b, c: reference.adamw_first_step(
            p, b, spec, dep.trained, lr=o.lr, b1=o.b1, b2=o.b2, eps=o.eps,
            weight_decay=o.weight_decay, clip_norm=o.clip_norm, low=low,
            choices=c))(params, batch, choices))


class Deployment(train.Deployment):
    def setup(self) -> None:
        import jax
        from repro.core import Chipmink
        from repro.core.ascc import readonly_state_leaves
        from repro.train.train_step import touched_prefixes_from_metrics

        first = self.build()
        # what the reference needs after the window, when the traffic has
        # moved on from the initial state
        self.initial = jax.device_get({"params": self.state["params"],
                                       "batch": first})
        self.readings = step_readings(self, first)

        self.touched = lambda metrics: touched_prefixes_from_metrics(
            self.arch, metrics, self.frozen)
        self.ck = Chipmink(common.store_of(self.dep, self.store_dir),
                           common.policy_of(self.dep),
                           chunk_bytes=self.chunk_bytes,
                           async_mode=self.dep["async_mode"],
                           async_depth=self.dep["async_depth"])
        self.log.watch(self.ck)
        readonly = readonly_state_leaves(self.step_keep, self.state, first)
        self.readonly = {"params/" + p if not p.startswith(
            ("params", "opt", "step")) else p for p in readonly}
        self.active_bytes = sum(
            int(v.nbytes) for p, v in train._device_leaves(self.state).items()
            if p not in self.readonly)
        self.saved = False
        n_saves = 1 + self.mix["warm_saves"]
        while len(self.log.records) < n_saves:
            self.round()
        self.ck.wait()

    def build(self) -> Dict[str, Any]:
        """The seeded state, the data, the ESFT selection and the timed
        step; returns the first batch (the pipeline is rewound to it)."""
        import jax
        import jax.numpy as jnp
        from repro.models.model import api, init_model_params
        from repro.train.data import TokenPipeline
        from repro.train.optimizer import OptConfig
        from repro.train.train_step import init_train_state, make_train_step

        arch = arch_config(self.cfg)
        self.opt = opt = OptConfig(**self.dep["optimizer"])
        self.arch = arch
        self.state = jax.jit(lambda k: init_train_state(
            arch, init_model_params(arch, k), opt))(
                common.jax_key(self.seed, common.WEIGHTS))
        self.pipeline = TokenPipeline(
            arch.vocab, self.dep["global_batch"], self.dep["seq_len"],
            seed=common.child_seed(self.seed, common.DATA))
        first = {k: jnp.asarray(v)
                 for k, v in self.pipeline.next_batch().items()}
        self.pipeline.restore({**self.pipeline.cursor(), "step": 0})

        routed = jax.jit(lambda p, b: api(arch).loss_fn(
            p, b, arch, remat=False)[1]["expert_counts"])
        moe_layers = [i for i, (_m, f) in enumerate(arch.layer_plan())
                      if f == "moe"]
        self.trained = esft_token_selection(
            np.asarray(routed(self.state["params"], first)), moe_layers,
            self.mix["trained_experts"])
        self.frozen = tuple(frozen_groups(self.state["params"]))
        # remat: the step's activations take 5.2 GB without it and 2.7 GB
        # with it (compiled for a v5e), beside the saves' snapshots
        step = make_train_step(arch, opt, frozen=self.frozen,
                               expert_mask=self.trained, remat=True)

        def chipbench_step(state, batch):
            return step(state, batch)

        self.step_keep = jax.jit(chipbench_step)
        self.step_donate = jax.jit(chipbench_step, donate_argnums=0)
        return first

    def traffic_faults(self) -> int:
        # the traffic is over and the check reads the store and the save
        # log, so the live state makes room for the reference
        self.state = None
        ref_loss, ref, routing = reference_step(
            self, self.initial["params"], self.initial["batch"],
            choices=self.readings["choices"])
        self.model_errors = model_errors(
            self.readings, ref_loss, ref, routing,
            _trained_rows(self.initial["params"], self.trained), self.opt)
        del self.initial, ref, routing
        print("model check: " + ", ".join(
            f"{k} {v:.6g}" for k, v in self.model_errors.items()),
            flush=True)
        outside = sum(self.model_errors[k] > limit
                      for k, limit in TOLERANCE.items())
        outside += self.model_errors["frozen_moved"] > 0
        return super().traffic_faults() + int(outside)


def model_errors(got: Dict[str, Any], ref_loss, ref, routing,
                 p0: Dict[str, Any], opt) -> Dict[str, float]:
    """The readings `TOLERANCE` bounds, of the step's readings `got`
    (`step_readings`) against the reference's (`reference_step`, routed
    by `got`'s picks), from the trained rows' initial parameters `p0`
    ({layer: {leaf: rows}}) under the `OptConfig` `opt`: the loss's
    relative error; the worst relative error of a trained slab's first
    moment (`m`), second moment (`nu`), and parameter change against
    AdamW's update of `got`'s own moments (`update`); how far the picks
    fall short of a top-k of the reference's router at worst (`route`,
    ≥ 0); and the frozen leaves and rows that moved."""
    def worst(a, b):
        return max(reference.relative_error(a[i][n][j], b[i][n][j])
                   for i in b for n in b[i] for j in range(len(b[i][n])))

    def f32(x):
        return np.asarray(x, np.float32)

    change = {i: {n: f32(got["params"][i][n]) - f32(p0[i][n])
                  for n in p0[i]} for i in p0}
    update = {i: {n: f32(reference.adamw_params(
        p0[i][n], f32(got["mu"][i][n]), f32(got["nu"][i][n]), 1,
        lr=opt.lr, b1=opt.b1, b2=opt.b2, eps=opt.eps,
        weight_decay=opt.weight_decay).astype(p0[i][n].dtype))
        - f32(p0[i][n]) for n in p0[i]} for i in p0}
    margin = min(float(np.min(np.asarray(m)))
                 for m in routing["margin"].values())
    return {"loss": abs(got["loss"] - float(ref_loss)) / abs(float(ref_loss)),
            "m": worst(got["mu"], ref["mu"]),
            "nu": worst(got["nu"], ref["nu"]),
            "update": worst(change, update),
            "route": max(0.0, -margin),
            "frozen_moved": float(got["frozen_moved"])}
