"""Train-step factory: loss + grad + optimizer update as one pjit-able
function over TrainState = {"params", "opt", "step" [, "ef"]}.

Features wired for scale:
  * microbatch gradient accumulation (python-unrolled: each microbatch's
    backward reduce-scatters as it finishes — compute/comm overlap under
    XLA's latency-hiding scheduler; unrolled loops also keep HLO cost
    accounting exact for the roofline),
  * activation checkpointing (remat) per layer,
  * frozen-parameter masks (updates zeroed; paths exported for Chipmink's
    active-variable filter — provably clean pods),
  * expert-row masks inside stacked expert leaves (expert-specialized
    fine-tuning, ESFT: only the listed experts train; the other rows keep
    their parameters and optimizer state bit-identical),
  * optional int8 error-feedback gradient compression,
  * MoE touch-report: the step's routed-token counts per expert,
    returned in metrics.
"""
from __future__ import annotations

import functools
from typing import (Any, Callable, Dict, List, Mapping, Optional, Sequence,
                    Tuple)

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ArchConfig
from ..models.model import api
from .grad_compress import tree_quantize_dequantize
from .optimizer import (OptConfig, clip_by_global_norm, is_frozen, opt_init,
                        opt_update)


def init_train_state(cfg: ArchConfig, params: Any, opt_cfg: OptConfig,
                     grad_compress: bool = False) -> Dict:
    state = {"params": params, "opt": opt_init(params, opt_cfg),
             "step": jnp.zeros((), jnp.int32)}
    if grad_compress:
        state["ef"] = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.bfloat16), params)
    return state


def _zero_frozen(grads: Any, frozen: Sequence[str], prefix=()) -> Any:
    if not frozen:
        return grads
    if isinstance(grads, dict):
        return {k: _zero_frozen(v, frozen, prefix + (k,))
                for k, v in grads.items()}
    return jnp.zeros_like(grads) if is_frozen(prefix, frozen) else grads


EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def expert_row_masks(cfg: ArchConfig,
                     expert_mask: Mapping[Any, Sequence[int]]
                     ) -> Dict[str, np.ndarray]:
    """{MoE layer index: (held,) bool, True for the held experts that
    train} from {layer index: held-expert indices}; a MoE layer that the
    mapping leaves out trains none of its experts."""
    want = {str(k): tuple(int(e) for e in v) for k, v in expert_mask.items()}
    held = cfg.moe.held
    out = {}
    for i, (_mixer, ffn) in enumerate(cfg.layer_plan()):
        if ffn == "moe":
            rows = np.zeros(held, bool)
            rows[list(want.pop(str(i), ()))] = True
            out[str(i)] = rows
    if want:
        raise ValueError(f"expert mask names layers without experts: "
                         f"{sorted(want)}")
    return out


def _select_rows(new: Any, old: Any, masks: Dict[str, np.ndarray],
                 path=()) -> Any:
    """`new`, except in the expert rows that `masks` does not train, which
    come from `old`.  Walks a params tree or an optimizer state whose
    subtrees mirror it (AdamW's `mu`/`nu`, Adafactor's factors)."""
    if isinstance(new, dict):
        return {k: _select_rows(new[k], old[k], masks, path + (k,))
                for k in new}
    for j in range(len(path) - 3):
        if (path[j] == "layers" and path[j + 1] in masks
                and path[j + 2] == "ffn" and path[j + 3] in EXPERT_LEAVES):
            rows = masks[path[j + 1]]
            rows = rows.reshape(rows.shape + (1,) * (new.ndim - 1))
            return jnp.where(rows, new, old)
    return new


def make_train_step(cfg: ArchConfig, opt_cfg: OptConfig, *,
                    microbatches: int = 1,
                    frozen: Sequence[str] = (),
                    expert_mask: Optional[Mapping[Any, Sequence[int]]] = None,
                    grad_compress: bool = False,
                    q_chunk: Optional[int] = None,
                    remat: Optional[bool] = None,
                    microbatch_scan: bool = False,
                    accum_dtype=jnp.float32) -> Callable:
    """`microbatch_scan=True` runs microbatches under `lax.scan` (small HLO;
    note: HLO cost analysis counts the body once — the roofline harness
    multiplies by the trip count).  `accum_dtype=bf16` halves the gradient-
    accumulation residency for 100B+ models."""
    m = api(cfg)
    remat = cfg.remat if remat is None else remat
    # frozen specs may be given as state paths ("params/layers/0") or
    # params-subtree paths ("layers/0"); normalize to the latter since the
    # masks walk the params tree
    frozen = tuple(f[len("params/"):] if f.startswith("params/") else f
                   for f in frozen)
    masks = None if expert_mask is None else expert_row_masks(cfg,
                                                              expert_mask)

    def loss_fn(params, mb):
        return m.loss_fn(params, mb, cfg, q_chunk=q_chunk, remat=remat)

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def train_step(state: Dict, batch: Dict) -> Tuple[Dict, Dict]:
        params = state["params"]
        if microbatches == 1:
            (loss, metrics), grads = grad_fn(params, batch)
        else:
            def slice_mb(x, i):
                mb = x.shape[0] // microbatches
                return jax.lax.dynamic_slice_in_dim(x, i * mb, mb, axis=0)

            if microbatch_scan:
                def body(carry, i):
                    acc, loss_acc = carry
                    mb = jax.tree.map(lambda x: slice_mb(x, i), batch)
                    (l, met), g = grad_fn(params, mb)
                    acc = jax.tree.map(
                        lambda a, b: a + (b / microbatches).astype(a.dtype),
                        acc, g)
                    return (acc, loss_acc + l / microbatches), met

                acc0 = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, accum_dtype), params)
                (grads, loss), mets = jax.lax.scan(
                    body, (acc0, jnp.zeros((), jnp.float32)),
                    jnp.arange(microbatches))
                metrics = jax.tree.map(lambda x: x[-1], mets)
            else:
                loss = 0.0
                metrics: Dict = {}
                grads = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, accum_dtype), params)
                for i in range(microbatches):  # unrolled: overlap + exact HLO
                    mb = jax.tree.map(lambda x: slice_mb(x, i), batch)
                    (l, met), g = grad_fn(params, mb)
                    loss = loss + l / microbatches
                    grads = jax.tree.map(
                        lambda a, b: a + (b / microbatches).astype(a.dtype),
                        grads, g)
                    metrics = met  # keep last microbatch's aux
            metrics["nll"] = loss

        grads = _zero_frozen(grads, frozen)
        if masks is not None:
            grads = _select_rows(grads, jax.tree.map(jnp.zeros_like, grads),
                                 masks)
        new_ef = None
        if grad_compress:
            grads, new_ef = tree_quantize_dequantize(grads, state.get("ef"))
        grads, gnorm = clip_by_global_norm(grads, opt_cfg.clip_norm)
        new_params, new_opt = opt_update(grads, state["opt"], params,
                                         state["step"], opt_cfg)
        # frozen leaves pass through IDENTICALLY (ASCC proves them
        # read-only; Chipmink skips their pods without hashing)
        if frozen:
            def keep_frozen(new, old, prefix=()):
                if isinstance(new, dict):
                    return {k: keep_frozen(new[k], old[k], prefix + (k,))
                            for k in new}
                return old if is_frozen(prefix, frozen) else new
            new_params = keep_frozen(new_params, params)
        if masks is not None:
            new_params = _select_rows(new_params, params, masks)
            new_opt = _select_rows(new_opt, state["opt"], masks)

        new_state = {"params": new_params, "opt": new_opt,
                     "step": state["step"] + 1}
        if grad_compress:
            new_state["ef"] = new_ef
        metrics = dict(metrics)
        metrics["grad_norm"] = gnorm
        return new_state, metrics

    return train_step


def touched_prefixes_from_metrics(cfg: ArchConfig, metrics: Dict,
                                  frozen: Sequence[str] = ()) -> Optional[List[str]]:
    """Derive Chipmink `touched_prefixes` from the step's touch report.

    For MoE models the report is `expert_counts` (n_moe_layers, X), the
    last step's routed tokens per expert.  It does not prove an expert
    clean: under AdamW an expert that got no token still moves (weight
    decay; moments that decay from earlier steps), so every expert leaf
    that is not frozen is touched.  Rows that an ESFT `expert_mask`
    freezes inside a touched leaf stay bit-identical, and chunk-level
    change detection finds them clean.  Returns None (= everything may be
    touched) when no report is available.
    """
    if "expert_counts" not in metrics or cfg.moe is None:
        return None
    plan = cfg.layer_plan()
    moe_layers = [i for i, (_mx, f) in enumerate(plan) if f == "moe"]
    touched: List[str] = []
    # non-expert state is always (potentially) touched
    touched.append("params/embed")
    if not cfg.tie_embeddings:
        touched.append("params/lm_head")
    touched.append("params/final_norm")
    if cfg.vlm is not None:
        touched.append("params/patch_proj")
    for layer in moe_layers:
        base = f"params/layers/{layer}"
        for name in ("norm1", "norm2"):
            touched.append(f"{base}/{name}")
        touched.append(f"{base}/attn")
        # the AVF works at leaf granularity and the expert leaves stack
        # every expert's rows; chunk-level change detection isolates the
        # rows that moved
        touched.append(f"{base}/ffn")
    for i, (_mx, f) in enumerate(plan):
        if f != "moe":
            touched.append(f"params/layers/{i}")
    # optimizer/step mirror params
    touched.extend(["opt", "step", "ef", "data"])
    return [t for t in touched
            if not any(t == f or t.startswith(f + "/") for f in frozen)]
