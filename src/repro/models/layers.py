"""Shared model layers: norms, projections, rotary embeddings (RoPE and
Qwen2-VL's multimodal M-RoPE), MLPs.

All layers are pure functions over param dicts.  Parameter shapes, dtypes,
logical sharding axes and initializers are declared once via `ParamDef`
tables (models/<arch>.py builds them); the same table drives real
initialization (smoke tests, examples) and abstract ShapeDtypeStruct
construction (the multi-pod dry-run — no allocation).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Path = Tuple[str, ...]


@dataclasses.dataclass(frozen=True)
class ParamDef:
    shape: Tuple[int, ...]
    dtype: Any
    axes: Tuple[Optional[str], ...]   # logical axis names, len == ndim
    init: str = "normal"              # normal | zeros | ones | embed

    def scale(self) -> float:
        if self.init == "embed":
            # unit-variance activations after the sqrt(d_model) input scale,
            # ~N(0,1) logits under tied embeddings
            return 1.0 / float(self.shape[-1]) ** 0.5
        fan_in = self.shape[0] if len(self.shape) >= 1 else 1
        if len(self.shape) >= 2:
            fan_in = int(np.prod(self.shape[:-1]))
        return 1.0 / max(1.0, float(fan_in)) ** 0.5


ParamDefs = Dict[Path, ParamDef]


def init_params(defs: ParamDefs, key: jax.Array, dtype=jnp.bfloat16) -> Dict:
    """Materialize parameters from defs (used by smoke tests / examples)."""
    flat: Dict[Path, jax.Array] = {}
    keys = jax.random.split(key, max(len(defs), 1))
    for (path, d), k in zip(sorted(defs.items()), keys):
        dt = d.dtype or dtype
        if d.init == "zeros":
            v = jnp.zeros(d.shape, dt)
        elif d.init == "ones":
            v = jnp.ones(d.shape, dt)
        else:
            v = (jax.random.normal(k, d.shape, jnp.float32) * d.scale()).astype(dt)
        flat[path] = v
    return unflatten(flat)


def abstract_params(defs: ParamDefs, dtype=jnp.bfloat16) -> Dict:
    """ShapeDtypeStruct tree (dry-run: no allocation)."""
    flat = {p: jax.ShapeDtypeStruct(d.shape, d.dtype or dtype)
            for p, d in defs.items()}
    return unflatten(flat)


def logical_axes(defs: ParamDefs) -> Dict:
    flat = {p: d.axes for p, d in defs.items()}
    return unflatten(flat)


def unflatten(flat: Dict[Path, Any]) -> Dict:
    out: Dict[str, Any] = {}
    for path, v in flat.items():
        cur = out
        for p in path[:-1]:
            cur = cur.setdefault(p, {})
        cur[path[-1]] = v
    return out


# ---------------------------------------------------------------------------
# norms / projections
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(dt)


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


def dense(x: jax.Array, w: jax.Array, b: Optional[jax.Array] = None) -> jax.Array:
    y = jnp.einsum("...d,df->...f", x, w)
    if b is not None:
        y = y + b.astype(y.dtype)
    return y


def swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
           w_down: jax.Array) -> jax.Array:
    g = dense(x, w_gate)
    u = dense(x, w_up)
    return dense(jax.nn.silu(g) * u, w_down)


def gelu_mlp(x: jax.Array, w_up: jax.Array, b_up: Optional[jax.Array],
             w_down: jax.Array, b_down: Optional[jax.Array]) -> jax.Array:
    h = jax.nn.gelu(dense(x, w_up, b_up), approximate=True)
    return dense(h, w_down, b_down)


# ---------------------------------------------------------------------------
# rotary embeddings
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float = 10000.0) -> jax.Array:
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponent)           # (head_dim/2,)


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature, 0.1 · mscale · ln(factor) + 1."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_freqs(head_dim: int, theta: float, factor: float,
               original_max_position: int, beta_fast: float,
               beta_slow: float) -> jax.Array:
    """YaRN's rotary frequencies [arXiv:2309.00071], as DeepSeek-V2 sets
    them: lanes that turn more than `beta_fast` times over the original
    context keep their frequency, lanes that turn fewer than `beta_slow`
    times are divided by `factor`, and a linear ramp joins the two."""
    def lane(rotations: float) -> float:
        return (head_dim * math.log(original_max_position
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(lane(beta_fast)), 0)
    high = min(math.ceil(lane(beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    extra = rope_freqs(head_dim, theta)
    return extra / factor * ramp + extra * (1.0 - ramp)


def apply_rope(x: jax.Array, positions: jax.Array,
               theta: float = 10000.0, inv_freq: Optional[jax.Array] = None,
               mscale: float = 1.0) -> jax.Array:
    """x: (..., S, H, D); positions: broadcastable to (..., S).  Rotary
    pairs are (i, i + D/2).  `inv_freq` (D/2,) replaces the plain
    frequencies of `theta` (YaRN); `mscale` multiplies cos and sin."""
    D = x.shape[-1]
    inv = rope_freqs(D, theta) if inv_freq is None else inv_freq  # (D/2,)
    ang = positions[..., None].astype(jnp.float32) * inv      # (..., S, D/2)
    cos = mscale * jnp.cos(ang)[..., None, :]                 # (..., S, 1, D/2)
    sin = mscale * jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x: jax.Array, positions: jax.Array,
                sections: Sequence[int], theta: float = 10000.0) -> jax.Array:
    """Qwen2-VL multimodal rotary embedding [arXiv:2409.12191].

    x: (B, S, H, D); positions: (3, B, S) — temporal / height / width ids.
    The D/2 frequency lanes are partitioned into `sections` (t, h, w); each
    section rotates by its own position stream.
    """
    D = x.shape[-1]
    inv = rope_freqs(D, theta)                                # (D/2,)
    assert sum(sections) == D // 2, (sections, D)
    pieces = []
    start = 0
    for sec, pos in zip(sections, positions):
        ang = pos[..., None].astype(jnp.float32) * inv[start:start + sec]
        pieces.append(ang)                                    # (B, S, sec)
        start += sec
    ang = jnp.concatenate(pieces, axis=-1)                    # (B, S, D/2)
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)
