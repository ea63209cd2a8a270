"""Shared model primitives: norms, embeddings, RoPE, MLPs, initializers.

Parameters are plain pytrees (nested dicts of jnp arrays).  Every leaf is
declared through :class:`Spec`, which carries the *logical axes* used by
``parallel/sharding.py`` to derive PartitionSpecs (MaxText-style).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

# ----------------------------------------------------------------------------
# parameter specs
# ----------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Spec:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]     # logical axis names, len == ndim
    init: str = "normal"                # normal | zeros | ones | scaled
    scale: float = 1.0
    dtype: str = "bfloat16"
    fan_in: Optional[int] = None        # preserved across layer stacking

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


# jitted per leaf: the f32 normal draw fuses into the cast, so a stacked
# leaf never lands in HBM in f32 (a 16-layer starcoder2 ``wi`` would be a
# 5.4 GB transient); the values are those of the eager draw
@functools.partial(jax.jit, static_argnums=0)
def _init_leaf(spec: Spec, key) -> jnp.ndarray:
    dt = jnp.dtype(spec.dtype)
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, dt)
    if spec.init == "ones":
        return jnp.ones(spec.shape, dt)
    fan_in = spec.fan_in or (spec.shape[0] if spec.shape else 1)
    std = spec.scale / math.sqrt(max(1, fan_in))
    return (jax.random.normal(key, spec.shape, jnp.float32) * std).astype(dt)


def materialize(tree, key):
    """Spec tree -> concrete parameter tree."""
    leaves, treedef = jax.tree.flatten(tree, is_leaf=lambda x: isinstance(x, Spec))
    keys = jax.random.split(key, len(leaves))
    out = [_init_leaf(l, k) for l, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, out)


def abstract(tree):
    """Spec tree -> ShapeDtypeStruct tree (dry-run: no allocation)."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.dtype(s.dtype)),
        tree, is_leaf=lambda x: isinstance(x, Spec))


def axes_tree(tree):
    """Spec tree -> logical-axes tree."""
    return jax.tree.map(lambda s: s.axes, tree, is_leaf=lambda x: isinstance(x, Spec))


def stack_specs(tree, repeats: int):
    """Add a leading stacked-layer dimension to every Spec in the tree."""
    return jax.tree.map(
        lambda s: Spec((repeats,) + s.shape, ("layers",) + s.axes, s.init,
                       s.scale, s.dtype,
                       s.fan_in or (s.shape[0] if s.shape else 1)),
        tree, is_leaf=lambda x: isinstance(x, Spec))


# ----------------------------------------------------------------------------
# numerics
# ----------------------------------------------------------------------------


def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))
    return out.astype(x.dtype)


def softcap(x: jnp.ndarray, cap: float) -> jnp.ndarray:
    if not cap:
        return x
    return cap * jnp.tanh(x / cap)


def rope_angles(positions: jnp.ndarray, dim: int, theta: float) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """positions: (...,) -> cos/sin of shape (..., dim//2)."""
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
               pct: float = 1.0) -> jnp.ndarray:
    """x: (B, S, H, D); positions: (B, S) or (S,). Partial rotary via pct."""
    d = x.shape[-1]
    rot = int(d * pct)
    rot -= rot % 2
    if rot == 0:
        return x
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    cos, sin = rope_angles(positions, rot, theta)          # (B,S,rot/2)
    cos = cos[..., None, :].astype(x.dtype)                # (B,S,1,rot/2)
    sin = sin[..., None, :].astype(x.dtype)
    x1, x2 = x_rot[..., ::2], x_rot[..., 1::2]
    o1 = x1 * cos - x2 * sin
    o2 = x2 * cos + x1 * sin
    out = jnp.stack([o1, o2], axis=-1).reshape(x_rot.shape)
    return jnp.concatenate([out, x_pass], axis=-1)


def activation(name: str) -> Callable:
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu}[name]


# ----------------------------------------------------------------------------
# dense MLP
# ----------------------------------------------------------------------------


def mlp_specs(d_model: int, d_ff: int, act: str) -> Dict[str, Spec]:
    if act == "silu":  # SwiGLU: gate + up
        return {
            "wi_gate": Spec((d_model, d_ff), ("embed", "mlp")),
            "wi_up": Spec((d_model, d_ff), ("embed", "mlp")),
            "wo": Spec((d_ff, d_model), ("mlp", "embed")),
        }
    return {
        "wi": Spec((d_model, d_ff), ("embed", "mlp")),
        "wo": Spec((d_ff, d_model), ("mlp", "embed")),
    }


def mlp_apply(p: Dict[str, jnp.ndarray], x: jnp.ndarray, act: str) -> jnp.ndarray:
    f = activation(act)
    if "wi_gate" in p:
        h = f(x @ p["wi_gate"]) * (x @ p["wi_up"])
    else:
        h = f(x @ p["wi"])
    return h @ p["wo"]
