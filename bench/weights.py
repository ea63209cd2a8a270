"""Seeded random weights in the layout the served path reads, made on the
device in one jitted call and in the type they are served in.

Each element is a counter-based hash of (seed, leaf, element index) turned
into a uniform draw with the leaf's standard deviation, so the whole tree
is one elementwise program per leaf fused into its cast: no float32 copy of
a leaf ever lands in device memory, and it compiles in seconds.  The
benchmark makes the weights itself, so the plain reference can use them
without taking anything the program made.

Layout (a dense GQA stack in one group)::

    embed (Vp, D) · final_norm (D,) · lm_head (D, Vp)
    groups.g0.p0: norm1, norm2 (L, D); attn.wq (L, D, H, hd),
      attn.wk / attn.wv (L, D, Hkv, hd), attn.wo (L, H, hd, D),
      attn.q_scale / attn.k_scale (L, hd) with qk-norm;
      mlp.wi (L, D, F), mlp.wo (L, F, D) for GELU, or
      mlp.wi_gate / mlp.wi_up (L, D, F), mlp.wo (L, F, D) for SwiGLU

Norm scales are drawn too (standard deviation 0.1 around the ``1 + w``
convention), so the reference checks that each scale is applied.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

__all__ = ["leaf_specs", "make_params", "vocab_padded"]


def vocab_padded(vocab: int) -> int:
    return (vocab + 255) // 256 * 256


def leaf_specs(m: Dict) -> Dict[str, Tuple[Tuple[int, ...], float]]:
    """Path -> (shape, standard deviation) of every leaf, in a fixed order
    (the order numbers the leaves in the hash)."""
    L, D, H, Hkv, hd, F = (m["layers"], m["d_model"], m["n_heads"],
                           m["n_kv_heads"], m["head_dim"], m["d_ff"])
    Vp = vocab_padded(m["vocab"])
    s = {
        "embed": ((Vp, D), 1.0),
        "final_norm": ((D,), 0.1),
        "lm_head": ((D, Vp), 1 / math.sqrt(D)),
        "norm1": ((L, D), 0.1),
        "norm2": ((L, D), 0.1),
        "attn.wq": ((L, D, H, hd), 1 / math.sqrt(D)),
        "attn.wk": ((L, D, Hkv, hd), 1 / math.sqrt(D)),
        "attn.wv": ((L, D, Hkv, hd), 1 / math.sqrt(D)),
        "attn.wo": ((L, H, hd, D), 1 / math.sqrt(H * hd)),
    }
    if m["qk_norm"]:
        s["attn.q_scale"] = ((L, hd), 0.1)
        s["attn.k_scale"] = ((L, hd), 0.1)
    if m["act"] == "silu":
        s["mlp.wi_gate"] = ((L, D, F), 1 / math.sqrt(D))
        s["mlp.wi_up"] = ((L, D, F), 1 / math.sqrt(D))
    else:
        s["mlp.wi"] = ((L, D, F), 1 / math.sqrt(D))
    s["mlp.wo"] = ((L, F, D), 1 / math.sqrt(F))
    return s


def _fmix32(h):
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


def _draw(shape, std, salt, dtype):
    """Uniform draw with standard deviation ``std`` from a hash of each
    element's linear index and ``salt`` (uint32[3]: seed words, leaf)."""
    idx = jnp.zeros(shape, jnp.uint32)
    stride = 1
    for ax in range(len(shape) - 1, -1, -1):
        idx = idx + jax.lax.broadcasted_iota(jnp.uint32, shape, ax) * \
            jnp.uint32(stride & 0xFFFFFFFF)
        stride *= shape[ax]
    h = _fmix32(idx * jnp.uint32(0x9E3779B1) + salt[0])
    h = _fmix32(h ^ salt[1])
    h = _fmix32(h + salt[2] * jnp.uint32(0x632BE5AB))
    u = (h >> 8).astype(jnp.float32) * (1.0 / (1 << 24))      # [0, 1)
    return ((u * 2.0 - 1.0) * (std * math.sqrt(3.0))).astype(dtype)


@functools.partial(jax.jit, static_argnames=("spec", "dtype"))
def _make(salt, *, spec, dtype):
    out = {}
    for i, (path, (shape, std)) in enumerate(spec):
        leaf_salt = jnp.stack([salt[0], salt[1], jnp.uint32(i + 1)])
        out[path] = _draw(shape, std, leaf_salt, dtype)
    return out


def make_params(m: Dict, seed: int, dtype: str = "bfloat16") -> Dict:
    """The whole parameter tree for model spec ``m`` from ``seed`` (any
    non-negative whole number; both 32-bit words of it are used)."""
    seed = int(seed) % (1 << 64)
    salt = jnp.asarray([seed & 0xFFFFFFFF, seed >> 32], jnp.uint32)
    spec = tuple(leaf_specs(m).items())
    flat = _make(salt, spec=spec, dtype=jnp.dtype(dtype).name)
    layer: Dict = {"attn": {}, "mlp": {}}
    for path, a in flat.items():
        if path in ("embed", "final_norm", "lm_head"):
            continue
        head, _, tail = path.partition(".")
        if tail:
            layer[head][tail] = a
        else:
            layer[head] = a
    return {"embed": flat["embed"], "final_norm": flat["final_norm"],
            "lm_head": flat["lm_head"], "groups": {"g0": {"p0": layer}}}
