"""Gradient compression for the data-parallel all-reduce: int8 ring
reduce-scatter + all-gather with per-chunk scales and error feedback.

Wire bytes: 1 byte/element/hop instead of 4 (f32) or 2 (bf16) -- the
standard distributed-optimization trick for DCI-limited multi-pod meshes
(the 'pod' axis crosses data-center interconnect at a fraction of ICI
bandwidth).  Error feedback keeps the quantization bias out of the
optimizer: the residual of each step is added back before the next
quantization (Karimireddy et al. '19).

Implemented with shard_map + ppermute so the int8 wire format is explicit
in the HLO (XLA cannot be asked to compress a psum).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map


def _quantize(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    scale = jnp.max(jnp.abs(x)) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _dequantize(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    return q.astype(jnp.float32) * scale


def ring_allreduce_int8(x: jnp.ndarray, axis: str) -> jnp.ndarray:
    """Inside shard_map: ring reduce-scatter + ring all-gather, int8 wire.

    x: (n*chunk, ...) flat leading dim divisible by axis size.
    """
    n = jax.lax.axis_size(axis)
    idx = jax.lax.axis_index(axis)
    chunks = x.reshape((n, -1) + x.shape[1:])        # (n, chunk, ...)
    fwd = [(i, (i + 1) % n) for i in range(n)]

    # --- reduce-scatter: after n-1 hops, rank i owns the sum of chunk i+1 ---
    def rs_step(s, carry):
        acc = carry
        # send the partial for chunk (idx - s), receive for (idx - s - 1)
        send_i = (idx - s) % n
        part = acc[send_i]
        q, scale = _quantize(part)
        q_r = jax.lax.ppermute(q, axis, fwd)
        s_r = jax.lax.ppermute(scale, axis, fwd)
        recv_i = (idx - s - 1) % n
        acc = acc.at[recv_i].add(_dequantize(q_r, s_r))
        return acc

    acc = jax.lax.fori_loop(0, n - 1, rs_step, chunks)
    own = (idx + 1) % n
    owned = acc[own]                                  # fully reduced chunk

    # --- all-gather: n-1 hops of the owned (quantized once) chunk ---
    q0, s0 = _quantize(owned)

    def ag_step(s, carry):
        out, q, sc = carry
        q = jax.lax.ppermute(q, axis, fwd)
        sc = jax.lax.ppermute(sc, axis, fwd)
        src = (idx - s) % n                           # whose chunk arrived
        out = out.at[src].set(_dequantize(q, sc))
        return out, q, sc

    out0 = jnp.zeros_like(chunks).at[own].set(owned)
    out, _, _ = jax.lax.fori_loop(0, n - 1, ag_step, (out0, q0, s0))
    return out.reshape(x.shape)


def compressed_psum(x: jnp.ndarray, mesh: Mesh, axis: str = "data"):
    """jit-able compressed all-reduce over one mesh axis (replicated in/out)."""
    n = mesh.shape[axis]
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % n
    flat = jnp.pad(flat, (0, pad))

    fn = shard_map(functools.partial(ring_allreduce_int8, axis=axis),
                   mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False)
    out = fn(flat)
    return out[: x.size].reshape(x.shape)


def compress_with_feedback(grads, residual, mesh: Mesh, axis: str = "data"):
    """Error-feedback wrapper: g' = AR_int8(g + r); r' = (g + r) - g'_local.

    The residual tree lives in the optimizer state; quantization error does
    not accumulate across steps.
    """
    def one(g, r):
        gr = g.astype(jnp.float32) + r
        reduced = compressed_psum(gr, mesh, axis)
        n = mesh.shape[axis]
        mean = reduced / n
        new_r = gr - mean   # local error kept for the next step
        return mean.astype(g.dtype), new_r

    flat_g, tdef = jax.tree.flatten(grads)
    flat_r = tdef.flatten_up_to(residual)
    outs = [one(g, r) for g, r in zip(flat_g, flat_r)]
    return tdef.unflatten([o[0] for o in outs]), \
        tdef.unflatten([o[1] for o in outs])
