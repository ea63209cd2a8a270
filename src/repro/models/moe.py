"""Mixture-of-experts FFN with expert-parallel-friendly dispatch.

Routing: token-choice top-k with renormalized weights.  Dispatch uses the
capacity-bounded *per-expert top-C tokens* formulation: a gather into
(E, C, d), per-expert matmuls, scatter-add combine.  Under the production
mesh the expert dimension is sharded over the ``model`` axis (EP); the
combine's partial sums reduce with one psum inserted by SPMD.

Memory: O(E_local * C * d) activations -- no (T, E, C) dispatch one-hots,
which would be ~40 TB for deepseek-v3 at train_4k.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.layers import Spec, mlp_apply, mlp_specs
from repro.parallel.sharding import constrain, get_mesh


def moe_specs(cfg: ArchConfig) -> Dict[str, Spec]:
    m, D = cfg.moe, cfg.d_model
    s = {
        "router": Spec((D, m.n_experts), ("embed", "experts_router"), "normal",
                       1.0, "float32"),
        "wi_gate": Spec((m.n_experts, D, m.d_ff), ("experts", "embed", "mlp")),
        "wi_up": Spec((m.n_experts, D, m.d_ff), ("experts", "embed", "mlp")),
        "wo": Spec((m.n_experts, m.d_ff, D), ("experts", "mlp", "embed")),
    }
    if m.n_shared:
        s["shared"] = mlp_specs(D, m.d_ff * m.n_shared, cfg.act)
    return s


def _moe_local(p, xt, cfg, T, D):
    """Token-choice routing + expert-choice capacity on LOCAL tokens;
    returns (dispatch info, aux).  Shared by the GSPMD and shard_map paths."""
    m = cfg.moe
    logits = (xt.astype(jnp.float32) @ p["router"])      # (T, E)
    gates = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = jax.lax.top_k(gates, m.top_k)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
    sel = jax.nn.one_hot(top_i, m.n_experts, dtype=jnp.float32)
    score_et = (sel * top_w[..., None]).sum(1).T         # (E, T)
    C = int(m.capacity_factor * T * m.top_k / m.n_experts)
    C = max(1, min(T, max(C, min(T, m.top_k))))
    cw, ci = jax.lax.top_k(score_et, C)
    density = sel.sum(1).mean(0)
    mean_gate = gates.mean(0)
    aux = {
        "moe_aux": m.aux_coef * m.n_experts * jnp.sum(density * mean_gate),
        "moe_z": m.router_z_coef * jnp.mean(
            jax.nn.logsumexp(logits, axis=-1) ** 2),
    }
    return cw, ci, C, aux


def _expert_ffn(p, xe, cfg):
    f = jax.nn.silu if cfg.act == "silu" else jax.nn.gelu
    h = f(jnp.einsum("ecd,edf->ecf", xe, p["wi_gate"]))
    h = h * jnp.einsum("ecd,edf->ecf", xe, p["wi_up"])
    return jnp.einsum("ecf,efd->ecd", h, p["wo"])


def apply_moe_ep(p, x, *, cfg, mesh):
    """Explicit expert parallelism via shard_map (EXPERIMENTS §Perf A.3):
    each model shard routes a sequence slice of the local batch, exchanges
    the capacity-selected tokens with an all-to-all over `model`, runs its
    local experts, and all-to-alls the outputs home -- NO full-activation
    all-reduce (the GSPMD-derived path moved 17.9 GB/layer on deepseek-v3).
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    m = cfg.moe
    B, S, D = x.shape
    tp = mesh.shape["model"]
    fsdp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    bspec = fsdp[0] if len(fsdp) == 1 else fsdp

    def block(xb, router, wig, wiu, wo, shared):
        # xb: (B_l, S/tp, D) -- this shard's sequence slice
        Bl, Sl, _ = xb.shape
        T = Bl * Sl
        xt = xb.reshape(T, D)
        lp = {"router": router}
        cw, ci, C, aux = _moe_local(lp, xt, cfg, T, D)
        taken = cw > 0.0
        xe = jnp.take(xt, ci.reshape(-1), axis=0).reshape(m.n_experts, C, D)
        # dispatch: (E, C, D) -> (E/tp, C*tp, D) rows of local experts
        xr = jax.lax.all_to_all(xe, "model", split_axis=0, concat_axis=1,
                                tiled=True)
        wp = {"wi_gate": wig, "wi_up": wiu, "wo": wo}
        yr = _expert_ffn(wp, xr, cfg)                 # (E/tp, C*tp, D)
        # combine: route outputs back to the token-owner shard
        ye = jax.lax.all_to_all(yr, "model", split_axis=1, concat_axis=0,
                                tiled=True)           # (E, C, D)
        ye = ye * (cw * taken).astype(ye.dtype)[..., None]
        out = jnp.zeros((T, D), xb.dtype).at[ci.reshape(-1)].add(
            ye.astype(xb.dtype).reshape(-1, D), mode="drop")
        out = out.reshape(Bl, Sl, D)
        if m.n_shared:
            out = out + mlp_apply(shared, xb, cfg.act)
        # average aux over all shards so the loss is mesh-independent
        for ax in ("model",) + fsdp:
            aux = jax.tree.map(lambda a: jax.lax.pmean(a, ax), aux)
        return out, aux

    shared_p = p.get("shared", {"_": jnp.zeros((), x.dtype)})
    shared_spec = jax.tree.map(lambda _: P(), shared_p)
    out, aux = shard_map(
        block, mesh=mesh,
        in_specs=(P(bspec, "model", None), P(), P("model"), P("model"),
                  P("model"), shared_spec),
        out_specs=(P(bspec, "model", None), P()),
        check_vma=False,
    )(x, p["router"].astype(jnp.float32), p["wi_gate"], p["wi_up"], p["wo"],
      shared_p)
    return out, aux


def apply_moe(
    p: Dict[str, jnp.ndarray],
    x: jnp.ndarray,                         # (B, S, D) normed
    *,
    cfg: ArchConfig,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    m = cfg.moe
    B, S, D = x.shape
    mesh = get_mesh()
    if (mesh is not None and "model" in mesh.axis_names
            and m.n_experts % mesh.shape["model"] == 0
            and S % mesh.shape["model"] == 0):
        return apply_moe_ep(p, x, cfg=cfg, mesh=mesh)
    T = B * S
    xt = x.reshape(T, D)

    logits = (xt.astype(jnp.float32) @ p["router"])      # (T, E)
    gates = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = jax.lax.top_k(gates, m.top_k)         # (T, k)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    # (E, T) routing score matrix restricted to selected pairs
    sel = jax.nn.one_hot(top_i, m.n_experts, dtype=jnp.float32)  # (T,k,E)
    score_et = (sel * top_w[..., None]).sum(1).T         # (E, T)

    # capacity floored at top_k so tiny decode batches never drop tokens
    C = int(m.capacity_factor * T * m.top_k / m.n_experts)
    C = max(1, min(T, max(C, min(T, m.top_k))))
    cw, ci = jax.lax.top_k(score_et, C)                  # (E, C) weights+token ids
    taken = cw > 0.0                                      # padding / unrouted
    xe = jnp.take(xt, ci.reshape(-1), axis=0).reshape(m.n_experts, C, D)

    f = jax.nn.silu if cfg.act == "silu" else jax.nn.gelu
    h = f(jnp.einsum("ecd,edf->ecf", xe, p["wi_gate"]))
    h = h * jnp.einsum("ecd,edf->ecf", xe, p["wi_up"])
    ye = jnp.einsum("ecf,efd->ecd", h, p["wo"])          # (E, C, D)
    ye = ye * (cw * taken).astype(ye.dtype)[..., None]

    # combine: local scatter-add per expert shard, ONE psum of (T, D) in the
    # activation dtype (not f32)
    out = jnp.zeros((T, D), x.dtype).at[ci.reshape(-1)].add(
        ye.astype(x.dtype).reshape(-1, D), mode="drop")
    out = out.reshape(B, S, D)
    out = constrain(out, ("batch", None, None))

    if m.n_shared:
        out = out + mlp_apply(p["shared"], x, cfg.act)

    # aux losses (Switch-style load balance + router z-loss)
    density = sel.sum(1).mean(0)                         # fraction routed per e
    mean_gate = gates.mean(0)
    aux = {
        "moe_aux": m.aux_coef * m.n_experts * jnp.sum(density * mean_gate),
        "moe_z": m.router_z_coef * jnp.mean(
            jax.nn.logsumexp(logits, axis=-1) ** 2),
    }
    return out, aux
