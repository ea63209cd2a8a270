"""Model assembly: embeddings -> scanned layer groups -> norm -> logits.

Every architecture in configs/ compiles through this one function.  Layer
groups are executed with ``jax.lax.scan`` over pattern repeats (weights
stacked on a leading "layers" axis), so compile time is O(pattern), not
O(depth) -- a 100-layer model compiles one pattern body.

Modes:
  train:   full-seq forward (+ caller takes grads); returns (logits, aux)
  prefill: full-seq forward, returns (logits, cache)
  decode:  one token per sequence against the cache, returns (logits, cache)
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, LayerSpec
from repro.models import layers as L
from repro.models.attention import apply_attn, attn_specs
from repro.models.moe import apply_moe, moe_specs
from repro.parallel.sharding import constrain
from repro.models.ssm import (_st_write, apply_mamba2, apply_rwkv6,
                              mamba2_dims, mamba2_specs, rwkv6_dims,
                              rwkv6_specs)

Spec = L.Spec


# ============================================================================
# parameter specs
# ============================================================================


def _layer_specs(cfg: ArchConfig, spec: LayerSpec) -> Dict[str, Any]:
    D = cfg.d_model
    s: Dict[str, Any] = {}
    if spec.mixer == "attn":
        s["norm1"] = Spec((D,), ("embed",), "zeros")
        s["attn"] = attn_specs(cfg, spec.attn_kind)
        if cfg.post_norms:
            s["post_norm1"] = Spec((D,), ("embed",), "zeros")
    elif spec.mixer == "mamba2":
        s["norm1"] = Spec((D,), ("embed",), "zeros")
        s["mamba"] = mamba2_specs(cfg)
    elif spec.mixer == "rwkv6":
        s["norm1"] = Spec((D,), ("embed",), "zeros")
        s["norm_cm"] = Spec((D,), ("embed",), "zeros")
        s["rwkv"] = rwkv6_specs(cfg)
    if spec.mlp == "dense":
        if not spec.parallel:           # a parallel MLP shares norm1
            s["norm2"] = Spec((D,), ("embed",), "zeros")
        s["mlp"] = L.mlp_specs(D, cfg.d_ff, cfg.act)
        if cfg.post_norms:
            s["post_norm2"] = Spec((D,), ("embed",), "zeros")
    elif spec.mlp == "moe":
        s["norm2"] = Spec((D,), ("embed",), "zeros")
        s["moe"] = moe_specs(cfg)
    return s


def build_specs(cfg: ArchConfig) -> Dict[str, Any]:
    D, V = cfg.d_model, cfg.vocab_padded
    specs: Dict[str, Any] = {
        "embed": Spec((V, D), ("vocab", "embed"), "normal", 1.0),
        "final_norm": Spec((D,), ("embed",), "zeros"),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = Spec((D, V), ("embed", "vocab"))
    groups = {}
    for gi, g in enumerate(cfg.groups):
        pat = {f"p{pi}": L.stack_specs(_layer_specs(cfg, ls), g.repeats)
               for pi, ls in enumerate(g.pattern)}
        groups[f"g{gi}"] = pat
    specs["groups"] = groups
    if any(ls.shared_attn for g in cfg.groups for ls in g.pattern):
        specs["shared_attn"] = {
            "norm": Spec((D,), ("embed",), "zeros"),
            "attn": attn_specs(cfg, "full"),
        }
    if cfg.encoder_groups:
        egroups = {}
        for gi, g in enumerate(cfg.encoder_groups):
            pat = {f"p{pi}": L.stack_specs(_layer_specs(cfg, ls), g.repeats)
                   for pi, ls in enumerate(g.pattern)}
            egroups[f"g{gi}"] = pat
        specs["encoder"] = {"groups": egroups,
                            "final_norm": Spec((D,), ("embed",), "zeros"),
                            "pos_embed": Spec((cfg.n_frontend_tokens, D),
                                              ("seq", "embed"), "normal", 1.0)}
    if cfg.mtp:
        specs["mtp"] = {
            "norm_h": Spec((D,), ("embed",), "zeros"),
            "norm_e": Spec((D,), ("embed",), "zeros"),
            "proj": Spec((2 * D, D), ("embed2", "embed")),
            "layer": _layer_specs(cfg, LayerSpec(mixer="attn", attn_kind="full",
                                                 mlp="dense")),
        }
    return specs


def init_params(cfg: ArchConfig, key) -> Dict[str, Any]:
    return L.materialize(build_specs(cfg), key)


def abstract_params(cfg: ArchConfig):
    return L.abstract(build_specs(cfg))


def params_logical_axes(cfg: ArchConfig):
    return L.axes_tree(build_specs(cfg))


# ============================================================================
# caches
# ============================================================================


def _layer_cache_spec(cfg: ArchConfig, spec: LayerSpec, batch: int, seq: int,
                      dtype) -> Dict[str, Any]:
    Hkv, hd = cfg.n_kv_heads, cfg.head_dim_
    out: Dict[str, Any] = {}
    if spec.mixer == "attn":
        if spec.attn_kind == "mla":
            m = cfg.mla
            out["ckv"] = ((batch, seq, m.kv_lora_rank),
                          ("batch", "kv_seq", None))
            out["k_rope"] = ((batch, seq, m.qk_rope_head_dim),
                             ("batch", "kv_seq", None))
        elif spec.attn_kind == "cross":
            t = cfg.n_frontend_tokens
            out["k"] = ((batch, t, Hkv, hd), ("batch", None, "kv_heads", None))
            out["v"] = ((batch, t, Hkv, hd), ("batch", None, "kv_heads", None))
        else:
            out["k"] = ((batch, seq, Hkv, hd),
                        ("batch", "kv_seq", "kv_heads", None))
            out["v"] = ((batch, seq, Hkv, hd),
                        ("batch", "kv_seq", "kv_heads", None))
    elif spec.mixer == "mamba2":
        d_inner, nh, ds, dc = mamba2_dims(cfg)
        out["conv"] = ((batch, dc - 1, d_inner + 2 * ds),
                       ("batch", None, "mlp_state"))
        out["ssm"] = ((batch, nh, ds, cfg.ssm.head_dim),
                      ("batch", "heads", None, None))
    elif spec.mixer == "rwkv6":
        H, hd6 = rwkv6_dims(cfg)
        out["state"] = ((batch, H, hd6, hd6), ("batch", "heads", None, None))
        out["tm_shift"] = ((batch, cfg.d_model), ("batch", None))
        out["cm_shift"] = ((batch, cfg.d_model), ("batch", None))
    if spec.shared_attn:
        out["shared_k"] = ((batch, seq, Hkv, hd),
                           ("batch", "kv_seq", "kv_heads", None))
        out["shared_v"] = ((batch, seq, Hkv, hd),
                           ("batch", "kv_seq", "kv_heads", None))
    return out


def cache_shapes(cfg: ArchConfig, batch: int, seq: int, dtype="bfloat16"):
    """Returns (ShapeDtypeStruct tree, logical-axes tree) for the decode cache.

    Cache state arrays are fp32 (ssm/rwkv states); K/V are model dtype.
    """
    shapes: Dict[str, Any] = {"pos": jax.ShapeDtypeStruct((batch,), jnp.int32)}
    axes: Dict[str, Any] = {"pos": ("batch",)}
    sh_groups, ax_groups = {}, {}
    for gi, g in enumerate(cfg.groups):
        sh_pat, ax_pat = {}, {}
        for pi, ls in enumerate(g.pattern):
            lc = _layer_cache_spec(cfg, ls, batch, seq, dtype)
            sh, ax = {}, {}
            for name, (shape, a) in lc.items():
                dt = jnp.float32 if name in ("ssm", "state") else jnp.dtype(dtype)
                sh[name] = jax.ShapeDtypeStruct((g.repeats,) + shape, dt)
                ax[name] = ("layers",) + a
            if sh:
                sh_pat[f"p{pi}"] = sh
                ax_pat[f"p{pi}"] = ax
        sh_groups[f"g{gi}"] = sh_pat
        ax_groups[f"g{gi}"] = ax_pat
    shapes["groups"] = sh_groups
    axes["groups"] = ax_groups
    return shapes, axes


def init_cache(cfg: ArchConfig, batch: int, seq: int, dtype="bfloat16"):
    shapes, _ = cache_shapes(cfg, batch, seq, dtype)
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)


# ============================================================================
# forward
# ============================================================================


def _remat(cfg: ArchConfig, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    return jax.checkpoint(fn)


def _apply_layer(lp, spec: LayerSpec, x, *, cfg, mode, lcache, pos, kv_source,
                 shared_params, layer_idx=None):
    """One pattern-position layer. Returns (x, new_lcache, aux).  With
    ``spec.parallel`` the dense MLP reads the attention's own norm of the
    layer's input: ``x + attn(norm1(x)) + mlp(norm1(x))``."""
    aux = {"moe_aux": jnp.zeros((), jnp.float32),
           "moe_z": jnp.zeros((), jnp.float32)}
    new_cache: Dict[str, Any] = {}

    if spec.mixer == "attn":
        h = L.rms_norm(x, lp["norm1"], cfg.norm_eps)
        o, c = apply_attn(lp["attn"], h, cfg=cfg, kind=spec.attn_kind,
                          mode=mode,
                          cache=lcache if lcache else None,
                          pos=pos, kv_source=kv_source, causal=spec.causal,
                          layer_idx=layer_idx)
        if cfg.post_norms:
            o = L.rms_norm(o, lp["post_norm1"], cfg.norm_eps)
        x = x + o
        if c:
            new_cache.update(c)
    elif spec.mixer == "mamba2":
        h = L.rms_norm(x, lp["norm1"], cfg.norm_eps)
        o, c = apply_mamba2(lp["mamba"], h, cfg=cfg, mode=mode,
                            cache=lcache if lcache else None,
                            layer_idx=layer_idx)
        x = x + o
        if c:
            new_cache.update(c)
    elif spec.mixer == "rwkv6":
        h = L.rms_norm(x, lp["norm1"], cfg.norm_eps)
        tm_out, cm_fn, c = apply_rwkv6(lp["rwkv"], h, None, cfg=cfg, mode=mode,
                                       cache=lcache if lcache else None,
                                       layer_idx=layer_idx)
        x = x + tm_out
        hc = L.rms_norm(x, lp["norm_cm"], cfg.norm_eps)
        cm_out, cm_shift = cm_fn(hc)
        x = x + cm_out
        if c is not None:
            new_cache.update(c)
            if mode == "decode":
                new_cache["cm_shift"] = _st_write(lcache["cm_shift"],
                                                  layer_idx, cm_shift)
            else:
                new_cache["cm_shift"] = cm_shift

    if spec.shared_attn:
        h = L.rms_norm(x, shared_params["norm"], cfg.norm_eps)
        scache = None
        if lcache and "shared_k" in lcache:
            scache = {"k": lcache["shared_k"], "v": lcache["shared_v"]}
        o, c = apply_attn(shared_params["attn"], h, cfg=cfg, kind="full",
                          mode=mode, cache=scache, pos=pos,
                          layer_idx=layer_idx)
        x = x + o
        if c:
            new_cache["shared_k"] = c["k"]
            new_cache["shared_v"] = c["v"]

    if spec.mlp == "dense":
        if not spec.parallel:
            h = L.rms_norm(x, lp["norm2"], cfg.norm_eps)
        o = L.mlp_apply(lp["mlp"], h, cfg.act)
        if cfg.post_norms:
            o = L.rms_norm(o, lp["post_norm2"], cfg.norm_eps)
        x = x + o
    elif spec.mlp == "moe":
        h = L.rms_norm(x, lp["norm2"], cfg.norm_eps)
        o, a = apply_moe(lp["moe"], h, cfg=cfg)
        aux = {k: aux[k] + a[k] for k in aux}
        x = x + o

    return x, new_cache, aux


def _run_groups(groups_params, groups_def, x, *, cfg, mode, cache, pos,
                kv_source, shared_params):
    total_aux = {"moe_aux": jnp.zeros((), jnp.float32),
                 "moe_z": jnp.zeros((), jnp.float32)}
    new_cache: Dict[str, Any] = {}
    for gi, g in enumerate(groups_def):
        gp = groups_params[f"g{gi}"]
        gc = cache["groups"][f"g{gi}"] if cache is not None else None

        def body(carry, xs):
            xb, auxb = carry
            layer_params, layer_cache = xs
            xb = constrain(xb, ("batch", None, None))
            nc_out = {}
            for pi, ls in enumerate(g.pattern):
                lc = layer_cache.get(f"p{pi}") if layer_cache else None
                xb, nc, a = _apply_layer(
                    layer_params[f"p{pi}"], ls, xb, cfg=cfg, mode=mode,
                    lcache=lc, pos=pos, kv_source=kv_source,
                    shared_params=shared_params)
                auxb = {k: auxb[k] + a[k] for k in auxb}
                if nc:
                    nc_out[f"p{pi}"] = nc
            return (xb, auxb), nc_out

        body_fn = _remat(cfg, body) if mode == "train" else body
        if mode == "train":
            (x, total_aux), _ = jax.lax.scan(
                lambda c, p: (body_fn(c, (p, None))[0], None),
                (x, total_aux), gp)
        elif gc is None:  # prefill: no input cache, collect the produced one
            (x, total_aux), nc = jax.lax.scan(
                lambda c, p: body_fn(c, (p, None)), (x, total_aux), gp)
            new_cache[f"g{gi}"] = nc
        else:
            # decode: the STACKED cache is the loop CARRY; each layer writes
            # its new token directly at [layer, batch, pos] (one tiny
            # scatter).  Routing per-layer cache slices through scan xs->ys
            # (or re-stacking slices with a second DUS) made XLA rewrite the
            # whole stacked cache through f32 converts every layer --
            # observed 566 GB/step on codeqwen decode_32k, O(L^2) traffic.
            def dbody(i, state):
                xb, auxb, cache_st = state
                lp = jax.tree.map(
                    lambda a: jax.lax.dynamic_index_in_dim(a, i, 0,
                                                           keepdims=False), gp)
                nc_out = dict(cache_st)
                for pi, ls in enumerate(g.pattern):
                    lc = cache_st.get(f"p{pi}")
                    xb, nc, a = _apply_layer(
                        lp[f"p{pi}"], ls, xb, cfg=cfg, mode=mode,
                        lcache=lc, pos=pos, kv_source=kv_source,
                        shared_params=shared_params, layer_idx=i)
                    auxb = {k: auxb[k] + a[k] for k in auxb}
                    if nc:
                        nc_out[f"p{pi}"] = nc
                return xb, auxb, nc_out

            x, total_aux, gc_new = jax.lax.fori_loop(
                0, g.repeats, dbody, (x, total_aux, gc))
            new_cache[f"g{gi}"] = gc_new
    return x, new_cache, total_aux


def apply_model(
    params: Dict[str, Any],
    tokens: jnp.ndarray,                    # (B, S) int32
    *,
    cfg: ArchConfig,
    mode: str = "train",
    cache: Optional[Dict[str, Any]] = None,
    frontend: Optional[jnp.ndarray] = None,  # (B, T, D) stub embeddings
) -> Tuple[jnp.ndarray, Optional[Dict[str, Any]], Dict[str, jnp.ndarray]]:
    dt = jnp.dtype(cfg.dtype)
    x = jnp.take(params["embed"], tokens, axis=0).astype(dt)
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, dt)
    x = constrain(x, ("batch", None, None))

    pos = cache["pos"] if (cache is not None and mode == "decode") else None

    kv_source = None
    if cfg.encoder_groups and mode != "decode":
        # enc-dec (whisper): run the encoder on the stub frontend embeddings
        enc = frontend.astype(dt) + params["encoder"]["pos_embed"][None].astype(dt)
        enc, _, _ = _run_groups(params["encoder"]["groups"], cfg.encoder_groups,
                                enc, cfg=cfg, mode="train", cache=None,
                                pos=None, kv_source=None, shared_params=None)
        kv_source = L.rms_norm(enc, params["encoder"]["final_norm"], cfg.norm_eps)
    elif frontend is not None and mode != "decode":
        kv_source = frontend.astype(dt)     # vlm: pre-projected image tokens

    shared = params.get("shared_attn")
    x, new_cache, aux = _run_groups(params["groups"], cfg.groups, x, cfg=cfg,
                                    mode=mode, cache=cache, pos=pos,
                                    kv_source=kv_source, shared_params=shared)

    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    mtp_hidden = x
    if mode == "prefill":
        # only the last position's logits are needed: slice BEFORE the head
        # matmul (otherwise a (B, S, V) tensor materializes just to be
        # discarded -- observed as a 200 GiB all-reduce in the dry-run)
        x = x[:, -1:]
    head = params.get("lm_head")
    if head is None:
        head = params["embed"].T
    logits = jnp.einsum("bsd,dv->bsv", x, head.astype(dt))
    logits = L.softcap(logits, cfg.logit_softcap)
    if cfg.vocab_padded != cfg.vocab:
        pad_mask = jnp.arange(cfg.vocab_padded) < cfg.vocab
        logits = jnp.where(pad_mask, logits, -1e30)
    logits = constrain(logits, ("batch", None, "vocab"))

    out_cache = None
    if mode == "decode":
        out_cache = {"pos": cache["pos"] + 1, "groups": new_cache}
    elif mode == "prefill":
        B, S = tokens.shape
        out_cache = {"pos": jnp.full((B,), S, jnp.int32), "groups": new_cache}

    if cfg.mtp and mode == "train":
        aux = dict(aux)
        aux["mtp_hidden"] = mtp_hidden      # for the MTP head in the loss
    return logits, out_cache, aux
