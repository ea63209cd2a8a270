"""Attention variants: GQA (full / sliding-window), MLA (DeepSeek-V3
compressed-latent attention, with the absorbed decode path), and
cross-attention (VLM image tokens / enc-dec).

``apply_attn`` handles three modes:
  train/prefill: full-sequence flash-style attention (chunked online softmax)
  decode:        one query token against a KV cache written at ``pos``

Cache layouts (all batch-major, stacked over layer repeats by the caller):
  full/local: {"k": (B,S,Hkv,D), "v": (B,S,Hkv,Dv)}
  mla:        {"ckv": (B,S,r_kv), "k_rope": (B,S,rope_dim)}   (compressed!)
  cross:      {"k": (B,T,Hkv,D), "v": (B,T,Hkv,Dv)}           (precomputed)
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.kernels import ops as kops
from repro.models.layers import Spec, apply_rope, rms_norm
from repro.parallel import sharding as shlib


def _tp_size(mesh) -> int:
    return mesh.shape["model"] if (mesh is not None and
                                   "model" in mesh.axis_names) else 1


def _flash(q, k, v, *, cfg, causal, window, softcap_v, scale):
    """Full-sequence attention; context-parallel over the model axis when
    the head count does not divide TP (starcoder2: 36H, whisper: 12H).

    Heads stay replicated in that case, so without this every device would
    redo ALL heads (16x waste -- 'useful'=0.14 on starcoder2 train).  Here
    each model-shard takes a slice of the QUERY sequence instead: zero extra
    communication (K/V are already replicated over 'model'), causal masking
    offset by the shard's position."""
    mesh = shlib.get_mesh()
    tp = _tp_size(mesh)
    H, Hkv = q.shape[2], k.shape[2]
    # CP also when KV heads can't shard: replicated K/V makes GSPMD gather
    # full-batch K/V blocks per (q-chunk x layer) iteration (observed 2x805GB
    # on stablelm prefill: kv=8 on TP16)
    if tp == 1 or (H % tp == 0 and Hkv % tp == 0):
        return kops.flash_attention(q, k, v, causal=causal, window=window,
                                    softcap=softcap_v, scale=scale)

    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    B, S = q.shape[0], q.shape[1]
    pad = (-S) % tp
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else q
    Sp = S + pad
    fsdp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    bspec = fsdp[0] if len(fsdp) == 1 else fsdp
    s_local = Sp // tp

    def body(qs, ks, vs):
        off = jax.lax.axis_index("model") * s_local
        from repro.kernels.ref import flash_attention_ref
        return flash_attention_ref(qs, ks, vs, causal=causal, window=window,
                                   softcap=softcap_v, scale=scale,
                                   q_offset=off)

    out = shard_map(
        body, mesh=mesh,
        in_specs=(P(bspec, "model"), P(bspec), P(bspec)),
        out_specs=P(bspec, "model"),
        check_vma=False)(qp, k, v)
    return out[:, :S]


def _cache_read(arr, idx):
    """Slice layer ``idx`` from a stacked cache leaf (None = unstacked)."""
    if idx is None:
        return arr
    return jax.lax.dynamic_index_in_dim(arr, idx, 0, keepdims=False)


def _cache_write_token(arr, idx, bidx, pos, val):
    """Write one decoded token into a (stacked) KV cache leaf IN PLACE.

    Uses a uniform-position dynamic_update_slice (pos[0]): a per-batch
    scatter forces XLA into full-cache convert+scatter chains (observed
    4.1 TB/step on codeqwen decode_32k).  The dense serve_step therefore
    assumes aligned decode offsets -- the standard static-batch layout;
    ragged per-request positions are the PAGED path's job (block tables +
    kernels/paged_attention.py), where writes are per-page."""
    val = val.astype(arr.dtype)
    pos0 = pos[0]
    # (B, ...) -> (B, 1, ...) update block at [batch0=0, seq=pos0]
    upd = val[:, None]
    if idx is None:
        starts = (0, pos0) + (0,) * (arr.ndim - 2)
        return jax.lax.dynamic_update_slice(arr, upd, starts)
    starts = (idx, 0, pos0) + (0,) * (arr.ndim - 3)
    return jax.lax.dynamic_update_slice(arr, upd[None], starts)


# ----------------------------------------------------------------------------
# specs
# ----------------------------------------------------------------------------


def attn_specs(cfg: ArchConfig, kind: str) -> Dict[str, Spec]:
    D, H, Hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    if kind == "mla":
        m = cfg.mla
        qk_dim = m.qk_nope_head_dim + m.qk_rope_head_dim
        s = {
            "wq_a": Spec((D, m.q_lora_rank), ("embed", "q_lora")),
            "q_norm": Spec((m.q_lora_rank,), ("q_lora",), "zeros"),
            "wq_b": Spec((m.q_lora_rank, H, qk_dim), ("q_lora", "heads", "head_dim")),
            "wkv_a": Spec((D, m.kv_lora_rank + m.qk_rope_head_dim), ("embed", "kv_lora")),
            "kv_norm": Spec((m.kv_lora_rank,), ("kv_lora",), "zeros"),
            "wk_b": Spec((m.kv_lora_rank, H, m.qk_nope_head_dim),
                         ("kv_lora", "heads", "head_dim")),
            "wv_b": Spec((m.kv_lora_rank, H, m.v_head_dim),
                         ("kv_lora", "heads", "head_dim")),
            "wo": Spec((H, m.v_head_dim, D), ("heads", "head_dim", "embed")),
        }
        return s
    s = {
        "wq": Spec((D, H, hd), ("embed", "heads", "head_dim")),
        "wk": Spec((D, Hkv, hd), ("embed", "kv_heads", "head_dim")),
        "wv": Spec((D, Hkv, hd), ("embed", "kv_heads", "head_dim")),
        "wo": Spec((H, hd, D), ("heads", "head_dim", "embed")),
    }
    if cfg.qk_norm:
        s["q_scale"] = Spec((hd,), ("head_dim",), "zeros")
        s["k_scale"] = Spec((hd,), ("head_dim",), "zeros")
    return s


# ----------------------------------------------------------------------------
# apply
# ----------------------------------------------------------------------------


def _attn_scale(cfg: ArchConfig, qk_dim: int) -> float:
    if cfg.attn_scale:
        return 1.0 / math.sqrt(cfg.attn_scale)
    return 1.0 / math.sqrt(qk_dim)


def apply_attn(
    p: Dict[str, jnp.ndarray],
    x: jnp.ndarray,                        # (B, S, D) normed input
    *,
    cfg: ArchConfig,
    kind: str,                             # full | local | mla | cross
    mode: str,                             # train | prefill | decode
    cache: Optional[Dict[str, jnp.ndarray]] = None,
    pos: Optional[jnp.ndarray] = None,     # (B,) decode positions
    kv_source: Optional[jnp.ndarray] = None,   # (B, T, D) for cross prefill/train
    causal: bool = True,
    layer_idx=None,                # decode: index into the STACKED cache
) -> Tuple[jnp.ndarray, Optional[Dict[str, jnp.ndarray]]]:
    if kind == "mla":
        return _apply_mla(p, x, cfg=cfg, mode=mode, cache=cache, pos=pos,
                          layer_idx=layer_idx)
    B, S, D = x.shape
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    window = cfg.window if kind == "local" else 0
    scale = _attn_scale(cfg, hd)

    q = jnp.einsum("bsd,dhe->bshe", x, p["wq"])
    if kind == "cross":
        if mode == "decode":
            k = _cache_read(cache["k"], layer_idx)
            v = _cache_read(cache["v"], layer_idx)
            new_cache = cache
            if cfg.qk_norm:
                q = rms_norm(q, p["q_scale"], cfg.norm_eps)
            out = kops.decode_attention(
                q, k, v, jnp.full((B,), k.shape[1], jnp.int32),
                softcap=cfg.attn_softcap, scale=scale)
        else:
            k = jnp.einsum("btd,dhe->bthe", kv_source, p["wk"])
            v = jnp.einsum("btd,dhe->bthe", kv_source, p["wv"])
            if cfg.qk_norm:
                q = rms_norm(q, p["q_scale"], cfg.norm_eps)
                k = rms_norm(k, p["k_scale"], cfg.norm_eps)
            out = _flash(q, k, v, cfg=cfg, causal=False, window=0,
                         softcap_v=cfg.attn_softcap, scale=scale)
            new_cache = {"k": k, "v": v} if mode == "prefill" else None
        o = jnp.einsum("bshe,hed->bsd", out, p["wo"])
        return o, new_cache

    k = jnp.einsum("bsd,dhe->bshe", x, p["wk"])
    v = jnp.einsum("bsd,dhe->bshe", x, p["wv"])
    if cfg.qk_norm:
        q = rms_norm(q, p["q_scale"], cfg.norm_eps)
        k = rms_norm(k, p["k_scale"], cfg.norm_eps)

    if mode == "decode":
        positions = pos[:, None]                       # (B,1)
        q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_pct)
        k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_pct)
        bidx = jnp.arange(B)
        k_full = _cache_write_token(cache["k"], layer_idx, bidx, pos, k[:, 0])
        v_full = _cache_write_token(cache["v"], layer_idx, bidx, pos, v[:, 0])
        out = kops.decode_attention(
            q, _cache_read(k_full, layer_idx), _cache_read(v_full, layer_idx),
            pos + 1, window=window, softcap=cfg.attn_softcap, scale=scale)
        new_cache = {"k": k_full, "v": v_full}
    else:
        positions = jnp.arange(S)[None]
        q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_pct)
        k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_pct)
        out = _flash(q, k, v, cfg=cfg, causal=causal, window=window,
                     softcap_v=cfg.attn_softcap, scale=scale)
        new_cache = {"k": k, "v": v} if mode == "prefill" else None
    o = jnp.einsum("bshe,hed->bsd", out, p["wo"])
    return o, new_cache


def _apply_mla(p, x, *, cfg, mode, cache, pos, layer_idx=None):
    """DeepSeek-V3 multi-head latent attention.

    train/prefill: explicit (decompressed) form through flash attention.
    decode: ABSORBED form -- queries projected into the latent space, scores
    against the compressed cache directly; cache is (B,S,r_kv)+(B,S,rope).
    """
    B, S, D = x.shape
    m, H = cfg.mla, cfg.n_heads
    nope, rope_d, vd = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    scale = 1.0 / math.sqrt(nope + rope_d)

    ql = rms_norm(x @ p["wq_a"], p["q_norm"], cfg.norm_eps)
    q = jnp.einsum("bsr,rhe->bshe", ql, p["wq_b"])     # (B,S,H,nope+rope)
    q_nope, q_rope = q[..., :nope], q[..., nope:]

    kv_a = x @ p["wkv_a"]                              # (B,S,r_kv+rope)
    ckv = rms_norm(kv_a[..., : m.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    k_rope = kv_a[..., m.kv_lora_rank:]                # (B,S,rope) shared head

    if mode == "decode":
        positions = pos[:, None]
        q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
        k_rope = apply_rope(k_rope[:, :, None], positions, cfg.rope_theta)[:, :, 0]
        bidx = jnp.arange(B)
        ckv_full = _cache_write_token(cache["ckv"], layer_idx, bidx, pos,
                                      ckv[:, 0])
        krope_full = _cache_write_token(cache["k_rope"], layer_idx, bidx, pos,
                                        k_rope[:, 0])
        ckv_c = _cache_read(ckv_full, layer_idx)
        krope_c = _cache_read(krope_full, layer_idx)
        # absorbed: q_lat = q_nope @ wk_b^T  -> score against compressed cache
        q_lat = jnp.einsum("bshe,rhe->bshr", q_nope, p["wk_b"])     # (B,1,H,r)
        s = (jnp.einsum("bshr,btr->bhst", q_lat, ckv_c) +
             jnp.einsum("bshe,bte->bhst", q_rope, krope_c)) * scale
        t_idx = jnp.arange(ckv_c.shape[1])[None]
        mask = t_idx <= pos[:, None]
        s = jnp.where(mask[:, None, None], s, -1e30)
        pr = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(x.dtype)
        ctx = jnp.einsum("bhst,btr->bshr", pr, ckv_c)               # (B,1,H,r)
        out = jnp.einsum("bshr,rhe->bshe", ctx, p["wv_b"])          # (B,1,H,vd)
        o = jnp.einsum("bshe,hed->bsd", out, p["wo"])
        return o, {"ckv": ckv_full, "k_rope": krope_full}

    positions = jnp.arange(S)[None]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    k_rope = apply_rope(k_rope[:, :, None], positions, cfg.rope_theta)[:, :, 0]
    k_nope = jnp.einsum("bsr,rhe->bshe", ckv, p["wk_b"])
    v = jnp.einsum("bsr,rhe->bshe", ckv, p["wv_b"])
    k = jnp.concatenate([k_nope,
                         jnp.broadcast_to(k_rope[:, :, None], (B, S, H, rope_d))],
                        axis=-1)
    qf = jnp.concatenate([q_nope, q_rope], axis=-1)
    out = kops.flash_attention(qf, k, v, causal=True, scale=scale)
    o = jnp.einsum("bshe,hed->bsd", out, p["wo"])
    new_cache = {"ckv": ckv, "k_rope": k_rope} if mode == "prefill" else None
    return o, new_cache
