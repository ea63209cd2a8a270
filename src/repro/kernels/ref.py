"""Pure-jnp oracles for every Pallas kernel, and the memory-efficient
attention used by the model code itself at long sequence length.

* ``flash_attention_ref``: double-chunked online-softmax attention (bounded
  memory at 32k/500k sequence).  Supports causal, sliding-window, logit
  softcap, GQA.  This is both the model's XLA path and the kernel oracle.
* ``decode_attention_ref``: single-token attention against a (possibly
  partially filled) KV cache.
* ``paged_attention_ref``: decode attention against a paged block pool.
* ``linear_scan_ref`` / ``linear_scan_exact``: chunked gated-linear
  recurrences (Mamba2 scalar decay / RWKV6 vector decay).
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _softcap(x, cap):
    return cap * jnp.tanh(x / cap) if cap else x


# ============================================================================
# attention
# ============================================================================


def flash_attention_ref(
    q: jnp.ndarray,              # (B, Sq, H, D)
    k: jnp.ndarray,              # (B, Sk, Hkv, D)
    v: jnp.ndarray,              # (B, Sk, Hkv, Dv)
    *,
    causal: bool = True,
    window: int = 0,             # 0 = unlimited; else sliding window size
    softcap: float = 0.0,
    scale: Optional[float] = None,
    q_offset: int = 0,           # absolute position of q[0] (prefill continuation)
    q_block: int = 512,
    kv_block: int = 1024,
) -> jnp.ndarray:
    B, Sq, H, D = q.shape
    Sk, Hkv, Dv = k.shape[1], k.shape[2], v.shape[3]
    G = H // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)

    q_block = min(q_block, Sq)
    kv_block = min(kv_block, Sk)
    pad_q = (-Sq) % q_block
    pad_k = (-Sk) % kv_block
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    Sq_p, Sk_p = Sq + pad_q, Sk + pad_k
    nq, nk = Sq_p // q_block, Sk_p // kv_block

    qr = q.reshape(B, nq, q_block, Hkv, G, D)
    kr = k.reshape(B, nk, kv_block, Hkv, D)
    vr = v.reshape(B, nk, kv_block, Hkv, Dv)

    q_pos_base = jnp.arange(Sq_p).reshape(nq, q_block) + q_offset
    k_pos_base = jnp.arange(Sk_p).reshape(nk, kv_block)

    def q_chunk(qi, qc):
        qpos = q_pos_base[qi]                       # (q_block,)

        def kv_step(carry, inputs):
            m, l, acc = carry
            kc, vc, kpos = inputs
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qc, kc,
                           preferred_element_type=jnp.float32) * scale
            s = _softcap(s, softcap)
            mask = kpos[None, :] <= qpos[:, None] if causal else (
                kpos[None, :] < Sk)
            if window:
                mask = mask & (kpos[None, :] > qpos[:, None] - window)
            mask = mask & (kpos[None, :] < Sk)      # kv padding
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p.astype(vc.dtype), vc,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, Hkv, G, q_block), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hkv, G, q_block), jnp.float32)
        a0 = jnp.zeros((B, Hkv, G, q_block, Dv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (kr.swapaxes(0, 1), vr.swapaxes(0, 1), k_pos_base))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out.astype(q.dtype)                  # (B,Hkv,G,q_block,Dv)

    # checkpoint each q-chunk: backward recomputes the block scores instead
    # of saving (nq, B, H, q_block, kv_block) probability tensors -- the
    # in-XLA analogue of flash attention's recomputation (observed: 19 GB of
    # saved scores per layer on starcoder2 train_4k without this)
    outs = jax.lax.map(lambda args: jax.checkpoint(q_chunk)(*args),
                       (jnp.arange(nq), qr.swapaxes(0, 1)))
    out = outs.transpose(1, 0, 4, 2, 3, 5).reshape(B, Sq_p, Hkv * G, Dv)
    return out[:, :Sq]


def decode_attention_ref(
    q: jnp.ndarray,              # (B, 1, H, D)
    k_cache: jnp.ndarray,        # (B, S, Hkv, D)
    v_cache: jnp.ndarray,        # (B, S, Hkv, Dv)
    kv_len: jnp.ndarray,         # (B,) number of valid cache positions
    *,
    window: int = 0,
    softcap: float = 0.0,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    B, _, H, D = q.shape
    S, Hkv, Dv = k_cache.shape[1], k_cache.shape[2], v_cache.shape[3]
    G = H // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    qr = q.reshape(B, Hkv, G, D)
    s = jnp.einsum("bhgd,bshd->bhgs", qr, k_cache,
                   preferred_element_type=jnp.float32) * scale
    s = _softcap(s, softcap)
    pos = jnp.arange(S)[None]                         # (1, S)
    mask = pos < kv_len[:, None]
    if window:
        mask = mask & (pos > kv_len[:, None] - 1 - window)
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, 1, H, Dv).astype(q.dtype)


def paged_attention_ref(
    q: jnp.ndarray,              # (B, H, D)
    k_pages: jnp.ndarray,        # (P, page, Hkv * D)  -- the shared block pool
    v_pages: jnp.ndarray,        # (P, page, Hkv * Dv)
    block_table: jnp.ndarray,    # (B, max_pages) int32 page ids (-1 pad)
    lengths: jnp.ndarray,        # (B,) valid tokens per sequence
    *,
    softcap: float = 0.0,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    B, H, D = q.shape
    _, page, KD = k_pages.shape
    Hkv = KD // D
    max_pages = block_table.shape[1]
    safe_table = jnp.maximum(block_table, 0)
    # (B, max_pages, page, Hkv * D) -> (B, max_pages * page, Hkv, D)
    k = k_pages[safe_table].reshape(B, max_pages * page, Hkv, D)
    v = v_pages[safe_table].reshape(B, max_pages * page, Hkv, -1)
    return decode_attention_ref(q[:, None], k, v, lengths,
                                softcap=softcap, scale=scale)[:, 0]


# ============================================================================
# gated linear recurrences (Mamba2 / RWKV6)
# ============================================================================


def linear_scan_step(
    q: jnp.ndarray,              # (B, H, K)
    k: jnp.ndarray,              # (B, H, K)
    v: jnp.ndarray,              # (B, H, Vd)
    log_decay: jnp.ndarray,      # (B, H) or (B, H, K)
    state: jnp.ndarray,          # (B, H, K, Vd)
    bonus: Optional[jnp.ndarray] = None,   # (H, K) rwkv6 'u'
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Single recurrent step (decode)."""
    a = jnp.exp(log_decay.astype(jnp.float32))
    if a.ndim == 2:
        a = a[..., None]
    kv = k[..., :, None] * v[..., None, :]           # (B,H,K,Vd)
    if bonus is not None:
        cur = state + bonus[None, :, :, None] * kv
        out = jnp.einsum("bhk,bhkv->bhv", q, cur.astype(q.dtype))
        new_state = a[..., None] * state + kv
    else:
        new_state = a[..., None] * state + kv
        out = jnp.einsum("bhk,bhkv->bhv", q, new_state.astype(q.dtype))
    return out, new_state


def linear_scan_exact(
    q, k, v, log_decay, *, state=None, bonus=None, chunk: int = 32
):
    """Exact chunked scan; vector decay handled with an (L, L, K) broadcast.

    The numerical oracle for both the model path and the Pallas kernel.
    q,k: (B,S,H,K); v: (B,S,H,Vd); log_decay: (B,S,H) or (B,S,H,K).

    Semantics:
      mamba2 (bonus=None):  S_t = a_t S_{t-1} + k_t v_t ; o_t = q_t . S_t
      rwkv6  (bonus=u):     S_t = w_t S_{t-1} + k_t v_t ; o_t = q_t . (S_{t-1} + u k_t v_t)
    Returns (out (B,S,H,Vd), final_state (B,H,K,Vd)).
    """
    B, S, H, K = q.shape
    Vd = v.shape[-1]
    vec = log_decay.ndim == 4
    ld = log_decay.astype(jnp.float32)
    if not vec:
        ld = ld[..., None]
    pad = (-S) % chunk
    if pad:
        zq = ((0, 0), (0, pad), (0, 0), (0, 0))
        q = jnp.pad(q, zq); k = jnp.pad(k, zq); v = jnp.pad(v, zq)
        ld = jnp.pad(ld, zq)
    n = (S + pad) // chunk
    qs = q.reshape(B, n, chunk, H, K).astype(jnp.float32)
    ks = k.reshape(B, n, chunk, H, K).astype(jnp.float32)
    vs = v.reshape(B, n, chunk, H, Vd).astype(jnp.float32)
    lds = ld.reshape(B, n, chunk, H, ld.shape[-1])
    if state is None:
        state = jnp.zeros((B, H, K, Vd), jnp.float32)

    idx = jnp.arange(chunk)
    strict_lower = idx[:, None] > idx[None, :]
    rwkv = bonus is not None

    def chunk_step(st, inp):
        qc, kc, vc, ldc = inp                         # (B,L,H,*)
        cl = jnp.cumsum(ldc, axis=1)                  # inclusive cum log decay
        clq = cl - ldc if rwkv else cl                # q-side: exclusive for rwkv
        # decay(i<-j): exp(clq_i - cl_j) for j < i (rwkv) / j < i (mamba; j=i is 1)
        dd = clq[:, :, None] - cl[:, None, :]         # (B,L,L,H,Kd)
        wmask = strict_lower[None, :, :, None, None]
        w = jnp.exp(jnp.where(wmask, dd, 0.0)) * wmask
        if w.shape[-1] == 1:                          # scalar decay: no K broadcast
            qk = jnp.einsum("blhk,bmhk->bhlm", qc, kc)
            scores = qk * w[..., 0].transpose(0, 3, 1, 2)
        else:
            scores = jnp.einsum("blhk,bmhk,blmhk->bhlm", qc, kc, w)
        if rwkv:
            dsc = jnp.einsum("blhk,blhk,hk->bhl", qc, kc, bonus.astype(jnp.float32))
        else:
            dsc = jnp.einsum("blhk,blhk->bhl", qc, kc)
        scores = scores + dsc[:, :, :, None] * jnp.eye(chunk, dtype=jnp.float32)[None, None]
        y_intra = jnp.einsum("bhlm,bmhv->blhv", scores, vc)
        decay_i = jnp.exp(clq)                        # (B,L,H,Kd)
        q_eff = qc * jnp.broadcast_to(decay_i, qc.shape)
        y_inter = jnp.einsum("blhk,bhkv->blhv", q_eff, st)
        total = jnp.exp(cl[:, -1])                    # (B,H,Kd)
        rem = jnp.exp(cl[:, -1:, :, :] - cl)          # decay j -> chunk end
        k_rem = kc * jnp.broadcast_to(rem, kc.shape)
        if vec:
            st_new = st * total[..., None]
        else:
            st_new = st * total[..., 0][:, :, None, None]
        st_new = st_new + jnp.einsum("blhk,blhv->bhkv", k_rem, vc)
        return st_new, (y_intra + y_inter)

    state, ys = jax.lax.scan(chunk_step, state,
                             (qs.swapaxes(0, 1), ks.swapaxes(0, 1),
                              vs.swapaxes(0, 1), lds.swapaxes(0, 1)))
    out = ys.transpose(1, 0, 2, 3, 4).reshape(B, n * chunk, H, Vd)[:, :S]
    return out.astype(v.dtype), state


def linear_scan_ref(q, k, v, log_decay, *, state=None, bonus=None,
                    chunk: int = 128, clamp: float = 75.0):
    """Factored chunked scan (what the Pallas kernel implements).

    Scalar decay (mamba2): mathematically exact.  Vector decay (rwkv6):
    factored form ``(q*exp(clq)) . (k*exp(-cl))`` with amplification clamped
    at ``exp(clamp)`` -- matches the exact oracle to ~1e-3 for realistic
    decays (tests check this).
    """
    B, S, H, K = q.shape
    Vd = v.shape[-1]
    vec = log_decay.ndim == 4
    ld = log_decay.astype(jnp.float32)
    if not vec:
        ld = ld[..., None]
    pad = (-S) % chunk
    if pad:
        zq = ((0, 0), (0, pad), (0, 0), (0, 0))
        q = jnp.pad(q, zq); k = jnp.pad(k, zq); v = jnp.pad(v, zq)
        ld = jnp.pad(ld, zq)
    n = (S + pad) // chunk
    qs = q.reshape(B, n, chunk, H, K).astype(jnp.float32)
    ks = k.reshape(B, n, chunk, H, K).astype(jnp.float32)
    vs = v.reshape(B, n, chunk, H, Vd).astype(jnp.float32)
    lds = ld.reshape(B, n, chunk, H, ld.shape[-1])
    if state is None:
        state = jnp.zeros((B, H, K, Vd), jnp.float32)

    idx = jnp.arange(chunk)
    strict_lower = (idx[:, None] > idx[None, :]).astype(jnp.float32)
    rwkv = bonus is not None

    def chunk_step(st, inp):
        qc, kc, vc, ldc = inp
        cl = jnp.cumsum(ldc, axis=1)
        clq = cl - ldc if rwkv else cl
        q_eff = qc * jnp.broadcast_to(jnp.exp(clq), qc.shape)
        k_eff = kc * jnp.broadcast_to(jnp.exp(jnp.minimum(-cl, clamp)), kc.shape)
        scores = jnp.einsum("blhk,bmhk->bhlm", q_eff, k_eff)
        scores = scores * strict_lower[None, None]
        if rwkv:
            dsc = jnp.einsum("blhk,blhk,hk->bhl", qc, kc, bonus.astype(jnp.float32))
        else:
            dsc = jnp.einsum("blhk,blhk->bhl", qc, kc)
        scores = scores + dsc[:, :, :, None] * jnp.eye(chunk, dtype=jnp.float32)[None, None]
        y = jnp.einsum("bhlm,bmhv->blhv", scores, vc)
        y = y + jnp.einsum("blhk,bhkv->blhv", q_eff, st)
        total = jnp.exp(cl[:, -1])
        rem = jnp.exp(cl[:, -1:, :, :] - cl)
        k_rem = kc * jnp.broadcast_to(rem, kc.shape)
        if vec:
            st_new = st * total[..., None]
        else:
            st_new = st * total[..., 0][:, :, None, None]
        st_new = st_new + jnp.einsum("blhk,blhv->bhkv", k_rem, vc)
        return st_new, y

    state, ys = jax.lax.scan(chunk_step, state,
                             (qs.swapaxes(0, 1), ks.swapaxes(0, 1),
                              vs.swapaxes(0, 1), lds.swapaxes(0, 1)))
    out = ys.transpose(1, 0, 2, 3, 4).reshape(B, n * chunk, H, Vd)[:, :S]
    return out.astype(v.dtype), state
