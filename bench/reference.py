"""The plain reference: the configuration's forward pass in float32
``jax.numpy`` at ``highest`` matmul precision, teacher-forced over what the
served path fed, layer by layer so that it fits beside the bf16 weights.

It imports nothing of the program.  It reads the model's sizes from the
configuration file's ``model`` group and the benchmark's own weights
(``bench/weights.py``), and computes what the configuration states the
program runs (``departures`` in the file list where that differs from the
published model):

    x = embed[tokens]
    per layer:  h = rms(x) * (1 + norm1)
                q, k, v = h @ wq, h @ wk, h @ wv      (GQA: Hkv heads)
                q, k = rms(q) * (1 + q_scale), ...   (qk-norm, if stated)
                q, k = rope(q), rope(k)               (interleaved pairs, the
                                                       first rope_pct of hd)
                x = x + softmax_causal(q k^T / sqrt(hd)) v @ wo
                h = rms(x) * (1 + norm2)
                x = x + gelu_tanh(h @ wi) @ wo        (GELU)
                  | silu(h @ wi_gate) * (h @ wi_up) @ wo   (SwiGLU)
    logits = (rms(x) * (1 + final_norm)) @ lm_head

``lower="fp8"`` is the control: the same forward with every matmul operand
rounded through float8 (e4m3), the precision below the configuration's
bfloat16 that a faster program would be tempted to serve in.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["fed_tokens", "served_gaps", "control_gaps", "forward_logits"]

HI = jax.lax.Precision.HIGHEST


def fed_tokens(prompt: Sequence[int], out: Sequence[int]) -> List[int]:
    """The tokens the served path fed for a request that produced ``out``:
    the prompt, the prompt's last token fed a second time by the first
    decode step, then every output but the last.  Position
    ``len(prompt) + j`` of this row chose ``out[j]``.  A program that stops
    re-feeding the last prompt token changes this one function."""
    return list(prompt) + list(prompt[-1:]) + list(out[:-1])


def _q(x, lower):
    if lower == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return x


def _rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w)


def _rope(x, pos, theta, pct):
    d = x.shape[-1]
    rot = int(d * pct)
    rot -= rot % 2
    if rot == 0:
        return x
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = pos.astype(jnp.float32)[:, None] * inv              # (S, rot/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0:rot:2], x[..., 1:rot:2]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([out.reshape(x[..., :rot].shape), x[..., rot:]],
                           axis=-1)


def _mm(eq, a, b, lower):
    return jnp.einsum(eq, _q(a, lower), _q(b, lower), precision=HI)


@functools.partial(jax.jit, static_argnames=("m", "lower"))
def _layer(x, lp, *, m, lower):
    """One layer over rows ``x`` (R, S, D) float32."""
    f32 = lambda a: a.astype(jnp.float32)
    eps, H, Hkv, hd = m["norm_eps"], m["n_heads"], m["n_kv_heads"], \
        m["head_dim"]
    R, S, _ = x.shape
    pos = jnp.arange(S)
    h = _rms(x, f32(lp["norm1"]), eps)
    q = _mm("rsd,dhe->rshe", h, f32(lp["wq"]), lower)
    k = _mm("rsd,dhe->rshe", h, f32(lp["wk"]), lower)
    v = _mm("rsd,dhe->rshe", h, f32(lp["wv"]), lower)
    if m["qk_norm"]:
        q = _rms(q, f32(lp["q_scale"]), eps)
        k = _rms(k, f32(lp["k_scale"]), eps)
    q = _rope(q, pos, m["rope_theta"], m["rope_pct"])
    k = _rope(k, pos, m["rope_theta"], m["rope_pct"])
    qg = q.reshape(R, S, Hkv, H // Hkv, hd)
    s = _mm("rqkge,rtke->rkgqt", qg, k, lower) / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = _mm("rkgqt,rtke->rqkge", p, v, lower).reshape(R, S, H, hd)
    x = x + _mm("rshe,hed->rsd", o, f32(lp["wo"]), lower)
    h = _rms(x, f32(lp["norm2"]), eps)
    if m["act"] == "silu":
        g = jax.nn.silu(_mm("rsd,df->rsf", h, f32(lp["wi_gate"]), lower))
        u = g * _mm("rsd,df->rsf", h, f32(lp["wi_up"]), lower)
    else:
        u = jax.nn.gelu(_mm("rsd,df->rsf", h, f32(lp["wi"]), lower),
                        approximate=True)
    return x + _mm("rsf,fd->rsd", u, f32(lp["wo_mlp"]), lower)


@functools.partial(jax.jit, static_argnames=("m", "lower"))
def _head(x, final_norm, head, *, m, lower):
    h = _rms(x, final_norm.astype(jnp.float32), m["norm_eps"])
    return _mm("rsd,dv->rsv", h, head.astype(jnp.float32),
               lower)[..., :m["vocab"]]


@jax.jit
def _layer_params(group, li):
    attn, mlp = group["attn"], group["mlp"]
    lp = {"norm1": group["norm1"][li], "norm2": group["norm2"][li],
          "wo_mlp": mlp["wo"][li]}
    for k in ("wq", "wk", "wv", "wo", "q_scale", "k_scale"):
        if k in attn:
            lp[k] = attn[k][li]
    for k in ("wi", "wi_gate", "wi_up"):
        if k in mlp:
            lp[k] = mlp[k][li]
    return lp


def _bucket(n: int, step: int = 256) -> int:
    return -(-n // step) * step


def forward_logits(params, m: Dict, rows: Sequence[Sequence[int]],
                   picks: Sequence[Sequence[int]], *,
                   lower: Optional[str] = None, block: int = 4) -> List:
    """Float32 logits ``(len(picks[i]), vocab)`` of each row at the
    positions ``picks[i]``.  Rows run ``block`` at a time, zero-padded to a
    multiple of 256 positions (the causal mask keeps the padding out of the
    earlier positions), so a handful of programs serve every sample."""
    m_key = _hashable(m)
    group = params["groups"]["g0"]["p0"]
    out: List = []
    for b0 in range(0, len(rows), block):
        chunk = rows[b0:b0 + block]
        S = _bucket(max(len(r) for r in chunk))
        toks = np.zeros((block, S), np.int32)
        for i, r in enumerate(chunk):
            toks[i, :len(r)] = r
        with jax.default_matmul_precision("highest"):
            x = jnp.take(params["embed"], jnp.asarray(toks),
                         axis=0).astype(jnp.float32)
            for li in range(m["layers"]):
                x = _layer(x, _layer_params(group, jnp.int32(li)), m=m_key,
                           lower=lower)
            for i in range(len(chunk)):
                pk = list(picks[b0 + i])
                # pad the picked positions to a multiple of 64 as well
                idx = np.asarray(pk + pk[-1:] * (_bucket(len(pk), 64) - len(pk)))
                sel = x[i:i + 1, idx]
                out.append(np.asarray(_head(sel, params["final_norm"],
                                            params["lm_head"], m=m_key,
                                            lower=lower))[0, :len(pk)])
    return out


class _hashable(dict):
    """A model spec usable as a static jit argument."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def _rows(samples):
    rows = [fed_tokens(s["prompt"], s["out"]) for s in samples]
    picks = [list(range(len(s["prompt"]), len(s["prompt"]) + len(s["out"])))
             for s in samples]
    return rows, picks


def served_gaps(params, m: Dict, samples: Sequence[Dict]) -> np.ndarray:
    """Per served token: how far its reference logit lies below the
    reference's best at that position (0 where the program chose the
    reference's top-1).  ``samples``: dicts with ``prompt`` and ``out``."""
    rows, picks = _rows(samples)
    gaps = []
    for s, ref in zip(samples, forward_logits(params, m, rows, picks)):
        chosen = ref[np.arange(len(s["out"])), np.asarray(s["out"])]
        gaps.append(ref.max(axis=-1) - chosen)
    return np.concatenate(gaps)


def control_gaps(params, m: Dict, samples: Sequence[Dict],
                 lower: str = "fp8") -> np.ndarray:
    """The control's gaps at the same positions: the token the
    lower-precision forward puts first, read against the float32
    reference's best."""
    rows, picks = _rows(samples)
    ref = forward_logits(params, m, rows, picks)
    low = forward_logits(params, m, rows, picks, lower=lower)
    gaps = []
    for r, lo in zip(ref, low):
        top = lo.argmax(axis=-1)
        gaps.append(r.max(axis=-1) - r[np.arange(len(top)), top])
    return np.concatenate(gaps)
