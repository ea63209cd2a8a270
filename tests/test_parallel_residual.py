"""stablelm-12b's parallel residual, ``x + attn(n1(x)) + mlp(n1(x))``: the
dense forward and the paged serving path (chunked paged prefill, then
paged decode steps) against a plain float32 ``jax.numpy`` block at
``highest`` precision, on the registry's SMOKE configuration with every
norm scale drawn."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_smoke_config
from repro.models.model import apply_model, init_params
from repro.runtime.kv_store import PagedKVStore
from repro.serve.paged_model import paged_decode_step, prefill_kv_chunked

PAGE = 4


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w)


def _rope(x, theta, pct):
    """Interleaved pairs of the first ``pct`` of each head; x (S, h, hd)."""
    S, _, hd = x.shape
    rot = int(hd * pct) // 2 * 2
    inv = 1.0 / theta ** (np.arange(0, rot, 2) / rot)
    ang = np.arange(S)[:, None, None] * inv                   # (S, 1, rot/2)
    c, s = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0:rot:2], x[..., 1:rot:2]
    r = jnp.stack([a * c - b * s, b * c + a * s], -1).reshape(S, -1, rot)
    return jnp.concatenate([r, x[..., rot:]], -1)


def _reference_logits(p, cfg, tokens):
    """Every position's logits of ``tokens``, one parallel block a layer."""
    eps, H, Hkv = cfg.norm_eps, cfg.n_heads, cfg.n_kv_heads
    hd = cfg.head_dim_
    g = p["groups"]["g0"]["p0"]
    S = len(tokens)
    with jax.default_matmul_precision("highest"):
        x = p["embed"][jnp.asarray(tokens)]
        for li in range(cfg.n_layers):
            a, m = g["attn"], g["mlp"]
            h = _rms(x, g["norm1"][li], eps)
            q = jnp.einsum("sd,dhe->she", h, a["wq"][li])
            k = jnp.einsum("sd,dhe->she", h, a["wk"][li])
            v = jnp.einsum("sd,dhe->she", h, a["wv"][li])
            q = _rope(_rms(q, a["q_scale"][li], eps), cfg.rope_theta,
                      cfg.rope_pct)
            k = _rope(_rms(k, a["k_scale"][li], eps), cfg.rope_theta,
                      cfg.rope_pct)
            k, v = (jnp.repeat(t, H // Hkv, axis=1) for t in (k, v))
            s = jnp.einsum("qhe,khe->hqk", q, k) / np.sqrt(hd)
            s = jnp.where(np.tril(np.ones((S, S), bool)), s, -jnp.inf)
            o = jnp.einsum("hqk,khe->qhe", jax.nn.softmax(s, -1), v)
            attn = jnp.einsum("she,hed->sd", o, a["wo"][li])
            mlp = (jax.nn.silu(h @ m["wi_gate"][li]) * (h @ m["wi_up"][li])) \
                @ m["wo"][li]
            x = x + attn + mlp
        logits = _rms(x, p["final_norm"], eps) @ p["lm_head"]
    return np.asarray(logits[:, :cfg.vocab])


@pytest.fixture(scope="module")
def model():
    cfg = get_smoke_config("stablelm_12b").scaled(dtype="float32")
    assert cfg.groups[0].pattern[0].parallel
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          init_params(cfg, jax.random.PRNGKey(3)))
    # norm scales start at zero: draw them, so that each one is checked
    keys = iter(jax.random.split(jax.random.PRNGKey(4), 64))
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: 0.1 * jax.random.normal(next(keys), a.shape)
        if "norm" in str(path) or "scale" in str(path) else a, params)
    rng = np.random.default_rng(0)
    tokens = rng.integers(1, cfg.vocab, 14).tolist()
    return cfg, params, tokens, _reference_logits(params, cfg, tokens)


def test_parallel_layer_has_one_norm(model):
    _, params, _, _ = model
    assert "norm2" not in params["groups"]["g0"]["p0"]


def test_dense_forward_is_the_parallel_block(model):
    cfg, params, tokens, want = model
    got = apply_model(params, jnp.asarray([tokens]), cfg=cfg,
                      mode="train")[0][0, :, :cfg.vocab]
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-4, rtol=2e-4)


def test_paged_path_is_the_parallel_block(model):
    cfg, params, tokens, want = model
    n = 9                                   # prefilled, then decoded
    store = PagedKVStore(cfg, num_blocks=8, page_size=PAGE)
    blocks = list(range(1, 1 + -(-len(tokens) // PAGE)))
    for end, logits in prefill_kv_chunked(params, cfg, store, blocks,
                                          tokens[:n], 4, impl="interpret"):
        start = end - logits.shape[0]
        np.testing.assert_allclose(np.asarray(logits)[:, :cfg.vocab],
                                   want[start:end], atol=2e-4, rtol=2e-4)
    for pos in range(n, len(tokens)):
        logits = paged_decode_step(params, cfg, store, [blocks], [pos],
                                   [tokens[pos]], impl="interpret")
        np.testing.assert_allclose(np.asarray(logits)[0, :cfg.vocab],
                                   want[pos], atol=2e-4, rtol=2e-4)
