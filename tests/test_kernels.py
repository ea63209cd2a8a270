"""Per-Pallas-kernel validation: interpret mode (kernel body executed on CPU)
against the pure-jnp oracles in kernels/ref.py, sweeping shapes and dtypes."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.linear_scan import linear_scan_pallas
from repro.kernels.paged_attention import paged_attention_pallas

RNG = np.random.default_rng(7)


def _rand(shape, dtype):
    return jnp.asarray(RNG.normal(size=shape), jnp.float32).astype(dtype)


# ----------------------------------------------------------------------------
# flash attention
# ----------------------------------------------------------------------------

FLASH_CASES = [
    # (B, Sq, Sk, H, Hkv, D, causal, window, softcap)
    (1, 128, 128, 4, 4, 64, True, 0, 0.0),
    (2, 64, 64, 4, 2, 32, True, 0, 0.0),          # GQA
    (2, 64, 64, 8, 2, 32, True, 24, 0.0),         # sliding window
    (1, 128, 128, 4, 4, 64, True, 0, 50.0),       # softcap (gemma2)
    (2, 96, 96, 4, 4, 32, False, 0, 0.0),         # bidirectional (whisper enc)
    (1, 80, 80, 2, 2, 64, True, 0, 0.0),          # non-multiple of block
]


@pytest.mark.parametrize("case", FLASH_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_kernel_vs_oracle(case, dtype):
    B, Sq, Sk, H, Hkv, D, causal, window, cap = case
    q = _rand((B, Sq, H, D), dtype)
    k = _rand((B, Sk, Hkv, D), dtype)
    v = _rand((B, Sk, Hkv, D), dtype)
    got = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                 softcap=cap, block_q=32, block_kv=32,
                                 interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                   softcap=cap, q_block=32, kv_block=32)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


# ----------------------------------------------------------------------------
# gated linear scan
# ----------------------------------------------------------------------------

SCAN_CASES = [
    # (B, S, H, K, Vd, vector_decay, bonus, chunk)
    (2, 128, 2, 32, 32, False, False, 32),        # mamba2-style
    (1, 96, 4, 16, 64, False, False, 32),         # Vd != K, ragged S
    (2, 128, 2, 32, 32, True, True, 32),          # rwkv6-style
    (1, 64, 2, 16, 16, True, True, 16),
]


@pytest.mark.parametrize("case", SCAN_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_linear_scan_kernel_vs_oracle(case, dtype):
    B, S, H, K, Vd, vec, bonus, chunk = case
    q = _rand((B, S, H, K), dtype)
    k = _rand((B, S, H, K), dtype)
    v = _rand((B, S, H, Vd), dtype)
    ld_shape = (B, S, H, K) if vec else (B, S, H)
    ld = jnp.asarray(-RNG.uniform(0.01, 1.0, ld_shape), jnp.float32)
    u = _rand((H, K), jnp.float32) if bonus else None
    got, st = linear_scan_pallas(q, k, v, ld, bonus=u, chunk=chunk,
                                 interpret=True)
    want, st_want = ref.linear_scan_exact(q, k, v, ld, bonus=u, chunk=chunk)
    tol = 5e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(st), np.asarray(st_want),
                               atol=tol, rtol=tol)


def test_linear_scan_kernel_matches_sequential_recurrence():
    """End-to-end: kernel vs the literal step recurrence."""
    B, S, H, K, Vd = 1, 40, 2, 8, 8
    q = _rand((B, S, H, K), jnp.float32)
    k = _rand((B, S, H, K), jnp.float32)
    v = _rand((B, S, H, Vd), jnp.float32)
    ld = jnp.asarray(-RNG.uniform(0.05, 0.5, (B, S, H, K)), jnp.float32)
    u = _rand((H, K), jnp.float32)
    got, _ = linear_scan_pallas(q, k, v, ld, bonus=u, chunk=8, interpret=True)
    st = jnp.zeros((B, H, K, Vd))
    outs = []
    for t in range(S):
        o, st = ref.linear_scan_step(q[:, t], k[:, t], v[:, t], ld[:, t], st, u)
        outs.append(o)
    want = jnp.stack(outs, 1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-3, rtol=1e-3)


# ----------------------------------------------------------------------------
# paged attention
# ----------------------------------------------------------------------------

PAGED_CASES = [
    # (B, H, Hkv, D, n_pool_pages, page, max_pages)
    (2, 4, 2, 32, 16, 16, 4),
    (3, 8, 8, 64, 32, 8, 6),
    (1, 4, 4, 32, 8, 16, 3),
]


@pytest.mark.parametrize("case", PAGED_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_kernel_vs_oracle(case, dtype):
    B, H, Hkv, D, P, page, max_pages = case
    q = _rand((B, H, D), dtype)
    k_pages = _rand((Hkv, P, page, D), dtype)
    v_pages = _rand((Hkv, P, page, D), dtype)
    # build random block tables + lengths
    lengths = jnp.asarray(RNG.integers(1, page * max_pages, (B,)), jnp.int32)
    table = np.full((B, max_pages), -1, np.int32)
    used = set()
    for b in range(B):
        n = int(np.ceil(int(lengths[b]) / page))
        for i in range(n):
            pid = int(RNG.integers(0, P))
            while pid in used:
                pid = (pid + 1) % P
            used.add(pid)
            table[b, i] = pid
    table = jnp.asarray(table)
    got = paged_attention_pallas(q, k_pages, v_pages, table, lengths,
                                 interpret=True)
    want = ref.paged_attention_ref(q, k_pages, v_pages, table, lengths)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


def test_paged_kernel_ignores_dead_table_entries():
    """Pages past a sequence's length (or -1 slots) must not contribute."""
    B, H, D, P, page, mp = 1, 2, 32, 8, 8, 4
    q = _rand((B, H, D), jnp.float32)
    kp = _rand((H, P, page, D), jnp.float32)
    vp = _rand((H, P, page, D), jnp.float32)
    table = jnp.asarray([[3, 5, -1, -1]], jnp.int32)
    lengths = jnp.asarray([12], jnp.int32)
    got = paged_attention_pallas(q, kp, vp, table, lengths, interpret=True)
    # poison the dead pages: result must be identical
    kp2 = kp.at[:, 6].set(1e9)
    vp2 = vp.at[:, 6].set(1e9)
    got2 = paged_attention_pallas(q, kp2, vp2, table, lengths, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(got2))
