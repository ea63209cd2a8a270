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
    # (B, H, Hkv, D, n_pool_pages, page, max_pages, lengths, shared)
    # lengths None: drawn at random; shared: every row reads one block list
    (2, 4, 2, 32, 16, 16, 4, None, False),
    (3, 8, 8, 64, 32, 8, 6, None, False),
    (1, 4, 4, 32, 8, 16, 3, None, False),
    # ragged: rows far shorter than the table is wide
    (4, 8, 2, 128, 96, 16, 21, (1, 17, 40, 330), False),
    # live pages not a multiple of the kernel's block: 11 and 7
    (2, 4, 2, 128, 32, 16, 12, (170, 100), False),
    # an empty row between live ones, at head dims 128 and 32
    (3, 4, 2, 128, 16, 16, 4, (20, 0, 50), False),
    (3, 4, 2, 32, 16, 16, 4, (20, 0, 50), False),
    # starcoder2's head grouping (G = 9) and head dim
    (2, 36, 4, 128, 24, 16, 5, None, False),
    # stablelm-12b's KV heads and head dim
    (2, 16, 8, 160, 16, 16, 3, None, False),
    # stablelm-12b's grouping (G = 4) at head dim 160: a ragged decode
    # batch with an empty row, and a prefill chunk
    (4, 32, 8, 160, 48, 16, 10, (150, 0, 17, 33), False),
    (8, 32, 8, 160, 16, 16, 6, tuple(range(86, 94)), True),
    # a prefill chunk: 8 rows over one block list, lengths start+1..start+8
    (8, 8, 2, 128, 16, 16, 6, tuple(range(71, 79)), True),
    # starcoder2's grouping in a prefill chunk
    (8, 36, 4, 128, 16, 16, 4, tuple(range(40, 48)), True),
]


def _pages(Hkv, P, page, D, dtype):
    """A random page pool in the kernel's layout, ``(P, page, Hkv * D)``."""
    return _rand((P, page, Hkv * D), dtype)


@pytest.mark.parametrize("case", PAGED_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_kernel_vs_oracle(case, dtype):
    B, H, Hkv, D, P, page, max_pages, lengths, shared = case
    q = _rand((B, H, D), dtype)
    k_pages = _pages(Hkv, P, page, D, dtype)
    v_pages = _pages(Hkv, P, page, D, dtype)
    drawn = lengths is None
    if drawn:
        lengths = RNG.integers(1, page * max_pages, (B,))
    lengths = np.asarray(lengths)
    live = -(-lengths // page)
    # every table slot a distinct page (one list for all rows when shared)
    ids = RNG.permutation(P)
    if shared:
        table = np.tile(ids[:max_pages], (B, 1)).astype(np.int32)
    else:
        table = ids[:B * max_pages].reshape(B, max_pages).astype(np.int32)
    if drawn:
        # build_block_table's -1 padding past each row's pages; the given
        # lengths keep real pages there, which the kernel must not attend
        table[np.arange(max_pages)[None, :] >= live[:, None]] = -1
    table = jnp.asarray(table)
    lengths = jnp.asarray(lengths, jnp.int32)
    want = ref.paged_attention_ref(q, k_pages, v_pages, table, lengths)
    # poison every page no row reaches: the output must not move
    read = np.zeros(P, bool)
    for b in range(B):
        read[np.asarray(table)[b, :live[b]]] = True
    dead = jnp.asarray(~read)[:, None, None]
    k_pages = jnp.where(dead, jnp.asarray(1e9, dtype), k_pages)
    v_pages = jnp.where(dead, jnp.asarray(1e9, dtype), v_pages)
    got = paged_attention_pallas(q, k_pages, v_pages, table, lengths,
                                 interpret=True)
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    empty = np.asarray(lengths) == 0
    assert (got[empty] == 0).all()               # exact zeros, not NaN
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(got[~empty], want[~empty], atol=tol, rtol=tol)


def test_paged_kernel_under_tpu_interpreter_is_race_free():
    """The TPU interpreter runs every DMA at its wait, fills VMEM the kernel
    never wrote with NaN and checks each access against the DMAs in flight:
    a block computed from pages not yet fetched, a buffer reused too early
    or a read of unwritten VMEM would show as a race or as NaN."""
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call
    from jax.experimental.pallas import tpu as pltpu

    B, H, Hkv, D, P, page, mp = 5, 8, 2, 128, 64, 16, 12
    q = _rand((B, H, D), jnp.bfloat16)
    kp = _pages(Hkv, P, page, D, jnp.bfloat16)
    vp = _pages(Hkv, P, page, D, jnp.bfloat16)
    # empty rows, and 9, 1 and 12 pages: blocks of 8, some partial
    lengths = np.asarray([0, 130, 7, 0, 185])
    table = np.full((B, mp), -1, np.int32)
    for b in range(B):
        n = -(-int(lengths[b]) // page)
        table[b, :n] = RNG.choice(P, n, replace=False)
    table, lengths = jnp.asarray(table), jnp.asarray(lengths, jnp.int32)
    params = pltpu.InterpretParams(detect_races=True,
                                   uninitialized_memory="nan")
    got = np.asarray(paged_attention_pallas(q, kp, vp, table, lengths,
                                            interpret=params), np.float32)
    assert not interpret_pallas_call.races.races_found
    want = np.asarray(ref.paged_attention_ref(q, kp, vp, table, lengths),
                      np.float32)
    live = np.asarray(lengths) > 0
    assert (got[~live] == 0).all()
    np.testing.assert_allclose(got[live], want[live], atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("lengths", [(0, 0), (1, 16, 17, 0), (5, 300, 64)])
def test_table_pages_counts_the_built_table(lengths):
    """``table_pages`` gives the slots of the table ``build_block_table``
    packs, and the pages of it that the rows' lengths reach."""
    from repro.kernels.paged_attention import build_block_table, table_pages

    page = 16
    blocks = [list(range(100 * i, 100 * i + 20)) for i in range(len(lengths))]
    table, _ = build_block_table(blocks, lengths, page=page)
    live, slots = table_pages(lengths, page)
    assert slots == table.size
    assert live == int((np.asarray(table) >= 0).sum())


def test_fetch_plan_on_the_host_is_the_kernels():
    """The numpy fetch plan that counts the kernel's page DMAs is the one
    the kernel steers them by: ragged rows, empty rows, -1 entries inside
    a row and rows that share one block list."""
    import jax.numpy as jnp_
    from repro.kernels.paged_attention import fetch_plan

    for trial in range(20):
        B, width, page, ppb = 1 + trial % 6, 1 + trial % 9, 16, 1 + trial % 4
        table = RNG.integers(-1, 50, (B, width)).astype(np.int32)
        if trial % 3 == 0:
            table[:] = table[0]
        lengths = RNG.integers(0, page * width + 1, B).astype(np.int32)
        want = fetch_plan(jnp_.asarray(table), jnp_.asarray(lengths), page,
                          ppb, jnp_)
        np.testing.assert_array_equal(
            fetch_plan(table, lengths, page, ppb), np.asarray(want))


def test_paged_kernel_reads_page_0_for_a_dead_entry_inside_a_row():
    """A -1 entry inside a row's length reads page 0, unmasked, as the XLA
    reference does: the kernel masks by length alone.  (The block-table
    builder never writes a -1 there.)"""
    B, H, Hkv, D, P, page = 2, 4, 2, 128, 8, 16
    q = _rand((B, H, D), jnp.float32)
    kp = _pages(Hkv, P, page, D, jnp.float32)
    vp = _pages(Hkv, P, page, D, jnp.float32)
    table = jnp.asarray([[3, -1, 5, -1], [-1, 6, -1, -1]], jnp.int32)
    lengths = jnp.asarray([40, 20], jnp.int32)
    got = paged_attention_pallas(q, kp, vp, table, lengths, interpret=True)
    want = ref.paged_attention_ref(q, kp, vp, table, lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    # page 0 is read: the same table with 0 in those slots agrees exactly
    zeroed = paged_attention_pallas(q, kp, vp, jnp.maximum(table, 0),
                                    lengths, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(zeroed))


def test_paged_kernel_ignores_dead_table_entries():
    """Pages past a sequence's length (or -1 slots) must not contribute."""
    B, H, D, P, page, mp = 1, 2, 32, 8, 8, 4
    q = _rand((B, H, D), jnp.float32)
    kp = _pages(H, P, page, D, jnp.float32)
    vp = _pages(H, P, page, D, jnp.float32)
    table = jnp.asarray([[3, 5, -1, -1]], jnp.int32)
    lengths = jnp.asarray([12], jnp.int32)
    got = paged_attention_pallas(q, kp, vp, table, lengths, interpret=True)
    # poison the dead pages: result must be identical
    kp2 = kp.at[6].set(1e9)
    vp2 = vp.at[6].set(1e9)
    got2 = paged_attention_pallas(q, kp2, vp2, table, lengths, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(got2))
