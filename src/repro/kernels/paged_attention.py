"""Paged decode attention as a Pallas TPU kernel -- the device half of the
POP-managed KV block pool (DESIGN.md §2.3).

The block table produced by the host-side ``runtime/block_pool.py`` is a
*scalar-prefetch* operand: the BlockSpec index_map reads it to decide which
physical page of the pool to DMA into VMEM next, so the gather happens in
the memory pipeline (double-buffered page fetches), not as a materialized
(B, max_pages*page, ...) tensor in HBM like the XLA reference.

Page layout: ``(Hkv, P, page, D)`` per layer -- head-major, so one grid
step's block ``(1, 1, page, D)`` has the array's own last two dimensions.
That is what Mosaic's tiling rule asks of a block (last two dims divisible
by (8, 128) or equal to the array's), so the kernel lowers at any Hkv,
page size and head dim; a token-major ``(P, page, Hkv, D)`` pool would need
a ``(page, 1, D)`` block, which the TPU compiler refuses whenever Hkv > 1.

grid = (B, Hkv, n_pages); pages are the sequential axis with the online
softmax state (m, l, acc) in VMEM scratch.  Dead table entries (-1) are
masked and their DMA redirected to page 0.

Ragged decode batches: a serving step batches requests whose block lists
have wildly different lengths (fresh single-page requests next to
max-pages ones, and rows that hold zero tokens).  :func:`build_block_table`
packs such ragged lists into the kernel's padded ``(B, max_pages)`` layout
-- table width is the BATCH max, not the engine max, so short batches do
not pay dead grid iterations -- and the kernel itself guarantees a
fully-dead row (length 0, all entries -1) produces exact zeros instead of
NaN garbage, so empty requests ride through the batched call unharmed.
"""

from __future__ import annotations

import functools
import math
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def paged_impl() -> str:
    """Kernel mode of the paged path on this process's backend: ``"pallas"``
    (compiled) on a TPU, ``"interpret"`` (the kernel body run by the Pallas
    interpreter) anywhere else.  The one place that decides; callers that
    need the chip check the returned mode instead of trusting it."""
    return "pallas" if jax.default_backend() == "tpu" else "interpret"


def build_block_table(
    blocks: Sequence[Sequence[int]],
    lengths: Sequence[int],
    *,
    page: int,
    min_pages: int = 1,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Pack per-request block lists into a padded ``(B, max_pages)`` table.

    ``blocks[b]`` is request b's physical page list (block-pool ids, prefix-
    shared pages first); ``lengths[b]`` the number of tokens it currently
    holds.  Only the pages that cover ``lengths[b]`` tokens enter the row --
    trailing pre-allocated-but-unwritten pages are dead entries (-1), so a
    premature gather of an unwritten page can never look valid.  Width is
    max(ceil(len/page)) over the batch, floored at ``min_pages`` so the
    kernel grid never gets a zero-sized axis (an all-empty batch still
    produces a well-formed (B, min_pages) table of -1s).
    """
    rows: List[List[int]] = []
    for i, (bl, ln) in enumerate(zip(blocks, lengths)):
        used = -(-int(ln) // page)          # pages holding actual tokens
        if len(bl) < used:
            # silent truncation would mask positions the caller claims
            # exist -- wrong attention with no error; fail loudly instead
            raise ValueError(
                f"request {i}: {int(ln)} tokens need {used} pages, "
                f"block list has {len(bl)}")
        rows.append(list(bl[:used]))
    width = max([min_pages] + [len(r) for r in rows])
    table = np.full((len(rows), width), -1, np.int32)
    for i, r in enumerate(rows):
        table[i, :len(r)] = r
    return (jnp.asarray(table),
            jnp.asarray(np.asarray(lengths, np.int32)))


def _paged_kernel(table_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, page, n_pages, scale, softcap, g):
    b = pl.program_id(0)
    pi = pl.program_id(2)

    @pl.when(pi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)          # (G, D)
    k = k_ref[0, 0].astype(jnp.float32)          # (page, D)
    v = v_ref[0, 0].astype(jnp.float32)          # (page, Dv)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + pi * page
    valid = (pos < len_ref[b]) & (table_ref[b, pi] >= 0)
    s = jnp.where(valid, s, NEG_INF)

    m_prev, l_prev = m_scr[...], l_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    # explicit dead-position zeroing: when a row has NO valid position at all
    # (empty request in a ragged batch), m_new stays at NEG_INF and
    # exp(s - m_new) would be exp(0) = 1 for every dead slot -- the masked
    # weights must be forced to zero so the row accumulates nothing and the
    # final normalization (l == 0) yields exact zeros, not a mean over junk
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    m_scr[...] = m_new
    l_scr[...] = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(pi == n_pages - 1)
    def _fin():
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(
            o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("softcap", "scale", "interpret"))
def paged_attention_pallas(
    q: jnp.ndarray,               # (B, H, D)
    k_pages: jnp.ndarray,         # (Hkv, P, page, D)
    v_pages: jnp.ndarray,         # (Hkv, P, page, Dv)
    block_table: jnp.ndarray,     # (B, max_pages) int32, -1 padded
    lengths: jnp.ndarray,         # (B,) int32
    *,
    softcap: float = 0.0,
    scale: Optional[float] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    B, H, D = q.shape
    Hkv, P, page, _ = k_pages.shape
    Dv = v_pages.shape[-1]
    G = H // Hkv
    max_pages = block_table.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(D)

    qh = q.reshape(B, Hkv, G, D)
    safe_table = jnp.maximum(block_table, 0).astype(jnp.int32)

    grid = (B, Hkv, max_pages)
    kernel = functools.partial(_paged_kernel, page=page, n_pages=max_pages,
                               scale=scale, softcap=softcap, g=G)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,        # block table + lengths
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, G, D), lambda b, h, p, tbl, lens: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, page, D),
                             lambda b, h, p, tbl, lens: (h, tbl[b, p], 0, 0)),
                pl.BlockSpec((1, 1, page, Dv),
                             lambda b, h, p, tbl, lens: (h, tbl[b, p], 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, G, Dv),
                                   lambda b, h, p, tbl, lens: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, Dv), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, Dv), q.dtype),
        interpret=interpret,
    )(safe_table, lengths.astype(jnp.int32), qh, k_pages, v_pages)

    return out.reshape(B, H, Dv)


def _scatter_kernel(blk_ref, slot_ref, vals_ref, page_ref, out_ref):
    # grid=(Hkv, T): the index maps steered this step to the whole
    # (page, D) tile pages[h, blk[t]].  Tokens bound for one page arrive
    # back to back, and while the output block index stays put Pallas keeps
    # the tile in VMEM and skips the refetch -- so copy the page in on the
    # first token of a run, then overwrite one row per token.
    t = pl.program_id(1)
    first = jnp.logical_or(t == 0, blk_ref[jnp.maximum(t - 1, 0)] != blk_ref[t])

    @pl.when(first)
    def _load():
        out_ref[...] = page_ref[...]

    row = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape[2:], 0)
    out_ref[0, 0] = jnp.where(row == slot_ref[t], vals_ref[0, 0],
                              out_ref[0, 0])


def paged_scatter_pallas(
    pages: jnp.ndarray,           # (Hkv, P, page, D) physical page pool
    block_idx: jnp.ndarray,       # (T,) destination page per token
    slot_idx: jnp.ndarray,        # (T,) destination slot per token
    vals: jnp.ndarray,            # (T, Hkv, D) token K (or V) vectors
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    """Token scatter as a Pallas kernel: write T token vectors into the
    physical page pool at ``pages[:, block_idx[t], slot_idx[t]]`` -- the
    write half of the block-table contract :func:`paged_attention_pallas`
    reads.

    The destination indices are scalar-prefetch operands driving the
    BlockSpec index maps (the same trick the gather kernel uses for its
    page DMA).  Each grid step moves a whole ``(page, D)`` tile, the only
    block shape over this layout that the TPU tiling rule admits, and
    rewrites one row of it.  ``input_output_aliases`` makes the pool buffer
    the output buffer: untouched pages are preserved and -- when the caller
    donates ``pages`` under jit, as the device KV storage does -- the
    update is in place, O(tokens * page) moved instead of O(pool).

    Tokens may come in any order.  The kernel keeps a page resident only
    while consecutive tokens target it -- a page revisited after another
    would be refetched while its first write may still be in flight -- so
    the tokens are first stably sorted by page: each page's writes become
    one run, and of two writes to one slot the later still wins.
    """
    T, Hkv, D = vals.shape
    page = pages.shape[2]
    order = jnp.argsort(block_idx, stable=True)
    block_idx, slot_idx, vals = block_idx[order], slot_idx[order], vals[order]
    return pl.pallas_call(
        _scatter_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,        # block_idx + slot_idx
            grid=(Hkv, T),
            in_specs=[
                pl.BlockSpec((1, 1, 1, D), lambda h, t, blk, slot: (t, h, 0, 0)),
                pl.BlockSpec((1, 1, page, D),
                             lambda h, t, blk, slot: (h, blk[t], 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, page, D),
                                   lambda h, t, blk, slot: (h, blk[t], 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct(pages.shape, pages.dtype),
        # flattened input index 3 = pages (after the 2 prefetch operands
        # and vals): alias it straight into the output pool
        input_output_aliases={3: 0},
        interpret=interpret,
    )(block_idx.astype(jnp.int32), slot_idx.astype(jnp.int32),
      vals.astype(pages.dtype)[:, :, None, :], pages)
