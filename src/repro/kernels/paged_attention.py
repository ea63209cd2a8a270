"""Paged decode attention as a Pallas TPU kernel -- the device half of the
POP-managed KV block pool (DESIGN.md §2.3).

The block table produced by the host-side ``runtime/block_pool.py`` is a
*scalar-prefetch* operand: the kernel reads it to decide which physical
pages of the pool to DMA into VMEM next, so the gather happens in the
memory pipeline, not as a materialized (B, max_pages*page, ...) tensor in
HBM like the XLA reference.

Page layout: ``(P, page, Hkv * D)`` per layer -- token-major, every KV head
of a token side by side along the lanes.  A page is one contiguous
``(page, Hkv * D)`` tile, one DMA, and its minor dimension is lane-dense
whenever ``Hkv * D`` is a multiple of 128 (stablelm-12b: 8 x 160 = 1280,
where a head-major ``(page, 160)`` block is eight DMAs and takes 256 lanes
in VMEM).  Measured alone on a v5e, the kernel and the page write ran
faster on this layout at head dims 160 and 128 alike.

grid = (B, blocks): each grid step folds one block of ``ppb`` pages of a
row into its online softmax.  ``ppb`` follows from the shapes
(:func:`_pages_per_block`: 128 tokens' worth, within a VMEM budget).  The
block's pages are ``ppb`` K and ``ppb`` V operands whose index maps read a
scalar-prefetched page list (:func:`fetch_plan`), so the pipeline
double-buffers their DMAs.  Each row fetches its **live pages only** --
``ceil(len / page)`` of them (:func:`live_pages`), not the table's width,
which the batch's longest row sets: past them an operand keeps the page it
holds, so the pipeline moves nothing, and the block is skipped;
:func:`page_fetches` counts the DMAs a call makes.  Positions past the
length inside the last page are masked.  A -1 entry inside a row's length
reads page 0, as the XLA reference does; :func:`build_block_table` never
writes one there.

Every KV head is scored against its own lanes of a page by one matmul: the
query enters block-diagonal, ``(H, Hkv * D)`` with row ``h * G + g``
holding head ``(h, g)``'s query in head ``h``'s lanes and zeros elsewhere,
and the output leaves as ``(H, Hkv * D)``, of which each row keeps its own
head's lanes; both are laid out and taken apart by XLA around the kernel.
The online softmax (m, l, acc) and both matmuls run in f32.

Every (rows, table width) of a call is a program of its own; the serving
path builds its inputs at :func:`call_shape`'s buckets so that calls share
programs.

Ragged decode batches: a serving step batches requests whose block lists
have wildly different lengths (fresh single-page requests next to
max-pages ones, and rows that hold zero tokens).  :func:`build_block_table`
packs such ragged lists into the kernel's padded ``(B, max_pages)`` layout
-- table width is the BATCH max, not the engine max -- and the kernel
itself guarantees a fully-dead row (length 0, all entries -1) produces
exact zeros instead of NaN garbage, so empty requests ride through the
batched call unharmed.
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


def live_pages(lengths, page: int):
    """Pages that rows of ``lengths`` tokens reach: the pages a row's table
    entries cover, and all that :func:`paged_attention_pallas` fetches of
    it.  Takes ints and numpy or jax arrays alike."""
    return -(-lengths // page)


def table_pages(lengths: Sequence[int], page: int) -> Tuple[int, int]:
    """``(live, slots)`` for rows holding ``lengths`` tokens: the pages the
    rows reach, all that the kernel fetches, and rows x the widest row's
    pages, the slots of the table :func:`build_block_table` packs for them
    at its default ``min_pages`` (before any :func:`call_shape` padding)."""
    live = [live_pages(int(n), page) for n in lengths]
    return sum(live), len(live) * max([1] + live)


def build_block_table(
    blocks: Sequence[Sequence[int]],
    lengths: Sequence[int],
    *,
    page: int,
    min_pages: int = 1,
) -> Tuple[np.ndarray, np.ndarray]:
    """Pack per-request block lists into a padded ``(B, max_pages)`` table.

    ``blocks[b]`` is request b's physical page list (block-pool ids, prefix-
    shared pages first); ``lengths[b]`` the number of tokens it currently
    holds.  Only the pages that cover ``lengths[b]`` tokens enter the row --
    trailing pre-allocated-but-unwritten pages are dead entries (-1), so a
    premature gather of an unwritten page can never look valid.  Width is
    max(ceil(len/page)) over the batch, floored at ``min_pages`` so the
    kernel grid never gets a zero-sized axis (an all-empty batch still
    produces a well-formed (B, min_pages) table of -1s).  Returns numpy
    int32 ``(table, lengths)``.
    """
    rows: List[List[int]] = []
    for i, (bl, ln) in enumerate(zip(blocks, lengths)):
        used = live_pages(int(ln), page)    # pages holding actual tokens
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
    return table, np.asarray(lengths, np.int32)


#: tokens one block of pages should carry at least: one MXU-wide K tile
_BLOCK_TOKENS = 128
#: VMEM the K/V block buffers may take (two slots each of K and V)
_BLOCK_VMEM = 4 << 20


def _pages_per_block(page: int, hkv: int, d: int, itemsize: int,
                     width: int) -> int:
    """Pages one block of the kernel fetches and computes: enough for
    :data:`_BLOCK_TOKENS` tokens, within :data:`_BLOCK_VMEM` of K/V
    buffers (a page of every KV head, lanes padded to 128), and no more
    than the table is wide."""
    lanes = -(-(hkv * d) // 128) * 128
    per_page = 2 * 2 * page * lanes * itemsize     # K, V x two slots
    cap = max(1, _BLOCK_VMEM // per_page)
    return max(1, min(-(-_BLOCK_TOKENS // page), cap, width))


def call_shape(rows: int, width: int, *, page: int, hkv: int, d: int,
               itemsize: int) -> Tuple[int, int]:
    """The (rows, table width) to build a call's inputs at: rows padded to
    a multiple of 8 (padded rows empty) and the width to one of the block's
    pages.  Each shape of the inputs is a program of its own, loaded in
    set-up, and the serving path meets hundreds; padded to these, calls
    share one program per bucket."""
    ppb = _pages_per_block(page, hkv, d, itemsize, width)
    return -(-rows // 8) * 8, -(-width // ppb) * ppb


def fetch_plan(table, lengths, page: int, ppb: int, xp=np):
    """``fetch[s, j]``: the page K/V operand ``j`` holds at grid step ``s``
    (row-major over (rows, blocks)) of a call over ``table`` and
    ``lengths``.  Past a row's live pages it is the page the operand
    already holds, so the pipeline moves nothing; a -1 entry inside a
    row's length reads page 0.  ``xp`` is ``numpy`` or ``jax.numpy``: the
    kernel steers its DMAs by the second, :func:`page_fetches` counts them
    by the first."""
    B, width = table.shape
    nb = -(-width // ppb)                        # blocks a row walks
    table = xp.pad(table, ((0, 0), (0, nb * ppb - width)),
                   constant_values=-1)
    live = xp.clip(live_pages(lengths, page), 0, width)
    steps = xp.arange(B * nb, dtype=table.dtype)[:, None]
    held = (xp.arange(nb * ppb, dtype=table.dtype)[None, :]
            < live[:, None]).reshape(B * nb, ppb)
    last = xp.maximum.accumulate(xp.where(held, steps, -1), axis=0)
    fetch = xp.take_along_axis(table.reshape(B * nb, ppb),
                               xp.maximum(last, 0), axis=0)
    return xp.where(last >= 0, xp.maximum(fetch, 0), 0)


def page_fetches(table: np.ndarray, lengths: np.ndarray, *, page: int,
                 hkv: int, d: int, itemsize: int) -> int:
    """Page DMAs of the K operands of one kernel call over ``table`` and
    ``lengths`` (the V operands make as many): every operand at the first
    grid step, then one wherever an operand's page differs from the step
    before."""
    ppb = _pages_per_block(page, hkv, d, itemsize, table.shape[1])
    f = fetch_plan(np.asarray(table), np.asarray(lengths), page, ppb)
    return int(f.shape[1] + (f[1:] != f[:-1]).sum())


def _paged_kernel(fetch_ref, len_ref, live_ref,                   # SMEM
                  q_ref, *refs, page, ppb, scale, softcap):
    """grid = (B, blocks): the pipeline brings block i's pages as ``ppb``
    K and ``ppb`` V operands; a block past the row's live pages keeps the
    pages already resident (no DMA) and is skipped."""
    k_refs, v_refs = refs[:ppb], refs[ppb:2 * ppb]
    o_ref, m_scr, l_scr, acc_scr = refs[2 * ppb:]
    b, i = pl.program_id(0), pl.program_id(1)

    @pl.when(i == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(i * ppb < live_ref[b])
    def _():
        bk = ppb * page
        valid = (jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1) + i * bk
                 < len_ref[b])
        # q (H, Hkv*D) block-diagonal, k (bk, Hkv*D), v (bk, Hkv*Dv), all
        # in f32: one matmul scores every head against its own lanes
        k, v = (jnp.concatenate([r[...].astype(jnp.float32) for r in refs_],
                                axis=0) for refs_ in (k_refs, v_refs))
        q = q_ref[0].astype(jnp.float32) * scale
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        s = jnp.where(valid, s, NEG_INF)
        m_prev, l_prev = m_scr[...], l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # explicit dead-position zeroing: while a row has no valid position
        # (an empty request in a ragged batch), m_new stays at NEG_INF and
        # exp(s - m_new) would be exp(0) = 1 for every dead slot -- the
        # masked weights must be zero so the row accumulates nothing and
        # the final normalization (l == 0) yields exact zeros
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        m_scr[...] = m_new
        l_scr[...] = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jnp.dot(
            p, v, preferred_element_type=jnp.float32)

    # an empty row attends nothing and finishes as exact zeros (l == 0)
    @pl.when(i == pl.num_programs(1) - 1)
    def _():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(
            o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("softcap", "scale", "interpret"))
def paged_attention_pallas(
    q: jnp.ndarray,               # (B, H, D)
    k_pages: jnp.ndarray,         # (P, page, Hkv * D)
    v_pages: jnp.ndarray,         # (P, page, Hkv * Dv)
    block_table: jnp.ndarray,     # (B, max_pages) int32, -1 padded
    lengths: jnp.ndarray,         # (B,) int32
    *,
    softcap: float = 0.0,
    scale: Optional[float] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    B, H, D = q.shape
    P, page, KD = k_pages.shape
    Hkv = KD // D
    VD = v_pages.shape[-1]
    Dv = VD // Hkv
    max_pages = block_table.shape[1]
    G = H // Hkv
    ppb = _pages_per_block(page, Hkv, max(D, Dv), k_pages.dtype.itemsize,
                           max_pages)
    nb = -(-max_pages // ppb)                    # blocks a row walks
    lens = lengths.astype(jnp.int32)
    live = jnp.clip(live_pages(lens, page), 0, max_pages)
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    fetch = fetch_plan(block_table.astype(jnp.int32), lens, page, ppb, jnp)
    # the query block-diagonal over the KV heads: (B, H, Hkv * D)
    eye = jnp.eye(Hkv, dtype=q.dtype)[None, :, None, :, None]
    q_bd = (q.reshape(B, Hkv, G, 1, D) * eye).reshape(B, H, KD)

    def page_spec(j, w):
        return pl.BlockSpec(
            (None, page, w),
            lambda b, i, f, *_: (f[(b * nb + i) * ppb + j], 0, 0))

    out = pl.pallas_call(
        functools.partial(_paged_kernel, page=page, ppb=ppb, scale=scale,
                          softcap=softcap),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, nb),
            in_specs=[pl.BlockSpec((1, H, KD), lambda b, i, *_: (b, 0, 0))]
            + [page_spec(j, KD) for j in range(ppb)]
            + [page_spec(j, VD) for j in range(ppb)],
            out_specs=pl.BlockSpec((1, H, VD), lambda b, i, *_: (b, 0, 0)),
            scratch_shapes=[pltpu.VMEM((H, 1), jnp.float32),
                            pltpu.VMEM((H, 1), jnp.float32),
                            pltpu.VMEM((H, VD), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, VD), q.dtype),
        interpret=interpret,
    )(fetch.reshape(-1), lens, live, q_bd,
      *[k_pages] * ppb, *[v_pages] * ppb)
    # a row's lanes of the other KV heads weigh their values by its own
    # head's softmax: the mask keeps its own head's, a sum of one term
    out = out.reshape(B, Hkv, G, Hkv, Dv) * eye.astype(out.dtype)
    return out.sum(axis=3).reshape(B, H, Dv)

