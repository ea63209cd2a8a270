"""Paged decode forward: the model math of a decode step driven through the
physically paged KV store and the Pallas paged-attention kernel.

The dense serving path runs :func:`repro.models.model.apply_model` in decode
mode against a per-request ``(L, max_seq, Hkv, hd)`` cache -- every token
functionally updates the whole cache and prefix "sharing" is a snapshot
copy.  This module is the paged twin: attention reads K/V straight out of
the :class:`~repro.runtime.kv_store.PagedKVStore`'s physical pages through a
per-request block table (``kernels/paged_attention.py``), and the only
per-token write is a single page-slot scatter.

Scope: the paged path supports the GQA transformer family the serving demo
and tests exercise -- every layer ``mixer="attn"`` with ``attn_kind="full"``
and a dense MLP (qk_norm / post_norms / softcaps / partial rotary all
honored).  MLA, sliding-window, SSM/RWKV mixers, MoE, cross-attention and
weight-tied shared attention keep using the dense path;
:func:`check_paged_support` rejects them up front so the failure mode is a
clear error at engine construction, not silent wrong math.

The per-layer loop runs at host level (a page-store write sits between the
projection math and the kernel call), so this is NOT one jitted function;
the projection and MLP halves of a layer are jitted programs shared by all
layers, and the kernel runs in the mode each entry point's required
``impl`` names (``kernels.paged_attention.paged_impl()`` gives compiled
Pallas on a TPU and interpret mode elsewhere; there is no default).  The
data plane, however, is storage-aware end to end: the new K/V stay jax
arrays from projection to
:meth:`PagedKVStore.append_tokens`/``write_prefill`` (under device storage
that is a donated in-place scatter with ZERO host traffic), and
``layer_pages`` hands the kernel the store's resident arrays -- no
per-layer, per-step pool re-upload.  Each layer's
write -> gather -> kernel-dispatch span runs under
:meth:`PagedKVStore.write_guard` so a concurrent writer's buffer donation
can never invalidate the pages mid-dispatch.
"""

from __future__ import annotations

import functools
import math
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import layers as L
from repro.models.layers import apply_rope, rms_norm
from repro.models.model import apply_model
from repro.runtime.kv_store import PagedKVStore, kv_layer_order

__all__ = ["check_paged_support", "prefill_kv", "prefill_kv_chunked",
           "prefill_chunk_step", "paged_decode_step", "kv_fetch_bytes"]


def check_paged_support(cfg: ArchConfig) -> None:
    """Raise ValueError unless every layer of ``cfg`` is paged-decodable."""
    problems: List[str] = []
    if cfg.encoder_groups:
        problems.append("encoder_groups (enc-dec)")
    if cfg.mtp:
        problems.append("mtp head")
    for gi, g in enumerate(cfg.groups):
        for pi, ls in enumerate(g.pattern):
            where = f"g{gi}/p{pi}"
            if ls.mixer != "attn":
                problems.append(f"{where}: mixer={ls.mixer}")
            elif ls.attn_kind != "full":
                problems.append(f"{where}: attn_kind={ls.attn_kind}")
            if ls.mlp != "dense":
                problems.append(f"{where}: mlp={ls.mlp}")
            if ls.shared_attn:
                problems.append(f"{where}: shared_attn")
            if not ls.causal:
                problems.append(f"{where}: non-causal")
    if problems:
        raise ValueError(
            "config not supported by the paged KV path (use kv_store="
            "'dense'): " + "; ".join(problems))


# ----------------------------------------------------------------------------
# prefill: dense full-sequence forward, K/V extracted for the page writes
# ----------------------------------------------------------------------------


def prefill_kv(params, cfg: ArchConfig,
               tokens: Sequence[int]) -> Tuple[np.ndarray, np.ndarray]:
    """Prefill a prompt with the standard full-sequence forward and return
    its per-layer post-rope K/V as ``(L, S, Hkv, hd)`` numpy arrays, in
    :func:`~repro.runtime.kv_store.kv_layer_order` order -- ready for
    :meth:`PagedKVStore.write_prefill`.

    Prefill stays dense on purpose (one batched matmul pass beats S
    single-token steps); only the *storage* of its result is paged.
    """
    toks = jnp.asarray([list(tokens)], jnp.int32)
    _, cache, _ = apply_model(params, toks, cfg=cfg, mode="prefill")
    ks, vs = [], []
    for gi, pi, rep in kv_layer_order(cfg):
        lc = cache["groups"][f"g{gi}"][f"p{pi}"]
        # keep the cache's own dtype: the page arrays store exactly the
        # values the dense path would (bit-for-bit for bf16 and f32 alike)
        ks.append(np.asarray(lc["k"][rep, 0]))               # (S, Hkv, hd)
        vs.append(np.asarray(lc["v"][rep, 0]))
    return np.stack(ks), np.stack(vs)


# ----------------------------------------------------------------------------
# shared forward: decode steps and prefill chunks are the same math
# ----------------------------------------------------------------------------


# The layer math around the page write and the kernel call, jitted per
# half-layer.  ``gp`` is a group's stacked weights and ``rep`` a traced
# layer index, so one program serves every layer and the layer's weights
# are sliced inside it: an eager ``a[rep]`` would copy each layer's weights
# per call, and a host loop running ahead of the device would hold many
# such copies at once.


def _layer(gp, rep):
    return jax.tree.map(lambda a: a[rep], gp)


@functools.partial(jax.jit, static_argnames="cfg")
def _embed(embed, toks, *, cfg: ArchConfig):
    x = jnp.take(embed, toks[:, None], axis=0).astype(cfg.dtype)  # (B,1,D)
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, cfg.dtype)
    return x


@functools.partial(jax.jit, static_argnames=("cfg", "rows"))
def _qkv(gp, rep, x, positions, *, cfg: ArchConfig, rows: int):
    """norm -> q/k/v projections -> qk-norm -> rope, for one row per
    batch entry: ``(q (rows,H,hd) f32, k (B,Hkv,hd), v (B,Hkv,hd))``, q
    padded with zero rows to the attention call's ``rows``."""
    lp = _layer(gp, rep)
    h = rms_norm(x, lp["norm1"], cfg.norm_eps)
    ap = lp["attn"]
    q = jnp.einsum("bsd,dhe->bshe", h, ap["wq"])
    k = jnp.einsum("bsd,dhe->bshe", h, ap["wk"])
    v = jnp.einsum("bsd,dhe->bshe", h, ap["wv"])
    if cfg.qk_norm:
        q = rms_norm(q, ap["q_scale"], cfg.norm_eps)
        k = rms_norm(k, ap["k_scale"], cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_pct)
    k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_pct)
    q = q[:, 0].astype(jnp.float32)
    return (jnp.pad(q, ((0, rows - q.shape[0]), (0, 0), (0, 0))),
            k[:, 0], v[:, 0])


@functools.partial(jax.jit, static_argnames=("cfg", "parallel"))
def _out_mlp(gp, rep, x, attn, *, cfg: ArchConfig, parallel: bool):
    """Output projection + residual, then the MLP block + residual: off a
    second norm of the new residual, or with ``parallel`` off the
    attention's own norm of the layer's input ``x``.  ``attn``'s rows past
    ``x``'s are padding, dropped."""
    lp = _layer(gp, rep)
    B = x.shape[0]
    attn = attn[:B]
    H, hd = attn.shape[1:]
    o = jnp.einsum("bshe,hed->bsd",
                   attn.reshape(B, 1, H, hd).astype(cfg.dtype),
                   lp["attn"]["wo"])
    if cfg.post_norms:
        o = rms_norm(o, lp["post_norm1"], cfg.norm_eps)
    if parallel:
        h = rms_norm(x, lp["norm1"], cfg.norm_eps)
    x = x + o
    if not parallel:
        h = rms_norm(x, lp["norm2"], cfg.norm_eps)
    o = L.mlp_apply(lp["mlp"], h, cfg.act)
    if cfg.post_norms:
        o = rms_norm(o, lp["post_norm2"], cfg.norm_eps)
    return x + o


@functools.partial(jax.jit, static_argnames="cfg")
def _logits(final_norm, head, x, *, cfg: ArchConfig):
    x = rms_norm(x, final_norm, cfg.norm_eps)
    logits = jnp.einsum("bsd,dv->bsv", x, head.astype(cfg.dtype))
    logits = L.softcap(logits, cfg.logit_softcap)
    if cfg.vocab_padded != cfg.vocab:
        pad_mask = jnp.arange(cfg.vocab_padded) < cfg.vocab
        logits = jnp.where(pad_mask, logits, -1e30)
    return logits[:, 0]


def _call_inputs(store: PagedKVStore, cfg: ArchConfig, blocks, lens):
    """``(rows, table, att)``: the attention call's row count, numpy block
    table and attended lengths for rows ``blocks``/``lens``, at the call's
    bucket of shapes (padded rows empty, so calls share one program per
    bucket).  Each row attends its fed token too."""
    from repro.kernels.paged_attention import (build_block_table,
                                               call_shape, live_pages)

    att = np.asarray(lens, np.int64) + 1
    B = len(att)
    rows, width = call_shape(B, int(live_pages(att, store.page).max()),
                             page=store.page, hkv=cfg.n_kv_heads,
                             d=cfg.head_dim_,
                             itemsize=jnp.dtype(cfg.dtype).itemsize)
    att = np.pad(att, (0, rows - B))
    table, att = build_block_table(list(blocks) + [[]] * (rows - B), att,
                                   page=store.page, min_pages=width)
    return rows, table, att


def kv_fetch_bytes(store: PagedKVStore, cfg: ArchConfig, blocks,
                   lens: Sequence[int]) -> int:
    """Device bytes of the K and V pages that the attention calls of a
    paged forward over rows ``blocks``/``lens`` fetch, summed over layers:
    each page at its size on the device, padding included, as often as
    the kernel's DMAs move it (:func:`~repro.kernels.paged_attention.
    page_fetches`)."""
    from repro.kernels.paged_attention import page_fetches

    _, table, att = _call_inputs(store, cfg, blocks, lens)
    n = page_fetches(table, att, page=store.page, hkv=cfg.n_kv_heads,
                     d=cfg.head_dim_, itemsize=jnp.dtype(cfg.dtype).itemsize)
    return n * store.page_fetch_bytes * len(store.layer_order)


def _paged_forward(params, cfg: ArchConfig, store: PagedKVStore,
                   blocks, lens, tokens, *, impl: str,
                   write_layer) -> jnp.ndarray:
    """The transformer loop both paged entry points share: embed the fed
    tokens (one per row), and per layer project -> rope -> hand the new K/V
    to ``write_layer`` (which scatters them into the physical pages) ->
    gather through the padded block table with the paged-attention kernel.

    ``blocks``/``lens``/``tokens`` are per-ROW: a decode step has one row
    per request (each its own block list); a prefill chunk has one row per
    chunk position, all rows sharing ONE block list with consecutive
    positions.  Causality is the kernel's length masking: row i's K/V is in
    the pages before any row gathers (``write_layer`` runs first), and row
    i attends only to positions < lens[i] + 1.
    """
    from repro.kernels import ops as kops

    # the table and lengths (and q, in _qkv) at the attention call's bucket
    # of shapes
    rows, table, att = _call_inputs(store, cfg, blocks, lens)
    table, att_lens = jnp.asarray(table), jnp.asarray(att)
    positions = jnp.asarray(np.asarray(lens), jnp.int32)[:, None]   # (B,1)
    x = _embed(params["embed"], jnp.asarray(list(tokens), jnp.int32),
               cfg=cfg)                                      # (B,1,D)
    scale = (1.0 / math.sqrt(cfg.attn_scale) if cfg.attn_scale
             else 1.0 / math.sqrt(cfg.head_dim_))

    for li, (gi, pi, rep) in enumerate(store.layer_order):
        gp = params["groups"][f"g{gi}"][f"p{pi}"]
        parallel = cfg.groups[gi].pattern[pi].parallel
        q, k, v = _qkv(gp, rep, x, positions, cfg=cfg, rows=rows)
        # physical write: every row's K/V lands in its page BEFORE the
        # gather, so each new position attends to itself (and, in a prefill
        # chunk, to its chunk-mates) exactly like the dense path -- model
        # dtype preserved end to end.  The K/V stay jax arrays: under
        # device storage the scatter and the gather both run against the
        # RESIDENT pages (no host round trip), and the guard keeps a
        # concurrent writer's buffer donation from invalidating the pages
        # between fetch and kernel dispatch.
        with store.write_guard():
            write_layer(li, k, v)                            # (B, Hkv, hd)
            k_pages, v_pages = store.layer_pages(li)
            out = kops.paged_attention(
                q, k_pages, v_pages, table, att_lens,        # q (B, H, hd)
                softcap=cfg.attn_softcap, scale=scale, impl=impl)
        x = _out_mlp(gp, rep, x, out, cfg=cfg, parallel=parallel)

    head = params.get("lm_head")
    if head is None:
        head = params["embed"].T
    return _logits(params["final_norm"], head, x, cfg=cfg)


# ----------------------------------------------------------------------------
# decode: batched step over block tables
# ----------------------------------------------------------------------------


def decode_table_pages(store: PagedKVStore,
                       lens: Sequence[int]) -> Tuple[int, int]:
    """:func:`~repro.kernels.paged_attention.table_pages` of a
    :func:`paged_decode_step` over requests holding ``lens`` tokens, each
    of which attends its fed token too."""
    from repro.kernels.paged_attention import table_pages

    return table_pages([n + 1 for n in lens], store.page)


def paged_decode_step(
    params,
    cfg: ArchConfig,
    store: PagedKVStore,
    blocks: Sequence[Sequence[int]],     # per-request page lists (shared first)
    lens: Sequence[int],                 # tokens already stored per request
    last_tokens: Sequence[int],          # token fed this step, per request
    *,
    impl: str,
) -> jnp.ndarray:
    """One batched decode step for a ragged batch of requests.

    For each request the fed token's K/V is appended at page slot
    ``lens[b]`` (ONE batched scatter per layer into the shared physical
    pool, not a per-request loop), then every layer's attention gathers
    through the padded block table -- prefix-shared pages are read in
    place, whichever engine wrote them.  Returns the ``(B, vocab_padded)``
    logits of the new position.
    """
    page = store.page
    lens_np = np.asarray(lens, np.int64)
    blk = [blocks[b][int(p) // page] for b, p in enumerate(lens_np)]
    slot = [int(p) % page for p in lens_np]

    def write_layer(li, k_b, v_b):                           # (B, Hkv, hd)
        store.append_tokens(blk, slot, k_b, v_b, layer=li)

    return _paged_forward(params, cfg, store, blocks, lens, last_tokens,
                          impl=impl, write_layer=write_layer)


# ----------------------------------------------------------------------------
# chunked prefill: q block x page gather (the async prefill pipeline's unit)
# ----------------------------------------------------------------------------


def prefill_chunk_step(
    params,
    cfg: ArchConfig,
    store: PagedKVStore,
    blocks: Sequence[int],               # the ONE request's page list
    tokens: Sequence[int],               # the chunk's prompt tokens
    start: int,                          # sequence position of tokens[0]
    *,
    impl: str,
) -> jnp.ndarray:
    """One chunked-prefill forward: the chunk's positions become batch ROWS
    over one shared block table (the ROADMAP's "q block x page gather").

    Row i carries prompt position ``start + i``; its K/V is written into
    the physical pages (one :meth:`PagedKVStore.write_prefill` slice per
    layer, ``start=`` addressed) before any row gathers, and the kernel's
    per-row length mask (``att_len = start + i + 1``) keeps attention
    causal within the chunk while earlier chunks -- and prefix-shared pages
    -- are gathered in place.  Returns the ``(chunk, vocab_padded)`` logits
    (the last row is the next-token distribution after the chunk).
    """
    c = len(tokens)
    rows = [list(blocks)] * c
    lens = list(range(start, start + c))

    def write_layer(li, k_c, v_c):                            # (c, Hkv, hd)
        store.write_prefill(blocks, k_c, v_c, start=start, layer=li)

    return _paged_forward(params, cfg, store, rows, lens, tokens,
                          impl=impl, write_layer=write_layer)


def prefill_kv_chunked(
    params,
    cfg: ArchConfig,
    store: PagedKVStore,
    blocks: Sequence[int],
    prompt: Sequence[int],
    chunk: int,
    *,
    start: int = 0,
    impl: str,
):
    """Chunked paged prefill of ``prompt[start:]``: a generator issuing one
    batched forward per ``chunk`` tokens and yielding ``(end, logits)``
    after each, where ``end`` is the number of prompt tokens whose K/V now
    physically sits in the pages.

    The caller runs its safepoint (``pool.safepoint``) between iterations,
    which is the whole point of chunking: a reclaimer ping that lands
    mid-prefill is serviced at the next chunk boundary, so the publish-on-
    ping delivery window is bounded by ``chunk`` tokens of forward work
    instead of the entire prompt.  ``start`` resumes a partial prefill (a
    prefix-cache hit, or a request handed between prefill workers); the
    generator can be abandoned mid-prompt and re-entered later with
    ``start=`` wherever it left off.
    """
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")
    pos = start
    n = len(prompt)
    while pos < n:
        toks = list(prompt[pos:pos + chunk])
        logits = prefill_chunk_step(params, cfg, store, blocks, toks, pos,
                                    impl=impl)
        pos += len(toks)
        yield pos, logits
