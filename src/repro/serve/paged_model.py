"""Paged forward: the model math of a decode step or prefill chunk driven
through the physically paged KV store and the Pallas paged-attention
kernel -- the one way the serving runtime runs a model.

Attention reads K/V straight out of the
:class:`~repro.runtime.kv_store.PagedKVStore`'s device-resident pages
through a per-request block table (``kernels/paged_attention.py``), and the
only per-token write is a single page-slot write.  The plain full-sequence
forward, :func:`repro.models.model.apply_model`, is the reference the tests
and the benchmark hold this path to.

Scope: the GQA transformer family -- every layer ``mixer="attn"`` with
``attn_kind="full"`` and a dense MLP (qk_norm / post_norms / softcaps /
partial rotary / parallel residual all honored).  MLA, sliding-window,
SSM/RWKV mixers, MoE, cross-attention, weight-tied shared attention,
encoders and MTP heads are not served: :func:`check_paged_support` rejects
them up front so the failure mode is a clear error at engine construction,
not silent wrong math.

The layer loop runs at host level, two launches a layer: the kernel call
(``kops.paged_attention``, compiled Pallas on a TPU and interpret mode
elsewhere, as each entry point's required ``impl`` names; there is no
default), and one jitted program for everything between two kernel calls
-- layer i's output projection and MLP, then layer i+1's projections and
its K/V page write (``_mlp_qkv``; ``_embed_qkv`` before the first layer,
``_mlp_logits`` after the last).  The step's table, lengths, positions,
tokens and write indices go to the device in one ``jax.device_put``.  A
layer's page arrays are donated into the program that writes them
(:meth:`PagedKVStore.write_in`) -- an in-place write with ZERO host
traffic -- and ``layer_pages`` hands the kernel the store's resident
arrays.  The whole forward runs under :meth:`PagedKVStore.write_guard`, so
a concurrent writer's buffer donation can never invalidate the pages
mid-step.
"""

from __future__ import annotations

import functools
import math
import threading
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import layers as L
from repro.models.layers import apply_rope, rms_norm
from repro.runtime.kv_store import PagedKVStore, _set_rows

__all__ = ["check_paged_support", "prefill_kv_chunked",
           "prefill_chunk_step", "paged_decode_step", "kv_fetch_bytes",
           "forward_counts"]


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
            "config not supported by the paged KV path, the only "
            "serving path: " + "; ".join(problems))


# ----------------------------------------------------------------------------
# shared forward: decode steps and prefill chunks are the same math
# ----------------------------------------------------------------------------


# The layer math, jitted per part.  ``gp`` is a group's stacked weights
# and ``rep`` a traced layer index, so one program serves every layer and
# the layer's weights are sliced inside it: an eager ``a[rep]`` would copy
# each layer's weights per call, and a host loop running ahead of the
# device would hold many such copies at once.  The forward launches them
# fused (``_embed_qkv``, ``_mlp_qkv``, ``_mlp_logits``, below).


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


def _project(qkv, gp, rep, x, positions, pages, blk, slot, *, cfg, rows):
    """Layer ``rep``'s q, and its K/V written into ``pages``: ``(q,
    k_pages, v_pages)``."""
    q, k, v = qkv(gp, rep, x, positions, cfg=cfg, rows=rows)
    kp, vp = pages
    return q, _set_rows(kp, blk, slot, k), _set_rows(vp, blk, slot, v)


# The programs of a forward.  The layer math comes in as static callables,
# read from this module when the forward runs: a replaced ``_qkv``,
# ``_out_mlp`` or ``_logits`` is a new static value, so the programs trace
# it anew.  ``pages`` (a layer's K and V arrays) are donated: XLA aliases
# them into the outputs, so the write is in place.


@functools.partial(jax.jit, donate_argnames="pages",
                   static_argnames=("cfg", "rows", "qkv"))
def _embed_qkv(embed, toks, gp, rep, positions, pages, blk, slot, *,
               cfg: ArchConfig, rows: int, qkv):
    """Before the first kernel call: embed the fed tokens, then layer
    ``rep``'s projections and page write."""
    x = _embed(embed, toks, cfg=cfg)
    return (x,) + _project(qkv, gp, rep, x, positions, pages, blk, slot,
                           cfg=cfg, rows=rows)


@functools.partial(jax.jit, donate_argnames="pages",
                   static_argnames=("cfg", "rows", "parallel", "out_mlp",
                                    "qkv"))
def _mlp_qkv(gp, rep, x, attn, next_gp, next_rep, positions, pages, blk,
             slot, *, cfg: ArchConfig, rows: int, parallel: bool, out_mlp,
             qkv):
    """Between two kernel calls: layer ``rep``'s output projection and MLP,
    then layer ``next_rep``'s projections and page write."""
    x = out_mlp(gp, rep, x, attn, cfg=cfg, parallel=parallel)
    return (x,) + _project(qkv, next_gp, next_rep, x, positions, pages, blk,
                           slot, cfg=cfg, rows=rows)


@functools.partial(jax.jit,
                   static_argnames=("cfg", "parallel", "out_mlp", "logits"))
def _mlp_logits(gp, rep, x, attn, final_norm, head, embed, *,
                cfg: ArchConfig, parallel: bool, out_mlp, logits):
    """After the last kernel call: its layer's output projection and MLP,
    then the logits, off ``head`` or, tied (``head`` None), ``embed``."""
    x = out_mlp(gp, rep, x, attn, cfg=cfg, parallel=parallel)
    return logits(final_norm, embed.T if head is None else head, x, cfg=cfg)


@functools.cache
def _device_ints(n: int):
    """Device scalars 0..n-1, the layer indices the programs take: put on
    the device once, where a Python int would be uploaded on every call.
    Concrete even when first asked for inside a trace, so the cache never
    keeps a tracer."""
    with jax.ensure_compile_time_eval():
        return tuple(jax.device_put(np.int32(i)) for i in range(n))


_counts = threading.local()


def forward_counts() -> dict:
    """``{"launches", "uploads"}`` of the calling thread's last paged
    forward: the programs it launched (kernel calls included) and the
    ``jax.device_put`` calls it made."""
    return {"launches": _counts.launches, "uploads": _counts.uploads}


def _paged_forward(params, cfg: ArchConfig, store: PagedKVStore,
                   blocks, lens, tokens, *, impl: str) -> jnp.ndarray:
    """The transformer loop both paged entry points share: embed the fed
    tokens (one per row), and per layer project -> rope -> write the new
    K/V into the physical pages -> gather through the padded block table
    with the paged-attention kernel.

    ``blocks``/``lens``/``tokens`` are per-ROW: a decode step has one row
    per request (each its own block list); a prefill chunk has one row per
    chunk position, all rows sharing ONE block list with consecutive
    positions.  Row r's token lands at position ``lens[r]``: page
    ``blocks[r][lens[r] // page]``, slot ``lens[r] % page``.  Causality is
    the kernel's length masking: every row's K/V is in the pages before any
    row gathers, and row r attends only to positions < lens[r] + 1.
    """
    from repro.kernels import ops as kops

    page = store.page
    # the table and lengths (and q, in _qkv) at the attention call's bucket
    # of shapes
    rows, table, att = _call_inputs(store, cfg, blocks, lens)
    pos = np.asarray(lens, np.int32)
    blk = np.asarray([bl[p // page] for bl, p in zip(blocks, pos)],
                     np.int32)
    slot = pos % page
    table, att, positions, toks, blk_d, slot_d = jax.device_put(
        (table, att, pos[:, None], np.asarray(tokens, np.int32), blk, slot))
    order = store.layer_order
    reps = _device_ints(1 + max(rep for _, _, rep in order))
    scale = (1.0 / math.sqrt(cfg.attn_scale) if cfg.attn_scale
             else 1.0 / math.sqrt(cfg.head_dim_))
    # the layer math as it stands in this module now
    qkv, out_mlp, logits = _qkv, _out_mlp, _logits

    def layer_in(li, program, *args, **static):
        """Launch ``program`` with layer ``li``'s pages donated into it; it
        writes the layer's K/V there: ``(x, q)``."""
        def run(kp, vp):
            x, q, kp, vp = program(*args, (kp, vp), blk_d, slot_d, **static)
            return kp, vp, (x, q)
        return store.write_in(li, len(pos), run)

    def weights(li):
        gi, pi, rep = order[li]
        return (params["groups"][f"g{gi}"][f"p{pi}"], reps[rep],
                cfg.groups[gi].pattern[pi].parallel)

    with store.write_guard():
        gp, rep, parallel = weights(0)
        x, q = layer_in(0, _embed_qkv, params["embed"], toks, gp, rep,
                        positions, cfg=cfg, rows=rows, qkv=qkv)
        launches = 1
        for li in range(len(order)):
            k_pages, v_pages = store.layer_pages(li)
            out = kops.paged_attention(
                q, k_pages, v_pages, table, att,             # q (rows, H, hd)
                softcap=cfg.attn_softcap, scale=scale, impl=impl)
            if li + 1 < len(order):
                ngp, nrep, nparallel = weights(li + 1)
                x, q = layer_in(li + 1, _mlp_qkv, gp, rep, x, out, ngp, nrep,
                                positions, cfg=cfg, rows=rows,
                                parallel=parallel, out_mlp=out_mlp, qkv=qkv)
                gp, rep, parallel = ngp, nrep, nparallel
            else:
                result = _mlp_logits(gp, rep, x, out, params["final_norm"],
                                     params.get("lm_head"), params["embed"],
                                     cfg=cfg, parallel=parallel,
                                     out_mlp=out_mlp, logits=logits)
            launches += 2
    _counts.launches = launches
    _counts.uploads = 1
    return result


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
    ``lens[b]`` (ONE batched write per layer into the shared physical
    pool, not a per-request loop), then every layer's attention gathers
    through the padded block table -- prefix-shared pages are read in
    place, whichever engine wrote them.  Returns the ``(B, vocab_padded)``
    logits of the new position.
    """
    return _paged_forward(params, cfg, store, blocks, lens, last_tokens,
                          impl=impl)


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
    the physical pages (one batched write per layer, as a decode step's)
    before any row gathers, and the kernel's
    per-row length mask (``att_len = start + i + 1``) keeps attention
    causal within the chunk while earlier chunks -- and prefix-shared pages
    -- are gathered in place.  Returns the ``(chunk, vocab_padded)`` logits
    (the last row is the next-token distribution after the chunk).
    """
    c = len(tokens)
    rows = [list(blocks)] * c
    lens = list(range(start, start + c))
    return _paged_forward(params, cfg, store, rows, lens, tokens, impl=impl)


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
