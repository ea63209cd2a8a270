"""Device-paged KV store: the physical half of the POP-managed block pool.

:class:`~repro.runtime.block_pool.BlockPool` owns block *identity* --
allocation, ownership, reader sessions, and (through the pluggable
:class:`~repro.runtime.reclaim.ReclaimPolicy`) the decision of when a
retired block may be recycled.  :class:`PagedKVStore` owns the block
*contents*: one physical K page and one V page per (layer, block id), laid
out exactly as ``kernels/paged_attention.py`` consumes them --
``(num_blocks, page, Hkv * hd)`` per layer, token-major so a page is one
contiguous tile whose lanes stay dense at a head dim that is not a
multiple of 128 -- so a decode step gathers shared prefix pages physically
through the block table instead of replaying a per-request cache.

Lifecycle of a physical page (mirrors the paper's retire/ping/free cycle;
see docs/ARCHITECTURE.md):

    allocate ── pool hands the block id to an engine; the store clears the
                poison mark (``on_alloc`` listener) so the fresh owner may
                write
    write    ── the paged forward writes each step's tokens inside its own
                program (``write_in``); ``write_prefill`` and
                ``append_tokens`` write host- or device-sourced K/V
                directly; shared-prefix pages are written ONCE by whichever
                engine prefilled them
    share    ── the block id enters the pool's prefix cache; readers gather
                the same physical page through their block tables, no copy
    retire   ── last reference drops; the block sits on the retired list
                while the SMR policy proves no reader session spans it
    poison   ── the policy frees the block (``on_free`` listener): the store
                marks the id and overwrites the page with a huge finite
                sentinel (``POISON``; deliberately not NaN -- see
                :meth:`PagedKVStore.on_free`), so any freed-then-read
                gather trips a hard
                :class:`~repro.core.sim.engine.UseAfterFree` -- the same
                deterministic tripwire the simulated backends give the
                schemes
    recycle  ── the pool re-allocates the id; ``on_alloc`` un-poisons and
                the new owner's writes take the page over

The pages are per-layer jax arrays resident on the device and updated IN
PLACE: writes are jitted ``.at[].set`` scatters (:func:`_set_rows`) with
**buffer donation** (XLA aliases the input pool buffer into the output, so
no per-write pool copy -- verified in the tests via
``unsafe_buffer_pointer`` stability), poison-on-free and zero-on-alloc are
donated device fills at the pool-listener choke points, and
``layer_pages`` hands the RESIDENT arrays straight to the kernel.
``bytes_h2d`` counts the KV bytes host-sourced writes upload (O(tokens
written)); a steady-state decode step uploads none, since the K/V it writes
are computed on the device.  Index vectors and fed token ids are O(batch)
scalars and deliberately not counted: the metric is KV *payload* traffic.
On CPU the "device" is the CPU backend -- the same code path compiles on
TPU, which is what lets the CI interpret lane and real HBM residency share
this one lifecycle implementation.
"""

from __future__ import annotations

import functools
import threading
from typing import List, Sequence, Tuple

import numpy as np

from repro.core.sim.engine import UseAfterFree

__all__ = ["PagedKVStore", "kv_layer_order"]


def kv_layer_order(cfg) -> List[Tuple[int, int, int]]:
    """Global layer enumeration ``[(group, pattern_pos, repeat), ...]`` in
    execution order -- the single source of truth both the prefill cache
    extraction and the paged decode loop index physical layers by."""
    order: List[Tuple[int, int, int]] = []
    for gi, g in enumerate(cfg.groups):
        for rep in range(g.repeats):
            for pi in range(len(g.pattern)):
                order.append((gi, pi, rep))
    return order


def _set_rows(pages, blk, slot, vals):
    """The jnp page write: vals (T, Hkv, hd) -> pages[blk[t], slot[t]]
    (T, Hkv * hd)."""
    return pages.at[blk, slot].set(vals.reshape(vals.shape[0], -1))


@functools.cache
def _device_fns():
    """Jitted in-place page updaters, built lazily so importing this module
    never drags jax in.  ``donate_argnums=0`` is the load-bearing bit: XLA
    aliases the incoming pool buffer into the output, so a scatter/fill is
    a true in-place update of the resident pages, not an O(pool) copy."""
    import jax

    scatter = jax.jit(_set_rows, donate_argnums=(0,))

    @functools.partial(jax.jit, donate_argnums=(0,))
    def fill(pages, blk, value):
        return pages.at[blk].set(value)

    return scatter, fill


class PagedKVStore:
    """Physical page arrays for K and V, keyed by BlockPool block ids: one
    resident jax array per layer for each, updated in place by donated
    jitted writes.

    Thread-safe for the serving runtime's access pattern: every block is
    written by exactly one engine (its owner) while it is live, and the
    poison/unpoison transitions are serialized by the pool's free-list lock
    (the listeners fire inside pool operations).  A small internal lock
    guards the poison set itself so ``assert_alive`` can be called from any
    reader without racing a concurrent free.

    Donation invalidates the OLD buffer object, so a read-modify-write race
    between two writers -- or a reader that fetched a layer just before a
    writer donated it -- would raise "array deleted".  :meth:`write_guard`
    (an RLock, also taken by every write and fill) is the store's contract:
    the paged forward holds it across its whole write -> fetch ->
    kernel-dispatch sequence, which is exactly the span in which a stale
    reference could exist.

    Given a ``metrics`` registry, the store counts once, at construction,
    the bytes its page arrays take on the device (``kv_pool_device_bytes``,
    padding included) and the bytes of their elements
    (``kv_pool_element_bytes``).
    """

    #: freed-page fill value (finite on purpose; see :meth:`on_free`)
    POISON = 1e9

    def __init__(self, cfg, num_blocks: int, page_size: int, dtype=None,
                 metrics=None):
        import jax.numpy as jnp

        self.cfg = cfg
        self.num_blocks = num_blocks
        self.page = page_size
        self.layer_order = kv_layer_order(cfg)
        L = len(self.layer_order)
        Hkv, hd = cfg.n_kv_heads, cfg.head_dim_
        # pages live in the MODEL dtype (ml_dtypes makes bfloat16 a numpy
        # dtype once jax is imported), so the store holds exactly the values
        # the model computes -- the reference parity contract holds for bf16
        # configs, not just f32
        dtype = np.dtype(cfg.dtype if dtype is None else dtype)
        self.dtype = dtype
        self._k = [jnp.zeros((num_blocks, page_size, Hkv * hd), dtype)
                   for _ in range(L)]
        self._v = [jnp.zeros((num_blocks, page_size, Hkv * hd), dtype)
                   for _ in range(L)]
        self._pages_lock = threading.RLock()
        self._lock = threading.Lock()
        self._poisoned: set = set()
        # observability: the benchmark's bytes-moved axes read these
        self.bytes_written = 0          # KV bytes physically written
        self.bytes_h2d = 0              # KV bytes uploaded from the host
        self.poisons = 0                # pages poisoned (freed under the store)
        self.token_bytes = int(2 * L * Hkv * hd * dtype.itemsize)
        # what the pool takes on the device, padding included, and the K
        # and V bytes one page of one layer moves when the kernel fetches it
        self.device_bytes = sum(int(a.on_device_size_in_bytes())
                                for a in (*self._k, *self._v))
        self.page_fetch_bytes = self.device_bytes // (L * num_blocks)
        if metrics is not None:
            metrics.counter("kv_pool_device_bytes").inc(self.device_bytes)
            metrics.counter("kv_pool_element_bytes").inc(self.nbytes)

    # ------------------------------------------------------------------
    # pool listener hooks (wired via BlockPool.add_block_listener)
    # ------------------------------------------------------------------

    def on_alloc(self, blocks: Sequence[int]) -> None:
        """A block id left the free list: its previous life is over, the new
        owner may write.  Clearing the mark here (not at write time) keeps
        ``assert_alive`` honest for tail pages that are allocated to a
        request but not yet written; zeroing the page (a donated device
        fill) keeps not-yet-written slots inert under the kernel's masking
        (0 * masked-weight = 0, whereas leftover poison would still be
        gathered by the DMA)."""
        with self._lock:
            self._poisoned.difference_update(blocks)
            self._fill(blocks, 0.0)

    def on_free(self, blocks: Sequence[int]) -> None:
        """The reclaim policy proved the block safe to recycle -- or, under
        :class:`~repro.runtime.reclaim.UnsafeEagerPolicy`, decided to free
        it out from under live readers.  Either way the physical page is
        dead: poison it so a stale gather is a hard error -- and, should a
        checker be bypassed, the page contents themselves are overwritten
        (a donated device fill) with a huge finite sentinel (not NaN: dead
        table entries redirect their DMA to page 0, and a NaN there would
        leak through the kernel's masked lanes as 0 * NaN) so silently-read
        junk shows up as blown-out logits instead of plausibly stale K/V."""
        with self._lock:
            self._poisoned.update(blocks)
            self._fill(blocks, self.POISON)
            self.poisons += len(blocks)

    def _fill(self, blocks, value: float) -> None:
        import jax.numpy as jnp

        bl = list(blocks)
        if not bl:
            return
        _, fill = _device_fns()
        bidx = jnp.asarray(np.asarray(bl, np.int32))
        with self._pages_lock:
            for li in range(len(self._k)):
                self._k[li] = fill(self._k[li], bidx, value)
                self._v[li] = fill(self._v[li], bidx, value)

    # ------------------------------------------------------------------
    # writes (owner-engine only)
    # ------------------------------------------------------------------

    def _scatter(self, layers, blk, slot, k, v) -> None:
        """Write ``k``/``v`` rows into ``layers``' pages at (``blk``,
        ``slot``); ``k[i]`` goes to ``layers[i]``.  Host-sourced values
        pay the upload, O(tokens written); device-computed K/V is free."""
        import jax.numpy as jnp

        if isinstance(k, np.ndarray):
            self.bytes_h2d += int(k.nbytes) + int(v.nbytes)
        scatter, _ = _device_fns()
        bidx = jnp.asarray(np.asarray(blk, np.int32))
        sidx = jnp.asarray(np.asarray(slot, np.int32))
        with self._pages_lock:
            for i, li in enumerate(layers):
                self._k[li] = scatter(self._k[li], bidx, sidx,
                                      jnp.asarray(k[i], self.dtype))
                self._v[li] = scatter(self._v[li], bidx, sidx,
                                      jnp.asarray(v[i], self.dtype))

    def _token_coords(self, blocks: Sequence[int], start: int, T: int):
        """(block id, slot) per token for T consecutive positions from
        ``start``, through the request's page list."""
        pos = np.arange(start, start + T)
        blk = np.asarray(blocks, np.int64)[pos // self.page]
        return blk, pos % self.page

    def write_prefill(self, blocks: Sequence[int], k, v,
                      start: int = 0) -> int:
        """Write a prefilled token range into ``blocks``.

        ``k``/``v``: ``(L, T, Hkv, hd)`` -- the per-layer post-rope K/V of T
        consecutive tokens starting at sequence position ``start``, in
        :func:`kv_layer_order`.  ``blocks`` is the request's page list from
        position 0, so token ``start + i`` lands in ``blocks[(start + i) //
        page]`` slot ``(start + i) % page``.  Returns the number of bytes
        written.  Accepts numpy or jax arrays; device-resident inputs write
        with zero host traffic.
        """
        T = k.shape[1]
        blk, slot = self._token_coords(blocks, start, T)
        self._scatter(range(len(self._k)), blk, slot, k, v)
        written = int(T * self.token_bytes)
        self.bytes_written += written
        return written

    def append_tokens(self, blocks: Sequence[int], slots: Sequence[int],
                      k, v, layer: int) -> int:
        """Batched append: token b of the batch lands in ``blocks[b]`` slot
        ``slots[b]`` of ``layer``.  ``k``/``v`` are ``(B, Hkv, hd)`` -- ONE
        write for the whole ragged batch."""
        self._scatter([layer], blocks, slots, k[None], v[None])
        written = int(len(blocks) * self.token_bytes) // len(self._k)
        self.bytes_written += written
        return written

    def write_in(self, layer: int, tokens: int, program):
        """Hand ``layer``'s K and V page arrays to ``program``, one jitted
        program that donates them, writes ``tokens`` tokens into them (with
        :func:`_set_rows`) and returns them: ``program(k_pages, v_pages) ->
        (k_pages, v_pages, out)``.  The store keeps the returned arrays and
        this returns ``out``."""
        with self._pages_lock:
            self._k[layer], self._v[layer], out = program(self._k[layer],
                                                          self._v[layer])
        self.bytes_written += tokens * self.token_bytes // len(self._k)
        return out

    def write_guard(self):
        """The RLock the paged forward holds across its whole write -> fetch
        -> kernel-dispatch sequence, which makes in-place buffer donation
        safe against a concurrent writer invalidating the fetched page
        arrays (see the class docstring)."""
        return self._pages_lock

    def sync(self) -> None:
        """Block until every pending device write has landed -- the
        benchmarks' timing fence."""
        with self._pages_lock:
            for a in (*self._k, *self._v):
                a.block_until_ready()

    # ------------------------------------------------------------------
    # reads (any engine holding a reservation)
    # ------------------------------------------------------------------

    def assert_alive(self, engine: int, blocks: Sequence[int]) -> None:
        """The physical-page use-after-free tripwire: raise if any block a
        reader is about to gather was freed (poisoned) under it.  Mirrors
        the simulated allocator's FREED-state check, at page granularity.
        One set intersection under the lock -- this sits on every gather in
        the batch hot path, so it must not loop in Python per block."""
        with self._lock:
            bad = self._poisoned.intersection(blocks)
        if bad:
            raise UseAfterFree(engine, min(bad), "kv-gather")

    def layer_pages(self, layer: int):
        """The (num_blocks, page, Hkv * hd) K and V page arrays of one
        layer: the resident arrays themselves, ready for the kernel -- zero
        bytes moved."""
        with self._pages_lock:
            return self._k[layer], self._v[layer]

    # whole-pool views (tests/debugging): the per-layer arrays stacked, so
    # treat them as a snapshot, not a handle

    @property
    def k(self):
        import jax.numpy as jnp

        with self._pages_lock:
            return jnp.stack(self._k)

    @property
    def v(self):
        import jax.numpy as jnp

        with self._pages_lock:
            return jnp.stack(self._v)

    # ------------------------------------------------------------------
    # observability
    # ------------------------------------------------------------------

    def is_poisoned(self, block: int) -> bool:
        with self._lock:
            return block in self._poisoned

    @property
    def nbytes(self) -> int:
        """Total physical pool footprint (constant -- the paged path's peak
        KV memory regardless of request count)."""
        return sum(int(a.nbytes) for a in (*self._k, *self._v))
