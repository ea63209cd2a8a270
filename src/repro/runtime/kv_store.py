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
through the block table instead of replaying a per-request dense cache.

Lifecycle of a physical page (mirrors the paper's retire/ping/free cycle;
see docs/ARCHITECTURE.md):

    allocate ── pool hands the block id to an engine; the store clears the
                poison mark (``on_alloc`` listener) so the fresh owner may
                write
    write    ── prefill (``write_prefill``) or per-token decode append
                (``append_token``/``append_tokens``) fill slots; shared-
                prefix pages are written ONCE by whichever engine prefilled
                them
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

The lifecycle above is storage-agnostic; WHERE the pages physically live is
the ``storage`` seam:

* ``storage="host"`` -- numpy arrays written in place.  Cheap to write, but
  the *read* path must re-materialize each layer's page array for the
  kernel every decode step: O(entire pool) host->device traffic per layer
  per step, which on real hardware dwarfs every SMR cost this repo
  measures.  Kept as the reference implementation and for CPU-light unit
  tests.
* ``storage="device"`` -- per-layer jax device arrays updated IN PLACE:
  token writes are jitted ``.at[].set`` scatters with **buffer donation**
  (XLA aliases the input pool buffer into the output, so no per-write pool
  copy -- verified in the tests via ``unsafe_buffer_pointer`` stability),
  or optionally a Pallas scatter kernel
  (:func:`repro.kernels.paged_attention.paged_scatter_pallas`) sharing the
  paged-attention kernel's block layout.  The paged forward writes a
  step's tokens inside its own program instead (``write_in``: the layer's
  arrays donated into that program and taken back).  ``layer_pages``
  hands the RESIDENT arrays straight to the kernel -- zero host->device
  bytes per step -- and poison-on-free / zero-on-alloc become device fills at the
  same pool-listener choke points, so the UseAfterFree tripwire semantics
  are identical on both storages.

Both storages meter data movement: ``bytes_h2d`` counts host->device KV
bytes (host storage pays O(pool * layers) per decode step at read time;
device storage pays only for host-sourced writes such as the dense prefill
extraction -- O(tokens written) -- and 0 during steady-state decode, where
the K/V being scattered are already device-resident), ``bytes_d2h`` the
reverse direction (host storage pays it per write, device storage never).
Index vectors and fed token ids are O(batch) scalars and deliberately not
counted: the metric is KV *payload* traffic.  On CPU the "device" is the
CPU backend -- the arrays are jax buffers and the same code path compiles
on TPU, which is what lets the CI interpret lane and real HBM residency
share this one lifecycle implementation.
"""

from __future__ import annotations

import contextlib
import functools
import threading
from typing import List, Optional, Sequence, Tuple

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


# ----------------------------------------------------------------------------
# physical storage backends (the storage="host"|"device" seam)
# ----------------------------------------------------------------------------


class _HostPages:
    """Numpy page arrays: writes are host slice-assignments, reads upload
    the whole layer to the device every call (the O(pool) tax the device
    storage removes)."""

    kind = "host"

    def __init__(self, L, num_blocks, page, Hkv, hd, dtype):
        self.k = np.zeros((L, num_blocks, page, Hkv * hd), dtype)
        self.v = np.zeros_like(self.k)
        self.bytes_h2d = 0
        self.bytes_d2h = 0

    def guard(self):
        # host writes are plain numpy stores to disjoint slots; the racing
        # serving threads never overlap blocks, so no lock is needed
        return contextlib.nullcontext()

    def scatter(self, layer, blk, slot, k, v) -> None:
        # device-computed K/V must come down to the host first (this is the
        # d2h half of the host storage's per-token round trip)
        if not isinstance(k, np.ndarray):
            self.bytes_d2h += int(k.nbytes) + int(v.nbytes)
        k, v = np.asarray(k), np.asarray(v)
        blk = np.asarray(blk, np.int64)
        slot = np.asarray(slot, np.int64)
        # (.., T, Hkv, hd) values -> one (Hkv * hd) page row per token
        if layer is None:
            self.k[:, blk, slot] = k.reshape(k.shape[:2] + (-1,))
            self.v[:, blk, slot] = v.reshape(v.shape[:2] + (-1,))
        else:
            self.k[layer][blk, slot] = k.reshape(len(k), -1)
            self.v[layer][blk, slot] = v.reshape(len(v), -1)

    def fill(self, blocks, value: float) -> None:
        bl = list(blocks)
        self.k[:, bl] = value
        self.v[:, bl] = value

    def layer(self, li):
        import jax.numpy as jnp

        # the host storage's read tax: one full-layer upload per call
        self.bytes_h2d += int(self.k[li].nbytes) + int(self.v[li].nbytes)
        return jnp.asarray(self.k[li]), jnp.asarray(self.v[li])

    def stacked(self):
        return self.k, self.v

    def sync(self) -> None:
        pass

    @property
    def nbytes(self) -> int:
        return self.k.nbytes + self.v.nbytes

    @property
    def device_bytes(self) -> int:
        return self.nbytes


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


@functools.cache
def _pallas_write():
    """The Pallas page write, in this process's kernel mode: one object, so
    a program that takes it as a static argument compiles once."""
    from repro.kernels.paged_attention import paged_impl, paged_scatter_pallas

    return functools.partial(paged_scatter_pallas,
                             interpret=paged_impl() == "interpret")


@functools.cache
def _pallas_scatter_fn():
    import jax

    return jax.jit(_pallas_write(), donate_argnums=(0,))


class _DevicePages:
    """Device-resident page arrays: one jax array per layer, updated in
    place by donated jitted scatters (or the Pallas scatter kernel).  Reads
    hand back the resident arrays -- zero transfer.

    Concurrency: donation invalidates the OLD buffer object, so a
    read-modify-write race between two writers -- or a reader that fetched
    a layer just before a writer donated it -- would raise "array deleted".
    ``guard()`` (an RLock, also taken by every scatter/fill/write_in) is
    the store's contract: the paged forward holds it across its whole
    write -> fetch -> kernel-dispatch sequence, which is exactly the span
    in which a stale reference could exist."""

    kind = "device"

    def __init__(self, L, num_blocks, page, Hkv, hd, dtype,
                 scatter_impl: str = "jnp"):
        import jax.numpy as jnp

        if scatter_impl not in ("jnp", "pallas"):
            raise ValueError(f"scatter_impl must be 'jnp' or 'pallas', "
                             f"got {scatter_impl!r}")
        self.L = L
        self.dtype = dtype
        self.scatter_impl = scatter_impl
        # the page write a forward's own program traces in (``write_in``)
        self.write = _set_rows if scatter_impl == "jnp" else _pallas_write()
        self.k = [jnp.zeros((num_blocks, page, Hkv * hd), dtype)
                  for _ in range(L)]
        self.v = [jnp.zeros((num_blocks, page, Hkv * hd), dtype)
                  for _ in range(L)]
        self.bytes_h2d = 0
        self.bytes_d2h = 0
        self._lock = threading.RLock()

    def guard(self):
        return self._lock

    def _scatter_fn(self):
        if self.scatter_impl == "pallas":
            return _pallas_scatter_fn()
        return _device_fns()[0]

    def scatter(self, layer, blk, slot, k, v) -> None:
        import jax.numpy as jnp

        # host-sourced values (e.g. the dense prefill extraction) pay the
        # upload -- O(tokens written), the ONLY h2d the device storage ever
        # does; device-computed K/V (the steady-state decode path) is free
        if isinstance(k, np.ndarray):
            self.bytes_h2d += int(k.nbytes) + int(v.nbytes)
        sc = self._scatter_fn()
        bidx = jnp.asarray(np.asarray(blk, np.int32))
        sidx = jnp.asarray(np.asarray(slot, np.int32))
        with self._lock:
            if layer is None:
                for li in range(self.L):
                    self.k[li] = sc(self.k[li], bidx, sidx,
                                    jnp.asarray(k[li], self.dtype))
                    self.v[li] = sc(self.v[li], bidx, sidx,
                                    jnp.asarray(v[li], self.dtype))
            else:
                self.k[layer] = sc(self.k[layer], bidx, sidx,
                                   jnp.asarray(k, self.dtype))
                self.v[layer] = sc(self.v[layer], bidx, sidx,
                                   jnp.asarray(v, self.dtype))

    def write_in(self, layer, program):
        with self._lock:
            self.k[layer], self.v[layer], out = program(
                self.write, self.k[layer], self.v[layer])
        return out

    def fill(self, blocks, value: float) -> None:
        import jax.numpy as jnp

        bl = list(blocks)
        if not bl:
            return
        _, fill = _device_fns()
        bidx = jnp.asarray(np.asarray(bl, np.int32))
        with self._lock:
            for li in range(self.L):
                self.k[li] = fill(self.k[li], bidx, value)
                self.v[li] = fill(self.v[li], bidx, value)

    def layer(self, li):
        # the whole point: the resident arrays ARE the kernel operands --
        # no jnp.asarray, no h2d, no per-step pool re-upload
        with self._lock:
            return self.k[li], self.v[li]

    def stacked(self):
        import jax.numpy as jnp

        with self._lock:
            return jnp.stack(self.k), jnp.stack(self.v)

    def sync(self) -> None:
        with self._lock:
            for a in (*self.k, *self.v):
                a.block_until_ready()

    @property
    def nbytes(self) -> int:
        return sum(int(a.nbytes) for a in self.k) + \
            sum(int(a.nbytes) for a in self.v)

    @property
    def device_bytes(self) -> int:
        # the arrays as the device lays them out, with any padding of a
        # dimension to the device's tile
        return sum(int(a.on_device_size_in_bytes()) for a in self.k) + \
            sum(int(a.on_device_size_in_bytes()) for a in self.v)


class PagedKVStore:
    """Physical page arrays for K and V, keyed by BlockPool block ids.

    Thread-safe for the serving runtime's access pattern: every block is
    written by exactly one engine (its owner) while it is live, and the
    poison/unpoison transitions are serialized by the pool's free-list lock
    (the listeners fire inside pool operations).  A small internal lock
    guards the poison set itself so ``assert_alive`` can be called from any
    reader without racing a concurrent free; device storage additionally
    serializes its in-place buffer swaps behind :meth:`write_guard`.

    ``storage`` selects the physical backend (see the module docstring):
    ``"host"`` keeps the numpy reference implementation, ``"device"`` holds
    the pages as jax device arrays updated in place with buffer donation.
    ``scatter_impl`` ("jnp" | "pallas") picks the device write primitive.

    Given a ``metrics`` registry, the store counts once, at construction,
    the bytes its page arrays take on the device (``kv_pool_device_bytes``,
    padding included) and the bytes of their elements
    (``kv_pool_element_bytes``).
    """

    #: freed-page fill value (finite on purpose; see :meth:`on_free`)
    POISON = 1e9

    def __init__(self, cfg, num_blocks: int, page_size: int, dtype=None,
                 storage: str = "host", scatter_impl: str = "jnp",
                 metrics=None):
        if storage not in ("host", "device"):
            raise ValueError(
                f"storage must be 'host' or 'device', got {storage!r}")
        self.cfg = cfg
        self.num_blocks = num_blocks
        self.page = page_size
        self.storage = storage
        self.layer_order = kv_layer_order(cfg)
        L = len(self.layer_order)
        Hkv, hd = cfg.n_kv_heads, cfg.head_dim_
        # pages live in the MODEL dtype (ml_dtypes makes bfloat16 a numpy
        # dtype once jax is imported), so the paged path stores exactly the
        # values the dense cache would -- the paged/dense parity contract
        # holds for bf16 configs, not just f32, and resident-bytes
        # comparisons are apples to apples
        dtype = np.dtype(cfg.dtype if dtype is None else dtype)
        if storage == "device":
            self._st = _DevicePages(L, num_blocks, page_size, Hkv, hd, dtype,
                                    scatter_impl=scatter_impl)
        else:
            self._st = _HostPages(L, num_blocks, page_size, Hkv, hd, dtype)
        self._lock = threading.Lock()
        self._poisoned: set = set()
        # observability: the benchmark's bytes-moved axes read these
        self.bytes_written = 0          # KV bytes physically written
        self.poisons = 0                # pages poisoned (freed under the store)
        self.token_bytes = int(2 * L * Hkv * hd * dtype.itemsize)
        # what the pool takes on the device, padding included, and the K
        # and V bytes one page of one layer moves when the kernel fetches it
        self.device_bytes = self._st.device_bytes
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
        request but not yet written; zeroing the page keeps not-yet-written
        slots inert under the kernel's masking (0 * masked-weight = 0,
        whereas leftover poison would still be gathered by the DMA).  On
        device storage the zeroing is a donated device fill -- same choke
        point, no host traffic."""
        with self._lock:
            self._poisoned.difference_update(blocks)
            self._st.fill(blocks, 0.0)

    def on_free(self, blocks: Sequence[int]) -> None:
        """The reclaim policy proved the block safe to recycle -- or, under
        :class:`~repro.runtime.reclaim.UnsafeEagerPolicy`, decided to free
        it out from under live readers.  Either way the physical page is
        dead: poison it so a stale gather is a hard error -- and, should a
        checker be bypassed, the page contents themselves are overwritten
        with a huge finite sentinel (not NaN: dead table entries redirect
        their DMA to page 0, and a NaN there would leak through the
        kernel's masked lanes as 0 * NaN) so silently-read junk shows up as
        blown-out logits instead of plausibly stale K/V.  On device storage
        the poison is a donated device fill at this same choke point."""
        with self._lock:
            self._poisoned.update(blocks)
            self._st.fill(blocks, self.POISON)
            self.poisons += len(blocks)

    # ------------------------------------------------------------------
    # writes (owner-engine only)
    # ------------------------------------------------------------------

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
        consecutive tokens starting at sequence position ``start`` (the
        prefill cache leaves, see serve/paged_model.py).  ``blocks`` is the
        request's page list from position 0, so token ``start + i`` lands in
        ``blocks[(start + i) // page]`` slot ``(start + i) % page``.
        Returns the number of bytes written.  Accepts numpy or jax arrays;
        on device storage, device-resident inputs scatter with zero host
        traffic.
        """
        T = k.shape[1]
        blk, slot = self._token_coords(blocks, start, T)
        self._st.scatter(None, blk, slot, k, v)
        written = int(T * self.token_bytes)
        self.bytes_written += written
        return written

    def append_token(self, block: int, slot: int, k, v,
                     layer: Optional[int] = None) -> int:
        """Write one decoded token's K/V into ``block`` at ``slot`` -- a
        single-slot scatter.  With ``layer=None`` the arrays are ``(L, Hkv,
        hd)`` and every layer is written; with a layer index they are
        ``(Hkv, hd)``.  Batched decode steps should prefer
        :meth:`append_tokens` (one scatter for the whole batch row-set)."""
        if layer is None:
            self._st.scatter(None, [block], [slot], k[:, None], v[:, None])
            written = 2 * int(np.prod(k.shape)) * self._itemsize
        else:
            self._st.scatter(layer, [block], [slot], k[None], v[None])
            written = 2 * int(np.prod(k.shape)) * self._itemsize
        self.bytes_written += written
        return written

    def append_tokens(self, blocks: Sequence[int], slots: Sequence[int],
                      k, v, layer: int) -> int:
        """Batched decode append: token b of the batch lands in
        ``blocks[b]`` slot ``slots[b]`` of ``layer``.  ``k``/``v`` are
        ``(B, Hkv, hd)`` -- ONE scatter for the whole ragged batch, the
        paged decode step's entire per-layer write cost."""
        self._st.scatter(layer, blocks, slots, k, v)
        written = 2 * int(np.prod(k.shape)) * self._itemsize
        self.bytes_written += written
        return written

    def write_in(self, layer: int, tokens: int, program):
        """Hand ``layer``'s K and V page arrays to ``program``, one jitted
        program that donates them, writes ``tokens`` tokens into them and
        returns them: ``program(write, k_pages, v_pages) -> (k_pages,
        v_pages, out)``, where ``write(pages, blk, slot, vals)`` is the
        storage's page write (the jnp or Pallas scatter) for the program to
        trace in.  The store keeps the returned arrays and this returns
        ``out``.  Device storage only: host pages are written on the host
        (:meth:`append_tokens`)."""
        if self.storage != "device":
            raise ValueError("write_in needs device storage")
        out = self._st.write_in(layer, program)
        self.bytes_written += tokens * self.token_bytes // len(
            self.layer_order)
        return out

    @property
    def _itemsize(self) -> int:
        L, Hkv, hd = (len(self.layer_order), self.cfg.n_kv_heads,
                      self.cfg.head_dim_)
        return self.token_bytes // (2 * L * Hkv * hd)

    def write_guard(self):
        """Context manager the paged forward holds across its whole
        write -> fetch -> kernel-dispatch sequence.  A no-op for host storage;
        for device storage it is the RLock that makes in-place buffer
        donation safe against a concurrent writer invalidating the fetched
        page arrays (see :class:`_DevicePages`)."""
        return self._st.guard()

    def sync(self) -> None:
        """Block until every pending device write has landed (no-op on
        host storage) -- the benchmarks' timing fence."""
        self._st.sync()

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
        layer, as jax arrays ready for the kernel.  Host storage uploads
        the layer (and meters it as ``bytes_h2d``); device storage returns
        the resident arrays -- zero bytes moved."""
        return self._st.layer(layer)

    # storage-agnostic whole-pool views (tests/debugging; device storage
    # stacks its per-layer arrays, so treat as a snapshot, not a handle)

    @property
    def k(self):
        return self._st.stacked()[0]

    @property
    def v(self):
        return self._st.stacked()[1]

    # ------------------------------------------------------------------
    # observability
    # ------------------------------------------------------------------

    @property
    def bytes_h2d(self) -> int:
        """Host->device KV bytes moved through the store (the benchmark's
        bytes_h2d column): host storage pays O(pool * layers) per decode
        step at gather time, device storage only for host-sourced writes
        (0 in steady-state decode)."""
        return self._st.bytes_h2d

    @property
    def bytes_d2h(self) -> int:
        """Device->host KV bytes (host storage downloads every written
        K/V; device storage never does)."""
        return self._st.bytes_d2h

    @property
    def poisoned_blocks(self) -> int:
        with self._lock:
            return len(self._poisoned)

    def is_poisoned(self, block: int) -> bool:
        with self._lock:
            return block in self._poisoned

    @property
    def nbytes(self) -> int:
        """Total physical pool footprint (constant -- the paged path's peak
        KV memory regardless of request count)."""
        return self._st.nbytes
