"""Multi-engine litmus: N real engine reader threads + a dedicated
reclaimer over ONE shared BlockPool, with cross-engine prefix sharing --
the paper's many-readers scenario at serving granularity.

Contract, at high eviction pressure with engines >= 2:

1. under EVERY registered SMR scheme (and the native EpochPOP pool) no
   touch may ever raise UseAfterFree, even while prefix-shared blocks are
   retired under open reader sessions on other engines;
2. under the deliberately unsafe free-on-retire policy the same traffic
   MUST raise UseAfterFree (the tripwires actually fire);
3. the scheduler hands out request ids race-free when clients submit from
   many threads (the `self._rid += 1` fix).
"""

import random
import threading

import pytest

from repro.configs.base import ArchConfig, dense_stack
from repro.core.sim.engine import UseAfterFree
from repro.runtime.block_pool import BlockPool, OutOfBlocks
from repro.runtime.reclaim import (SimulatedSMRPolicy, UnsafeEagerPolicy,
                                   make_policy, supported_schemes)
from repro.serve.engine import ServeEngine
from repro.serve.worker import Reclaimer

SAFE_SCHEMES = supported_schemes()

TINY = ArchConfig(
    name="tiny-sched", d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
    vocab=128, groups=dense_stack(2), remat="none", dtype="float32")


def churn_engines(pool: BlockPool, n_engines: int, *, steps: int = 40,
                  per_req: int = 2, window: int = 3, n_keys: int = 2,
                  reclaimer: bool = True):
    """Worker-protocol churn on real threads: allocate-or-acquire a shared
    prefix, batched reserve + touch of the whole working set, retire/release
    the oldest request.  Returns (uaf_count, other_errors)."""
    uaf = [0]
    errors = []
    rec = (Reclaimer(pool, engine_id=n_engines, interval_s=0.001)
           if reclaimer else None)

    def engine(eid: int):
        rng = random.Random(eid)
        live = []
        try:
            for _ in range(steps):
                pool.start_step(eid)
                shared, extra = [], []
                key = ("px", rng.randrange(n_keys))
                hit = pool.acquire_prefix(eid, key)
                if hit is not None:
                    shared = hit[0]
                else:
                    try:
                        pfx = pool.allocate(eid, 1)
                    except OutOfBlocks:
                        pool.reclaim(eid)
                        pool.end_step(eid)
                        continue
                    if pool.share_prefix(eid, key, pfx):
                        shared = pfx
                    else:
                        extra = pfx
                try:
                    priv = pool.allocate(eid, per_req)
                except OutOfBlocks:
                    if shared:
                        pool.release_shared(eid, shared)
                    if extra:
                        pool.retire(eid, extra)
                    pool.evict_prefixes(eid)
                    pool.reclaim(eid)
                    pool.end_step(eid)
                    continue
                live.append((shared, extra + priv))
                session = [b for sh, pv in live for b in sh + pv]
                # traversal: additionally reserve a hot prefix's blocks
                # WITHOUT taking request refs (a reader walking another
                # request's shared pages), re-validating after the reserve
                # like a hazard-pointer reader re-reads the pointer -- here
                # SMR, not refcounting, is what keeps the touch safe
                pk = ("px", rng.randrange(n_keys))
                entry = pool._prefix_cache.get(pk)
                if entry is not None:
                    pool.reserve(eid, entry[0])
                    if pool._prefix_cache.get(pk) is entry:
                        session = session + entry[0]
                pool.reserve(eid, session)
                pool.touch(eid, session)
                if len(live) > window:
                    sh, pv = live.pop(0)
                    pool.retire(eid, pv)
                    if sh:
                        pool.release_shared(eid, sh)
                pool.end_step(eid)
        except UseAfterFree as e:
            uaf[0] += 1
            errors.append(("uaf", str(e)))
        except Exception as e:  # noqa: BLE001
            errors.append(("err", f"{type(e).__name__}: {e}"))
        finally:
            for sh, pv in live:
                try:
                    pool.retire(eid, pv)
                    if sh:
                        pool.release_shared(eid, sh)
                except Exception:  # noqa: BLE001 -- teardown best effort
                    pass

    threads = [threading.Thread(target=engine, args=(i,))
               for i in range(n_engines)]
    if rec:
        rec.start()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    if rec:
        rec.stop()
        assert rec.error is None, f"reclaimer died: {rec.error}"
    return uaf[0], [e for kind, e in errors if kind == "err"]


@pytest.mark.parametrize("scheme", SAFE_SCHEMES)
def test_registered_schemes_never_uaf_multi_engine(scheme):
    """engines=2 + reclaimer, tight pool: no scheme may ever free a block
    under a live session or live set -- including prefix-shared blocks
    retired by eviction while other engines hold them."""
    pool = BlockPool(24, n_engines=3, reclaim_threshold=4, pressure_factor=1,
                     policy=SimulatedSMRPolicy(scheme))
    uaf, errors = churn_engines(pool, 2, steps=40)
    assert uaf == 0, f"use-after-free under {scheme}"
    assert not errors, errors
    pool.evict_prefixes(0)
    pool.policy.flush()
    assert pool.check_no_leaks()


def test_native_epoch_pop_never_uaf_multi_engine():
    pool = BlockPool(24, n_engines=3, reclaim_threshold=4, pressure_factor=1,
                     ping_timeout_s=0.5, policy=make_policy(None))
    uaf, errors = churn_engines(pool, 2, steps=200)
    assert uaf == 0
    assert not errors, errors
    pool.evict_prefixes(0)
    pool.reclaim()
    assert pool.check_no_leaks()


def test_unsafe_policy_always_fires_multi_engine():
    """The same cross-engine traffic under free-on-retire MUST trip the
    use-after-free detector: engine 1's session spans a shared block whose
    last reference drops on engine 0."""
    pool = BlockPool(16, n_engines=2, reclaim_threshold=4,
                     policy=UnsafeEagerPolicy())
    shared = pool.allocate(0, 2)
    pool.share_prefix(0, "hot", shared)
    pool.start_step(1)
    pool.reserve(1, shared)
    pool.touch(1, shared)                # fine: cache + engine-0 refs live
    pool.release_shared(0, shared)       # engine 0's request finishes
    pool.evict_prefixes(0)               # last ref -> retire -> EAGER free
    with pytest.raises(UseAfterFree):
        pool.touch(1, shared)


def test_unsafe_policy_detects_recycled_prefix_block():
    """ABA variant: the eagerly freed prefix block is recycled into a new
    request on the other engine; the stale session must still trip via the
    allocation generation, not just the free list."""
    pool = BlockPool(4, n_engines=2, reclaim_threshold=2,
                     policy=UnsafeEagerPolicy())
    shared = pool.allocate(0, 2)
    pool.share_prefix(0, "hot", shared)
    pool.start_step(1)
    pool.reserve(1, shared)
    pool.touch(1, shared)
    pool.release_shared(0, shared)
    pool.evict_prefixes(0)               # eager free
    again = pool.allocate(0, 2)          # recycle the same physical blocks
    assert set(again) & set(shared), "LIFO free list should recycle"
    with pytest.raises(UseAfterFree):
        pool.touch(1, shared)


def test_prefix_blocks_never_recycled_under_any_engine_session():
    """Deterministic single-interleaving check for every safe scheme: a
    shared block retired by eviction while another engine's session spans
    it must stay allocated until that session closes."""
    for scheme in SAFE_SCHEMES:
        pool = BlockPool(16, n_engines=2, reclaim_threshold=2,
                         pressure_factor=1,
                         policy=SimulatedSMRPolicy(scheme))
        blocks = pool.allocate(0, 2)
        pool.share_prefix(0, "hot", blocks)
        pool.start_step(1)
        pool.reserve(1, blocks)
        pool.release_shared(0, blocks)
        pool.evict_prefixes(0)           # retire under engine 1's session
        pool.reclaim(0)
        assert all(b not in pool._freeset for b in blocks), \
            f"{scheme} recycled a prefix block under a live session"
        pool.touch(1, blocks)            # must not raise
        pool.end_step(1)


def test_scheduler_rid_thread_safe_and_places_across_engines():
    """8 client threads x 50 submits: ids must be dense and unique (the
    `_rid += 1` data race fix), and placement must spread work across
    workers."""
    eng = ServeEngine(TINY, params=None, n_engines=2, num_pages=32,
                      page_size=8)   # never started: no decode
    rids = []
    lock = threading.Lock()

    def client():
        mine = [eng.submit([1, 2, 3]).rid for _ in range(50)]
        with lock:
            rids.extend(mine)

    threads = [threading.Thread(target=client) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert sorted(rids) == list(range(1, 401)), "request ids raced"
    sizes = [w.queue.qsize() for w in eng.workers]
    assert sum(sizes) == 400
    assert all(s > 0 for s in sizes), f"placement starved a worker: {sizes}"
