"""Migration litmus: :meth:`BlockPool.adopt` racing reclamation, on every
registered SMR scheme and both simulator backends.

Cross-engine migration re-homes a request's KV blocks between engine live
sets while reclaimers run.  The adopt-vs-ping interleavings under test:

1. **migrate-then-retire under an open reader session** (all 13 schemes x
   {gen, vec}): engine 0's request migrates to engine 1 while engine 2
   holds a reader session over its blocks; the new owner retires them and
   reclaim runs.  Safe schemes must keep the session's touches valid;
   ``HP-broken`` (unfenced reservation stores, invisible to a concurrent
   scan under store-buffer costs) must still trip :class:`UseAfterFree` --
   proving the litmus can actually catch an unsafe scheme, not merely that
   nothing fired.
2. **adopt while a native POP pass is mid-publish**: the destination
   engine publishes BEFORE the adopt, so neither published set contains
   the migrated blocks -- the pass must still not free them, because the
   post-adopt retire lands at an epoch >= the pass's cut.
3. **migrate a request whose source engine crashed** (all 13 x {gen,
   vec}): adopt-before-crash completes and the destination finishes
   normally; crash-before-adopt is a *stale handoff* -- the pool must
   refuse (:class:`StaleHandoff`) without mutating any ledger, because the
   crashed source's blocks were already recovered and may be reallocated.
4. a serving-stack smoke: ``ServeEngine`` with static (skew-prone)
   placement, migration on, and a stalled engine 0 completes every
   request with zero UAF and a leak-free pool.

Store-buffer costs mirror ``tests/test_sim_vec.py``: drains effectively
never complete on their own (``drain_latency=10_000_000``) and only a
signal forces them (``signal_latency=500``) -- deterministic for both the
HP-broken trip and the safe schemes' survival.
"""

import threading

import pytest

from repro.core.sim.engine import Costs, UseAfterFree
from repro.core.smr.registry import SCHEMES
from repro.runtime.block_pool import BlockPool, StaleHandoff
from repro.runtime.reclaim import (SimulatedSMRPolicy, make_policy,
                                   supported_schemes)

ALL_SCHEMES = list(SCHEMES)
SAFE_SCHEMES = supported_schemes()
BACKENDS = ("gen", "vec")

# store-buffer regime: reservation stores stay buffered ~forever unless a
# signal (publish-on-ping) forces the drain -- HP-broken's unfenced store
# is deterministically invisible to a concurrent reclaim scan, while every
# fenced/POP scheme survives the identical costs
LITMUS_COSTS = Costs(drain_latency=10_000_000, drain_jitter=0,
                     signal_latency=500)


def sim_pool(scheme: str, backend: str, *, num_blocks: int = 48,
             n_engines: int = 3) -> BlockPool:
    return BlockPool(num_blocks, n_engines=n_engines, reclaim_threshold=2,
                     pressure_factor=1,
                     policy=SimulatedSMRPolicy(scheme, backend=backend,
                                               costs=LITMUS_COSTS))


# ----------------------------------------------------------------------------
# 1. migrate-then-retire under an open reader session
# ----------------------------------------------------------------------------


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("scheme", ALL_SCHEMES)
def test_migrate_then_retire_under_reader_session(scheme, backend):
    """Engine 0's request migrates to engine 1 while engine 2 reads its
    blocks; the new owner retires them under the open session.  Safe
    schemes keep every touch valid; HP-broken must fire."""
    pool = sim_pool(scheme, backend)
    pool.start_step(0)
    blocks = pool.allocate(0, 3)
    pool.end_step(0)

    # engine 2: reader session over the request's blocks (the prefix-shared
    # traversal a migration must never invalidate)
    pool.start_step(2)
    pool.reserve(2, blocks)
    pool.touch(2, blocks)

    # advance engine 1's sim clock past the reader's reservation issue
    # times before it ever scans: driven-mode threads advance only when
    # driven, and HPAsym's membarrier drains stores *issued before* the
    # scanning thread's clock -- a reclaimer whose clock never moved would
    # (unphysically) membarrier "before" reservations that really happened
    # earlier.  Allocation traffic is how a real engine's clock advances.
    pool.start_step(1)
    junk = pool.allocate(1, 16)
    pool.end_step(1)

    # the migration, racing nothing yet: ledger moves 0 -> 1
    pool.adopt(0, 1, blocks)
    assert pool.stats.adopts == 1 and pool.stats.adopted_blocks == 3

    # the new owner finishes the request and retires its blocks while the
    # session is still open, then reclaim runs
    pool.start_step(1)
    pool.retire(1, blocks)
    pool.end_step(1)

    if scheme == "HP-broken":
        # the unfenced reservation store never reached shared memory: the
        # scan frees the session-held blocks and the next touch must trip
        with pytest.raises(UseAfterFree):
            pool.reclaim()
            pool.touch(2, blocks)
        return

    pool.reclaim()
    pool.touch(2, blocks)            # session must STILL protect them
    pool.end_step(2)
    pool.retire(1, junk)
    # quiescent steps so epoch/era schemes can advance, then flush
    for e in range(3):
        pool.start_step(e)
        pool.end_step(e)
    pool.reclaim()
    assert pool.check_no_leaks()


# ----------------------------------------------------------------------------
# 2. adopt while a native publish-on-ping pass is mid-publish
# ----------------------------------------------------------------------------


def test_adopt_races_native_pop_pass_mid_publish():
    """The nastiest interleaving, frozen deterministically: the POP pass
    pings; the DESTINATION publishes before the adopt; then the blocks
    move src->dst and the new owner retires them; the remaining engines
    publish and the pass completes.  Neither published set contains the
    blocks -- the pass must exclude them anyway, because their retire
    landed at an epoch >= the pass's cut.  Freeing them here would be a
    use-after-free by protocol."""
    pool = BlockPool(32, n_engines=3, reclaim_threshold=100,
                     ping_timeout_s=10.0,
                     policy=make_policy(None, pop_every=1))
    blocks = pool.allocate(0, 4)
    # eligible garbage retired BEFORE the pass, so it has real work
    junk = pool.allocate(2, 4)
    pool.retire(2, junk)

    flags = pool.policy._ping_flags
    done = threading.Event()
    result = {}

    def reclaimer():
        result["freed"] = pool.reclaim(None)   # pings engines 0, 1, 2
        done.set()

    t = threading.Thread(target=reclaimer, daemon=True)
    t.start()
    assert flags[1].wait(timeout=5.0), "POP pass never pinged engine 1"

    pool.safepoint(1)                # dst publishes its PRE-adopt live set
    pool.adopt(0, 1, blocks)         # the migration, mid-pass
    pool.retire(1, blocks)           # new owner retires: epoch >= cut
    pool.safepoint(0)                # src publishes post-adopt (no blocks)
    pool.safepoint(2)
    done.wait(timeout=15.0)
    t.join(timeout=15.0)
    assert done.is_set(), "POP pass did not complete"

    # the pass must NOT have freed the migrated blocks (their retire is
    # after its cut), even though no published set contained them
    with pool._lock:
        assert not (set(blocks) & pool._freeset), \
            "POP pass freed blocks whose adopt raced its publish window"
    assert pool.retired_blocks >= len(blocks)
    # a later, quiescent pass frees them through the epoch fast path
    pool.reclaim()
    with pool._lock:
        assert set(blocks) <= pool._freeset
    assert pool.check_no_leaks()


# ----------------------------------------------------------------------------
# 3. migration vs. source-engine crash
# ----------------------------------------------------------------------------


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("scheme", ALL_SCHEMES)
def test_adopt_before_crash_completes_safely(scheme, backend):
    """Migration wins the race: the blocks moved before the source died,
    so the crash recovers nothing and the destination finishes the request
    normally -- no UAF, no leak."""
    pool = sim_pool(scheme, backend)
    pool.start_step(0)
    blocks = pool.allocate(0, 3)
    pool.end_step(0)
    pool.adopt(0, 1, blocks)
    assert pool.crash_engine(0) == 0     # src owned nothing anymore
    pool.start_step(1)
    pool.reserve(1, blocks)
    pool.touch(1, blocks)
    pool.end_step(1)
    pool.retire(1, blocks)
    for e in (1, 2):
        pool.start_step(e)
        pool.end_step(e)
    pool.reclaim()
    assert pool.check_no_leaks()
    assert pool.stats.stale_handoffs == 0


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("scheme", ALL_SCHEMES)
def test_crash_before_adopt_is_refused_as_stale(scheme, backend):
    """The crash wins the race: the source's blocks were recovered onto a
    survivor (and may be freed or REALLOCATED by now), so the queued
    migration's adopt must be refused with no ledger mutation -- and a new
    request that legitimately reallocated those block ids keeps working."""
    pool = sim_pool(scheme, backend)
    blocks = pool.allocate(0, 3)
    assert pool.crash_engine(0) == 3     # orphans retired on a survivor
    adopts_before = pool.stats.adopts

    with pytest.raises(StaleHandoff):
        pool.adopt(0, 1, blocks)         # the stale queued migration
    assert pool.stats.stale_handoffs == 1
    assert pool.stats.adopts == adopts_before, "refusal must not count"
    # no resurrection: the blocks did NOT enter the destination's live set
    assert not (set(blocks) & pool._live_local[1])

    # a survivor's fresh request is unaffected (block ids may even recycle)
    pool.start_step(2)
    fresh = pool.allocate(2, 3)
    pool.reserve(2, fresh)
    pool.touch(2, fresh)
    pool.end_step(2)
    pool.retire(2, fresh)
    for e in (1, 2):
        pool.start_step(e)
        pool.end_step(e)
    pool.reclaim()
    assert pool.check_no_leaks()


def test_stale_shared_reference_also_refused():
    """The shared-block leg of the validation: a handoff whose SHARED
    request references the source no longer holds is refused too."""
    pool = BlockPool(16, n_engines=3, reclaim_threshold=8)
    blocks = pool.allocate(0, 2)
    assert pool.share_prefix(0, ("k", 1), blocks)
    pool.release_shared(0, blocks)       # source dropped its request refs
    with pytest.raises(StaleHandoff):
        pool.adopt(0, 1, [], shared=blocks)
    assert pool.stats.stale_handoffs == 1
    pool.evict_prefixes(1)
    pool.reclaim()
    assert pool.check_no_leaks()


# ----------------------------------------------------------------------------
# 4. serving-stack smoke: migration rescues a stalled, statically-placed fleet
# ----------------------------------------------------------------------------


@pytest.mark.parametrize("scheme", ["EpochPOP-pool", "EpochPOP", "EBR"])
def test_serving_migration_smoke(scheme):
    """End-to-end: static placement piles requests onto a stalled engine 0;
    the migration monitor re-homes them (adopts under live reclamation).
    Every request must complete, zero UAF, pool leak-free."""
    jax = pytest.importorskip("jax")
    from repro.configs.base import ArchConfig, dense_stack
    from repro.models.model import init_params
    from repro.serve.engine import ServeEngine

    cfg = ArchConfig(name="mig-smoke", d_model=32, n_heads=4, n_kv_heads=2,
                     d_ff=64, vocab=64, groups=dense_stack(2), remat="none",
                     dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))
    smr = None if scheme == "EpochPOP-pool" else scheme
    eng = ServeEngine(cfg, params, max_batch=2, page_size=4, num_pages=96,
                      smr=smr, n_engines=3, sim_backend="vec",
                      place_policy="static", migrate=True,
                      migrate_interval_s=0.005, migrate_threshold=2,
                      stall_every=2, stall_s=0.05, stall_workers=(0,))
    eng.start()
    reqs = [eng.submit([1 + (i % 7), 2, 3, 4 + (i % 5)], max_new=4)
            for i in range(12)]
    for r in reqs:
        assert r.done.wait(timeout=120), f"request {r.rid} never finished"
        assert len(r.out) == 4
    eng.stop()
    assert eng.error is None, f"engine failed: {eng.error!r}"
    eng.pool.evict_prefixes(0)
    eng.pool.policy.flush()
    assert eng.pool.check_no_leaks()
    assert eng.pool.stats.stale_handoffs == 0
