"""Property tests for the continuous-batching scheduler: under random
submit / preempt / migrate / prefill-worker-crash sequences, every
submitted request completes exactly once with EXACTLY the tokens an
unperturbed run produces, and the pool is leak-free at shutdown.

The oracle is greedy argmax decode: the next token is a pure function of
(params, prompt, tokens so far), so scheduling -- queue order, chunk
preemption, cross-engine migration, which worker ran which chunk -- must
not change the output.  Any lost/duplicated chunk, double decode, or
block mix-up between requests shows up as a token mismatch or a wrong
output length; any dropped request shows up as a done.wait timeout; any
admission/handoff accounting bug shows up as a pool leak.

The property itself lives in :func:`check_perturbed_run`.  It is driven
two ways: a seeded-``random`` generator (always runs -- the container may
not ship hypothesis, and the invariant is too important to skip) and a
``hypothesis`` ``@given`` wrapper with full shrinking when the library is
importable.

Also here: the Scheduler.stop() regression tests -- shutdown must
finalize requests stranded on the shared prefill queue through the
worker-independent ``finalize_request`` seam, i.e. with ZERO prefill
workers configured (the old code reached into
``self.prefill_workers[0]._finalize`` and would have crashed).
"""

import random
import threading

import pytest

jax = pytest.importorskip("jax")

try:
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # container without hypothesis: seeded driver only
    HAVE_HYPOTHESIS = False

from repro.configs.base import ArchConfig, dense_stack
from repro.models.model import init_params
from repro.serve.engine import ServeEngine
from repro.serve.scheduler import SCHED_POLICIES, PrefillQueue
from repro.serve.worker import Request

from model_ref import GreedyReference

CFG = ArchConfig(name="sched-props", d_model=32, n_heads=4, n_kv_heads=2,
                 d_ff=64, vocab=64, groups=dense_stack(2), remat="none",
                 dtype="float32")
MAX_NEW = 4


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def oracle(params):
    """Reference tokens from a plain single-threaded greedy decode by the
    reference forward -- no scheduler, no pool, no pages."""
    return GreedyReference(params, CFG, max_new=MAX_NEW)


# -- the property --


def check_perturbed_run(prompts, policy, crash, deadlines, params, oracle):
    """Run ``prompts`` through a maximally perturbed pipeline -- ``policy``
    ordering, chunk preemption ON, migration monitor ON with a
    hair-trigger threshold, optionally a prefill worker crashed mid-run --
    and assert byte-identical outputs to the unperturbed oracle, exactly
    once per request, leak-free."""
    eng = ServeEngine(CFG, params, max_batch=2, page_size=4, num_pages=96,
                      n_engines=2, prefill_workers=2,
                      prefill_chunk=4, sched_policy=policy,
                      preempt_prefill=True, migrate=True,
                      migrate_interval_s=0.005, migrate_threshold=1)
    eng.start()
    try:
        reqs = [eng.submit(p, max_new=MAX_NEW, deadline_s=d)
                for p, d in zip(prompts, deadlines)]
        if crash:
            # kill one prefill worker mid-stream: its in-flight request is
            # re-queued resumable; the survivor (or a decode worker after
            # the reroute) adopts the blocks and continues from r.prefilled
            pw = eng.prefill_workers[0]
            pw._stop.set()
            if pw._thread is not None:
                pw._thread.join(timeout=30)
            pw.error = RuntimeError("injected crash")
            eng.scheduler.reroute_prefill_queue()
        for r in reqs:
            assert r.done.wait(timeout=120), f"rid {r.rid} never completed"
    finally:
        eng.stop()
    err = eng.error
    if crash:
        # the injected marker is the ONLY tolerated error
        assert err is None or str(err) == "injected crash", err
    else:
        assert err is None, err
    for p, r in zip(prompts, reqs):
        # exactly-once: a double decode would overshoot max_new, a lost
        # handoff would undershoot or time out above
        assert len(r.out) == MAX_NEW, (r.rid, r.out)
        assert list(r.out) == oracle(p), (r.rid, p, r.out, oracle(p))
    eng.pool.reclaim()
    assert eng.pool.check_no_leaks()
    assert eng.pool.stats.stale_handoffs == 0  # no pool-level crash here


def _draw_case(rng: random.Random):
    prompts = [[rng.randint(1, 63) for _ in range(rng.randint(1, 12))]
               for _ in range(rng.randint(3, 8))]
    policy = rng.choice(SCHED_POLICIES)
    crash = rng.random() < 0.5
    deadlines = [rng.uniform(0.01, 0.5) if rng.random() < 0.5 else None
                 for _ in prompts]
    return prompts, policy, crash, deadlines


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_token_parity_under_perturbation(seed, params, oracle):
    prompts, policy, crash, deadlines = _draw_case(random.Random(seed))
    check_perturbed_run(prompts, policy, crash, deadlines, params, oracle)


if HAVE_HYPOTHESIS:
    prompts_st = st.lists(
        st.lists(st.integers(min_value=1, max_value=63),
                 min_size=1, max_size=12),
        min_size=3, max_size=8)

    @settings(max_examples=5, deadline=None,
              suppress_health_check=[HealthCheck.too_slow,
                                     HealthCheck.function_scoped_fixture])
    @given(data=st.data())
    def test_token_parity_under_perturbation_hypothesis(data, params, oracle):
        prompts = data.draw(prompts_st)
        policy = data.draw(st.sampled_from(SCHED_POLICIES))
        crash = data.draw(st.booleans())
        deadlines = [data.draw(st.one_of(
            st.none(), st.floats(min_value=0.01, max_value=0.5)))
            for _ in prompts]
        check_perturbed_run(prompts, policy, crash, deadlines, params, oracle)


# -- queue-level properties (pure, no engine) --


@pytest.mark.parametrize("policy", SCHED_POLICIES)
@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4])
def test_prefill_queue_drains_completely_in_policy_order(policy, seed):
    """Every put is popped exactly once; sjf pops in nondecreasing
    remaining-length order; fifo preserves arrival order and never counts
    a reorder."""
    rng = random.Random(seed)
    lens = [rng.randint(0, 30) for _ in range(rng.randint(1, 30))]
    q = PrefillQueue(policy)
    reqs = [Request(i + 1, [0] * n, 1) for i, n in enumerate(lens)]
    for r in reqs:
        q.put(r)
    popped = []
    while not q.empty():
        popped.append(q.get_nowait())
    assert sorted(r.rid for r in popped) == sorted(r.rid for r in reqs)
    if policy == "sjf":
        rem = [len(r.prompt) for r in popped]
        assert rem == sorted(rem)
    if policy == "fifo":
        assert [r.rid for r in popped] == [r.rid for r in reqs]
        assert q.reorders == 0


def test_sjf_sorts_resumed_partial_by_remaining_not_total():
    """A re-queued partial sorts by what is LEFT: a 20-token prompt with 18
    prefilled beats a fresh 5-token prompt under sjf."""
    q = PrefillQueue("sjf")
    fresh = Request(1, [0] * 5, 1)
    partial = Request(2, [0] * 20, 1)
    partial.prefilled = 18
    q.put(fresh)
    q.put(partial)
    assert q.get_nowait().rid == 2
    assert q.reorders == 1


def test_deadline_policy_orders_by_deadline_then_best_effort():
    q = PrefillQueue("deadline")
    lazy = Request(1, [0] * 2, 1)                     # no deadline: last
    late = Request(2, [0] * 9, 1)
    late.deadline_s = 9.0
    soon = Request(3, [0] * 9, 1)
    soon.deadline_s = 1.0
    for r in (lazy, late, soon):
        q.put(r)
    assert [q.get_nowait().rid for _ in range(3)] == [3, 2, 1]
    assert q.reorders >= 1


# -- Scheduler.stop() regression: the worker-independent finalize seam --


def test_stop_finalizes_queued_partials_with_zero_prefill_workers(params):
    """A request stranded on the prefill queue with blocks admitted but no
    prefill worker in existence: stop() must release its waiter and return
    its blocks through finalize_request, not reach into
    prefill_workers[0]."""
    eng = ServeEngine(CFG, params, max_batch=2, page_size=4, num_pages=32,
                      n_engines=1, prefill_workers=0)
    w = eng.workers[0]
    r = Request(1, [1, 2, 3, 4, 5], MAX_NEW)
    assert w._admit_blocks(r)          # engine 0 owns blocks now
    r.prefilled = 2                    # mid-prefill partial shape
    eng.scheduler.prefill_queue.put(r)
    eng.scheduler.stop()               # workers never started; must not hang
    assert r.done.is_set()
    assert not r.blocks and not r.shared_blocks
    eng.pool.reclaim()
    assert eng.pool.check_no_leaks()


def test_stop_releases_unadmitted_queued_requests(params):
    """Same seam, un-admitted request (no blocks yet): the waiter is still
    released and nothing leaks."""
    eng = ServeEngine(CFG, params, max_batch=2, page_size=4, num_pages=32,
                      n_engines=1, prefill_workers=0)
    r = Request(7, [1, 2, 3], MAX_NEW)
    eng.scheduler.prefill_queue.put(r)
    eng.scheduler.stop()
    assert r.done.is_set()
    assert eng.pool.check_no_leaks()


def test_stop_unblocks_concurrent_waiter(params):
    """A client thread blocked in done.wait on a stranded request is
    released by stop() -- the shutdown contract clients rely on."""
    eng = ServeEngine(CFG, params, max_batch=2, page_size=4, num_pages=32,
                      n_engines=1, prefill_workers=0)
    r = Request(9, [1, 2], MAX_NEW)
    eng.scheduler.prefill_queue.put(r)
    woke = threading.Event()

    def waiter():
        if r.done.wait(timeout=60):
            woke.set()

    t = threading.Thread(target=waiter)
    t.start()
    eng.scheduler.stop()
    t.join(timeout=60)
    assert woke.is_set()
