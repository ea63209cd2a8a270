"""Async prefill pipeline: chunked-vs-full prefill parity, the chunk-bounded
ping-delivery window, partial-prefill handoff/resume, and no-UAF under the
reclaim policies.

The tentpole contract: a paged-path cache miss no longer runs one
full-prompt forward inside the decode loop.  Prefill is chunked (one
batched forward per ``prefill_chunk`` tokens through the paged kernel, a
``pool.safepoint()`` between chunks) and optionally asynchronous (dedicated
:class:`~repro.serve.worker.PrefillWorker` threads, each a first-class SMR
reader).  So:

1. chunked prefill writes the SAME pages (and final logits) as the
   reference forward's one-shot prefill, config by config;
2. a reclaimer ping that lands mid-prefill is serviced within ONE chunk
   boundary, not one prompt (the publish-on-ping delivery window);
3. a request stopped mid-prefill is resumable: a peer worker adopts its
   blocks and continues from ``r.prefilled``, and the result is identical;
4. the full pipeline (prefill workers + decode workers + reclaimer) raises
   zero UseAfterFree under the native EpochPOP pool and simulated schemes,
   and produces the same tokens as the inline-prefill path.
"""

import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.configs.base import ArchConfig, dense_stack  # noqa: E402
from repro.models.model import apply_model, init_params  # noqa: E402
from repro.runtime.block_pool import BlockPool  # noqa: E402
from repro.runtime.kv_store import PagedKVStore  # noqa: E402
from repro.runtime.reclaim import EpochPOPPolicy, make_policy  # noqa: E402
from repro.serve.engine import ServeEngine  # noqa: E402
from repro.serve.paged_model import (paged_decode_step,  # noqa: E402
                                     prefill_kv_chunked)
from repro.serve.worker import PrefillWorker, Request  # noqa: E402

import jax.numpy as jnp  # noqa: E402

from model_ref import prefill_kv  # noqa: E402

# the same two architectures the kv-store parity suite pins: plain GQA and
# one exercising qk_norm / post_norms / softcap / partial rotary / tying
CFG_PLAIN = ArchConfig(
    name="pf-plain", d_model=32, n_heads=4, n_kv_heads=2, d_ff=64,
    vocab=64, groups=dense_stack(2), remat="none", dtype="float32")
CFG_FANCY = ArchConfig(
    name="pf-fancy", d_model=32, n_heads=4, n_kv_heads=4, d_ff=48,
    vocab=80, groups=dense_stack(3), remat="none", dtype="float32",
    qk_norm=True, post_norms=True, attn_softcap=30.0, rope_pct=0.5,
    tie_embeddings=True)

PAGE = 4


# ----------------------------------------------------------------------------
# parity: chunked paged prefill == the reference's full prefill (pages and
# logits)
# ----------------------------------------------------------------------------


@pytest.mark.parametrize("cfg", [CFG_PLAIN, CFG_FANCY], ids=lambda c: c.name)
@pytest.mark.parametrize("chunk", [1, 3, 16])
def test_chunked_vs_full_prefill_page_and_logit_parity(cfg, chunk):
    """Chunk size must be a storage/scheduling knob, not a model change:
    the pages after chunked prefill match the reference's one-shot
    prefill, and the final chunk's last-row logits match its logits
    (chunk=1 is the old token-by-token replay; 16 > prompt is one shot)."""
    params = init_params(cfg, jax.random.PRNGKey(7))
    prompt = [2, 7, 1, 8, 2, 8, 1, 4, 5, 9, 3]      # 11: ragged tail page
    blocks = [0, 1, 2]
    full = PagedKVStore(cfg, num_blocks=4, page_size=PAGE)
    k, v = prefill_kv(params, cfg, prompt)
    full.write_prefill(blocks, k, v)

    chunked = PagedKVStore(cfg, num_blocks=4, page_size=PAGE)
    last = None
    for end, logits in prefill_kv_chunked(params, cfg, chunked, blocks,
                                          prompt, chunk, impl="interpret"):
        last = logits
    np.testing.assert_allclose(full.k, chunked.k, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(full.v, chunked.v, atol=2e-5, rtol=2e-5)

    ref_logits, _, _ = apply_model(params, jnp.asarray([prompt], jnp.int32),
                                     cfg=cfg, mode="prefill")
    np.testing.assert_allclose(np.asarray(last[-1], np.float32),
                               np.asarray(ref_logits[0, -1], np.float32),
                               atol=2e-4, rtol=2e-4)

    # and the next decode step over either store agrees on the token
    a = paged_decode_step(params, cfg, full, [blocks], [len(prompt)],
                          [prompt[-1]], impl="interpret")
    b = paged_decode_step(params, cfg, chunked, [blocks], [len(prompt)],
                          [prompt[-1]], impl="interpret")
    assert int(jnp.argmax(a[0])) == int(jnp.argmax(b[0]))


def test_chunked_prefill_resumes_from_start():
    """``start=`` re-enters a partial prefill exactly where it left off --
    the resumable-handoff contract at the function level."""
    cfg, chunk = CFG_PLAIN, 3
    params = init_params(cfg, jax.random.PRNGKey(8))
    prompt = [5, 3, 9, 1, 2, 6, 4, 8, 7, 2]
    blocks = [0, 1, 2]
    whole = PagedKVStore(cfg, num_blocks=4, page_size=PAGE)
    for _ in prefill_kv_chunked(params, cfg, whole, blocks, prompt, chunk,
                                impl="interpret"):
        pass
    split = PagedKVStore(cfg, num_blocks=4, page_size=PAGE)
    gen = prefill_kv_chunked(params, cfg, split, blocks, prompt, chunk,
                             impl="interpret")
    end, _ = next(gen)                    # one chunk, then abandon
    gen.close()
    assert 0 < end < len(prompt)
    for _ in prefill_kv_chunked(params, cfg, split, blocks, prompt, chunk,
                                start=end, impl="interpret"):
        pass
    np.testing.assert_allclose(whole.k, split.k, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(whole.v, split.v, atol=2e-5, rtol=2e-5)


# ----------------------------------------------------------------------------
# the ping-delivery window: bounded by one chunk, not one prompt
# ----------------------------------------------------------------------------


def test_ping_mid_prefill_is_serviced_within_a_chunk():
    """A publish-on-ping pass that lands while a prefill worker is deep in
    a long-prompt cache miss must complete within ~one chunk of forward
    work -- the whole point of the chunked pipeline.  The inline
    full-prompt prefill this replaces would only publish after the entire
    prompt."""
    cfg = CFG_PLAIN
    chunk = 2
    prompt = [1 + (i % 40) for i in range(40)]
    params = init_params(cfg, jax.random.PRNGKey(9))
    eng = ServeEngine(cfg, params, max_batch=2, page_size=PAGE,
                      num_pages=32, n_engines=1,
                      prefill_workers=1, prefill_chunk=chunk)
    policy = eng.pool.policy
    assert isinstance(policy, EpochPOPPolicy)
    prefill_eid = eng.prefill_workers[0].engine_id
    eng.start()
    try:
        r = eng.submit(prompt, max_new=1)
        # wait for the miss prefill to be genuinely mid-prompt
        deadline = time.monotonic() + 120
        while r.prefilled < 2 * chunk and time.monotonic() < deadline:
            time.sleep(0.001)
        assert r.prefilled >= 2 * chunk, "prefill never started"
        p0 = r.prefilled
        snap = policy._publish_counter[prefill_eid]
        policy._ping_flags[prefill_eid].set()       # the reclaimer's ping
        deadline = time.monotonic() + 120
        while (policy._publish_counter[prefill_eid] <= snap
               and time.monotonic() < deadline):
            time.sleep(0.0005)
        p1 = r.prefilled
        assert policy._publish_counter[prefill_eid] > snap, \
            "ping was never serviced"
        # serviced within one chunk boundary (+1 chunk in flight, +1 for
        # the progress-poll race), nowhere near the full prompt
        assert p1 - p0 <= 3 * chunk, \
            f"publish took {p1 - p0} tokens of prefill (chunk={chunk})"
        assert p1 < len(prompt), "only published after the whole prompt"
    finally:
        eng.stop()
    assert eng.error is None, f"engine failed: {eng.error!r}"


# ----------------------------------------------------------------------------
# partial prefill is resumable across workers (the handoff race)
# ----------------------------------------------------------------------------


def test_partial_prefill_resumable_across_workers():
    """A prefill worker stopped mid-request leaves it partially prefilled;
    a peer adopts the blocks (ownership moves engine->engine through
    BlockPool.adopt) and resumes from ``r.prefilled``.  Pages must equal an
    uninterrupted prefill's, and the pool ledger must follow the handoff."""
    cfg, chunk = CFG_PLAIN, 4
    params = init_params(cfg, jax.random.PRNGKey(10))
    prompt = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8]
    pool = BlockPool(16, n_engines=2, reclaim_threshold=4)
    store = PagedKVStore(cfg, pool.num_blocks, PAGE)
    pool.add_block_listener(store)
    mk = lambda eid: PrefillWorker(eid, cfg, params, pool,  # noqa: E731
                                   store, "interpret", page_size=PAGE,
                                   prefill_chunk=chunk)
    w0, w1 = mk(0), mk(1)

    r = Request(1, list(prompt), max_new=4)
    w0._stop.set()                       # stop lands after the first chunk
    assert w0.prefill_one(r) is False
    assert 0 < r.prefilled < len(prompt)
    assert r.owner == 0
    assert set(r.blocks) <= pool._live_local[0]

    assert w1.prefill_one(r) is True     # adopt + resume
    assert r.owner == 1
    assert r.prefilled == len(prompt)
    assert set(r.blocks) <= pool._live_local[1]
    assert not set(r.blocks) & pool._live_local[0]
    # the resuming worker only prefilled the remainder
    assert w1.prefill_tokens == len(prompt) - w0.prefill_tokens

    # pages match the reference's uninterrupted prefill in the written
    # range
    ref = PagedKVStore(cfg, pool.num_blocks, PAGE)
    k, v = prefill_kv(params, cfg, prompt)
    ref.write_prefill(r.all_blocks, k, v)
    for b_idx in r.all_blocks:
        np.testing.assert_allclose(ref.k[:, b_idx], store.k[:, b_idx],
                                   atol=2e-5, rtol=2e-5)
    pool.retire(1, r.blocks)
    pool.reclaim(1)
    assert pool.check_no_leaks()


def test_stop_finalizes_stranded_prefill_queue():
    """stop() mid-prefill must not strand the re-queued partial request:
    its waiter is released and its blocks go back through retire/release,
    leaving the pool leak-free."""
    cfg, chunk = CFG_PLAIN, 2
    params = init_params(cfg, jax.random.PRNGKey(15))
    prompt = [1 + (i % 30) for i in range(30)]
    eng = ServeEngine(cfg, params, max_batch=2, page_size=PAGE,
                      num_pages=32, n_engines=1,
                      prefill_workers=1, prefill_chunk=chunk)
    eng.start()
    r = eng.submit(prompt, max_new=2)
    deadline = time.monotonic() + 120
    while r.prefilled < chunk and time.monotonic() < deadline:
        time.sleep(0.001)
    assert r.prefilled >= chunk, "prefill never started"
    eng.stop()                    # worker re-queues the partial request
    assert r.done.is_set(), "stranded prefill request left hanging"
    assert not r.blocks and not r.shared_blocks
    eng.pool.policy.flush()
    assert eng.pool.check_no_leaks()


def test_stop_finalizes_inline_prefill_too():
    """The same guarantee on the inline path (prefill_workers=0): a decode
    worker stopped mid-chunked-prefill finalizes the request instead of
    stranding it on its private queue with blocks held."""
    cfg, chunk = CFG_PLAIN, 2
    params = init_params(cfg, jax.random.PRNGKey(17))
    prompt = [1 + (i % 30) for i in range(30)]
    eng = ServeEngine(cfg, params, max_batch=2, page_size=PAGE,
                      num_pages=32, n_engines=1,
                      prefill_workers=0, prefill_chunk=chunk)
    eng.start()
    r = eng.submit(prompt, max_new=2)
    deadline = time.monotonic() + 120
    while r.prefilled < chunk and time.monotonic() < deadline:
        time.sleep(0.001)
    assert r.prefilled >= chunk, "prefill never started"
    eng.stop()
    assert r.done.is_set(), "stranded inline-prefill request left hanging"
    assert not r.blocks and not r.shared_blocks
    eng.pool.policy.flush()
    assert eng.pool.check_no_leaks()


def test_reroute_hands_queued_requests_to_decode():
    """reroute_prefill_queue (the dead-stage path) places queued requests
    on the decode fleet instead of completing them empty."""
    cfg = CFG_PLAIN
    params = init_params(cfg, jax.random.PRNGKey(16))
    eng = ServeEngine(cfg, params, max_batch=2, page_size=PAGE,
                      num_pages=32, n_engines=1,
                      prefill_workers=1, prefill_chunk=4)
    r = Request(1, [5, 3, 9], max_new=2)
    eng.scheduler.prefill_queue.put(r)
    eng.scheduler.reroute_prefill_queue()
    assert eng.scheduler.prefill_queue.empty()
    assert eng.workers[0].queue.qsize() == 1
    assert not r.done.is_set()


def test_scheduler_routes_around_dead_prefill_stage():
    """When every prefill worker has failed, submit degrades to direct
    decode placement and the decode worker's inline chunked prefill still
    serves the request."""
    cfg = CFG_PLAIN
    params = init_params(cfg, jax.random.PRNGKey(11))
    eng = ServeEngine(cfg, params, max_batch=2, page_size=PAGE,
                      num_pages=32, n_engines=1,
                      prefill_workers=1, prefill_chunk=4)
    eng.start()
    try:
        eng.prefill_workers[0].error = RuntimeError("injected")
        r = eng.submit([5, 3, 9, 1, 2], max_new=3)
        assert r.done.wait(timeout=300)
        assert len(r.out) == 3
    finally:
        eng.stop()
    # the decode fleet itself stayed healthy
    assert all(w.error is None for w in eng.workers)


# ----------------------------------------------------------------------------
# full pipeline: token parity and no-UAF under the reclaim policies
# ----------------------------------------------------------------------------


def _run(eng, prompts, max_new=3):
    eng.start()
    reqs = [eng.submit(p, max_new=max_new) for p in prompts]
    for r in reqs:
        assert r.done.wait(timeout=600)
    eng.stop()
    assert eng.error is None, f"engine failed: {eng.error!r}"
    return [list(r.out) for r in reqs]


@pytest.mark.parametrize("cfg", [CFG_PLAIN, CFG_FANCY], ids=lambda c: c.name)
def test_async_prefill_token_parity(cfg):
    """prefill_workers=2 must be a scheduling change only: same tokens as
    the inline-prefill engine, config by config."""
    params = init_params(cfg, jax.random.PRNGKey(12))
    prompts = [[1, 9, 3, 5, 2], [7, 2, 8, 6, 4, 1, 3, 5], [11],
               [5, 3, 9, 1, 2, 6, 4, 8, 7, 2, 9]]
    outs = {}
    for n_pw in (0, 2):
        eng = ServeEngine(cfg, params, max_batch=4, page_size=PAGE,
                          num_pages=64, n_engines=1,
                          prefill_workers=n_pw, prefill_chunk=3)
        outs[n_pw] = _run(eng, prompts)
        if n_pw:
            # prefill genuinely ran in the dedicated stage
            assert sum(pw.requests for pw in eng.prefill_workers) == len(
                prompts)
            assert all(w.prefill_tokens == 0 for w in eng.workers)
    assert outs[0] == outs[2]


@pytest.mark.parametrize("smr", ["EpochPOP-pool", "HazardPtrPOP", "EBR"])
def test_async_prefill_no_uaf_under_reclaim_policies(smr):
    """The whole pipeline -- prefill workers allocating/writing, decode
    workers gathering, the reclaimer pinging everyone -- under the native
    pool policy and two simulated schemes: zero UseAfterFree, leak-free."""
    cfg = CFG_PLAIN
    params = init_params(cfg, jax.random.PRNGKey(13))
    pool = BlockPool(48, n_engines=4, reclaim_threshold=4,
                     pressure_factor=2, policy=make_policy(smr))
    eng = ServeEngine(cfg, params, max_batch=4, page_size=PAGE,
                      pool=pool, n_engines=1, prefill_workers=2,
                      prefill_chunk=2, prefix_cache=True)
    eng.start()
    hot = [5, 3, 9, 1]
    reqs = [eng.submit(hot + [i + 1, i + 2], max_new=2) for i in range(6)]
    for r in reqs:
        assert r.done.wait(timeout=600)
    eng.stop()
    assert eng.error is None, f"engine failed under {smr}: {eng.error!r}"
    pool.evict_prefixes(0)
    pool.policy.flush()
    assert pool.stats.freed > 0
    assert eng.kv_store.poisons == pool.stats.freed
    assert pool.check_no_leaks()


def test_prefill_worker_ownership_handoff_is_leak_free():
    """Blocks allocated under a prefill worker's engine id and adopted by a
    decode worker retire cleanly: nothing stranded in either live set."""
    cfg = CFG_PLAIN
    params = init_params(cfg, jax.random.PRNGKey(14))
    eng = ServeEngine(cfg, params, max_batch=2, page_size=PAGE,
                      num_pages=32, n_engines=1,
                      prefill_workers=1, prefill_chunk=4)
    outs = _run(eng, [[1, 2, 3, 4, 5, 6], [9, 8, 7]])
    assert all(len(o) == 3 for o in outs)
    eng.pool.policy.flush()
    assert eng.pool.check_no_leaks()
    assert all(not s for s in eng.pool._live_local)
