"""End-to-end system tests: train a tiny model -> checkpoint -> restore ->
serve it through the paged engine with POP block-pool reclamation."""

import pytest

from repro.configs.base import ArchConfig, dense_stack
from repro.data.pipeline import DataConfig
from repro.runtime.block_pool import BlockPool
from repro.serve.engine import ServeEngine
from repro.train.trainer import Trainer, TrainerConfig

TINY = ArchConfig(
    name="tiny-sys", d_model=64, n_heads=4, n_kv_heads=2, d_ff=128, vocab=128,
    groups=dense_stack(2), remat="none", dtype="float32")


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("sys")
    tcfg = TrainerConfig(steps=30, ckpt_every=10, log_every=1000,
                         ckpt_dir=str(tmp / "ckpt"), lr_peak=2e-3)
    dcfg = DataConfig(vocab=TINY.vocab, seq_len=32, global_batch=4, seed=1)
    tr = Trainer(TINY, tcfg, dcfg)
    out = tr.run()
    return tr, out


def test_train_checkpoint_restore_serve(trained):
    tr, out = trained
    assert out["step"] == 30
    # restore from disk into a fresh trainer
    tr2 = Trainer(TINY, tr.tcfg, None)
    restored = tr2.try_restore()
    assert restored is not None
    params, _, start = restored
    assert start == 30

    # serve the restored model through the paged engine + POP pool
    pool = BlockPool(64, n_engines=1, reclaim_threshold=4, pressure_factor=2)
    eng = ServeEngine(TINY, params, max_batch=4, page_size=8,
                      pool=pool)
    eng.start()
    reqs = [eng.submit([1 + i, 5, 9], max_new=6) for i in range(6)]
    for r in reqs:
        assert r.done.wait(timeout=120), "generation timed out"
        assert len(r.out) == 6
        assert all(0 <= t < TINY.vocab_padded for t in r.out)
    eng.stop()
    # all request blocks retired and reclaimed through the pool
    assert pool.stats.freed > 0
    assert pool.check_no_leaks()


def test_serve_deterministic_greedy(trained):
    tr, out = trained
    params = out["params"]
    pool = BlockPool(32, n_engines=1, reclaim_threshold=4)
    eng = ServeEngine(TINY, params, max_batch=2, page_size=8,
                      pool=pool)
    eng.start()
    a = eng.submit([3, 7], max_new=5)
    b = eng.submit([3, 7], max_new=5)
    assert a.done.wait(timeout=120) and b.done.wait(timeout=120)
    eng.stop()
    assert a.out == b.out, "greedy decode must be deterministic"


def test_serve_multi_engine_prefix_cache(trained):
    """Sharded runtime end-to-end: 2 engine workers + reclaimer over one
    pool, prefix cache on.  Shared-prefix prompts must hit the cache, skip
    prefill for the cached pages, decode identically to fresh prefills, and
    leave the pool leak-free after eviction + reclamation."""
    tr, out = trained
    params = out["params"]
    eng = ServeEngine(TINY, params, max_batch=2, page_size=8,
                      num_pages=64, n_engines=2, prefix_cache=True)
    eng.start()
    prefix = [2, 4, 6, 8, 1, 3, 5, 7]           # exactly one full page
    reqs = [eng.submit(prefix + [9 + i % 2], max_new=5) for i in range(6)]
    for r in reqs:
        assert r.done.wait(timeout=120), "generation timed out"
        assert len(r.out) == 5
    eng.stop()
    assert eng.error is None, f"engine failed: {eng.error}"
    s = eng.pool.stats
    assert s.prefix_hits > 0, "shared prompts never hit the prefix cache"
    assert sum(w.prefill_tokens_skipped for w in eng.workers) > 0
    # identical prompts must decode identically whether the prefix came
    # from a cache hit or a fresh prefill, on any engine
    outs = {}
    for r in reqs:
        outs.setdefault(tuple(r.prompt), set()).add(tuple(r.out))
    assert all(len(v) == 1 for v in outs.values()), outs
    eng.pool.evict_prefixes(0)
    eng.pool.reclaim()
    assert eng.pool.check_no_leaks()
