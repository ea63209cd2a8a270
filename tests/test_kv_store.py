"""Device-paged KV store: paged-vs-reference parity, physical-page
use-after-free tripwires, and block-table raggedness edge cases.

Parity is the load-bearing contract: the paged path (device pages +
Pallas paged-attention kernel in interpret mode) must produce the SAME
tokens and logits as the reference forward, ``apply_model`` against a
per-request cache, config by config -- otherwise "physically shared
prefixes" would be a different model, not a storage layer.
"""

import numpy as np
import pytest

# skip-if-no-jax, same idiom the property suite uses for hypothesis: the
# paged path is jax end to end (model forward + Pallas interpret kernel)
jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.configs.base import ArchConfig, dense_stack  # noqa: E402
from repro.core.sim.engine import UseAfterFree  # noqa: E402
from repro.kernels.paged_attention import (build_block_table,  # noqa: E402
                                           paged_attention_pallas)
from repro.models.model import apply_model, init_cache, init_params  # noqa: E402
from repro.runtime.block_pool import BlockPool  # noqa: E402
from repro.runtime.kv_store import PagedKVStore, kv_layer_order  # noqa: E402
from repro.runtime.reclaim import UnsafeEagerPolicy  # noqa: E402
from repro.serve.engine import ServeEngine  # noqa: E402
from repro.serve.paged_model import (check_paged_support,  # noqa: E402
                                     paged_decode_step, prefill_kv_chunked)

from model_ref import GreedyReference, prefill_kv  # noqa: E402

RNG = np.random.default_rng(3)

# two distinct architectures: plain GQA, and one exercising qk_norm,
# post_norms, attention softcap, partial rotary, and tied embeddings
CFG_PLAIN = ArchConfig(
    name="kv-plain", d_model=32, n_heads=4, n_kv_heads=2, d_ff=64,
    vocab=64, groups=dense_stack(2), remat="none", dtype="float32")
CFG_FANCY = ArchConfig(
    name="kv-fancy", d_model=32, n_heads=4, n_kv_heads=4, d_ff=48,
    vocab=80, groups=dense_stack(3), remat="none", dtype="float32",
    qk_norm=True, post_norms=True, attn_softcap=30.0, rope_pct=0.5,
    tie_embeddings=True)
CONFIGS = [CFG_PLAIN, CFG_FANCY]

PAGE = 4

#: (Hkv, head_dim) of the two benchmark cells: starcoder2-7b, stablelm-12b
GEOMETRIES = [(4, 128), (8, 160)]


def _geometry_cfg(Hkv, hd):
    return ArchConfig(name="kv-wide", d_model=64, n_heads=2 * Hkv,
                      n_kv_heads=Hkv, head_dim=hd, d_ff=64, vocab=64,
                      groups=dense_stack(2), remat="none", dtype="float32")


def _engine(cfg, params, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("page_size", PAGE)
    kw.setdefault("num_pages", 64)
    return ServeEngine(cfg, params, **kw)


def _run(eng, prompts, max_new=4):
    eng.start()
    reqs = [eng.submit(p, max_new=max_new) for p in prompts]
    for r in reqs:
        assert r.done.wait(timeout=300)
    eng.stop()
    assert eng.error is None, f"engine failed: {eng.error!r}"
    return [list(r.out) for r in reqs]


# ----------------------------------------------------------------------------
# parity: the paged path and the reference forward agree
# ----------------------------------------------------------------------------


@pytest.mark.parametrize("cfg", CONFIGS, ids=lambda c: c.name)
def test_paged_dense_token_parity(cfg):
    """The paged engine's greedy tokens are the reference forward's."""
    params = init_params(cfg, jax.random.PRNGKey(1))
    # varied raggedness: single-token tail page (5 = PAGE+1), page-aligned
    # (8), minimal (1), and a longer multi-page prompt
    prompts = [[1, 9, 3, 5, 2], [7, 2, 8, 6, 4, 1, 3, 5], [11],
               [int(x) for x in RNG.integers(1, cfg.vocab, 11)]]
    ref = GreedyReference(params, cfg, max_new=4)
    assert _run(_engine(cfg, params), prompts) == [ref(p) for p in prompts]


@pytest.mark.parametrize("parallel", [False, True],
                         ids=["serial", "parallel"])
@pytest.mark.parametrize("base", CONFIGS, ids=lambda c: c.name)
@pytest.mark.parametrize("dtype,atol", [("float32", 2e-4),
                                        ("bfloat16", 5e-2)])
def test_paged_decode_logits_match_dense(dtype, atol, base, parallel):
    """One decode step, same prompt: paged logits vs the reference's
    decode logits, for both configs with a serial and a parallel residual.
    The bf16 case pins the store to the MODEL dtype (pages must hold
    exactly the values the reference cache would, not silently-upcast f32:
    resident bf16 device arrays)."""
    cfg = base.scaled(dtype=dtype, groups=dense_stack(
        base.groups[0].repeats, parallel=parallel))
    prompt = [3, 1, 4, 1, 5, 9, 2]
    params = init_params(cfg, jax.random.PRNGKey(2))
    n = len(prompt)

    # reference: token-by-token prefill, then one decode
    cache = init_cache(cfg, 1, 32, cfg.dtype)
    toks = jnp.asarray([prompt], jnp.int32)
    for t in range(n):
        _, cache, _ = apply_model(params, toks[:, t:t + 1], cfg=cfg,
                                  mode="decode", cache=cache)
    dense_logits, _, _ = apply_model(
        params, jnp.asarray([[prompt[-1]]], jnp.int32), cfg=cfg,
        mode="decode", cache=cache)

    # paged: the reference prefill written into pages, then one paged step
    store = PagedKVStore(cfg, num_blocks=8, page_size=PAGE)
    assert store.k.dtype == np.dtype(cfg.dtype)
    blocks = [0, 1, 2]
    k, v = prefill_kv(params, cfg, prompt)
    store.write_prefill(blocks, k, v)
    paged_logits = paged_decode_step(params, cfg, store, [blocks], [n],
                                     [prompt[-1]], impl="interpret")
    np.testing.assert_allclose(np.asarray(paged_logits[0], np.float32),
                               np.asarray(dense_logits[0, -1], np.float32),
                               atol=atol, rtol=atol)


@pytest.mark.parametrize("parallel", [False, True],
                         ids=["serial", "parallel"])
@pytest.mark.parametrize("base", CONFIGS, ids=lambda c: c.name)
@pytest.mark.parametrize("dtype,atol", [("float32", 2e-4),
                                        ("bfloat16", 5e-2)])
def test_paged_prefill_chunk_logits_match_dense(dtype, atol, base,
                                                parallel):
    """A prompt prefilled in chunks of 3 through the paged forward: every
    chunk row's logits against the reference full-sequence forward's at
    that position, for both configs, serial and parallel residual."""
    cfg = base.scaled(dtype=dtype, groups=dense_stack(
        base.groups[0].repeats, parallel=parallel))
    prompt = [2, 7, 1, 8, 2, 8, 1, 4, 5, 9, 3]
    params = init_params(cfg, jax.random.PRNGKey(12))
    want = apply_model(params, jnp.asarray([prompt], jnp.int32), cfg=cfg,
                       mode="train")[0]
    want = np.asarray(want[0, :, :cfg.vocab], np.float32)
    store = PagedKVStore(cfg, num_blocks=8, page_size=PAGE)
    start = 0
    for end, logits in prefill_kv_chunked(params, cfg, store, [5, 0, 3],
                                          prompt, 3, impl="interpret"):
        np.testing.assert_allclose(
            np.asarray(logits[:, :cfg.vocab], np.float32), want[start:end],
            atol=atol, rtol=atol)
        start = end
    assert start == len(prompt)


@pytest.mark.parametrize("cfg", CONFIGS, ids=lambda c: c.name)
def test_forward_launches_two_programs_a_layer(cfg):
    """A decode step and a prefill chunk of an L-layer model each launch
    2L + 1 programs -- the kernel, and one program between two kernel
    calls -- and put the step's inputs on the device in one upload."""
    from repro.serve.paged_model import forward_counts, prefill_chunk_step

    L = len(kv_layer_order(cfg))
    params = init_params(cfg, jax.random.PRNGKey(13))
    store = PagedKVStore(cfg, num_blocks=8, page_size=PAGE)
    want = {"launches": 2 * L + 1, "uploads": 1}
    prefill_chunk_step(params, cfg, store, [1, 2], [3, 1, 4, 1, 5], 0,
                       impl="interpret")
    assert forward_counts() == want
    paged_decode_step(params, cfg, store, [[1, 2], [4]], [5, 1], [9, 2],
                      impl="interpret")
    assert forward_counts() == want


def test_fused_layer_program_writes_its_pages_in_place():
    """The program between two kernel calls donates the next layer's K and
    V pages: the compiled program aliases both into its outputs, and a
    decode step leaves every layer's page buffers where they were."""
    from repro.serve.paged_model import _mlp_qkv, _out_mlp, _qkv

    cfg = CFG_PLAIN
    params = init_params(cfg, jax.random.PRNGKey(14))
    store = PagedKVStore(cfg, num_blocks=8, page_size=PAGE)
    gp = params["groups"]["g0"]["p0"]
    B, H, hd = 3, cfg.n_heads, cfg.head_dim_
    x = jnp.zeros((B, 1, cfg.d_model), cfg.dtype)
    attn = jnp.zeros((8, H, hd), jnp.float32)
    idx = jnp.zeros((B,), jnp.int32)
    pages = store.layer_pages(1)
    compiled = _mlp_qkv.lower(
        gp, 0, x, attn, gp, 1, idx[:, None], pages, idx, idx, cfg=cfg,
        rows=8, parallel=False, out_mlp=_out_mlp, qkv=_qkv).compile()
    assert compiled.memory_analysis().alias_size_in_bytes == \
        pages[0].nbytes + pages[1].nbytes

    store.sync()
    before = [store.layer_pages(li)[0].unsafe_buffer_pointer()
              for li in range(len(store.layer_order))]
    paged_decode_step(params, cfg, store, [[0, 1], [2]], [5, 2], [9, 2],
                      impl="interpret")
    store.sync()
    assert [store.layer_pages(li)[0].unsafe_buffer_pointer()
            for li in range(len(store.layer_order))] == before


def test_concurrent_prefill_write_during_decode_steps():
    """A prefill worker's ``write_prefill`` from another thread, racing a
    run of decode steps on one device store: no "array deleted" error, and
    every token either side wrote is in the pages -- as a serial run of
    the same writes leaves them."""
    import sys
    import threading
    import time

    cfg = CFG_PLAIN
    params = init_params(cfg, jax.random.PRNGKey(15))
    prompt = [3, 1, 4, 1, 5]
    k, v = prefill_kv(params, cfg, prompt)
    other = [np.asarray(a) for a in prefill_kv(params, cfg, [7, 2, 8, 6])]

    def decode(store, n_steps):
        tok, n = prompt[-1], len(prompt)
        for _ in range(n_steps):
            logits = paged_decode_step(params, cfg, store, [[0, 1, 2]], [n],
                                       [tok], impl="interpret")
            tok, n = int(np.argmax(np.asarray(logits[0]))), n + 1
        return tok

    def writes(store, n, done=None):
        # the same tokens into pages 3-6, again and again until ``done``
        i = 0
        while i < n or (done is not None and not done.is_set()):
            store.write_prefill([3 + i % 4], *other)
            i += 1

    serial = PagedKVStore(cfg, num_blocks=8, page_size=PAGE)
    serial.write_prefill([0, 1, 2], k, v)
    want_tok = decode(serial, 6)
    writes(serial, 4)

    store = PagedKVStore(cfg, num_blocks=8, page_size=PAGE)
    store.write_prefill([0, 1, 2], k, v)
    fetch = store.layer_pages

    def slow_fetch(li):
        # a slow host between fetching a layer's pages and the kernel call:
        # the window in which a writer's donation would delete them
        pages = fetch(li)
        time.sleep(0.002)
        return pages

    store.layer_pages = slow_fetch
    errors, done = [], threading.Event()

    def writer():
        try:
            writes(store, 4, done)
        except Exception as e:  # noqa: BLE001 -- reported below
            errors.append(e)

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        t = threading.Thread(target=writer)
        t.start()
        got_tok = decode(store, 6)
        done.set()
        t.join(timeout=120)
    finally:
        done.set()
        sys.setswitchinterval(was)
    assert not t.is_alive()
    assert not errors, errors
    assert got_tok == want_tok
    np.testing.assert_array_equal(np.asarray(store.k), np.asarray(serial.k))
    np.testing.assert_array_equal(np.asarray(store.v), np.asarray(serial.v))


def test_prefill_kv_matches_decode_appends():
    """The reference prefill's K/V and the per-token paged decode steps
    must write the SAME physical pages (post-rope K/V, same layer
    order)."""
    cfg, prompt = CFG_FANCY, [2, 7, 1, 8, 2, 8]
    params = init_params(cfg, jax.random.PRNGKey(3))
    a = PagedKVStore(cfg, num_blocks=4, page_size=PAGE)
    b = PagedKVStore(cfg, num_blocks=4, page_size=PAGE)
    k, v = prefill_kv(params, cfg, prompt)
    a.write_prefill([0, 1], k, v)
    for t in range(len(prompt)):
        paged_decode_step(params, cfg, b, [[0, 1]], [t], [prompt[t]],
                          impl="interpret")
    np.testing.assert_allclose(a.k, b.k, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(a.v, b.v, atol=2e-5, rtol=2e-5)


# ----------------------------------------------------------------------------
# prefix sharing installs no copies on the paged path
# ----------------------------------------------------------------------------


def test_paged_prefix_hit_installs_zero_bytes():
    cfg = CFG_PLAIN
    params = init_params(cfg, jax.random.PRNGKey(4))
    prompt = [5, 3, 9, 1, 2, 6, 4, 8]          # exactly 2 pages at PAGE=4
    eng = _engine(cfg, params, prefix_cache=True, n_engines=1)
    eng.start()
    r1 = eng.submit(prompt, max_new=3)
    assert r1.done.wait(timeout=300)
    r2 = eng.submit(prompt, max_new=3)
    assert r2.done.wait(timeout=300)
    eng.stop()
    assert eng.error is None, f"engine failed: {eng.error!r}"
    assert r1.out == r2.out
    stats = eng.kv_copy_stats()
    assert stats["admitted_hit"] >= 1
    # the hit's pages entered the block table directly: ZERO bytes copied
    assert stats["bytes_hit"] == 0
    assert stats["bytes_miss"] > 0
    assert eng.pool.stats.prefix_hits >= 1


# ----------------------------------------------------------------------------
# physical-page use-after-free tripwires
# ----------------------------------------------------------------------------


@pytest.mark.parametrize("Hkv,hd", GEOMETRIES)
def test_poison_on_unsafe_free_trips_gather(Hkv, hd):
    """A freed-then-gathered page must be a hard UseAfterFree, exactly like
    the simulated backends' FREED-state check.  The poison fill is itself
    a device op (donated ``pages.at[blocks].set``), at both cells' head
    geometries."""
    cfg = _geometry_cfg(Hkv, hd)
    pool = BlockPool(8, n_engines=2, policy=UnsafeEagerPolicy())
    store = PagedKVStore(cfg, pool.num_blocks, PAGE)
    pool.add_block_listener(store)
    blocks = pool.allocate(0, 2)
    L = len(kv_layer_order(cfg))
    store.write_prefill(blocks, np.ones((L, PAGE, Hkv, hd), np.float32),
                        np.ones((L, PAGE, Hkv, hd), np.float32))
    # engine 1 opens a reader session over the blocks -- the unsafe policy
    # frees them on retire anyway
    pool.reserve(1, blocks)
    store.assert_alive(1, blocks)              # still live: no error
    pool.retire(0, blocks)                     # unsafe: freed immediately
    assert all(store.is_poisoned(b) for b in blocks)
    with pytest.raises(UseAfterFree):
        store.assert_alive(1, blocks)
    # the page contents themselves are poisoned too (belt and braces)
    assert float(np.max(store.k[:, blocks[0]])) >= PagedKVStore.POISON


def test_safe_policy_keeps_pages_alive_under_session():
    """Under the default EpochPOP policy the same sequence must NOT free:
    the open reader session pins the retired blocks."""
    cfg = CFG_PLAIN
    pool = BlockPool(8, n_engines=2, reclaim_threshold=1, pressure_factor=1,
                     ping_timeout_s=0.2)
    store = PagedKVStore(cfg, pool.num_blocks, PAGE)
    pool.add_block_listener(store)
    pool.start_step(1)
    blocks = pool.allocate(0, 2)
    pool.reserve(1, blocks)
    pool.retire(0, blocks)
    pool.reclaim(0)
    store.assert_alive(1, blocks)              # session open: still live
    assert not any(store.is_poisoned(b) for b in blocks)
    pool.end_step(1)                           # session closes
    pool.reclaim(0)
    assert all(store.is_poisoned(b) for b in blocks)
    with pytest.raises(UseAfterFree):
        store.assert_alive(1, blocks)


@pytest.mark.parametrize("Hkv,hd", GEOMETRIES)
def test_realloc_unpoisons_and_zeroes(Hkv, hd):
    cfg = _geometry_cfg(Hkv, hd)
    pool = BlockPool(2, n_engines=1, policy=UnsafeEagerPolicy())
    store = PagedKVStore(cfg, pool.num_blocks, PAGE)
    pool.add_block_listener(store)
    blocks = pool.allocate(0, 2)
    pool.retire(0, blocks)                     # freed + poisoned
    again = pool.allocate(0, 2)                # recycled ids
    assert sorted(again) == sorted(blocks)
    store.assert_alive(0, again)               # new life: no error
    assert float(np.max(np.abs(store.k))) == 0.0   # pages zeroed


# ----------------------------------------------------------------------------
# device residency: zero h2d in steady state, in-place scatters
# ----------------------------------------------------------------------------


def test_device_steady_state_decode_moves_zero_kv_bytes():
    """The tentpole acceptance check: once a request's pages are resident,
    decode steps upload NO KV bytes (the old host path re-uploaded the
    whole pool per layer per step), and the per-layer page buffers are
    updated in place (donation), not re-materialized."""
    cfg = CFG_PLAIN
    params = init_params(cfg, jax.random.PRNGKey(6))
    prompt = [3, 1, 4, 1, 5]
    store = PagedKVStore(cfg, num_blocks=8, page_size=PAGE)
    blocks = [0, 1, 2]                 # 12 slots: 5 prompt + 4 decode fits
    k, v = prefill_kv(params, cfg, prompt)
    store.write_prefill(blocks, k, v)
    # the reference prefill's K/V come from the host, so write_prefill pays
    # h2d for them -- the steady-state claim is about what happens AFTER
    # this point
    baseline = store.bytes_h2d
    store.sync()
    ptr_before = store.layer_pages(0)[0].unsafe_buffer_pointer()
    tok, n = prompt[-1], len(prompt)
    for _ in range(4):
        logits = paged_decode_step(params, cfg, store, [blocks], [n], [tok],
                                   impl="interpret")
        tok, n = int(np.argmax(np.asarray(logits[0]))), n + 1
    store.sync()
    assert store.bytes_h2d == baseline, (
        f"steady-state decode uploaded {store.bytes_h2d - baseline} KV bytes")
    # donated scatters reuse the same device buffer: in place, O(tokens)
    assert store.layer_pages(0)[0].unsafe_buffer_pointer() == ptr_before


def test_forward_writes_pages_where_numpy_puts_them():
    """A prefill through the store, then two decode steps of the paged
    forward, which writes each fed token's K/V inside its own programs:
    the device pages equal a numpy page array this test fills itself from
    the reference prefill of the same tokens, slot by slot, and untouched
    pages stay zero."""
    cfg = CFG_FANCY
    params = init_params(cfg, jax.random.PRNGKey(7))
    prompt, fed = [2, 7, 1, 8, 2, 8, 1], [9, 4]
    blocks = [3, 0, 5]                 # 7 prompt + 2 decode positions
    store = PagedKVStore(cfg, num_blocks=6, page_size=PAGE)
    k, v = prefill_kv(params, cfg, prompt)
    store.write_prefill(blocks, k, v)
    for i, tok in enumerate(fed):
        paged_decode_step(params, cfg, store, [blocks], [len(prompt) + i],
                          [tok], impl="interpret")
    k, v = prefill_kv(params, cfg, prompt + fed)       # (L, S, Hkv, hd)
    L, S = k.shape[:2]
    want = {n: np.zeros((L, 6, PAGE, k.shape[2] * k.shape[3]), np.float32)
            for n in "kv"}
    for n, a in (("k", k), ("v", v)):
        for pos in range(S):
            want[n][:, blocks[pos // PAGE], pos % PAGE] = \
                a[:, pos].reshape(L, -1)
    np.testing.assert_allclose(np.asarray(store.k), want["k"],
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(store.v), want["v"],
                               atol=2e-5, rtol=2e-5)


def test_set_rows_takes_pages_in_any_order():
    """The page write, jitted and donated as the store runs it: tokens
    whose pages interleave (a page revisited after another) land where a
    numpy loop over the tokens puts them, and other pages are left as
    they were."""
    from repro.runtime.kv_store import _device_fns

    scatter, _ = _device_fns()
    P, page, Hkv, D = 5, 4, 2, 16
    pages = RNG.normal(size=(P, page, Hkv * D)).astype(np.float32)
    blk = np.asarray([3, 1, 3, 0, 1, 3], np.int32)
    slot = np.asarray([0, 2, 1, 3, 0, 2], np.int32)
    vals = RNG.normal(size=(6, Hkv, D)).astype(np.float32)
    want = pages.copy()
    for t in range(6):
        want[blk[t], slot[t]] = vals[t].reshape(-1)
    got = scatter(jnp.asarray(pages), jnp.asarray(blk), jnp.asarray(slot),
                  jnp.asarray(vals))
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("Hkv,hd", [(8, 160), (4, 128)])
def test_store_writes_and_fills_token_major_pages(Hkv, hd, dtype):
    """A store's prefill writes, decode appends, poison and zeroing land
    where a plain numpy model of the ``(P, page, Hkv * hd)`` layout puts
    them, in the model's dtype."""
    cfg = _geometry_cfg(Hkv, hd).scaled(dtype=dtype)
    dt = np.dtype(jnp.dtype(dtype))
    L, P, W = 2, 6, Hkv * hd
    store = PagedKVStore(cfg, num_blocks=P, page_size=PAGE)
    want = {"k": np.zeros((L, P, PAGE, W), dt),
            "v": np.zeros((L, P, PAGE, W), dt)}
    # a 6-token prefill into pages 4 then 1, all layers at once
    kv = {n: RNG.normal(size=(L, 6, Hkv, hd)).astype(dt) for n in "kv"}
    store.write_prefill([4, 1], kv["k"], kv["v"])
    for n in "kv":
        for pos in range(6):
            want[n][:, [4, 1][pos // PAGE], pos % PAGE] = \
                kv[n][:, pos].reshape(L, W)
    # one decode append of two rows into layer 1
    kv = {n: RNG.normal(size=(2, Hkv, hd)).astype(dt) for n in "kv"}
    store.append_tokens([1, 3], [2, 0], jnp.asarray(kv["k"]),
                        jnp.asarray(kv["v"]), layer=1)
    for n in "kv":
        want[n][1, 1, 2] = kv[n][0].reshape(W)
        want[n][1, 3, 0] = kv[n][1].reshape(W)
    store.on_free([3])
    store.on_alloc([4])
    for n in "kv":
        want[n][:, 3] = PagedKVStore.POISON
        want[n][:, 4] = 0.0
    assert store.k.dtype == dt
    for n, got in (("k", store.k), ("v", store.v)):
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      want[n].astype(np.float32))


@pytest.mark.parametrize("Hkv,hd", GEOMETRIES)
def test_store_counts_its_device_and_element_bytes(Hkv, hd):
    """The store puts the pool's bytes on the registry once, at
    construction; off a TPU nothing is padded."""
    from repro.obs import MetricsRegistry

    reg = MetricsRegistry()
    store = PagedKVStore(_geometry_cfg(Hkv, hd), num_blocks=8,
                         page_size=PAGE, metrics=reg)
    got = reg.counters()
    assert got["kv_pool_element_bytes"] == store.nbytes > 0
    assert got["kv_pool_device_bytes"] == store.device_bytes == store.nbytes
    L = len(store.layer_order)
    assert store.page_fetch_bytes * L * 8 == store.device_bytes


def test_engine_rejects_bad_kv_storage():
    params = init_params(CFG_PLAIN, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="kv_storage"):
        ServeEngine(CFG_PLAIN, params, kv_store="paged", kv_storage="hbm")


@pytest.mark.parametrize("kw", [{"kv_store": "dense"},
                                {"kv_storage": "host"}],
                         ids=["dense", "host"])
def test_engine_refuses_the_removed_kv_paths(kw):
    """The dense path and host page storage are gone: asking for either is
    a construction error that says so."""
    with pytest.raises(ValueError, match="removed"):
        ServeEngine(CFG_PLAIN, None, **kw)


def test_kv_copy_stats_of_a_served_run():
    """The engine's KV accounting after real traffic: every prompt's pages
    written once, no host upload, no field of a removed storage."""
    params = init_params(CFG_PLAIN, jax.random.PRNGKey(8))
    eng = _engine(CFG_PLAIN, params)
    prompts = [[1, 9, 3, 5, 2], [7, 2, 8]]
    _run(eng, prompts)
    stats = eng.kv_copy_stats()
    assert set(stats) == {"kv_store", "admitted_hit", "admitted_miss",
                          "bytes_hit", "bytes_miss", "bytes_per_hit",
                          "bytes_per_miss", "bytes_h2d"}
    assert stats["kv_store"] == "paged" and stats["bytes_h2d"] == 0
    assert stats["admitted_miss"] == 2 and stats["bytes_hit"] == 0
    assert stats["bytes_miss"] == \
        sum(map(len, prompts)) * eng.kv_store.token_bytes


# ----------------------------------------------------------------------------
# block-table raggedness edge cases
# ----------------------------------------------------------------------------


def _rand(shape):
    return jnp.asarray(RNG.normal(size=shape), jnp.float32)


def test_block_table_empty_request_row_yields_zeros():
    """A zero-length row in a ragged batch must come back as exact zeros
    (not NaN, not a mean over masked junk)."""
    P, page, H, D = 8, 4, 2, 32
    q = _rand((2, H, D))
    kp, vp = _rand((P, page, H * D)), _rand((P, page, H * D))
    table, lens = build_block_table([[], [3, 5]], [0, 6], page=page)
    out = paged_attention_pallas(q, kp, vp, table, lens, interpret=True)
    assert np.all(np.isfinite(np.asarray(out)))
    np.testing.assert_array_equal(np.asarray(out[0]), 0.0)
    assert float(np.max(np.abs(np.asarray(out[1])))) > 0.0


def test_block_table_all_empty_batch():
    table, lens = build_block_table([[], []], [0, 0], page=4)
    assert table.shape == (2, 1)               # min_pages floor
    assert np.all(np.asarray(table) == -1)
    q = _rand((2, 2, 32))
    kp = _rand((4, 4, 2 * 32))                 # (P, page, Hkv * D)
    out = paged_attention_pallas(q, kp, kp, table, lens, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), 0.0)


def test_block_table_trims_unwritten_tail_pages():
    """Pre-allocated but unwritten tail pages must be dead entries, and the
    table width is the batch max, not the allocation max."""
    table, lens = build_block_table([[7, 2, 4], [1]], [5, 1], page=4)
    assert table.shape == (2, 2)               # ceil(5/4)=2 pages max
    np.testing.assert_array_equal(np.asarray(table),
                                  [[7, 2], [1, -1]])


def test_single_token_tail_page_and_max_pages_parity():
    """Ragged batch mixing a single-token tail page with a request filling
    every table slot: kernel output matches the reference oracle."""
    from repro.kernels import ref
    P, page, H, D = 16, 4, 2, 32
    q = _rand((2, H, D))
    kp, vp = _rand((P, page, H * D)), _rand((P, page, H * D))
    blocks = [[3], [8, 9, 10, 11]]
    lens = [1, 16]                             # tail page of 1; max pages
    table, lengths = build_block_table(blocks, lens, page=page)
    got = paged_attention_pallas(q, kp, vp, table, lengths, interpret=True)
    want = ref.paged_attention_ref(q, kp, vp, table, lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


# ----------------------------------------------------------------------------
# paged serving under the SMR policies (pages recycle through the scheme)
# ----------------------------------------------------------------------------


@pytest.mark.parametrize("smr", ["EpochPOP-pool", "HazardPtrPOP", "EBR"])
def test_paged_serving_under_smr_policy(smr):
    """Real paged serving traffic with prefix sharing under a native and
    two simulated schemes: zero UseAfterFree, pages poisoned only after
    the scheme frees, pool leak-free at shutdown."""
    from repro.runtime.reclaim import make_policy

    cfg = CFG_PLAIN
    params = init_params(cfg, jax.random.PRNGKey(5))
    pool = BlockPool(32, n_engines=2, reclaim_threshold=4,
                     pressure_factor=2, policy=make_policy(smr))
    eng = ServeEngine(cfg, params, max_batch=4, page_size=PAGE,
                      pool=pool, n_engines=1, prefix_cache=True)
    eng.start()
    prompt = [5, 3, 9, 1]
    reqs = [eng.submit(prompt + [i + 1], max_new=3) for i in range(4)]
    for r in reqs:
        assert r.done.wait(timeout=300)
    eng.stop()
    assert eng.error is None, f"engine failed under {smr}: {eng.error!r}"
    pool.evict_prefixes(0)
    pool.policy.flush()
    assert pool.stats.freed > 0
    # every freed block's pages got poisoned (retire -> scheme free -> poison)
    assert eng.kv_store.poisons == pool.stats.freed
    assert pool.check_no_leaks()


def test_dispatch_span_counts_live_and_table_pages():
    """Each paged decode step's ``dispatch`` span carries the pages the
    kernel fetches -- every row's own, up to its length with the fed token
    -- and the padded table's slots, rows x the longest row's pages."""
    from repro.obs.trace import Tracer

    tr = Tracer()
    params = init_params(CFG_PLAIN, jax.random.PRNGKey(6))
    eng = _engine(CFG_PLAIN, params, trace=tr)
    # queued before the engine starts, so the first step admits all three
    reqs = [eng.submit(p, max_new=3) for p in ([1, 2], [3] * 9, [5] * 14)]
    eng.start()
    for r in reqs:
        assert r.done.wait(timeout=300)
    eng.stop()
    assert eng.error is None, f"engine failed: {eng.error!r}"
    got = [(e["args"]["live_pages"], e["args"]["table_pages"])
           for e in tr.to_dict()["traceEvents"] if e["name"] == "dispatch"]
    # stored lengths 2, 9, 14, one more each step; the kernel attends
    # len+1 positions, so pages of 4 give 1+3+4 live of 3x4 slots twice,
    # then 2+3+5 of 3x5
    assert got == [(8, 12), (8, 12), (10, 15)]


def test_kv_fetch_bytes_counts_the_kernels_page_dmas():
    """Bytes of the pages the attention calls fetch, by hand: the first
    grid step loads every operand of the block (page 0 where a row holds
    fewer), later steps only the operands whose page changes, and rows
    of a prefill chunk that hold the same pages fetch nothing anew."""
    from repro.serve.paged_model import kv_fetch_bytes

    store = PagedKVStore(CFG_PLAIN, num_blocks=40, page_size=PAGE)
    # one K + V page of one layer: 2 x 4 tokens x 2 heads x 8 x 4 B
    assert store.page_fetch_bytes == 512
    per = store.page_fetch_bytes * 2                        # two layers
    # decode: 1, 3 and 4 live pages, blocks of 4 pages, rows padded to 8:
    # [5, 0, 0, 0] (4 DMAs), then 3 and 4 changed operands
    got = kv_fetch_bytes(store, CFG_PLAIN,
                         [[5], [7, 8, 9], [10, 11, 12, 13]], [2, 9, 14])
    assert got == (4 + 3 + 4) * per
    # a 4-row chunk over one list: 8, 8, 9, 9 live pages in a block of 9:
    # 9 DMAs, then page 28 once
    got = kv_fetch_bytes(store, CFG_PLAIN, [list(range(20, 29))] * 4,
                         range(30, 34))
    assert got == (9 + 1) * per


def test_spans_carry_the_kv_bytes_they_fetch():
    """Each traced ``dispatch`` and ``prefill_chunk`` span has a
    ``kv_bytes`` arg: whole pages of every layer."""
    from repro.obs.trace import Tracer

    tr = Tracer()
    params = init_params(CFG_PLAIN, jax.random.PRNGKey(6))
    eng = _engine(CFG_PLAIN, params, trace=tr, prefill_chunk=4)
    _run(eng, [[1, 2, 3, 4, 5, 6], [3] * 9], max_new=3)
    eng.stop()
    assert eng.error is None, f"engine failed: {eng.error!r}"
    unit = eng.kv_store.page_fetch_bytes * 2
    got = {}
    for e in tr.to_dict()["traceEvents"]:
        if e["name"] in ("dispatch", "prefill_chunk"):
            got.setdefault(e["name"], []).append(e["args"]["kv_bytes"])
    assert len(got["prefill_chunk"]) == 2 + 3 and len(got["dispatch"]) >= 3
    for b in got["dispatch"] + got["prefill_chunk"]:
        assert b > 0 and b % unit == 0


def test_spans_carry_the_forward_counts():
    """Each traced ``dispatch`` and ``prefill_chunk`` span has the
    forward's ``launches`` (2L + 1) and ``uploads`` (1)."""
    from repro.obs.trace import Tracer

    tr = Tracer()
    params = init_params(CFG_FANCY, jax.random.PRNGKey(6))
    eng = _engine(CFG_FANCY, params, trace=tr, prefill_chunk=4)
    _run(eng, [[1, 2, 3, 4, 5, 6], [3] * 9], max_new=3)
    L = len(eng.kv_store.layer_order)
    got = {}
    for e in tr.to_dict()["traceEvents"]:
        if e["name"] in ("dispatch", "prefill_chunk"):
            got.setdefault(e["name"], []).append(
                (e["args"]["launches"], e["args"]["uploads"]))
    assert len(got["prefill_chunk"]) == 2 + 3 and len(got["dispatch"]) >= 3
    assert set(got["dispatch"] + got["prefill_chunk"]) == {(2 * L + 1, 1)}


def test_forward_traced_whole_leaves_nothing_traced_behind():
    """A paged decode step traced whole into one program, as a compile
    check does, and then run eagerly: the layer indices the forward keeps
    on the device stay concrete, so no tracer escapes the trace."""
    from repro.serve import paged_model

    cfg = CFG_PLAIN
    params = init_params(cfg, jax.random.PRNGKey(16))
    store = PagedKVStore(cfg, num_blocks=8, page_size=PAGE)

    class Traced:
        layer_order, page = store.layer_order, PAGE

        def __init__(self, k, v):
            self.k, self.v = list(k), list(v)

        def write_guard(self):
            return store.write_guard()

        def write_in(self, layer, tokens, program):
            self.k[layer], self.v[layer], out = program(self.k[layer],
                                                        self.v[layer])
            return out

        def layer_pages(self, li):
            return self.k[li], self.v[li]

    def step(k, v):
        return paged_decode_step(params, cfg, Traced(k, v), [[0, 1]], [5],
                                 [9], impl="interpret")

    paged_model._device_ints.cache_clear()
    pages = [store.layer_pages(li) for li in range(len(store.layer_order))]
    traced = jax.jit(step)([k for k, _ in pages], [v for _, v in pages])
    reps = paged_model._device_ints(len(store.layer_order))
    assert not any(isinstance(r, jax.core.Tracer) for r in reps)
    eager = paged_decode_step(params, cfg, store, [[0, 1]], [5], [9],
                              impl="interpret")
    np.testing.assert_allclose(np.asarray(eager), np.asarray(traced),
                               atol=1e-5, rtol=1e-5)


def test_decode_steps_in_one_bucket_share_a_kernel_program():
    """Batches of 9 and 13 requests run the paged kernel at one bucketed
    call shape (16 rows), so its jitted entry point compiles one program
    for both: set-up loads a program per bucket, not per batch shape."""
    params = init_params(CFG_PLAIN, jax.random.PRNGKey(8))
    store = PagedKVStore(CFG_PLAIN, num_blocks=80, page_size=PAGE)
    before = paged_attention_pallas._cache_size()
    for B in (9, 13):
        blocks = [list(range(5 * b, 5 * b + 5)) for b in range(B)]
        lens = [17 + b % 3 for b in range(B)]            # 5 pages each
        logits = paged_decode_step(params, CFG_PLAIN, store, blocks, lens,
                                   [1] * B, impl="interpret")
        assert logits.shape[0] == B
    assert paged_attention_pallas._cache_size() == before + 1


# ----------------------------------------------------------------------------
# config gating
# ----------------------------------------------------------------------------


def test_unsupported_config_rejected_up_front():
    bad = ArchConfig(name="bad", d_model=32, n_heads=4, n_kv_heads=2,
                     d_ff=64, vocab=64, remat="none", dtype="float32",
                     groups=dense_stack(2, attn_kind="local"))
    with pytest.raises(ValueError, match="attn_kind"):
        check_paged_support(bad)
    params = init_params(CFG_PLAIN, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="not supported"):
        ServeEngine(bad, params, kv_store="paged")
    with pytest.raises(ValueError, match="kv_store"):
        ServeEngine(CFG_PLAIN, params, kv_store="blocked")


@pytest.mark.parametrize("groups", [
    dense_stack(2, attn_kind="local"), dense_stack(2, attn_kind="mla"),
    dense_stack(2, mlp="moe")], ids=["local", "mla", "moe"])
def test_engine_refuses_configs_the_paged_path_cannot_run(groups):
    """No config is served another way: one the paged path refuses is a
    construction error, with no ``kv_store`` asked for."""
    bad = CFG_PLAIN.scaled(groups=groups)
    with pytest.raises(ValueError, match="not supported"):
        ServeEngine(bad, None)
