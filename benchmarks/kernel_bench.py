"""Kernel micro-benchmarks: wall time of the XLA reference path on CPU
(what this container can execute) + MXU-roofline projections for the Pallas
kernels on the v5e target derived from their block shapes."""

from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.launch.compile_cache import enable_compile_cache

PEAK = 197e12
HBM = 819e9


def _time(fn, *args, iters=3):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        fn(*args).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
        (out[0] if isinstance(out, tuple) else out).block_until_ready()
    return (time.perf_counter() - t0) / iters * 1e6   # us


def bench_flash(B=2, S=2048, H=8, Hkv=4, D=128):
    q = jnp.ones((B, S, H, D), jnp.bfloat16)
    k = jnp.ones((B, S, Hkv, D), jnp.bfloat16)
    v = jnp.ones((B, S, Hkv, D), jnp.bfloat16)
    f = jax.jit(lambda q, k, v: ref.flash_attention_ref(q, k, v))
    us = _time(f, q, k, v)
    flops = 2 * B * S * S / 2 * H * D * 2
    tpu_roofline_us = flops / PEAK * 1e6
    return {"name": f"flash_attention_ref B{B} S{S} H{H}", "us_per_call": us,
            "flops": flops, "v5e_roofline_us": tpu_roofline_us}


def bench_linear_scan(B=2, S=2048, H=8, K=64, Vd=64):
    q = jnp.ones((B, S, H, K), jnp.bfloat16)
    k = jnp.ones((B, S, H, K), jnp.bfloat16)
    v = jnp.ones((B, S, H, Vd), jnp.bfloat16)
    ld = -jnp.ones((B, S, H), jnp.float32) * 0.1
    f = jax.jit(lambda q, k, v, ld: ref.linear_scan_ref(q, k, v, ld)[0])
    us = _time(f, q, k, v, ld)
    chunk = 128
    flops = B * S * H * (2 * chunk * K + 2 * K * Vd + 2 * chunk * Vd)
    return {"name": f"linear_scan_ref B{B} S{S} H{H} K{K}", "us_per_call": us,
            "flops": flops, "v5e_roofline_us": flops / PEAK * 1e6}


def bench_paged(B=8, P=512, page=16, Hkv=8, D=128, max_pages=64):
    q = jnp.ones((B, Hkv * 2, D), jnp.bfloat16)
    kp = jnp.ones((Hkv, P, page, D), jnp.bfloat16)
    vp = jnp.ones((Hkv, P, page, D), jnp.bfloat16)
    bt = jnp.tile(jnp.arange(max_pages, dtype=jnp.int32)[None], (B, 1))
    lens = jnp.full((B,), page * max_pages, jnp.int32)
    f = jax.jit(lambda q, kp, vp, bt, l: ref.paged_attention_ref(q, kp, vp, bt, l))
    us = _time(f, q, kp, vp, bt, lens)
    bytes_moved = B * max_pages * page * Hkv * D * 2 * 2
    return {"name": f"paged_attention_ref B{B} kv{page*max_pages}",
            "us_per_call": us, "bytes": bytes_moved,
            "v5e_roofline_us": bytes_moved / HBM * 1e6}


def bench_page_scatter(P=256, page=16, Hkv=8, D=128, B=8, layers=4,
                       chunk=64):
    """The write half of the paged KV path: one decode step's batched
    ``append_tokens`` (B tokens written into B pages per layer), one
    ``chunk``-token ``write_prefill``, and the ``layer_pages`` reads a
    forward makes, through the real :class:`PagedKVStore` lifecycle.  The
    writes are in-place donated scatters (O(tokens) moved) and the reads
    hand back the resident arrays (nothing moved)."""
    import numpy as np

    from repro.configs.base import ArchConfig, dense_stack
    from repro.runtime.kv_store import PagedKVStore

    cfg = ArchConfig(name="scatter-bench", d_model=Hkv * D, n_heads=Hkv,
                     n_kv_heads=Hkv, d_ff=2 * Hkv * D, vocab=256,
                     groups=dense_stack(layers), remat="none",
                     dtype="bfloat16")
    rng = np.random.default_rng(0)
    k_tok = jnp.asarray(rng.normal(size=(B, Hkv, D)), jnp.bfloat16)
    v_tok = jnp.asarray(rng.normal(size=(B, Hkv, D)), jnp.bfloat16)
    n_blk = -(-chunk // page)
    k_chunk = jnp.asarray(
        rng.normal(size=(layers, chunk, Hkv, D)), jnp.bfloat16)
    v_chunk = jnp.asarray(
        rng.normal(size=(layers, chunk, Hkv, D)), jnp.bfloat16)
    store = PagedKVStore(cfg, num_blocks=P, page_size=page)
    blk = [int(x) for x in rng.choice(P, B, replace=False)]
    slot = [int(x) for x in rng.integers(0, page, B)]

    def append_step():
        for li in range(layers):
            store.append_tokens(blk, slot, k_tok, v_tok, layer=li)
        store.sync()

    def prefill_chunk():
        store.write_prefill(list(range(n_blk)), k_chunk, v_chunk)
        store.sync()

    def read_layers():
        for li in range(layers):
            kp, vp = store.layer_pages(li)
        kp.block_until_ready()

    rows = []
    for op, fn, moved in (
            ("append_tokens", append_step, 2 * layers * B * Hkv * D * 2),
            ("write_prefill", prefill_chunk,
             2 * layers * chunk * Hkv * D * 2),
            ("layer_pages", read_layers, 0)):
        fn()                                   # warmup (jit trace)
        t0 = time.perf_counter()
        iters = 5
        for _ in range(iters):
            fn()
        us = (time.perf_counter() - t0) / iters * 1e6
        rows.append({
            "name": f"page_scatter:{op} P{P} page{page} L{layers}",
            "us_per_call": us, "bytes": moved,
            "v5e_roofline_us": moved / HBM * 1e6})
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="results/kernel_bench.json")
    args = ap.parse_args()
    enable_compile_cache()
    rows = [bench_flash(), bench_linear_scan(), bench_paged()]
    rows += bench_page_scatter()
    for r in rows:
        print(f"{r['name']:40s} {r['us_per_call']:12.1f}us "
              f"(v5e roofline {r['v5e_roofline_us']:.1f}us)")
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps(rows, indent=1))
    return rows


if __name__ == "__main__":
    main()
