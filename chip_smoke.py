#!/usr/bin/env python3
"""Chip smoke: serve starcoder2-7b at full width on one TPU through the
paged path.

    python3 chip_smoke.py        # needs a TPU; exits non-zero anywhere else

The model is starcoder2-7b at its published widths (d=4608, 36 heads, 4 KV
heads, head_dim 128, d_ff 18432, vocab 49152, bf16) with depth cut from 32
to 16 layers -- every layer has the same kind, so the cut keeps whole
periods -- and random weights drawn from a seed.  Eight requests of 128-512
prompt tokens, two of them sharing a 20-page prefix, each generate 16
tokens through the serving main path::

    ServeEngine -> Scheduler -> EngineWorker -> BlockPool (native EpochPOP)
      -> device-resident PagedKVStore -> compiled Pallas paged attention

It fails (non-zero exit, no JSON line) unless the platform is ``tpu``, the
engine runs the ``pallas`` kernel, every request completes, the engine
reports no error (a use-after-free is one), the pool is leak-free, the
first-step logits of one request agree with a plain ``apply_model``
forward at the same dtype, and every generated token of every request is
that forward's top-1 or a near tie (see :data:`LOGIT_TOL`).  The
reference feeds the last prompt token twice, as the serving path does
today, so it checks the paged path against the plain model, not that
habit.  Its timings are the readings of one
smoke run, not benchmark results.  The last line of stdout is::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

:func:`run_smoke` is the whole flow for any config and kernel mode, so a
CPU test drives it at a tiny size in interpret mode.
"""

from __future__ import annotations

import json
import sys
import time
from collections import Counter
from pathlib import Path
from typing import Callable, Dict, List, Sequence

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

LAYERS = 16                      # of starcoder2-7b's 32
PAGE = 16
NUM_PAGES = 4096                 # 64k tokens of KV: 2 GiB at 16 layers
# request 2 opens with request 1's whole prompt: 320 tokens, 20 pages
PROMPT_LENS = (512, 320, 400, 128, 256, 448, 192, 384)
MAX_NEW = 16
PREFILL_CHUNK = 64
SEED = 0

#: max |paged - reference| over the first-step logits, as a fraction of the
#: reference's largest |logit|.  Both sides run in bf16 and round at
#: different points (per-layer host loop and f32 kernel accumulation
#: against a scanned full-sequence forward), so exact equality is not the
#: contract; a layout or indexing fault moves the logits by O(1) of that
#: scale.  Top-1 must agree unless the reference's own top-1/top-2 margin
#: is within the measured difference.
LOGIT_TOL = 0.05


def smoke_config(layers: int = LAYERS):
    """starcoder2-7b at published widths, depth cut to ``layers``."""
    from repro.configs.base import dense_stack
    from repro.configs.registry import get_config

    return get_config("starcoder2_7b").scaled(groups=dense_stack(layers))


def make_prompts(vocab: int, lens: Sequence[int], page: int,
                 seed: int) -> List[List[int]]:
    """Seeded random prompts.  Request 2 opens with request 1's
    page-aligned prompt prefix -- the prefix the cache publishes once
    request 1 is prefilled -- so its admission is a prefix hit."""
    import numpy as np

    rng = np.random.default_rng(seed)
    prompts = [[int(t) for t in rng.integers(1, vocab, n)] for n in lens]
    shared = len(prompts[1]) // page * page
    if not 0 < shared < len(prompts[2]):
        raise ValueError("request 2 must be longer than request 1's "
                         "page-aligned prompt, which must be non-empty")
    prompts[2][:shared] = prompts[1][:shared]
    return prompts


class CompileLog:
    """XLA compiles (or persistent-cache reads) seen while active, from
    JAX's monitoring events: count and seconds per function name, and the
    persistent cache's hits and misses."""

    def __init__(self):
        self.seconds: Counter = Counter()
        self.count: Counter = Counter()
        self.hits = 0
        self.misses = 0

    def _duration(self, event, secs, fun_name="?", **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds[fun_name] += secs
            self.count[fun_name] += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)


def _first_step_logits(params, cfg, prompt, *, page, chunk, impl):
    """The logits a worker's first decode step computes for ``prompt``:
    the chunked prefill, then one paged decode step that feeds the last
    prompt token again (the serving path does so today), on a private page
    store."""
    import jax.numpy as jnp

    from repro.runtime.kv_store import PagedKVStore
    from repro.serve.paged_model import paged_decode_step, prefill_kv_chunked

    n = len(prompt)
    blocks = list(range(-(-(n + 1) // page)))
    store = PagedKVStore(cfg, len(blocks), page)
    for _ in prefill_kv_chunked(params, cfg, store, blocks, prompt, chunk,
                                impl=impl):
        pass
    logits = paged_decode_step(params, cfg, store, [blocks], [n],
                               [prompt[-1]], impl=impl)
    return jnp.asarray(logits[0], jnp.float32)


def _reference_logits(params, cfg, prompts, outputs):
    """Teacher-forced reference for every generated token: one plain
    full-sequence ``apply_model`` forward over each request's
    ``prompt + [prompt[-1]] + out[:-1]`` -- the tokens its decode steps fed
    -- returning ``(B, max_new, vocab)`` float32 logits, row ``j`` being
    the logits that chose ``out[j]``.  Rows are zero-padded to one length
    (the causal mask keeps the padding out of earlier positions), so the
    batch is one compile."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models.model import apply_model

    n_new = len(outputs[0])
    seqs = [p + p[-1:] + o[:-1] for p, o in zip(prompts, outputs)]
    width = max(len(s) for s in seqs)
    toks = np.zeros((len(seqs), width), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    pos = np.asarray([[len(p) + j for j in range(n_new)] for p in prompts],
                     np.int32)

    @jax.jit
    def fwd(params, toks, pos):
        logits = apply_model(params, toks, cfg=cfg, mode="train")[0]
        picked = jnp.take_along_axis(logits, pos[:, :, None], axis=1)
        return picked[..., :cfg.vocab].astype(jnp.float32)

    return np.asarray(fwd(params, jnp.asarray(toks), jnp.asarray(pos)))


def _check_against_reference(params, cfg, prompts, out, *, page, chunk, impl,
                             log) -> None:
    """Hold the paged path to the plain forward at the same weights and
    dtype, appending to ``out["failures"]``:

    * request 0's first-step logits, recomputed through the paged path on a
      private page store, within :data:`LOGIT_TOL`;
    * every token the engine generated, in every batch row and step: it
      is the reference's top-1, or the reference ranks it within the
      logit tolerance of its top-1 (a near tie that rounding may flip).
      A wrong row or step picks a token far down the reference's ranking.
    """
    import numpy as np

    failures = out["failures"]
    ref = _reference_logits(params, cfg, prompts, out["outputs"])
    got = np.asarray(_first_step_logits(params, cfg, prompts[0], page=page,
                                        chunk=chunk, impl=impl)[:cfg.vocab])
    r0 = ref[0, 0]
    diff = float(np.max(np.abs(got - r0)))
    scale = float(np.max(np.abs(r0)))
    top2 = np.sort(r0)[-2:]
    margin = float(top2[1] - top2[0])
    top1 = int(np.argmax(r0)) == int(np.argmax(got))
    out.update(max_abs_diff=diff, ref_max_abs=scale, top1_agree=top1,
               top1_margin=margin)
    log(f"logits vs apply_model ({cfg.dtype}, request 0 first step, "
        f"{len(prompts[0])}-token prompt): max|d| {diff} of max|ref| "
        f"{scale} (tolerance {LOGIT_TOL} x max|ref|) | top-1 agree: {top1} "
        f"(reference top-1/top-2 margin {margin})")
    if not np.all(np.isfinite(got)) or diff > LOGIT_TOL * scale:
        failures.append(f"logits off: max|d| {diff} > {LOGIT_TOL} x {scale}")
    if not top1 and margin > diff:
        failures.append("top-1 disagrees beyond the near-tie margin")

    toks = np.asarray(out["outputs"])                       # (B, max_new)
    chosen = np.take_along_axis(ref, toks[..., None], axis=-1)[..., 0]
    lead = ref.max(axis=-1) - chosen          # the reference's lead over it
    allow = LOGIT_TOL * np.abs(ref).max(axis=-1)
    exact = int(np.sum(lead == 0))
    bad = np.argwhere(lead > allow)
    out.update(tokens_top1=exact, tokens_checked=int(toks.size),
               tokens_max_lead=float(lead.max()))
    log(f"engine tokens vs apply_model teacher-forced: {exact}/{toks.size} "
        f"are the reference's top-1, largest reference lead over an "
        f"engine token {float(lead.max())} | outside tolerance: {len(bad)}")
    for b, j in bad[:4]:
        failures.append(f"request {b} step {j}: token {toks[b, j]} trails "
                        f"the reference's top-1 by {lead[b, j]} > "
                        f"{allow[b, j]}")
    if len(bad) > 4:
        failures.append(f"... and {len(bad) - 4} more tokens off")


def run_smoke(cfg, impl: str, *, prompt_lens: Sequence[int] = PROMPT_LENS,
              page: int = PAGE,
              num_pages: int = NUM_PAGES, prefill_chunk: int = PREFILL_CHUNK,
              max_new: int = MAX_NEW, seed: int = SEED,
              log: Callable[[str], None] = print) -> Dict:
    """Serve ``prompt_lens`` requests of ``cfg`` through the paged engine,
    which must pick kernel mode ``impl`` on this backend, and check the
    result.  Returns a dict whose ``failures`` lists every check that
    failed (empty means pass)."""
    import jax

    from repro.models.model import init_params
    from repro.serve.engine import ServeEngine

    failures: List[str] = []
    out: Dict = {"failures": failures}
    with CompileLog() as comp:
        t0 = time.perf_counter()
        params = init_params(cfg, jax.random.PRNGKey(seed))
        jax.block_until_ready(params)
        out["weight_bytes"] = sum(a.nbytes for a in jax.tree.leaves(params))
        log(f"weights: {out['weight_bytes']} bytes ({cfg.dtype}, seed {seed}), "
            f"drawn in {time.perf_counter() - t0:.1f} s")

        prompts = make_prompts(cfg.vocab, prompt_lens, page, seed)
        # the engine picks its kernel mode itself; the smoke holds it to
        # the one it expects
        eng = ServeEngine(cfg, params, prefix_cache=True,
                          max_batch=len(prompts), page_size=page,
                          num_pages=num_pages, prefill_chunk=prefill_chunk)
        out["kernel_impl"] = eng.kernel_impl
        out["kv_bytes"] = eng.kv_store.nbytes
        log(f"KV pool: {num_pages} pages x {page} tokens, "
            f"{out['kv_bytes']} bytes on the device")
        if eng.kernel_impl != impl:
            failures.append(f"engine runs kernel {eng.kernel_impl!r}, "
                            f"not {impl!r}")

        t0 = time.perf_counter()
        eng.start()
        reqs = [eng.submit(p, max_new=max_new) for p in prompts]
        finished = [r.done.wait(timeout=900) for r in reqs]
        eng.stop()
        serve_s = time.perf_counter() - t0
        completed = sum(f and len(r.out) == max_new
                        for f, r in zip(finished, reqs))
        out["completed"] = completed
        out["outputs"] = [list(r.out) for r in reqs]
        pool = eng.pool
        s = pool.stats
        log(f"requests: {completed}/{len(reqs)} completed "
            f"({max_new} tokens each, prompts {min(prompt_lens)}-"
            f"{max(prompt_lens)}) in {serve_s:.1f} s | kernel impl: "
            f"{eng.kernel_impl} | prefix hits: {s.prefix_hits}")
        if completed != len(reqs):
            failures.append(f"{len(reqs) - completed} requests incomplete")
        if s.prefix_hits < 1:
            failures.append("the shared prefix was never hit")

        from repro.core.sim.engine import UseAfterFree

        err = eng.error
        out["uaf"] = int(isinstance(err, UseAfterFree))
        if err is not None:
            failures.append(f"engine error: {type(err).__name__}: {err}")
        pool.evict_prefixes(0)
        pool.policy.flush()
        out["no_leaks"] = pool.check_no_leaks()
        log(f"use-after-free: {out['uaf']} | pages freed {s.freed}, "
            f"poisoned {eng.kv_store.poisons} | no leaks: {out['no_leaks']}")
        if not out["no_leaks"]:
            failures.append("block pool leaked")
        lat = eng.latency_summary(fields=("p50",))
        out["decode_step_p50_s"] = lat.get("tok_latency_p50_s")
        log(f"steady decode: {eng.steps} steps, step time p50 "
            f"{out['decode_step_p50_s']} s (inter-token gap, batch <= "
            f"{len(reqs)})")

        if completed == len(reqs):
            _check_against_reference(params, cfg, prompts, out, page=page,
                                     chunk=prefill_chunk, impl=impl, log=log)

    kernel = {n: c for n, c in comp.count.items() if "paged" in n}
    out.update(compile_s=sum(comp.seconds.values()),
               compiles=sum(comp.count.values()), cache_hits=comp.hits,
               cache_misses=comp.misses, kernel_compiles=kernel)
    log(f"compile: {out['compiles']} XLA compiles or cache reads, "
        f"{out['compile_s']:.1f} s | persistent cache hits {comp.hits}, "
        f"misses {comp.misses} | paged kernel programs: {kernel}")
    return out


def main() -> int:
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip smoke needs a TPU; JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    cfg = smoke_config()
    print(f"chip smoke (not a benchmark): {cfg.name} at published widths, "
          f"layers {LAYERS} of 32 (depth cut only) | device "
          f"{dev.device_kind} x{len(devices)} | compile cache {cache}")
    res = run_smoke(cfg, "pallas")
    stats = dev.memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        print(f"device memory: peak {stats['peak_bytes_in_use']} of "
              f"{stats.get('bytes_limit')} bytes")
    if res["failures"]:
        for f in res["failures"]:
            print(f"FAILED: {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
