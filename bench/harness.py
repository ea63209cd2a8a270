"""One run of one cell: build the served path, warm it up with the cell's
own traffic, measure a window, check what the window produced against the
plain reference, and reduce everything to the cell's metrics.

The entry the window drives is the served path::

    ServeEngine.submit -> Scheduler -> EngineWorker -> BlockPool (native
    EpochPOP) -> device PagedKVStore -> serve/paged_model.py paged forward
    -> compiled Pallas paged_attention

Times are ``time.monotonic()`` seconds.  The benchmark times every
output token itself: a thread of its own watches each request's output
grow and stamps each new token when it sees it, so the end-to-end metrics
rest on the benchmark's clock and not on what the program records.  A
request's latency counts from when it was due, not from when it was
submitted, so a late generator cannot hide a stall.  With ``trace`` the program's tracer is on, and the
JAX profiler covers a few seconds in the middle of the window; the
per-layer metrics are read from that run, the end-to-end ones from a run
with both off.
"""

from __future__ import annotations

import gc
import random
import shutil
import tempfile
import threading
import time
from collections import Counter
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional

from bench import devtrace, reference, traffic, weights
from bench.recorder import CompileLog, RecordingRegistry
from bench.registry import Cell, load_peaks, load_reader
from bench.stats import percentile

__all__ = ["NoChip", "Run", "run_cell"]

#: seconds past the window's close that a due request may still finish in
GRACE_S = 60.0
#: longest profiled stretch of a traced run (seconds)
PROFILE_S = 5.0
#: longest wait for the requests that warm every prompt length (seconds)
WARM_TIMEOUT_S = 300.0
#: how often the observer looks at the requests' outputs (seconds)
OBSERVE_S = 0.002


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclass
class Tracked:
    """A request the benchmark sent: what it asked, when it was due and
    sent, and the program's request object."""

    req: traffic.Req
    phase: str
    due: float
    sent: float = 0.0
    r: object = None
    tok_t: List[float] = field(default_factory=list)   # each token, as seen

    @property
    def done(self) -> bool:
        return self.r is not None and self.r.done.is_set()

    @property
    def finished(self) -> bool:
        return self.done and len(self.r.out) >= self.req.max_new


@dataclass
class Run:
    """Everything the metric readers see of one run."""

    cell: Cell
    seed: int
    seconds: float
    t0: float = 0.0                      # window open (monotonic)
    t1: float = 0.0                      # window close
    setup_s: float = 0.0
    loop: str = "open"
    tracked: List[Tracked] = field(default_factory=list)
    samples: Optional[RecordingRegistry] = None
    compiles: Optional[CompileLog] = None
    pool_open: Dict = field(default_factory=dict)
    pool_close: Dict = field(default_factory=dict)
    steps_open: int = 0
    steps_close: int = 0
    pool_metrics: object = None
    spans: List[Dict] = field(default_factory=list)   # tracer X events
    span_t0: float = 0.0                 # monotonic time of tracer ts 0
    device: Optional[devtrace.DeviceTrace] = None
    mono_to_ns: float = 0.0              # trace ns = mono * 1e9 + this
    profile_work: Dict = field(default_factory=dict)
    peaks: Optional[Dict] = None

    @property
    def model(self) -> Dict:
        return self.cell.model

    @property
    def slo(self) -> Dict:
        return self.cell.workload["slo"]

    def window_requests(self) -> List[Tracked]:
        """The requests the window is judged by.  Open loop: those due in
        it.  Closed loop: those that produced a token in it, whenever they
        were sent; a request still running at the close is checked on the
        tokens it had produced (a closed-loop request has no due time)."""
        if self.loop == "open":
            return [t for t in self.tracked if t.phase == "window"]
        return [t for t in self.tracked if t.tok_t and t.tok_t[-1] >= self.t0]

    def failed(self) -> List[Tracked]:
        """Window requests that never produced all their tokens: open loop,
        by the end of the grace after the close; closed loop, those the
        engine ended early before the close."""
        if self.loop == "open":
            return [t for t in self.window_requests() if not t.finished]
        return [t for t in self.window_requests()
                if t.done and not t.finished and t.tok_t[-1] < self.t1]

    def in_window(self, name: str) -> List[float]:
        """The program's raw samples ``name`` recorded in the window."""
        return self.samples.between(name, self.t0, self.t1)

    def window_tokens(self) -> int:
        """Output tokens the benchmark saw arrive in the window."""
        return sum(1 for t in self.tracked for x in t.tok_t
                   if self.t0 <= x < self.t1)

    def window_gaps(self) -> List[float]:
        """Every gap between two output tokens of one request, as the
        benchmark saw them, whose later token arrived in the window."""
        return [b - a for t in self.tracked
                for a, b in zip(t.tok_t, t.tok_t[1:])
                if self.t0 <= b < self.t1]

    def spans_named(self, name: str) -> List[Dict]:
        """Tracer spans ``name`` that start in the window, each with
        ``t`` (monotonic start) and ``dur_s``."""
        out = []
        for ev in self.spans:
            if ev.get("name") != name:
                continue
            t = self.span_t0 + ev["ts"] / 1e6
            if self.t0 <= t < self.t1:
                out.append({"t": t, "dur_s": ev["dur"] / 1e6,
                            "args": ev.get("args", {})})
        return out


# --------------------------------------------------------------------------
# the served path
# --------------------------------------------------------------------------


def program_config(cell: Cell):
    """The program's configuration of the cell's model, depth cut to the
    file's layer count and with the file's rope base and norm epsilon;
    refuses one whose sizes differ from the file's."""
    from repro.configs.base import dense_stack
    from repro.configs.registry import get_config, get_smoke_config

    m = cell.model
    get = get_smoke_config if cell.config.get("smoke") else get_config
    cfg = get(cell.config["registry"]).scaled(
        groups=dense_stack(m["layers"]), rope_theta=m["rope_theta"],
        norm_eps=m["norm_eps"])
    have = {"layers": cfg.n_layers, "d_model": cfg.d_model,
            "n_heads": cfg.n_heads, "n_kv_heads": cfg.n_kv_heads,
            "head_dim": cfg.head_dim_, "d_ff": cfg.d_ff, "vocab": cfg.vocab,
            "act": cfg.act, "qk_norm": cfg.qk_norm, "rope_pct": cfg.rope_pct,
            "rope_theta": cfg.rope_theta, "norm_eps": cfg.norm_eps,
            "dtype": cfg.dtype}
    bad = {k: (v, m[k]) for k, v in have.items() if v != m[k]}
    if bad or cfg.tie_embeddings or cfg.post_norms or cfg.attn_softcap \
            or cfg.logit_softcap or cfg.embed_scale:
        raise ValueError(f"{cell.config['name']}: the program's "
                         f"{cell.config['registry']} differs from the "
                         f"file's model group (program, file): {bad}")
    return cfg


def build_engine(cfg, params, engine: Dict, *, tracer=None, metrics=None):
    from repro.serve.engine import ServeEngine

    return ServeEngine(
        cfg, params, kv_store="paged", kv_storage=engine["kv_storage"],
        prefix_cache=engine["prefix_cache"], smr=engine["smr"],
        max_batch=engine["max_batch"], page_size=engine["page_size"],
        num_pages=engine["num_pages"], prefill_chunk=engine["prefill_chunk"],
        prefill_workers=engine["prefill_workers"], trace=tracer,
        metrics=metrics)


# --------------------------------------------------------------------------
# load
# --------------------------------------------------------------------------


def _sleep_until(t: float) -> None:
    d = t - time.monotonic()
    if d > 0:
        time.sleep(d)


def _send(eng, tr: Tracked) -> Tracked:
    tr.sent = time.monotonic()
    tr.r = eng.submit(tr.req.prompt, max_new=tr.req.max_new)
    return tr


class _PageFills:
    """A pool listener that keeps the page count of every allocation and
    free (each one page-fill program shape) with its time."""

    def __init__(self) -> None:
        self.events: List[tuple] = []

    def on_alloc(self, blocks) -> None:
        self.events.append((time.monotonic(), len(blocks)))

    def on_free(self, blocks) -> None:
        self.events.append((time.monotonic(), len(blocks)))

    def summary(self, t0: float, t1: float) -> str:
        before = {n for t, n in self.events if t < t0}
        inside = sorted({n for t, n in self.events if t0 <= t < t1})
        return (f"page fills in the window: {len(inside)} sizes "
                f"({inside[:1]}..{inside[-1:]}), "
                f"{len([n for n in inside if n not in before])} not seen "
                f"before it")


def _warm_shapes(eng, run: Run) -> None:
    """Before the engine starts: run the program's decode step once for
    every (rows, table width) in the workload's ``warm_decode`` ranges, and
    its page fill for every page count in ``warm_fill_pages``, so that every
    program shape the window's decode steps and page recycling reach is
    compiled, or read from the compile cache, in set-up.  The steps write
    into pages no request holds yet; a page is zeroed when it is allocated.
    """
    import jax.numpy as jnp
    import numpy as np

    from repro.serve.paged_model import paged_decode_step

    wl, store = run.cell.workload, eng.kv_store
    lo, hi = wl.get("warm_fill_pages", (1, 0))
    for n in range(int(lo), int(hi) + 1):
        store.on_alloc(range(n))
    dec = wl.get("warm_decode")
    if not dec:
        return
    page = store.page
    for b in range(dec["rows"][0], dec["rows"][1] + 1):
        for w in range(dec["pages"][0], dec["pages"][1] + 1):
            # row 0 spans ``w`` pages, the others one: the table is (b, w)
            logits = paged_decode_step(
                eng.params, eng.cfg, store, [list(range(w))] * b,
                [page * w - 1] + [0] * (b - 1), [1] * b,
                impl=eng.kernel_impl)
            np.asarray(jnp.argmax(logits, axis=-1))


def _observe(run: Run, stop: threading.Event) -> None:
    """Stamp each output token of every sent request when it is first seen,
    looking every ``OBSERVE_S`` until ``stop``, and once more after it."""
    active: List[Tracked] = []
    n_seen = 0
    while True:
        last = stop.is_set()
        new = run.tracked[n_seen:]
        n_seen += len(new)
        active.extend(new)
        now = time.monotonic()
        keep = []
        for t in active:
            done = t.r.done.is_set()        # before the count: then it is final
            n = len(t.r.out)
            if n > len(t.tok_t):
                t.tok_t.extend([now] * (n - len(t.tok_t)))
            if not done:
                keep.append(t)
        active = keep
        if last:
            return
        time.sleep(OBSERVE_S)


def _warm_every_length(eng, run: Run, seed: int, vocab: int) -> None:
    """Send, at once, one request of every prompt length the mix can draw
    (``lo``..``hi`` of its ``prompt_len``), each answering one token, and
    wait for all: every prefill chunk shape of the cell's traffic is then
    compiled, or read from the compile cache, before the window."""
    import numpy as np

    mix = run.cell.traffic
    spec = mix["prompt_len"]
    rng = np.random.default_rng([seed % (1 << 64), 4])
    prefixes = traffic.tenant_prefixes(mix, vocab, seed)
    sent = []
    for i, n in enumerate(range(int(spec["lo"]), int(spec["hi"]) + 1)):
        pre = prefixes[i % len(prefixes)] if prefixes else []
        q = traffic.Req(0.0, -1, len(pre), list(pre) +
                        rng.integers(1, vocab, n).tolist(), 1)
        sent.append(_send(eng, Tracked(q, "warmup", time.monotonic())))
    run.tracked.extend(sent)
    for t in sent:
        t.r.done.wait(WARM_TIMEOUT_S)


def _drive_open(eng, run: Run, seed: int, vocab: int, warmup_s: float,
                t_base: float, at_open: Callable[[], None]) -> None:
    """Replay the warm-up schedule, open the window at ``t_base +
    warmup_s``, replay the window's schedule, and wait for its close."""
    mix, rate = run.cell.traffic, run.cell.workload["rate_rps"]
    prefixes = traffic.tenant_prefixes(mix, vocab, seed)
    warm = traffic.open_schedule(mix, rate, warmup_s, seed, vocab, "warmup",
                                 prefixes)
    win = traffic.open_schedule(mix, rate, run.seconds, seed, vocab,
                                "window", prefixes)
    # each tenant's shared prefix is first sent once on its own, so the
    # prefix cache holds it (system prompts are cached ahead of traffic)
    prime = [traffic.Req(0.0, i, len(p), list(p), 1)
             for i, p in enumerate(prefixes)]
    plan = ([Tracked(q, "warmup", t_base) for q in prime]
            + [Tracked(q, "warmup", t_base + q.t_s) for q in warm])
    t_open = t_base + warmup_s
    plan += [Tracked(q, "window", t_open + q.t_s) for q in win]
    opened = False
    for tr in plan:
        if not opened and tr.phase == "window":
            _sleep_until(t_open)
            at_open()
            opened = True
        _sleep_until(tr.due)
        run.tracked.append(_send(eng, tr))
    if not opened:
        _sleep_until(t_open)
        at_open()
    _sleep_until(run.t1)


def _drive_closed(eng, run: Run, seed: int, vocab: int, warmup_s: float,
                  t_base: float, at_open: Callable[[], None]) -> None:
    """``clients`` callers, all started with the warm-up; each sends its
    next request when the last completes, until the window closes.  Clients
    take the mix's requests in turn, so their answers, of different
    lengths, end at different times and the batch stays full without a
    ramp whose small batches would each be a program of their own."""
    mix = run.cell.traffic
    n_cli = int(mix["clients"])
    reqs = traffic.closed_requests(mix, seed, vocab)
    nxt = 0
    current: List[Optional[Tracked]] = [None] * n_cli
    t_open = t_base + warmup_s
    opened = False
    while True:
        now = time.monotonic()
        if not opened and now >= t_open:
            at_open()
            opened = True
        if now >= t_open + run.seconds:
            return
        phase = "window" if opened else "warmup"
        for i in range(n_cli):
            cur = current[i]
            if cur is not None and not cur.done:
                continue
            q = reqs[nxt % len(reqs)]
            nxt += 1
            current[i] = _send(eng, Tracked(q, phase, now))
            run.tracked.append(current[i])
        time.sleep(0.002)


# --------------------------------------------------------------------------
# profiling (traced runs)
# --------------------------------------------------------------------------


def _progress(run: Run) -> Dict[int, tuple]:
    """Per sent request: (prefilled, outputs) now -- the request-level
    facts the work in the profiled window is computed from."""
    return {id(t): (t.r.prefilled, len(t.r.out), bool(t.r.t_admitted))
            for t in list(run.tracked) if t.r is not None}


def _profile(run: Run, t_start: float, t_stop: float) -> None:
    """Profile ``[t_start, t_stop]`` from a thread of its own, so the
    generator never waits on the profiler; the trace is read, then its
    directory (under ``TMPDIR``) removed."""
    import jax

    out_dir = tempfile.mkdtemp(prefix="bench-profile-")
    _sleep_until(t_start)
    jax.profiler.start_trace(out_dir)
    try:
        with jax.profiler.TraceAnnotation(devtrace.WINDOW):
            t_ann = time.monotonic()
            run.profile_work["before"] = _progress(run)
            _sleep_until(t_stop)
            run.profile_work["after"] = _progress(run)
            run.profile_work["t"] = (t_ann, time.monotonic())
    finally:
        jax.profiler.stop_trace()
    path = next(Path(out_dir).rglob("*.xplane.pb"), None)
    run.device = devtrace.load(str(path)) if path else None
    shutil.rmtree(out_dir, ignore_errors=True)
    if run.device is not None:
        run.mono_to_ns = run.device.t0_ns - t_ann * 1e9


def profile_calls(run: Run) -> Dict[str, list]:
    """The paged-attention work of the profiled window, from each request's
    progress at its start and stop: one ``(FLOPs, bytes)`` per layer call
    share (a decode row, a prefill chunk), and the tokens computed with
    their contexts."""
    from bench import work

    m = run.model
    before, after = run.profile_work["before"], run.profile_work["after"]
    chunk = run.cell.engine["prefill_chunk"]
    calls, decode_ctx, prefill_pos = [], [], []
    for t in run.tracked:
        if id(t) not in after:
            continue
        p0, o0, adm0 = before.get(id(t), (0, 0, False))
        p1, o1, _ = after[id(t)]
        n = len(t.req.prompt)
        hit = t.req.shared if getattr(t.r, "cache_hit", False) else 0
        lo = p0 if adm0 else hit
        # chunks run from the admission's prefix hit in steps of ``chunk``
        s = lo
        while s < p1:
            e = min(s + chunk - (s - hit) % chunk, n)
            calls.append(work.prefill_chunk(m, s, e))
            prefill_pos.extend(range(s, e))
            s = e
        for j in range(o0, o1):
            ctx = n + j + 1
            calls.append(work.decode_row(m, ctx))
            decode_ctx.append(ctx)
    return {"calls": calls, "decode_ctx": decode_ctx,
            "prefill_pos": prefill_pos}


def _breakdown(run: Run) -> Dict:
    """Top device modules, and idle time by the program span the host was
    in at each gap's middle."""
    dt = run.device
    by_label: Dict[str, float] = {}
    lo, hi = run.profile_work["t"]
    spans = [(t, d, n) for t, d, n in
             ((run.span_t0 + e["ts"] / 1e6, e["dur"] / 1e6, e["name"])
              for e in run.spans) if t < hi and t + d > lo]
    for a, b in dt.idle_gaps():
        mid = ((a + b) / 2 - run.mono_to_ns) / 1e9
        inside = [(d, n) for t, d, n in spans if t <= mid < t + d]
        label = min(inside)[1] if inside else "no program span"
        by_label[label] = by_label.get(label, 0.0) + (b - a) / 1e9
    gaps = sorted(by_label.items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[n, s] for n, s in dt.top_modules(10)],
            "idle_gaps": [[n, s] for n, s in gaps]}


# --------------------------------------------------------------------------
# correctness
# --------------------------------------------------------------------------


def choose_sample(run: Run, seed: int, budget: int) -> List[Tracked]:
    """Window requests to check (open loop: the finished ones): the one
    with the most served tokens, a prefix-cache hit where there is one,
    then others in an order drawn from the seed until ``budget`` served
    tokens are covered."""
    done = [t for t in run.window_requests()
            if t.finished or (run.loop == "closed" and t.r.out)]
    if not done:
        return []
    longest = max(done, key=lambda t: len(t.r.out))
    rest = [t for t in done if t is not longest]
    random.Random(f"{seed}:sample").shuffle(rest)
    hits = [t for t in rest if t.r.cache_hit]
    order = [longest] + hits[:1] + [t for t in rest if t not in hits[:1]]
    out, tokens = [], 0
    for t in order:
        if tokens >= budget:
            break
        out.append(t)
        tokens += len(t.r.out)
    return out


# --------------------------------------------------------------------------
# one run
# --------------------------------------------------------------------------


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: Optional[float] = None, allow_cpu: bool = False,
             control: bool = False,
             log: Callable[[str], None] = print) -> Dict:
    """Run ``cell`` once and return its result: ``correct``, ``attempted``,
    ``failed``, ``metrics``, ``device`` (and ``breakdown`` when traced),
    then ``checks``, each number compared beside its limit.

    Raises :class:`NoChip` unless JAX's first device is a TPU and there are
    as many as the cell asks for; ``allow_cpu`` lets a test drive the whole
    run on the CPU, with the kernels in interpret mode.  ``control`` also
    reads the float8 control's gap on the same sample (``control_gap`` in
    the result), for setting the limit; the benchmark's runs leave it off.
    """
    import jax

    t_start = time.monotonic() if t_start is None else t_start
    devs = jax.devices()
    dev = devs[0]
    on_chip = dev.platform == "tpu"
    if not (on_chip or allow_cpu):
        raise NoChip(f"the benchmark needs a TPU; JAX found {dev.platform}")
    if len(devs) < cell.chips:
        raise NoChip(f"{cell.name} needs {cell.chips} chips, JAX found "
                     f"{len(devs)}")
    peaks = load_peaks(dev.device_kind, cell.bench_dir) if on_chip else None

    from repro.obs import Tracer

    cfg = program_config(cell)
    params = weights.make_params(cell.model, seed, cfg.dtype)
    jax.block_until_ready(params)
    log(f"weights: {sum(a.nbytes for a in jax.tree.leaves(params))} bytes "
        f"({cfg.dtype}, seed {seed}) at {time.monotonic() - t_start:.1f} s")
    recorder = RecordingRegistry()
    tracer = Tracer() if trace else None
    eng = build_engine(cfg, params, cell.engine, tracer=tracer,
                       metrics=recorder)
    fills = _PageFills()
    eng.pool.add_block_listener(fills)
    want = "pallas" if on_chip else "interpret"
    run = Run(cell, seed, seconds, loop=cell.traffic["loop"],
              samples=recorder, peaks=peaks)
    warmup_s = float(cell.workload["warmup_s"])
    prof: List[threading.Thread] = []

    def at_open() -> None:
        run.t0 = time.monotonic()
        run.t1 = run.t0 + seconds
        run.setup_s = run.t0 - t_start
        eng.pool.metrics.reset()
        run.pool_metrics = eng.pool.metrics
        run.pool_open = dict(vars(eng.pool.stats))
        run.steps_open = eng.steps
        if trace and on_chip:
            p = min(PROFILE_S, seconds / 2)
            mid = run.t0 + seconds / 2
            th = threading.Thread(target=_profile, daemon=True,
                                  args=(run, mid - p / 2, mid + p / 2))
            th.start()
            prof.append(th)

    drive = _drive_open if run.loop == "open" else _drive_closed
    stop_obs = threading.Event()
    observer = threading.Thread(target=_observe, args=(run, stop_obs),
                                daemon=True)
    with CompileLog() as comp:
        run.compiles = comp
        _warm_shapes(eng, run)
        log(f"decode and page-fill shapes warmed at "
            f"{time.monotonic() - t_start:.1f} s")
        eng.start()
        observer.start()
        try:
            _warm_every_length(eng, run, seed, cfg.vocab)
            log(f"every prompt length warmed at "
                f"{time.monotonic() - t_start:.1f} s")
            drive(eng, run, seed, cfg.vocab, warmup_s, time.monotonic(),
                  at_open)
            run.pool_close = dict(vars(eng.pool.stats))
            run.steps_close = eng.steps
            for th in prof:
                th.join()
            if run.loop == "open":
                for t in run.window_requests():
                    t.r.done.wait(max(0.0, run.t1 + GRACE_S -
                                      time.monotonic()))
        finally:
            stop_obs.set()
            observer.join()
            eng.stop()
    stats = dev.memory_stats() or {}
    memory_peak = int(stats.get("peak_bytes_in_use", 0))
    if tracer is not None:
        run.spans = [e for e in tracer.to_dict()["traceEvents"]
                     if e.get("ph") == "X"]
        run.span_t0 = -tracer.wall_ts(0.0) / 1e6

    # -- what the window did --
    win = run.window_requests()
    failed = run.failed()
    lateness = sorted(t.sent - t.due for t in win)
    log(f"window: {len(win)} requests, {sum(t.finished for t in win)} "
        f"finished, {len(failed)} failed, generator lateness p99 "
        f"{lateness[int(0.99 * (len(lateness) - 1))] if lateness else 0} s")
    steps = run.steps_close - run.steps_open
    toks = run.window_tokens()
    po, pc = run.pool_open, run.pool_close
    log(f"batch occupancy: {toks / max(steps, 1)} tokens per decode step "
        f"over {steps} steps | prefix hits {pc['prefix_hits'] - po['prefix_hits']}"
        f", misses {pc['prefix_misses'] - po['prefix_misses']} | pages freed "
        f"{pc['freed'] - po['freed']} | pings {pc['pings'] - po['pings']}")
    log(f"compiles: {len(comp.between(0, run.t0))} programs compiled or "
        f"read from the cache in set-up ({comp.seconds(0, run.t0)} s), "
        f"{len(comp.between(run.t0, run.t1))} in the window "
        f"({comp.seconds(run.t0, run.t1)} s); persistent cache hits "
        f"{len(comp.cache_hits)}")
    names = Counter(e[2] for e in comp.between(run.t0, run.t1))
    log(f"compiled in the window: {dict(names)}")
    gaps = run.window_gaps()
    log("token gaps in the window: " + ", ".join(
        f"p{round(q * 100)} {percentile(gaps, q)}"
        for q in (0.5, 0.9, 0.95, 0.99)) + f" s over {len(gaps)} gaps")
    log(fills.summary(run.t0, run.t1))
    for name in ("decode_step", "prefill_chunk"):
        sp = run.spans_named(name)
        if sp:
            log(f"{name} spans in the window: {len(sp)}, mean "
                f"{sum(x['dur_s'] for x in sp) / len(sp)} s")
    log(f"device memory: peak {memory_peak} of {stats.get('bytes_limit')} "
        f"bytes")

    # -- metrics --
    metrics: Dict[str, Dict] = {}
    if trace and run.device is not None:
        run.profile_work.update(profile_calls(run))
    for m in cell.metrics(trace):
        v = load_reader(m["name"], cell.bench_dir)(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    # -- correctness --
    from repro.core.sim.engine import UseAfterFree

    err = eng.error
    uaf = int(isinstance(err, UseAfterFree))
    eng.pool.evict_prefixes(0)
    eng.pool.policy.flush()
    leak = int(not eng.pool.check_no_leaks())
    limits = cell.workload["limits"]
    sample = choose_sample(run, seed, int(limits["check_tokens"]))
    rows = [{"prompt": t.req.prompt, "out": list(t.r.out)} for t in sample]
    kernel = eng.kernel_impl
    del eng
    gc.collect()
    t_ref = time.monotonic()
    gap = (float(reference.served_gaps(params, cell.model, rows).max())
           if rows else 0.0)
    log(f"reference: {len(rows)} requests, "
        f"{sum(len(r['out']) for r in rows)} served tokens, "
        f"{sum(t.r.cache_hit for t in sample)} of them prefix hits, checked "
        f"in {time.monotonic() - t_ref:.1f} s")
    if control and rows:
        result_control = float(
            reference.control_gaps(params, cell.model, rows).max())
        log(f"control (float8): max gap {result_control}")
    checks = {
        "max_logit_gap": {"value": gap, "limit": limits["max_logit_gap"]},
        "unchecked": {"value": int(not rows), "limit": 0},
        "failed_requests": {"value": len(failed), "limit": 0},
        "engine_errors": {"value": int(err is not None), "limit": 0},
        "use_after_free": {"value": uaf, "limit": 0},
        "pool_leaks": {"value": leak, "limit": 0},
        "kernel_not_compiled": {"value": int(kernel != want), "limit": 0},
    }
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": len(win),
              "failed": len(failed), "metrics": metrics, "device": device}
    if trace and run.device is not None:
        device["busy_s"] = run.device.busy_s()
        device["window_s"] = run.device.window_s
        result["breakdown"] = _breakdown(run)
    if control and rows:
        result["control_gap"] = result_control
    result["checks"] = checks
    return result
