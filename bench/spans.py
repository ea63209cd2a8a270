"""The program's tracer spans as the per-layer readers see them: one line
of nested spans per thread, the engine thread's steps, and the device's
idle time put down to what the engine thread was doing.

``Run.spans`` holds the tracer's Chrome ``X`` events: a name, a start and
length on the tracer's clock, a thread (``tid``) and args.  Spans of one
thread nest by interval.  The engine thread's line is the one that carries
``engine_step`` spans (the line with the most, should there be several);
at any instant its innermost open span says what the thread that feeds the
chip was doing, and outside every ``engine_step`` it was between steps
(``engine idle``).

Device idle time is on the profiler's clock; a span's monotonic time maps
onto it through the harness's anchor (``Run.mono_to_ns``), taken as the
profiled window opened.
"""

from __future__ import annotations

import bisect
import sys
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Optional, Tuple

__all__ = ["SMR", "ENGINE_IDLE", "Span", "lines", "engine_tid",
           "window_steps", "innermost", "idle_split", "idle_overlap",
           "log_outliers", "log_idle"]

#: the pool's reader work on the engine thread: the SMR share of a step
SMR = ("pool_reserve", "pool_tripwire", "pool_retire", "pop_publish")
#: the label of engine-thread time outside every ``engine_step``
ENGINE_IDLE = "engine idle"
#: spans drawn on a synthetic track per reader slot, not on a thread
_SYNTHETIC = ("publish",)
_EPS = 1e-9


@dataclass
class Span:
    name: str
    t: float                 # start, monotonic seconds
    dur: float               # seconds
    args: Dict
    children: List["Span"] = field(default_factory=list)

    @property
    def end(self) -> float:
        return self.t + self.dur

    @property
    def self_s(self) -> float:
        """Its time less that of the spans nested directly in it."""
        return self.dur - sum(c.dur for c in self.children)

    def walk(self) -> Iterator["Span"]:
        yield self
        for c in self.children:
            yield from c.walk()


def lines(run, lo: float, hi: float) -> Dict[int, List[Span]]:
    """Each thread's outermost spans that overlap ``[lo, hi)``, in start
    order, with the spans nested in them."""
    flat: Dict[int, List[Span]] = defaultdict(list)
    for ev in run.spans:
        if ev.get("name") in _SYNTHETIC:
            continue
        t = run.span_t0 + ev["ts"] / 1e6
        d = ev["dur"] / 1e6
        if t < hi and t + d > lo:
            flat[ev["tid"]].append(Span(ev["name"], t, d,
                                        ev.get("args") or {}))
    out: Dict[int, List[Span]] = {}
    for tid, ss in flat.items():
        ss.sort(key=lambda s: (s.t, -s.dur))
        roots: List[Span] = []
        stack: List[Span] = []
        for s in ss:
            while stack and s.t >= stack[-1].end - _EPS:
                stack.pop()
            (stack[-1].children if stack else roots).append(s)
            stack.append(s)
        out[tid] = roots
    return out


def engine_tid(by_tid: Dict[int, List[Span]]) -> Optional[int]:
    """The line that carries the most ``engine_step`` spans, or None."""
    n = {tid: sum(s.name == "engine_step" for s in roots)
         for tid, roots in by_tid.items()}
    best = max(n, key=n.get, default=None)
    return best if best is not None and n[best] else None


def window_steps(run) -> List[Span]:
    """The engine thread's ``engine_step`` spans that start in the window."""
    by_tid = lines(run, run.t0, run.t1)
    tid = engine_tid(by_tid)
    if tid is None:
        return []
    return [s for s in by_tid[tid]
            if s.name == "engine_step" and run.t0 <= s.t < run.t1]


def innermost(roots: List[Span],
              outside: Optional[str] = None) -> List[Tuple[float, float, str]]:
    """A line's time cut into ``(start, end, name)`` pieces, each named by
    the innermost span open in it, in order; with ``outside``, the time
    between the outermost spans too."""
    out: List[Tuple[float, float, str]] = []

    def cut(s: Span) -> None:
        t = s.t
        for c in s.children:
            if c.t > t:
                out.append((t, c.t, s.name))
            cut(c)
            t = max(t, c.end)
        if s.end > t:
            out.append((t, s.end, s.name))

    t = float("-inf")
    for r in roots:
        if outside is not None and r.t > t:
            out.append((t, r.t, outside))
        cut(r)
        t = max(t, r.end)
    if outside is not None:
        out.append((t, float("inf"), outside))
    return out


def _on_device_clock(pieces, run) -> List[Tuple[float, float, str]]:
    k = run.mono_to_ns
    return [(a * 1e9 + k, b * 1e9 + k, n) for a, b, n in pieces]


def _overlap(gaps, pieces) -> Dict[str, float]:
    """Seconds of the sorted ``gaps`` (ns) covered by each name of the
    sorted, disjoint ``pieces`` (ns)."""
    by: Dict[str, float] = defaultdict(float)
    starts = [p[0] for p in pieces]
    for a, b in gaps:
        i = max(bisect.bisect_right(starts, a) - 1, 0)
        while i < len(pieces) and pieces[i][0] < b:
            pa, pb, name = pieces[i]
            d = min(b, pb) - max(a, pa)
            if d > 0:
                by[name] += d / 1e9
            i += 1
    return dict(by)


def _profiled(run):
    lo, hi = run.profile_work["t"]
    return lines(run, lo - 1.0, hi + 1.0)


def idle_split(run) -> Optional[Dict[str, float]]:
    """Seconds of the profiled window in which the chip ran nothing, by
    the engine thread's innermost span at the time (``engine idle``
    between its steps); None without a device trace or an engine line."""
    if run.device is None or "t" not in run.profile_work:
        return None
    by_tid = _profiled(run)
    tid = engine_tid(by_tid)
    if tid is None:
        return None
    pieces = _on_device_clock(innermost(by_tid[tid], ENGINE_IDLE), run)
    return _overlap(run.device.idle_gaps(), pieces)


def idle_overlap(run) -> Dict[str, float]:
    """Seconds of device idle during which a span of a thread other than
    the engine's was open, by that thread's innermost span: the time the
    engine thread shared the interpreter with, say, a reclaim pass."""
    if run.device is None or "t" not in run.profile_work:
        return {}
    by_tid = _profiled(run)
    eng = engine_tid(by_tid)
    gaps = run.device.idle_gaps()
    total: Dict[str, float] = defaultdict(float)
    for tid, roots in by_tid.items():
        if tid == eng:
            continue
        for name, s in _overlap(gaps, _on_device_clock(innermost(roots),
                                                       run)).items():
            total[name] += s
    return dict(total)


def _fmt(by: Dict[str, float], scale: float = 1.0, k: int = 8) -> str:
    top = sorted(by.items(), key=lambda kv: -kv[1])[:k]
    return ", ".join(f"{n} {v * scale:.6g}" for n, v in top)


def _say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def log_outliers(run, steps: List[Span]) -> None:
    """Standard error: the window's five longest engine steps, each with
    its own time split by span name, and for its three longest
    publish-on-ping passes what the engine thread was doing as the ping
    was set."""
    for s in sorted(steps, key=lambda s: -s.dur)[:5]:
        split: Dict[str, float] = defaultdict(float)
        for x in s.walk():
            split[x.name] += x.self_s
        _say(f"long engine_step: {s.dur * 1e3:.3f} ms at +{s.t - run.t0:.3f}"
             f" s, {s.args}; own ms by span: {_fmt(split, 1e3)}")
    by_tid = lines(run, run.t0, run.t1)
    eng = engine_tid(by_tid)
    passes = [(p, tid) for tid, roots in by_tid.items() for r in roots
              for p in r.walk()
              if p.name == "pop_pass" and run.t0 <= p.t < run.t1]
    pieces = innermost(by_tid.get(eng, []), ENGINE_IDLE)
    starts = [p[0] for p in pieces]
    prof = run.profile_work.get("t")
    for p, tid in sorted(passes, key=lambda pt: -pt[0].dur)[:3]:
        i = bisect.bisect_right(starts, p.t) - 1
        doing = pieces[i][2] if i >= 0 else ENGINE_IDLE
        where = "the engine thread" if tid == eng else f"thread {tid}"
        extra = (f"; profile ran +{prof[0] - run.t0:.3f}.."
                 f"+{prof[1] - run.t0:.3f} s" if prof else "")
        _say(f"long pop_pass: {p.dur * 1e3:.3f} ms at +{p.t - run.t0:.3f} s"
             f" on {where}, {p.args}; engine thread in {doing} at the "
             f"ping{extra}")


def log_idle(run, split: Dict[str, float]) -> None:
    """Standard error: device idle by the engine thread's innermost span,
    and idle during other threads' spans."""
    _say(f"device idle by engine span (s): {_fmt(split, k=12)}")
    _say(f"device idle during other threads' spans (s): "
         f"{_fmt(idle_overlap(run), k=12)}")
