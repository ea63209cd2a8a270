"""Reduction of a JAX profiler trace (``*.xplane.pb``) to the device
numbers the benchmark reports: busy time, per-module device time, the
idle gaps and what the host was doing in them.

The profiled window is the benchmark's own ``TraceAnnotation``
(:data:`WINDOW`), which puts its start and length on the trace's clock
(the device planes share it).  Each ``/device:TPU:<n>`` plane has an
``XLA Ops`` line, one event per operation run one after another, which
gives the busy time, and an ``XLA Modules`` line, one event per program
run, named ``<jitted function>(<fingerprint>)``: a kernel's time is that
of the jitted function that launches it (``jit_paged_attention_pallas``
for the paged kernel).

    python bench/devtrace.py <file.xplane.pb>   # print planes, lines, names
"""

from __future__ import annotations

import re
import sys
from collections import Counter
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

__all__ = ["WINDOW", "DeviceTrace", "load", "union_length"]

#: the annotation that marks the profiled window on the host
WINDOW = "bench/profiled_window"
_DEVICE = re.compile(r"^/device:TPU:\d+$")


@dataclass
class Op:
    start_ns: float
    dur_ns: float
    name: str
    module: str


@dataclass
class DeviceTrace:
    """Device ops and program runs per chip, and the window, all on the
    trace's clock (ns)."""

    t0_ns: float
    t1_ns: float
    devices: Dict[str, List[Op]] = field(default_factory=dict)
    modules: Dict[str, List[Op]] = field(default_factory=dict)

    @property
    def window_s(self) -> float:
        return (self.t1_ns - self.t0_ns) / 1e9

    def _per_chip(self, total_ns: float) -> float:
        return total_ns / max(len(self.devices), 1) / 1e9

    def busy_s(self) -> float:
        """Seconds in which some operation ran, averaged over the chips."""
        return self._per_chip(sum(
            union_length([(o.start_ns, o.start_ns + o.dur_ns) for o in ops],
                         self.t0_ns, self.t1_ns)
            for ops in self.devices.values()))

    def module_s(self, pattern: str) -> float:
        """Device seconds of the program runs whose jitted function matches
        ``pattern``, clipped to the window and averaged over the chips."""
        rx = re.compile(pattern)
        return self._per_chip(sum(
            _clip(o.start_ns, o.start_ns + o.dur_ns, self.t0_ns, self.t1_ns)
            for runs in self.modules.values() for o in runs
            if rx.search(o.module)))

    def top_modules(self, k: int = 10) -> List[Tuple[str, float]]:
        c: Counter = Counter()
        for runs in self.modules.values():
            for o in runs:
                c[o.module] += _clip(o.start_ns, o.start_ns + o.dur_ns,
                                     self.t0_ns, self.t1_ns)
        return [(name, self._per_chip(ns)) for name, ns in c.most_common(k)]

    def idle_gaps(self) -> List[Tuple[float, float]]:
        """Intervals of the window in which the first chip ran nothing."""
        if not self.devices:
            return [(self.t0_ns, self.t1_ns)]
        ops = next(iter(self.devices.values()))
        merged = _merge([(o.start_ns, o.start_ns + o.dur_ns) for o in ops])
        gaps, t = [], self.t0_ns
        for a, b in merged:
            if b <= self.t0_ns:
                continue
            if a >= self.t1_ns:
                break
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
        if t < self.t1_ns:
            gaps.append((t, self.t1_ns))
        return gaps


def _clip(a, b, t0, t1) -> float:
    return max(0.0, min(b, t1) - max(a, t0))


def _merge(iv: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def union_length(iv: Sequence[Tuple[float, float]], t0: float,
                 t1: float) -> float:
    """Length of the union of intervals ``iv``, clipped to ``[t0, t1]``."""
    return sum(_clip(a, b, t0, t1) for a, b in _merge(iv))


def load(path: str) -> Optional[DeviceTrace]:
    """The device trace of one profile, or None if it has no window
    annotation (the profile did not cover the window)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    window = None
    devices: Dict[str, List[Op]] = {}
    modules: Dict[str, List[Op]] = {}
    for plane in pd.planes:
        if _DEVICE.match(plane.name):
            devices[plane.name], modules[plane.name] = [], []
            for ln in plane.lines:
                if ln.name == "XLA Ops":
                    devices[plane.name] = [
                        Op(float(e.start_ns), float(e.duration_ns), e.name,
                           "") for e in ln.events]
                elif ln.name == "XLA Modules":
                    modules[plane.name] = [
                        Op(float(e.start_ns), float(e.duration_ns), e.name,
                           e.name.split("(")[0]) for e in ln.events]
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name == WINDOW:
                        window = (float(ev.start_ns),
                                  float(ev.start_ns + ev.duration_ns))
    if window is None:
        return None
    return DeviceTrace(window[0], window[1], devices, modules)


def _dump(path: str) -> None:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"plane {plane.name!r}: {len(lines)} lines")
        for ln in lines:
            evs = list(ln.events)
            names = Counter(e.name for e in evs)
            print(f"  line {ln.name!r}: {len(evs)} events; top names "
                  f"{names.most_common(6)}")
            for e in evs[:2]:
                print(f"    e.g. {e.name[:120]!r} start {e.start_ns} dur "
                      f"{e.duration_ns}")


if __name__ == "__main__":
    _dump(sys.argv[1])
