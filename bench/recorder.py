"""What the benchmark records from the program while a cell runs: raw
latency samples with their times, and the compiles JAX reports.

:class:`RecordingRegistry` is handed to ``ServeEngine(metrics=...)``.  It
keeps every sample the engine records (``ttft_s``, ``tok_latency_s``,
``queue_wait_s``) with the monotonic time it was recorded, so tails are
taken over raw samples in the window, not over the engine's log-bucketed
histograms, whose percentiles move in steps of about 9 %.  It still
records into those histograms, so the engine's own cost is unchanged.

:class:`CompileLog` grows ``chip_smoke.py``'s: each compile stage with its
time, so the compiles inside the measured window can be counted.
"""

from __future__ import annotations

import time
from collections import defaultdict
from typing import Dict, List, Tuple

from repro.obs import MetricsRegistry

__all__ = ["RecordingRegistry", "CompileLog"]


class RecordingRegistry(MetricsRegistry):
    """A metrics registry that also keeps ``(monotonic time, value)`` for
    every sample (``list.append`` is atomic, so worker threads need no
    lock)."""

    def __init__(self) -> None:
        super().__init__()
        self.samples: Dict[str, List[Tuple[float, float]]] = \
            defaultdict(list)

    def record(self, name: str, value: float) -> None:
        self.samples[name].append((time.monotonic(), value))
        super().record(name, value)

    def between(self, name: str, t0: float, t1: float) -> List[float]:
        """Values of ``name`` recorded in ``[t0, t1)``."""
        return [v for t, v in list(self.samples.get(name, ()))
                if t0 <= t < t1]


class CompileLog:
    """JAX's compile work while active, from its monitoring events, each
    with the monotonic time it ended: ``events`` holds ``(time, stage,
    function, seconds)``, stage ``trace`` (Python to jaxpr), ``lower``
    (jaxpr to MLIR) or ``compile`` (an XLA compile, or a read of the
    persistent compilation cache: JAX times both as one backend compile).
    """

    STAGES = {"/jax/core/compile/jaxpr_trace_duration": "trace",
              "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
              "/jax/core/compile/backend_compile_duration": "compile"}

    def __init__(self):
        self.events: List[Tuple[float, str, str, float]] = []
        self.cache_hits: List[float] = []

    def _duration(self, event, secs, fun_name="?", **_):
        stage = self.STAGES.get(event)
        if stage is not None:
            self.events.append((time.monotonic(), stage, fun_name, secs))

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits.append(time.monotonic())

    def between(self, t0: float, t1: float, stage: str = "compile"):
        """The events of ``stage`` that ended in ``[t0, t1)``."""
        return [e for e in list(self.events)
                if t0 <= e[0] < t1 and e[1] == stage]

    def seconds(self, t0: float, t1: float) -> float:
        """Seconds of tracing, lowering and compiling that ended in
        ``[t0, t1)``."""
        return sum(e[3] for e in list(self.events) if t0 <= e[0] < t1)

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)
