"""The engine loop's account of its own time: ONE point, two planes.

``with engine._phase("retire"): ...`` enters
``jax.profiler.TraceAnnotation("engine.retire")`` (under a profiler the
stretch is on the device's clock, where ``chipbench/trace_reduce.py`` names
the device's idle gaps by it) and, on exit, folds one ``time.perf_counter()``
difference into a fixed table (always on, printed on ``/metrics`` by
``llm/metrics.py EngineDispatchMetrics``).  No switch: like the hop account
(docs/tracing.md) it is always on and cheap (two clock reads, a lock, a
search over six bounds, three adds), one observation a phase an ITERATION,
never one a row, a token or a request.

Two tables, kept apart because the first TILES the loop thread's time and
the second lies inside it:

- ``LOOP_PHASES``: every stretch of a fused session's iteration
  (``engine/pipeline.py _decode_pipeline``, ``_run_unified``,
  ``_harvest_pending``) and of ``engine.py _run_loop`` where it does the
  same work is inside exactly one of them, none inside another, so over a
  session their sums add up to its wall (``pipeline_wall_s``).
- ``DEVICE_CALLS``: calls on the pool's worker threads, nested in time
  inside the loop phase that waits for them: the jitted call that enqueues
  a program (``dispatch:*``, inside ``enqueue:*``) and the copy of the
  sampled tokens to the host (``fetch:*``, inside ``harvest:*``).

docs/tracing.md has the table of phase, code covered, thread, series and
benchmark metric.
"""

from __future__ import annotations

import threading
import time
from bisect import bisect_left
from typing import Any, Dict

from jax.profiler import TraceAnnotation

# Upper bounds of the histograms' buckets, seconds (+Inf is the last): a
# sound chain break's longest phase is 16 ms, so 0.064 is four times that.
LE = (0.001, 0.004, 0.016, 0.064, 0.256, 1.024)

LOOP_PHASES = (
    "retire", "merge", "admit", "prompt_build", "enqueue:unified",
    "enqueue:decode", "schedule", "harvest:first", "harvest:spec",
    "harvest:decode", "emit", "yield",
)
DEVICE_CALLS = (
    "dispatch:unified", "dispatch:decode", "fetch:first", "fetch:spec",
    "fetch:decode",
)


class _Row:
    __slots__ = ("span", "sum", "count", "buckets")

    def __init__(self, name: str):
        self.span = "engine." + name
        self.sum = 0.0
        self.count = 0
        self.buckets = [0] * (len(LE) + 1)

    def summary(self) -> Dict[str, Any]:
        return {"sum": self.sum, "count": self.count, "buckets": list(self.buckets)}


class _Timed:
    """One pass through a phase: the clock reads around the annotation, so
    what the profiler's plane costs when it is on is inside the account."""

    __slots__ = ("_row", "_lock", "_span", "_t0")

    def __init__(self, row: _Row, lock: threading.Lock):
        self._row = row
        self._lock = lock

    def __enter__(self) -> None:
        self._t0 = time.perf_counter()
        self._span = TraceAnnotation(self._row.span)
        self._span.__enter__()

    def __exit__(self, *exc) -> bool:
        self._span.__exit__(*exc)
        dt = time.perf_counter() - self._t0
        row = self._row
        # Two fetches of one kind can end at once on two worker threads.
        with self._lock:
            row.sum += dt
            row.count += 1
            row.buckets[bisect_left(LE, dt)] += 1
        return False


class PhaseAccount:
    """The fixed table; ``phase(name)`` is the only way in (an unknown name
    raises ``KeyError``: a phase is added here, with its row in
    docs/tracing.md, or not at all)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._rows = {n: _Row(n) for n in LOOP_PHASES + DEVICE_CALLS}

    def phase(self, name: str) -> _Timed:
        return _Timed(self._rows[name], self._lock)

    def waited_s(self) -> float:
        """Seconds the loop spent in ``harvest:*``: nothing of its own to
        do but wait for the device (``dispatch_summary`` host_gap_frac)."""
        return sum(
            self._rows[n].sum for n in LOOP_PHASES if n.startswith("harvest:")
        )

    def summary(self) -> Dict[str, Any]:
        return {
            "le": list(LE),
            "loop": {n: self._rows[n].summary() for n in LOOP_PHASES},
            "calls": {n: self._rows[n].summary() for n in DEVICE_CALLS},
        }
