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

Three tables, kept apart because the first TILES the loop thread's time, the
second lies inside it and the third tiles another wall, the start's:

- ``LOOP_PHASES``: every stretch of a fused session's iteration
  (``engine/pipeline.py _decode_pipeline``, ``_run_unified``,
  ``_harvest_pending``) and of ``engine.py _run_loop`` where it does the
  same work is inside exactly one of them, none inside another, so over a
  session their sums add up to its wall (``pipeline_wall_s``).
- ``DEVICE_CALLS``: calls on the pool's worker threads, nested in time
  inside the loop phase that waits for them: the jitted call that enqueues
  a program (``dispatch:*``, inside ``enqueue:*``) and the copy of the
  sampled tokens to the host (``fetch:*``, inside ``harvest:*``).

- ``SETUP_PHASES``: every stretch from the process's start to ``ready`` (the
  HTTP service accepting) is inside exactly one of them (``SetupAccount``):
  ``enter(name)`` closes the phase that is open and opens ``name`` with the
  SAME clock read, so their sums add up to ``ready - process start`` by
  construction.  Entered a handful of times a process: a sum and a count a
  phase, no histogram.  JAX's own trace, lower, compile and cache seconds
  (``engine/xla_cache.py``) are work INSIDE these phases, on several threads.

docs/tracing.md has the tables of phase, code covered, thread, series and
benchmark metric.
"""

from __future__ import annotations

import functools
import os
import threading
import time
from bisect import bisect_left
from typing import Any, Dict, Optional

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
# The start, in the order a serving process goes through it (docs/tracing.md
# "The start's account" says what code each covers and on which thread).
SETUP_PHASES = (
    "import", "build:params", "build:cache", "build:calibrate", "build:other",
    "warm:lower", "warm:compile", "warm:walk", "warm:sp", "serve:listen",
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

    def open(self, t0: float) -> None:
        self._t0 = t0
        self._span = TraceAnnotation(self._row.span)
        self._span.__enter__()

    def close(self) -> float:
        """Returns its closing clock read: the start's account opens the
        next phase with it, so no stretch lies between two phases."""
        self._span.__exit__(None, None, None)
        t1 = time.perf_counter()
        dt = t1 - self._t0
        row = self._row
        # Two fetches of one kind can end at once on two worker threads.
        with self._lock:
            row.sum += dt
            row.count += 1
            row.buckets[bisect_left(LE, dt)] += 1
        return t1

    def __enter__(self) -> None:
        self.open(time.perf_counter())

    def __exit__(self, *exc) -> bool:
        self.close()
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


_T_IMPORTED = time.time()


@functools.lru_cache(maxsize=None)
def process_start_time() -> float:
    """Epoch second at which the operating system started this process
    (``/proc/self/stat`` field 22 against the boot clock, in 10 ms steps);
    this module's import where there is no such file."""
    try:
        with open("/proc/self/stat") as f:
            # The command's name (field 2) may hold spaces and brackets.
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        age = time.clock_gettime(time.CLOCK_BOOTTIME) - ticks / os.sysconf("SC_CLK_TCK")
        return time.time() - age
    except (OSError, ValueError, IndexError, AttributeError):
        return _T_IMPORTED


_import_claimed = False  # a process starts once: its first account holds ``import``


class SetupAccount:
    """The start's table.  Exactly one phase is open from the account's
    making until ``mark_ready()``; ``enter(name)`` is the only way from one to the
    next (an unknown name raises ``KeyError``).  A span opened on one thread
    may be closed on another (warm-up runs on a worker thread): the profiler
    pairs the two.  After ``mark_ready()`` the account is static and ``enter``
    does nothing (a later ``warmup()`` is no part of the start)."""

    def __init__(self, from_process_start: bool = False):
        global _import_claimed
        self._lock = threading.Lock()  # the rows' sums
        self._edge = threading.Lock()  # which phase is open
        self._rows = {n: _Row("setup:" + n) for n in SETUP_PHASES}
        if from_process_start and not _import_claimed:
            _import_claimed = True
            row = self._rows["import"]
            # No span: the profiler was not imported when the phase began.
            row.sum = max(0.0, time.time() - process_start_time())
            row.count = 1
        self._open: Optional[_Timed] = _Timed(self._rows["build:other"], self._lock)
        self._open.open(time.perf_counter())

    def enter(self, name: str) -> None:
        row = self._rows[name]
        with self._edge:
            if self._open is not None:
                nxt = _Timed(row, self._lock)
                nxt.open(self._open.close())
                self._open = nxt

    def mark_ready(self) -> None:
        """The service accepts: close the open phase and open no other."""
        with self._edge:
            if self._open is not None:
                self._open.close()
                self._open = None

    @property
    def ready(self) -> bool:
        return self._open is None

    def warm_s(self) -> float:
        """Seconds in ``warm:*`` so far: ``dynamo_tpu_engine_warmup_seconds``."""
        return sum(r.sum for n, r in self._rows.items() if n.startswith("warm:"))

    def summary(self) -> Dict[str, Any]:
        """``seconds`` is the sum of the closed passes: process start (or the
        account's making) to ``ready`` once ``ready`` is true."""
        phases = {n: {"sum": r.sum, "count": r.count} for n, r in self._rows.items()}
        return {
            "phases": phases, "ready": self.ready,
            "process_start_time": process_start_time(),
            "seconds": sum(p["sum"] for p in phases.values()),
        }
