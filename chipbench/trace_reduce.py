"""From a profiler trace to numbers: busy union, idle share, per-name sums,
idle gaps and what the host was doing in them.  The arithmetic works on plain
tuples, so it is tested on small recorded traces (tests/chipbench/fixtures)
without JAX; only ``load_xplane`` touches ``jax.profiler``.

An event is ``(name, start_ns, dur_ns)``.  A device plane has, among others,
a line of XLA ops (one event per executed HLO op or kernel) and a line of XLA
modules (one event per executed jitted program).  Busy time is the union of
the op events; a program's device time is its module event.  The host's
planes hold, one line a thread, the program's own ``engine.*`` annotations
(``jax.profiler.TraceAnnotation``: docs/tracing.md); kept as ``(name,
start_ns, dur_ns, thread)``, they name the device's idle gaps.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PLANE_RE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# The program's host annotations: the engine loop's phases (on the loop's
# thread) and ``engine.dispatch:*`` (on the worker thread that enqueues).
HOST_PREFIX = "engine."
DISPATCH_PREFIX = "engine.dispatch:"
# A gap shorter than this is the device's own (microseconds between two
# programs or two ops); no host phase explains it.
HOST_GAP_NS = 100_000
# The clock (``DeviceTrace.clock``).  The runtime's own event around the
# enqueue of a program and the stat that ties it to the device's module event.
ENQUEUE_EVENT = "DoEnqueueProgram"
RUN_ID_STAT = "run_id"
# The fused decode program and the annotation around the call that enqueues
# it: what the check pairs.
CLOCK_MODULE_RE = re.compile(r"^jit__multi")
CLOCK_DISPATCH = "engine.dispatch:decode"
CLOCK_SLACK_NS = 100_000
# The runtime enqueues a program within a few hundred microseconds of the
# call's return, and an idle device begins it within as many of the enqueue.
CLOCK_LATE_NS = 1_000_000
# The share of the fused decode programs that must find their annotation: a
# tracer's edge may cut one off.
CLOCK_PAIRED = 0.9
# An op event's name is its whole HLO text, "%fusion.247 = f32[512,37888]{...}
# fusion(s8[512,3584]{...} %fusion.246, ...)": operands included, so a pattern
# must never be matched against it.  Kept: the instruction's own name without
# its number, and the shape it produces.
_HLO_RE = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)* = \(?([a-z]+\d*\[[\d,]*\])?")


def short_name(name: str) -> str:
    """``fusion f32[512,37888]`` from an op's HLO text; other names as they are."""
    m = _HLO_RE.match(name)
    if not m:
        return name[:80]
    return m.group(1) + (" " + m.group(2) if m.group(2) else "")


def self_times(events) -> list:
    """The events with each one's duration cut by what its nested events
    cover: a ``while`` or ``conditional`` wraps the ops of its body, and a sum
    over names would count those twice.  Events of one line nest or follow
    one another; they never partly overlap."""
    out, stack = [], []  # stack of [name, start, end, covered]

    def close(upto):
        while stack and stack[-1][2] <= upto:
            name, start, end, covered = stack.pop()
            out.append((name, start, max(0, end - start - covered)))
            if stack:
                stack[-1][3] += end - start

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        stack.append([name, start, start + dur, 0])
    close(float("inf"))
    return out


def busy_union_ns(events, t0_ns=None, t1_ns=None) -> int:
    """Nanoseconds covered by at least one event, clipped to [t0, t1]."""
    ivs = []
    for _, start, dur in events:
        a, b = start, start + dur
        if t0_ns is not None:
            a = max(a, t0_ns)
        if t1_ns is not None:
            b = min(b, t1_ns)
        if b > a:
            ivs.append((a, b))
    ivs.sort()
    total, cur_a, cur_b = 0, None, None
    for a, b in ivs:
        if cur_b is None or a > cur_b:
            if cur_b is not None:
                total += cur_b - cur_a
            cur_a, cur_b = a, b
        elif b > cur_b:
            cur_b = b
    if cur_b is not None:
        total += cur_b - cur_a
    return total


def span_ns(events):
    """(first start, last end) over the events, or None when there are none."""
    if not events:
        return None
    return min(s for _, s, _ in events), max(s + d for _, s, d in events)


def sum_by_name(events) -> dict:
    out: dict = {}
    for name, _, dur in events:
        out[name] = out.get(name, 0) + dur
    return out


def sum_matching_ns(events, pattern: str) -> int:
    rx = re.compile(pattern)
    return sum(dur for name, _, dur in events if rx.search(name))


def count_matching(events, pattern: str) -> int:
    rx = re.compile(pattern)
    return sum(1 for name, _, _ in events if rx.search(name))


def idle_gaps(events, t0_ns: int, t1_ns: int, top: int = 10) -> list:
    """The longest intervals of [t0, t1] in which no event runs, as ``(name of
    the event that ended last before the gap, gap_ns, gap start_ns)``."""
    evs = sorted((s, s + d, n) for n, s, d in events if s + d > t0_ns and s < t1_ns)
    gaps, edge, last = [], t0_ns, "window_start"
    for a, b, name in evs:
        if a > edge:
            gaps.append((last, a - edge, edge))
        if b > edge:
            edge, last = b, name
    if t1_ns > edge:
        gaps.append((last, t1_ns - edge, edge))
    gaps.sort(key=lambda g: -g[1])
    return gaps if top is None else gaps[:top]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load_xplane(path: str, plane_re=DEVICE_PLANE_RE, lines=(OPS_LINE, MODULES_LINE)):
    """``({plane name: {line name: [(event name, start_ns, dur_ns), ...]}},
    extent, host)`` from ONE parse of the file: the planes whose name matches
    (device planes by default) with the lines named (``None``: every line);
    ``extent``, (first start, last end) over EVERY event of the file, host
    threads included: what the trace itself shows of when it was recording;
    and ``host``: ``annotations``, the events of any plane whose name starts
    ``HOST_PREFIX`` (the program's own) as ``(name, start_ns, dur_ns,
    thread)``, a thread being a line of its plane (several carry one name);
    and ``launches``, ``(module start_ns, enqueue start_ns)`` for every
    module event (of the first kept plane that ran it) whose ``run_id`` the
    runtime's ``DoEnqueueProgram`` event carries too: the profiler's own link
    from the host's clock to the device's."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out: dict = {}
    notes: list = []
    began, enqueued = {}, {}
    first, last = None, None
    for plane in data.planes:
        keep = out.setdefault(plane.name, {}) if plane_re.match(plane.name) else None
        for at, line in enumerate(plane.lines):
            is_modules = keep is not None and line.name == MODULES_LINE
            evs = []
            for ev in line.events:
                name, start = ev.name, int(ev.start_ns)
                evs.append((name, start, int(ev.duration_ns)))
                if is_modules or name == ENQUEUE_EVENT:
                    run = dict(ev.stats).get(RUN_ID_STAT)
                    if run is not None:
                        (began if is_modules else enqueued).setdefault(run, start)
            if not evs:
                continue
            a, b = span_ns(evs)
            first = a if first is None else min(first, a)
            last = b if last is None else max(last, b)
            if keep is not None and (lines is None or line.name in lines):
                keep.setdefault(line.name, []).extend((short_name(n), s, d) for n, s, d in evs)
            thread = f"{plane.name}#{at}:{line.name}"
            notes.extend((n, s, d, thread) for n, s, d in evs if n.startswith(HOST_PREFIX))
    host = {"annotations": notes,
            "launches": sorted((began[r], enqueued[r]) for r in began if r in enqueued)}
    return out, (first, last) if first is not None else None, host


def merged(ivs) -> list:
    """The intervals ``(start, end)`` as sorted disjoint ones."""
    out: list = []
    for a, b in sorted(ivs):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        elif b > a:
            out.append([a, b])
    return out


def overlap_ns(ivs, a: int, b: int) -> int:
    """Nanoseconds of [a, b] that the intervals ``(start, end)`` cover."""
    return sum(max(0, min(y, b) - max(x, a)) for x, y in merged(ivs))


class DeviceTrace:
    """One traced window, reduced once for the readers: the device's planes
    and, where the trace was loaded with them, the host's ``engine.*``
    annotations.  Without ``host`` every reading is the device's alone."""

    def __init__(self, planes: dict, t_start_s: float | None = None,
                 t_stop_s: float | None = None, extent=None, host=None):
        self.planes = planes
        # The interval in which the trace was surely recording: from after
        # start_trace returned to before stop_trace was called, in seconds
        # since the measured window began (host clock).  Readers join it with
        # the generator's request records.
        self.t_start_s, self.t_stop_s = t_start_s, t_stop_s
        self._self_ops = None
        self.ops = {p: lines.get(OPS_LINE, []) for p, lines in planes.items()}
        self.modules = {p: lines.get(MODULES_LINE, []) for p, lines in planes.items()}
        # The traced window in the trace's own clock: the extent of all its
        # events (``load_xplane``), so that time at its edges in which the
        # device ran nothing counts as idle; without one, the ops' own span.
        spans = [s for s in (span_ns(e) for e in self.ops.values()) if s]
        if extent is None and spans:
            extent = (min(s[0] for s in spans), max(s[1] for s in spans))
        self.t0_ns, self.t1_ns = extent or (0, 0)
        # The host's side (``load_xplane``), its times moved onto the device's
        # clock once ``clock`` has measured how far the two are apart.
        host = host or {}
        self.launches = host.get("launches", [])
        self.written = host.get("annotations", [])
        self._gaps = self._clock = self._read = self._skew_ns = None

    @property
    def n_devices(self) -> int:
        return len(self.planes)

    @property
    def window_s(self) -> float:
        """Length of the traced window: the trace's extent, and no less than
        the host-clock interval in which it was surely recording (a trace of
        an idle process may hold no event near its edges)."""
        sure = (self.t_stop_s - self.t_start_s) if self.t_start_s is not None else 0.0
        return max((self.t1_ns - self.t0_ns) / 1e9, sure)

    @property
    def busy_s(self) -> float:
        """Seconds an op ran, averaged over the traced devices."""
        if not self.ops:
            return 0.0
        per = [busy_union_ns(evs, self.t0_ns, self.t1_ns) for evs in self.ops.values()]
        return sum(per) / len(per) / 1e9

    def all_ops(self) -> list:
        """Every device's op events, each with its SELF time as duration."""
        if self._self_ops is None:
            self._self_ops = [e for evs in self.ops.values() for e in self_times(evs)]
        return self._self_ops

    def all_modules(self) -> list:
        return [e for evs in self.modules.values() for e in evs]

    def gaps(self) -> list:
        """Every idle gap of the first device inside the traced window, the
        longest first: ``(program that began last before it, gap_ns, start_ns)``."""
        if self._gaps is None:
            plane = next(iter(self.planes), None)
            mods = sorted((s, name) for name, s, _ in self.modules.get(plane, []))
            starts = [s for s, _ in mods]
            self._gaps = [
                (mods[at - 1][1].split("(")[0] if at else "window_start", ns, start)
                for _, ns, start in idle_gaps(self.ops.get(plane, []), self.t0_ns, self.t1_ns, None)
                for at in [bisect.bisect_right(starts, start)]]
        return self._gaps

    def idle_before(self, starts: list) -> list:
        """Of the programs' start times given, those the device was idle
        before: an idle gap of ``HOST_GAP_NS`` or more ends as they begin."""
        ends = sorted(start + ns for _, ns, start in self.gaps() if ns >= HOST_GAP_NS)
        out = []
        for s in starts:
            at = bisect.bisect_left(ends, s)
            if at < len(ends) and ends[at] - s <= CLOCK_SLACK_NS:
                out.append(s)
        return out

    def clock(self) -> dict | None:
        """How far the host's clock and the device's are apart in this trace,
        measured and then checked before any gap is named by an annotation;
        ``None`` where the trace holds nothing to show it by.

        Measured on the profiler's own link: the runtime's enqueue event and
        the module event it started carry one ``run_id`` (``launches``).  A
        program the device was idle before begins as it is enqueued, so over
        those the largest distance from begin back to enqueue is the clocks'
        ``skew`` (to a launch's own latency, which ``spread`` bounds); the
        annotations are then read ``skew`` earlier, on the device's clock.

        Checked on the program's annotations: each fused decode program of the
        first device (``jit__multi``) is paired, through its enqueue event,
        with the ``engine.dispatch:decode`` annotation around the call that
        enqueued it: the last one that began before the enqueue, if the
        enqueue came no later than ``CLOCK_LATE_NS`` after it ended and no
        other program took it (an order of events would do no better: either
        tracer may outlive the other by tens of milliseconds, so either side's
        last events may lack their partners).  It holds when the launches on
        an idle device agree to ``CLOCK_LATE_NS``, at least ``CLOCK_PAIRED``
        of the programs find their annotation, and every one of those begins
        no earlier than its annotation began, less ``CLOCK_SLACK_NS``."""
        if self._clock is None:
            self._clock = self._check_clock() or {}
        return self._clock or None

    def _check_clock(self) -> dict | None:
        plane = next(iter(self.planes), None)
        began = dict(self.launches)
        mods = sorted(s for n, s, _ in self.modules.get(plane, [])
                      if CLOCK_MODULE_RE.match(n) and s in began)
        calls = sorted((s, s + d) for n, s, d, _ in self.written if n == CLOCK_DISPATCH)
        apart = sorted(began[s] - s for s in self.idle_before(sorted(began)))
        if not mods or not calls or not apart:
            return None
        skew = self._skew_ns = apart[-1]
        starts, taken, leads = [a for a, _ in calls], set(), []
        for m in mods:
            at = bisect.bisect_right(starts, began[m]) - 1
            if at >= 0 and at not in taken and began[m] - calls[at][1] <= CLOCK_LATE_NS:
                taken.add(at)
                leads.append(m + skew - calls[at][0])
        leads.sort()
        ok = (apart[-1] - apart[0] <= CLOCK_LATE_NS and len(leads) >= CLOCK_PAIRED * len(mods)
              and leads[0] >= -CLOCK_SLACK_NS)
        return {"ok": ok, "skew_us": skew / 1e3, "launches_after_a_gap": len(apart),
                "spread_us": (apart[-1] - apart[0]) / 1e3, "programs": len(mods),
                "pairs": len(leads), "lead_min_us": leads[0] / 1e3 if leads else None,
                "lead_median_us": leads[len(leads) // 2] / 1e3 if leads else None}

    @property
    def annotations(self) -> list:
        """The host's annotations on the DEVICE's clock, by start; empty
        where the clock check did not hold (a wrong name is worse than none)."""
        if self._read is None:
            clock = self.clock()
            self._read = sorted(((n, s - self._skew_ns, d, t) for n, s, d, t in self.written),
                                key=lambda e: e[1]) if clock and clock["ok"] else []
        return self._read

    def name_gap(self, prog: str, ns: int, start: int) -> str:
        """What the host was doing in one idle gap: of the ``engine.*``
        annotations that overlap it (any thread), the name that covers most
        of it, if it covers at least half (of two that cover alike, the one
        nested in the other).  Else most of it is time the program does not
        annotate: ``host_unannotated_after:`` and the loop's thread's
        annotation that ended last before the gap's longest stretch outside
        every annotation began.  The program that began last before the gap
        stays in every name.  A gap under ``HOST_GAP_NS`` is the device's
        own, and without a shared clock a wrong name is worse than none:
        both keep ``unattributed_after:<program>``."""
        if ns < HOST_GAP_NS or not self.annotations:
            return f"unattributed_after:{prog}"
        end, over, shortest = start + ns, {}, {}
        for name, s, d, _ in self.annotations:
            if s < end and s + d > start:
                over.setdefault(name, []).append((s, s + d))
                shortest[name] = min(d, shortest.get(name, d))
        cover = max(((overlap_ns(ivs, start, end), -shortest[name], name)
                     for name, ivs in over.items()), default=None)
        if cover and 2 * cover[0] >= ns:
            return f"{cover[2]}/after:{prog}"
        covered = merged((max(s, start), min(e, end)) for ivs in over.values() for s, e in ivs)
        edges = [start, *(t for iv in covered for t in iv), end]
        bare = max(zip(edges[::2], edges[1::2]), key=lambda ab: ab[1] - ab[0])[0]
        loop = self.loop_thread()
        ended = [(s + d, name) for name, s, d, thread in self.annotations
                 if thread == loop and s + d <= bare]
        last = max(ended)[1] if ended else "trace_start"
        return f"host_unannotated_after:{last}/after:{prog}"

    def loop_thread(self):
        """The thread of the engine loop: the one that holds most of the
        annotations that are no ``engine.dispatch:*`` (those are the worker
        thread's, inside the call that enqueues a program)."""
        counts: dict = {}
        for name, _, _, thread in self.annotations:
            if not name.startswith(DISPATCH_PREFIX):
                counts[thread] = counts.get(thread, 0) + 1
        return max(counts, key=counts.get) if counts else None

    def idle_named_share(self) -> float | None:
        """Of the idle time in gaps of ``HOST_GAP_NS`` or more, the percentage
        inside the union of the host's annotations; ``None`` without a
        shared clock or where those gaps hold under 1 ms together."""
        if not self.annotations:
            return None
        gaps = [(start, start + ns) for _, ns, start in self.gaps() if ns >= HOST_GAP_NS]
        idle = sum(b - a for a, b in gaps)
        if idle < 1_000_000:
            return None
        spans = merged((s, s + d) for _, s, d, _ in self.annotations)
        inside = sum(max(0, min(b, y) - max(a, x)) for a, b in gaps for x, y in spans)
        return 100.0 * inside / idle

    def breakdown(self, top: int = 10) -> dict:
        """The ops that took most device time (self time, summed by short
        name, averaged over devices) and the longest idle gaps of the first
        device, each named by what the host was doing in it (``name_gap``)."""
        n = max(1, self.n_devices)
        sums = sorted(sum_by_name(self.all_ops()).items(), key=lambda kv: -kv[1])[:top]
        named = [[self.name_gap(prog, ns, start), ns / 1e9] for prog, ns, start in self.gaps()[:top]]
        return {"device_ops": [[name, ns / n / 1e9] for name, ns in sums], "idle_gaps": named}
