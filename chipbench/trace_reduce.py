"""From a profiler trace to numbers: busy union, idle share, per-name sums,
idle gaps.  The arithmetic works on plain tuples, so it is tested on a small
recorded trace (tests/chipbench/fixtures) without JAX; only ``load_xplane``
touches ``jax.profiler``.

An event is ``(name, start_ns, dur_ns)``.  A device plane has, among others,
a line of XLA ops (one event per executed HLO op or kernel) and a line of XLA
modules (one event per executed jitted program).  Busy time is the union of
the op events; a program's device time is its module event.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE_RE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
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
    return gaps[:top]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load_xplane(path: str, plane_re=DEVICE_PLANE_RE, lines=(OPS_LINE, MODULES_LINE)):
    """``({plane name: {line name: [(event name, start_ns, dur_ns), ...]}},
    extent)`` for the planes whose name matches (device planes by default) and
    the lines named (``None``: every line).  ``extent`` is (first start, last
    end) over EVERY event of the file, host threads included: what the trace
    itself shows of when it was recording."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out: dict = {}
    first, last = None, None
    for plane in data.planes:
        keep = out.setdefault(plane.name, {}) if plane_re.match(plane.name) else None
        for line in plane.lines:
            evs = [(ev.name, int(ev.start_ns), int(ev.duration_ns)) for ev in line.events]
            if not evs:
                continue
            a, b = span_ns(evs)
            first = a if first is None else min(first, a)
            last = b if last is None else max(last, b)
            if keep is not None and (lines is None or line.name in lines):
                keep.setdefault(line.name, []).extend((short_name(n), s, d) for n, s, d in evs)
    return out, (first, last) if first is not None else None


class DeviceTrace:
    """The device side of one traced window, reduced once for the readers."""

    def __init__(self, planes: dict, t_start_s: float | None = None,
                 t_stop_s: float | None = None, extent=None):
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

    def breakdown(self, top: int = 10) -> dict:
        """The ops that took most device time (self time, summed by short
        name, averaged over devices) and the longest idle gaps of the first
        device.  Nothing on the host is traced against these gaps yet, so a
        gap is named only by the program that ran before it."""
        n = max(1, self.n_devices)
        sums = sorted(sum_by_name(self.all_ops()).items(), key=lambda kv: -kv[1])[:top]
        plane = next(iter(self.planes), None)
        gaps = idle_gaps(self.ops.get(plane, []), self.t0_ns, self.t1_ns, top)
        mods = sorted((s, name) for name, s, _ in self.modules.get(plane, []))
        named = []
        for _, ns, start in gaps:
            before = [name for s, name in mods if s <= start]
            prog = before[-1].split("(")[0] if before else "window_start"
            named.append([f"unattributed_after:{prog}", ns / 1e9])
        return {"device_ops": [[name, ns / n / 1e9] for name, ns in sums], "idle_gaps": named}
