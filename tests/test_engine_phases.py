"""The engine loop's account of its own time (engine/phases.py, ISSUE 41).

ONE fused session with churn on the tiny engine, under ``jax.profiler`` on
the CPU, read on both planes: the always-on account (``dispatch_summary``,
``/metrics``) and the annotations in the trace (read back as
``chipbench/run.py``'s rehearsal reads them).  What is held is an order, a
count or a ratio of two clock readings of ONE stretch, never a duration:
the phases of the loop's thread tile a session (their sums add up to its
wall, none overlaps another, nothing is left bare), every phase of the
account is in the trace under ``engine.<phase>``, and ``host_gap_frac`` is
made from the account.  Where the operating system takes the loop's thread
between two phases (the driver runs six workers on these cores) the glue it
stretches is a FEW long gaps: they are counted, and the sum is taken over
the others, so a phase left out of an iteration (a gap in every one) still
fails both tests of the tiling.
"""

import asyncio
import os
import re
import sys
import threading

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import trace_reduce  # noqa: E402
from dynamo_tpu.engine import EngineConfig  # noqa: E402
from dynamo_tpu.engine.engine import TpuEngine  # noqa: E402
from dynamo_tpu.engine.phases import DEVICE_CALLS, LE, LOOP_PHASES, PhaseAccount  # noqa: E402
from dynamo_tpu.llm.metrics import engine_dispatch_metrics  # noqa: E402
from test_continuous_batching import CFG, _churn  # noqa: E402

# Everything a session with churn and no speculation goes through.
SEEN = tuple(p for p in LOOP_PHASES if p != "harvest:spec")
CALLS_SEEN = tuple(c for c in DEVICE_CALLS if c != "fetch:spec")
# The four names the benchmark read before the account (chipbench/README.md).
OLD_NAMES = ("engine.schedule", "engine.dispatch:decode", "engine.harvest:decode", "engine.emit")
SESSION = "engine.test_session"  # (trace_reduce keeps what begins with "engine.")
# A gap between two phases longer than this is the operating system's (the
# glue is 3 us of Python) or a hole in the tiling: the first are few.
LONG_GAP_NS = 200_000


def _few(gaps: list) -> int:
    """How many long gaps the operating system may account for: one in fifty
    (a hole in the tiling shows in every iteration, one gap in a dozen)."""
    return max(1, len(gaps) // 50)


def _loop_total(engine) -> float:
    return sum(row["sum"] for row in engine.phases.summary()["loop"].values())


def _record_sessions(engine) -> list:
    """Per fused session: growth of the loop phases' sums, of the time in
    ``harvest:*`` and of ``pipeline_wall_s``; on the profiler's clock the
    session is the span ``SESSION`` (the test's own, around the engine's)."""
    from jax.profiler import TraceAnnotation

    grown, run = [], engine._decode_pipeline

    async def session(members):
        t0, h0, w0 = _loop_total(engine), engine.phases.waited_s(), engine.pipeline_wall_s
        try:
            with TraceAnnotation(SESSION):
                return await run(members)
        finally:
            grown.append((_loop_total(engine) - t0, engine.phases.waited_s() - h0,
                          engine.pipeline_wall_s - w0))

    engine._decode_pipeline = session
    return grown


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    import jax

    trace_dir = str(tmp_path_factory.mktemp("phases_trace"))

    async def main():
        engine = TpuEngine(EngineConfig(**CFG))
        try:
            await asyncio.to_thread(engine.warmup)
            engine_dispatch_metrics.set_source(engine.dispatch_summary)
            # The tiny model's step takes a hundredth of any served model's,
            # so beside it the glue between two phases (3 us of Python) is
            # not small; the pace hook, awaited inside a phase before every
            # device op, stands for a device that takes its time.
            engine.pace_hook = lambda: asyncio.sleep(0.002)
            sessions = _record_sessions(engine)
            join, join_calls = engine._join_fn, []

            def join_fn(*a):
                join_calls.append(1)
                return join(*a)

            join_fn._cache_size = join._cache_size
            engine.__dict__["_join_fn"] = join_fn
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            try:
                await _churn(engine, temperature=0.0, n=8)
            finally:
                jax.profiler.stop_trace()
            return {"summary": engine.dispatch_summary(), "sessions": sessions,
                    "join_calls": len(join_calls),
                    "text": engine_dispatch_metrics.render(),
                    "waited_s": engine.pipeline_waited_s, "wall_s": engine.pipeline_wall_s}
        finally:
            engine_dispatch_metrics.reset()
            await engine.close()

    out = asyncio.run(main())
    _, _, host = trace_reduce.load_xplane(
        trace_reduce.find_xplane(trace_dir), re.compile(r"^/host:CPU$"), lines=None)
    spans = sorted(host["annotations"], key=lambda e: e[1])
    out["annotations"] = [e for e in spans if e[0] != SESSION]
    out["session_spans"] = [e for e in spans if e[0] == SESSION]
    return out


def _loop_thread(annotations) -> list:
    """The loop's thread's annotations by start: the thread that holds the
    ``engine.retire`` phases."""
    threads = {t for n, _, _, t in annotations if n == "engine.retire"}
    assert len(threads) == 1, threads
    return [e for e in annotations if e[3] in threads]


# ------------------------------------------------------------------ the account
def test_every_tiling_phase_of_a_session_with_churn_is_observed(traced):
    loop = traced["summary"]["phases"]["loop"]
    assert tuple(loop) == LOOP_PHASES
    assert [p for p in SEEN if loop[p]["count"] == 0] == []
    assert loop["harvest:spec"]["count"] == 0
    for row in loop.values():
        assert sum(row["buckets"]) == row["count"] and len(row["buckets"]) == len(LE) + 1


def test_the_phases_of_a_session_add_up_to_its_wall(traced):
    assert traced["sessions"], "no fused session ran"
    for phases_s, _, wall_s in traced["sessions"]:
        # no phase inside another: the sums cannot pass the wall they tile
        assert 0 < phases_s <= wall_s * (1 + 1e-6), (phases_s, wall_s)
    # What the wall holds beside the phases is the glue between them.  The
    # same seams are on the profiler's clock (the account's clock reads are
    # around the annotation, so a seam there is no shorter): the few that the
    # operating system stretched are counted there and taken off the sum.
    assert len(traced["session_spans"]) == len(traced["sessions"])
    loop = _loop_thread(traced["annotations"])
    gaps = []
    for _, s0, d0, _ in traced["session_spans"]:
        inside = [e for e in loop if e[1] >= s0 and e[1] + e[2] <= s0 + d0]
        edges = [(s0, s0)] + [(s, s + d) for _, s, d, _ in inside] + [(s0 + d0, s0 + d0)]
        gaps += [b[0] - a[1] for a, b in zip(edges, edges[1:])]
    long_gaps = [g for g in gaps if g > LONG_GAP_NS]
    assert len(long_gaps) <= _few(gaps), sorted(gaps)[-5:]
    wall_s = sum(w for _, _, w in traced["sessions"])
    glue_s = wall_s - sum(p for p, _, _ in traced["sessions"])
    assert glue_s - sum(long_gaps) * 1e-9 <= 0.02 * wall_s, (glue_s, long_gaps, wall_s)


def test_one_enqueue_and_one_jitted_call_a_fused_chunk(traced):
    s = traced["summary"]
    chunks = s["kinds"]["decode_dispatch"]["dispatches"]
    assert s["phases"]["loop"]["enqueue:decode"]["count"] == chunks > 0
    assert s["phases"]["calls"]["dispatch:decode"]["count"] == chunks
    assert s["phases"]["calls"]["fetch:decode"]["count"] == chunks
    steps = s["kinds"].get("unified", {"dispatches": 0})["dispatches"] \
        + s["kinds"]["unified_fetch"]["dispatches"]
    assert s["phases"]["loop"]["enqueue:unified"]["count"] == steps
    assert s["phases"]["loop"]["prompt_build"]["count"] == steps
    assert s["phases"]["calls"]["dispatch:unified"]["count"] == steps
    # the jitted call lies inside the loop phase that waits for it (a fetch
    # begins an iteration before its wait does, so it may outlast it)
    for call, phase in (("dispatch:decode", "enqueue:decode"), ("dispatch:unified", "enqueue:unified")):
        assert 0 < s["phases"]["calls"][call]["sum"] <= s["phases"]["loop"][phase]["sum"]


def test_the_merge_phase_is_entered_once_a_join_and_the_counter_counts_each_kind(traced):
    """Phase ``merge`` is the host's cost of a join of either kind: one pass
    for each device-side join (slots, the row's sampling scalars, the small
    program's enqueue) and one for each chain-break merge, each of which may
    take several rows.  ``dynamo_tpu_engine_joins_total{how=}`` counts rows."""
    joins = traced["summary"]["pipeline"]["joins"]
    assert joins["device"] >= 1 and joins["break"] >= 1, joins
    assert 1 <= traced["join_calls"] <= joins["device"]
    merges = traced["summary"]["phases"]["loop"]["merge"]["count"]
    assert traced["join_calls"] + 1 <= merges <= traced["join_calls"] + joins["break"]
    for how, n in joins.items():
        assert f'dynamo_tpu_engine_joins_total{{how="{how}"}} {n}' in traced["text"]
    assert "# TYPE dynamo_tpu_engine_joins_total counter" in traced["text"]


def test_host_gap_frac_is_made_from_the_account_and_counts_a_second_once(traced):
    pipe = traced["summary"]["pipeline"]
    waited = sum(w for _, w, _ in traced["sessions"])
    assert traced["waited_s"] == pytest.approx(waited, abs=1e-9)
    assert 0.0 < traced["waited_s"] < traced["wall_s"]
    assert pipe["host_gap_frac"] == round(1.0 - traced["waited_s"] / traced["wall_s"], 4)
    assert 0.0 <= pipe["host_gap_frac"] <= 1.0


def test_metrics_hold_both_histograms_with_the_seven_buckets(traced):
    text = traced["text"]
    assert "# TYPE dynamo_tpu_engine_loop_phase_seconds histogram" in text
    assert "# TYPE dynamo_tpu_engine_device_call_seconds histogram" in text
    les = ["0.001", "0.004", "0.016", "0.064", "0.256", "1.024", "+Inf"]
    for series, label, names in (("dynamo_tpu_engine_loop_phase_seconds", "phase", LOOP_PHASES),
                                 ("dynamo_tpu_engine_device_call_seconds", "call", DEVICE_CALLS)):
        for name in names:
            rows = re.findall(
                rf'^{series}_bucket\{{{label}="{re.escape(name)}",le="([^"]+)"\}} (\d+)$', text, re.M)
            assert [le for le, _ in rows] == les, (name, rows)
            counts = [int(n) for _, n in rows]
            assert counts == sorted(counts)  # cumulative
            count = re.search(rf'^{series}_count\{{{label}="{re.escape(name)}"\}} (\d+)$', text, re.M)
            assert count and int(count.group(1)) == counts[-1]
            assert re.search(rf'^{series}_sum\{{{label}="{re.escape(name)}"\}} [0-9.e+-]+$', text, re.M)
    assert "dynamo_tpu_engine_dispatch_host_gap_frac" in text
    assert "decode_busy" not in text and "loop_gap" not in text


# -------------------------------------------------------------------- the trace
def test_every_phase_of_the_account_is_in_the_trace_under_its_name(traced):
    names = {n for n, _, _, _ in traced["annotations"]}
    assert names == {"engine." + p for p in SEEN + CALLS_SEEN}
    assert set(OLD_NAMES) <= names
    by_name = {}
    for n, _, _, _ in traced["annotations"]:
        by_name[n] = by_name.get(n, 0) + 1
    account = {**traced["summary"]["phases"]["loop"], **traced["summary"]["phases"]["calls"]}
    for phase in SEEN + CALLS_SEEN:
        # the trace began after warmup and ended with the last stream: it holds
        # the account's observations but for the loop's last passes behind it
        assert 0 < by_name["engine." + phase] <= account[phase]["count"], phase


def test_the_loop_threads_phases_tile_no_two_overlap_and_nothing_is_left_bare(traced):
    loop = _loop_thread(traced["annotations"])
    on_loop = {n for n, _, _, _ in loop}
    assert on_loop == {"engine." + p for p in SEEN}, on_loop
    # the worker threads' calls are on other threads
    assert not any(n in on_loop for n in ("engine." + c for c in CALLS_SEEN))
    # a session: from the first fused chunk's enqueue to the last accept
    first = min(s for n, s, _, _ in loop if n == "engine.enqueue:decode")
    last = max(s + d for n, s, d, _ in loop if n == "engine.emit")
    inside = [e for e in loop if e[1] >= first and e[1] + e[2] <= last]
    assert len(inside) > 50
    bare = []
    for (_, s0, d0, _), (n1, s1, _, _) in zip(inside, inside[1:]):
        assert s1 >= s0 + d0, f"{n1} begins inside the phase before it"
        bare.append(s1 - (s0 + d0))
    # A hole in the tiling shows in every iteration (one gap in a dozen); the
    # operating system taking the thread between two phases shows once.
    assert sum(1 for b in bare if b > LONG_GAP_NS) <= _few(bare), sorted(bare)[-5:]
    short = [b for b in bare if b <= LONG_GAP_NS]
    assert sum(short) <= 0.02 * (last - first), (sum(short), last - first)


def test_a_device_call_lies_inside_the_loop_phase_that_waits_for_it(traced):
    loop = _loop_thread(traced["annotations"])
    for call, phase in (("engine.dispatch:decode", "engine.enqueue:decode"),
                        ("engine.dispatch:unified", "engine.enqueue:unified")):
        outer = [(s, s + d) for n, s, d, _ in loop if n == phase]
        calls = [(s, s + d) for n, s, d, _ in traced["annotations"] if n == call]
        assert calls
        for a, b in calls:
            assert any(x <= a and b <= y for x, y in outer), (call, a, b)


# ------------------------------------------------------------------- the helper
def test_an_unknown_phase_is_refused_and_a_bound_is_inclusive():
    account = PhaseAccount()
    with pytest.raises(KeyError):
        account.phase("whole_iteration")
    row = account._rows["retire"]
    with account.phase("retire"):
        pass
    assert row.count == 1 and row.buckets[0] == 1 and 0.0 <= row.sum < 0.001
    assert account.summary()["loop"]["retire"]["buckets"] == row.buckets
    assert account.waited_s() == 0.0


def test_two_threads_ending_one_call_at_once_lose_no_observation():
    account = PhaseAccount()
    n, per = 16, 2000
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)

    def work():
        for _ in range(per):
            with account.phase("fetch:first"):
                pass

    try:
        threads = [threading.Thread(target=work) for _ in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    row = account.summary()["calls"]["fetch:first"]
    assert row["count"] == n * per == sum(row["buckets"])


def test_one_helper_is_the_only_caller_of_trace_annotation_in_the_engine():
    engine_dir = os.path.join(ROOT, "dynamo_tpu", "engine")
    users = sorted(
        f for f in os.listdir(engine_dir) if f.endswith(".py")
        and "TraceAnnotation" in open(os.path.join(engine_dir, f)).read())
    assert users == ["phases.py"]
    for gone in ("decode_busy_s", "loop_gap_max"):
        hits = [os.path.join(d, f) for d, _, fs in os.walk(os.path.join(ROOT, "dynamo_tpu"))
                for f in fs if f.endswith(".py") and gone in open(os.path.join(d, f)).read()]
        assert hits == [], (gone, hits)
