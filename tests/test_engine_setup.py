"""The start's account (engine/phases.py ``SetupAccount``, engine/xla_cache.py
``compile_stages``; ISSUE 58): every stretch from the process's start to
``ready`` is inside exactly one named phase, with JAX's own trace, lower,
compile and cache seconds beside it.

What is held is a construction, a count or an order, never a duration: the
phases tile because a phase's closing clock read IS the next one's opening
read (a counted clock shows it), ``warmup_seconds`` is the ``warm:*`` sum,
``/metrics`` holds every phase once, the listener folds four events and no
other, and the start gained no wait (``block_until_ready``, ``device_get``,
``np.asarray``) over the tree before it.
"""

import asyncio
import inspect
import os
import re
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.request

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import promtext, trace_reduce  # noqa: E402
from dynamo_tpu.engine import EngineConfig, build_tpu_engine  # noqa: E402
from dynamo_tpu.engine import phases, xla_cache  # noqa: E402
from dynamo_tpu.engine.engine import TpuEngine  # noqa: E402
from dynamo_tpu.engine.phases import SETUP_PHASES, SetupAccount  # noqa: E402
from dynamo_tpu.llm.http_service import HttpService  # noqa: E402
from dynamo_tpu.llm.metrics import engine_dispatch_metrics  # noqa: E402
from test_continuous_batching import CFG  # noqa: E402

EN = "dynamo_tpu_engine"
# A start as the serving process walks it: (phase, thread it is entered on).
WALK = (("build:params", "main"), ("build:other", "main"), ("build:cache", "main"),
        ("build:other", "main"), ("warm:walk", "worker"), ("warm:lower", "worker"),
        ("warm:compile", "worker"), ("warm:walk", "worker"), ("serve:listen", "worker"))


class CountedClock:
    """``perf_counter`` that says how often it was read; it steps by a
    quarter, so sums of its differences are exact in floating point."""

    def __init__(self):
        self.reads = []

    def __call__(self) -> float:
        self.reads.append(0.25 * len(self.reads))
        return self.reads[-1]


def _walk(account: SetupAccount) -> None:
    def on_worker():
        for phase, where in WALK:
            if where == "worker":
                account.enter(phase)

    for phase, where in WALK:
        if where == "main":
            account.enter(phase)
    t = threading.Thread(target=on_worker)
    t.start()
    t.join(timeout=60)
    assert not t.is_alive()
    account.mark_ready()


# ------------------------------------------------------------------- the tiling
def test_a_phases_closing_clock_read_is_the_next_ones_opening_read(monkeypatch):
    clock = CountedClock()
    monkeypatch.setattr(phases.time, "perf_counter", clock)
    account = SetupAccount()
    _walk(account)
    # one read to open the first phase, one a seam, one at ready: no pair
    assert len(clock.reads) == 1 + len(WALK) + 1
    summary = account.summary()
    assert summary["ready"] is True
    assert summary["seconds"] == clock.reads[-1] - clock.reads[0]
    assert summary["seconds"] == sum(p["sum"] for p in summary["phases"].values())
    seen = {p: r["count"] for p, r in summary["phases"].items() if r["count"]}
    assert seen == {"build:params": 1, "build:cache": 1, "build:other": 3, "warm:lower": 1,
                    "warm:compile": 1, "warm:walk": 2, "serve:listen": 1}
    assert account.warm_s() == sum(
        summary["phases"][p]["sum"] for p in ("warm:lower", "warm:compile", "warm:walk"))


def test_the_table_is_fixed_and_an_unknown_phase_is_refused():
    account = SetupAccount()
    assert tuple(account.summary()["phases"]) == SETUP_PHASES
    for unknown in ("warmup", "retire", "build"):
        with pytest.raises(KeyError):
            account.enter(unknown)
    # the refusal moved nothing: the phase that was open still is
    account.mark_ready()
    assert account.summary()["phases"]["build:other"]["count"] == 1


def test_after_ready_the_account_is_static():
    account = SetupAccount()
    account.enter("warm:walk")
    account.mark_ready()
    before = account.summary()
    account.enter("warm:walk")  # a later warmup() is no part of the start
    account.mark_ready()
    time.sleep(0.002)
    assert account.summary() == before and before["ready"] is True


def test_only_the_first_account_of_a_process_holds_import(monkeypatch):
    monkeypatch.setattr(phases, "_import_claimed", False)
    first = SetupAccount(from_process_start=True)
    second = SetupAccount(from_process_start=True)
    built_directly = SetupAccount()
    row = first.summary()["phases"]["import"]
    assert row["count"] == 1 and row["sum"] > 0.0
    # process start as the operating system has it: before this module ran
    assert first.summary()["process_start_time"] < phases._T_IMPORTED <= time.time()
    for other in (second, built_directly):
        assert other.summary()["phases"]["import"] == {"sum": 0.0, "count": 0}


def test_the_spans_tile_on_the_profilers_clock_across_threads(tmp_path):
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        account = SetupAccount()
        _walk(account)
    finally:
        jax.profiler.stop_trace()
    _, _, host = trace_reduce.load_xplane(
        trace_reduce.find_xplane(str(tmp_path)), re.compile(r"^/host:CPU$"), lines=None)
    spans = sorted((e for e in host["annotations"] if e[0].startswith("engine.setup:")),
                   key=lambda e: e[1])
    assert [n for n, _, _, _ in spans] == [
        "engine.setup:" + p for p in ("build:other",) + tuple(p for p, _ in WALK)]
    for (_, s0, d0, _), (n1, s1, _, _) in zip(spans, spans[1:]):
        assert s1 >= s0 + d0, f"{n1} begins inside the phase before it"


# ------------------------------------------------------------------- the engine
@pytest.fixture(scope="module")
def started():
    """A tiny engine through its whole start, with the side-by-side pass
    (which a CPU engine skips for want of a cache directory) switched in."""
    engine = TpuEngine(EngineConfig(**CFG))
    try:
        engine.compile_cache_dir = "(none: the pass only reads whether there is one)"
        engine.warmup()
        open_summary = engine.device_summary()
        engine.setup.mark_ready()
        engine_dispatch_metrics.set_source(engine.dispatch_summary)
        yield {"engine": engine, "open": open_summary, "summary": engine.device_summary(),
               "text": engine_dispatch_metrics.render()}
    finally:
        engine_dispatch_metrics.reset()
        asyncio.run(engine.close())


def test_an_engine_built_directly_records_its_own_build_and_warm_phases(started):
    rows = started["summary"]["setup"]["phases"]
    entered = {p for p, r in rows.items() if r["count"]}
    assert entered == {"build:params", "build:cache", "build:other", "warm:lower",
                       "warm:compile", "warm:walk", "serve:listen"}
    assert rows["warm:walk"]["count"] == 2 and rows["build:other"]["count"] == 3
    assert started["summary"]["setup"]["ready"] and not started["open"]["setup"]["ready"]


def test_warmup_seconds_is_the_sum_of_the_warm_phases(started):
    rows = started["summary"]["setup"]["phases"]
    warm = sum(rows[p]["sum"] for p in SETUP_PHASES if p.startswith("warm:"))
    assert warm > 0.0 and started["summary"]["warmup_s"] == round(warm, 3)
    parsed = promtext.parse(started["text"])
    on_metrics = sum(promtext.value(parsed, f"{EN}_setup_phase_seconds", {"phase": p})
                     for p in SETUP_PHASES if p.startswith("warm:"))
    assert abs(promtext.value(parsed, f"{EN}_warmup_seconds") - on_metrics) <= 0.001


def test_metrics_hold_every_phase_once_with_help_and_type(started):
    text = started["text"]
    for series, kind in ((f"{EN}_setup_phase_seconds", "gauge"), (f"{EN}_setup_seconds", "gauge"),
                         ("dynamo_tpu_process_start_time_seconds", "gauge"),
                         (f"{EN}_jax_compile_seconds_total", "counter"),
                         (f"{EN}_jax_compile_events_total", "counter"),
                         (f"{EN}_compile_cache_hits", "gauge"),
                         (f"{EN}_compile_cache_misses", "gauge")):
        assert text.count(f"# HELP {series} ") == 1, series
        assert text.count(f"# TYPE {series} {kind}\n") == 1, series
    lines = [ln for ln in text.splitlines() if ln.startswith(f"{EN}_setup_phase_seconds{{")]
    assert [ln.split('"')[1] for ln in lines] == list(SETUP_PHASES)
    parsed = promtext.parse(text)
    total = promtext.value(parsed, f"{EN}_setup_seconds")
    assert abs(total - promtext.value(parsed, f"{EN}_setup_phase_seconds")) < 1e-9
    assert total == started["summary"]["setup"]["seconds"]
    for stage in xla_cache.STAGE_OF_EVENT.values():
        for series in ("seconds", "events"):
            assert promtext.value(
                parsed, f"{EN}_jax_compile_{series}_total", {"stage": stage}) is not None
    # what the two older gauges count is in their HELP
    assert "read back from the persistent compilation cache" in text
    assert "compiled anew and written to the persistent compilation cache" in text


def test_the_account_on_metrics_is_that_of_the_engine_the_source_is_set_to(started):
    other = SetupAccount()
    other.enter("build:params")
    other.mark_ready()
    assert other.summary()["seconds"] != started["summary"]["setup"]["seconds"]
    parsed = promtext.parse(engine_dispatch_metrics.render())
    assert promtext.value(parsed, f"{EN}_setup_seconds") == started["summary"]["setup"]["seconds"]


def test_the_log_line_the_account_replaces_is_gone():
    src = inspect.getsource(TpuEngine._compile_side_by_side) + inspect.getsource(TpuEngine.warmup)
    assert "logger.info" not in src and "time.monotonic" not in src
    assert "side by side in" not in open(os.path.join(ROOT, "docs", "tracing.md")).read()


# Waits in the start's source on the tree before the account (PR 57): the
# account reads clocks and adds none (docs/tracing.md, the contract).
WAITS_BEFORE = [
    (build_tpu_engine, 0), (TpuEngine.__init__, 2), (TpuEngine.warmup, 4),
    (TpuEngine._compile_side_by_side, 0), (SetupAccount.enter, 0),
    (SetupAccount.mark_ready, 0), (phases._Timed.open, 0), (phases._Timed.close, 0),
]


@pytest.mark.parametrize("fn,before", WAITS_BEFORE, ids=[f.__qualname__ for f, _ in WAITS_BEFORE])
def test_the_start_gained_no_wait_for_the_device(fn, before):
    waits = re.findall(r"block_until_ready|device_get|np\.asarray\(|jax\.block", inspect.getsource(fn))
    assert len(waits) == before, waits


# ------------------------------------------------------------ JAX's own seconds
@pytest.fixture
def stages():
    """The table as it stood, put back afterwards (it is the process's)."""
    kept = {s: dict(r) for s, r in xla_cache.compile_stages.items()}
    yield xla_cache.compile_stages
    for s, r in kept.items():
        xla_cache.compile_stages[s].update(r)


@pytest.mark.parametrize("event,stage", sorted(xla_cache.STAGE_OF_EVENT.items()))
def test_the_listener_folds_each_of_the_four_events_into_its_stage(stages, event, stage):
    before = {s: dict(r) for s, r in stages.items()}
    xla_cache._on_duration(event, 0.5, fun_name="step")
    xla_cache._on_duration(event, 0.25)
    for s, row in stages.items():
        grew = (row["seconds"] - before[s]["seconds"], row["events"] - before[s]["events"])
        assert grew == ((0.75, 2) if s == stage else (0.0, 0)), s


@pytest.mark.parametrize("event", [
    "/jax/compilation_cache/compile_time_saved_sec",
    "/jax/core/compile/jaxpr_trace_duration/", "/jax/checkpoint/write/durations_sec", ""])
def test_the_listener_ignores_every_other_event(stages, event):
    before = {s: dict(r) for s, r in stages.items()}
    xla_cache._on_duration(event, 3.0)
    assert {s: dict(r) for s, r in stages.items()} == before


def test_registering_twice_counts_an_event_once(stages):
    import jax

    xla_cache.setup_compilation_cache()
    xla_cache.setup_compilation_cache()  # a second engine in the process
    row = stages["backend_compile"]
    n, s = row["events"], row["seconds"]
    jax.monitoring.record_event_duration_secs("/jax/core/compile/backend_compile_duration", 2.0)
    assert (row["events"] - n, row["seconds"] - s) == (1, 2.0)
    hits = xla_cache.cache_events["hits"]
    jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
    assert xla_cache.cache_events["hits"] == hits + 1
    xla_cache.cache_events["hits"] = hits


def test_a_compile_shows_in_the_stages_it_paid():
    import jax
    import jax.numpy as jnp

    xla_cache.setup_compilation_cache()
    before = {s: dict(r) for s, r in xla_cache.compile_stages.items()}
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(7))
    for stage in ("trace", "lower", "backend_compile"):
        row = xla_cache.compile_stages[stage]
        assert row["events"] > before[stage]["events"] and row["seconds"] > before[stage]["seconds"]


# ------------------------------------------------------------------ the service
async def test_the_service_says_when_it_accepts():
    service = HttpService(host="127.0.0.1", port=0)
    stop, seen = asyncio.Event(), []

    def on_listening():
        with socket.create_connection(("127.0.0.1", service.port), timeout=5):
            seen.append(service.port)
        stop.set()

    await asyncio.wait_for(service.run(stop, on_listening=on_listening), timeout=30)
    assert seen and seen[0] != 0


def test_a_served_start_is_ready_and_its_phases_add_up_to_process_start_to_ready(tmp_path):
    """``cli run in=http out=tpu`` as an operator types it, on the tiny model:
    the account opens at the operating system's start of the process
    (``import``) and closes where the service accepts."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    t_spawn = time.time()
    proc = subprocess.Popen(
        [sys.executable, "-m", "dynamo_tpu.cli", "run", "in=http", "out=tpu", "--arch",
         "debug-tiny", "--dtype", "float32", "--port", str(port), "--max-model-len", "256",
         "--prefill-chunk", "32", "--max-batch", "2", "--num-blocks", "64"],
        cwd=str(tmp_path), env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    try:
        text, t_end = None, time.time() + 240
        while time.time() < t_end and proc.poll() is None:
            try:
                with urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics", timeout=2) as r:
                    text = r.read().decode()
                    break
            except OSError:
                time.sleep(0.2)
        t_scraped = time.time()
        assert text is not None, f"the server exited {proc.poll()} or never listened"
    finally:
        if proc.poll() is None:
            proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=30)
    parsed = promtext.parse(text)
    by_phase = {p: promtext.value(parsed, f"{EN}_setup_phase_seconds", {"phase": p})
                for p in SETUP_PHASES}
    for phase in ("import", "build:params", "build:cache", "build:other", "warm:walk",
                  "serve:listen"):
        assert by_phase[phase] > 0.0, phase
    total = promtext.value(parsed, f"{EN}_setup_seconds")
    assert abs(total - sum(by_phase.values())) < 1e-6
    # ready, seen from outside: the process was spawned before its start and
    # scraped after it was ready (an order of clock readings, not a duration)
    started = promtext.value(parsed, "dynamo_tpu_process_start_time_seconds")
    assert t_spawn - 0.05 <= started and started + total <= t_scraped + 0.05
    assert abs(promtext.value(parsed, f"{EN}_warmup_seconds")
               - sum(v for p, v in by_phase.items() if p.startswith("warm:"))) <= 0.001
