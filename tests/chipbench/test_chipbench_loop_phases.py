"""The ten metrics that read the engine loop's phase account (PR 41): each
file against its BENCHMARK.json entry, the one new reader on hand-written
Prometheus text, the two old readers on the account's series, and how a gap
is named once the loop's phases tile its thread (a worker thread's call is
nested in the loop phase that waits for it).  No JAX."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

from chipbench import loader, promtext  # noqa: E402
from chipbench.readers import prom_histogram_over, prom_label_share, prom_mean_delta  # noqa: E402
from test_chipbench_host_trace import _names, _trace  # noqa: E402

LOOP = "dynamo_tpu_engine_loop_phase_seconds"
CALL = "dynamo_tpu_engine_device_call_seconds"
BENCH = loader.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
# name: (unit, better, moves, reader, the label it reads)
NEW = {
    "loop_retire_ms": ("ms", "lower", "tpot_ms_p90", "prom_mean_delta", {"phase": "retire"}),
    "loop_merge_ms": ("ms", "lower", "tpot_ms_p90", "prom_mean_delta", {"phase": "merge"}),
    "loop_yield_ms": ("ms", "lower", "tpot_ms_p90", "prom_mean_delta", {"phase": "yield"}),
    "loop_enqueue_decode_ms": ("ms", "lower", "tpot_ms_p90", "prom_mean_delta",
                               {"phase": "enqueue:decode"}),
    "loop_admit_ms": ("ms", "lower", "ttft_ms_p50", "prom_mean_delta", {"phase": "admit"}),
    "loop_prompt_build_ms": ("ms", "lower", "ttft_ms_p50", "prom_mean_delta",
                             {"phase": "prompt_build"}),
    "loop_enqueue_unified_ms": ("ms", "lower", "ttft_ms_p50", "prom_mean_delta",
                                {"phase": "enqueue:unified"}),
    "dispatch_decode_call_ms": ("ms", "lower", "tpot_ms_p90", "prom_mean_delta",
                                {"call": "dispatch:decode"}),
    "loop_harvest_wait_share": ("%", "higher", "tpot_ms_p90", "prom_label_share",
                                {"phase": "harvest:decode"}),
    "loop_host_phases_over_64ms": ("count", "lower", "tpot_ms_p90", "prom_histogram_over", None),
}


def _histogram(series, label, rows):
    """Prometheus text of a histogram as llm/metrics.py prints it: ``rows`` is
    ``{label value: (sum, [observations a bucket, +Inf last])}``."""
    les = ["0.001", "0.004", "0.016", "0.064", "0.256", "1.024", "+Inf"]
    lines = [f"# TYPE {series} histogram"]
    for value, (total, buckets) in rows.items():
        seen = 0
        for le, n in zip(les, buckets):
            seen += n
            lines.append(f'{series}_bucket{{{label}="{value}",le="{le}"}} {seen}')
        lines.append(f'{series}_sum{{{label}="{value}"}} {total}')
        lines.append(f'{series}_count{{{label}="{value}"}} {seen}')
    return "\n".join(lines) + "\n"


BEFORE = _histogram(LOOP, "phase", {
    "retire": (0.010, [10, 0, 0, 0, 0, 0, 0]),
    "yield": (0.300, [5, 0, 0, 0, 1, 0, 0]),           # one pass over 64 ms BEFORE the window
    "harvest:decode": (1.000, [0, 0, 50, 0, 0, 0, 0]),
}) + _histogram(CALL, "call", {"dispatch:decode": (0.100, [0, 40, 0, 0, 0, 0, 0])})
AFTER = _histogram(LOOP, "phase", {
    "retire": (0.030, [19, 1, 0, 0, 0, 0, 0]),          # 10 more passes, 2 ms each
    "yield": (0.700, [8, 0, 0, 1, 1, 2, 0]),            # 3 + one of 60 ms + two over 64 ms
    "merge": (0.200, [0, 0, 0, 0, 0, 0, 1]),            # absent before: counts from zero
    "harvest:decode": (9.070, [0, 0, 90, 9, 1, 0, 0]),  # a wait of over 64 ms is the device's
}) + _histogram(CALL, "call", {"dispatch:decode": (0.400, [0, 90, 10, 0, 0, 0, 0])})


def _ctx():
    return {"before": promtext.parse(BEFORE), "after": promtext.parse(AFTER)}


def _spec(name):
    return loader.read_json(loader.data_file("layer_metrics", name))


def test_ten_entries_are_appended_to_per_layer_for_every_cell():
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names[-len(NEW):] == list(NEW)
    for cell in CELLS:
        reported = {m["name"] for m in loader.load_cell(cell)["per_layer"]}
        assert set(NEW) <= reported, cell


@pytest.mark.parametrize("name", list(NEW))
def test_metric_file_matches_its_entry_and_reads_the_account(name):
    unit, better, moves, reader, labels = NEW[name]
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert entry == {"name": name, "unit": unit, "better": better, "source": "program_counter",
                     "layer": "engine loop", "moves": moves}
    spec = _spec(name)
    assert (spec["name"], spec["unit"], spec["layer"], spec["moves"], spec["reader"]) == (
        name, unit, "engine loop", moves, reader)
    assert "engine/phases.py" in spec["about"] or "dynamo_tpu_engine" in spec["about"]
    loader.load_reader(reader)
    if labels is not None:
        assert spec["args"]["labels"] == labels
        series = CALL if "call" in labels else LOOP
        assert all(v.startswith(series) for k, v in spec["args"].items() if k.endswith("series"))


def test_a_phase_s_mean_is_growth_of_its_sum_over_growth_of_its_count():
    assert prom_mean_delta.read(_ctx(), **_spec("loop_retire_ms")["args"]) == pytest.approx(2.0)
    assert prom_mean_delta.read(_ctx(), **_spec("loop_merge_ms")["args"]) == pytest.approx(200.0)
    assert prom_mean_delta.read(_ctx(), **_spec("dispatch_decode_call_ms")["args"]) \
        == pytest.approx(5.0)
    # a phase the window never went through, and a program without the account
    assert prom_mean_delta.read(_ctx(), **_spec("loop_admit_ms")["args"]) is None
    empty = {"before": {}, "after": {}}
    for name in NEW:
        spec = _spec(name)
        assert loader.load_reader(spec["reader"]).read(empty, **spec["args"]) is None, name


def test_harvest_wait_share_is_over_every_phase_s_sum_and_not_over_the_buckets():
    # growth: retire 0.02 + yield 0.4 + merge 0.2 + harvest:decode 8.07 = 8.69 s
    got = prom_label_share.read(_ctx(), **_spec("loop_harvest_wait_share")["args"])
    assert got == pytest.approx(100.0 * 8.07 / 8.69)


def test_passes_over_64_ms_are_counted_over_the_window_and_never_in_a_harvest():
    args = _spec("loop_host_phases_over_64ms")["args"]
    # yield: 3 passes over 64 ms after, 1 before: 2 in the window; merge: 1;
    # retire: none; harvest:decode's one is the device's and is skipped
    assert prom_histogram_over.read(_ctx(), **args) == 3.0
    assert prom_histogram_over.read(_ctx(), **dict(args, skip_prefix="")) == 4.0
    assert prom_histogram_over.read(_ctx(), **dict(args, le="0.016")) == 4.0
    quiet = {"before": promtext.parse(AFTER), "after": promtext.parse(AFTER)}
    assert prom_histogram_over.read(quiet, **args) == 0.0
    # a bound the histogram does not have reads nothing, not a count
    assert prom_histogram_over.read(_ctx(), **dict(args, le="0.05")) is None


# ------------------------------------------------- a gap under a tiled loop thread
def _tiled(notes):
    """The default device of ``_trace`` (idle 1000-3000 and 4000-9000 us) with
    a dispatch annotation of the test's own around each enqueue."""
    return _trace(notes, dispatches="given")


def test_a_gap_inside_the_jitted_call_keeps_its_name_though_the_loop_s_phase_covers_it_too():
    # The call (worker) covers the whole 2 ms gap; so does the loop's enqueue
    # phase around it: alike, and the nested one names it.
    trace = _tiled([
        ("engine.enqueue:decode", -950, -20, "loop"), ("engine.dispatch:decode", -900, -50, "worker"),
        ("engine.enqueue:decode", 900, 3010, "loop"), ("engine.dispatch:decode", 950, 3005, "worker"),
        ("engine.enqueue:decode", 8000, 9010, "loop"), ("engine.dispatch:decode", 8100, 8950, "worker"),
    ])
    assert trace.clock()["ok"]
    assert _names(trace)[:2] == ["host_unannotated_after:engine.enqueue:decode/after:jit__multi",
                                 "engine.dispatch:decode/after:jit__multi"]


def test_a_gap_that_reaches_over_the_thread_hop_takes_the_loop_s_phase():
    # The loop is in enqueue:decode for the whole 5 ms gap (the hop to the
    # worker thread took 3.5 of them); the call itself covers its last 900 us.
    trace = _tiled([
        ("engine.enqueue:decode", -950, -20, "loop"), ("engine.dispatch:decode", -900, -50, "worker"),
        ("engine.enqueue:decode", 2050, 3010, "loop"), ("engine.dispatch:decode", 2100, 2950, "worker"),
        ("engine.enqueue:decode", 3900, 9010, "loop"), ("engine.dispatch:decode", 8100, 8950, "worker"),
    ])
    assert trace.clock()["ok"]
    assert _names(trace)[0] == "engine.enqueue:decode/after:jit__multi"


def test_a_tiled_break_is_named_by_the_phase_that_covers_over_half_or_by_none():
    # A chain break of 5 ms under a tiling: harvest's tail, emit, yield,
    # retire, merge, schedule, then the enqueue.  merge covers 2.6 ms of it.
    tiles = [("engine.harvest:decode", 3900, 4300), ("engine.emit", 4300, 4700),
             ("engine.yield", 4700, 4900), ("engine.retire", 4900, 5200),
             ("engine.merge", 5200, 7800), ("engine.schedule", 7800, 8000),
             ("engine.enqueue:decode", 8000, 9010)]
    calls = [("engine.dispatch:decode", -900, -50, "worker"), ("engine.dispatch:decode", 2100, 2950, "worker"),
             ("engine.dispatch:decode", 8100, 8950, "worker")]
    trace = _tiled([(n, a, b, "loop") for n, a, b in tiles] + calls)
    assert _names(trace)[0] == "engine.merge/after:jit__multi"
    # idle: 1 ms before the first program, 2 ms, 5 ms; covered: 850 us of each
    # of the first two (the calls), all of the third
    assert trace.idle_named_share() == pytest.approx(100.0 * (850 + 850 + 5000) / 8000)
    # No phase covers half: the reduction the benchmark has (PR 40) then calls
    # the gap unannotated although every microsecond of it is in a phase, and
    # names it by the phase before its first instant (PERF.md section 7).
    even = [("engine.harvest:decode", 3900, 4900), ("engine.emit", 4900, 5900),
            ("engine.retire", 5900, 6900), ("engine.merge", 6900, 8000),
            ("engine.enqueue:decode", 8000, 9010)]
    trace = _tiled([(n, a, b, "loop") for n, a, b in even] + calls)
    assert _names(trace)[0] == "host_unannotated_after:trace_start/after:jit__multi"
    assert trace.idle_named_share() == pytest.approx(100.0 * (850 + 850 + 5000) / 8000)
