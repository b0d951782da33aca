"""The host's side of a trace: the clocks measured and checked, every idle gap
named by the ``engine.*`` annotation it fell in, ``idle_gap_named_share``, on
a slice of a real traced window kept WITH its host events and on hand-made
traces; the prompt kernel's operations and roofline reader by hand (no JAX)."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import loader, run, shapes_mla_dense as sh, trace_reduce as tr  # noqa: E402
from chipbench.readers import (kernel_roofline_mla_dense_prefill as prefill_reader,  # noqa: E402
                               trace_idle_named_share, trace_module_percentile)

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")
PLANE = "/device:TPU:0"
US = 1000
CELLS = [w["name"] for w in loader.load_benchmark()["workloads"]]
NEW = ["idle_gap_named_share", "fused_chunk_device_ms_p50", "mla_dense_prefill_kernel_time_share",
       "mla_dense_prefill_roofline"]


def _planes(doc):
    return {p: {line: [(doc["names"][i], s, d) for i, s, d in evs] for line, evs in lines.items()}
            for p, lines in doc["planes"].items()}


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(FIXTURES, "trace_host_small.json")) as f:
        doc = json.load(f)
    host = {"annotations": [tuple(a) for a in doc["host"]["annotations"]],
            "launches": [tuple(pair) for pair in doc["host"]["launches"]]}
    return doc, tr.DeviceTrace(_planes(doc), doc["t_start_s"], doc["t_stop_s"],
                               tuple(doc["extent"]), host)


# ------------------------------------------------------------ hand-made traces
SKEW = 500  # us by which the host's clock is ahead of the device's


def _trace(notes, *, launches="measured", dispatches="paired", busy=None, mods=None):
    """A device that ran ``busy`` (us intervals, one op each) under ``mods``,
    and a host whose annotations ``notes`` are given ON THE DEVICE'S CLOCK as
    ``(name, start_us, end_us, thread)``; they are stored ``SKEW`` later, as a
    host ahead of the device would have written them.  By default: three fused
    decode programs with an idle gap of 2 ms and one of 5 ms between them, a
    launch record a program, and a dispatch annotation around each enqueue."""
    busy = busy or [(0, 1000), (3000, 4000), (9000, 10000)]
    mods = mods or [("jit__multi(7)", a, b) for a, b in busy]
    planes = {PLANE: {tr.OPS_LINE: [("fusion.1", a * US, (b - a) * US) for a, b in busy],
                      tr.MODULES_LINE: [(n, a * US, (b - a) * US) for n, a, b in mods]}}
    if dispatches == "paired":  # each call returns 50 us before its program begins
        notes = list(notes) + [("engine.dispatch:decode", a - 900, a - 50, "worker")
                               for _, a, _ in mods]
    host = {"annotations": [(n, (a + SKEW) * US, (b - a) * US, t) for n, a, b, t in notes],
            "launches": [(a * US, (a + SKEW) * US) for _, a, _ in mods]
            if launches == "measured" else launches}
    return tr.DeviceTrace(planes, 1.0, 1.011, (-1000 * US, 10000 * US), host)


def _names(trace):
    return [name for name, _ in trace.breakdown()["idle_gaps"]]


def test_the_clocks_skew_is_measured_on_the_launches_and_checked_on_the_annotations():
    trace = _trace([])
    clock = trace.clock()
    assert clock["ok"] and clock["skew_us"] == SKEW and clock["spread_us"] == 0
    # all three programs began on an idle device (the trace's first millisecond
    # holds none): each begins as it is enqueued
    assert clock["launches_after_a_gap"] == 3 and (clock["pairs"], clock["programs"]) == (3, 3)
    assert clock["lead_min_us"] == 900 and clock["lead_median_us"] == 900
    # the annotations are read on the device's clock
    assert [s for _, s, _, _ in trace.annotations] == [-900 * US, 2100 * US, 8100 * US]
    # the skew is the LARGEST distance back to an enqueue over the idle launches (no program
    # begins before it is enqueued), and what the others lag is the spread
    uneven = _trace([], launches=[(0, 500 * US), (3000 * US, 3380 * US), (9000 * US, 9450 * US)])
    assert uneven.clock()["skew_us"] == 500 and uneven.clock()["spread_us"] == 120


def test_either_tracer_may_outlive_the_other_and_the_programs_still_find_their_annotations():
    # dispatches whose programs had not begun when the trace stopped pair with nothing
    later = [("engine.dispatch:decode", 9200, 9500, "worker"), ("engine.dispatch:decode", 9600, 9900, "worker")]
    clock = _trace(later).clock()
    assert clock["ok"] and (clock["pairs"], clock["programs"]) == (3, 3)
    # a program whose dispatch preceded the host's trace, or followed its end, is left
    # unpaired: one of twelve may be
    busy = [(i * 1000, i * 1000 + 600) for i in range(12)]
    mods = [("jit__multi(7)", a, b) for a, b in busy]
    calls = [("engine.dispatch:decode", a - 300, a - 50, "worker") for a, _ in busy]
    assert _trace(calls[1:], dispatches=None, busy=busy, mods=mods).clock() == dict(
        _trace(calls, dispatches=None, busy=busy, mods=mods).clock(), pairs=11)
    cut = _trace(calls[:-1], dispatches=None, busy=busy, mods=mods)
    assert cut.clock()["ok"] and cut.clock()["pairs"] == 11
    assert _trace(calls[:-2], dispatches=None, busy=busy, mods=mods).clock()["ok"] is False
    # a program without a launch record (its enqueue fell outside the host's trace) is not counted
    known = [(a * US, (a + SKEW) * US) for a, _ in busy[:-1]]
    clock = _trace(calls, dispatches=None, busy=busy, mods=mods, launches=known).clock()
    assert clock["ok"] and (clock["pairs"], clock["programs"]) == (11, 11)


def test_a_gap_inside_one_annotation_takes_its_name():
    trace = _trace([("engine.harvest:decode", 500, 3200, "loop")])
    assert _names(trace)[:2] == ["host_unannotated_after:engine.harvest:decode/after:jit__multi",
                                 "engine.harvest:decode/after:jit__multi"]
    # the 5 ms gap holds the dispatch's 850 us alone: its bare stretch begins with the gap


def test_a_gap_across_three_annotations_is_named_by_the_one_that_covers_over_half():
    notes = [("engine.harvest:decode", 3500, 4500, "loop"),  # 0.5 of the 5 ms gap
             ("engine.emit", 4500, 5000, "loop"),            # 0.5
             ("engine.schedule", 5000, 8050, "loop")]        # 3.05: over half
    assert _names(_trace(notes))[0] == "engine.schedule/after:jit__multi"
    short = notes[:2] + [("engine.schedule", 5000, 7000, "loop")]  # 2.0: under half
    assert _names(_trace(short))[0] == "host_unannotated_after:engine.schedule/after:jit__multi"


def test_a_gap_in_no_annotation_is_named_by_the_loop_s_last_one_before_its_bare_stretch():
    notes = [("engine.harvest:decode", 3200, 4300, "loop"), ("engine.emit", 4300, 4600, "loop"),
             ("engine.schedule", 7800, 8000, "loop")]
    names = _names(_trace(notes))
    # 4.6 .. 7.8 ms is bare: after engine.emit; the dispatch on the worker thread is no loop phase
    assert names[0] == "host_unannotated_after:engine.emit/after:jit__multi"
    # nothing of the loop's ended before the 2 ms gap's bare stretch
    assert names[1] == "host_unannotated_after:trace_start/after:jit__multi"
    # the trace's first millisecond: the first program's enqueue covers 0.85 of it
    assert names[2:] == ["engine.dispatch:decode/after:window_start"]
    assert _trace(notes).loop_thread() == "loop"


def test_two_threads_overlapping_the_larger_cover_names_the_gap_and_the_nested_one_wins_a_tie():
    loop = ("engine.harvest:decode", 4000, 7000, "loop")               # 3.0 of 5
    worker = ("engine.dispatch:unified", 5000, 8800, "worker")         # 3.8 of 5
    assert _names(_trace([loop, worker]))[0] == "engine.dispatch:unified/after:jit__multi"
    outer = ("engine.harvest:decode", 3900, 9100, "loop")
    inner = ("engine.emit", 3950, 9050, "loop")  # both cover the whole gap: the inner is the phase
    assert _names(_trace([outer, inner]))[0] == "engine.emit/after:jit__multi"


@pytest.mark.parametrize("fault", ["no launch record", "launches that disagree by 3 ms", "no annotation",
                                   "annotations that are not the enqueues'"])
def test_without_a_shared_clock_gaps_keep_today_s_names_and_no_share_is_read(fault):
    notes = [("engine.harvest:decode", 500, 3200, "loop")]
    trace = {
        "no launch record": lambda: _trace(notes, launches=[]),
        # one of the three programs that began on an idle device began 3 ms after its enqueue
        "launches that disagree by 3 ms": lambda: _trace(
            notes, launches=[(0, 500 * US), (3000 * US, 500 * US), (9000 * US, 9500 * US)]),
        "no annotation": lambda: _trace([], dispatches=None),
        # every call returned 5 ms before its program was enqueued
        "annotations that are not the enqueues'": lambda: _trace(
            notes + [("engine.dispatch:decode", a - 6000, a - 5000, "worker") for a in (0, 3000, 9000)],
            dispatches=None),
    }[fault]()
    clock = trace.clock()
    assert clock is None or clock["ok"] is False
    assert trace.annotations == []
    assert _names(trace) == ["unattributed_after:jit__multi", "unattributed_after:jit__multi",
                             "unattributed_after:window_start"]
    assert trace.idle_named_share() is None
    assert trace_idle_named_share.read({"trace": trace}) is None
    assert trace_idle_named_share.read({"trace": None}) is None


def test_idle_gap_named_share_by_hand():
    notes = [("engine.harvest:decode", 500, 1400, "loop"),   # 0.4 ms of the 2 ms gap
             ("engine.emit", 1400, 1600, "loop"),            # 0.2
             ("engine.schedule", 5000, 7000, "loop"),        # 2.0 of the 5 ms gap
             ("engine.dispatch:unified", 6500, 7500, "worker")]  # 0.5 more: the union counts once
    trace = _trace(notes)
    # with each gap's own dispatch (850 us, the last 50 us of the gap bare): 0.6 + 0.85 and 2.5 + 0.85
    # of the 2 + 5 ms, and the 1 ms before the first program holds its dispatch's 0.85
    inside = (0.4 + 0.2 + 0.85) + (2.0 + 0.5 + 0.85) + 0.85
    assert trace.idle_named_share() == pytest.approx(100 * inside / (2 + 5 + 1))
    assert trace_idle_named_share.read({"trace": trace}) == trace.idle_named_share()
    # gaps under 100 us are the device's own and count on neither side; under 1 ms together: nothing
    tight = _trace([], busy=[(0, 1000), (1090, 4000), (4400, 10000)],
                   mods=[("jit__multi(7)", 0, 1000), ("jit__multi(7)", 1090, 4000),
                         ("jit__multi(7)", 4400, 10000)])
    assert [ns for _, ns, _ in tight.gaps()] == [1000 * US, 400 * US, 90 * US]
    assert _names(tight)[2] == "unattributed_after:jit__multi"
    assert _trace([], busy=[(-1000, 1000), (1400, 10000)],
                  mods=[("jit__multi(7)", -1000, 1000), ("jit__multi(7)", 1400, 10000)]
                  ).idle_named_share() is None


def _parent_breakdown(trace, top=10):
    """``DeviceTrace.breakdown`` as it was before the host's side was read."""
    n = max(1, trace.n_devices)
    sums = sorted(tr.sum_by_name(trace.all_ops()).items(), key=lambda kv: -kv[1])[:top]
    plane = next(iter(trace.planes), None)
    gaps = tr.idle_gaps(trace.ops.get(plane, []), trace.t0_ns, trace.t1_ns, top)
    mods = sorted((s, name) for name, s, _ in trace.modules.get(plane, []))
    named = []
    for _, ns, start in gaps:
        before = [name for s, name in mods if s <= start]
        prog = before[-1].split("(")[0] if before else "window_start"
        named.append([f"unattributed_after:{prog}", ns / 1e9])
    return {"device_ops": [[name, ns / n / 1e9] for name, ns in sums], "idle_gaps": named}


@pytest.mark.parametrize("fixture", ["trace_small.json", "trace_host_small.json"])
def test_a_trace_without_host_events_gives_the_breakdown_it_gave_before_to_the_byte(fixture):
    with open(os.path.join(FIXTURES, fixture)) as f:
        doc = json.load(f)
    trace = tr.DeviceTrace(_planes(doc), doc["t_start_s"], doc["t_stop_s"])
    assert json.dumps(trace.breakdown()) == json.dumps(_parent_breakdown(trace))
    assert trace.clock() is None and trace.annotations == [] and trace.idle_named_share() is None


# --------------------------------------------------------- the recorded slice
def test_recorded_slice_the_device_reads_0_0015_s_early_and_the_check_holds(recorded):
    doc, trace = recorded
    clock = trace.clock()
    assert clock == doc["expect"]["clock"] and clock["ok"]
    # both programs the device was idle before began within 50 us of one skew
    leads = [e - m for m, e in trace.launches if m in trace.idle_before(sorted(dict(trace.launches)))]
    assert len(leads) == 2 and max(leads) - min(leads) < 50 * US
    assert 1_400 < clock["skew_us"] < 1_600
    # a host whose clock reads 1.4 ms less measures 1.4 ms less of skew, and the names stand
    other = tr.DeviceTrace(trace.planes, extent=(trace.t0_ns, trace.t1_ns),
                           host={"annotations": [(n, s - 1_400_000, d, t) for n, s, d, t in trace.written],
                                 "launches": [(m, e - 1_400_000) for m, e in trace.launches]})
    assert other.clock()["ok"] and other.clock()["skew_us"] == pytest.approx(clock["skew_us"] - 1400)
    assert other.breakdown() == trace.breakdown()
    # read where the host wrote them, unmoved (enqueue events that claim the clocks agree), the 13.3 ms
    # gap would be a bare one: the enqueue's call covers 49% of it there, 60% on the device's clock
    unmoved = tr.DeviceTrace(trace.planes, extent=(trace.t0_ns, trace.t1_ns),
                             host={"annotations": trace.written,
                                   "launches": [(m, e - round(clock["skew_us"] * 1e3)) for m, e in trace.launches]})
    assert unmoved.clock()["ok"] and unmoved.clock()["skew_us"] == 0
    assert unmoved.breakdown()["idle_gaps"][0][0].startswith("host_unannotated_after:engine.emit")


def test_recorded_slice_names_both_chain_breaks_and_the_share_matches_a_brute_force_count(recorded):
    doc, trace = recorded
    gaps = trace.breakdown()["idle_gaps"]
    assert gaps == doc["expect"]["idle_gaps"]
    assert gaps[0] == ["engine.dispatch:decode/after:jit__multi", 0.013337638]
    assert gaps[1] == ["host_unannotated_after:engine.emit/after:jit__multi", 0.011948327]
    assert all(name.startswith("unattributed_after:") for name, s in gaps if s < 1e-4)
    assert not any(name.startswith("unattributed_after:") for name, s in gaps if s >= 1e-4)
    # painted in cells of 1 us: a different algorithm on purpose
    cells = bytearray((trace.t1_ns - trace.t0_ns) // US + 2)
    for _, s, d, _ in trace.annotations:
        a, b = max(0, round((s - trace.t0_ns) / US)), max(0, round((s + d - trace.t0_ns) / US))
        cells[a:b] = b"\x01" * (b - a)
    idle = inside = 0
    for _, ns, start in trace.gaps():
        if ns >= tr.HOST_GAP_NS:
            a, b = round((start - trace.t0_ns) / US), round((start + ns - trace.t0_ns) / US)
            idle, inside = idle + b - a, inside + sum(cells[a:b])
    share = trace.idle_named_share()
    assert share == pytest.approx(100 * inside / idle, abs=0.05)
    assert share == pytest.approx(doc["expect"]["idle_gap_named_share"], rel=1e-9)
    assert trace.busy_s == pytest.approx(doc["expect"]["busy_s"], rel=1e-9)
    assert {t for n, _, _, t in trace.annotations if n == "engine.emit"} == {trace.loop_thread()}
    assert trace.loop_thread() not in {t for n, _, _, t in trace.annotations if n.startswith(tr.DISPATCH_PREFIX)}
    # the decode-side twin of step_device_ms_p50 reads the fused chunk's module events
    p50 = trace_module_percentile.read({"trace": trace}, pattern="^jit__multi", q=50)
    assert 16.5 < p50 < 18.5


# ------------------------------------------------------- entries and data files
@pytest.mark.parametrize("name", NEW)
def test_the_data_files_load_and_say_what_they_read(name):
    spec = loader.read_json(loader.data_file("layer_metrics", name))
    assert len(spec["about"]) > 200
    assert callable(loader.load_reader(spec["reader"]).read)
    said = {"idle_gap_named_share": ["engine.schedule", "engine.dispatch", "engine.harvest",
                                     "engine.emit", "100 us", "clock"],
            "fused_chunk_device_ms_p50": ["jit__multi", "decode_steps"],
            "mla_dense_prefill_roofline": ["mla_dense_prefill_attention", "from below", "prefix hit"],
            "mla_dense_prefill_kernel_time_share": ["mla_dense_prefill_attention", "PR 39", "fori_loop",
                                                    "mla_dense_prefill_attn_time_share"]}
    for word in said[name]:
        assert word in spec["about"], (name, word)


def test_the_new_entries_are_appended_and_list_the_cells_that_have_something_to_read():
    bench = loader.load_benchmark()
    entries = {m["name"]: m for m in bench["per_layer"]}
    idle = entries["idle_gap_named_share"]
    assert idle["source"] == "program_span" and idle["moves"] == "tpot_ms_p90"
    # a cell whose device is never idle (its gaps are 5 us) has nothing to read
    assert sorted(idle["workloads"]) == sorted(c for c in CELLS if c != "qwen2.5-7b.prefill-closed")
    assert "workloads" not in entries["fused_chunk_device_ms_p50"]
    for cell in CELLS:
        names = [m["name"] for m in loader.load_cell(cell)["per_layer"]]
        assert "fused_chunk_device_ms_p50" in names
        assert ("idle_gap_named_share" in names) == (cell != "qwen2.5-7b.prefill-closed")
    for name in ("mla_dense_prefill_kernel_time_share", "mla_dense_prefill_roofline"):
        assert entries[name]["workloads"] == ["kimi-k2-6l-ep32.agent-shared"]
        spec = loader.read_json(loader.data_file("layer_metrics", name))
        assert spec["args"] == {"pattern": "^mla_dense_prefill_attention"}
    # the old name's file is tests/test_tpu_compile.py's to read: untouched, its pattern the loop's
    old = loader.read_json(loader.data_file("layer_metrics", "mla_dense_prefill_attn_time_share"))
    assert "convolution_bitcast_fusion" in old["args"]["pattern"] and "mla_dense_prefill_attn_time_share" in entries
    twin = loader.read_json(loader.data_file("layer_metrics", "step_device_ms_p50"))
    fused = loader.read_json(loader.data_file("layer_metrics", "fused_chunk_device_ms_p50"))
    assert fused["reader"] == twin["reader"] and fused["args"] == {"pattern": "^jit__multi", "q": 50}


# ------------------------------------------- the prompt kernel's need, by hand
@pytest.fixture(scope="module")
def kimi():
    cfg = loader.read_json(loader.data_file("configs", "kimi-k2-6l-ep32"))
    return run.model_of(cfg, False), cfg["serve"]


def test_prompt_attention_is_counted_from_below(kimi):
    model, serve = kimi
    assert sh.prefill_query_flops_per_position(model) == 2 * 64 * (128 + 64 + 128)
    assert sh.decompress_flops_per_position(model) == 2 * 64 * 512 * 256
    # one query on an empty context attends to itself; its one position is decompressed once
    assert sh.prefill_request_flops(model, serve, 1, 0) == 2 * 64 * 320 + 2 * 64 * 512 * 256
    # ISSUE 40's hand count: 512 queries behind a hit of 12488: 0.49 TFLOP and 2.49 ms at the peak
    # with every query against all 13000 positions; 0.485 and 2.46 with each against its own
    one = sh.prefill_request_flops(model, serve, 13000, 12488)
    attended = sum(p + 1 for p in range(12488, 13000))
    assert one == attended * 40960 + 13000 * 16_777_216
    assert one / 1e12 == pytest.approx(0.485, abs=0.001) and one / 197e12 * 1e3 == pytest.approx(2.46, abs=0.01)
    assert one < 512 * 13000 * 40960 + 13000 * 16_777_216 == pytest.approx(0.4907e12, rel=1e-3)
    # a turn of 600 tokens is two chunks: the context is decompressed at 12800 and at 12888 positions
    two = sh.prefill_request_flops(model, serve, 12888, 12288)
    assert two == sum(p + 1 for p in range(12288, 12888)) * 40960 + (12800 + 12888) * 16_777_216
    # a cold prompt: every query computed, a decompression a chunk; the hit is never the whole prompt
    assert sh.prefill_request_flops(model, serve, 1024, 0) == (1024 * 1025 // 2) * 40960 + (512 + 1024) * 16_777_216
    assert sh.prefill_request_flops(model, serve, 512, 512) == sh.prefill_request_flops(model, serve, 512, 511)


def _prefill_ctx(kimi, kernel_ms, requests):
    model, serve = kimi
    ops = [("mla_dense_prefill_attention bf16[512,8192]", i * 10**7, int(kernel_ms * 1e6 / 6)) for i in range(6)]
    ops.append(("mla_dense_decode_attention bf16[16,64,512]", 10**9, 10**6))
    planes = {PLANE: {tr.OPS_LINE: ops, tr.MODULES_LINE: []}}
    cell = {"mix": {"sharing": {"kind": "shared_prefix", "groups": 4, "prefix_len": 12288}}}
    return {"trace": tr.DeviceTrace(planes, 10.0, 13.0), "model": model, "serve": serve, "cell": cell,
            "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}, "window": {"requests": requests}}


def test_prefill_roofline_on_a_hand_made_trace(kimi):
    model, serve = kimi
    need = sh.prefill_request_flops(model, serve, 12800, 12288)
    inside = {"ok": True, "t_ref": 10.5, "t_first": 10.7, "t_last": 11.0, "n_tokens": 64, "prompt_len": 12800}
    half = dict(inside, t_ref=9.9, t_first=10.1)      # half of its wait lies in the traced interval
    outside = dict(inside, t_ref=13.5, t_first=13.7)  # sent after it
    failed = dict(inside, ok=False)
    floor_ms = 6 * 1.5 * need / 197e12 * 1e3
    ctx = _prefill_ctx(kimi, 2 * floor_ms, [inside, half, outside, failed])
    assert prefill_reader.read(ctx, pattern="^mla_dense_prefill_attention") == pytest.approx(50.0, rel=1e-4)
    # the hit is the mix's shared prefix in whole pages, never the whole prompt
    got = prefill_reader.prompts_in([inside, half, outside, failed], 10.0, 13.0)
    assert got == [(12800, 1.0), (12800, pytest.approx(0.5))]
    assert prefill_reader.read(ctx, pattern="^no_such_kernel") is None
    assert prefill_reader.read(dict(ctx, trace=None), pattern="x") is None
    assert prefill_reader.read(_prefill_ctx(kimi, 1.0, [outside]), pattern="^mla_dense_prefill") is None
    selector = dict(ctx, model=dict(model, index_topk=2048))
    assert prefill_reader.read(selector, pattern="^mla_dense_prefill_attention") is None
