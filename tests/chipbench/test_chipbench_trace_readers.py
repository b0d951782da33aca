"""The reduction from a trace to numbers, on a small recorded trace kept as a
fixture, and the per-layer readers on synthetic /metrics text (no JAX)."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import loader, promtext, shapes, trace_reduce as tr  # noqa: E402
from chipbench.readers import (decode_roofline, prompt_tokens_skipped,  # noqa: E402
                               tokens_per_dispatch, trace_idle_share,
                               trace_module_percentile, trace_time_share)

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures", "trace_small.json")


@pytest.fixture(scope="module")
def recorded():
    with open(FIXTURE) as f:
        doc = json.load(f)
    planes = {p: {line: [(doc["names"][i], s, d) for i, s, d in evs]
                  for line, evs in lines.items()}
              for p, lines in doc["planes"].items()}
    return doc, tr.DeviceTrace(planes, doc["t_start_s"], doc["t_stop_s"])


def _brute_busy(events, t0, t1, cell_ns=200):
    """Busy time by painting cells of 0.2 us: a different algorithm on purpose."""
    cells = bytearray((t1 - t0) // cell_ns + 2)
    for _, s, d in events:
        a, b = round((s - t0) / cell_ns), round((s + d - t0) / cell_ns)
        cells[a:b] = b"\x01" * (b - a)
    return sum(cells) * cell_ns


def test_busy_union_on_hand_made_intervals():
    evs = [("a", 0, 10), ("b", 5, 10), ("c", 20, 5), ("d", 21, 2), ("e", 40, 0)]
    assert tr.busy_union_ns(evs) == 15 + 5
    assert tr.busy_union_ns(evs, 8, 22) == 7 + 2
    assert tr.busy_union_ns([]) == 0
    assert tr.span_ns(evs) == (0, 40) and tr.span_ns([]) is None
    assert tr.sum_by_name(evs + [("a", 50, 3)])["a"] == 13
    assert tr.sum_matching_ns(evs, "^[ab]$") == 20 and tr.count_matching(evs, "c|d") == 2


def test_idle_gaps_name_what_ran_before_them():
    evs = [("a", 0, 10), ("b", 30, 5), ("c", 32, 1), ("d", 50, 10)]
    gaps = tr.idle_gaps(evs, 0, 70)
    assert gaps == [("a", 20, 10), ("b", 15, 35), ("d", 10, 60)]
    assert tr.idle_gaps(evs, 0, 70, top=1) == [("a", 20, 10)]
    assert tr.idle_gaps([], 5, 9) == [("window_start", 4, 5)]


def test_self_times_cut_a_wrapper_by_what_it_wraps():
    evs = [("while", 0, 100), ("k", 10, 20), ("fusion", 30, 40), ("inner", 35, 10),
           ("alone", 120, 5)]
    got = {n: d for n, _, d in tr.self_times(evs)}
    assert got == {"while": 40, "k": 20, "fusion": 30, "inner": 10, "alone": 5}
    assert sum(got.values()) == tr.busy_union_ns(evs)


def test_short_names_drop_the_operands_a_pattern_must_not_see():
    hlo = ("%fusion.250 = bf16[512,3584]{1,0:T(8,128)(2,1)S(1)} fusion(bf16[512,3584]{1,0} "
           "%get-tuple-element.9, f32[32,2,28,128]{3,2,1,0} %fused_decode_attention.236)")
    assert tr.short_name(hlo) == "fusion bf16[512,3584]"
    kernel = "%fused_decode_attention.236 = (f32[32,2,28,128]{3,2,1,0:T(8,128)S(1)}, f32[32,2,28,1]{3,2,1,0}) custom-call(...)"
    assert tr.short_name(kernel) == "fused_decode_attention f32[32,2,28,128]"
    assert tr.short_name("%while.5 = (s32[]{:T(128)}, s8[28,12288,16,8,128]{4,3}) while(...)") == "while s32[]"
    assert tr.short_name("jit__multi(11963186632579417845)") == "jit__multi(11963186632579417845)"
    assert tr.short_name("%copy-start.7 = (f32[152064]{0}, u32[]) copy-start(...)") == "copy-start f32[152064]"


def test_recorded_trace_busy_union_matches_a_brute_force_count(recorded):
    doc, trace = recorded
    ops = trace.all_ops()
    assert len(ops) >= 100, "the fixture is a slice of a real device trace"
    raw = [e for evs in trace.ops.values() for e in evs]
    brute = _brute_busy(raw, trace.t0_ns, trace.t1_ns)
    assert trace.busy_s * 1e9 == pytest.approx(brute, rel=0.01)
    assert sum(d for _, _, d in ops) == pytest.approx(trace.busy_s * 1e9, rel=1e-6)
    assert 0 < trace.busy_s <= trace.window_s
    assert trace.busy_s == pytest.approx(doc["expect"]["busy_s"], rel=1e-9)
    assert trace.window_s == pytest.approx(doc["expect"]["window_s"], rel=1e-9)


def test_recorded_trace_per_name_sums_and_breakdown(recorded):
    doc, trace = recorded
    sums = tr.sum_by_name(trace.all_ops())
    assert sum(sums.values()) == sum(d for _, _, d in trace.all_ops())
    assert any(n.startswith("fused_decode_attention") for n in sums)
    top_name, top_ns = max(sums.items(), key=lambda kv: kv[1])
    b = trace.breakdown()
    assert b["device_ops"][0] == [top_name, top_ns / 1e9]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert all(name.startswith("unattributed_after:") for name, _ in b["idle_gaps"])
    gaps = [s for _, s in b["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
    idle = trace_idle_share.read({"trace": trace})
    assert idle == pytest.approx(100 * (1 - trace.busy_s / trace.window_s))
    assert idle == pytest.approx(doc["expect"]["idle_share"], rel=1e-9)
    for pattern, share in doc["expect"]["time_share"].items():
        got = trace_time_share.read({"trace": trace}, pattern=pattern)
        assert got == pytest.approx(share, rel=1e-9) and 0 < got <= 100
    assert trace_time_share.read({"trace": trace}, pattern="no_such_kernel") == 0.0


def test_the_traced_window_counts_idle_edges():
    """One op of 1 s in the middle of a trace whose host threads show 4 s of
    recording: the device was idle for 3 of them, not for none."""
    planes = {"/device:TPU:0": {tr.OPS_LINE: [("fusion.1", 2_000_000_000, 1_000_000_000)],
                                tr.MODULES_LINE: []}}
    own_span = tr.DeviceTrace(planes)
    assert own_span.window_s == pytest.approx(1.0)  # the old reading: never idle
    trace = tr.DeviceTrace(planes, 10.0, 13.5, extent=(500_000_000, 4_500_000_000))
    assert trace.window_s == pytest.approx(4.0) and trace.busy_s == pytest.approx(1.0)
    assert trace_idle_share.read({"trace": trace}) == pytest.approx(75.0)
    gaps = tr.idle_gaps(trace.ops["/device:TPU:0"], trace.t0_ns, trace.t1_ns)
    assert sorted(g[1] for g in gaps) == [1_500_000_000, 1_500_000_000]
    # the trace of an idle process may hold nothing near its edges: the host's
    # clock around the traced sleep is then the longer, and it counts
    quiet = tr.DeviceTrace(planes, 10.0, 15.0, extent=(1_900_000_000, 3_100_000_000))
    assert quiet.window_s == pytest.approx(5.0)


def test_module_percentile_is_the_device_time_of_one_program():
    mods = [("jit__step(1)", i * 10**8, (10 + i) * 10**6) for i in range(5)]
    mods.append(("jit__multi(2)", 0, 90 * 10**6))
    trace = tr.DeviceTrace({"/device:TPU:0": {tr.OPS_LINE: [], tr.MODULES_LINE: mods}})
    assert trace_module_percentile.read({"trace": trace}, pattern="^jit__step", q=50) == 12.0
    assert trace_module_percentile.read({"trace": trace}, pattern="^jit__absent", q=50) is None
    assert trace_module_percentile.read({"trace": None}, pattern="x", q=50) is None


def test_trace_readers_return_nothing_without_a_trace():
    assert trace_idle_share.read({"trace": None}) is None
    assert trace_time_share.read({"trace": None}, pattern="x") is None
    assert decode_roofline.read({"trace": None}, module_pattern="x") is None


# ------------------------------------------------------------ /metrics readers
BEFORE = """# HELP dynamo_tpu_prefill_tokens_total Prompt tokens computed
dynamo_tpu_prefill_tokens_total 1000
dynamo_tpu_prefill_chunk_seconds{quantile="0.5"} 0.05
dynamo_tpu_prefill_chunk_seconds_sum 2.0
dynamo_tpu_prefill_chunk_seconds_count 40
dynamo_tpu_engine_dispatch_window_dispatches{kind="decode_dispatch"} 10
dynamo_tpu_engine_dispatch_window_dispatches{kind="unified"} 7
dynamo_tpu_engine_compiled_programs{fn="step"} 7
dynamo_tpu_engine_compiled_programs{fn="multi"} 2
dynamo_tpu_engine_info{jax="0.9.0",device_kind="TPU v5 lite"} 1
"""
AFTER = BEFORE.replace("total 1000", "total 9000").replace("_sum 2.0", "_sum 8.0") \
    .replace("_count 40", "_count 140").replace('decode_dispatch"} 10', 'decode_dispatch"} 110') \
    .replace('"0.5"} 0.05', '"0.5"} 0.061')


def _ctx(**kw):
    ctx = {"before": promtext.parse(BEFORE), "after": promtext.parse(AFTER),
           "serve": {"decode_steps": 4}, "window": {}}
    ctx.update(kw)
    return ctx


def test_promtext_reads_labels_sums_and_deltas():
    p = promtext.parse(AFTER)
    assert promtext.value(p, "dynamo_tpu_engine_compiled_programs") == 9
    assert promtext.value(p, "dynamo_tpu_engine_compiled_programs", {"fn": "multi"}) == 2
    assert promtext.value(p, "no_such_series") is None
    assert promtext.labels_of(p, "dynamo_tpu_engine_info")["device_kind"] == "TPU v5 lite"
    assert promtext.delta(promtext.parse(BEFORE), p, "dynamo_tpu_prefill_tokens_total") == 8000


def test_prefix_hit_rate_is_the_share_of_prompt_tokens_not_computed():
    ctx = _ctx(window={"prompt_tokens_total": 10000})
    assert prompt_tokens_skipped.read(ctx, series="dynamo_tpu_prefill_tokens_total") == pytest.approx(20.0)
    ctx = _ctx(window={"prompt_tokens_total": 8000})
    assert prompt_tokens_skipped.read(ctx, series="dynamo_tpu_prefill_tokens_total") == pytest.approx(0.0)
    assert prompt_tokens_skipped.read(_ctx(window={"prompt_tokens_total": 0}), series="x") is None


def test_decode_rows_leaves_out_first_tokens():
    reqs = [{"n_tokens": 101}] * 20 + [{"n_tokens": 0}]
    ctx = _ctx(window={"output_tokens_total": 2020, "requests": reqs})
    rows = tokens_per_dispatch.read(
        ctx, series="dynamo_tpu_engine_dispatch_window_dispatches",
        labels={"kind": "decode_dispatch"}, steps_flag="decode_steps")
    assert rows == pytest.approx(2000 / (100 * 4))


# ---------------------------------------------------------- shapes, roofline
def _config(name):
    cfg = loader.read_json(loader.data_file("configs", name))
    return cfg, cfg["serve"]


def test_decode_bytes_of_the_dense_configuration():
    model, serve = _config("qwen2.5-7b")
    w = shapes.decode_weight_bytes(model, serve)
    # 28 layers x (attention 29.4M + FFN 203.7M) + head 545M, one byte each
    assert w == pytest.approx(28 * (3584 * 3584 * 2 + 2 * 3584 * 512 + 3 * 3584 * 18944)
                              + 3584 * 152064)
    assert 6.9e9 < w < 7.3e9
    assert shapes.kv_bytes_per_token(model, serve) == 2 * 28 * 4 * 128
    assert shapes.decode_step_bytes(model, serve, 20000) == w + 20000 * 28672


def test_decode_bytes_of_a_sparse_ffn_are_refused_until_a_cell_brings_them():
    model, serve = _config("qwen2.5-7b")
    with pytest.raises(NotImplementedError):
        shapes.decode_weight_bytes(dict(model, num_local_experts=8), serve)


def test_in_flight_averages_rows_and_context_over_the_traced_interval():
    reqs = [
        {"ok": True, "t_first": 0.0, "t_last": 10.0, "n_tokens": 100, "prompt_len": 500},
        {"ok": True, "t_first": 4.0, "t_last": 6.0, "n_tokens": 20, "prompt_len": 100},
        {"ok": False, "t_first": 0.0, "t_last": 10.0, "n_tokens": 1, "prompt_len": 9},
        {"ok": True, "t_first": 20.0, "t_last": 30.0, "n_tokens": 5, "prompt_len": 9},
    ]
    rows, tokens = decode_roofline.in_flight(reqs, 4.0, 8.0)
    assert rows == pytest.approx(1.5)  # one all along, one for half of it
    # first: 500 + 10/s x 6 s at the middle = 560; second: (100 + 10) for half the time
    assert tokens == pytest.approx(560 + 110 / 2)


def test_decode_roofline_on_a_hand_made_trace():
    model, serve = _config("qwen2.5-7b")
    need = shapes.decode_step_bytes(model, serve, 1200.0)
    floor_s = need / 819e9
    step_ns = int(floor_s * 2 * 1e9)  # every step takes twice the floor
    mods = [("jit__multi(123)", i * 10 * step_ns, 4 * step_ns) for i in range(5)]
    mods.append(("jit__step(9)", 7, 10**6))
    planes = {"/device:TPU:0": {tr.OPS_LINE: [("fusion.1", 0, 10)], tr.MODULES_LINE: mods}}
    reqs = [{"ok": True, "t_first": 0.0, "t_last": 10.0, "n_tokens": 100, "prompt_len": 550}] * 2
    ctx = {"trace": tr.DeviceTrace(planes, 4.0, 6.0), "model": model, "serve": serve,
           "peaks": {"hbm_bytes_per_s": 819e9}, "window": {"requests": reqs}}
    share = decode_roofline.read(ctx, module_pattern="^jit__multi", steps_flag="decode_steps")
    assert share == pytest.approx(50.0, rel=1e-3)
    assert decode_roofline.read(ctx, module_pattern="^jit__absent") is None
