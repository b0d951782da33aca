"""The k-exaone-236b-a23b-8l-ep8 configuration, its cell, metrics and readers
load, and the bytes and operations of a model with window and full attention
layers (chipbench/shapes_swa_moe.py) with its readers, by hand (no JAX).
Nothing here asserts a position of an entry or a count of cells."""

import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import loader, promtext, run, shapes_swa_moe as sh, trace_reduce as tr  # noqa: E402
from chipbench import traffic  # noqa: E402
from chipbench.readers import decode_roofline_swa_moe as step_reader  # noqa: E402
from chipbench.readers import kernel_roofline_swa_window as window_reader  # noqa: E402
from chipbench.readers import prom_label_share, prom_mean_delta, trace_time_share  # noqa: E402

CELL = "k-exaone-236b-a23b-8l-ep8.agent-shared"
# name -> (unit, better, source, layer, moves, reader)
NEW = {
    "swa_moe_decode_step_roofline": ("%", "higher", "device_trace", "model", "tpot_ms_p90",
                                     "decode_roofline_swa_moe"),
    "swa_window_decode_time_share": ("%", "lower", "device_trace", "kernels", "tpot_ms_p90",
                                     "trace_time_share"),
    "swa_window_decode_kernel_roofline": ("%", "higher", "device_trace", "kernels", "tpot_ms_p90",
                                          "kernel_roofline_swa_window"),
    "swa_window_prefill_time_share": ("%", "lower", "device_trace", "kernels", "ttft_ms_p50",
                                      "trace_time_share"),
    "swa_window_prefill_roofline": ("%", "higher", "device_trace", "kernels", "ttft_ms_p50",
                                    "kernel_roofline_swa_window"),
    "swa_window_pages_per_row": ("pages", "lower", "program_counter", "scheduler", "tpot_ms_p90",
                                 "prom_mean_delta"),
    "swa_hit_tokens_resumed_share": ("%", "higher", "program_counter", "scheduler", "ttft_ms_p50",
                                     "prom_label_share"),
}
APPENDED = ["moe_local_pairs_per_token", "moe_held_experts_read_share",
            "moe_grouped_matmul_time_share", "device_idle_share.closed_tpot",
            "idle_gap_named_share"]
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12, "int8_ops": 393e12}
BENCH = loader.load_benchmark()


@pytest.fixture(scope="module")
def config():
    cfg = loader.read_json(loader.data_file("configs", "k-exaone-236b-a23b-8l-ep8"))
    return run.model_of(cfg, False), cfg["serve"]


def test_the_cell_is_the_issue_s(config):
    cell = loader.load_cell(CELL)
    assert cell["cell"]["chips"] == 1 and cell["cell"]["traffic"] == "agent-shared"
    assert "1/8 expert load" in cell["cell"]["why"] and len(cell["cell"]["why"]) <= 200
    entry = next(c for c in BENCH["configs"] if c["name"] == cell["cell"]["config"])
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200
    mix = cell["mix"]
    assert mix["loop"] == "closed" and mix["schedule_seed"] == 23
    assert mix["sharing"] == {"kind": "shared_prefix", "groups": 4, "prefix_len": 12288}
    assert [m["name"] for m in cell["end_to_end"]] == ["ttft_ms_p50", "tpot_ms_p90", "setup_s"]
    model, serve = config
    assert model["model_type"] == "exaone_moe" and model["sliding_window"] == 128
    assert cell["config"]["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "layer_types", "mlp_layer_types", "num_experts", "ep_size",
        "vocab_size", "num_nextn_predict_layers"]
    assert set(cell["config"]["assumed"]) >= {
        "qk_norm", "norm_placement", "unrotated_full_layers", "window_edge", "selection_bias",
        "expert_halves", "weights", "kv_pages", "draw"}
    # the mix is one file: the serving sizes are the other agent-shared cell's
    other = loader.load_cell("kimi-k2-6l-ep32.agent-shared")
    assert other["mix"] == mix
    for key in ("block_size", "num_blocks", "max_model_len", "max_batch", "prefill_chunk",
                "decode_steps"):
        assert serve[key] == other["config"]["serve"][key], key
    assert cell["params"]["clients"] * 2 == serve["max_batch"] == 16
    # the shared context is a whole number of resume strides, and so is the probe's hit
    assert mix["sharing"]["prefix_len"] % serve["prefill_chunk"] == 0
    assert mix["prompt"]["max"] + mix["output"]["max"] <= serve["max_model_len"]
    reqs = traffic.build_requests(mix, 16, seed=3_000_000_019, vocab=model["vocab_size"])
    assert len({tuple(r["prompt"][:12288]) for r in reqs}) == 4
    assert max(max(r["prompt"]) for r in reqs) < model["vocab_size"] == 19200
    assert "1.15" in cell["params"]["set_from"]
    assert cell["params"]["pool_per_s"] * cell["run_seconds"] > 110
    # a CPU rehearsal exists and is small
    small = cell["config"]["rehearsal"]["model"]
    assert small["model_type"] == "exaone_moe" and small["hidden_size"] <= 128


def _entry(name):
    unit, better, source, layer, moves, _ = NEW[name]
    return {"name": name, "unit": unit, "better": better, "source": source, "layer": layer,
            "moves": moves, "workloads": [CELL]}


@pytest.mark.parametrize("name", list(NEW))
def test_every_new_file_matches_the_entry_a_benchmark_pr_appends(name):
    """The files stand by, each agreeing with the entry ``_entry`` gives."""
    unit, better, source, layer, moves, reader = NEW[name]
    spec = loader.read_json(loader.data_file("layer_metrics", name))
    assert (spec["name"], spec["unit"], spec["layer"], spec["moves"], spec["reader"]) == (
        name, unit, layer, moves, reader)
    assert len(spec["about"]) > 200
    assert callable(loader.load_reader(reader).read)
    assert layer in {m["layer"] for m in BENCH["per_layer"]}  # a layer the benchmark already names


def test_the_seven_have_no_entry_and_the_cell_reports_the_shared_metrics():
    """BENCHMARK.json has no entry for the seven (an accepted test holds PR 41's
    ten to the END of ``per_layer`` and an entry ahead of them reads as a change
    to what was there: PERF.md section 7 (av))."""
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    assert not set(NEW) & set(by_name)
    names = [m["name"] for m in loader.load_cell(CELL)["per_layer"]]
    for name in APPENDED + ["prefix_hit_rate", "attn_decode_time_share",
                            "attn_prefill_time_share", "step_device_ms_p50"]:
        assert name in names, name
    for name in APPENDED:
        assert CELL in by_name[name]["workloads"]
    for name in ("hybrid_decode_step_roofline", "short_conv_time_share", "decode_step_roofline",
                 "mla_dense_decode_kernel_roofline", "dsa_selected_share"):
        assert name not in names
    for m in BENCH["end_to_end"]:
        assert CELL not in m.get("workloads", [])


def test_every_cell_still_loads_and_none_reports_the_new_metrics():
    for w in BENCH["workloads"]:
        reported = {m["name"] for m in loader.load_cell(w["name"])["per_layer"]}
        assert not set(NEW) & reported, w["name"]


def _copy_with(tmp_path, entries):
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"), os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = loader.load_benchmark()
    bench["per_layer"].extend(entries)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root, bench


def test_the_seven_entries_appended_to_a_copy_are_this_cell_s_alone(tmp_path):
    """What a benchmark PR appends: the new cell reports all seven, each as its
    entry says, and no other cell any."""
    root, bench = _copy_with(tmp_path, [_entry(name) for name in NEW])
    for w in bench["workloads"]:
        reported = {m["name"]: m for m in loader.load_cell(w["name"], root)["per_layer"]}
        if w["name"] != CELL:
            assert not set(NEW) & set(reported), w["name"]
            continue
        assert set(NEW) <= set(reported)
        for name in NEW:
            assert {k: reported[name][k] for k in ("unit", "layer", "moves")} == {
                k: _entry(name)[k] for k in ("unit", "layer", "moves")}
    e2e = {m["name"] for m in loader.load_cell(CELL)["end_to_end"]}
    assert {v[4] for v in NEW.values()} <= e2e


def test_the_five_ssm_entries_appended_to_a_copy_are_still_granite_s_alone(tmp_path):
    """What tests/chipbench/test_chipbench_ssm_hybrid.py's check of the same
    name MEANS, whatever cell comes last in BENCHMARK.json (that test reads
    the loop's LAST cell where it means granite's: PERF.md section 7)."""
    from tests.chipbench import test_chipbench_ssm_hybrid as ssm

    entries = []
    for name, (better, source) in ssm.ENTRIES.items():
        spec = loader.read_json(loader.data_file("layer_metrics", name))
        entries.append({"name": name, "unit": spec["unit"], "better": better, "source": source,
                        "layer": spec["layer"], "moves": spec["moves"], "workloads": [ssm.CELL]})
    root, bench = _copy_with(tmp_path, entries)
    for w in bench["workloads"]:
        reported = {m["name"] for m in loader.load_cell(w["name"], root)["per_layer"]}
        assert (set(ssm.NEW) <= reported) if w["name"] == ssm.CELL else not set(ssm.NEW) & reported


def test_weight_bytes_are_the_issue_s_arithmetic(config):
    model, serve = config
    assert sh.layer_counts(model) == {"window": 6, "full": 2, "dense": 1, "moe": 7}
    assert sh.attention_elements(model) == 113_246_208  # ISSUE 47: 113.2M a layer
    assert sh.expert_elements(model) == sh.shared_elements(model) == 37_748_736
    assert sh.dense_elements(model) == 339_738_624 and sh.router_width(model) == 128
    fixed = sh.fixed_weight_bytes(model, serve)
    want = (8 * (113_246_208 + 2 * 128 * 2) + 17 * 6144 * 2 + 339_738_624
            + 7 * (37_748_736 + 6144 * 128 * 2 + 128 * 4) + 6144 * 19200)
    assert fixed == pytest.approx(want) and 1.62e9 < fixed < 1.64e9
    assert sh.decode_weight_bytes(model, serve, 0) == fixed
    # one row chooses 8 of 128, of which 1 is held on average
    assert sh.decode_weight_bytes(model, serve, 1) - fixed == pytest.approx(7 * 1 * 37_748_736)
    assert 5.5 < sh.experts_touched(model, 7) < 6.0  # ISSUE 47: about 6 of 16 at 7 rows


def test_cache_bytes_follow_the_window_and_operations(config):
    model, serve = config
    assert sh.kv_bytes_per_position(model, serve) == 2048  # 2 x 8 x 128 int8 values a layer
    # 7 rows at 12.8k: every position in 2 layers, 128 a row in 6
    rows, held = 7, 7 * 12800
    assert sh.window_positions(model, held, rows) == 7 * 128
    assert sh.window_positions(model, 7 * 50, rows) == 7 * 50  # under the window: what is there
    assert sh.window_positions(model, 0, 0) == 0
    step = sh.decode_step_bytes(model, serve, rows, held)
    assert step == pytest.approx(sh.decode_weight_bytes(model, serve, rows)
                                 + 2 * held * 2048 + 6 * 7 * 128 * 2048)
    # ISSUE 47's reckoning: about 3.5 GB a step (0.37 GB of full K/V, 0.01 GB of window K/V)
    assert 3.3e9 < step < 3.7e9
    assert 2 * held * 2048 == pytest.approx(0.367e9, rel=0.01)
    assert 6 * 7 * 128 * 2048 == pytest.approx(0.011e9, rel=0.01)
    per_row = (8 * 113_246_208 + 339_738_624
               + 7 * (37_748_736 + 6144 * 128 + 8 * 37_748_736) + 6144 * 19200)
    assert sh.decode_step_ops(model, 1, 0) == 2 * per_row
    assert sh.decode_step_ops(model, 0, 100) == 2 * 100 * 2 * 2 * 64 * 128  # full layers only
    assert step / 819e9 > 10 * sh.decode_step_ops(model, rows, held) / 393e12  # the bytes bind
    # a window layer's decode call: 128 positions a row, whatever the context
    assert sh.window_decode_call_need_s(model, serve, rows, held, PEAKS) == pytest.approx(
        7 * 128 * 2048 / 819e9)


def test_window_prefill_pairs_by_hand(config):
    model, _ = config
    assert sh.window_prefill_pairs(model, 1, 0) == 1
    assert sh.window_prefill_pairs(model, 127, 0) == 127 * 128 / 2
    assert sh.window_prefill_pairs(model, 128, 0) == 127 * 128 / 2 + 128
    # behind a hit past the window: 128 positions a query
    assert sh.window_prefill_pairs(model, 12800, 12288) == 512 * 128
    assert sh.window_prefill_pairs(model, 200, 100) == sum(
        min(t + 1, 128) for t in range(100, 200))
    assert sh.window_prefill_flops(model, 12800, 12288) == 4 * 64 * 128 * 512 * 128


def _ctx(model, serve, ops, mods, reqs, mix=None):
    planes = {"/device:TPU:0": {tr.OPS_LINE: ops, tr.MODULES_LINE: mods}}
    return {"trace": tr.DeviceTrace(planes, 4.0, 6.0), "model": model, "serve": serve,
            "peaks": PEAKS, "window": {"requests": reqs}, "cell": {"mix": mix or {}}}


def test_the_rooflines_and_the_shares_on_a_hand_made_trace(config):
    model, serve = config
    reqs = [{"ok": True, "t_ref": -1.0, "t_first": 0.0, "t_last": 10.0, "n_tokens": 200,
             "prompt_len": 12700}] * 3
    held = 3 * 12800.0  # three rows, each at 12700 + 100 tokens mid-trace
    floor_s = sh.decode_step_bytes(model, serve, 3, held) / 819e9
    step_ns = int(floor_s * 2 * 1e9)  # every step takes twice its floor
    mods = [("jit__multi(7)", i * 10 * step_ns, serve["decode_steps"] * step_ns) for i in range(5)]
    call_s = sh.window_decode_call_need_s(model, serve, 3, held, PEAKS)
    call_ns = int(call_s * 4 * 1e9)  # a window call takes four times its need
    ops = [(tr.short_name(hlo), start, dur) for hlo, start, dur in (
        ("%window_decode_attention.3 = f32[16,1,64,128]{3,2,1,0} custom-call(s32[16] %a)", 0, call_ns),
        ("%window_decode_attention.4 = f32[16,1,64,128]{3,2,1,0} custom-call(s32[16] %a)", 2 * call_ns, call_ns),
        ("%fused_decode_attention.5 = f32[16,1,64,128]{3,2,1,0} custom-call(s32[16] %a)", 4 * call_ns, 6 * call_ns),
        ("%fusion.9 = bf16[16,6144]{1,0} fusion(bf16[16,6144] %window_decode_attention.3)", 10 * call_ns, 8 * call_ns),
    )]
    ctx = _ctx(model, serve, ops, mods, reqs)
    assert step_reader.read(ctx, module_pattern="^jit__multi") == pytest.approx(50.0, rel=1e-3)
    assert window_reader.read(ctx, pattern="^window_decode_attention", phase="decode") == \
        pytest.approx(25.0, rel=1e-3)
    busy = 16 * call_ns  # the union of the four ops
    assert trace_time_share.read(ctx, pattern="^window_decode_attention") == pytest.approx(
        100 * 2 * call_ns / busy, rel=1e-3)
    # the old pattern reads the full layers' call and no window call (nor an operand's name)
    assert trace_time_share.read(ctx, pattern="^fused_decode_attention") == pytest.approx(
        100 * 6 * call_ns / busy, rel=1e-3)
    # a configuration without a window, or a trace without the calls, reads nothing
    assert step_reader.read(dict(ctx, model={"hidden_size": 1}), module_pattern="^jit__multi") is None
    assert window_reader.read(ctx, pattern="^window_prefill_attention", phase="prefill") is None
    assert window_reader.read(dict(ctx, model={"hidden_size": 1}),
                              pattern="^window_decode_attention", phase="decode") is None


def test_the_prefill_roofline_counts_the_window_behind_the_hit(config):
    model, serve = config
    mix = {"sharing": {"kind": "shared_prefix", "groups": 4, "prefix_len": 12288}}
    # one request whose whole wait for the first token lies in the traced interval
    reqs = [{"ok": True, "t_ref": 4.5, "t_first": 5.0, "t_last": 9.0, "n_tokens": 100,
             "prompt_len": 12800}]
    need_s = 6 * sh.window_prefill_flops(model, 12800, 12288) / 197e12
    ops = [("window_prefill_attention f32[1,640,8,8,128]", 0, int(need_s * 5 * 1e9))]
    ctx = _ctx(model, serve, ops, [], reqs, mix)
    assert window_reader.read(ctx, pattern="^window_prefill_attention", phase="prefill") == \
        pytest.approx(20.0, rel=1e-3)


def test_the_counters_readers_on_hand_made_scrapes():
    before = promtext.parse(
        "dynamo_tpu_kv_window_pages_total 100\ndynamo_tpu_kv_window_rows_total 10\n"
        'dynamo_tpu_swa_hit_tokens_total{outcome="resumed"} 1000\n'
        'dynamo_tpu_swa_hit_tokens_total{outcome="cut"} 24\n')
    after = promtext.parse(
        "dynamo_tpu_kv_window_pages_total 1150\ndynamo_tpu_kv_window_rows_total 110\n"
        'dynamo_tpu_swa_hit_tokens_total{outcome="resumed"} 13288\n'
        'dynamo_tpu_swa_hit_tokens_total{outcome="cut"} 536\n')
    ctx = {"before": before, "after": after}
    spec = loader.read_json(loader.data_file("layer_metrics", "swa_window_pages_per_row"))
    assert prom_mean_delta.read(ctx, **spec["args"]) == pytest.approx(10.5)
    spec = loader.read_json(loader.data_file("layer_metrics", "swa_hit_tokens_resumed_share"))
    assert prom_label_share.read(ctx, **spec["args"]) == pytest.approx(100 * 12288 / 12800)
    # a program without the counters (the parent) reports nothing and does not raise
    empty = {"before": promtext.parse(""), "after": promtext.parse("")}
    assert prom_mean_delta.read(empty, **loader.read_json(
        loader.data_file("layer_metrics", "swa_window_pages_per_row"))["args"]) is None
    assert prom_label_share.read(empty, **spec["args"]) is None


def test_the_reference_copy_is_the_programs_reference():
    with open(os.path.join(ROOT, "chipbench/reference/exaone_moe.py")) as f:
        copy = f.read()
    with open(os.path.join(ROOT, "dynamo_tpu/models/reference/exaone_moe.py")) as f:
        assert copy == f.read()
    assert "import dynamo_tpu" not in copy and "from dynamo_tpu" not in copy
