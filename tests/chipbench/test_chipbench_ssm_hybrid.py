"""The granite-4.0-h-small-10l-ep2 configuration, its cell, metrics and readers
load, and the bytes and operations of a decode step of a hybrid model with
Mamba-2 layers (chipbench/shapes_ssm_hybrid.py) with its roofline reader, the
two scopes' time shares and the two label shares, by hand (no JAX).  Nothing
here asserts a position or a count of cells."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import loader, promtext, run, shapes_ssm_hybrid as sh, trace_reduce as tr  # noqa: E402
from chipbench import traffic  # noqa: E402
from chipbench.readers import decode_roofline_ssm_hybrid as step_reader  # noqa: E402
from chipbench.readers import prom_label_share, trace_time_share  # noqa: E402

CELL = "granite-4.0-h-small-10l-ep2.assist-shared"
# the five metrics' files stand by: name -> (better, source) of the entry a benchmark PR appends
ENTRIES = {"ssm_hybrid_decode_step_roofline": ("higher", "device_trace"),
           "ssm_step_time_share": ("lower", "device_trace"),
           "ssm_scan_time_share": ("lower", "device_trace"),
           "ssm_snapshot_start_share": ("higher", "program_counter"),
           "ssm_hit_tokens_resumed_share": ("higher", "program_counter")}
NEW = list(ENTRIES)
APPENDED = ["moe_local_pairs_per_token", "moe_held_experts_read_share",
            "moe_grouped_matmul_time_share", "device_idle_share.closed_tpot",
            "idle_gap_named_share"]
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12, "int8_ops": 393e12}


@pytest.fixture(scope="module")
def config():
    cfg = loader.read_json(loader.data_file("configs", "granite-4.0-h-small-10l-ep2"))
    return run.model_of(cfg, False), cfg["serve"]


def test_the_cell_is_the_issue_s(config):
    cell = loader.load_cell(CELL)
    assert cell["cell"]["chips"] == 1 and cell["cell"]["traffic"] == "assist-shared"
    mix = cell["mix"]
    assert mix["loop"] == "closed" and mix["schedule_seed"] == 23
    assert mix["sharing"] == {"kind": "shared_prefix", "groups": 8, "prefix_len": 2048}
    assert [m["name"] for m in cell["end_to_end"]] == ["ttft_ms_p50", "tpot_ms_p90", "setup_s"]
    model, serve = config
    assert model["model_type"] == "granitemoehybrid"
    assert cell["config"]["reduced"] == ["num_hidden_layers", "layer_types", "num_local_experts",
                                         "ep_size", "vocab_size"]
    assert set(cell["config"]["assumed"]) >= {"in_proj_order", "expert_halves", "state_float32",
                                              "weights"}
    # every live slot in use; the shared prefix is a whole number of resume strides
    assert cell["params"]["clients"] == serve["max_batch"] == 32
    assert mix["sharing"]["prefix_len"] % serve["prefill_chunk"] == 0
    assert (run.PROBE["chunks"] * serve["prefill_chunk"]) % serve["prefill_chunk"] == 0
    assert mix["prompt"]["max"] + mix["output"]["max"] <= serve["max_model_len"]
    assert serve["num_blocks"] * serve["block_size"] > 32 * serve["max_model_len"] + 8 * 2048
    reqs = traffic.build_requests(mix, 32, seed=3_000_000_019, vocab=model["vocab_size"])
    assert len({tuple(r["prompt"][:2048]) for r in reqs}) == 8
    assert max(max(r["prompt"]) for r in reqs) < model["vocab_size"] == 50176
    # the pool does not wrap: 1.15 x what the window completes
    assert "1.15" in cell["params"]["set_from"]
    assert cell["params"]["pool_per_s"] * cell["run_seconds"] > 110


def test_the_shared_metrics_are_appended_and_the_five_new_ones_stand_by():
    """BENCHMARK.json has no entry for the five (an accepted test holds PR 41's
    ten to the END of ``per_layer``, PERF.md section 7): the cell reports the
    shared metrics, and no cell loads a file of the five."""
    bench = loader.load_benchmark()
    names = [m["name"] for m in loader.load_cell(CELL)["per_layer"]]
    for name in APPENDED + ["prefix_hit_rate", "attn_decode_time_share", "step_device_ms_p50"]:
        assert name in names
    for name in ("hybrid_decode_step_roofline", "short_conv_time_share", "conv_tail_start_share",
                 "decode_step_roofline", "dsa_selected_share"):
        assert name not in names
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in APPENDED:
        assert CELL in by_name[name]["workloads"]
    for w in bench["workloads"]:
        assert not set(NEW) & {m["name"] for m in loader.load_cell(w["name"])["per_layer"]}
    for m in bench["end_to_end"]:
        assert CELL not in m.get("workloads", [])


def test_the_five_entries_appended_to_a_copy_are_this_cell_s_alone(tmp_path):
    """What a benchmark PR appends (ENTRIES): every file agrees with its entry,
    the new cell reports all five and no other cell any."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"), os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = loader.load_benchmark()
    for name, (better, source) in ENTRIES.items():
        spec = loader.read_json(loader.data_file("layer_metrics", name))
        bench["per_layer"].append({"name": name, "unit": spec["unit"], "better": better,
                                   "source": source, "layer": spec["layer"],
                                   "moves": spec["moves"], "workloads": [CELL]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    layers = {m["layer"] for m in loader.load_benchmark()["per_layer"]}
    for w in bench["workloads"]:
        reported = {m["name"]: m for m in loader.load_cell(w["name"], root)["per_layer"]}
        assert (set(NEW) <= set(reported)) if w["name"] == CELL else not set(NEW) & set(reported)
    assert {reported[n]["unit"] for n in NEW} == {"%"}
    assert {reported[n]["layer"] for n in NEW} <= layers  # layers the benchmark already names
    e2e = {m["name"] for m in loader.load_cell(CELL)["end_to_end"]}
    assert {reported[n]["moves"] for n in NEW} <= e2e


@pytest.mark.parametrize("name", NEW)
def test_every_new_metric_says_what_it_reads_and_its_reader_loads(name):
    spec = loader.read_json(loader.data_file("layer_metrics", name))
    assert len(spec["about"]) > 80
    assert callable(loader.load_reader(spec["reader"]).read)


def test_weight_bytes_are_the_issue_s_arithmetic(config):
    model, serve = config
    assert sh.layer_counts(model) == {"mamba": 9, "attn": 1, "moe": 10}
    assert sh.mamba_dims(model) == (8192, 128, 64, 128, 4, 8448)
    per = sh.mixer_weight_elements(model)
    assert sum(per["mamba"].values()) == 102_286_976  # the issue's Mamba-2 mixer
    assert per["attn"]["quant"] == 41_943_040
    assert sh.expert_elements(model) == 9_437_184 and sh.shared_elements(model) == 18_874_368
    assert sh.router_width(model) == 72
    fixed = sh.fixed_weight_bytes(model, serve)
    want = (9 * (102_236_160 + 50_432 * 2 + 384 * 4) + 41_943_040 + 21 * 4096 * 2
            + 10 * (18_874_368 + 4096 * 72 * 2) + 4096 * 50176)
    assert fixed == pytest.approx(want)
    assert sh.decode_weight_bytes(model, serve, 0) == fixed
    # one row chooses 10 of 72, of which 5 are held on average
    assert sh.decode_weight_bytes(model, serve, 1) - fixed == pytest.approx(10 * 5 * 9_437_184)
    assert 34.5 < sh.experts_touched(model, 26) < 36
    # a decode step of 26 rows: about 4.7 GB of weights, 3.3 of them experts
    whole = sh.decode_weight_bytes(model, serve, 26)
    assert 4.5e9 < whole < 4.8e9 and 3.2e9 < whole - fixed < 3.4e9


def test_state_and_cache_bytes_and_operations(config):
    model, serve = config
    assert sh.kv_bytes_per_token(model, serve) == 2048  # 2 x 8 x 128 int8 values in ONE layer
    # a row's slot read and written in 9 layers: the issue's 38,204,928 B twice
    assert sh.state_bytes_per_row(model, serve) == 2 * 38_204_928
    assert sh.decode_step_bytes(model, serve, 26, 26 * 2900) == pytest.approx(
        sh.decode_weight_bytes(model, serve, 26) + 26 * 2900 * 2048 + 26 * 76_409_856)
    # the state is a quarter of a step's bytes at 26 rows: about 6.8 GB, 8.3 ms at 819 GB/s
    step = sh.decode_step_bytes(model, serve, 26, 26 * 2900)
    assert 6.5e9 < step < 7.1e9 and 0.27 < 26 * 76_409_856 / step < 0.32
    per_row = (9 * (102_236_160 + 2 * 128 * 64 * 128) + 41_943_040
               + 10 * (18_874_368 + 4096 * 72 + 10 * 9_437_184) + 4096 * 50176)
    assert sh.decode_step_ops(model, 1, 0) == 2 * per_row
    assert sh.decode_step_ops(model, 0, 100) == 2 * 100 * 2 * 32 * 128
    assert step / 819e9 > 10 * sh.decode_step_ops(model, 26, 26 * 2900) / 393e12  # the bytes bind


def _ctx(model, serve, ops, mods, reqs):
    planes = {"/device:TPU:0": {tr.OPS_LINE: ops, tr.MODULES_LINE: mods}}
    return {"trace": tr.DeviceTrace(planes, 4.0, 6.0), "model": model, "serve": serve,
            "peaks": PEAKS, "window": {"requests": reqs}}


def test_the_step_roofline_and_the_scopes_shares_on_a_hand_made_trace(config):
    model, serve = config
    reqs = [{"ok": True, "t_first": 0.0, "t_last": 10.0, "n_tokens": 200, "prompt_len": 2500}] * 3
    held = 3 * 2600.0  # three rows, each at 2500 + 100 tokens mid-trace
    floor_s = sh.decode_step_bytes(model, serve, 3, held) / 819e9
    step_ns = int(floor_s * 2 * 1e9)  # every step takes twice its floor
    mods = [("jit__multi(7)", i * 10 * step_ns, serve["decode_steps"] * step_ns) for i in range(5)]
    ops = [(tr.short_name(hlo), start, dur) for hlo, start, dur in (
        ("%select_dynamic-update-slice_fusion.7 = f32[9,134,8192,128]{3,2,1,0} fusion(f32[9,134,8192,128] %p)", 0, 300),
        ("%convolution_add_fusion.3 = f32[128,64,128]{2,1,0} fusion(f32[128,128,64] %f)", 400, 100),
        ("%fusion.9 = bf16[512,4096]{1,0} fusion(s8[512,4096] %q)", 600, 600))]  # not a scope's
    ctx = _ctx(model, serve, ops, mods, reqs)
    assert step_reader.read(ctx, module_pattern="^jit__multi") == pytest.approx(50.0, rel=1e-3)
    step = loader.read_json(loader.data_file("layer_metrics", "ssm_step_time_share"))
    scan = loader.read_json(loader.data_file("layer_metrics", "ssm_scan_time_share"))
    assert trace_time_share.read(ctx, **step["args"]) == pytest.approx(30.0)
    assert trace_time_share.read(ctx, **scan["args"]) == pytest.approx(10.0)
    # nothing to read: no such program, no trace, another family (the parent's line leaves it out)
    assert step_reader.read(ctx, module_pattern="^jit__absent") is None
    assert step_reader.read(dict(ctx, trace=None), module_pattern="^jit__multi") is None
    lfm2 = {"layer_types": ["conv", "full_attention"], "hidden_size": 2048}
    assert step_reader.read(dict(ctx, model=lfm2), module_pattern="^jit__multi") is None


def test_the_scopes_patterns_match_whole_short_names_only():
    step = re.compile(loader.read_json(
        loader.data_file("layer_metrics", "ssm_step_time_share"))["args"]["pattern"])
    scan = re.compile(loader.read_json(
        loader.data_file("layer_metrics", "ssm_scan_time_share"))["args"]["pattern"])
    for name in ("select_dynamic-update-slice_fusion f32[9,134,8192,128]",
                 "multiply_reduce_fusion f32[32,8192]", "fusion f32[32,8448]"):
        assert step.search(name) and not scan.search(name), name
    for name in ("convolution_add_fusion f32[128,64,128]", "convolution_multiply_fusion f32[128,128,64]",
                 "subtract_exponential_fusion f32[128,128]", "fusion bf16[512,8448]",
                 "fusion f32[512,8448]", "dynamic_update_slice f32[640,128,64]"):
        assert scan.search(name) and not step.search(name), name
    # ops BOTH programs run under one name and shape are in neither share
    for name in ("moe_grouped_matmul", "fused_decode_attention", "fusion bf16[512,4096]",
                 "fusion bf16[32,16768]", "fusion f32[32,50176]", "fusion bf16[9,3,134,8448]",
                 "fusion f32[4,8448]"):
        assert not step.search(name) and not scan.search(name), name


@pytest.mark.parametrize("metric,series,label,other", [
    ("ssm_snapshot_start_share", "dynamo_tpu_ssm_request_starts_total", 'state="snapshot"',
     'state="zero"'),
    ("ssm_hit_tokens_resumed_share", "dynamo_tpu_ssm_hit_tokens_total", 'outcome="resumed"',
     'outcome="given_back"'),
])
def test_the_label_shares_read_one_label_over_all(metric, series, label, other):
    text = f"{series}{{{other}}} %d\n{series}{{{label}}} %d\n"
    ctx = {"before": promtext.parse(text % (10, 30)), "after": promtext.parse(text % (34, 246))}
    args = loader.read_json(loader.data_file("layer_metrics", metric))["args"]
    assert args["series"] == series
    assert prom_label_share.read(ctx, **args) == pytest.approx(90.0)
    # a program without the counter (the parent), or a window in which nothing was admitted
    assert prom_label_share.read({"before": {}, "after": {}}, **args) is None
    assert prom_label_share.read({"before": ctx["after"], "after": ctx["after"]}, **args) is None


def test_the_rehearsal_of_the_cell_runs_on_the_cpu():
    """CLI -> HTTP -> scheduler -> slots and snapshots -> both programs at the
    configuration's rehearsal size: never a result, but the probe's hit (a
    snapshot) must read a gap of 0.0 and nothing may compile in the window."""
    env = {k: v for k, v in os.environ.items() if not k.startswith(("JAX_", "XLA_", "DYN_"))}
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"), "--workload", CELL, "--seed",
         "2147483659", "--seconds", "6", "--trace", "0", "--rehearse-cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] and not line["correct"] and line["failed"] == 0
    assert line["compared"]["probe_hit_gap"]["value"] == 0.0
    assert line["compared"]["probe_cold_gap"]["value"] == 0.0
    assert line["compared"]["programs_compiled_in_window"]["value"] == 0
    assert "ssm_slot" in line["engine"]["cache_kinds"]
