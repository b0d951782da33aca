"""The kimi-linear-48b-a3b-8l-ep8 configuration, its cell, the metrics it is
guarded by and the three ``kda_*`` metric files that STAND BY load, and the
bytes and operations of a model with Kimi Delta Attention layers
(chipbench/shapes_kda_hybrid.py) with its reader, by hand (no JAX).  Nothing
here asserts a position of an entry or a count of cells."""

import json
import os
import re
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import loader, run, shapes_kda_hybrid as sh, trace_reduce as tr  # noqa: E402
from chipbench import traffic  # noqa: E402
from chipbench.readers import decode_roofline_kda_hybrid as step_reader  # noqa: E402
from chipbench.readers import trace_time_share  # noqa: E402

CELL = "kimi-linear-48b-a3b-8l-ep8.reason-shared"
# What a `benchmark` PR appends, written out whole: name -> the entry.
ENTRIES = {
    "kda_hybrid_decode_step_roofline": {
        "name": "kda_hybrid_decode_step_roofline", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "model", "moves": "tpot_ms_p90", "workloads": [CELL]},
    "kda_step_time_share": {
        "name": "kda_step_time_share", "unit": "%", "better": "lower", "source": "device_trace",
        "layer": "kernels", "moves": "tpot_ms_p90", "workloads": [CELL]},
    "kda_scan_time_share": {
        "name": "kda_scan_time_share", "unit": "%", "better": "lower", "source": "device_trace",
        "layer": "kernels", "moves": "ttft_ms_p50", "workloads": [CELL]},
}
READERS = {"kda_hybrid_decode_step_roofline": "decode_roofline_kda_hybrid",
           "kda_step_time_share": "trace_time_share", "kda_scan_time_share": "trace_time_share"}
# The accepted metrics whose ``workloads`` lists the cell's name was appended to.
APPENDED = ["moe_local_pairs_per_token", "moe_held_experts_read_share",
            "moe_grouped_matmul_time_share", "device_idle_share.closed_tpot",
            "idle_gap_named_share", "mla_attended_positions_per_query",
            "mla_dense_decode_kernel_time_share", "mla_dense_decode_kernel_roofline"]
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12, "int8_ops": 393e12}
BENCH = loader.load_benchmark()


@pytest.fixture(scope="module")
def config():
    cfg = loader.read_json(loader.data_file("configs", "kimi-linear-48b-a3b-8l-ep8"))
    return run.model_of(cfg, False), cfg["serve"]


def test_the_cell_is_the_issue_s(config):
    cell = loader.load_cell(CELL)
    assert cell["cell"]["chips"] == 1 and cell["cell"]["traffic"] == "reason-shared"
    assert "1/8 expert load" in cell["cell"]["why"] and len(cell["cell"]["why"]) <= 200
    entry = next(c for c in BENCH["configs"] if c["name"] == cell["cell"]["config"])
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200
    mix = cell["mix"]
    assert mix["loop"] == "closed" and mix["schedule_seed"] == 23
    assert mix["sharing"] == {"kind": "shared_prefix", "groups": 8, "prefix_len": 1024}
    assert mix["prompt"] == {"dist": "uniform", "min": 1152, "max": 1536}
    assert mix["output"] == {"dist": "uniform", "min": 512, "max": 1024}
    assert [m["name"] for m in cell["end_to_end"]] == ["ttft_ms_p50", "tpot_ms_p90", "setup_s"]
    model, serve = config
    assert model["model_type"] == "kimi_linear" and model["mla_use_nope"] is True
    assert model["linear_attn_config"] == {
        "full_attn_layers": [4, 8], "head_dim": 128, "kda_layers": [1, 2, 3, 5, 6, 7],
        "num_heads": 32, "short_conv_kernel_size": 4}
    assert cell["config"]["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "linear_attn_config", "num_experts", "ep_size"]
    assert set(cell["config"]["assumed"]) >= {
        "kda_projections", "kda_conv", "kda_qk_norm", "kda_decay", "kda_output", "state_float32",
        "kda_chunk", "mla", "gate", "expert_halves", "weights", "draw", "latent_pages"}
    assert serve == {"dtype": "bfloat16", "weight_quant": "int8", "kv_cache_dtype": "bfloat16",
                     "block_size": 16, "num_blocks": 32768, "max_model_len": 4096, "max_batch": 32,
                     "prefill_chunk": 512, "decode_steps": 4}
    assert cell["params"]["clients"] == serve["max_batch"] == 32  # every live slot in use
    # the shared prefix is a whole number of resume strides, and the request's own
    # tokens never cross the next: ONE prompt step behind a hit
    assert mix["sharing"]["prefix_len"] % serve["prefill_chunk"] == 0
    assert mix["prompt"]["max"] - mix["sharing"]["prefix_len"] <= serve["prefill_chunk"]
    assert mix["prompt"]["max"] + mix["output"]["max"] <= serve["max_model_len"]
    reqs = traffic.build_requests(mix, 32, seed=3_000_000_019, vocab=model["vocab_size"])
    assert len({tuple(r["prompt"][:1024]) for r in reqs}) == 8
    assert max(max(r["prompt"]) for r in reqs) < model["vocab_size"] == 163840
    assert "1.15" in cell["params"]["set_from"]
    assert cell["params"]["pool_per_s"] * cell["run_seconds"] > 110
    small = cell["config"]["rehearsal"]["model"]
    assert small["model_type"] == "kimi_linear" and small["hidden_size"] <= 128


@pytest.mark.parametrize("name", list(ENTRIES))
def test_every_new_file_matches_the_entry_a_benchmark_pr_appends(name):
    """The files stand by, each agreeing with the entry written out above."""
    entry = ENTRIES[name]
    spec = loader.read_json(loader.data_file("layer_metrics", name))
    assert {k: spec[k] for k in ("name", "unit", "layer", "moves")} == {
        k: entry[k] for k in ("name", "unit", "layer", "moves")}
    assert spec["reader"] == READERS[name] and len(spec["about"]) > 200
    assert callable(loader.load_reader(spec["reader"]).read)
    assert entry["layer"] in {m["layer"] for m in BENCH["per_layer"]}  # a layer the benchmark names
    assert entry["source"] in loader.SOURCES and entry["better"] in ("lower", "higher")
    assert set(entry) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}


def test_the_two_shares_patterns_are_disjoint():
    """A trace keeps an op's name and shape, not its program: no name the
    one-step form's pattern holds may match the chunked form's, nor the other
    way (tests/test_tpu_compile.py holds both against the compiled programs)."""
    step = loader.read_json(loader.data_file("layer_metrics", "kda_step_time_share"))
    scan = loader.read_json(loader.data_file("layer_metrics", "kda_scan_time_share"))
    assert step["holds"] and scan["holds"]
    for name in step["holds"]:
        assert re.search(step["args"]["pattern"], name), name
        assert not re.search(scan["args"]["pattern"], name), name
    for name in scan["holds"]:
        assert re.search(scan["args"]["pattern"], name), name
        assert not re.search(step["args"]["pattern"], name), name
    for word in ("kda_step", "models/kda.py"):
        assert word in step["about"]
    for word in ("kda_scan", "models/kda.py"):
        assert word in scan["about"]


def test_the_three_have_no_entry_and_the_cell_is_guarded_by_what_the_benchmark_has():
    """BENCHMARK.json has no entry for the three (an accepted test holds PR 41's
    ten to the END of ``per_layer``: PERF.md section 7); the cell reports the
    accepted metrics whose lists its name was appended to."""
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    assert not set(ENTRIES) & set(by_name)
    names = [m["name"] for m in loader.load_cell(CELL)["per_layer"]]
    for name in APPENDED + ["prefix_hit_rate", "step_device_ms_p50", "fused_chunk_device_ms_p50"]:
        assert name in names, name
    for name in APPENDED:
        assert by_name[name]["workloads"][-1] == CELL  # appended, at the end
    # counted for every layer as latent, or stale, or held to kimi-k2's cell by an accepted test
    for name in ("mla_dense_decode_step_roofline", "mla_dense_prefill_attn_time_share",
                 "mla_dense_prefill_roofline", "mla_dense_prefill_kernel_time_share",
                 "ssm_hybrid_decode_step_roofline", "hybrid_decode_step_roofline"):
        assert name not in names, name
    for m in BENCH["end_to_end"]:
        assert CELL not in m.get("workloads", [])


def test_every_cell_still_loads_and_none_reports_the_new_metrics():
    for w in BENCH["workloads"]:
        reported = {m["name"] for m in loader.load_cell(w["name"])["per_layer"]}
        assert not set(ENTRIES) & reported, w["name"]


def test_the_three_entries_appended_to_a_copy_are_this_cell_s_alone(tmp_path):
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"), os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = loader.load_benchmark()
    bench["per_layer"].extend(ENTRIES.values())
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    for w in bench["workloads"]:
        reported = {m["name"]: m for m in loader.load_cell(w["name"], root)["per_layer"]}
        if w["name"] != CELL:
            assert not set(ENTRIES) & set(reported), w["name"]
            continue
        for name, entry in ENTRIES.items():
            assert {k: reported[name][k] for k in ("unit", "layer", "moves")} == {
                k: entry[k] for k in ("unit", "layer", "moves")}
    e2e = {m["name"] for m in loader.load_cell(CELL)["end_to_end"]}
    assert {e["moves"] for e in ENTRIES.values()} <= e2e


def test_weight_bytes_are_the_issue_s_arithmetic(config):
    model, serve = config
    assert sh.layer_counts(model) == {"kda": 6, "mla": 2, "dense": 1, "moe": 7}
    assert sh.kda_dims(model) == (32, 128, 4, 12288)
    per = sh.mixer_weight_elements(model)
    assert sum(per["kda"].values()) == 39_518_368  # ISSUE 53: 39.5 M a KDA layer
    assert sum(per["mla"].values()) == 29_114_880  # 29.1 M a latent layer
    assert sh.expert_elements(model) == 7_077_888 and sh.router_width(model) == 256
    fixed = sh.fixed_weight_bytes(model, serve)
    want = (6 * (37_748_736 + 1_765_504 * 2 + 4128 * 4) + 2 * (24_920_064 + 4_194_816 * 2)
            + 17 * 2304 * 2 + 3 * 2304 * 9216
            + 7 * (7_077_888 + 2304 * 256 * 2 + 256 * 4) + 2304 * 163840)
    assert fixed == pytest.approx(want) and 0.80e9 < fixed < 0.83e9
    assert sh.decode_weight_bytes(model, serve, 0) == fixed
    # one row chooses 8 of 256, of which 1 is held on average
    assert sh.decode_weight_bytes(model, serve, 1) - fixed == pytest.approx(7 * 1 * 7_077_888)
    assert 20 < sh.experts_touched(model, 32) < 21  # of 32 held, at 32 rows: about 1.0 GB a step


def test_state_and_latent_bytes_and_operations(config):
    model, serve = config
    # a slot: 6 x (32 x 128 x 128 x 4 + 3 x 12288 x 2) = 13,025,280 B, read once and written once
    assert sh.state_bytes_per_row(model, serve) == 2 * 13_025_280
    assert sh.latent_bytes_per_token(model, serve) == 2 * 576 * 2  # the TWO latent layers only
    rows, held = 32, 32 * 2000
    step = sh.decode_step_bytes(model, serve, rows, held)
    assert step == pytest.approx(sh.decode_weight_bytes(model, serve, rows)
                                 + held * 2304 + rows * 26_050_560)
    # ISSUE 53's reckoning: about 2.9 GB a step, 0.83 GB of it KDA state, 3.6 ms at 819 GB/s
    assert 2.7e9 < step < 3.0e9 and rows * 26_050_560 == pytest.approx(0.83e9, rel=0.01)
    assert step / 819e9 > 5 * sh.decode_step_ops(model, rows, held) / 393e12  # the bytes bind
    per_row = (6 * (37_748_736 + 1_765_504 + 4 * 32 * 128 * 128) + 2 * (24_920_064 + 4_194_816)
               + 3 * 2304 * 9216 + 7 * (9 * 7_077_888 + 2304 * 256) + 2304 * 163840)
    assert sh.decode_step_ops(model, 1, 0) == 2 * per_row
    assert sh.decode_step_ops(model, 0, 100) == 2 * 100 * 2 * 32 * (2 * 512 + 64)


def test_the_roofline_and_the_shares_on_a_hand_made_trace(config):
    model, serve = config
    reqs = [{"ok": True, "t_ref": -1.0, "t_first": 0.0, "t_last": 10.0, "n_tokens": 1000,
             "prompt_len": 1300}] * 3
    held = 3 * 1800.0  # three rows, each at 1300 + 500 tokens mid-trace
    floor_s = sh.decode_step_bytes(model, serve, 3, held) / 819e9
    step_ns = int(floor_s * 2 * 1e9)  # every step takes twice its floor
    mods = [("jit__multi(7)", i * 10 * step_ns, serve["decode_steps"] * step_ns) for i in range(5)]
    step_spec = loader.read_json(loader.data_file("layer_metrics", "kda_step_time_share"))
    scan_spec = loader.read_json(loader.data_file("layer_metrics", "kda_scan_time_share"))
    ops = [(step_spec["holds"][0], 0, 300), (scan_spec["holds"][0], 300, 100),
           ("mla_dense_decode_attention bf16[32,32,512]", 400, 600)]
    planes = {"/device:TPU:0": {tr.OPS_LINE: ops, tr.MODULES_LINE: mods}}
    ctx = {"trace": tr.DeviceTrace(planes, 4.0, 6.0), "model": model, "serve": serve,
           "peaks": PEAKS, "window": {"requests": reqs}, "cell": {"mix": {}}}
    assert step_reader.read(ctx, **loader.read_json(loader.data_file(
        "layer_metrics", "kda_hybrid_decode_step_roofline"))["args"]) == pytest.approx(50.0, rel=1e-3)
    assert trace_time_share.read(ctx, **step_spec["args"]) == pytest.approx(30.0)
    assert trace_time_share.read(ctx, **scan_spec["args"]) == pytest.approx(10.0)
    # a configuration without KDA layers (the parent's every cell) reads nothing and does not raise
    assert step_reader.read(dict(ctx, model={"hidden_size": 1}), module_pattern="^jit__multi") is None
    assert step_reader.read(dict(ctx, trace=None), module_pattern="^jit__multi") is None


def test_the_reference_copy_is_the_programs_reference():
    with open(os.path.join(ROOT, "chipbench/reference/kimi_linear.py")) as f:
        copy = f.read()
    with open(os.path.join(ROOT, "dynamo_tpu/models/reference/kimi_linear.py")) as f:
        assert copy == f.read()
    assert "import dynamo_tpu" not in copy and "from dynamo_tpu" not in copy
