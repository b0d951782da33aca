"""The jamba2-3b configuration, its cell, the accepted metrics it is guarded by
and the three ``mamba1_*`` metric files that STAND BY load, and the bytes and
operations of a model with Mamba-1 layers (chipbench/shapes_mamba1_hybrid.py)
with its reader, by hand (no JAX).  Nothing here asserts a position of an entry
or a count of cells."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import loader, run, shapes_mamba1_hybrid as sh, trace_reduce as tr  # noqa: E402
from chipbench import traffic  # noqa: E402
from chipbench.readers import prompt_step_mfu_mamba1_hybrid as mfu_reader  # noqa: E402
from chipbench.readers import trace_time_share  # noqa: E402

CELL = "jamba2-3b.prefill-closed"
# What a `benchmark` PR appends, written out whole: name -> the entry.
ENTRIES = {
    "mamba1_scan_time_share": {
        "name": "mamba1_scan_time_share", "unit": "%", "better": "lower", "source": "device_trace",
        "layer": "kernels", "moves": "ttft_ms_p50", "workloads": [CELL]},
    "mamba1_step_time_share": {
        "name": "mamba1_step_time_share", "unit": "%", "better": "lower", "source": "device_trace",
        "layer": "kernels", "moves": "tpot_ms_p90", "workloads": [CELL]},
    "mamba1_prefill_step_mfu": {
        "name": "mamba1_prefill_step_mfu", "unit": "%", "better": "higher", "source": "device_trace",
        "layer": "model", "moves": "ttft_ms_p50", "workloads": [CELL]},
}
READERS = {"mamba1_scan_time_share": "trace_time_share",
           "mamba1_step_time_share": "trace_time_share",
           "mamba1_prefill_step_mfu": "prompt_step_mfu_mamba1_hybrid"}
# The accepted metric whose ``workloads`` list the cell's name was appended to.
APPENDED = ["device_idle_share.closed_tpot"]
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12, "int8_ops": 393e12}
BENCH = loader.load_benchmark()


@pytest.fixture(scope="module")
def config():
    cfg = loader.read_json(loader.data_file("configs", "jamba2-3b"))
    return run.model_of(cfg, False), cfg["serve"]


def test_the_cell_is_the_issue_s(config):
    cell = loader.load_cell(CELL)
    assert cell["cell"] == {k: cell["cell"][k] for k in ("name", "config", "traffic", "chips", "why")}
    assert (cell["cell"]["config"], cell["cell"]["traffic"], cell["cell"]["chips"]) == (
        "jamba2-3b", "prefill-closed", 1)
    assert "nothing cut" in cell["cell"]["why"] and len(cell["cell"]["why"]) <= 200
    entry = next(c for c in BENCH["configs"] if c["name"] == "jamba2-3b")
    assert len(entry["why"]) <= 200 and entry["reduced"] == [] == cell["config"]["reduced"]
    assert entry["source"] == cell["config"]["source"] == (
        "https://huggingface.co/ai21labs/AI21-Jamba2-3B/blob/main/config.json")
    mix = cell["mix"]  # the mix as qwen2.5-7b.prefill-closed runs it, unedited
    assert mix["loop"] == "closed" and mix["schedule_seed"] == 23
    assert mix["sharing"] == {"kind": "none"}
    assert mix["prompt"] == {"dist": "lognormal", "median": 2048, "sigma": 0.4, "min": 1024,
                             "max": 3584}
    assert mix["output"] == {"dist": "uniform", "min": 16, "max": 64}
    assert [m["name"] for m in cell["end_to_end"]] == ["ttft_ms_p50", "tpot_ms_p90", "setup_s"]
    model, serve = config
    assert model["model_type"] == "jamba" and model["num_experts"] == 1
    assert (model["attn_layer_period"], model["attn_layer_offset"], model["mamba_d_state"],
            model["mamba_dt_rank"], model["num_key_value_heads"]) == (14, 7, 16, 160, 1)
    assert set(cell["config"]["assumed"]) >= {
        "layer_order", "head_dim", "no_positions", "split_orders", "inner_norms", "swiglu_halves",
        "state_float32", "summation_order", "draw", "weights", "kv_pages"}
    assert serve == {"dtype": "bfloat16", "kv_cache_dtype": "bfloat16", "block_size": 16,
                     "num_blocks": 32768, "max_model_len": 4096, "max_batch": 32,
                     "prefill_chunk": 512, "decode_steps": 4}
    assert cell["params"]["clients"] == 8
    assert mix["prompt"]["max"] + mix["output"]["max"] <= serve["max_model_len"]
    reqs = traffic.build_requests(mix, 32, seed=3_000_000_019, vocab=model["vocab_size"])
    assert max(max(r["prompt"]) for r in reqs) < model["vocab_size"] == 65536
    assert "1.15" in cell["params"]["set_from"]
    # at least 120 completions in the window, and a pool that does not wrap
    assert cell["params"]["pool_per_s"] * cell["run_seconds"] > 1.1 * 120
    small = cell["config"]["rehearsal"]["model"]
    assert small["model_type"] == "jamba" and small["hidden_size"] <= 128


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
    one-step form's pattern holds may match the scan's, nor the other way
    (tests/test_tpu_compile.py holds both against the compiled programs)."""
    step = loader.read_json(loader.data_file("layer_metrics", "mamba1_step_time_share"))
    scan = loader.read_json(loader.data_file("layer_metrics", "mamba1_scan_time_share"))
    assert step["holds"] and scan["holds"]
    for name in step["holds"]:
        assert re.search(step["args"]["pattern"], name), name
        assert not re.search(scan["args"]["pattern"], name), name
    for name in scan["holds"]:
        assert re.search(scan["args"]["pattern"], name), name
        assert not re.search(step["args"]["pattern"], name), name
    # A = -exp(A_log) runs under the same name in both programs: in neither pattern
    for spec in (step, scan):
        assert not re.search(spec["args"]["pattern"], "negate_bitcast_fusion f32[16,5120]")
    for word in ("mamba1_step", "models/mamba1.py"):
        assert word in step["about"]
    for word in ("mamba1_scan", "models/mamba1.py"):
        assert word in scan["about"]


def test_the_three_have_no_entry_and_the_cell_is_guarded_by_what_the_benchmark_has():
    """BENCHMARK.json has no entry for the three (an accepted test holds PR 41's
    ten to the END of ``per_layer``: PERF.md section 7); the cell reports the
    accepted metric whose list its name was appended to and every metric that
    lists no cells.  It is NOT on ``idle_gap_named_share``'s list: its device is
    never idle (busy 3.018 s of 3.018, the longest gap 7 us), so the reader has
    nothing to read, as on qwen's ``prefill-closed``, and the benchmark check
    refuses a new cell that lists a metric its traced line lacks."""
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    assert not set(ENTRIES) & set(by_name)
    names = [m["name"] for m in loader.load_cell(CELL)["per_layer"]]
    for name in APPENDED + ["prefix_hit_rate", "step_device_ms_p50", "fused_chunk_device_ms_p50",
                            "attn_prefill_time_share", "attn_decode_time_share"]:
        assert name in names, name
    for name in APPENDED:
        assert CELL in by_name[name]["workloads"]
    # no idle gap to name; no experts, no latent pages, no short convolution; qwen's lists stay qwen's
    for name in ("idle_gap_named_share", "moe_local_pairs_per_token", "moe_held_experts_read_share",
                 "moe_grouped_matmul_time_share", "decode_step_roofline",
                 "device_idle_share.saturated", "hybrid_decode_step_roofline"):
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
    assert sh.layer_counts(model) == {"mamba1": 26, "attn": 2, "dense": 28}
    assert sh.mamba1_dims(model) == (5120, 16, 4, 160)
    per = sh.mixer_weight_elements(model)
    assert sum(per["mamba1"].values()) == 41_241_792  # ISSUE 56: a Mamba-1 mixer
    assert per["mamba1"]["matmul"] == 26_214_400 + 983_040 + 819_200 + 13_107_200
    assert per["mamba1"]["f32"] == 81_920 + 5_120 + 5_120
    assert sum(per["attn"].values()) == 13_762_560
    # 3,029,337,472 parameters at 2 B, the 92,160 float32 elements a Mamba-1 layer at 4
    assert sh.weight_bytes(model, serve) == 2 * 3_029_337_472 + 26 * 92_160 * 2
    # int8 serving would leave every Mamba-1 leaf as it is
    int8 = dict(serve, weight_quant="int8")
    mamba = 26 * (per["mamba1"]["matmul"] + per["mamba1"]["float"]) * 2 + 26 * 92_160 * 4
    assert sh.weight_bytes(model, int8) == (
        mamba + 2 * 13_762_560 + 57 * 2560 * 2 + 28 * 62_914_560 + 167_772_160)


def test_state_and_page_bytes_and_operations(config):
    model, serve = config
    # a slot: 26 x (16 x 5120 x 4 + 3 x 5120 x 2) = 9,318,400 B, read once and written once
    assert sh.state_bytes_per_row(model, serve) == 2 * 9_318_400
    assert sh.state_bytes_per_row(model, serve) == 26 * 2 * (327_680 + 30_720)
    assert sh.kv_bytes_per_token(model, serve) == 1024  # the TWO attention layers, ONE K/V head
    assert sh.scan_updates_per_token(model) == 26 * 81_920
    rows, held = 8, 8 * 2200
    step = sh.decode_step_bytes(model, serve, rows, held)
    assert step == sh.weight_bytes(model, serve) + held * 1024 + rows * 18_636_800
    assert 6.2e9 < step < 6.3e9  # the bf16 weights are 97% of it
    per_token = 2 * (26 * 41_123_840 + 2 * 13_762_560 + 28 * 62_914_560) + 6 * 26 * 81_920
    assert sh.token_ops(model) == per_token and 5.7e9 < per_token < 5.8e9
    assert sh.decode_step_ops(model, 1, 0) == per_token + 2 * 2560 * 65536
    assert sh.decode_step_ops(model, 0, 100) == 2 * 100 * 2 * 20 * 2 * 128
    assert step / 819e9 > 20 * sh.decode_step_ops(model, rows, held) / 197e12  # the bytes bind
    # a 512-token chunk in one row behind 1024 cached positions
    attended = 512 * 1024 + 512 * 513 / 2
    assert sh.prompt_step_ops(model, 512, 1024) == pytest.approx(
        512 * per_token + 2 * attended * 2 * 20 * 2 * 128 + 2 * 2560 * 65536)
    assert 2.9e12 < sh.prompt_step_ops(model, 512, 1024) < 3.0e12  # 15 ms at the bf16 peak


def test_the_mfu_and_the_shares_on_a_hand_made_trace(config):
    model, serve = config
    reqs = [{"ok": True, "t_ref": -1.0, "t_first": 0.0, "t_last": 10.0, "n_tokens": 40,
             "prompt_len": 2048}] * 3
    floor_s = sh.prompt_step_ops(model, 512, 1024) / 197e12
    step_ns = int(floor_s * 4 * 1e9)  # the median step takes four times its floor
    mods = [("jit__step(7)", i * 10 * step_ns, d) for i, d in enumerate(
        [step_ns // 3, step_ns, step_ns, step_ns, 2 * step_ns])]
    mods.append(("jit__multi(9)", 60 * step_ns, 17))
    step_spec = loader.read_json(loader.data_file("layer_metrics", "mamba1_step_time_share"))
    scan_spec = loader.read_json(loader.data_file("layer_metrics", "mamba1_scan_time_share"))
    ops = [(step_spec["holds"][0], 0, 300), (scan_spec["holds"][0], 300, 100),
           ("fused_prefill_attention bf16[512,20,128]", 400, 600)]
    planes = {"/device:TPU:0": {tr.OPS_LINE: ops, tr.MODULES_LINE: mods}}
    ctx = {"trace": tr.DeviceTrace(planes, 4.0, 6.0), "model": model, "serve": serve,
           "peaks": PEAKS, "window": {"requests": reqs}, "cell": {"mix": {}}}
    mfu_spec = loader.read_json(loader.data_file("layer_metrics", "mamba1_prefill_step_mfu"))
    assert mfu_reader.read(ctx, **mfu_spec["args"]) == pytest.approx(25.0, rel=1e-3)
    assert trace_time_share.read(ctx, **step_spec["args"]) == pytest.approx(30.0)
    assert trace_time_share.read(ctx, **scan_spec["args"]) == pytest.approx(10.0)
    # a configuration without Mamba-1 layers (the parent's every cell), a run without a
    # trace and a trace without the program read nothing and do not raise
    assert mfu_reader.read(dict(ctx, model={"hidden_size": 1}), module_pattern="^jit__step") is None
    assert mfu_reader.read(dict(ctx, trace=None), module_pattern="^jit__step") is None
    assert mfu_reader.read(ctx, module_pattern="^jit__absent") is None


def test_the_reference_copy_is_the_programs_reference():
    with open(os.path.join(ROOT, "chipbench/reference/jamba.py")) as f:
        copy = f.read()
    with open(os.path.join(ROOT, "dynamo_tpu/models/reference/jamba.py")) as f:
        assert copy == f.read()
    assert "import dynamo_tpu" not in copy and "from dynamo_tpu" not in copy


@pytest.mark.slow
def test_the_cell_rehearses_end_to_end_on_the_cpu():
    """`run.py --rehearse-cpu` walks CLI -> HTTP -> scheduler -> K/V pages and
    state slots at the configuration's tiny size: both probe gaps 0.0, never a
    result."""
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"), "--workload", CELL,
         "--seed", "5", "--seconds", "6", "--trace", "1", "--rehearse-cpu"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, timeout=600, cwd=ROOT)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is False and line["rehearsal"] is True and line["failed"] == 0
    assert line["compared"]["probe_hit_gap"]["value"] == 0.0
    assert line["compared"]["probe_cold_gap"]["value"] == 0.0
    assert "mamba1_slot" in line["engine"]["cache_kinds"]
