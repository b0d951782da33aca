"""The lfm2-8b-a1b configuration, its mix, cell, metrics and readers load, and
the bytes and operations of a hybrid decode step (chipbench/shapes_hybrid.py)
with its roofline reader and the label-share reader, by hand (no JAX)."""

import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import loader, promtext, run, shapes_hybrid as sh, trace_reduce as tr  # noqa: E402
from chipbench import traffic  # noqa: E402
from chipbench.readers import decode_roofline_hybrid as step_reader  # noqa: E402
from chipbench.readers import prom_label_share, trace_time_share  # noqa: E402

CELL = "lfm2-8b-a1b.assist-shared"
NEW = ["short_conv_time_share", "conv_tail_start_share", "hybrid_decode_step_roofline"]
APPENDED = ["moe_local_pairs_per_token", "moe_held_experts_read_share",
            "moe_grouped_matmul_time_share", "device_idle_share.closed_tpot"]
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12, "int8_ops": 393e12}


@pytest.fixture(scope="module")
def config():
    cfg = loader.read_json(loader.data_file("configs", "lfm2-8b-a1b"))
    return run.model_of(cfg, False), cfg["serve"]


def test_the_cell_is_the_issue_s(config):
    cell = loader.load_cell(CELL)
    assert cell["cell"]["chips"] == 1 and cell["params"]["clients"] in (24, 32)
    mix = cell["mix"]
    assert mix["loop"] == "closed" and mix["schedule_seed"] == 23
    assert mix["sharing"] == {"kind": "shared_prefix", "groups": 8, "prefix_len": 2048}
    assert mix["prompt"] == {"dist": "uniform", "min": 2176, "max": 3072}
    assert mix["output"] == {"dist": "uniform", "min": 128, "max": 384}
    assert [m["name"] for m in cell["end_to_end"]] == ["ttft_ms_p50", "tpot_ms_p90", "setup_s"]
    model, serve = config
    assert model["model_type"] == "lfm2_moe" and cell["config"]["reduced"] == []
    assert serve["kv_cache_dtype"] == "int8" and serve["num_blocks"] == 16384
    assert cell["params"]["clients"] <= serve["max_batch"] == 32
    assert mix["prompt"]["max"] + mix["output"]["max"] <= serve["max_model_len"]
    # every client's longest request fits the pool beside the shared prefixes
    assert serve["num_blocks"] * serve["block_size"] > 32 * serve["max_model_len"] + 8 * 2048
    reqs = traffic.build_requests(mix, 32, seed=3_000_000_019, vocab=model["vocab_size"])
    assert all(2176 <= r["prompt_len"] <= 3072 for r in reqs)
    assert len({tuple(r["prompt"][:2048]) for r in reqs}) == 8
    assert max(max(r["prompt"]) for r in reqs) < model["vocab_size"]
    # the pool does not wrap: a little over what the window completes
    assert cell["params"]["pool_per_s"] * cell["run_seconds"] > 110


def test_the_new_metrics_are_this_cell_s_alone_and_the_shared_ones_are_appended():
    names = [m["name"] for m in loader.load_cell(CELL)["per_layer"]]
    for name in NEW + APPENDED + ["prefix_hit_rate", "attn_decode_time_share",
                                  "attn_prefill_time_share", "step_device_ms_p50"]:
        assert name in names
    for name in ("decode_step_roofline", "mla_dense_decode_step_roofline", "dsa_selected_share",
                 "device_idle_share.paced", "mla_attended_positions_per_query"):
        assert name not in names
    for other in ("qwen2.5-7b.chat-open", "qwen2.5-7b.prefill-closed",
                  "deepseek-v3.2-exp-6l-ep16.longdoc-shared", "kimi-k2-6l-ep32.agent-shared"):
        theirs = [m["name"] for m in loader.load_cell(other)["per_layer"]]
        assert not set(NEW) & set(theirs)
    _holds_this_pr_s_entries(loader.load_benchmark())


def _holds_this_pr_s_entries(bench: dict) -> None:
    """PR 36's three metrics stand together and in order, its cell and its
    configuration are listed: wherever they stand, since every later PR appends."""
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(NEW[0])
    assert names[at:at + 3] == NEW
    assert CELL in [w["name"] for w in bench["workloads"]]
    assert "lfm2-8b-a1b" in [c["name"] for c in bench["configs"]]
    for m in bench["end_to_end"]:
        assert CELL not in m.get("workloads", [])


def test_entries_appended_by_later_prs_do_not_move_this_pr_s():
    """A copy of the benchmark with one more per-layer entry, one more cell and
    one more configuration at the END of their lists, as the builder's contract
    has every later PR add them."""
    bench = loader.load_benchmark()
    later = dict(bench,
                 per_layer=bench["per_layer"] + [dict(bench["per_layer"][0], name="a_later_metric")],
                 workloads=bench["workloads"] + [dict(bench["workloads"][0], name="a-later.cell")],
                 configs=bench["configs"] + [dict(bench["configs"][0], name="a-later-config")])
    _holds_this_pr_s_entries(later)
    moved = dict(bench, per_layer=[m for m in bench["per_layer"] if m["name"] != NEW[1]])
    with pytest.raises(AssertionError):
        _holds_this_pr_s_entries(moved)


@pytest.mark.parametrize("name", NEW)
def test_every_new_metric_says_what_it_reads_and_its_reader_loads(name):
    spec = loader.read_json(loader.data_file("layer_metrics", name))
    assert len(spec["about"]) > 80
    assert callable(loader.load_reader(spec["reader"]).read)


def test_weight_bytes_are_the_issue_s_arithmetic(config):
    model, serve = config
    assert sh.layer_counts(model) == {"conv": 18, "attn": 6, "dense": 2, "moe": 22}
    per = sh.mixer_weight_elements(model)
    assert per["conv"]["quant"] + per["conv"]["float"] == 16_783_360
    assert per["attn"]["quant"] + per["attn"]["float"] == 10_485_888
    assert sh.expert_elements(model) == 11_010_048 and sh.router_width(model) == 32
    fixed = sh.fixed_weight_bytes(model, serve)
    want = (18 * (16_777_216 + 2 * 6144) + 6 * (10_485_760 + 2 * 128) + 49 * 2048 * 2
            + 2 * 44_040_192 + 22 * (65_536 * 2 + 32 * 4) + 134_217_728)
    assert fixed == pytest.approx(want)
    assert sh.decode_weight_bytes(model, serve, 0) == fixed
    # one row chooses 4 of 32: four experts a layer; 24 rows nearly all 32
    assert sh.decode_weight_bytes(model, serve, 1) - fixed == pytest.approx(22 * 4 * 11_010_048)
    assert sh.experts_touched(model, 24) == pytest.approx(32 * (1 - 0.875 ** 24))
    assert 30.5 < sh.experts_touched(model, 24) < 31
    assert sh.experts_touched(model, 10 ** 6) == pytest.approx(32)
    # a decode step of 24 rows: about 8 GB, of which about 7.4 are experts
    whole = sh.decode_weight_bytes(model, serve, 24)
    assert 7.9e9 < whole < 8.1e9 and 7.3e9 < whole - fixed < 7.6e9


def test_cache_and_state_bytes_and_operations(config):
    model, serve = config
    assert sh.kv_bytes_per_token(model, serve) == 6144  # 2 x 8 x 64 int8 values in 6 layers
    assert sh.state_bytes_per_row(model, serve) == 2 * 147_456  # an entry read, an entry written
    assert sh.decode_step_bytes(model, serve, 24, 24 * 2900) == pytest.approx(
        sh.decode_weight_bytes(model, serve, 24) + 24 * 2900 * 6144 + 24 * 294_912)
    per_row = (18 * 16_777_216 + 6 * 10_485_760 + 2 * 44_040_192
               + 22 * (65_536 + 4 * 11_010_048) + 134_217_728)
    assert sh.decode_step_ops(model, 1, 0) == 2 * per_row
    assert sh.decode_step_ops(model, 0, 100) == 2 * 100 * 2 * 32 * 64 * 6
    # the bytes bind at 24 rows: ~9.9 ms against ~0.2 ms of operations
    assert sh.decode_step_bytes(model, serve, 24, 70000) / 819e9 > 20 * (
        sh.decode_step_ops(model, 24, 70000) / 393e12)
    bf16 = dict(serve, weight_quant=None, kv_cache_dtype="bfloat16")
    assert sh.kv_bytes_per_token(model, bf16) == 12288


def _ctx(model, serve, ops, mods, reqs):
    planes = {"/device:TPU:0": {tr.OPS_LINE: ops, tr.MODULES_LINE: mods}}
    return {"trace": tr.DeviceTrace(planes, 4.0, 6.0), "model": model, "serve": serve,
            "peaks": PEAKS, "window": {"requests": reqs}}


def test_the_step_roofline_and_the_scope_s_share_on_a_hand_made_trace(config):
    model, serve = config
    reqs = [{"ok": True, "t_first": 0.0, "t_last": 10.0, "n_tokens": 200, "prompt_len": 2500}] * 3
    held = 3 * 2600.0  # three rows, each at 2500 + 100 tokens mid-trace
    floor_s = sh.decode_step_bytes(model, serve, 3, held) / 819e9
    step_ns = int(floor_s * 2 * 1e9)  # every step takes twice its floor
    mods = [("jit__multi(7)", i * 10 * step_ns, serve["decode_steps"] * step_ns) for i in range(5)]
    ops = [(tr.short_name(hlo), start, dur) for hlo, start, dur in (
        ("%fusion.7 = bf16[18,16384,2,2048]{3,2,1,0} fusion(bf16[18,16384,2,2048] %p)", 0, 300),
        ("%copy.3 = bf16[32,18,2,2048]{3,2,1,0} copy(bf16[18,32,2,2048] %f)", 400, 100),
        ("%fusion.9 = bf16[512,2048]{1,0} fusion(s8[512,2048] %q)", 600, 600))]  # the last: not the scope's
    ctx = _ctx(model, serve, ops, mods, reqs)
    assert step_reader.read(ctx, module_pattern="^jit__multi") == pytest.approx(50.0, rel=1e-3)
    spec = loader.read_json(loader.data_file("layer_metrics", "short_conv_time_share"))
    assert trace_time_share.read(ctx, **spec["args"]) == pytest.approx(40.0)
    # nothing to read: no such program, no trace, another family
    assert step_reader.read(ctx, module_pattern="^jit__absent") is None
    assert step_reader.read(dict(ctx, trace=None), module_pattern="^jit__multi") is None
    dense_gqa = {"hidden_size": 3584, "num_attention_heads": 28}
    assert step_reader.read(dict(ctx, model=dense_gqa), module_pattern="^jit__multi") is None
    latent = {"kv_lora_rank": 512, "n_routed_experts": 12}
    assert step_reader.read(dict(ctx, model=latent), module_pattern="^jit__multi") is None


def test_the_scope_s_pattern_matches_whole_short_names_only():
    spec = loader.read_json(loader.data_file("layer_metrics", "short_conv_time_share"))
    pattern = re.compile(spec["args"]["pattern"])
    for name in ("copy bf16[32,18,2,2048]", "fusion bf16[18,16384,2,2048]", "slice bf16[32,1,2048]",
                 "fusion bf16[512,1,2048]", "pad_maximum_fusion bf16[256,2,2048]",
                 "reduce-precision_convert_fusion bf16[1024,2048]", "fusion bf16[96,2,2048]"):
        assert pattern.search(name), name
    for name in ("fusion bf16[512,2048]", "fusion f32[1,2048]", "fusion bf16[18,32,2,2048]",
                 "moe_grouped_matmul", "fused_decode_attention", "fusion bf16[512,6144]"):
        assert not pattern.search(name), name


def test_the_tail_share_reads_one_label_over_all():
    text = ('dynamo_tpu_conv_row_starts_total{state="zero"} %d\n'
            'dynamo_tpu_conv_row_starts_total{state="tail"} %d\n')
    ctx = {"before": promtext.parse(text % (10, 30)), "after": promtext.parse(text % (34, 246))}
    args = loader.read_json(loader.data_file("layer_metrics", "conv_tail_start_share"))["args"]
    assert prom_label_share.read(ctx, **args) == pytest.approx(90.0)
    # a program without the counter, or a window in which nothing was dispatched
    assert prom_label_share.read({"before": {}, "after": {}}, **args) is None
    assert prom_label_share.read({"before": ctx["after"], "after": ctx["after"]}, **args) is None
