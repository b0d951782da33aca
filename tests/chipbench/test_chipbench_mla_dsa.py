"""Bytes and operations of a decode step of the latent-attention configuration
(chipbench/shapes_mla_dsa.py) and its roofline reader, by hand (no JAX)."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import loader, run, shapes_mla_dsa as sh, trace_reduce as tr  # noqa: E402
from chipbench.readers import decode_roofline_mla_dsa as reader  # noqa: E402

CELL = "deepseek-v3.2-exp-6l-ep16.longdoc-shared"


@pytest.fixture(scope="module")
def config():
    cfg = loader.read_json(loader.data_file("configs", "deepseek-v3.2-exp-6l-ep16"))
    return run.model_of(cfg, False), cfg["serve"]


def test_layer_weight_elements_are_the_issue_s_arithmetic(config):
    model, _ = config
    per = sh.layer_weight_elements(model)
    # MLA: q_a 11.0 M, q_b 37.7 M, kv_a 4.1 M, o 117.4 M; the selector's wq_b 12.6 M
    assert per["quant"] == (7168 * 1536 + 1536 * 24576 + 7168 * 576 + 16384 * 7168
                            + 1536 * 8192)
    # W^UK and W^UV 8.4 M each; the selector's wk and weights_proj
    assert per["float"] == 2 * 128 * 512 * 128 + 7168 * 128 + 7168 * 64


@pytest.mark.parametrize("rows, want", [(0, 0.0), (1, 0.5), (32, 16 * (1 - (31 / 32) ** 32))])
def test_experts_touched_follows_the_rows(config, rows, want):
    model, _ = config
    assert sh.experts_touched(model, rows) == pytest.approx(want)
    assert sh.experts_touched(model, rows) <= model["n_routed_experts"]


def test_decode_weight_bytes_count_only_the_experts_some_row_chose(config):
    model, serve = config
    none = sh.decode_weight_bytes(model, serve, 0)
    per = sh.layer_weight_elements(model)
    want = (6 * (per["quant"] + 2 * per["float"]) + 3 * 7168 * 18432
            + 5 * (2 * 7168 * 256 + 3 * 7168 * 2048) + 7168 * 16160)
    assert none == pytest.approx(want)
    one = sh.decode_weight_bytes(model, serve, 1)
    # one row chooses 8 of 256: half an expert of the 16 held, in each of 5 layers
    assert one - none == pytest.approx(5 * 0.5 * 3 * 7168 * 2048)
    everything = 5 * 16 * 3 * 7168 * 2048
    assert sh.decode_weight_bytes(model, serve, 10**6) == pytest.approx(none + everything)
    # stored weights are 5.7 GB: a step of few rows needs well under that
    assert 2.0e9 < one < 2.4e9 and none + everything < 5.8e9


def test_cache_bytes_score_every_position_and_keep_the_selected(config):
    model, serve = config
    # bf16 pages: 128 indexer values scored, 576 latent values kept, 6 layers
    assert sh.cache_bytes(model, serve, 1, 0) == 6 * 128 * 2
    assert sh.cache_bytes(model, serve, 0, 1) == 6 * 576 * 2
    assert sh.decode_step_bytes(model, serve, 2, 16400, 4096) == pytest.approx(
        sh.decode_weight_bytes(model, serve, 2) + 16400 * 1536 + 4096 * 6912)
    assert sh.decode_attention_flops(model, 1, 0) == 6 * 2 * 64 * 128
    assert sh.decode_attention_flops(model, 0, 1) == 6 * (2 * 128 * 576 + 2 * 128 * 512)


def test_in_flight_keeps_at_most_topk_of_each_row():
    reqs = [
        {"ok": True, "t_first": 0.0, "t_last": 10.0, "n_tokens": 100, "prompt_len": 500},
        {"ok": True, "t_first": 4.0, "t_last": 6.0, "n_tokens": 20, "prompt_len": 100},
        {"ok": False, "t_first": 0.0, "t_last": 10.0, "n_tokens": 1, "prompt_len": 9},
    ]
    rows, held, kept = reader.in_flight(reqs, 4.0, 8.0, topk=200)
    assert rows == pytest.approx(1.5)
    assert held == pytest.approx(560 + 110 / 2)
    assert kept == pytest.approx(200 + 110 / 2)  # the long row keeps 200 of its 560


def test_roofline_on_a_hand_made_trace(config):
    model, serve = config
    reqs = [{"ok": True, "t_first": 0.0, "t_last": 10.0, "n_tokens": 40, "prompt_len": 8000}] * 2
    rows, held, kept = reader.in_flight(reqs, 4.0, 6.0, model["index_topk"])
    assert (rows, kept) == (2.0, 4096.0)
    floor_s = sh.decode_step_bytes(model, serve, rows, held, kept) / 819e9
    step_ns = int(floor_s * 4 * 1e9)  # every step takes four times the floor
    mods = [("jit__multi(5)", i * 10 * step_ns, serve["decode_steps"] * step_ns) for i in range(5)]
    planes = {"/device:TPU:0": {tr.OPS_LINE: [("fusion.1", 0, 10)], tr.MODULES_LINE: mods}}
    ctx = {"trace": tr.DeviceTrace(planes, 4.0, 6.0), "model": model, "serve": serve,
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
           "window": {"requests": reqs}}
    assert reader.read(ctx, module_pattern="^jit__multi") == pytest.approx(25.0, rel=1e-3)
    assert reader.read(ctx, module_pattern="^jit__absent") is None
    assert reader.read(dict(ctx, trace=None), module_pattern="^jit__multi") is None
    # a configuration without the selector's keys has nothing to read
    dense = {k: v for k, v in model.items() if k != "index_topk"}
    assert reader.read(dict(ctx, model=dense), module_pattern="^jit__multi") is None


def test_the_cell_reports_the_roofline_and_the_idle_share():
    names = [m["name"] for m in loader.load_cell(CELL)["per_layer"]]
    assert "mla_dsa_decode_step_roofline" in names and "device_idle_share.closed_tpot" in names
    for other in ("qwen2.5-7b.chat-open", "qwen2.5-7b.prefill-closed"):
        theirs = [m["name"] for m in loader.load_cell(other)["per_layer"]]
        assert "mla_dsa_decode_step_roofline" not in theirs
        assert "device_idle_share.closed_tpot" not in theirs


@pytest.mark.parametrize("name", ["dsa_select_time_share", "mla_sparse_attn_time_share",
                                  "dsa_selected_share", "moe_local_pairs_per_token",
                                  "device_idle_share.closed_tpot",
                                  "mla_dsa_decode_step_roofline"])
def test_every_new_metric_says_what_it_reads(name):
    spec = loader.read_json(loader.data_file("layer_metrics", name))
    assert len(spec["about"]) > 80


# The names docs/tracing.md lists for the PREFILL side of the two XLA-stage
# metrics (its rows "selector, device side" and "sparse latent attention,
# device side", as of PR 36), the decode-side names a saved trace of PR 37
# still holds (kept), and the ones no program has held since PR 31 (dropped).
SELECT_PREFILL = ["fusion f32[64,1024]", "reduce-window s32[64,72,128]",
                  "convert_reduce_fusion s32[64]", "slice_reduce_fusion s32[64,72]",
                  "fusion bf16[64,16,128]"]
SPARSE_PREFILL = ["fusion f32[64,128]", "fusion f32[64,128,512]", "fusion bf16[64,16,640]",
                  "constant_dynamic-slice_fusion bf16[64,128,640]", "broadcast f32[64,128,512]"]
PATTERN_CASES = (
    [("dsa_select_time_share", n, True)
     for n in SELECT_PREFILL + ["fusion bf16[9216,16,128]", "fusion f32[16,9216]"]]
    + [("dsa_select_time_share", n, False) for n in ("sort f32[16,9216]", "fusion u32[16,9216]")]
    + [("mla_sparse_attn_time_share", n, True) for n in SPARSE_PREFILL + ["fusion bf16[16,128,512]"]]
    + [("mla_sparse_attn_time_share", n, False)
       for n in ("fusion bf16[32768,640]", "fusion s32[32768]", "fusion f32[16,128,2048]",
                 "mla_sparse_decode_attention bf16[16,128,512]")])


@pytest.mark.parametrize("metric,op,listed", PATTERN_CASES)
def test_the_pruned_patterns_read_what_runs_and_nothing_dropped(metric, op, listed):
    spec = loader.read_json(loader.data_file("layer_metrics", metric))
    events = [(op, 0, 7), ("fusion f32[16]", 10, 5)]
    assert tr.sum_matching_ns(events, spec["args"]["pattern"]) == (7 if listed else 0)
    docs = os.path.join(ROOT, "docs", "tracing.md")
    if listed and os.path.exists(docs):
        assert f"`{op}`" in open(docs).read()


@pytest.mark.parametrize("name,stale", [
    ("mla_dense_decode_step_roofline", "reads every held expert"),
    ("mla_dsa_decode_step_roofline", "reads every held expert"),
    ("moe_local_pairs_per_token", "the 16 experts held here"),
    ("moe_held_experts_read_share", "100% means the mechanism idles")])
def test_the_reworded_metrics_no_longer_say_what_is_false(name, stale):
    spec = loader.read_json(loader.data_file("layer_metrics", name))
    assert stale not in spec["about"] and len(spec["about"]) > 80
    assert "bench.py l." not in loader.read_json(os.path.join(ROOT, "chipbench", "peaks.json"))["source"]
