"""The kimi-k2-6l-ep32 configuration, its mix, cell, metrics and readers load,
and the bytes and operations of dense latent decode
(chipbench/shapes_mla_dense.py) and its two roofline readers, by hand (no JAX)."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import loader, run, shapes_mla_dense as sh, shapes_mla_dsa, trace_reduce as tr  # noqa: E402
from chipbench import traffic  # noqa: E402
from chipbench.readers import decode_roofline_mla_dense as step_reader  # noqa: E402
from chipbench.readers import kernel_roofline_mla_dense as kernel_reader  # noqa: E402

CELL = "kimi-k2-6l-ep32.agent-shared"
NEW = ["mla_dense_prefill_attn_time_share", "mla_dense_decode_kernel_time_share",
       "mla_dense_decode_kernel_roofline", "mla_dense_decode_step_roofline",
       "mla_attended_positions_per_query"]
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}


@pytest.fixture(scope="module")
def config():
    cfg = loader.read_json(loader.data_file("configs", "kimi-k2-6l-ep32"))
    return run.model_of(cfg, False), cfg["serve"]


def test_the_cell_is_the_issue_s(config):
    cell = loader.load_cell(CELL)
    assert cell["cell"]["chips"] == 1 and cell["params"]["clients"] == 8
    mix = cell["mix"]
    assert mix["loop"] == "closed" and mix["schedule_seed"] == 23
    assert mix["sharing"] == {"kind": "shared_prefix", "groups": 4, "prefix_len": 12288}
    assert (mix["prompt"]["min"], mix["prompt"]["max"]) == (12544, 13056)
    assert mix["output"] == {"dist": "uniform", "min": 64, "max": 160}  # the fallback (96-224) was measured and not kept
    assert [m["name"] for m in cell["end_to_end"]] == ["ttft_ms_p50", "tpot_ms_p90", "setup_s"]
    model, serve = config
    assert "index_topk" not in model and model["model_type"] == "kimi_k2"
    # the longest prompt and answer fit a row; the pool does not wrap before the window ends
    assert mix["prompt"]["max"] + mix["output"]["max"] <= serve["max_model_len"]
    assert serve["num_blocks"] * serve["block_size"] > 8 * serve["max_model_len"] + 8 * 12288
    reqs = traffic.build_requests(mix, 16, seed=3_000_000_019, vocab=model["vocab_size"])
    assert all(12544 <= r["prompt_len"] <= 13056 for r in reqs)
    assert len({tuple(r["prompt"][:12288]) for r in reqs}) <= 4
    assert max(max(r["prompt"]) for r in reqs) < model["vocab_size"]


def test_the_new_metrics_are_this_cell_s_alone_and_the_shared_ones_are_appended():
    names = [m["name"] for m in loader.load_cell(CELL)["per_layer"]]
    for name in NEW + ["moe_local_pairs_per_token", "device_idle_share.closed_tpot",
                       "prefix_hit_rate", "step_device_ms_p50"]:
        assert name in names
    for name in ("mla_dsa_decode_step_roofline", "dsa_selected_share", "decode_step_roofline",
                 "mla_decode_kernel_time_share"):
        assert name not in names
    for other in ("qwen2.5-7b.chat-open", "qwen2.5-7b.prefill-closed",
                  "deepseek-v3.2-exp-6l-ep16.longdoc-shared"):
        theirs = [m["name"] for m in loader.load_cell(other)["per_layer"]]
        assert not set(NEW) & set(theirs)


@pytest.mark.parametrize("name", NEW)
def test_every_new_metric_says_what_it_reads_and_its_reader_loads(name):
    spec = loader.read_json(loader.data_file("layer_metrics", name))
    assert len(spec["about"]) > 80
    assert callable(loader.load_reader(spec["reader"]).read)


def test_layer_weight_elements_are_the_issue_s_arithmetic(config):
    model, serve = config
    per = sh.layer_weight_elements(model)
    # wq_a 11.0 M, wq_b 18.9 M, wkv_a 4.1 M, wo 58.7 M; W^UK and W^UV 4.2 M each; no selector
    assert per["quant"] == 7168 * 1536 + 1536 * 64 * 192 + 7168 * 576 + 64 * 128 * 7168
    assert per["float"] == 2 * 64 * 512 * 128
    none = sh.decode_weight_bytes(model, serve, 0)
    want = (6 * (per["quant"] + 2 * per["float"]) + 3 * 7168 * 18432
            + 5 * (2 * 7168 * 384 + 3 * 7168 * 2048) + 7168 * 20480)
    assert none == pytest.approx(want)
    # one row chooses 8 of 384: a quarter of an expert of the 12 held, in each of 5 layers
    assert sh.decode_weight_bytes(model, serve, 1) - none == pytest.approx(5 * 0.25 * 3 * 7168 * 2048)
    assert shapes_mla_dsa.experts_touched(model, 10**6) == pytest.approx(12)


def test_every_position_held_is_read_once_a_layer(config):
    model, serve = config
    assert sh.entry_bytes(model, serve) == 1152  # 576 bfloat16 values; the 64 zero lanes do not count
    assert sh.decode_step_bytes(model, serve, 7, 7 * 12800) == pytest.approx(
        sh.decode_weight_bytes(model, serve, 7) + 6 * 7 * 12800 * 1152)
    assert sh.attention_flops_per_position(model) == 2 * 64 * (576 + 512)
    assert sh.decode_attention_flops(model, 100) == 6 * 100 * 2 * 64 * 1088
    # at 64 heads the bytes bind: 1.41 ns against 0.71 ns a position
    assert sh.kernel_call_need_s(model, serve, 1000, PEAKS) == pytest.approx(1000 * 1152 / 819e9)
    few_bytes = dict(PEAKS, hbm_bytes_per_s=1e15)
    assert sh.kernel_call_need_s(model, serve, 1000, few_bytes) == pytest.approx(
        1000 * 2 * 64 * 1088 / 197e12)


def _ctx(model, serve, ops, mods, reqs):
    planes = {"/device:TPU:0": {tr.OPS_LINE: ops, tr.MODULES_LINE: mods}}
    return {"trace": tr.DeviceTrace(planes, 4.0, 6.0), "model": model, "serve": serve,
            "peaks": PEAKS, "window": {"requests": reqs}}


def test_the_two_rooflines_on_a_hand_made_trace(config):
    model, serve = config
    reqs = [{"ok": True, "t_first": 0.0, "t_last": 10.0, "n_tokens": 100, "prompt_len": 12750}] * 2
    held = 2 * 12800.0  # two rows, each at 12750 + 50 tokens mid-trace
    floor_s = sh.decode_step_bytes(model, serve, 2, held) / 819e9
    step_ns = int(floor_s * 4 * 1e9)  # every step takes four times its floor
    mods = [("jit__multi(5)", i * 10 * step_ns, serve["decode_steps"] * step_ns) for i in range(5)]
    call_ns = int(held * 1152 / 819e9 * 2 * 1e9)  # every kernel call twice its floor
    ops = [("mla_dense_decode_attention bf16[16,64,512]", i * 3 * call_ns, call_ns)
           for i in range(12)] + [("fusion f32[16]", 40 * call_ns, 10)]
    ctx = _ctx(model, serve, ops, mods, reqs)
    assert step_reader.read(ctx, module_pattern="^jit__multi") == pytest.approx(25.0, rel=1e-3)
    assert kernel_reader.read(ctx, pattern="^mla_dense_decode_attention") == pytest.approx(
        50.0, rel=1e-3)
    # nothing to read: no such program or op, no trace, another family
    assert step_reader.read(ctx, module_pattern="^jit__absent") is None
    assert kernel_reader.read(ctx, pattern="^mla_sparse_decode_attention") is None
    assert step_reader.read(dict(ctx, trace=None), module_pattern="^jit__multi") is None
    assert kernel_reader.read(dict(ctx, trace=None), pattern="^mla_dense") is None
    with_selector = dict(model, index_topk=2048, index_n_heads=64, index_head_dim=128)
    assert step_reader.read(dict(ctx, model=with_selector), module_pattern="^jit__multi") is None
    dense_gqa = {"hidden_size": 3584, "num_attention_heads": 28}
    assert step_reader.read(dict(ctx, model=dense_gqa), module_pattern="^jit__multi") is None
    assert kernel_reader.read(dict(ctx, model=dense_gqa), pattern="^mla_dense") is None
