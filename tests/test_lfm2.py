"""LFM2 (``model_type`` ``lfm2_moe``: the hybrid family of models/lfm2.py)
against the plain float32 reference (models/reference/lfm2_moe.py) on seeded
random weights at a small size on the CPU, in float32 under "highest" matmuls.

Tolerances.  LOGITS 2e-5 of the largest reference logit: both sides are
float32 and differ in summation order only (the taps over a run with its
predecessors from a page's entry against the reference's shifted copies of
the whole sequence; paged GQA with heads packed two a row against a whole
softmax a head; dispatch tables against a loop over experts); measured 3e-7.
A wrong tap order, a dropped or stale entry, a wrong rope half or gate moves
logits by 1e-2 or more at this size.  A prefix hit against a cold prefill of
the same prompt through the same program is held to EXACT equality.
"""

import asyncio
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models import lfm2
from dynamo_tpu.models.config import ModelConfig, register_config
from dynamo_tpu.models.family import RaggedBatch, family_of
from dynamo_tpu.models.reference import lfm2_moe as ref

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOGIT_TOL = 2e-5

# One period of the release's pattern and a second attention layer; head size
# 16 with 2 K/V heads: no packing (tests of the packed layout draw their own).
HF = {
    "model_type": "lfm2_moe", "vocab_size": 128, "hidden_size": 64, "intermediate_size": 96,
    "moe_intermediate_size": 32, "num_hidden_layers": 5,
    "layer_types": ["conv", "conv", "full_attention", "conv", "full_attention"],
    "num_dense_layers": 1, "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_experts": 8, "num_experts_per_tok": 2, "conv_L_cache": 3, "conv_bias": False,
    "norm_eps": 1e-5, "norm_topk_prob": True, "use_expert_bias": True,
    "routed_scaling_factor": 1, "rope_theta": 1000000, "max_position_embeddings": 1024,
}
PS, PP, NPAGES, S = 4, 12, 48, 4  # page size, pages a row, pages, rows
N = 40


@pytest.fixture(scope="module", autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def model():
    cfg = ModelConfig.from_hf_config(HF, name="lfm2-test").with_overrides(dtype="float32")
    params = lfm2.init_params(cfg, jax.random.PRNGKey(0))
    toks = np.random.RandomState(0).randint(0, HF["vocab_size"], size=N).astype(np.int32)
    return cfg, params, toks, np.asarray(ref.forward(params, HF, toks))


def rows_batch(rows, width, decode=False):
    """``rows``: (tokens, table, start, n) each, packed as the engine packs
    them (pipeline.py ``_build_ragged``; decode: one token a row)."""
    tok, pos = np.zeros(width, np.int32), np.zeros(width, np.int32)
    slots = np.full(width, -1, np.int32)
    tables, kv = np.zeros((S, PP), np.int32), np.zeros(S, np.int32)
    cu, at = np.zeros(S + 1, np.int32), 0
    for i, (toks, table, start, n) in enumerate(rows):
        p = np.arange(start, start + n)
        tok[at:at + n], pos[at:at + n] = toks[start:start + n], p
        slots[at:at + n] = table[p // PS] * PS + p % PS
        tables[i, :len(table)], kv[i] = table, start + n
        at += n
        cu[i + 1] = at
    cu[len(rows) + 1:] = at
    if decode:
        cu, num = np.arange(S + 1, dtype=np.int32), S
    else:
        num = len(rows)
    return RaggedBatch(tok, pos, slots, kv, tables, cu, np.asarray([num], np.int32))


def batch(toks, table, start, n, width, decode=False):
    return rows_batch([(toks, table, start, n)], width, decode)


def close(a, b):
    return float(np.max(np.abs(np.asarray(a) - b)) / np.max(np.abs(b)))


def new_cache(cfg):
    return lfm2.HybridCache.create(cfg, NPAGES, PS, dtype=jnp.float32)


def run_chunks(params, cfg, cache, toks, table, cuts, want):
    """Prefill ``toks[cuts[0]:cuts[-1]]`` in the chunks ``cuts`` bound; each
    chunk's last logits against the reference.  Returns the cache."""
    for a, b in zip(cuts, cuts[1:]):
        width = max(16, 1 << (b - a - 1).bit_length())
        lg, cache, _ = lfm2.forward_ragged(params, cfg, batch(toks, table, a, b - a, width), cache)
        assert close(lg[0], want[b - 1]) < LOGIT_TOL, (a, b)
    return cache


# ------------------------------------------------------- (a) chunks, decode
@pytest.mark.parametrize("cuts", [
    [0, 16, 29],            # chunk boundary on a page boundary, the second ends mid page
    [0, 6, 13, 14, 16, 29],  # boundaries in the middle of a page; chunks of 1 and 2 tokens
    [0, 1, 2, 3, 29],       # the convolution reaches across three one-token chunks
    [0, 29],
], ids=["page-boundary", "mid-page-1-2", "ones", "one-piece"])
def test_chunked_prefill_then_decode_matches_the_reference(model, cuts):
    """Prompt chunks through both page arrays, then decode: the fused
    program's path (``decode=True``) and a one-token row riding a mixed step,
    alternating, across three page boundaries."""
    cfg, params, toks, want = model
    table = np.arange(5, 5 + PP).astype(np.int32)
    cache = run_chunks(params, cfg, new_cache(cfg), toks, table, cuts, want)
    for t in range(29, N):
        decode = t % 2 == 0
        lg, cache, aux = lfm2.forward_ragged(
            params, cfg, batch(toks, table, t, 1, S if decode else 16, decode), cache,
            decode=decode)
        assert close(lg[0], want[t]) < LOGIT_TOL, (t, decode)
        assert [int(v) for v in aux] == [2 * 4, 4, int(aux[2]), 8 * 4]  # one real token, 4 MoE layers


def test_two_prompt_rows_and_a_decode_row_share_a_step(model):
    """Row A (29 cached) decodes one token in the step that prefills 8 tokens
    of row B at a past of 13 (mid page) and the first 5 of row C: every row's
    logits equal the reference's, whatever shares the step."""
    cfg, params, toks, want = model
    ta, tb = np.arange(2, 2 + PP).astype(np.int32), np.arange(39, 39 - PP, -1).astype(np.int32)
    tc = np.arange(14, 14 + PP).astype(np.int32)
    cache = run_chunks(params, cfg, new_cache(cfg), toks, ta, [0, 29], want)
    cache = run_chunks(params, cfg, cache, toks, tb, [0, 13], want)
    rb = rows_batch([(toks, ta, 29, 1), (toks, tb, 13, 8), (toks, tc, 0, 5)], 16)
    lg, cache, _ = lfm2.forward_ragged(params, cfg, rb, cache)
    assert close(lg[0], want[29]) < LOGIT_TOL and close(lg[1], want[20]) < LOGIT_TOL
    assert close(lg[2], want[4]) < LOGIT_TOL
    # and each goes on from what that step left
    rb = rows_batch([(toks, ta, 30, 1), (toks, tb, 21, 1), (toks, tc, 5, 1)], S, decode=True)
    lg, _, _ = lfm2.forward_ragged(params, cfg, rb, cache, decode=True)
    for i, t in enumerate((30, 21, 5)):
        assert close(lg[i], want[t]) < LOGIT_TOL, t


# ------------------------------------------------------------ (b) prefix hit
@pytest.mark.parametrize("hit", [8, 16, 24], ids=lambda h: f"hit-{h}")
def test_a_prefix_hit_equals_the_cold_prefill_exactly(model, hit):
    """Another request left ``hit`` tokens in sealed pages; this one shares
    those pages and starts at the block boundary from the last page's entry.
    Its logits are the cold prefill's to the bit (the same program over the
    same chunk: what the benchmark's probe compares), and the reference's."""
    cfg, params, toks, want = model
    other = np.arange(30, 30 + PP).astype(np.int32)
    mine = other.copy()
    mine[hit // PS:] = np.arange(4, 4 + PP - hit // PS)
    cache = run_chunks(params, cfg, new_cache(cfg), toks, other, [0, hit], want)
    sealed = jax.tree_util.tree_map(np.asarray, cache)
    lg_hit, cache, _ = lfm2.forward_ragged(params, cfg, batch(toks, mine, hit, 29 - hit, 32), cache)
    assert close(lg_hit[0], want[28]) < LOGIT_TOL
    # the sharer wrote none of the sealed pages, in either array
    for name in ("pages", "conv"):
        after = np.asarray(getattr(cache, name))[:, other[:hit // PS]]
        assert np.array_equal(after, getattr(sealed, name)[:, other[:hit // PS]]), name
    cold_table = np.arange(16, 16 + PP).astype(np.int32)
    cold = run_chunks(params, cfg, new_cache(cfg), toks, cold_table, [0, hit], want)
    lg_cold, _, _ = lfm2.forward_ragged(params, cfg, batch(toks, cold_table, hit, 29 - hit, 32), cold)
    assert np.array_equal(np.asarray(lg_hit[0]), np.asarray(lg_cold[0]))


# ------------------------------------------------------- (c) the fused chunk
def test_a_fused_chunk_of_four_steps_equals_four_single_steps(model):
    """The engine's fused decode program carries the cache (both arrays)
    through its steps on the device: the same scan here, teacher-forced,
    against four single steps from the same state, across a page boundary."""
    cfg, params, toks, want = model
    table = np.arange(5, 5 + PP).astype(np.int32)
    cache = run_chunks(params, cfg, new_cache(cfg), toks, table, [0, 30], want)

    def body(cache, t):
        rb = RaggedBatch(
            jnp.zeros((S,), jnp.int32).at[0].set(jnp.asarray(toks)[t]),
            jnp.zeros((S,), jnp.int32).at[0].set(t),
            jnp.full((S,), -1, jnp.int32).at[0].set(jnp.asarray(table)[t // PS] * PS + t % PS),
            jnp.zeros((S,), jnp.int32).at[0].set(t + 1),
            jnp.zeros((S, PP), jnp.int32).at[0].set(jnp.asarray(table)),
            jnp.arange(S + 1, dtype=jnp.int32), jnp.full((1,), S, jnp.int32))
        lg, cache, _ = lfm2.forward_ragged(params, cfg, rb, cache, decode=True)
        return cache, lg[0]

    fused_cache, fused = jax.jit(lambda c: jax.lax.scan(body, c, jnp.arange(30, 34)))(cache)
    for i, t in enumerate(range(30, 34)):
        lg, cache, _ = lfm2.forward_ragged(params, cfg, batch(toks, table, t, 1, S, True), cache,
                                           decode=True)
        assert close(fused[i], np.asarray(lg[0])) < LOGIT_TOL and close(fused[i], want[t]) < LOGIT_TOL
    assert close(fused_cache.conv, np.asarray(cache.conv)) < LOGIT_TOL


# ---------------------------------------------------- (e) who writes which page
def test_rows_write_their_own_pages_entries_and_no_others(model):
    """Stale data everywhere, a padding row of ``kv_len`` 0 and padding
    tokens: a step leaves every page it does not own as it was, in both
    arrays, and of its own pages' entries exactly those in which a run ended
    or a page filled."""
    cfg, params, toks, want = model
    stale = jax.tree_util.tree_map(
        lambda a: jnp.full(a.shape, 7.0, a.dtype), new_cache(cfg))
    table = np.arange(5, 5 + PP).astype(np.int32)
    lg, cache, _ = lfm2.forward_ragged(params, cfg, batch(toks, table, 0, 10, 16), stale)
    assert close(lg[0], want[9]) < LOGIT_TOL  # position 0 starts from zeros, not from stale data
    conv = np.asarray(cache.conv)
    written = {5, 6, 7}  # pages of positions 0-3, 4-7 (filled) and 8-9 (the run's end)
    for p in range(NPAGES):
        assert (p in written) == bool(np.any(conv[:, p] != 7.0)), p
    pages = np.asarray(cache.pages)
    assert np.all(pages[:, [p for p in range(NPAGES) if p not in written]] == 7.0)
    # a decode step with three padding rows (slot -1, kv_len 0) writes one entry
    lg, cache2, _ = lfm2.forward_ragged(params, cfg, batch(toks, table, 10, 1, S, True), cache,
                                        decode=True)
    assert close(lg[0], want[10]) < LOGIT_TOL
    changed = np.any(np.asarray(cache2.conv) != conv, axis=(0, 2, 3))
    assert list(np.nonzero(changed)[0]) == [7]


# ----------------------------------------------------------- (f) gate, QK-norm
def test_the_gate_by_hand_bias_in_the_choice_only_and_the_epsilon(model):
    cfg, _, _, _ = model
    x = jnp.eye(2, 64, dtype=jnp.float32)  # token t reads row t of the router
    logits = np.array([[2.0, 1.0, 0.0, -1.0, -2.0, 0.5, -0.5, 1.5],
                       [0.1, 0.2, 0.3, 0.4, 0.5, 0.6, 0.7, 0.8]], np.float32)
    bias = np.zeros(8, np.float32)
    bias[4] = 10.0  # the worst score of token 0 is chosen, and weighs what its score says
    lp = {"router": jnp.zeros((64, 8), jnp.float32).at[:2].set(logits),
          "router_bias": jnp.asarray(bias)}
    chosen, w = lfm2.latent.gate(x, lp, cfg)
    s = 1.0 / (1.0 + np.exp(-logits))
    for t, want_ids in enumerate(([4, 0], [4, 7])):
        assert sorted(np.asarray(chosen[t]).tolist()) == sorted(want_ids)
        sc = s[t, np.asarray(chosen[t])]
        np.testing.assert_allclose(np.asarray(w[t]), sc / (sc.sum() + 1e-6), rtol=1e-6)
    ref_chosen, ref_w = ref.gate({k: np.asarray(v) for k, v in lp.items()}, HF, x)
    assert np.array_equal(np.asarray(ref_chosen), np.asarray(chosen))
    np.testing.assert_allclose(np.asarray(ref_w), np.asarray(w), rtol=1e-6)
    # the epsilon is this family's: a latent configuration's program has no such op
    assert cfg.gate_norm_eps == 1e-6 and ModelConfig.__dataclass_fields__[
        "gate_norm_eps"].default == 0.0


def test_qk_norm_comes_before_the_rotation(model):
    """With q_norm / k_norm weights that differ across a head's two halves,
    norm-then-rotate and rotate-then-norm differ: the system agrees with the
    reference (norm first) and not with the other order."""
    cfg, params, toks, _ = model
    w = jnp.linspace(0.5, 1.5, 16, dtype=jnp.float32)
    p2 = dict(params, attn=dict(params["attn"], q_norm=jnp.tile(w, (2, 1)),
                                k_norm=jnp.tile(w[::-1], (2, 1))))
    want = np.asarray(ref.forward(p2, HF, toks[:12]))
    table = np.arange(5, 5 + PP).astype(np.int32)
    lg, _, _ = lfm2.forward_ragged(p2, cfg, batch(toks, table, 0, 12, 16), new_cache(cfg))
    assert close(lg[0], want[11]) < LOGIT_TOL
    plain = np.asarray(ref.forward(params, HF, toks[:12]))
    assert close(plain[11], want[11]) > 1e-3  # the weights matter at this size


@pytest.mark.parametrize("hd,kv,pack", [(64, 2, 2), (32, 4, 4), (16, 2, 1), (64, 3, 1)])
def test_heads_packed_into_one_lane_tile_change_nothing(hd, kv, pack):
    """Head size 64: two K/V heads share a 128-lane row of a page, queries
    carry zeros in the other half.  Logits equal the reference's as at any
    other head size; the page array has ``kv / pack`` rows of 128 lanes."""
    heads = kv * 2
    hf = dict(HF, hidden_size=heads * hd, num_attention_heads=heads, num_key_value_heads=kv,
              num_hidden_layers=2, layer_types=["full_attention", "conv"])
    cfg = ModelConfig.from_hf_config(hf, name="pack").with_overrides(dtype="float32")
    assert lfm2.head_pack(cfg) == pack and lfm2.attn_lanes(cfg) == hd * pack
    params = lfm2.init_params(cfg, jax.random.PRNGKey(3))
    toks = np.random.RandomState(1).randint(0, 128, size=14).astype(np.int32)
    want = np.asarray(ref.forward(params, hf, toks))
    cache = new_cache(cfg)
    assert cache.pages.shape == (1, NPAGES, PS, 2 * kv // pack, hd * pack)
    table = np.arange(3, 3 + PP).astype(np.int32)
    cache = run_chunks(params, cfg, cache, toks, table, [0, 9, 13], want)
    lg, _, _ = lfm2.forward_ragged(params, cfg, batch(toks, table, 13, 1, S, True), cache, decode=True)
    assert close(lg[0], want[13]) < LOGIT_TOL


# ------------------------------------------------- (g) the share test (s. 4)
def test_four_shares_of_eight_experts_add_up_to_the_uncut_layer():
    """model-configs section 4: over ALL ep_size shares of the experts the
    routed parts add up to the uncut reference's whole expert layer (there is
    no shared expert to count once).  Tolerance 1e-5 of the largest output."""
    full_hf = dict(HF, num_experts=32, num_experts_per_tok=4)
    full_cfg = ModelConfig.from_hf_config(full_hf, name="full").with_overrides(dtype="float32")
    full = lfm2.init_params(full_cfg, jax.random.PRNGKey(7))
    lp_full = {k: v[0] for k, v in full["moe"].items()}
    x = jax.random.normal(jax.random.PRNGKey(4), (48, 64), jnp.float32)
    want = np.asarray(ref.moe(lp_full, full_hf, x, list(range(32))))
    total = np.zeros_like(want)
    real = jnp.ones((48,), bool)
    for rank in range(4):
        hf = dict(full_hf, num_experts=8, num_experts_published=32, ep_size=4, ep_rank=rank)
        cfg = ModelConfig.from_hf_config(hf, name=f"share{rank}").with_overrides(dtype="float32")
        assert (cfg.num_experts, cfg.router_experts, cfg.ep_rank) == (8, 32, rank)
        lo = rank * 8
        lp = dict(lp_full, **{k: lp_full[k][lo:lo + 8] for k in ("moe_gate", "moe_up", "moe_down")})
        part, load = lfm2.moe_block(x, lp, cfg, real, None)
        assert close(part, np.asarray(ref.moe(lp, hf, x, range(lo, lo + 8)))) < 1e-5
        assert int(jnp.sum(load)) <= 48 * 4
        total += np.asarray(part)
    assert close(total, want) < 1e-5


# ------------------------------------------------------------ from_hf_config
def test_from_hf_config_reads_the_catalog_row_and_the_benchmarks_file():
    with open(os.path.join(ROOT, "chipbench/configs/lfm2-8b-a1b.json")) as f:
        body = json.load(f)
    published = None
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            published = next(r for r in map(json.loads, f) if r["name"] == "LFM2-8B-A1B")["config"]
        for key, value in published.items():  # uncut: every published key, as published
            assert body[key] == value, key
    assert body["reduced"] == [] and body["chips"] == 1
    cfg = ModelConfig.from_hf_config(body, name="whole")
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (
        24, 2048, 32, 8, 64)
    assert (cfg.num_experts, cfg.router_experts, cfg.num_experts_per_token, cfg.ep_size) == (
        32, 32, 4, 1)
    assert (cfg.intermediate_size, cfg.moe_intermediate_size, cfg.vocab_size) == (7168, 1792, 65536)
    assert cfg.layer_types.count("conv") == 18 and cfg.first_k_dense_replace == 2
    assert [i for i, t in enumerate(cfg.layer_types) if t == "full_attention"] == [
        2, 6, 10, 14, 18, 21]
    assert cfg.tie_word_embeddings and cfg.rms_norm_eps == 1e-5 and cfg.conv_L_cache == 3
    assert family_of(cfg).name == "hybrid" and lfm2.layer_counts(cfg) == (18, 6, 2, 22)
    # The issue's count: every parameter of the release, embedding tied.
    shapes = lfm2.leaf_shapes(cfg)
    assert sum(int(np.prod(s)) for g in shapes.values() for s in g.values()) == 8_339_930_560
    cache = jax.eval_shape(lambda: lfm2.HybridCache.create(cfg, 16384, 16, dtype=jnp.int8))
    assert cache.pages.shape == (6, 16384, 16, 8, 128) and cache.conv.shape == (18, 16384, 2, 2048)
    assert cache.conv.dtype == jnp.bfloat16
    assert cache.ssm is None and cache.tail is None  # no Mamba-2 layers: no slot pools
    per_page = sum(a.size * a.dtype.itemsize for a in cache if a is not None) // 16384
    assert per_page == 16 * 6144 + 147456 == 245760


@pytest.mark.parametrize("bad,match", [
    (dict(model_type="gemma9"), "model_type 'gemma9' is not supported"),
    (dict(model_type="lfm2"), "model_type 'lfm2' is not supported"),
    (dict(conv_bias=True), "conv_bias"),
    (dict(layer_types=["conv"] * 4), "layer_types"),
    (dict(layer_types=["conv", "conv", "sliding", "conv", "conv"]), "layer_types"),
    (dict(num_experts=8, ep_size=3, num_experts_published=32), "router's width"),
])
def test_what_the_configuration_cannot_mean_is_refused_by_name(bad, match):
    with pytest.raises(ValueError, match=match):
        ModelConfig.from_hf_config(dict(HF, **bad), name="bad")


@pytest.mark.parametrize("model_type", [None, "llama", "qwen2", "mistral", "mixtral"])
def test_the_llama_branch_keeps_its_names_and_an_absent_key(model_type):
    hf = {"vocab_size": 64, "hidden_size": 32, "num_hidden_layers": 1, "num_attention_heads": 2,
          "intermediate_size": 48}
    if model_type:
        hf["model_type"] = model_type
    cfg = ModelConfig.from_hf_config(hf, name="l")
    assert cfg.model_type == "llama" and family_of(cfg).name == "llama"
    assert cfg.qkv_bias == (model_type == "qwen2")


# ------------------------------------------------------------- the engine
ENGINE = dict(block_size=4, num_blocks=64, max_batch=4, max_model_len=64, prefill_chunk=16,
              dtype="float32", decode_steps=2)


@pytest.fixture(scope="module")
def engine():
    from dynamo_tpu.engine import EngineConfig
    from dynamo_tpu.engine.engine import TpuEngine

    register_config(ModelConfig.from_hf_config(HF, name="lfm2-engine"))
    return TpuEngine(EngineConfig(model="lfm2-engine", **ENGINE))


@pytest.mark.parametrize("flag,kw", [
    ("--host-cache-mb", dict(host_cache_bytes=1 << 20)),
    ("--spec-decode", dict(spec_decode={"enable": True})),
    ("--tp", dict(tp=2)),
])
def test_unsupported_engine_options_are_refused_by_flag(flag, kw):
    from dynamo_tpu.engine import EngineConfig
    from dynamo_tpu.engine.engine import TpuEngine

    register_config(ModelConfig.from_hf_config(HF, name="lfm2-engine"))
    with pytest.raises(ValueError, match=f"lfm2_moe.*{flag}"):
        TpuEngine(EngineConfig(model="lfm2-engine", **ENGINE, **kw))


def test_the_cache_is_two_page_arrays_under_one_table(engine):
    ps = ENGINE["block_size"]
    assert len(jax.tree_util.tree_leaves(engine.cache)) == 2
    # float32 here: K/V 2 layers x ps tokens x 2*2*16 values, the state 3 layers x 2 x 64
    assert engine.block_nbytes() == 2 * ps * 64 * 4 + 3 * 2 * 64 * 4
    assert engine.device_summary()["cache_kinds"] == "kv:256,conv_page:512"
    assert "inject" not in engine.compile_counts()
    assert engine.kv.beside.whole_blocks and not engine.kv.beside.stride  # resumed at a block

    async def main():
        with pytest.raises(ValueError, match="not K-plus-V pages"):
            await engine.export_prompt_blocks([1] * 8)

    asyncio.run(main())


def test_int8_kv_pages_keep_the_state_pages_in_the_activation_dtype():
    from dynamo_tpu.engine import EngineConfig
    from dynamo_tpu.engine.engine import TpuEngine

    register_config(ModelConfig.from_hf_config(HF, name="lfm2-engine"))
    eng = TpuEngine(EngineConfig(model="lfm2-engine", **ENGINE, cache_dtype="int8", kv_scale="auto"))
    assert eng.cache.pages.dtype == jnp.int8 and eng.cache.conv.dtype == jnp.float32
    assert np.asarray(eng.kv_scale).shape == (2,)  # one calibrated scale an ATTENTION layer


def _requests(engine):
    from dynamo_tpu.llm.protocols import PreprocessedRequest, SamplingOptions, StopConditions
    from dynamo_tpu.runtime.engine import Context, collect

    async def gen(tokens, n):
        req = PreprocessedRequest(
            token_ids=list(tokens), stop_conditions=StopConditions(max_tokens=n, ignore_eos=True),
            sampling_options=SamplingOptions()).to_dict()
        out = await collect(await engine.generate(Context(req)))
        return [t for item in out for t in item["token_ids"]]

    def check(prompt, got):
        """Teacher-forced: each token is the reference's argmax at its
        position (ONE causal pass over the prompt and the answer)."""
        logits = np.asarray(ref.forward(engine.params, HF, np.asarray(list(prompt) + got, np.int32)))
        for i, tok in enumerate(got):
            assert int(np.argmax(logits[len(prompt) - 1 + i])) == tok, len(prompt) + i

    return gen, check


def test_the_engine_serves_it_with_prefix_reuse_and_counts(engine):
    """Through TpuEngine's normal path (scheduler, block manager, prefix
    cache, unified step, fused decode chunks of 2): greedy tokens equal the
    reference's argmax; a second request reuses the first one's sealed pages
    and starts its convolutions from a page's entry; a prompt that is a WHOLE
    number of cached blocks gives its last block back and still agrees."""
    from dynamo_tpu.llm.metrics import sparse_model_metrics

    gen, check = _requests(engine)

    async def main():
        sparse_model_metrics.reset()
        rs = np.random.RandomState(5)
        doc = rs.randint(16, 128, 24).tolist()
        first = doc + rs.randint(16, 128, 5).tolist()
        check(first, await gen(first, 4))
        assert sparse_model_metrics.conv_row_starts == {"zero": 1, "tail": 1}  # 16 + 13 tokens
        hits0 = engine.kv.hit_rate
        prompt = doc + rs.randint(16, 128, 7).tolist()
        check(prompt, await gen(prompt, 6))
        assert engine.kv.hit_rate > hits0
        assert sparse_model_metrics.conv_row_starts == {"zero": 1, "tail": 2}
        # 24 tokens = 6 whole cached blocks: 20 are reused, the last block is computed again
        check(doc, await gen(doc, 5))
        assert sparse_model_metrics.conv_row_starts == {"zero": 1, "tail": 3}
        check(doc[:8], await gen(doc[:8], 9))
        assert sparse_model_metrics.conv_tokens > 0
        assert sparse_model_metrics.moe_local_pairs == 2 * sparse_model_metrics.moe_routed_tokens
        assert 0 < sparse_model_metrics.moe_experts_read <= sparse_model_metrics.moe_experts_held
        text = sparse_model_metrics.render()
        for name in ('conv_row_starts_total{state="zero"}', 'conv_row_starts_total{state="tail"}',
                     "conv_tokens_total", "moe_local_pairs_total", "moe_experts_read_total"):
            assert f"dynamo_tpu_{name}" in text
        assert "dsa_" not in text and "mla_" not in text
        counts = engine.dispatch_summary()["model"]
        assert counts["conv_row_starts"] == sparse_model_metrics.conv_row_starts

    asyncio.run(main())


def test_preemption_and_resume_go_on_from_the_pages(engine):
    """(d) A running sequence is preempted (its blocks freed, its tokens
    folded into the prompt) and resumes: its sealed blocks are a prefix hit
    like any other, and the tokens after the resume are those of an
    uninterrupted run."""
    gen, check = _requests(engine)

    async def main():
        rs = np.random.RandomState(11)
        prompt = rs.randint(16, 128, 21).tolist()
        whole = await gen(prompt, 12)
        check(prompt, whole)
        other = rs.randint(16, 128, 21).tolist()
        task = asyncio.ensure_future(gen(other, 14))
        while not any(len(s.output) >= 5 for s in engine.scheduler.running):
            await asyncio.sleep(0.005)
        async with engine._device_lock:
            seq = next(s for s in engine.scheduler.running if len(s.output) >= 5)
            engine.scheduler._preempt(seq)
        got = await task
        assert engine.scheduler.preempted >= 1 and len(got) == 14
        check(other, got)
        await engine.close()

    asyncio.run(main())


def test_conv_account_arithmetic(engine):
    from dynamo_tpu.llm.metrics import sparse_model_metrics

    sparse_model_metrics.reset()
    # prompt rows at 0 and at 20, a decode row riding (one token at 37), an idle row
    engine._count_dispatch("unified", [0, 20, 37, -1], [4, 3, 1, 1])
    engine._count_dispatch("unified", [0], [1])  # a one-token prompt starts from zeros
    engine._count_dispatch("decode", [9, -1], [2, 0])
    assert sparse_model_metrics.conv_row_starts == {"zero": 2, "tail": 1}
    assert sparse_model_metrics.conv_tokens == 4 + 3 + 1 + 1 + 2
    sparse_model_metrics.reset()
    assert sparse_model_metrics.render() == ""


def test_quantized_draw_and_its_float_tree():
    cfg = ModelConfig.from_hf_config(HF, name="q")
    q = lfm2.init_params_quantized(cfg, jax.random.PRNGKey(1))
    assert q["moe"]["moe_gate"].dtype == jnp.int8 and q["conv"]["taps"].dtype == jnp.bfloat16
    assert q["moe"]["moe_gate_scale"].shape == (4, 8, 32) and q["embed_scale"].shape == (128,)
    assert q["conv"]["in_proj_scale"].shape == (3, 192) and "lm_head" not in q
    assert q["moe"]["router"].dtype == jnp.bfloat16 and q["attn"]["wqkv"].dtype == jnp.int8
    f = lfm2.dequantize_params(q)
    assert f["attn"]["wo"].dtype == jnp.float32 and lfm2.quantize_params(q) is q
    again = lfm2.quantize_params(lfm2.init_params(cfg, jax.random.PRNGKey(1)))
    assert again["conv"]["out_proj"].dtype == jnp.int8 and again["layers"]["op_norm"].dtype != jnp.int8


def test_the_benchmarks_copy_of_the_reference_is_the_reference():
    with open(os.path.join(ROOT, "dynamo_tpu/models/reference/lfm2_moe.py")) as a, open(
            os.path.join(ROOT, "chipbench/reference/lfm2_moe.py")) as b:
        assert a.read() == b.read()
