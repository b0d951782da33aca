"""K-EXAONE-236B-A23B (``model_type`` ``exaone_moe``: the hybrid family of
models/lfm2.py with the mixer kind ``sliding_attention``) against the plain
float32 reference (models/reference/exaone_moe.py: the window as a mask over
the full score matrix, no cache) on seeded random weights at a small size on
the CPU, in float32 under "highest" matmuls.

Tolerances.  LOGITS 2e-5 of the largest reference logit: both sides are
float32 and differ in summation order only (paged GQA over a window's pages
against a masked whole softmax a head; dispatch tables against a loop over
experts); measured 3e-7.  The CONTROLS show what the limit catches at this
size: a window one position short or long, no window, a rotated full layer,
bfloat16 activations all move the logits by 1e-3 or more.  A chunk resumed
behind retained window pages against the same chunk of the cold run, through
the same program, is held to EXACT equality.
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
from dynamo_tpu.models.reference import exaone_moe as ref

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOGIT_TOL = 2e-5
W = 8  # the window, in small

# Two periods in small: L L L G L L L G, layer 0 dense, one shared expert.
HF = {
    "model_type": "exaone_moe", "vocab_size": 128, "hidden_size": 64, "intermediate_size": 96,
    "moe_intermediate_size": 32, "num_hidden_layers": 8,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"]
    + ["sliding_attention"] * 3 + ["full_attention"],
    "mlp_layer_types": ["dense"] + ["sparse"] * 7, "first_k_dense_replace": 1,
    "sliding_window": W, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "num_experts": 8, "num_experts_per_tok": 3, "num_shared_experts": 1, "n_group": 1,
    "topk_group": 1, "scoring_func": "sigmoid", "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "rope_parameters": {"rope_theta": 10000.0, "rope_type": "default"},
    "rms_norm_eps": 1e-5, "max_position_embeddings": 1024, "tie_word_embeddings": False,
    "num_nextn_predict_layers": 0,
}
PS, PP, NPAGES, S = 4, 16, 64, 4  # page size, pages a row, pages, rows
WP, RING = 8, 16  # a row's window table, and the ring of window pages the tests give a row
N = 48


@pytest.fixture(scope="module", autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def draw(cfg, seed):
    """Seeded weights with the attention inputs four times the draw's
    N(0, 0.02): at a hidden size of 64 that spreads the softmax, so that WHICH
    positions a query attends to moves the logits (the controls below)."""
    params = lfm2.init_params(cfg, jax.random.PRNGKey(seed))
    for g in ("attn", "wattn"):
        params[g]["wqkv"] = params[g]["wqkv"] * 4
    return params


@pytest.fixture(scope="module")
def model():
    cfg = ModelConfig.from_hf_config(HF, name="exaone-test").with_overrides(dtype="float32")
    params = draw(cfg, 0)
    toks = np.random.RandomState(0).randint(0, HF["vocab_size"], size=N).astype(np.int32)
    return cfg, params, toks, np.asarray(ref.forward(params, HF, toks))


def table(i):
    return np.arange(i * PP, (i + 1) * PP).astype(np.int32)


def window_page(i, block):
    """Row i's window page of logical block ``block``: a ring, so a page is
    written again RING blocks later, as a released page is by its next owner."""
    return i * RING + block % RING


def rows_batch(rows, width, decode=False, window=W):
    """``rows``: (tokens, table, start, n) each, packed as the engine packs
    them (pipeline.py ``_build_ragged``; decode: one token a row): the full
    layers' table and slots, and the window layers' (the table begins at the
    block the first query's window reaches)."""
    tok, pos = np.zeros(width, np.int32), np.zeros(width, np.int32)
    slot_map, wslot = np.full(width, -1, np.int32), np.full(width, -1, np.int32)
    tables, kv = np.zeros((S, PP), np.int32), np.zeros(S, np.int32)
    wtab, wlen = np.zeros((S, WP), np.int32), np.zeros(S, np.int32)
    cu, at = np.zeros(S + 1, np.int32), 0
    for i, (toks, tab, start, n) in enumerate(rows):
        p = np.arange(start, start + n)
        tok[at:at + n], pos[at:at + n] = toks[start:start + n], p
        slot_map[at:at + n] = tab[p // PS] * PS + p % PS
        tables[i, :len(tab)], kv[i] = tab, start + n
        base = max(0, start + 1 - window) // PS
        blocks = np.arange(base, (start + n - 1) // PS + 1)
        assert len(blocks) <= WP
        wtab[i, :len(blocks)] = [window_page(i, b) for b in blocks]
        wlen[i] = start + n - base * PS
        wslot[at:at + n] = np.asarray([window_page(i, b) for b in p // PS]) * PS + p % PS
        at += n
        cu[i + 1] = at
    cu[len(rows) + 1:] = at
    if decode:
        cu, num = np.arange(S + 1, dtype=np.int32), S
    else:
        num = len(rows)
    return RaggedBatch(tok, pos, slot_map, kv, tables, cu, np.asarray([num], np.int32),
                       window_indices=wtab, window_lens=wlen, window_slots=wslot)


def width_of(n):
    return max(16, 1 << (n - 1).bit_length())


def close(a, b):
    return float(np.max(np.abs(np.asarray(a) - b)) / np.max(np.abs(b)))


def new_cache(cfg):
    return lfm2.HybridCache.create(cfg, NPAGES, PS, dtype=jnp.float32, window_pages=S * RING)


_STEPS = {}


def forward(params, cfg, rb, cache, **kw):
    key = (id(params), id(cfg), tuple(sorted(kw.items())))
    if key not in _STEPS:
        _STEPS[key] = jax.jit(lambda rb, ca: lfm2.forward_ragged(params, cfg, rb, ca, **kw))
    return _STEPS[key](rb, cache)


def run_chunks(params, cfg, cache, toks, tab, cuts, want, **kw):
    for a, b in zip(cuts, cuts[1:]):
        rb = rows_batch([(toks, tab, a, b - a)], width_of(b - a))
        lg, cache, _ = forward(params, cfg, rb, cache, **kw)
        assert close(lg[0], want[b - 1]) < LOGIT_TOL, (a, b)
    return cache


# --------------------------------------- (a) contexts around the window's edge
@pytest.mark.parametrize("cuts", [
    [0, W - 1],                 # under the window: every position attended
    [0, W],                     # at it
    [0, W + 1],                 # over it by one: position 0 falls out
    [0, 5, 6, 11, 29],          # chunk boundaries inside a window
    [0, 16, 29],
    [0, 29],
], ids=["under", "at", "over", "four-steps", "two-steps", "one-piece"])
@pytest.mark.parametrize("kernels", [False, True], ids=["xla", "pallas"])
def test_chunked_prefill_then_decode_matches_the_reference(model, cuts, kernels):
    """Prompt chunks through both pools, then decode (the fused program's form
    and a one-token row riding a ragged step, alternating), in the XLA path and
    in both Pallas kernels under the interpreter."""
    cfg, params, toks, want = model
    kw = dict(attn_impl="xla", decode_kernel="pallas_fused", prefill_kernel="pallas") if kernels else {}
    cache = run_chunks(params, cfg, new_cache(cfg), toks, table(0), cuts, want, **kw)
    for t in range(cuts[-1], min(N, cuts[-1] + 2 * W + 3)):
        decode = t % 2 == 0
        rb = rows_batch([(toks, table(0), t, 1)], S if decode else 16, decode=decode)
        lg, cache, _ = forward(params, cfg, rb, cache, decode=decode, **kw)
        assert close(lg[0], want[t]) < LOGIT_TOL, t


def test_prompt_rows_and_decode_rows_share_a_step(model):
    """Rows of unlike length in one token axis, each under its own two tables."""
    cfg, params, toks, want = model
    rs = np.random.RandomState(3)
    others = [rs.randint(0, 128, size=N).astype(np.int32) for _ in range(3)]
    wants = [np.asarray(ref.forward(params, HF, o)) for o in others]
    cache = new_cache(cfg)
    past = [(others[0], table(1), 0, 11), (others[1], table(2), 0, 20), (others[2], table(3), 0, 7)]
    # Row i of the step must be ring i: place the three in rows 1..3.
    pad = (toks, table(0), 0, 1)
    _, cache, _ = forward(params, cfg, rows_batch([pad] + past, 64), cache)
    rows = [(toks, table(0), 0, 19), (others[0], table(1), 11, 13),
            (others[1], table(2), 20, 1), (others[2], table(3), 7, 1)]
    lg, cache, _ = forward(params, cfg, rows_batch(rows, 64), cache)
    for i, w in enumerate((want[18], wants[0][23], wants[1][20], wants[2][7])):
        assert close(lg[i], w) < LOGIT_TOL, i
    rows = [(toks, table(0), 19, 5), (others[0], table(1), 24, 1),
            (others[1], table(2), 21, 3), (others[2], table(3), 8, 2)]
    lg, cache, _ = forward(params, cfg, rows_batch(rows, 16), cache)
    for i, w in enumerate((want[23], wants[0][24], wants[1][23], wants[2][9])):
        assert close(lg[i], w) < LOGIT_TOL, i


@pytest.mark.parametrize("control", ["short", "long", "none", "rotated-full", "bfloat16"])
def test_the_controls_move_the_logits_past_the_limit(model, control):
    """The limit is tight enough: a window off by one either way, no window, a
    full layer that rotates and bfloat16 activations each fail it."""
    cfg, params, toks, want = model
    if control in ("short", "long", "none"):
        w = {"short": W - 1, "long": W + 1, "none": 0}[control]
        got = np.asarray(ref.forward(params, HF, toks, window=w))
        assert close(got[-1], want[-1]) > 50 * LOGIT_TOL
        # ... and the SYSTEM one position short fails against the reference too.
        if control == "short":
            short = cfg.with_overrides(sliding_window=W - 1)
            rb = rows_batch([(toks, table(0), 0, 29)], 32, window=W - 1)
            lg, _, _ = forward(params, short, rb, new_cache(cfg))
            assert close(lg[0], want[28]) > 50 * LOGIT_TOL
        return
    if control == "rotated-full":
        sys_cfg = cfg.with_overrides(rope_full_attention=True)
    else:
        sys_cfg = cfg.with_overrides(dtype="bfloat16")
        params = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.bfloat16) if a.dtype == jnp.float32 else a, params)
    cache = new_cache(cfg)
    if control == "bfloat16":
        cache = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), cache)
    lg, _, _ = forward(params, sys_cfg, rows_batch([(toks, table(0), 0, 29)], 32), cache)
    assert close(np.asarray(lg[0], np.float32), want[28]) > 50 * LOGIT_TOL


def test_a_fused_chunk_of_four_steps_equals_four_single_steps(model):
    """The decode program's scan over ``decode_steps`` under ONE window table
    (the one the chunk is enqueued with, begun at the first step's window)
    against single steps each under its own."""
    cfg, params, toks, want = model
    cache = run_chunks(params, cfg, new_cache(cfg), toks, table(0), [0, 30], want)
    t0 = 30
    base = max(0, t0 + 1 - W) // PS
    wtab = np.zeros((S, WP), np.int32)
    blocks = np.arange(base, (t0 + 3) // PS + 1)
    wtab[0, :len(blocks)] = [window_page(0, b) for b in blocks]

    def body(ca, t):
        row0 = jnp.arange(S) == 0
        rb = RaggedBatch(
            token_ids=jnp.where(row0, jnp.asarray(toks)[t], 0), positions=jnp.where(row0, t, 0),
            slot_mapping=jnp.where(row0, jnp.asarray(table(0))[t // PS] * PS + t % PS, -1),
            kv_lens=jnp.where(row0, t + 1, 0), page_indices=jnp.zeros((S, PP), jnp.int32).at[0].set(table(0)),
            cu_q_lens=jnp.arange(S + 1, dtype=jnp.int32), num_seqs=jnp.asarray([S], jnp.int32),
            window_indices=jnp.asarray(wtab), window_lens=jnp.where(row0, t + 1 - base * PS, 0),
            window_slots=jnp.where(row0, jnp.asarray(wtab)[0, t // PS - base] * PS + t % PS, -1))
        lg, ca, _ = lfm2.forward_ragged(params, cfg, rb, ca, decode=True)
        return ca, lg[0]

    _, lgs = jax.jit(lambda ca: jax.lax.scan(body, ca, jnp.arange(t0, t0 + 4)))(cache)
    for i in range(4):
        assert close(lgs[i], want[t0 + i]) < LOGIT_TOL, i


def test_the_shares_of_the_experts_add_up_to_the_uncut_layer():
    """model-configs section 4: over all ep_size shares the routed parts, and
    the shared expert counted ONCE, add up to the uncut reference's whole
    feed-forward.  Tolerance 1e-5 of the largest output."""
    full_hf = dict(HF, num_experts=16, num_experts_per_tok=5)
    full_cfg = ModelConfig.from_hf_config(full_hf, name="full").with_overrides(dtype="float32")
    full = lfm2.init_params(full_cfg, jax.random.PRNGKey(7))
    lp_full = {k: v[0] for g in ("moe", "shared") for k, v in full[g].items()}
    x = jax.random.normal(jax.random.PRNGKey(4), (48, 64), jnp.float32)
    want = np.asarray(ref.moe(lp_full, full_hf, x, list(range(16))))
    total = np.zeros_like(want)
    real = jnp.ones((48,), bool)
    for rank in range(8):
        hf = dict(full_hf, num_experts=2, num_experts_published=16, ep_size=8, ep_rank=rank)
        cfg = ModelConfig.from_hf_config(hf, name=f"share{rank}").with_overrides(dtype="float32")
        assert (cfg.num_experts, cfg.router_experts, cfg.ep_rank) == (2, 16, rank)
        lo = rank * 2
        lp = dict(lp_full, **{k: lp_full[k][lo:lo + 2] for k in ("moe_gate", "moe_up", "moe_down")})
        part, load = lfm2.moe_block(x, lp, cfg, real, None)
        assert close(part, np.asarray(ref.moe(lp, hf, x, range(lo, lo + 2), shared=False))) < 1e-5
        total += np.asarray(part)
    from dynamo_tpu.models.llama import mlp
    total += np.asarray(mlp(x, {k: lp_full[k] for k in ("w_gate", "w_up", "w_down")}))
    assert close(total, want) < 1e-5


def test_the_window_kernels_begin_each_q_block_at_its_own_window():
    """Both kernels at block sizes that make a row several compute blocks (a
    q-block's walk begins past block 0) against the XLA path, all under window
    tables that begin mid-context."""
    from dynamo_tpu.ops.decode_attention import fused_decode_attention
    from dynamo_tpu.ops.prefill_attention import fused_prefill_attention
    from dynamo_tpu.ops.ragged_attention import ragged_attention

    rs = np.random.RandomState(2)
    H, KV, D, ps, P, win = 4, 2, 128, 4, 40, 9
    pages = jnp.asarray(rs.randn(P, ps, 2 * KV, D), jnp.float32)
    # rows: 37 queries behind 11 positions of window table, 1 query, 20 queries from position 0
    q_lens, lens = [37, 1, 20], [11 + 37, 10, 20]
    T = 64
    cu = np.zeros(5, np.int32)
    cu[1:4] = np.cumsum(q_lens)
    cu[4] = cu[3]
    table = rs.permutation(P)[:36].reshape(3, 12).astype(np.int32)
    table = np.concatenate([table, np.zeros((1, 12), np.int32)])
    kv_lens = np.asarray(lens + [0], np.int32)
    q = jnp.asarray(rs.randn(T, H, D), jnp.float32)
    num = np.asarray([3], np.int32)
    want = ragged_attention(q, pages, kv_lens, table, cu, num, sm_scale=0.3, window=win)
    got = fused_prefill_attention(q, pages, kv_lens, table, cu, num, sm_scale=0.3, window=win,
                                  q_block=8, pages_per_block=2)
    assert np.allclose(got[:cu[3]], want[:cu[3]], atol=1e-5, rtol=1e-5)
    full = ragged_attention(q, pages, kv_lens, table, cu, num, sm_scale=0.3)
    assert not np.allclose(full[:cu[3]], want[:cu[3]], atol=1e-3)
    # decode: one query a row at the context's end
    qd = jnp.asarray(rs.randn(4, H, D), jnp.float32)
    dl = np.asarray([48, 10, 3, 0], np.int32)
    want = ragged_attention(qd, pages, dl, table, np.arange(5, dtype=np.int32),
                            np.asarray([4], np.int32), sm_scale=0.3, decode=True,
                            decode_kernel="xla", window=win)
    got = fused_decode_attention(qd, pages, dl, table, np.asarray([4], np.int32), sm_scale=0.3,
                                 window=win, pages_per_block=2)
    assert np.allclose(got, want, atol=1e-5, rtol=1e-5)


# ------------------------------------------------------------ from_hf_config
def test_from_hf_config_reads_the_catalog_row_and_the_benchmarks_file():
    with open(os.path.join(ROOT, "chipbench/configs/k-exaone-236b-a23b-8l-ep8.json")) as f:
        body = json.load(f)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "K-EXAONE-236B-A23B")
        assert body["source"] == row["source_url"]
        for key, value in row["config"].items():  # every published key, as published or reduced
            if key in body["reduced"]:
                continue
            assert body[key] == value, key
        whole = ModelConfig.from_hf_config(row["config"], name="whole")
        assert (whole.num_layers, whole.num_experts, whole.router_experts, whole.ep_size) == (
            48, 128, 128, 1)
        assert lfm2.window_layers(whole) == 36 and lfm2.layer_counts(whole) == (0, 12, 1, 47)
        shapes = lfm2.leaf_shapes(whole)
        # The release's "236B" (without its draft head).
        total = sum(int(np.prod(s)) for g in shapes.values() for s in g.values())
        assert 2.30e11 < total < 2.40e11, total
    assert body["reduced"] == ["num_hidden_layers", "layer_types", "mlp_layer_types", "num_experts",
                               "ep_size", "vocab_size", "num_nextn_predict_layers"]
    cfg = ModelConfig.from_hf_config(body, name="cut")
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (
        8, 6144, 64, 8, 128)
    assert (cfg.num_experts, cfg.router_experts, cfg.num_experts_per_token, cfg.ep_size,
            cfg.ep_rank) == (16, 128, 8, 8, 0)
    assert (cfg.intermediate_size, cfg.moe_intermediate_size, cfg.shared_intermediate_size,
            cfg.vocab_size, cfg.first_k_dense_replace) == (18432, 2048, 2048, 19200, 1)
    assert cfg.layer_types == (("sliding_attention",) * 3 + ("full_attention",)) * 2
    assert (cfg.sliding_window, cfg.rope_theta, cfg.routed_scaling_factor) == (128, 1e6, 2.5)
    assert cfg.post_norm and cfg.qk_norm and not cfg.rope_full_attention
    assert not cfg.tie_word_embeddings and cfg.norm_topk_prob and cfg.gate_scoring == "sigmoid"
    fam = family_of(cfg)
    assert fam.name == "hybrid" and fam.beside is not None
    assert lfm2.layer_counts(cfg) == (0, 2, 1, 7) and lfm2.window_layers(cfg) == 6
    shapes = lfm2.leaf_shapes(cfg)
    assert shapes["wattn"]["wqkv"] == (6, 6144, 10240) and shapes["attn"]["wo"] == (2, 8192, 6144)
    assert shapes["moe"]["moe_gate"] == (7, 16, 6144, 2048) and shapes["moe"]["router"] == (7, 6144, 128)
    assert shapes["dense"]["w_gate"] == (1, 6144, 18432) and shapes["shared"]["w_up"] == (7, 6144, 2048)
    total = sum(int(np.prod(s)) for g in shapes.values() for s in g.values())
    assert 5.9e9 < total < 6.05e9, total  # ISSUE 47's 5.98 G parameters
    from dynamo_tpu.engine import EngineConfig
    serve = body["serve"]
    ecfg = EngineConfig(model="cut", **{k: serve[k] for k in (
        "block_size", "num_blocks", "max_model_len", "max_batch", "prefill_chunk", "decode_steps")})
    kind = fam.beside(cfg, ecfg)
    assert type(kind).__name__ == "WindowPages" and kind.stride == ecfg.prefill_chunk
    assert (kind.cache_kw["window_pages"], kind.tokens, kind.row_pages) == (8192, 128, 41)
    cache = jax.eval_shape(lambda: lfm2.HybridCache.create(cfg, 32768, 16, dtype=jnp.int8,
                                                           window_pages=8192))
    assert cache.pages.shape == (2, 32768, 16, 16, 128) and cache.window.shape == (6, 8192, 16, 16, 128)


@pytest.mark.parametrize("bad,match", [
    (dict(model_type="exaone5"), "model_type 'exaone5' is not supported"),
    (dict(layer_types=["sliding_attention"] * 7 + ["conv"]), "layer_types"),
    (dict(sliding_window=0), "sliding_window"),
    (dict(mlp_layer_types=["sparse"] * 8), "mlp_layer_types"),
    (dict(n_group=2), "n_group"),
    (dict(scoring_func="softmax"), "scoring_func"),
    (dict(num_experts=4, num_experts_published=16, ep_size=2), "router's width"),
    (dict(rope_parameters={"rope_type": "yarn"}), "rope_type"),
])
def test_what_the_configuration_cannot_mean_is_refused_by_name(bad, match):
    with pytest.raises(ValueError, match=match):
        ModelConfig.from_hf_config(dict(HF, **bad), name="bad")


# ------------------------------------------------------------------- engine
ENGINE = dict(block_size=4, num_blocks=64, max_batch=4, max_model_len=64, prefill_chunk=16,
              dtype="float32", decode_steps=2)


def make_engine(**kw):
    from dynamo_tpu.engine import EngineConfig
    from dynamo_tpu.engine.engine import TpuEngine

    cfg = register_config(ModelConfig.from_hf_config(HF, name="exaone-engine"))
    engine = TpuEngine(EngineConfig(model="exaone-engine", **dict(ENGINE, **kw)),
                       params=draw(cfg.with_overrides(dtype="float32"), 2))
    # Warm-up compiles the device-side join in BOTH its forms; a join that ran
    # without it would leave the process's count of join programs odd, which
    # tests/test_continuous_batching.py reads when it shares a worker with this file.
    engine.warmup()
    return engine


@pytest.fixture(scope="module")
def engine():
    return make_engine()


@pytest.mark.parametrize("flag,kw", [
    ("--host-cache-mb", dict(host_cache_bytes=1 << 20)),
    ("--spec-decode", dict(spec_decode={"enable": True})),
    ("--lora", dict(lora={"enable": True})),
    ("--tp", dict(tp=2)),
    ("--prefill-chunk", dict(prefill_chunk=18)),
])
def test_unsupported_engine_options_are_refused_by_flag(flag, kw):
    with pytest.raises(ValueError, match=f"exaone_moe.*{flag}"):
        make_engine(**kw)


def test_the_cache_is_two_page_pools_under_one_manager(engine):
    assert len(jax.tree_util.tree_leaves(engine.cache)) == 2
    # ONE rule from flags that exist: the 2 pages before every stride of 16
    # tokens the 64 K/V pages hold (32), or twice what 4 rows can hold (7 each).
    kind = engine.kv.beside
    assert engine.kv.pools == [kind.pool]
    assert (kind.pool.size, kind.tokens, kind.row_pages, kind.blocks) == (56, W, 7, 2)
    assert engine.cache.pages.shape[:2] == (2, 64) and engine.cache.window.shape[:2] == (6, 56)
    assert engine.device_summary()["cache_kinds"] == "kv:256,kv_window:256"
    assert engine.scheduler.beside is kind and kind.stride == 16
    assert "inject" not in engine.compile_counts()


def _requests(engine):
    from dynamo_tpu.llm.protocols import PreprocessedRequest, SamplingOptions, StopConditions
    from dynamo_tpu.runtime.engine import Context, collect

    async def gen(tokens, n, logprobs=None):
        req = PreprocessedRequest(
            token_ids=list(tokens), stop_conditions=StopConditions(max_tokens=n, ignore_eos=True),
            sampling_options=SamplingOptions(logprobs=logprobs)).to_dict()
        out = await collect(await engine.generate(Context(req)))
        if logprobs is None:
            return [t for item in out for t in item["token_ids"]]
        return [(t, lp) for item in out
                for t, lp in zip(item["token_ids"], item.get("log_probs") or item["token_ids"])]

    def check(prompt, got):
        """Teacher-forced: each token is the reference's argmax at its
        position (ONE causal pass over the prompt and the answer)."""
        logits = np.asarray(ref.forward(engine.params, HF, np.asarray(list(prompt) + got, np.int32)))
        for i, tok in enumerate(got):
            assert int(np.argmax(logits[len(prompt) - 1 + i])) == tok, len(prompt) + i

    return gen, check


def test_the_engine_resumes_hits_behind_retained_window_pages_and_counts(engine):
    """Through TpuEngine's normal path (scheduler, both pools, unified step,
    fused decode chunks of 2), greedy tokens equal the reference's argmax:
    cold; behind a hit LONGER than its last resume point (cut back); for a
    prompt that ENDS on one (resumed from the one before); and after the
    retained pages were dropped (computed again).  A row's window pages stay
    bounded through a decode several windows long."""
    from dynamo_tpu.llm.metrics import swa_metrics

    gen, check = _requests(engine)
    chunks, held = [], []
    build = engine._build_ragged

    def spy(items):
        chunks.extend((st, n) for s, st, n in items if st < len(s.prompt))
        rb = build(items)
        held.extend(len(s.beside.ids) for s, _, _ in items)
        return rb

    engine._build_ragged = spy

    async def idle():
        for _ in range(500):  # a row retires behind its stream's end
            if not engine.scheduler.running:
                return
            await asyncio.sleep(0.01)

    async def main():
        swa_metrics.reset()
        rs = np.random.RandomState(5)
        doc = rs.randint(16, 128, 38).tolist()  # resume points at 16 and 32; 9 whole blocks
        first = doc + rs.randint(16, 128, 3).tolist()
        check(first, await gen(first, 20))  # decodes to position 61: 2.5 windows past the prompt
        await idle()
        assert chunks == [(0, 16), (16, 16), (32, 9)]
        assert max(held) <= engine.kv.beside.row_pages
        assert swa_metrics.hit_tokens == {"resumed": 0, "cut": 0}
        assert swa_metrics.pool_pages["retained"] == 4 and swa_metrics.pool_pages["live"] == 0
        # a hit of 36 tokens (9 blocks) is cut back to the resume point at 32
        del chunks[:]
        second = doc + rs.randint(16, 128, 5).tolist()
        check(second, await gen(second, 6))
        assert chunks == [(32, 11)]
        assert swa_metrics.hit_tokens == {"resumed": 32, "cut": 4}
        # a prompt that ends ON a resume point resumes from the one before
        del chunks[:]
        check(doc[:32], await gen(doc[:32], 5))
        assert chunks == [(16, 16)]
        assert swa_metrics.hit_tokens == {"resumed": 48, "cut": 20}
        # the pages before 32 dropped: the hit is cut back to 16
        from dynamo_tpu.tokens import hash_token_blocks

        at32 = engine.kv.block_of(hash_token_blocks(doc, 4, None)[7].sequence_hash)
        assert len(engine.kv.beside.pool._of[at32]) == 2
        engine.kv.beside.pool.drop(at32)
        del chunks[:]
        third = doc + rs.randint(16, 128, 2).tolist()
        check(third, await gen(third, 3))
        await idle()
        assert chunks[0][0] in (16, 32) and sum(n for _, n in chunks) == 40 - chunks[0][0]
        assert swa_metrics.pool_pages["live"] == 0
        assert (swa_metrics.pool_pages["retained"] + swa_metrics.pool_pages["free"]
                == engine.kv.beside.pool.size)
        assert swa_metrics.window_rows > 0 and swa_metrics.window_pages / swa_metrics.window_rows < 7
        assert swa_metrics.attended["window"] < swa_metrics.attended["full"]
        text = swa_metrics.render()
        for name in ('kv_window_pages_total', 'kv_window_pool_pages{state="retained"}',
                     'swa_hit_tokens_total{outcome="cut"}', 'swa_attended_positions_total{kind="window"}',
                     'swa_query_tokens_total'):
            assert f"dynamo_tpu_{name}" in text

    try:
        asyncio.run(main())
    finally:
        engine._build_ragged = build


def test_a_hit_served_twice_gives_the_cold_runs_tokens_and_logprobs(engine):
    """The probe of the benchmark in small: the same prompt cold and behind
    its hit (retained pages before 32), twice."""
    gen, _ = _requests(engine)

    async def main():
        prompt = np.random.RandomState(21).randint(16, 128, 35).tolist()
        cold = await gen(prompt, 6, logprobs=3)
        assert await gen(prompt, 6, logprobs=3) == cold
        assert await gen(prompt, 6, logprobs=3) == cold

    asyncio.run(main())


def test_preemption_returns_every_window_page_and_the_rows_go_on():
    """Three rows outgrow a pool of 20 blocks: the scheduler preempts one (its
    blocks and EVERY window page freed, tokens folded into the prompt) and
    admits it again; every row's tokens are those of an uninterrupted run."""
    from dynamo_tpu.llm.metrics import swa_metrics

    engine = make_engine(num_blocks=20)
    gen, check = _requests(engine)

    async def main():
        swa_metrics.reset()
        rs = np.random.RandomState(11)
        prompts = [rs.randint(16, 128, 21).tolist() for _ in range(3)]
        answers = await asyncio.gather(*(gen(p, 14) for p in prompts))
        assert engine.scheduler.preempted >= 1
        for p, got in zip(prompts, answers):
            assert len(got) == 14
            check(p, got)
        for _ in range(200):  # a row retires behind its stream's end
            if not engine.scheduler.running:
                break
            await asyncio.sleep(0.01)
        assert engine.kv.beside.rows == 0 and swa_metrics.pool_pages["live"] == 0
        await engine.close()

    asyncio.run(main())


def test_int8_pages_keep_v_beside_a_normed_k_through_the_gain(model):
    """K is normed a head (size 1) and V is as small as the layer's input, so
    ONE scale a page would round V away: ``kv_scale`` carries a gain a layer
    behind the scales, V is stored times it and the call's output divided by
    it.  int8 pages with scales and gains stay within a tenth of the reference;
    the same scales WITHOUT the gains lose V (chip, PR 47: 0.38 after one
    layer)."""
    cfg, params, toks, want = model
    params = dict(params, embed=params["embed"] * 0.05)  # a small input, as layer 0 sees on the chip
    want = np.asarray(ref.forward(params, HF, toks))
    probe = new_cache(cfg)
    _, probe, _ = forward(params, cfg, rows_batch([(toks, table(0), 0, 29)], 32), probe)
    both = jnp.concatenate([probe.pages, probe.window])
    k_max = np.asarray(jnp.max(jnp.abs(both[:, :, :, 0::2]), axis=(1, 2, 3, 4)))
    v_max = np.asarray(jnp.max(jnp.abs(both[:, :, :, 1::2]), axis=(1, 2, 3, 4)))
    assert float(np.max(k_max / v_max)) > 50  # the leading layer: what makes the gain necessary
    scales = k_max / 127.0
    int8 = lambda: jax.tree_util.tree_map(lambda a: a.astype(jnp.int8), new_cache(cfg))
    rb = rows_batch([(toks, table(0), 0, 29)], 32)
    with_gain, _, _ = forward(params, cfg, rb, int8(),
                              kv_scale=tuple(np.concatenate([scales, k_max / v_max]).tolist()))
    without, _, _ = forward(params, cfg, rb, int8(), kv_scale=tuple(scales.tolist()))
    assert close(with_gain[0], want[28]) < 0.1
    assert close(without[0], want[28]) > 3 * close(with_gain[0], want[28])
