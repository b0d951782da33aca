"""granite-4.0-h-small (``model_type`` ``granitemoehybrid``: the hybrid family
of models/lfm2.py with the Mamba-2 mixer of models/mamba2.py) against the
plain float32 reference (models/reference/granitemoehybrid.py, the recurrence
one token at a time) on seeded random weights at a small size on the CPU, in
float32 under "highest" matmuls.

Tolerances.  LOGITS 2e-5 of the largest reference logit: both sides are
float32 and differ in summation order only (the recurrence summed in chunks
from a slot's state against one token at a time; paged GQA against a whole
softmax a head; dispatch tables against a loop over experts); measured 2e-7.
The two CONTROLS of the reference show what the limit catches at this size:
the carried state rounded to bfloat16 moves the logits by 1e-3 or more, a
dropped ``D u_t`` term by 1e-1.  A chunk resumed from a snapshot against the
same chunk of the cold run, through the same program, is held to EXACT
equality: a snapshot is a copy, and a row's sums do not depend on where it
lies in a step.
"""

import asyncio
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models import lfm2, mamba2
from dynamo_tpu.models.config import ModelConfig, register_config
from dynamo_tpu.models.family import RaggedBatch, family_of
from dynamo_tpu.models.reference import granitemoehybrid as ref

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOGIT_TOL = 2e-5

# One period in small: Mamba-2 layers around one attention layer.
HF = {
    "model_type": "granitemoehybrid", "vocab_size": 128, "hidden_size": 64, "intermediate_size": 32,
    "shared_intermediate_size": 48, "num_hidden_layers": 4,
    "layer_types": ["mamba", "mamba", "attention", "mamba"],
    "num_attention_heads": 4, "num_key_value_heads": 2, "num_local_experts": 8,
    "num_experts_per_tok": 3, "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16,
    "mamba_d_conv": 4, "mamba_expand": 2, "mamba_n_groups": 1, "mamba_conv_bias": True,
    "mamba_proj_bias": False, "attention_multiplier": 0.0625, "embedding_multiplier": 12,
    "residual_multiplier": 0.22, "logits_scaling": 16, "position_embedding_type": "nope",
    "rms_norm_eps": 1e-5, "max_position_embeddings": 1024, "tie_word_embeddings": True,
}
PS, PP, NPAGES, S, SLOTS = 4, 16, 64, 4, 8  # page size, pages a row, pages, rows, state slots
N = 48


@pytest.fixture(scope="module", autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(params=[128, 8], ids=["one-chunk", "chunks-of-8"])
def ssd_chunk(request, monkeypatch):
    """Rows shorter than an SSD chunk, and rows of several."""
    monkeypatch.setattr(mamba2, "SSD_CHUNK", request.param)


def draw(cfg, seed):
    """Seeded weights with the Mamba-2 input projection eight times the
    draw's N(0, 0.02): at a hidden size of 64 that gives xBC the size it has
    at 4096 (about 1), so that the state is a third of the mixer's output
    beside D u and not a hundredth: the controls below show it."""
    params = lfm2.init_params(cfg, jax.random.PRNGKey(seed))
    params["mamba"]["in_proj"] = params["mamba"]["in_proj"] * 8
    return params


@pytest.fixture(scope="module")
def model():
    cfg = ModelConfig.from_hf_config(HF, name="granite-test").with_overrides(dtype="float32")
    params = draw(cfg, 0)
    toks = np.random.RandomState(0).randint(0, HF["vocab_size"], size=N).astype(np.int32)
    return cfg, params, toks, np.asarray(ref.forward(params, HF, toks))


def rows_batch(rows, width, decode=False, slots=None):
    """``rows``: (tokens, table, start, n) each, packed as the engine packs
    them (pipeline.py ``_build_ragged``; decode: one token a row).  ``slots``:
    (read, write, snapshot) a row, or None: row i lives in slot i."""
    tok, pos = np.zeros(width, np.int32), np.zeros(width, np.int32)
    slot_map = np.full(width, -1, np.int32)
    tables, kv = np.zeros((S, PP), np.int32), np.zeros(S, np.int32)
    cu, at = np.zeros(S + 1, np.int32), 0
    for i, (toks, table, start, n) in enumerate(rows):
        p = np.arange(start, start + n)
        tok[at:at + n], pos[at:at + n] = toks[start:start + n], p
        slot_map[at:at + n] = table[p // PS] * PS + p % PS
        tables[i, :len(table)], kv[i] = table, start + n
        at += n
        cu[i + 1] = at
    cu[len(rows) + 1:] = at
    if decode:
        cu, num = np.arange(S + 1, dtype=np.int32), S
    else:
        num = len(rows)
    state = None
    if slots is not None:
        state = np.full((S, 3), -1, np.int32)
        state[:len(slots)] = slots
    return RaggedBatch(tok, pos, slot_map, kv, tables, cu, np.asarray([num], np.int32),
                       state_slots=state)


def width_of(n):
    return max(16, 1 << (n - 1).bit_length())


def close(a, b):
    return float(np.max(np.abs(np.asarray(a) - b)) / np.max(np.abs(b)))


def new_cache(cfg):
    return lfm2.HybridCache.create(cfg, NPAGES, PS, dtype=jnp.float32, state_slots=SLOTS)


def table(i):
    return np.arange(i * PP, (i + 1) * PP).astype(np.int32)


_STEPS = {}


def forward(params, cfg, rb, cache, **kw):
    """``lfm2.forward_ragged`` under jit, one program a (form, SSD chunk,
    shape): the mixer's loops would compile anew at every eager call."""
    key = (id(params), mamba2.SSD_CHUNK, tuple(sorted(kw.items())))
    if key not in _STEPS:
        _STEPS[key] = jax.jit(lambda rb, ca: lfm2.forward_ragged(params, cfg, rb, ca, **kw))
    return _STEPS[key](rb, cache)


def run_chunks(params, cfg, cache, toks, tab, cuts, want, row=0):
    """Prefill ``toks[cuts[0]:cuts[-1]]`` as row ``row`` in the chunks ``cuts``
    bound; each chunk's last logits against the reference."""
    for a, b in zip(cuts, cuts[1:]):
        rows = [(toks, tab, a, b - a)]
        slots = [(row if a else -1, row, -1)]
        lg, cache, _ = forward(params, cfg, rows_batch(rows, width_of(b - a), slots=slots), cache)
        assert close(lg[0], want[b - 1]) < LOGIT_TOL, (a, b)
    return cache


# ------------------------------------------------- (a) chunks, ragged steps
@pytest.mark.parametrize("cuts", [
    [0, 16, 29],           # a row across two steps
    [0, 5, 6, 9, 29],      # across four: the taps reach over a one-token and a three-token chunk
    [0, 29],
], ids=["two-steps", "four-steps", "one-piece"])
def test_chunked_prefill_then_decode_matches_the_reference(model, ssd_chunk, cuts):
    """Prompt chunks through the slots, then decode: the fused program's form
    (``decode=True``: row i's state in slot i) and a one-token row riding a
    ragged step, alternating."""
    cfg, params, toks, want = model
    cache = run_chunks(params, cfg, new_cache(cfg), toks, table(0), cuts, want)
    for t in range(29, N):
        decode = t % 2 == 0
        rb = rows_batch([(toks, table(0), t, 1)], S if decode else 16, decode=decode)
        lg, cache, _ = forward(params, cfg, rb, cache, decode=decode)
        assert close(lg[0], want[t]) < LOGIT_TOL, t


def test_two_prompt_rows_and_decode_rows_share_a_step(model, ssd_chunk):
    """Rows of unlike length in one token axis, each from its own slot: two
    prompt rows (one from zeros, one going on) and two decode rows."""
    cfg, params, toks, want = model
    rs = np.random.RandomState(3)
    others = [rs.randint(0, 128, size=N).astype(np.int32) for _ in range(3)]
    wants = [np.asarray(ref.forward(params, HF, o)) for o in others]
    cache = new_cache(cfg)
    # rows 1..3 get a past: 11, 20 and 7 tokens
    past = [(others[0], table(1), 0, 11), (others[1], table(2), 0, 20), (others[2], table(3), 0, 7)]
    slots = [(-1, 1, -1), (-1, 2, -1), (-1, 3, -1)]
    _, cache, _ = forward(params, cfg, rows_batch(past, 64, slots=slots), cache)
    rows = [(toks, table(0), 0, 19), (others[0], table(1), 11, 13),
            (others[1], table(2), 20, 1), (others[2], table(3), 7, 1)]
    slots = [(-1, 0, -1), (1, 1, -1), (2, 2, -1), (3, 3, -1)]
    lg, cache, _ = forward(params, cfg, rows_batch(rows, 64, slots=slots), cache)
    assert close(lg[0], want[18]) < LOGIT_TOL
    assert close(lg[1], wants[0][23]) < LOGIT_TOL
    assert close(lg[2], wants[1][20]) < LOGIT_TOL
    assert close(lg[3], wants[2][7]) < LOGIT_TOL
    # ... and every row goes on from what the step left in its slot
    rows = [(toks, table(0), 19, 5), (others[0], table(1), 24, 1),
            (others[1], table(2), 21, 3), (others[2], table(3), 8, 2)]
    lg, cache, _ = forward(
        params, cfg, rows_batch(rows, 16, slots=[(i, i, -1) for i in range(4)]), cache)
    for i, w in enumerate((want[23], wants[0][24], wants[1][23], wants[2][9])):
        assert close(lg[i], w) < LOGIT_TOL, i


def test_the_controls_move_the_logits_past_the_limit(model):
    """The limit is tight enough: a bfloat16 state and a dropped D u_t fail it."""
    cfg, params, toks, want = model
    rounded = np.asarray(ref.forward(params, HF, toks, state=jnp.bfloat16))
    no_du = np.asarray(ref.forward(params, HF, toks, drop_du=True))
    assert close(rounded[-1], want[-1]) > 5 * LOGIT_TOL
    assert close(no_du[-1], want[-1]) > 1000 * LOGIT_TOL


# ------------------------------------------------------ (b) snapshots, bits
def test_a_chunk_resumed_from_a_snapshot_equals_the_cold_chunk_to_the_bit(model, ssd_chunk):
    """The cold run leaves a snapshot at 32 (a copy of its live slot's state
    and tail, in slot 6); another row, in another live slot and in a step it
    shares with a stranger, resumes from it: the logits and the state it
    leaves are the cold run's, bit for bit."""
    cfg, params, toks, want = model
    cache = new_cache(cfg)
    step = lambda rb, ca: forward(params, cfg, rb, ca)[:2]
    _, cache = step(rows_batch([(toks, table(0), 0, 16)], 16, slots=[(-1, 0, -1)]), cache)
    _, cache = step(rows_batch([(toks, table(0), 16, 16)], 16, slots=[(0, 0, 6)]), cache)
    assert np.array_equal(cache.ssm[:, 6], cache.ssm[:, 0])
    assert np.array_equal(cache.tail[:, :, 6], cache.tail[:, :, 0])
    assert float(jnp.abs(cache.ssm[:, 6]).max()) > 0
    cold, cache = step(rows_batch([(toks, table(0), 32, 9)], 16, slots=[(0, 0, -1)]), cache)
    assert close(cold[0], want[40]) < LOGIT_TOL
    # the hit: K/V of the first 32 tokens shared, the state read from slot 6 into slot 2
    other = np.random.RandomState(9).randint(0, 128, size=N).astype(np.int32)
    alone, c2 = step(rows_batch([(toks, table(0), 32, 9)], 16, slots=[(6, 2, -1)]), cache)
    assert np.array_equal(alone[0], cold[0])
    assert np.array_equal(c2.ssm[:, 2], cache.ssm[:, 0]) and np.array_equal(
        c2.tail[:, :, 2], cache.tail[:, :, 0])
    rows = [(other, table(1), 0, 5), (toks, table(0), 32, 9)]
    shared, _ = step(rows_batch(rows, 16, slots=[(-1, 1, -1), (6, 2, -1)]), cache)
    assert np.array_equal(shared[1], cold[0])
    # the control of chip_smoke's parity child: the state dropped at the boundary
    dropped, _, _ = forward(
        params, cfg, rows_batch([(toks, table(0), 32, 9)], 16, slots=[(6, 2, -1)]), cache,
        drop_state_at_stride=16)
    assert close(dropped[0], want[40]) > 100 * LOGIT_TOL


def test_a_fused_chunk_of_four_steps_equals_four_single_steps(model):
    """The decode program's scan over ``decode_steps`` against single steps:
    slots updated in place, a row past its limit left as it was."""
    cfg, params, toks, want = model
    cache = run_chunks(params, cfg, new_cache(cfg), toks, table(0), [0, 30], want)
    single = cache
    for t in range(30, 34):
        lg, single, _ = forward(
            params, cfg, rows_batch([(toks, table(0), t, 1)], S, decode=True), single, decode=True)
        assert close(lg[0], want[t]) < LOGIT_TOL

    def body(ca, t):
        rb = rows_batch([(toks, table(0), 0, 1)], S, decode=True)
        rb = rb._replace(token_ids=jnp.where(jnp.arange(S) == 0, jnp.asarray(toks)[t], 0),
                         positions=jnp.where(jnp.arange(S) == 0, t, 0),
                         slot_mapping=jnp.where(jnp.arange(S) == 0,
                                                jnp.asarray(table(0))[t // PS] * PS + t % PS, -1),
                         kv_lens=jnp.where(jnp.arange(S) == 0, t + 1, 0))
        lg, ca, _ = lfm2.forward_ragged(params, cfg, rb, ca, decode=True)
        return ca, lg[0]

    fused, lgs = jax.jit(lambda ca: jax.lax.scan(body, ca, jnp.arange(30, 34)))(cache)
    assert close(lgs[-1], want[33]) < LOGIT_TOL
    assert np.allclose(fused.ssm[:, 0], single.ssm[:, 0], rtol=0, atol=1e-6)
    assert np.array_equal(fused.ssm[:, 1:], cache.ssm[:, 1:])  # idle rows' slots untouched


def test_two_shares_of_the_experts_add_up_to_the_uncut_layer():
    """model-configs section 4: over both ep_size shares the routed parts, and
    the shared SwiGLU counted ONCE, add up to the uncut reference's whole
    feed-forward.  Tolerance 1e-5 of the largest output."""
    full_hf = dict(HF, num_local_experts=16, num_experts_per_tok=5)
    full_cfg = ModelConfig.from_hf_config(full_hf, name="full").with_overrides(dtype="float32")
    full = lfm2.init_params(full_cfg, jax.random.PRNGKey(7))
    lp_full = {k: v[0] for g in ("moe", "shared") for k, v in full[g].items()}
    x = jax.random.normal(jax.random.PRNGKey(4), (48, 64), jnp.float32)
    want = np.asarray(ref.moe(lp_full, full_hf, x, list(range(16))))
    total = np.zeros_like(want)
    real = jnp.ones((48,), bool)
    for rank in range(2):
        hf = dict(full_hf, num_local_experts=8, num_local_experts_published=16, ep_size=2,
                  ep_rank=rank)
        cfg = ModelConfig.from_hf_config(hf, name=f"share{rank}").with_overrides(dtype="float32")
        assert (cfg.num_experts, cfg.router_experts, cfg.ep_rank) == (8, 16, rank)
        lo = rank * 8
        lp = dict(lp_full, **{k: lp_full[k][lo:lo + 8] for k in ("moe_gate", "moe_up", "moe_down")})
        part, load = lfm2.moe_block(x, lp, cfg, real, None)
        assert close(part, np.asarray(ref.moe(lp, hf, x, range(lo, lo + 8), shared=False))) < 1e-5
        assert int(jnp.sum(load)) <= 48 * 5
        total += np.asarray(part)
    from dynamo_tpu.models.llama import mlp
    total += np.asarray(mlp(x, {k: lp_full[k] for k in ("w_gate", "w_up", "w_down")}))
    assert close(total, want) < 1e-5


# ------------------------------------------------------------ from_hf_config
def test_from_hf_config_reads_the_catalog_row_and_the_benchmarks_file():
    with open(os.path.join(ROOT, "chipbench/configs/granite-4.0-h-small-10l-ep2.json")) as f:
        body = json.load(f)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "granite-4.0-h-small")
        assert body["source"] == row["source_url"]
        for key, value in row["config"].items():  # every published key, as published or reduced
            if key in body["reduced"]:
                continue
            assert body[key] == value, key
        whole = ModelConfig.from_hf_config(row["config"], name="whole")
        assert (whole.num_layers, whole.num_experts, whole.router_experts, whole.ep_size) == (
            40, 72, 72, 1)
        shapes = lfm2.leaf_shapes(whole)
        # The release's "32B": every parameter, embedding tied.
        assert sum(int(np.prod(s)) for g in shapes.values() for s in g.values()) == 32_207_337_984
    assert body["reduced"] == ["num_hidden_layers", "layer_types", "num_local_experts", "ep_size",
                               "vocab_size"]
    cfg = ModelConfig.from_hf_config(body, name="cut")
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (
        10, 4096, 32, 8, 128)
    assert (cfg.num_experts, cfg.router_experts, cfg.num_experts_per_token, cfg.ep_size,
            cfg.ep_rank) == (36, 72, 10, 2, 0)
    assert (cfg.moe_intermediate_size, cfg.shared_intermediate_size, cfg.vocab_size) == (
        768, 1536, 50176)
    assert cfg.layer_types == ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
    assert (cfg.embedding_multiplier, cfg.residual_multiplier, cfg.attention_multiplier,
            cfg.logits_scaling) == (12.0, 0.22, 1 / 128, 16.0)
    assert not cfg.use_rope and cfg.gate_scoring == "softmax" and cfg.tie_word_embeddings
    assert mamba2.dims(cfg) == (8192, 128, 64, 128, 4) and mamba2.conv_width(cfg) == 8448
    fam = family_of(cfg)
    assert fam.name == "hybrid" and fam.beside is not None
    assert lfm2.layer_counts(cfg) == (0, 1, 0, 10) and lfm2.mamba_layers(cfg) == 9
    shapes = lfm2.leaf_shapes(cfg)
    assert shapes["mamba"]["in_proj"] == (9, 4096, 16768)
    # The issue's count of the cut: 4.76e9 parameters, 4.8 GB in int8.
    assert sum(int(np.prod(s)) for g in shapes.values() for s in g.values()) == 4_757_211_776
    cache = jax.eval_shape(lambda: lfm2.HybridCache.create(cfg, 16384, 16, dtype=jnp.int8,
                                                           state_slots=134))
    assert cache.pages.shape == (1, 16384, 16, 16, 128) and cache.conv is None
    assert cache.ssm.shape == (9, 134, 8192, 128) and cache.ssm.dtype == jnp.float32
    assert cache.tail.shape == (9, 3, 134, 8448) and cache.tail.dtype == jnp.bfloat16
    per_slot = (cache.ssm.size * 4 + cache.tail.size * 2) // 134
    assert per_slot == 38_204_928
    assert lfm2.snapshot_slots(16384, 16, 512) == 102
    # every other model: no multiplier, rotation, sigmoid gate, resumed at a token or a block
    other = ModelConfig.from_hf_config({"model_type": "llama", "vocab_size": 8, "hidden_size": 8,
                                        "num_hidden_layers": 1, "num_attention_heads": 1,
                                        "intermediate_size": 8})
    assert (other.embedding_multiplier, other.residual_multiplier, other.logits_scaling) == (1, 1, 1)
    assert other.attention_multiplier is None and other.use_rope and other.gate_scoring == "sigmoid"
    assert family_of(other).beside is None


@pytest.mark.parametrize("bad,match", [
    (dict(layer_types=["mamba", "conv", "attention", "mamba"]), "layer_types"),
    (dict(mamba_n_groups=8), "mamba_n_groups"),
    (dict(position_embedding_type="rope"), "position_embedding_type"),
    (dict(num_local_experts=4, num_local_experts_published=12, ep_size=2), "router's width"),
    (dict(mamba_n_heads=4), "mamba_expand"),
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

    cfg = register_config(ModelConfig.from_hf_config(HF, name="granite-engine"))
    return TpuEngine(EngineConfig(model="granite-engine", **dict(ENGINE, **kw)),
                     params=draw(cfg.with_overrides(dtype="float32"), 2))


@pytest.fixture(scope="module")
def engine():
    return make_engine()


@pytest.mark.parametrize("flag,kw", [
    ("--host-cache-mb", dict(host_cache_bytes=1 << 20)),
    ("--spec-decode", dict(spec_decode={"enable": True})),
    ("--lora", dict(lora={"enable": True})),
    ("--tp", dict(tp=2)),
])
def test_unsupported_engine_options_are_refused_by_flag(flag, kw):
    with pytest.raises(ValueError, match=f"granitemoehybrid.*{flag}"):
        make_engine(**kw)


def test_the_cache_is_pages_and_slots_under_one_manager(engine):
    assert len(jax.tree_util.tree_leaves(engine.cache)) == 3
    # ONE rule from flags that exist: max_batch live slots, and a snapshot for
    # every five resume strides (prefill_chunk) the pages can hold: 64 x 4 / 80.
    kind = engine.kv.beside
    assert type(kind).__name__ == "SlotState" and engine.kv.pools == [kind.live, kind.snapshots]
    assert (kind.live.first, kind.live.size, kind.snapshots.first, kind.snapshots.size) == (0, 4, 4, 3)
    assert engine.cache.ssm.shape == (3, 7, 128, 16) and engine.cache.ssm.dtype == jnp.float32
    assert engine.cache.tail.shape == (3, 3, 7, 160)
    assert engine.device_summary()["cache_kinds"] == "kv:256,ssm_slot:8192,conv_tail:1920"
    assert engine.scheduler.beside is kind and kind.stride == 16
    assert "inject" not in engine.compile_counts()


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


def test_the_engine_resumes_hits_from_snapshots_and_counts(engine):
    """Through TpuEngine's normal path (scheduler, block manager, unified step,
    fused decode chunks of 2), greedy tokens equal the reference's argmax:
    cold; behind a hit that is LONGER than its last snapshot (cut back); for a
    prompt that ENDS on its last snapshot (resumed from the one before); and
    after the snapshots were evicted (recomputed).  No prompt row's share of a
    step crosses a multiple of the stride."""
    from dynamo_tpu.llm.metrics import ssm_metrics

    gen, check = _requests(engine)
    chunks = []
    build = engine._build_ragged

    def spy(items):
        chunks.extend((st, n) for s, st, n in items if st < len(s.prompt))
        return build(items)

    engine._build_ragged = spy

    async def main():
        ssm_metrics.reset()
        rs = np.random.RandomState(5)
        doc = rs.randint(16, 128, 38).tolist()  # snapshots at 16 and 32; 9 whole blocks
        first = doc + rs.randint(16, 128, 3).tolist()
        check(first, await gen(first, 4))
        assert chunks == [(0, 16), (16, 16), (32, 9)]
        assert ssm_metrics.request_starts == {"zero": 1, "snapshot": 0}
        assert ssm_metrics.snapshots == {"taken": 2, "no_slot": 0, "evicted": 0}
        # a hit of 36 tokens (9 blocks) is cut back to the snapshot at 32
        del chunks[:]
        second = doc + rs.randint(16, 128, 5).tolist()
        check(second, await gen(second, 6))
        assert chunks == [(32, 11)]
        assert ssm_metrics.request_starts == {"zero": 1, "snapshot": 1}
        assert ssm_metrics.hit_tokens == {"resumed": 32, "given_back": 4}
        # a prompt that ends ON its last snapshot resumes from the one before
        del chunks[:]
        check(doc[:32], await gen(doc[:32], 5))
        assert chunks == [(16, 16)]
        assert ssm_metrics.hit_tokens == {"resumed": 48, "given_back": 20}
        assert ssm_metrics.slots_in_use["snapshot"] == 2
        # other prompts take the pool's three slots: the least recently used go first
        for i in range(2):
            filler = rs.randint(16, 128, 33).tolist()
            check(filler, await gen(filler, 2))
        assert ssm_metrics.snapshots["evicted"] >= 1 and ssm_metrics.slots_in_use["snapshot"] == 3
        del chunks[:]
        third = doc + rs.randint(16, 128, 2).tolist()
        check(third, await gen(third, 3))  # its blocks are there, its snapshots are not all
        assert chunks[0][0] in (0, 16) and sum(n for _, n in chunks) == 40 - chunks[0][0]
        text = ssm_metrics.render()
        for name in ('ssm_request_starts_total{state="snapshot"}', 'ssm_hit_tokens_total{outcome="given_back"}',
                     'ssm_snapshots_total{outcome="evicted"}', 'ssm_slots_in_use{kind="live"}'):
            assert f"dynamo_tpu_{name}" in text

    try:
        asyncio.run(main())
    finally:
        engine._build_ragged = build


def test_a_hit_served_twice_gives_the_cold_runs_tokens_and_logprobs(engine):
    """The probe of the benchmark in small: the same prompt cold and behind
    its hit (a snapshot at 32, the last step the cold run's last step)."""
    from dynamo_tpu.llm.protocols import PreprocessedRequest, SamplingOptions, StopConditions
    from dynamo_tpu.runtime.engine import Context, collect

    async def gen(tokens):
        req = PreprocessedRequest(
            token_ids=list(tokens), stop_conditions=StopConditions(max_tokens=6, ignore_eos=True),
            sampling_options=SamplingOptions(logprobs=3)).to_dict()
        out = await collect(await engine.generate(Context(req)))
        return [(t, lp) for item in out for t, lp in zip(item["token_ids"], item.get("log_probs") or item["token_ids"])]

    async def main():
        prompt = np.random.RandomState(21).randint(16, 128, 35).tolist()
        cold = await gen(prompt)
        hit = await gen(prompt)
        assert cold == hit

    asyncio.run(main())


def test_preemption_gives_the_slot_back_and_resumes_from_a_snapshot():
    """Three rows outgrow a pool of 20 blocks: the scheduler preempts one
    (blocks and live slot freed, tokens folded into the prompt) and admits it
    again, from its last snapshot or from zeros; every row's tokens are those
    of an uninterrupted run."""
    from dynamo_tpu.llm.metrics import ssm_metrics

    engine = make_engine(num_blocks=20)
    assert engine.kv.beside.snapshots.size == 1
    gen, check = _requests(engine)

    async def main():
        ssm_metrics.reset()
        rs = np.random.RandomState(11)
        prompts = [rs.randint(16, 128, 21).tolist() for _ in range(3)]
        answers = await asyncio.gather(*(gen(p, 14) for p in prompts))
        assert engine.scheduler.preempted >= 1
        for p, got in zip(prompts, answers):
            assert len(got) == 14
            check(p, got)
        assert sum(ssm_metrics.request_starts.values()) == 3 + engine.scheduler.preempted
        for _ in range(200):  # a row retires behind its stream's end
            if not engine.scheduler.running:
                break
            await asyncio.sleep(0.01)
        assert ssm_metrics.slots_in_use["live"] == 0 and engine.kv.beside.live.free == 4
        await engine.close()

    asyncio.run(main())


def test_a_sibling_waits_for_the_snapshot_a_running_row_is_about_to_leave():
    """Three prompts of one 36-token document arrive AT ONCE: the first is
    admitted from zeros; the others are not admitted beside it to compute the
    same tokens from zeros (their state's start is fixed at admission), they
    wait until it has left its snapshots at 16 and 32 and start from the one
    at 32.  Tokens are the reference's either way; a prompt of another
    document is held up by nothing but its place in the queue."""
    from dynamo_tpu.llm.metrics import ssm_metrics

    engine = make_engine()
    gen, check = _requests(engine)
    chunks = []
    build = engine._build_ragged

    def spy(items):
        chunks.extend((s.prompt[:36], st, n) for s, st, n in items if st < len(s.prompt))
        return build(items)

    engine._build_ragged = spy

    async def main():
        ssm_metrics.reset()
        rs = np.random.RandomState(31)
        doc = rs.randint(16, 128, 36).tolist()
        prompts = [doc + rs.randint(16, 128, n).tolist() for n in (3, 5, 4)]
        prompts.append(rs.randint(16, 128, 20).tolist())  # another document
        answers = await asyncio.gather(*(gen(p, 4) for p in prompts))
        for p, got in zip(prompts, answers):
            check(p, got)
        assert ssm_metrics.request_starts == {"zero": 2, "snapshot": 2}
        assert ssm_metrics.hit_tokens["resumed"] == 64  # (given back: the blocks sealed past 32 by then)
        # the document's prefix was computed ONCE; each sibling computed its own tail from 32
        of_doc = sorted((st, n) for head, st, n in chunks if head == doc)
        assert of_doc == [(0, 16), (16, 16), (32, 7), (32, 8), (32, 9)], of_doc
        await engine.close()

    asyncio.run(main())


def test_a_pinned_start_is_no_rows_snapshot_target_and_an_unrun_batch_leaks_nothing():
    """A pool of ONE snapshot: a row that resumes from it shares a step with a
    row that ends on a stride boundary behind it in the step.  The snapshot
    stays pinned while the step is built, so the second row is handed no slot
    (its block is not resumable, never wrong) and NOT the slot the first row
    reads; rows released before their step ran give pin and slot back."""
    from dynamo_tpu.engine.scheduler import SequenceState
    from dynamo_tpu.llm.metrics import ssm_metrics
    from dynamo_tpu.tokens import TokenBlockSequence

    engine = make_engine(num_blocks=20)
    snaps, live = engine.kv.beside.snapshots, engine.kv.beside.live
    assert snaps.size == 1
    gen, check = _requests(engine)
    rs = np.random.RandomState(17)
    doc = rs.randint(16, 128, 16).tolist()

    def seq_of(rid, prompt):
        return SequenceState(request_id=rid, prompt=list(prompt), max_new_tokens=2,
                             block_seq=TokenBlockSequence(block_size=4))

    async def main():
        ssm_metrics.reset()
        first = doc + rs.randint(16, 128, 5).tolist()
        check(first, await gen(first, 2))  # leaves the pool's one snapshot, at 16
        for _ in range(200):
            if not engine.scheduler.running:
                break
            await asyncio.sleep(0.01)
        ((slot,),) = snaps._of.values()
        sched, kv = engine.scheduler, engine.kv
        pins = lambda: {u + snaps.first: n for u, n in enumerate(snaps._rows) if n}
        hit = seq_of("hit", doc + rs.randint(16, 128, 5).tolist())
        other = seq_of("other", rs.randint(16, 128, 24).tolist())
        assert sched._try_admit(hit) and sched._try_admit(other)
        sched.running.extend([hit, other])
        assert hit.beside.start == slot and pins() == {slot: 1}
        other.num_computed = 11  # its share of this step ends ON the stride
        rb = engine._build_ragged([(hit, 16, 5), (other, 11, 5)])
        assert rb.state_slots[0].tolist() == [slot, hit.beside.slot, -1]
        assert rb.state_slots[1].tolist() == [-1, other.beside.slot, -1]
        assert ssm_metrics.snapshots["no_slot"] == 1 and pins() == {slot: 1}
        # enqueued: the pin goes, and a later step may take the slot
        kv.beside.enqueued(hit, 21)
        assert pins() == {} and hit.beside.start is None
        other.beside.start = None
        rb = engine._build_ragged([(other, 11, 5)])
        assert rb.state_slots[0].tolist() == [other.beside.slot, other.beside.slot, slot]
        assert other.beside.due is not None and not snaps.entries and not snaps.free
        assert pins() == {slot: 1}  # reserved for the step: a row's reference
        # the step never ran: both rows go, the reserved slot is free again
        sched.remove(hit)
        sched.remove(other)
        assert snaps._free == [slot] and other.beside is None and hit.beside is None
        assert live.free == 4 and pins() == {}
        await engine.close()

    asyncio.run(main())


def test_quantized_draw_and_its_float_tree():
    cfg = ModelConfig.from_hf_config(HF, name="q")
    q = lfm2.init_params_quantized(cfg, jax.random.PRNGKey(1))
    assert q["mamba"]["in_proj"].dtype == jnp.int8 and q["mamba"]["in_proj_scale"].shape == (3, 296)
    assert q["mamba"]["conv_w"].dtype == jnp.bfloat16 and q["mamba"]["norm_w"].dtype == jnp.bfloat16
    assert all(q["mamba"][k].dtype == jnp.float32 for k in ("A_log", "D", "dt_bias"))
    assert q["shared"]["w_up"].dtype == jnp.int8 and q["moe"]["router"].dtype == jnp.bfloat16
    assert "router_bias" not in q["moe"] and "q_norm" not in q["attn"] and "conv" not in q
    a = -np.exp(np.asarray(q["mamba"]["A_log"]))
    dt = np.log1p(np.exp(np.asarray(q["mamba"]["dt_bias"])))
    assert a.min() >= -16 and a.max() <= -1 and dt.min() >= 9e-4 and dt.max() <= 0.11
    f = lfm2.dequantize_params(q)
    assert f["mamba"]["out_proj"].dtype == jnp.float32 and lfm2.quantize_params(q) is q


def test_the_benchmarks_copy_of_the_reference_is_the_reference():
    with open(os.path.join(ROOT, "dynamo_tpu/models/reference/granitemoehybrid.py")) as a, open(
            os.path.join(ROOT, "chipbench/reference/granitemoehybrid.py")) as b:
        assert a.read() == b.read()
