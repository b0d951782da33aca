"""AI21-Jamba2-3B (``model_type`` ``jamba``: the hybrid family of
models/lfm2.py with the Mamba-1 mixer of models/mamba1.py, GQA of ONE K/V head
without rotation and a dense SwiGLU in every layer) against the plain float32
reference (models/reference/jamba.py, the recurrence one token at a time) on
seeded random weights at a small size on the CPU, in float32 under "highest"
matmuls.

Tolerances.  LOGITS and a mixer's OUTPUT 2e-5 of the largest reference value:
both sides are float32 and differ in summation order only (the 16 terms of
``C . S`` a channel, paged attention against a whole softmax a head); measured
2e-6.  The CONTROLS of the reference show what the limit catches at this size:
the carried state rounded to bfloat16, or the three inner norms left out, each
moves the logits by more than five times the limit.  A chunk resumed from a
snapshot against the same chunk of the cold run, through the same program, is
held to EXACT equality: a snapshot is a copy, and a row's sums do not depend
on where it lies in a step.
"""

import asyncio
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models import lfm2, mamba1, mamba2
from dynamo_tpu.models.config import ModelConfig, register_config
from dynamo_tpu.models.family import RaggedBatch, family_of
from dynamo_tpu.models.reference import jamba as ref

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOGIT_TOL = 2e-5

# One period in small (M M A M by offset 2, period 4); 4 query heads over ONE
# K/V head; every feed-forward dense.
HF = {
    "model_type": "jamba", "vocab_size": 128, "hidden_size": 64, "intermediate_size": 96,
    "num_hidden_layers": 4, "attn_layer_offset": 2, "attn_layer_period": 4,
    "expert_layer_offset": 1, "expert_layer_period": 2, "num_experts": 1, "num_experts_per_tok": 1,
    "num_attention_heads": 4, "num_key_value_heads": 1, "mamba_d_state": 16, "mamba_d_conv": 4,
    "mamba_expand": 2, "mamba_dt_rank": 4, "mamba_conv_bias": True, "mamba_proj_bias": False,
    "hidden_act": "silu", "rms_norm_eps": 1e-6, "sliding_window": None,
    "tie_word_embeddings": True, "max_position_embeddings": 4096,
}
PS, PP, NPAGES, S, SLOTS = 4, 16, 64, 4, 8  # page size, pages a row, pages, rows, state slots
N = 48
D, DI = 64, 128


@pytest.fixture(scope="module", autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(params=[64, 8], ids=["one-block", "blocks-of-8"])
def scan_block(request, monkeypatch):
    """Rows shorter than a block of the scan's tokens, and rows of several."""
    monkeypatch.setattr(mamba1, "SCAN_CHUNK", request.param)


def draw(cfg, seed):
    """Seeded weights with the mixer's input projection and ``W_x`` eight
    times the draw's N(0, 0.02): at a hidden size of 64 that gives the taps'
    input and B, C the size they have at 2560 (about 1), so that silu is no
    straight line and the state's read can be told from ``D c``."""
    params = lfm2.init_params(cfg, jax.random.PRNGKey(seed))
    for name in ("in_proj", "x_proj"):
        params["mamba1"][name] = params["mamba1"][name] * 8
    return params


@pytest.fixture(scope="module")
def model():
    cfg = ModelConfig.from_hf_config(HF, name="jamba-test").with_overrides(dtype="float32")
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
    """``lfm2.forward_ragged`` under jit, one program a (form, block, shape)."""
    key = (id(params), mamba1.SCAN_CHUNK, tuple(sorted(kw.items())))
    if key not in _STEPS:
        _STEPS[key] = jax.jit(lambda rb, ca: lfm2.forward_ragged(params, cfg, rb, ca, **kw))
    return _STEPS[key](rb, cache)


def run_chunks(params, cfg, cache, toks, tab, cuts, want, row=0):
    for a, b in zip(cuts, cuts[1:]):
        rows = [(toks, tab, a, b - a)]
        slots = [(row if a else -1, row, -1)]
        lg, cache, _ = forward(params, cfg, rows_batch(rows, width_of(b - a), slots=slots), cache)
        assert close(lg[0], want[b - 1]) < LOGIT_TOL, (a, b)
    return cache


# ------------------------------------------- (a) the mixer alone: scan, step
def mixer_rows(spans, T):
    """``mamba2.Rows`` of a ragged step whose row i covers tokens
    ``spans[i] = (first, count, read, write, snap)`` of the step's T."""
    first, count, read, write, snap = (np.asarray(v, np.int32) for v in zip(*spans))
    row_of = np.full(T, len(spans), np.int32)
    for i, (f, n) in enumerate(zip(first, count)):
        row_of[f:f + n] = i
    return mamba2.Rows(jnp.asarray(first), jnp.asarray(count), jnp.asarray(len(spans), jnp.int32),
                       jnp.asarray(row_of), jnp.asarray(read), jnp.asarray(write),
                       jnp.asarray(snap))


def mixer_case(seed):
    cfg = ModelConfig.from_hf_config(HF, name="mamba1-alone").with_overrides(dtype="float32")
    lp = {k: v[1] for k, v in draw(cfg, seed)["mamba1"].items()}
    rs = np.random.RandomState(seed)
    xs = [jnp.asarray(rs.randn(n, D), jnp.float32) for n in (41, 23)]
    return cfg, lp, xs, [np.asarray(ref.mamba1(lp, HF, x)) for x in xs]


def pools(cfg):
    cache = new_cache(cfg)
    return cache.ssm, cache.tail


def test_mamba1_scan_and_step_match_the_token_loop_at_ragged_rows_from_a_stored_state(scan_block):
    """Two sequences through ``scan`` in ragged steps: rows of unlike length
    that begin MID-SEQUENCE from the state and tail an earlier step stored in
    their slot (or in a snapshot's), then ``step`` after ``scan``, against the
    reference's loop over the whole sequences."""
    cfg, lp, (xa, xb), (wa, wb) = mixer_case(3)
    ssm, tail = pools(cfg)
    assert ssm.shape == (3, SLOTS, 16, DI) and tail.shape == (3, 3, SLOTS, DI)
    scan = jax.jit(lambda x, ssm, tail, rows: mamba1.scan(x, lp, cfg, ssm, tail, 1, rows))
    # step 1: a's first 13 tokens (slot 0, a snapshot in slot 5) and b's first 9 (slot 1)
    x = jnp.concatenate([xa[:13], xb[:9], jnp.zeros((10, D))])
    y, ssm, tail = scan(x, ssm, tail, mixer_rows([(0, 13, -1, 0, 5), (13, 9, -1, 1, -1)], 32))
    assert close(y[:13], wa[:13]) < LOGIT_TOL and close(y[13:22], wb[:9]) < LOGIT_TOL
    assert np.array_equal(ssm[1, 5], ssm[1, 0]) and float(jnp.abs(ssm[1, 5]).max()) > 0
    assert float(jnp.abs(ssm[0]).max()) == 0 and float(jnp.abs(ssm[2]).max()) == 0  # layer 1 alone
    assert float(jnp.abs(ssm[1, 2:5]).max()) == 0 and float(jnp.abs(ssm[1, 6:]).max()) == 0
    # step 2: b goes on first (14 tokens), then a from the SNAPSHOT into slot 2 (27 tokens)
    x = jnp.concatenate([xb[9:23], xa[13:40], jnp.zeros((23, D))])
    y, ssm, tail = scan(x, ssm, tail, mixer_rows([(0, 14, 1, 1, -1), (14, 27, 5, 2, -1)], 64))
    assert close(y[:14], wb[9:23]) < LOGIT_TOL and close(y[14:41], wa[13:40]) < LOGIT_TOL
    # a row's sums do not depend on where it lies in a step nor on what shares
    # it: a's 27 tokens ALONE, from the same snapshot, leave the same bits
    alone, ssm2, tail2 = scan(jnp.concatenate([xa[13:40], jnp.zeros((37, D))]), ssm, tail,
                              mixer_rows([(0, 27, 5, 3, -1)], 64))
    assert np.array_equal(alone[:27], y[14:41])
    assert np.array_equal(ssm2[1, 3], ssm[1, 2]) and np.array_equal(tail2[1, :, 3], tail[1, :, 2])
    # a row without a slot (warm-up) and a row of no tokens write nothing
    _, ssm3, tail3 = scan(x, ssm, tail, mixer_rows([(0, 14, 1, -1, -1), (14, 0, 5, 2, -1)], 64))
    assert np.array_equal(ssm3, ssm) and np.array_equal(tail3, tail)
    # step after scan: a's last token as row 2 of the decode form (row i is slot i)
    xs = jnp.zeros((S, D)).at[2].set(xa[40])
    before, tail_before = ssm, tail
    y, ssm, tail = jax.jit(lambda x, ssm, tail: mamba1.step(
        x, lp, cfg, ssm, tail, 1, jnp.arange(S) == 2))(xs, ssm, tail)
    assert close(y[2], wa[40]) < LOGIT_TOL
    assert np.array_equal(ssm[1, :2], before[1, :2]) and np.array_equal(ssm[1, 3:], before[1, 3:])
    assert np.array_equal(tail[1, :, :2], tail_before[1, :, :2])
    assert np.array_equal(ssm[0], before[0]) and not np.array_equal(ssm[1, 2], before[1, 2])


def test_the_state_is_float32_and_the_tail_the_activation_dtype():
    cfg = ModelConfig.from_hf_config(HF, name="jamba-bf16")
    cache = lfm2.HybridCache.create(cfg, NPAGES, PS, dtype=jnp.bfloat16, state_slots=SLOTS)
    assert cache.ssm.dtype == jnp.float32 and cache.tail.dtype == jnp.bfloat16
    lp = {k: v[0] for k, v in lfm2.init_params(cfg, jax.random.PRNGKey(1))["mamba1"].items()}
    x = jax.random.normal(jax.random.PRNGKey(2), (16, D), jnp.bfloat16)
    y, ssm, tail = jax.jit(lambda x, ssm, tail, rows: mamba1.scan(x, lp, cfg, ssm, tail, 0, rows))(
        x, cache.ssm, cache.tail, mixer_rows([(0, 11, -1, 0, -1)], 16))
    assert y.dtype == jnp.bfloat16 and ssm.dtype == jnp.float32 and tail.dtype == jnp.bfloat16
    y, ssm, tail = jax.jit(lambda x, ssm, tail: mamba1.step(
        x, lp, cfg, ssm, tail, 0, jnp.ones((S,), bool)))(x[:S], ssm, tail)
    assert y.dtype == jnp.bfloat16 and ssm.dtype == jnp.float32 and tail.dtype == jnp.bfloat16


# ------------------------------------------- (b) the model: chunks, ragged steps
@pytest.mark.parametrize("cuts", [
    [0, 16, 29],           # a row across two steps
    [0, 5, 6, 9, 29],      # across four: the taps reach over a one-token and a three-token chunk
    [0, 29],
], ids=["two-steps", "four-steps", "one-piece"])
def test_chunked_prefill_then_decode_matches_the_reference(model, cuts):
    """Prompt chunks through pages and slots, then decode: the fused program's
    form (``decode=True``: row i's state in slot i) and a one-token row riding
    a ragged step, alternating."""
    cfg, params, toks, want = model
    cache = run_chunks(params, cfg, new_cache(cfg), toks, table(0), cuts, want)
    for t in range(29, N):
        decode = t % 2 == 0
        rb = rows_batch([(toks, table(0), t, 1)], S if decode else 16, decode=decode)
        lg, cache, aux = forward(params, cfg, rb, cache, decode=decode)
        assert close(lg[0], want[t]) < LOGIT_TOL, t
    assert np.array_equal(aux, np.zeros(4, np.int32))  # no experts: nothing to count


def test_two_prompt_rows_and_decode_rows_share_a_step(model, scan_block):
    cfg, params, toks, want = model
    rs = np.random.RandomState(3)
    others = [rs.randint(0, 128, size=N).astype(np.int32) for _ in range(3)]
    wants = [np.asarray(ref.forward(params, HF, o)) for o in others]
    cache = new_cache(cfg)
    past = [(others[0], table(1), 0, 11), (others[1], table(2), 0, 20), (others[2], table(3), 0, 7)]
    slots = [(-1, 1, -1), (-1, 2, -1), (-1, 3, -1)]
    _, cache, _ = forward(params, cfg, rows_batch(past, 64, slots=slots), cache)
    rows = [(toks, table(0), 0, 19), (others[0], table(1), 11, 13),
            (others[1], table(2), 20, 1), (others[2], table(3), 7, 1)]
    slots = [(-1, 0, -1), (1, 1, -1), (2, 2, -1), (3, 3, -1)]
    lg, cache, _ = forward(params, cfg, rows_batch(rows, 64, slots=slots), cache)
    for i, w in enumerate((want[18], wants[0][23], wants[1][20], wants[2][7])):
        assert close(lg[i], w) < LOGIT_TOL, i
    rows = [(toks, table(0), 19, 5), (others[0], table(1), 24, 1),
            (others[1], table(2), 21, 3), (others[2], table(3), 8, 2)]
    lg, cache, _ = forward(
        params, cfg, rows_batch(rows, 16, slots=[(i, i, -1) for i in range(4)]), cache)
    for i, w in enumerate((want[23], wants[0][24], wants[1][23], wants[2][9])):
        assert close(lg[i], w) < LOGIT_TOL, i


@pytest.mark.parametrize("control", [dict(state=jnp.bfloat16), dict(inner_norms=False)],
                         ids=["state-bf16", "no-inner-norms"])
def test_the_controls_move_the_logits_past_the_limit(model, control):
    """The limit is tight enough: bfloat16 where float32 is stated fails it,
    and so does the mixer without Jamba's three inner norms."""
    cfg, params, toks, want = model
    moved = np.asarray(ref.forward(params, HF, toks, **control))
    assert close(moved[-1], want[-1]) > 5 * LOGIT_TOL


# ------------------------------------------------------ (c) snapshots, bits
def test_a_chunk_resumed_from_a_snapshot_equals_the_cold_chunk_to_the_bit(model, scan_block):
    """The cold run leaves a snapshot at 32 (a copy of its live slot's state
    and tail, in slot 6); another row, in another live slot, alone and in a
    step it shares with a stranger, resumes from it behind the shared K/V
    pages: the state it leaves is the cold run's, bit for bit, and so are the
    logits where the step is its own."""
    cfg, params, toks, want = model
    cache = new_cache(cfg)
    step = lambda rb, ca: forward(params, cfg, rb, ca)[:2]  # noqa: E731
    _, cache = step(rows_batch([(toks, table(0), 0, 16)], 16, slots=[(-1, 0, -1)]), cache)
    _, cache = step(rows_batch([(toks, table(0), 16, 16)], 16, slots=[(0, 0, 6)]), cache)
    assert np.array_equal(cache.ssm[:, 6], cache.ssm[:, 0])
    assert np.array_equal(cache.tail[:, :, 6], cache.tail[:, :, 0])
    assert float(jnp.abs(cache.ssm[:, 6]).max()) > 0
    cold, cache = step(rows_batch([(toks, table(0), 32, 9)], 16, slots=[(0, 0, -1)]), cache)
    assert close(cold[0], want[40]) < LOGIT_TOL
    other = np.random.RandomState(9).randint(0, 128, size=N).astype(np.int32)
    alone, c2 = step(rows_batch([(toks, table(0), 32, 9)], 16, slots=[(6, 2, -1)]), cache)
    assert np.array_equal(alone[0], cold[0])
    assert np.array_equal(c2.ssm[:, 2], cache.ssm[:, 0]) and np.array_equal(
        c2.tail[:, :, 2], cache.tail[:, :, 0])
    rows = [(other, table(1), 0, 5), (toks, table(0), 32, 9)]
    shared, c3 = step(rows_batch(rows, 16, slots=[(-1, 1, -1), (6, 2, -1)]), cache)
    assert np.array_equal(c3.ssm[:2, 2], cache.ssm[:2, 0])  # the Mamba-1 layers before attention
    assert close(shared[1], np.asarray(cold[0])) < 1e-6
    # the control of chip_smoke's parity child: the state dropped at the boundary
    dropped, _, _ = forward(
        params, cfg, rows_batch([(toks, table(0), 32, 9)], 16, slots=[(6, 2, -1)]), cache,
        drop_state_at_stride=16)
    assert close(dropped[0], want[40]) > 100 * LOGIT_TOL


def test_a_fused_chunk_of_four_steps_equals_four_single_steps(model):
    cfg, params, toks, want = model
    cache = run_chunks(params, cfg, new_cache(cfg), toks, table(0), [0, 30], want)
    single = cache
    for t in range(30, 34):
        lg, single, _ = forward(
            params, cfg, rows_batch([(toks, table(0), t, 1)], S, decode=True), single, decode=True)
        assert close(lg[0], want[t]) < LOGIT_TOL

    def body(ca, t):
        row0 = jnp.arange(S) == 0
        rb = rows_batch([(toks, table(0), 0, 1)], S, decode=True)
        rb = rb._replace(token_ids=jnp.where(row0, jnp.asarray(toks)[t], 0),
                         positions=jnp.where(row0, t, 0),
                         slot_mapping=jnp.where(row0, jnp.asarray(table(0))[t // PS] * PS + t % PS, -1),
                         kv_lens=jnp.where(row0, t + 1, 0))
        lg, ca, _ = lfm2.forward_ragged(params, cfg, rb, ca, decode=True)
        return ca, lg[0]

    fused, lgs = jax.jit(lambda ca: jax.lax.scan(body, ca, jnp.arange(30, 34)))(cache)
    assert close(lgs[-1], want[33]) < LOGIT_TOL
    assert close(fused.ssm[:, 0], np.asarray(single.ssm[:, 0])) < 1e-6
    assert np.array_equal(fused.ssm[:, 1:], cache.ssm[:, 1:])  # idle rows' slots untouched
    assert np.array_equal(fused.tail[:, :, 1:], cache.tail[:, :, 1:])


# --------------------------- (d) one K/V head under its queries, both impls
@pytest.mark.parametrize("impl", ["xla", "tpu"])
def test_one_kv_head_under_twenty_queries(impl):
    """The published head geometry in small widths: 20 query heads over ONE
    K/V head of 128 (pages [.., 2, 128], G = 20), through the XLA gather path
    and through both Pallas attention kernels (interpreted), a prompt chunk
    behind a cached prefix and then decode, against the reference."""
    hf = dict(HF, hidden_size=128, num_attention_heads=20, head_dim=128, intermediate_size=64,
              num_hidden_layers=2, attn_layer_offset=1, attn_layer_period=2, mamba_expand=1)
    cfg = ModelConfig.from_hf_config(hf, name="jamba-20q").with_overrides(dtype="float32")
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (20, 1, 128)
    params = lfm2.init_params(cfg, jax.random.PRNGKey(4))
    toks = np.random.RandomState(4).randint(0, 128, size=40).astype(np.int32)
    want = np.asarray(ref.forward(params, hf, toks))
    cache = lfm2.HybridCache.create(cfg, NPAGES, PS, dtype=jnp.float32, state_slots=SLOTS)
    assert cache.pages.shape == (1, NPAGES, PS, 2, 128)
    kw = dict(attn_impl=impl)
    if impl == "tpu":
        kw.update(decode_kernel="pallas_fused", prefill_kernel="pallas")
    steps = {d: jax.jit(lambda rb, ca, d=d: lfm2.forward_ragged(params, cfg, rb, ca, decode=d, **kw))
             for d in (False, True)}
    run = lambda rb, ca, decode=False: steps[decode](rb, ca)  # noqa: E731
    lg, cache, _ = run(rows_batch([(toks, table(0), 0, 16)], 16, slots=[(-1, 0, -1)]), cache)
    assert close(lg[0], want[15]) < LOGIT_TOL
    lg, cache, _ = run(rows_batch([(toks, table(0), 16, 21)], 32, slots=[(0, 0, -1)]), cache)
    assert close(lg[0], want[36]) < LOGIT_TOL
    for t in (37, 38):
        lg, cache, _ = run(rows_batch([(toks, table(0), t, 1)], S, decode=True), cache, decode=True)
        assert close(lg[0], want[t]) < LOGIT_TOL, t


# ------------------------------------------------------------ (e) from_hf_config
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def catalog_row():
    with open(CATALOG) as f:
        return next(r for r in map(json.loads, f) if r["name"] == "AI21-Jamba2-3B")


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="the catalog is the builder's")
def test_from_hf_config_builds_the_published_shapes_from_the_catalog_row():
    row = catalog_row()
    whole = ModelConfig.from_hf_config(row["config"], name="whole")
    assert (whole.num_layers, whole.hidden_size, whole.num_heads, whole.num_kv_heads,
            whole.head_dim, whole.vocab_size) == (28, 2560, 20, 1, 128, 65536)
    assert whole.layer_types == tuple(
        "full_attention" if l in (7, 21) else "mamba1" for l in range(28))
    assert (whole.num_experts, whole.first_k_dense_replace, whole.use_rope, whole.qk_norm,
            whole.tie_word_embeddings, whole.rms_norm_eps) == (0, 28, False, False, True, 1e-6)
    assert mamba1.dims(whole) == (5120, 16, 4, 160)
    assert lfm2.mamba1_layers(whole) == 26 and lfm2.layer_counts(whole) == (0, 2, 28, 0)
    shapes = lfm2.leaf_shapes(whole)
    assert set(shapes) == {"top", "layers", "attn", "dense", "mamba1"}  # no experts, no router
    assert shapes["mamba1"]["in_proj"] == (26, 2560, 10240)
    assert shapes["mamba1"]["x_proj"] == (26, 5120, 192) and shapes["mamba1"]["dt_proj"] == (
        26, 160, 5120)
    assert shapes["mamba1"]["A_log"] == (26, 16, 5120) and shapes["mamba1"]["conv_w"] == (26, 4, 5120)
    assert shapes["attn"]["wqkv"] == (2, 2560, 22 * 128) and shapes["attn"]["wo"] == (2, 2560, 2560)
    assert shapes["dense"]["w_gate"] == (28, 2560, 8192) and "lm_head" not in shapes["top"]
    # ISSUE 56's arithmetic: a Mamba-1 mixer 41,241,792, an attention mixer 13,762,560,
    # the whole model 3,029,337,472 parameters.
    count = lambda g: sum(int(np.prod(s)) for s in shapes[g].values())  # noqa: E731
    assert count("mamba1") // 26 == 41_241_792 and count("attn") // 2 == 13_762_560
    assert sum(count(g) for g in shapes) == 3_029_337_472


def test_the_benchmarks_file_is_the_catalog_row_uncut():
    with open(os.path.join(ROOT, "chipbench/configs/jamba2-3b.json")) as f:
        body = json.load(f)
    assert body["reduced"] == [] and body["chips"] == 1 and body["name"] == "jamba2-3b"
    if os.path.exists(CATALOG):
        row = catalog_row()
        assert body["source"] == row["source_url"]
        for key, value in row["config"].items():  # every published key, as published
            assert key in body and body[key] == value, key
    cfg = ModelConfig.from_hf_config(body, name="uncut")
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.vocab_size) == (
        28, 2560, 20, 1, 65536)
    fam = family_of(cfg)
    assert fam.name == "hybrid" and fam.beside is not None and fam.count_dispatch is not None
    cache = jax.eval_shape(lambda: lfm2.HybridCache.create(cfg, 32768, 16, dtype=jnp.bfloat16,
                                                           state_slots=236))
    assert cache.pages.shape == (2, 32768, 16, 2, 128) and cache.conv is None and cache.window is None
    assert cache.ssm.shape == (26, 236, 16, 5120) and cache.ssm.dtype == jnp.float32
    assert cache.tail.shape == (26, 3, 236, 5120) and cache.tail.dtype == jnp.bfloat16
    assert (cache.ssm.size * 4 + cache.tail.size * 2) // 236 == 9_318_400
    assert cache.pages.size * 2 == 536_870_912
    assert lfm2.snapshot_slots(32768, 16, 512) == 204
    serve = body["serve"]
    assert "weight_quant" not in serve
    assert (serve["dtype"], serve["kv_cache_dtype"], serve["block_size"], serve["num_blocks"],
            serve["max_model_len"], serve["max_batch"], serve["prefill_chunk"],
            serve["decode_steps"]) == ("bfloat16", "bfloat16", 16, 32768, 4096, 32, 512, 4)
    small = ModelConfig.from_hf_config(body["rehearsal"]["model"], name="rehearsal")
    assert small.layer_types == ("mamba1", "mamba1", "full_attention", "mamba1")


@pytest.mark.parametrize("bad,match", [
    (dict(num_experts=16), "num_experts"),
    (dict(mamba_proj_bias=True), "mamba_proj_bias"),
    (dict(mamba_conv_bias=False), "mamba_conv_bias"),
    (dict(sliding_window=4096), "sliding_window"),
    (dict(hidden_act="gelu"), "hidden_act"),
    (dict(attn_layer_offset=4), "attn_layer_offset"),
])
def test_what_the_configuration_cannot_mean_is_refused_by_name(bad, match):
    with pytest.raises(ValueError, match=match):
        ModelConfig.from_hf_config(dict(HF, **bad), name="bad")


def test_dt_rank_auto_is_a_sixteenth_of_the_hidden_size():
    cfg = ModelConfig.from_hf_config(dict(HF, mamba_dt_rank="auto", hidden_size=2560,
                                          num_attention_heads=20), name="auto")
    assert cfg.mamba_dt_rank == 160


# ------------------------------------------------------------------- engine
ENGINE = dict(block_size=4, num_blocks=64, max_batch=4, max_model_len=64, prefill_chunk=16,
              dtype="float32", decode_steps=2)


def make_engine(**kw):
    from dynamo_tpu.engine import EngineConfig
    from dynamo_tpu.engine.engine import TpuEngine

    cfg = register_config(ModelConfig.from_hf_config(HF, name="jamba-engine"))
    return TpuEngine(EngineConfig(model="jamba-engine", **dict(ENGINE, **kw)),
                     params=draw(cfg.with_overrides(dtype="float32"), 2))


@pytest.fixture(scope="module")
def engine():
    """Warmed up, as tests/test_kimi_linear.py's (the device-side join's
    program is one jitted function a PROCESS, made in two forms by a warm-up)."""
    engine = make_engine()
    engine.warmup()
    return engine


@pytest.mark.parametrize("flag,kw", [
    ("--host-cache-mb", dict(host_cache_bytes=1 << 20)),
    ("--spec-decode", dict(spec_decode={"enable": True})),
    ("--lora", dict(lora={"enable": True})),
    ("--dp", dict(dp=2)),  # (tp 2 is refused before the family is asked: one K/V head)
])
def test_unsupported_engine_options_are_refused_by_flag(flag, kw):
    with pytest.raises(ValueError, match=f"jamba.*{flag}"):
        make_engine(**kw)


def test_the_cache_is_kv_pages_and_slots_under_one_manager(engine):
    """PR 52's seam: ``SlotState`` and ``UnitPool`` as granite and kimi-linear
    have them, built from flags that exist, with other leaves in the slots."""
    assert len(jax.tree_util.tree_leaves(engine.cache)) == 3
    kind = engine.kv.beside
    assert type(kind).__name__ == "SlotState" and engine.kv.pools == [kind.live, kind.snapshots]
    assert (kind.live.first, kind.live.size, kind.snapshots.first, kind.snapshots.size) == (0, 4, 4, 3)
    assert engine.cache.pages.shape == (1, 64, 4, 2, 16)
    assert engine.cache.ssm.shape == (3, 7, 16, 128) and engine.cache.ssm.dtype == jnp.float32
    assert engine.cache.tail.shape == (3, 3, 7, 128)
    assert engine.device_summary()["cache_kinds"] == "kv:128,mamba1_slot:8192,conv_tail:1536"
    assert engine.scheduler.beside is kind and kind.stride == 16


def _requests(engine):
    from dynamo_tpu.llm.protocols import PreprocessedRequest, SamplingOptions, StopConditions
    from dynamo_tpu.runtime.engine import Context, collect

    async def gen(tokens, n, logprobs=None):
        req = PreprocessedRequest(
            token_ids=list(tokens), stop_conditions=StopConditions(max_tokens=n, ignore_eos=True),
            sampling_options=SamplingOptions(logprobs=logprobs)).to_dict()
        out = await collect(await engine.generate(Context(req)))
        if logprobs:
            return [(t, lp) for item in out
                    for t, lp in zip(item["token_ids"], item.get("log_probs") or item["token_ids"])]
        return [t for item in out for t in item["token_ids"]]

    def check(prompt, got):
        """Teacher-forced: each token is the reference's argmax at its position."""
        logits = np.asarray(ref.forward(engine.params, HF, np.asarray(list(prompt) + got, np.int32)))
        for i, tok in enumerate(got):
            assert int(np.argmax(logits[len(prompt) - 1 + i])) == tok, len(prompt) + i

    return gen, check


def test_the_engine_resumes_hits_from_snapshots_and_counts(engine):
    """Through TpuEngine's normal path (scheduler, block manager, unified step,
    fused decode chunks of 2), greedy tokens equal the reference's argmax: cold,
    and behind a hit that is longer than its last snapshot (cut back to it).
    The slots' account is ``ssm_*`` as it stands; the Mamba-1 layers' tokens
    are counted by form."""
    from dynamo_tpu.llm.metrics import sparse_model_metrics, ssm_metrics

    gen, check = _requests(engine)
    chunks = []
    build = engine._build_ragged

    def spy(items):
        chunks.extend((st, n) for s, st, n in items if st < len(s.prompt))
        return build(items)

    engine._build_ragged = spy

    async def main():
        ssm_metrics.reset()
        sparse_model_metrics.reset()
        rs = np.random.RandomState(5)
        doc = rs.randint(16, 128, 38).tolist()  # snapshots at 16 and 32; 9 whole blocks
        first = doc + rs.randint(16, 128, 3).tolist()
        check(first, await gen(first, 5))
        assert chunks == [(0, 16), (16, 16), (32, 9)]
        assert ssm_metrics.request_starts == {"zero": 1, "snapshot": 0}
        assert ssm_metrics.snapshots == {"taken": 2, "no_slot": 0, "evicted": 0}
        tokens = dict(sparse_model_metrics.mamba1_tokens)
        assert tokens["scan"] == 41 and tokens["step"] >= 4
        del chunks[:]
        second = doc + rs.randint(16, 128, 5).tolist()
        check(second, await gen(second, 6))
        assert chunks == [(32, 11)]
        assert ssm_metrics.request_starts == {"zero": 1, "snapshot": 1}
        assert ssm_metrics.hit_tokens == {"resumed": 32, "given_back": 4}
        text = sparse_model_metrics.render() + ssm_metrics.render()
        for name in ('mamba1_tokens_total{form="scan"}', 'mamba1_tokens_total{form="step"}',
                     'ssm_request_starts_total{state="snapshot"}'):
            assert f"dynamo_tpu_{name}" in text, name
        assert "kda_tokens_total" not in text and "mla_query_tokens_total" not in text
        assert engine.dispatch_summary()["model"]["mamba1_tokens"]["scan"] == 41 + 11

    try:
        asyncio.run(main())
    finally:
        engine._build_ragged = build


def test_a_hit_served_twice_gives_the_cold_runs_tokens_and_logprobs(engine):
    """The probe of the benchmark in small: the same prompt cold and behind
    its hit (a snapshot at 32, the last step the cold run's last step)."""
    gen, _ = _requests(engine)

    async def main():
        prompt = np.random.RandomState(21).randint(16, 128, 35).tolist()
        cold = await gen(prompt, 6, logprobs=3)
        assert cold == await gen(prompt, 6, logprobs=3)
        assert cold == await gen(prompt, 6, logprobs=3)

    asyncio.run(main())


def test_quantized_draw_leaves_the_mamba_blocks_out():
    """``--weight-quant int8`` quantizes the attention layers, the SwiGLUs and
    the embedding; no leaf of a Mamba-1 block (the release's card advises
    keeping them out): they stay in the activation dtype, A_log, D and dt_bias
    float32, A = 1..16 along the state index."""
    cfg = ModelConfig.from_hf_config(HF, name="jamba-q")
    params = lfm2.init_params_quantized(cfg, jax.random.PRNGKey(0))
    assert "moe" not in params and "shared" not in params
    assert params["attn"]["wqkv"].dtype == jnp.int8 and params["dense"]["w_up"].dtype == jnp.int8
    m = params["mamba1"]
    assert not any(k.endswith("_scale") for k in m)
    for name in ("in_proj", "x_proj", "dt_proj", "out_proj", "conv_w", "conv_b", "dt_norm",
                 "b_norm", "c_norm"):
        assert m[name].dtype == jnp.bfloat16, name
    for name in ("A_log", "D", "dt_bias"):
        assert m[name].dtype == jnp.float32, name
    assert np.allclose(np.exp(np.asarray(m["A_log"]))[0, :, 7], np.arange(1, 17))
    dt = np.log1p(np.exp(np.asarray(m["dt_bias"])))
    assert dt.min() >= 0.99e-3 and dt.max() <= 1.01e-1
    assert float(np.asarray(m["dt_norm"], np.float32).min()) == 1.0
    assert lfm2.quantize_params(params) is params
    flt = lfm2.dequantize_params(params)
    assert flt["attn"]["wqkv"].dtype == jnp.float32 and flt["mamba1"]["in_proj"].dtype == jnp.bfloat16


def test_the_benchmarks_copy_of_the_reference_is_the_reference():
    with open(os.path.join(ROOT, "dynamo_tpu/models/reference/jamba.py")) as a, open(
            os.path.join(ROOT, "chipbench/reference/jamba.py")) as b:
        assert a.read() == b.read()
