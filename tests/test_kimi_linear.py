"""Kimi-Linear-48B-A3B-Instruct (``model_type`` ``kimi_linear``: the hybrid
family of models/lfm2.py with the Kimi Delta Attention mixer of models/kda.py
and the latent family's MLA block without rotation) against the plain float32
reference (models/reference/kimi_linear.py, the recurrence one token at a
time) on seeded random weights at a small size on the CPU, in float32 under
"highest" matmuls.

Tolerances.  LOGITS and a mixer's OUTPUT 2e-5 of the largest reference value:
both sides are float32 and differ in summation order only (the delta rule
solved a chunk at a time from a slot's state against one token at a time;
paged MLA in the absorbed or the blocked decompressed form against a whole
softmax a head; dispatch tables against a loop over experts); measured 1.5e-6.
The CONTROLS of the reference show what the limit catches at this size: the
carried state rounded to bfloat16, or the decay ``g`` rounded to bfloat16,
each moves the logits by more than five times the limit.  A chunk resumed from
a snapshot against the same chunk of the cold run, through the same program,
is held to EXACT equality: a snapshot is a copy, and a row's sums do not
depend on where it lies in a step.
"""

import asyncio
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models import deepseek_v32 as latent
from dynamo_tpu.models import kda, lfm2, mamba2
from dynamo_tpu.models.config import ModelConfig, register_config
from dynamo_tpu.models.family import RaggedBatch, family_of
from dynamo_tpu.models.reference import kimi_linear as ref
from dynamo_tpu.ops import dense_mla

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOGIT_TOL = 2e-5

# One period in small (K K K M) with the leading dense layer; 12 routed
# experts of which 3 are held (a count that is no power of two), ONE group.
HF = {
    "model_type": "kimi_linear", "vocab_size": 128, "hidden_size": 64, "intermediate_size": 96,
    "num_hidden_layers": 4,
    "linear_attn_config": {"kda_layers": [1, 2, 3], "full_attn_layers": [4], "num_heads": 4,
                           "head_dim": 16, "short_conv_kernel_size": 4},
    "num_attention_heads": 4, "num_key_value_heads": 4, "kv_lora_rank": 32, "q_lora_rank": None,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16, "mla_use_nope": True,
    "first_k_dense_replace": 1, "num_experts": 3, "num_experts_published": 12, "ep_size": 4,
    "ep_rank": 1, "num_experts_per_token": 2, "num_shared_experts": 1,
    "moe_intermediate_size": 32, "moe_router_activation_func": "sigmoid", "moe_renormalize": True,
    "routed_scaling_factor": 2.446, "num_expert_group": 1, "topk_group": 1, "rms_norm_eps": 1e-5,
    "rope_scaling": None, "rope_theta": 10000, "tie_word_embeddings": False,
    "num_nextn_predict_layers": 0,
}
PS, PP, NPAGES, S, SLOTS = 4, 16, 64, 4, 8  # page size, pages a row, pages, rows, state slots
N = 48


@pytest.fixture(scope="module", autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(autouse=True)
def small_prefill_blocks(monkeypatch):
    """The latent prompt-chunk kernel's sizes at this file's shapes
    (tests/test_kimi_k2.py): several key blocks, query tiles and head groups a
    row."""
    monkeypatch.setattr(dense_mla, "PREFILL_BLOCK_K", 8)
    monkeypatch.setattr(dense_mla, "PREFILL_BLOCK_Q", 8)
    monkeypatch.setattr(dense_mla, "PREFILL_HEADS", 2)
    monkeypatch.setattr(dense_mla, "PREFILL_STEP_TOKENS", 16)


@pytest.fixture(params=[32, 8], ids=["one-chunk", "chunks-of-8"])
def kda_chunk(request, monkeypatch):
    """Rows shorter than a KDA chunk, and rows of several."""
    monkeypatch.setattr(kda, "KDA_CHUNK", request.param)


def draw(cfg, seed):
    """Seeded weights with KDA's q/k/v projection eight times the draw's
    N(0, 0.02): at a hidden size of 64 that gives the taps' input the size it
    has at 2304 (about 1), so that silu is no straight line and v has a size
    the state's read can be told from."""
    params = lfm2.init_params(cfg, jax.random.PRNGKey(seed))
    params["kda"]["wqkv"] = params["kda"]["wqkv"] * 8
    return params


@pytest.fixture(scope="module")
def model():
    cfg = ModelConfig.from_hf_config(HF, name="kimi-linear-test").with_overrides(dtype="float32")
    params = draw(cfg, 0)
    toks = np.random.RandomState(0).randint(0, HF["vocab_size"], size=N).astype(np.int32)
    return cfg, params, toks, np.asarray(ref.forward(params, HF, toks))


def rows_batch(rows, width, decode=False, slots=None, shift=0):
    """``rows``: (tokens, table, start, n) each, packed as the engine packs
    them (pipeline.py ``_build_ragged``; decode: one token a row).  ``slots``:
    (read, write, snapshot) a row, or None: row i lives in slot i.  ``shift``
    is added to every POSITION and to nothing else (the rotation's control)."""
    tok, pos = np.zeros(width, np.int32), np.zeros(width, np.int32)
    slot_map = np.full(width, -1, np.int32)
    tables, kv = np.zeros((S, PP), np.int32), np.zeros(S, np.int32)
    cu, at = np.zeros(S + 1, np.int32), 0
    for i, (toks, table, start, n) in enumerate(rows):
        p = np.arange(start, start + n)
        tok[at:at + n], pos[at:at + n] = toks[start:start + n], p + shift
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
    """``lfm2.forward_ragged`` under jit, one program a (form, chunk, shape)."""
    key = (id(params), kda.KDA_CHUNK, tuple(sorted(kw.items())))
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


def mixer_case(seed, strong=False):
    cfg = ModelConfig.from_hf_config(HF, name="kda-alone").with_overrides(dtype="float32")
    lp = {k: v[1] for k, v in draw(cfg, seed)["kda"].items()}
    if strong:
        # The trap's decay: A 16 and softplus(..) about 8 a channel, g = -128 a token.
        lp = dict(lp, A_log=jnp.full_like(lp["A_log"], np.log(16.0)),
                  dt_bias=jnp.full_like(lp["dt_bias"], 8.0))
    rs = np.random.RandomState(seed)
    xs = [jnp.asarray(rs.randn(n, 64), jnp.float32) for n in (41, 23)]
    return cfg, lp, xs, [np.asarray(ref.kda(lp, HF, x)) for x in xs]


def pools(cfg):
    cache = new_cache(cfg)
    return cache.ssm, cache.tail


def test_kda_scan_and_step_match_the_token_loop_at_ragged_rows_from_a_stored_state(kda_chunk):
    """Two sequences through ``scan`` in ragged steps: rows of unlike length
    that begin MID-SEQUENCE from the state and tail an earlier step stored in
    their slot (or in a snapshot's), then ``step`` after ``scan``, against the
    reference's loop over the whole sequences."""
    cfg, lp, (xa, xb), (wa, wb) = mixer_case(3)
    ssm, tail = pools(cfg)
    scan = jax.jit(lambda x, ssm, tail, rows: kda.scan(x, lp, cfg, ssm, tail, 1, rows))
    # step 1: a's first 13 tokens (slot 0, a snapshot in slot 5) and b's first 9 (slot 1)
    x = jnp.concatenate([xa[:13], xb[:9], jnp.zeros((10, 64))])
    y, ssm, tail = scan(x, ssm, tail, mixer_rows([(0, 13, -1, 0, 5), (13, 9, -1, 1, -1)], 32))
    assert close(y[:13], wa[:13]) < LOGIT_TOL and close(y[13:22], wb[:9]) < LOGIT_TOL
    assert np.array_equal(ssm[1, 5], ssm[1, 0]) and float(jnp.abs(ssm[1, 5]).max()) > 0
    assert float(jnp.abs(ssm[0]).max()) == 0 and float(jnp.abs(ssm[2]).max()) == 0  # layer 1 alone
    # step 2: b goes on first (14 tokens), then a from the SNAPSHOT into slot 2 (27 tokens)
    x = jnp.concatenate([xb[9:23], xa[13:40], jnp.zeros((23, 64))])
    y, ssm, tail = scan(x, ssm, tail, mixer_rows([(0, 14, 1, 1, -1), (14, 27, 5, 2, -1)], 64))
    assert close(y[:14], wb[9:23]) < LOGIT_TOL and close(y[14:41], wa[13:40]) < LOGIT_TOL
    # a row's sums do not depend on where it lies in a step nor on what shares
    # it: a's 27 tokens ALONE, from the same snapshot, leave the same bits
    alone, ssm2, tail2 = scan(jnp.concatenate([xa[13:40], jnp.zeros((37, 64))]), ssm, tail,
                              mixer_rows([(0, 27, 5, 3, -1)], 64))
    assert np.array_equal(alone[:27], y[14:41])
    assert np.array_equal(ssm2[1, 3], ssm[1, 2]) and np.array_equal(tail2[1, :, 3], tail[1, :, 2])
    # step after scan: a's last token as row 2 of the decode form (row i is slot i)
    xs = jnp.zeros((S, 64)).at[2].set(xa[40])
    before = ssm
    y, ssm, tail = jax.jit(lambda x, ssm, tail: kda.step(
        x, lp, cfg, ssm, tail, 1, jnp.arange(S) == 2))(xs, ssm, tail)
    assert close(y[2], wa[40]) < LOGIT_TOL
    assert np.array_equal(ssm[1, :2], before[1, :2]) and np.array_equal(ssm[1, 3:], before[1, 3:])


def test_a_decay_that_overflows_the_textbook_form_stays_finite_and_equal(kda_chunk):
    """The trap: with g about -128 a token the running sum G passes -1000
    inside a chunk and exp(-G_j) is no float32 (nor is exp(+89)).  Only
    exp(G_i - G_j), i >= j, is taken: finite and the reference's."""
    cfg, lp, (xa, _), (wa, _) = mixer_case(5, strong=True)
    assert np.isfinite(wa).all() and np.abs(wa).max() > 0
    ssm, tail = pools(cfg)
    x = jnp.concatenate([xa, jnp.zeros((23, 64))])
    y, ssm, _ = jax.jit(lambda x, ssm, tail, rows: kda.scan(x, lp, cfg, ssm, tail, 1, rows))(
        x, ssm, tail, mixer_rows([(0, 41, -1, 0, -1)], 64))
    assert np.isfinite(np.asarray(y)).all() and np.isfinite(np.asarray(ssm)).all()
    assert close(y[:41], wa) < LOGIT_TOL
    # ... and the textbook factoring is indeed out of range here
    g = -16.0 * np.log1p(np.exp(8.0)) * min(kda.KDA_CHUNK, 41)
    assert not np.isfinite(np.exp(np.float32(-g)))


# ------------------------------------------- (c) the model: chunks, ragged steps
@pytest.mark.parametrize("cuts", [
    [0, 16, 29],           # a row across two steps
    [0, 5, 6, 9, 29],      # across four: the taps reach over a one-token and a three-token chunk
    [0, 29],
], ids=["two-steps", "four-steps", "one-piece"])
def test_chunked_prefill_then_decode_matches_the_reference(model, kda_chunk, cuts):
    """Prompt chunks through pages and slots, then decode: the fused program's
    form (``decode=True``: row i's state in slot i) and a one-token row riding
    a ragged step, alternating."""
    cfg, params, toks, want = model
    cache = run_chunks(params, cfg, new_cache(cfg), toks, table(0), cuts, want)
    for t in range(29, N):
        decode = t % 2 == 0
        rb = rows_batch([(toks, table(0), t, 1)], S if decode else 16, decode=decode)
        lg, cache, _ = forward(params, cfg, rb, cache, decode=decode)
        assert close(lg[0], want[t]) < LOGIT_TOL, t


def test_two_prompt_rows_and_decode_rows_share_a_step(model, kda_chunk):
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
    lg, cache, aux = forward(params, cfg, rows_batch(rows, 64, slots=slots), cache)
    for i, w in enumerate((want[18], wants[0][23], wants[1][20], wants[2][7])):
        assert close(lg[i], w) < LOGIT_TOL, i
    # aux: pairs landed on the 3 held experts, tokens routed (34 x 3 expert layers),
    # held experts read, experts held
    assert int(aux[1]) == 34 * 3 and int(aux[3]) == 9 and 0 < int(aux[0]) <= 34 * 3 * 2
    rows = [(toks, table(0), 19, 5), (others[0], table(1), 24, 1),
            (others[1], table(2), 21, 3), (others[2], table(3), 8, 2)]
    lg, cache, _ = forward(
        params, cfg, rows_batch(rows, 16, slots=[(i, i, -1) for i in range(4)]), cache)
    for i, w in enumerate((want[23], wants[0][24], wants[1][23], wants[2][9])):
        assert close(lg[i], w) < LOGIT_TOL, i


def test_the_controls_move_the_logits_past_the_limit(model):
    """The limit is tight enough: bfloat16 where float32 is stated fails it,
    for the state and for the decay alike."""
    cfg, params, toks, want = model
    state16 = np.asarray(ref.forward(params, HF, toks, state=jnp.bfloat16))
    decay16 = np.asarray(ref.forward(params, HF, toks, decay=jnp.bfloat16))
    assert close(state16[-1], want[-1]) > 5 * LOGIT_TOL
    assert close(decay16[-1], want[-1]) > 5 * LOGIT_TOL


# ------------------------------------------------------ (d) snapshots, bits
def test_a_chunk_resumed_from_a_snapshot_equals_the_cold_chunk_to_the_bit(model, kda_chunk):
    """The cold run leaves a snapshot at 32 (a copy of its live slot's state
    and tails, in slot 6); another row, in another live slot and in a step it
    shares with a stranger, resumes from it behind the shared latent pages:
    the logits and the state it leaves are the cold run's, bit for bit."""
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
    # ... and in a step it shares with a stranger the KDA layers leave the
    # same bits (the mixer's own rows: the test above).  The logits are held to
    # 1e-6 there: the latent layer's XLA:CPU program is not independent of the
    # step's other rows to the last bit at every chunk size (1.5e-8 seen).
    rows = [(other, table(1), 0, 5), (toks, table(0), 32, 9)]
    shared, c3 = step(rows_batch(rows, 16, slots=[(-1, 1, -1), (6, 2, -1)]), cache)
    assert np.array_equal(c3.ssm[:, 2], cache.ssm[:, 0]) and np.array_equal(
        c3.tail[:, :, 2], cache.tail[:, :, 0])
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
    assert np.array_equal(fused.ssm[:, 1:], cache.ssm[:, 1:])  # idle rows' slots untouched


# ------------------------------------------------------------ (e) the share
def test_eight_shares_of_the_experts_add_up_to_the_uncut_layer():
    """model-configs section 4: over all ep_size shares the routed parts, and
    the shared expert counted ONCE, add up to the uncut reference's whole
    feed-forward.  Tolerance 1e-5 of the largest output."""
    full_hf = dict(HF, num_experts=16, num_experts_published=16, ep_size=1, ep_rank=0,
                   num_experts_per_token=5)
    full_cfg = ModelConfig.from_hf_config(full_hf, name="full").with_overrides(dtype="float32")
    full = lfm2.init_params(full_cfg, jax.random.PRNGKey(7))
    lp_full = {k: v[0] for g in ("moe", "shared") for k, v in full[g].items()}
    x = jax.random.normal(jax.random.PRNGKey(4), (48, 64), jnp.float32)
    want = np.asarray(ref.moe(lp_full, full_hf, x, list(range(16))))
    total = np.zeros_like(want)
    real = jnp.ones((48,), bool)
    for rank in range(8):
        hf = dict(full_hf, num_experts=2, ep_size=8, ep_rank=rank)
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


# --------------------------------------------- (f) MLA rotates nothing here
def test_moving_the_positions_leaves_the_latent_block_unchanged(model):
    """``mla_use_nope``: positions enter the latent layer through the causal
    bound alone (``kv_lens``), never through a rotation: every position moved
    by a constant, or by an UNEVEN amount (doubled), gives the same output to
    the bit.  With the rotation switched on (the latent family's default) the
    uneven move changes it; the constant one cannot tell, a rotation sees
    differences of positions only (ISSUE 53's control (f) as written; held to
    rounding here)."""
    cfg, params, toks, _ = model
    lp = {k: v[0] for k, v in params["mla"].items()}
    x = jax.random.normal(jax.random.PRNGKey(1), (16, 64), jnp.float32)
    lat = jnp.zeros((NPAGES, PS, latent.latent_width(cfg)), jnp.float32)

    def block(c, shift=0, times=1):
        rb = rows_batch([(toks, table(0), 0, 13)], 16, shift=shift)
        rb = jax.tree_util.tree_map(jnp.asarray, rb._replace(positions=rb.positions * times))
        y, _ = latent.mla_block(x, lp, c, rb, latent.mla_step(c, rb, False), 0, lat, NPAGES)
        return np.asarray(y[:13])

    assert np.array_equal(block(cfg), block(cfg, shift=1000))
    assert np.array_equal(block(cfg), block(cfg, times=2))
    rotating = cfg.with_overrides(mla_rope=True)
    assert close(block(rotating, times=2), block(rotating)) > 1e-3
    assert close(block(rotating, shift=1000), block(rotating)) < 1e-6
    assert close(block(rotating), block(cfg)) > 1e-3


# ------------------------------------------------------------ (g) from_hf_config
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def catalog_row():
    with open(CATALOG) as f:
        return next(r for r in map(json.loads, f) if r["name"] == "Kimi-Linear-48B-A3B-Instruct")


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="the catalog is the builder's")
def test_from_hf_config_builds_the_published_shapes_from_the_catalog_row():
    row = catalog_row()
    whole = ModelConfig.from_hf_config(row["config"], name="whole")
    assert (whole.num_layers, whole.num_experts, whole.router_experts, whole.ep_size) == (
        27, 256, 256, 1)
    assert whole.layer_types == tuple(
        "full_attention" if l in (4, 8, 12, 16, 20, 24, 27) else "kda" for l in range(1, 28))
    assert lfm2.kda_layers(whole) == 20 and lfm2.layer_counts(whole) == (0, 7, 1, 26)
    shapes = lfm2.leaf_shapes(whole)
    assert "attn" not in shapes
    assert shapes["kda"]["wqkv"] == (20, 2304, 12288) and shapes["kda"]["conv_w"] == (20, 4, 12288)
    assert shapes["kda"]["w_low"] == (20, 2304, 288) and shapes["kda"]["A_log"] == (20, 32)
    assert shapes["mla"]["wq"] == (7, 2304, 32 * 192) and shapes["mla"]["wkv_a"] == (7, 2304, 576)
    assert shapes["mla"]["w_uk"] == (7, 32, 512, 128) and shapes["mla"]["wo"] == (7, 4096, 2304)
    assert shapes["moe"]["moe_gate"] == (26, 256, 2304, 1024) and shapes["moe"]["router"] == (
        26, 2304, 256)
    assert shapes["shared"]["w_gate"] == (26, 2304, 1024) and shapes["dense"]["w_up"] == (1, 2304, 9216)
    # Every parameter of these leaves: 49.12e9.  The release's name says 48 B:
    # 2.3% under this count, of which the sizes the config does not give (the
    # two low-rank pairs, 32 M in all) are a thirtieth.
    total = sum(int(np.prod(s)) for g in shapes.values() for s in g.values())
    assert total == 49_122_763_648 and abs(total / 48e9 - 1) < 0.025
    # a KDA layer 39.5 M, a latent layer 29.1 M, an expert 7,077,888 (ISSUE 53's arithmetic)
    assert sum(int(np.prod(s)) for s in shapes["kda"].values()) // 20 == 39_518_368
    assert sum(int(np.prod(s)) for s in shapes["mla"].values()) // 7 == 29_114_880


def test_the_benchmarks_file_is_the_catalog_row_cut_as_it_says():
    with open(os.path.join(ROOT, "chipbench/configs/kimi-linear-48b-a3b-8l-ep8.json")) as f:
        body = json.load(f)
    assert body["reduced"] == ["num_hidden_layers", "linear_attn_config", "num_experts", "ep_size"]
    if os.path.exists(CATALOG):
        row = catalog_row()
        assert body["source"] == row["source_url"]
        for key, value in row["config"].items():  # every published key, as published or reduced
            if key not in body["reduced"]:
                assert body[key] == value, key
        lin, pub = body["linear_attn_config"], row["config"]["linear_attn_config"]
        assert {k: v for k, v in lin.items() if not k.endswith("_layers")} == {
            k: v for k, v in pub.items() if not k.endswith("_layers")}  # every width of the group
    cfg = ModelConfig.from_hf_config(body, name="cut")
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_heads, cfg.vocab_size) == (8, 2304, 32, 163840)
    assert (cfg.num_experts, cfg.router_experts, cfg.num_experts_per_token, cfg.ep_size,
            cfg.ep_rank) == (32, 256, 8, 8, 0)
    assert cfg.layer_types == ("kda", "kda", "kda", "full_attention") * 2
    assert (cfg.kv_lora_rank, cfg.q_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim, cfg.mla_rope, cfg.use_rope) == (512, 0, 128, 64, 128, False, False)
    assert (cfg.routed_scaling_factor, cfg.norm_topk_prob, cfg.n_group,
            cfg.shared_intermediate_size, cfg.first_k_dense_replace) == (2.446, True, 1, 1024, 1)
    assert kda.dims(cfg) == (32, 128, 4) and kda.conv_width(cfg) == 12288
    fam = family_of(cfg)
    assert fam.name == "hybrid" and fam.beside is not None and fam.count_dispatch is not None
    assert lfm2.layer_counts(cfg) == (0, 2, 1, 7) and lfm2.kda_layers(cfg) == 6
    shapes = lfm2.leaf_shapes(cfg)
    # The issue's count of the cut: 2.75e9 parameters, about 2.8 GB stored.
    assert sum(int(np.prod(s)) for g in shapes.values() for s in g.values()) == 2_753_177_536
    cache = jax.eval_shape(lambda: lfm2.HybridCache.create(cfg, 32768, 16, dtype=jnp.bfloat16,
                                                           state_slots=236))
    assert cache.pages.shape == (2, 32768, 16, 640) and cache.conv is None and cache.window is None
    assert cache.ssm.shape == (6, 236, 4096, 128) and cache.ssm.dtype == jnp.float32
    assert cache.tail.shape == (6, 3, 236, 12288) and cache.tail.dtype == jnp.bfloat16
    assert (cache.ssm.size * 4 + cache.tail.size * 2) // 236 == 13_025_280
    assert lfm2.snapshot_slots(32768, 16, 512) == 204
    serve = body["serve"]
    assert (serve["dtype"], serve["weight_quant"], serve["kv_cache_dtype"], serve["block_size"],
            serve["num_blocks"], serve["max_model_len"], serve["max_batch"], serve["prefill_chunk"],
            serve["decode_steps"]) == ("bfloat16", "int8", "bfloat16", 16, 32768, 4096, 32, 512, 4)
    ModelConfig.from_hf_config(body["rehearsal"]["model"], name="rehearsal")


@pytest.mark.parametrize("bad,match", [
    (dict(mla_use_nope=False), "mla_use_nope"),
    (dict(q_lora_rank=1536), "q_lora_rank"),
    (dict(num_expert_group=8), "num_expert_group"),
    (dict(num_nextn_predict_layers=1), "num_nextn_predict_layers"),
    (dict(moe_router_activation_func="softmax"), "moe_router_activation_func"),
    (dict(linear_attn_config=dict(HF["linear_attn_config"], full_attn_layers=[3, 4])),
     "linear_attn_config"),
    (dict(num_experts=4, num_experts_published=12, ep_size=2), "router's width"),
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

    cfg = register_config(ModelConfig.from_hf_config(HF, name="kimi-linear-engine"))
    return TpuEngine(EngineConfig(model="kimi-linear-engine", **dict(ENGINE, **kw)),
                     params=draw(cfg.with_overrides(dtype="float32"), 2))


@pytest.fixture(scope="module")
def engine():
    """Warmed up: the device-side join's program is one jitted function a
    PROCESS, made in two forms by a warm-up; an engine that serves unwarmed
    makes one of them, and a later test file in the same worker
    (tests/test_continuous_batching.py) counts them in pairs."""
    engine = make_engine()
    engine.warmup()
    return engine


@pytest.mark.parametrize("flag,kw", [
    ("--host-cache-mb", dict(host_cache_bytes=1 << 20)),
    ("--spec-decode", dict(spec_decode={"enable": True})),
    ("--kv-cache-dtype int8", dict(cache_dtype="int8")),
    ("--tp", dict(tp=2)),
])
def test_unsupported_engine_options_are_refused_by_flag(flag, kw):
    with pytest.raises(ValueError, match=f"kimi_linear.*{flag}"):
        make_engine(**kw)


def test_the_cache_is_latent_pages_and_slots_under_one_manager(engine):
    """PR 52's seam: ``SlotState`` and ``UnitPool`` as granite has them, built
    from flags that exist, with other leaves in the slots."""
    assert len(jax.tree_util.tree_leaves(engine.cache)) == 3
    kind = engine.kv.beside
    assert type(kind).__name__ == "SlotState" and engine.kv.pools == [kind.live, kind.snapshots]
    assert (kind.live.first, kind.live.size, kind.snapshots.first, kind.snapshots.size) == (0, 4, 4, 3)
    assert engine.cache.pages.shape == (1, 64, 4, 128)  # 32 + 8 lanes in one tile of 128
    assert engine.cache.ssm.shape == (3, 7, 64, 16) and engine.cache.ssm.dtype == jnp.float32
    assert engine.cache.tail.shape == (3, 3, 7, 192)
    assert engine.device_summary()["cache_kinds"] == "latent:512,kda_slot:4096,conv_tail:2304"
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
    The slots' account is ``ssm_*`` as it stands; the KDA layers' tokens are
    counted by form, the latent layers' queries as the latent family's."""
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
        kda_tokens = dict(sparse_model_metrics.kda_tokens)
        assert kda_tokens["scan"] == 41 and kda_tokens["step"] >= 4
        attended, queries = (sum(v[i] for v in sparse_model_metrics.mla.values()) for i in (0, 1))
        assert queries == kda_tokens["scan"] + kda_tokens["step"]  # a token's query counts once
        assert attended >= sum(range(1, 46))
        del chunks[:]
        second = doc + rs.randint(16, 128, 5).tolist()
        check(second, await gen(second, 6))
        assert chunks == [(32, 11)]
        assert ssm_metrics.request_starts == {"zero": 1, "snapshot": 1}
        assert ssm_metrics.hit_tokens == {"resumed": 32, "given_back": 4}
        text = sparse_model_metrics.render() + ssm_metrics.render()
        for name in ('kda_tokens_total{form="scan"}', 'kda_tokens_total{form="step"}',
                     'mla_query_tokens_total{kind="unified"}', 'mla_attended_positions_total{kind="decode"}',
                     'ssm_request_starts_total{state="snapshot"}', 'moe_local_pairs_total'):
            assert f"dynamo_tpu_{name}" in text, name
        assert engine.dispatch_summary()["model"]["kda_tokens"]["scan"] == 41 + 11

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

    asyncio.run(main())


def test_quantized_draw_and_its_float_tree():
    """int8 leaves with scales for the big projections; the taps, the low-rank
    pairs, the router and the norms in the activation dtype; A_log and dt_bias
    float32."""
    cfg = ModelConfig.from_hf_config(HF, name="kimi-linear-q")
    params = lfm2.init_params_quantized(cfg, jax.random.PRNGKey(0))
    k = params["kda"]
    assert k["wqkv"].dtype == jnp.int8 and k["wqkv_scale"].shape == (3, 192)
    assert k["wo"].dtype == jnp.int8 and params["mla"]["wq"].dtype == jnp.int8
    for name in ("conv_w", "w_low", "wf_b", "wg_b", "wg_bias", "norm_w"):
        assert k[name].dtype == jnp.bfloat16, name
    assert k["A_log"].dtype == jnp.float32 and k["dt_bias"].dtype == jnp.float32
    assert params["mla"]["w_uk"].dtype == jnp.bfloat16 and "wq_a" not in params["mla"]
    a = np.exp(np.asarray(k["A_log"]))
    assert a.min() >= 1 and a.max() <= 16
    dt = np.log1p(np.exp(np.asarray(k["dt_bias"])))
    assert dt.min() >= 0.99e-3 and dt.max() <= 1.01e-1
    flt = lfm2.dequantize_params(params)
    assert flt["kda"]["wqkv"].dtype == jnp.float32 and "wqkv_scale" not in flt["kda"]
    assert lfm2.quantize_params(params) is params


def test_the_benchmarks_copy_of_the_reference_is_the_reference():
    with open(os.path.join(ROOT, "dynamo_tpu/models/reference/kimi_linear.py")) as a, open(
            os.path.join(ROOT, "chipbench/reference/kimi_linear.py")) as b:
        assert a.read() == b.read()
