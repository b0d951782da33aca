"""Kimi-K2 (``model_type`` ``kimi_k2``: the latent family of
models/deepseek_v32.py WITHOUT a selector, ops/dense_mla.py) against the plain
float32 reference (models/reference/kimi_k2.py) on seeded random weights at a
small size on the CPU, in float32 under "highest" matmuls.

Tolerances.  LOGITS 2e-5 of the largest reference logit: both sides are
float32 and differ in summation order only (the absorbed one-query kernel and
the blocked, running softmax of a prompt chunk against the reference's
decompressed whole softmax; dispatch tables against a dense sum over
experts); measured 3e-7.  A wrong bound, rope pairing, scale or gate moves
logits by 1e-2 or more at this size.  test_deepseek_v32.py holds what the two
configurations share (the selector's path, the masked kernel, the gate's
group step); here is what the selector-less case adds, and the ties between
the two.
"""

import asyncio
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models import deepseek_v32 as ds
from dynamo_tpu.models.config import ModelConfig, register_config
from dynamo_tpu.models.family import RaggedBatch, family_of
from dynamo_tpu.models.reference import deepseek_v32 as ref_dsv32
from dynamo_tpu.models.reference import kimi_k2 as ref
from dynamo_tpu.ops import dense_mla, rope, sparse_mla

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOGIT_TOL = 2e-5

# 12 routed experts, 3 held (a count that is no power of two), ONE group.
HF = {
    "model_type": "kimi_k2", "vocab_size": 128, "hidden_size": 64, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 4, "intermediate_size": 96,
    "q_lora_rank": 32, "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "first_k_dense_replace": 1, "n_group": 1, "topk_group": 1,
    "num_experts_per_tok": 2, "n_routed_experts": 3, "n_routed_experts_published": 12,
    "ep_size": 4, "ep_rank": 1, "n_shared_experts": 1, "moe_intermediate_size": 32,
    "routed_scaling_factor": 2.827, "norm_topk_prob": True, "rms_norm_eps": 1e-6,
    "rope_theta": 50000,
    "rope_scaling": {"beta_fast": 1, "beta_slow": 1, "factor": 32, "mscale": 1,
                     "mscale_all_dim": 1, "original_max_position_embeddings": 16, "type": "yarn"},
    "max_position_embeddings": 1024,
}
PS, PP, NPAGES, S = 4, 12, 40, 4  # page size, pages a row, pages, rows


@pytest.fixture(scope="module", autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(autouse=True)
def small_prefill_blocks(monkeypatch):
    """The prompt-chunk kernel's sizes at this file's shapes: key blocks of 2
    pages, query tiles of 8 tokens, 2 of the 4 heads a program, 16 tokens of a
    step resident: several of each a row, and rows that lie across two."""
    monkeypatch.setattr(dense_mla, "PREFILL_BLOCK_K", 8)
    monkeypatch.setattr(dense_mla, "PREFILL_BLOCK_Q", 8)
    monkeypatch.setattr(dense_mla, "PREFILL_HEADS", 2)
    monkeypatch.setattr(dense_mla, "PREFILL_STEP_TOKENS", 16)


@pytest.fixture(scope="module")
def model():
    cfg = ModelConfig.from_hf_config(HF, name="kimi-test").with_overrides(dtype="float32")
    params = ds.init_params(cfg, jax.random.PRNGKey(0))
    toks = np.random.RandomState(0).randint(0, HF["vocab_size"], size=40).astype(np.int32)
    return cfg, params, toks, np.asarray(ref.forward(params, HF, toks))


def batch(toks, table, start, n, width, decode=False):
    """One row's tokens [start, start + n) as a RaggedBatch of ``width``."""
    tok, pos = np.zeros(width, np.int32), np.zeros(width, np.int32)
    slots = np.full(width, -1, np.int32)
    p = np.arange(start, start + n)
    tok[:n], pos[:n] = toks[start:start + n], p
    slots[:n] = table[p // PS] * PS + p % PS
    tables = np.zeros((S, PP), np.int32)
    tables[0] = table
    kv = np.zeros(S, np.int32)
    kv[0] = start + n
    if decode:
        cu, num = np.arange(S + 1, dtype=np.int32), S
    else:
        cu, num = np.zeros(S + 1, np.int32), 1
        cu[1:] = n
    return RaggedBatch(tok, pos, slots, kv, tables, cu, np.asarray([num], np.int32))


def close(a, b):
    return float(np.max(np.abs(np.asarray(a) - b)) / np.max(np.abs(b)))


# ------------------------------------------------------------- (a) logits
@pytest.mark.parametrize("sealed_prefix", [False, True], ids=["cold", "sealed-prefix"])
def test_chunked_prefill_then_decode_matches_the_reference(model, sealed_prefix):
    """Chunks of 16 through the paged latent cache (the kernel's query tiles
    of 8 and key blocks of 8: several of each a row), then decode (the fused
    program's path and a one-token row riding a mixed step).
    ``sealed-prefix``: the first 16 tokens were computed by ANOTHER request
    into pages this one shares."""
    cfg, params, toks, want = model
    cache = ds.LatentKVCache.create(cfg, NPAGES, PS, dtype=jnp.float32)
    assert cache.index is None
    table = np.arange(5, 5 + PP).astype(np.int32)
    if sealed_prefix:
        other = table.copy()
        other[4:] = np.arange(30, 30 + PP - 4)  # shares the first 4 pages only
        _, cache, _ = ds.forward_ragged(params, cfg, batch(toks, other, 0, 16, 16), cache)
    else:
        lg, cache, _ = ds.forward_ragged(params, cfg, batch(toks, table, 0, 16, 16), cache)
        assert close(lg[0], want[15]) < LOGIT_TOL
    lg, cache, aux = ds.forward_ragged(params, cfg, batch(toks, table, 16, 13, 16), cache)
    assert close(lg[0], want[28]) < LOGIT_TOL
    assert int(aux[1]) == 13 * 2  # real tokens x MoE layers; padding is not counted
    assert cache.index is None
    for t in range(29, 40):
        decode = t % 2 == 0
        lg, cache, _ = ds.forward_ragged(
            params, cfg, batch(toks, table, t, 1, S if decode else 16, decode), cache,
            decode=decode)
        assert close(lg[0], want[t]) < LOGIT_TOL, (t, decode)


def test_two_prompt_rows_and_a_decode_row_share_a_step(model):
    """Row A (29 cached) decodes one token in the step that prefills 8 tokens
    of row B at a past of 12 and the first 5 of row C: every row's logits
    equal the reference's, whatever shares its query block."""
    cfg, params, toks, want = model
    cache = ds.LatentKVCache.create(cfg, NPAGES, PS, dtype=jnp.float32)
    ta, tb = np.arange(2, 2 + PP).astype(np.int32), np.arange(39, 39 - PP, -1).astype(np.int32)
    tc = np.arange(14, 14 + PP).astype(np.int32)
    _, cache, _ = ds.forward_ragged(params, cfg, batch(toks, ta, 0, 29, 32), cache)
    _, cache, _ = ds.forward_ragged(params, cfg, batch(toks, tb, 0, 12, 16), cache)
    tok, pos = np.zeros(16, np.int32), np.zeros(16, np.int32)
    slots = np.full(16, -1, np.int32)
    tok[0], pos[0], slots[0] = toks[29], 29, ta[29 // PS] * PS + 29 % PS
    p = np.arange(12, 20)
    tok[1:9], pos[1:9], slots[1:9] = toks[12:20], p, tb[p // PS] * PS + p % PS
    p = np.arange(0, 5)
    tok[9:14], pos[9:14], slots[9:14] = toks[0:5], p, tc[p // PS] * PS + p % PS
    tables = np.zeros((S, PP), np.int32)
    tables[0], tables[1], tables[2] = ta, tb, tc
    rb = RaggedBatch(tok, pos, slots, np.array([30, 20, 5, 0], np.int32), tables,
                     np.array([0, 1, 9, 14, 14], np.int32), np.asarray([3], np.int32))
    lg, _, _ = ds.forward_ragged(params, cfg, rb, cache)
    assert close(lg[0], want[29]) < LOGIT_TOL and close(lg[1], want[19]) < LOGIT_TOL
    assert close(lg[2], want[4]) < LOGIT_TOL


# ------------------------------------------------- (b) the share test (s. 4)
def test_four_shares_of_three_experts_add_up_to_the_uncut_layer(model):
    """model-configs section 4: over ALL ep_size shares of the experts, the
    routed parts summed and the shared expert counted once equal the uncut
    reference's whole layer.  Tolerance 1e-5 of the largest output."""
    cfg, _, _, _ = model
    full_hf = dict(HF, n_routed_experts=12, ep_size=1, ep_rank=0)
    full_cfg = ModelConfig.from_hf_config(full_hf, name="kimi-full").with_overrides(dtype="float32")
    full = ds.init_params(full_cfg, jax.random.PRNGKey(7))
    lp_full = {k: v[0] for k, v in full["moe"].items()}
    x = jax.random.normal(jax.random.PRNGKey(4), (48, 64), jnp.float32)
    whole = np.asarray(ref.moe(lp_full, full_hf, x, list(range(12))))
    shared = np.asarray(ref.ffn(x, lp_full["shared_gate"], lp_full["shared_up"], lp_full["shared_down"]))
    total, pairs = np.zeros_like(whole), 0
    for rank in range(4):
        share_cfg = cfg.with_overrides(ep_rank=rank)
        lp = dict(lp_full)
        for name in ("moe_gate", "moe_up", "moe_down"):
            lp[name] = lp_full[name][rank * 3:(rank + 1) * 3]
        y, here = ds.moe_block(x, lp, share_cfg)
        assert list(ds.held_experts(share_cfg)) == list(range(rank * 3, rank * 3 + 3))
        # the reference given the same share says the same
        share_hf = dict(HF, ep_rank=rank)
        assert close(y, np.asarray(ref.moe(lp, share_hf, x, ref.held_experts(share_hf)))) < 1e-5
        total += np.asarray(y) - shared
        pairs += int(np.asarray(here).sum())
    assert close(total + shared, whole) < 1e-5
    assert pairs == 48 * HF["num_experts_per_tok"]  # every routed pair landed on one share


def test_one_group_gate_bias_for_choice_only_weights_scaled(model):
    cfg, params, _, _ = model
    lp = {k: v[0] for k, v in params["moe"].items()}
    x = jax.random.normal(jax.random.PRNGKey(3), (64, 64), jnp.float32)
    lp["router_bias"] = lp["router_bias"].at[5].set(10.0)  # forces expert 5 everywhere
    chosen, w = ds.gate(x, lp, cfg)
    rc, rw = ref.gate(lp, HF, x)
    assert (np.sort(np.asarray(chosen), -1) == np.sort(np.asarray(rc), -1)).all()
    assert np.allclose(np.sort(np.asarray(w), -1), np.sort(np.asarray(rw), -1), atol=1e-6)
    chosen, w = np.asarray(chosen), np.asarray(w)
    assert (chosen == 5).any(axis=1).all()
    s = np.asarray(jax.nn.sigmoid(x @ lp["router"]))
    picked = np.take_along_axis(s, chosen, axis=1)  # the WEIGHT is the score without the bias
    assert np.allclose(w, 2.827 * picked / picked.sum(1, keepdims=True), atol=1e-6)
    # One group: the choice is the plain top 2 of the biased scores over all 12.
    biased = s + np.asarray(lp["router_bias"])
    assert (np.sort(chosen, -1) == np.sort(np.argsort(-biased, axis=1)[:, :2], -1)).all()


# --------------------------------------- (c) the one-query kernel, no mask
def _rows(case, dtype):
    """One-query rows over a shared page pool: 6 rows, 12 pages of 4 a row,
    128 lanes of which 64 + 8 are the entry; every page holds DATA, owned or
    not (``stale``: large values, which a kernel that read beyond a row's
    ``kv_len`` or outside its table would show)."""
    rs = np.random.RandomState(11)
    R, H, Dk, NP = 6, 4, 128, 96
    f = lambda *shape: jnp.asarray(rs.standard_normal(shape), jnp.float32).astype(dtype)  # noqa: E731
    lat, q = f(NP, PS, Dk), f(R, H, Dk)
    kv = np.array([0, 5, 23, 48, 0, 32], np.int32)  # dead, short, ragged tail, full, dead, whole blocks
    tables = np.stack([rs.permutation(NP)[:PP] for _ in range(R)]).astype(np.int32)
    if case == "stale-pages":
        owned = np.zeros((NP, PS), bool)
        for r, n in enumerate(kv):
            for pos in range(n):
                owned[tables[r, pos // PS], pos % PS] = True
        lat = jnp.where(jnp.asarray(owned)[..., None], lat, jnp.asarray(1e4, dtype))
    if case == "every-row-dead":
        kv[:] = 0
    return q, lat, jnp.asarray(kv), jnp.asarray(tables), kv


def _xla_attention(q, lat, kv, tables, sm_scale, rank_v):
    """Plain attention over each row's gathered entries, whole softmax."""
    R = q.shape[0]
    rows = lat[tables].reshape(R, -1, lat.shape[-1]).astype(jnp.float32)  # [R, PP * ps, Dk]
    sc = jnp.einsum("rhd,rnd->rhn", q.astype(jnp.float32), rows) * sm_scale
    ok = (jnp.arange(rows.shape[1])[None, :] < kv[:, None])[:, None, :]
    p = jax.nn.softmax(jnp.where(ok, sc, -jnp.inf), axis=-1)
    p = jnp.where(ok, p, 0.0)  # a dead row: softmax of all -inf is NaN
    return jnp.einsum("rhn,rnc->rhc", p, rows[..., :rank_v])


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5), ("bfloat16", 2e-2)])
@pytest.mark.parametrize("case", ["ragged-rows", "stale-pages", "every-row-dead"])
def test_the_dense_decode_kernel_equals_plain_attention(case, dtype, tol, monkeypatch):
    """``dense_decode_attention`` (the Pallas kernel under the interpreter,
    the bound ``position < kv_len`` and no mask operand) against plain XLA
    attention over the gathered row.  Rows: ``kv_len`` 0 (zeros out, nothing
    fetched), 5 (a short row), 23 (no multiple of the 16-position key block
    nor of the page), 48 (every page), 32 (whole blocks, then none)."""
    q, lat, kv_d, tables, kv = _rows(case, jnp.dtype(dtype))
    monkeypatch.setattr(sparse_mla, "DECODE_BLOCK_K", 16)
    got = dense_mla.dense_decode_attention(q, lat, kv_d, tables, sm_scale=0.2, rank_v=64)
    want = np.asarray(_xla_attention(q, lat, kv_d, tables, 0.2, 64))
    assert got.dtype == q.dtype and got.shape == (6, 4, 64)
    got = np.asarray(got, np.float32)
    assert np.isfinite(got).all() and (got[kv == 0] == 0).all()
    if (kv > 0).any():
        assert np.abs(got - want).max() <= tol * np.abs(want).max()


def test_the_dense_kernel_is_the_masked_kernel_under_the_full_mask(monkeypatch):
    """One kernel body: with S_t = every position below ``kv_len`` the masked
    call gives the dense call's result bit for bit."""
    q, lat, kv_d, tables, kv = _rows("ragged-rows", jnp.float32)
    monkeypatch.setattr(sparse_mla, "DECODE_BLOCK_K", 16)
    mask = jnp.arange(PP * PS)[None, :] < kv_d[:, None]
    a = dense_mla.dense_decode_attention(q, lat, kv_d, tables, sm_scale=0.2, rank_v=64)
    b = sparse_mla.masked_decode_attention(q, mask, lat, kv_d, tables, sm_scale=0.2, rank_v=64,
                                           block_k=16, interpret=True)
    assert (np.asarray(a) == np.asarray(b)).all()


# ------------------------------- (c') the prompt-chunk kernel, decompressed
CHUNKS = {  # query tokens a row | kv_len a row; the sizes are ``small_prefill_blocks``'s
    "past-off-the-key-block": ([11], [37]),  # a chunk after 26 cached positions: no multiple of the key block (8)
    "two-prompt-rows-and-a-decode-row": ([9, 1, 21], [30, 17, 48]),
    "shorter-than-a-query-tile": ([3], [29]),
    "cold-first-chunk": ([40], [40]),  # kv_len = its own queries: pure causal; lies across resident blocks of 16
    # The kernel's second grid axis: ONE row behind a past, its tokens in three resident blocks of 16, each of which
    # decompresses the row's key blocks for itself.
    "one-row-across-two-token-blocks": ([40], [47]),
}


def _chunk_step(case, dtype, nan_elsewhere=False):
    """A step's prompt chunks over a shared page pool: 4 heads of 16 + 8 /
    16, rank 32, pages of 4 in 128 lanes, 12 pages a row.  ``nan_elsewhere``:
    every page that is no row's LIVE page (beyond ``kv_len``, unused table
    entries, rows past ``num_seqs``) holds NaN."""
    rs = np.random.RandomState(5)
    H, dn, dr, dv, rank, W, NP = 4, 16, 8, 16, 32, 128, 64
    f = lambda *shape: jnp.asarray(rs.standard_normal(shape), jnp.float32).astype(dtype)  # noqa: E731
    lat = f(NP, PS, W).at[:, :, rank + dr:].set(0)
    w_uk, w_uv = f(H, rank, dn) * 0.3, f(H, rank, dv) * 0.3
    q_lens, kv = CHUNKS[case]
    n = len(q_lens)
    T = -(-sum(q_lens) // 16) * 16
    cu = np.full(S + 1, sum(q_lens), np.int32)
    cu[: n + 1] = np.concatenate([[0], np.cumsum(q_lens)])
    kv_lens = np.zeros(S, np.int32)
    kv_lens[:n] = kv
    tables = np.stack([rs.permutation(NP)[:PP] for _ in range(S)]).astype(np.int32)
    if nan_elsewhere:
        live = np.zeros(NP, bool)
        for r in range(n):
            live[tables[r, : -(-kv[r] // PS)]] = True
        lat = jnp.where(jnp.asarray(live)[:, None, None], lat, jnp.nan)
    return f(T, H, dn + dr), lat, w_uk, w_uv, kv_lens, tables, cu, n


def _plain_chunk_attention(q, lat, w_uk, w_uv, kv_lens, tables, cu, n, sm_scale):
    """Float32, a whole softmax a query over the row's decompressed context
    (per-head keys [W^UK c ; k^R], values W^UV c); rows of one token at zero."""
    q, lat, w_uk, w_uv = (np.asarray(a, np.float32) for a in (q, lat, w_uk, w_uv))
    (H, rank, dn), dr = w_uk.shape, q.shape[2] - w_uk.shape[2]
    out = np.zeros(q.shape[:2] + (w_uv.shape[2],), np.float32)
    for r in range(n):
        t0, nq = cu[r], cu[r + 1] - cu[r]
        if nq <= 1:
            continue
        ctx = lat[tables[r]].reshape(-1, lat.shape[-1])[: kv_lens[r]]
        c, k_rope = ctx[:, :rank], ctx[:, rank:rank + dr]
        k = np.concatenate([np.einsum("sc,hcn->hsn", c, w_uk),
                            np.broadcast_to(k_rope, (H,) + k_rope.shape)], axis=-1)
        v = np.einsum("sc,hcv->hsv", c, w_uv)
        for i in range(nq):
            upto = kv_lens[r] - nq + i + 1
            sc = np.einsum("hd,hsd->hs", q[t0 + i], k[:, :upto]) * sm_scale
            p = np.exp(sc - sc.max(-1, keepdims=True))
            out[t0 + i] = np.einsum("hs,hsv->hv", p / p.sum(-1, keepdims=True), v[:, :upto])
    return out


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5), ("bfloat16", 2e-2)])
@pytest.mark.parametrize("case", list(CHUNKS))
def test_the_dense_prefill_kernel_equals_plain_attention(case, dtype, tol):
    """``dense_prefill_attention`` (the Pallas kernel under the interpreter)
    against plain float32 attention over each row's decompressed context.  A
    row of one token (the decode row), padding tokens and rows past
    ``num_seqs`` are left at zero."""
    q, lat, w_uk, w_uv, kv_lens, tables, cu, n = _chunk_step(case, jnp.dtype(dtype))
    got = dense_mla.dense_prefill_attention(
        q, lat, w_uk, w_uv, jnp.asarray(kv_lens), jnp.asarray(tables), jnp.asarray(cu),
        jnp.asarray([n], jnp.int32), sm_scale=0.2)
    want = _plain_chunk_attention(q, lat, w_uk, w_uv, kv_lens, tables, cu, n, 0.2)
    assert got.dtype == q.dtype and got.shape == q.shape[:2] + (16,)
    got = np.asarray(got, np.float32)
    assert np.abs(got - want).max() <= tol * np.abs(want).max()
    served = np.zeros(q.shape[0], bool)
    for r in range(n):
        served[cu[r]:cu[r + 1]] = cu[r + 1] - cu[r] > 1
    assert (got[~served] == 0).all() and np.abs(got[served]).min(axis=(1, 2)).min() > 0


def test_the_dense_prefill_kernel_reads_live_pages_alone():
    """Pages beyond a row's ``kv_len``, unused table entries and the tables
    of rows past ``num_seqs`` hold NaN: nothing of them is copied, and the
    result is the plain one."""
    q, lat, w_uk, w_uv, kv_lens, tables, cu, n = _chunk_step(
        "two-prompt-rows-and-a-decode-row", jnp.float32, nan_elsewhere=True)
    assert np.isnan(np.asarray(lat)).any()
    got = np.asarray(dense_mla.dense_prefill_attention(
        q, lat, w_uk, w_uv, jnp.asarray(kv_lens), jnp.asarray(tables), jnp.asarray(cu),
        jnp.asarray([n], jnp.int32), sm_scale=0.2))
    assert np.isfinite(got).all()
    want = _plain_chunk_attention(q, jnp.nan_to_num(lat), w_uk, w_uv, kv_lens, tables, cu, n, 0.2)
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()


# ----------------------------------------------- (d) the two references tie
def test_the_selectors_reference_with_topk_over_the_context_is_this_reference(model):
    """The ``deepseek_v32`` reference with ``index_topk`` at least the
    context (S_t = every position up to t, whatever the selector scores) and
    the ``kimi_k2`` reference, written apart, agree on the same weights."""
    _, _, toks, want = model
    hf = dict(HF, model_type="deepseek_v32", index_n_heads=4, index_head_dim=16, index_topk=64)
    cfg = ModelConfig.from_hf_config(hf, name="tie").with_overrides(dtype="float32")
    params = ds.init_params(cfg, jax.random.PRNGKey(9))  # with idx_* leaves, which kimi ignores
    assert "idx_wk" in params["layers"]
    a, masks = ref_dsv32.forward(params, hf, toks)
    b = ref.forward(params, HF, toks)
    assert close(a, np.asarray(b)) < 1e-6
    assert all(np.asarray(m).sum() == 40 * 41 // 2 for m in masks)
    # and the SYSTEM with the selector idling equals the system without one
    table = np.arange(5, 5 + PP).astype(np.int32)
    lg_sel, _, _ = ds.forward_ragged(params, cfg, batch(toks, table, 0, 30, 32),
                                     ds.LatentKVCache.create(cfg, NPAGES, PS, dtype=jnp.float32),
                                     block_q=8, block_k=8)
    kcfg = ModelConfig.from_hf_config(HF, name="tie-k").with_overrides(dtype="float32")
    lg_dense, _, _ = ds.forward_ragged(params, kcfg, batch(toks, table, 0, 30, 32),
                                       ds.LatentKVCache.create(kcfg, NPAGES, PS, dtype=jnp.float32))
    assert close(lg_sel, np.asarray(lg_dense)) < LOGIT_TOL


# ---------------------------------------------------------------- (e) rope
def test_yarn_frequencies_with_equal_betas_by_hand():
    sc = HF["rope_scaling"] | {"original_max_position_embeddings": 4096}
    inv = np.asarray(rope.rope_frequencies(64, 50000.0, sc))
    base = 50000.0 ** (-np.arange(0, 64, 2) / 64)
    # both correction dims come from 64 ln(4096 / (2 pi)) / (2 ln 50000) = 19.16: floor 19, ceiling 20
    corr = 64 * math.log(4096 / (2 * math.pi)) / (2 * math.log(50000))
    assert math.floor(corr) == 19 and math.ceil(corr) == 20
    assert np.allclose(inv[:20], base[:20], rtol=1e-6)  # pairs 0-19 keep their frequency
    assert np.allclose(inv[20:], base[20:] / 32, rtol=1e-6)  # pairs 20-31 are divided by the factor
    assert np.allclose(inv, np.asarray(ref.yarn_inv_freq(64, {"rope_theta": 50000.0, "rope_scaling": sc})),
                       rtol=1e-6)
    m = 0.1 * math.log(32) + 1
    assert np.isclose(rope.yarn_mscale(sc), m) and np.isclose(
        ref.softmax_scale({"qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rope_scaling": sc}),
        192 ** -0.5 * m * m, rtol=1e-6)


# ------------------------------------------------------- (f) from_hf_config
def test_from_hf_config_reads_the_catalog_row_and_the_cut_file():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    with open(os.path.join(ROOT, "chipbench/configs/kimi-k2-6l-ep32.json")) as f:
        body = json.load(f)
    assert body["reduced"] == ["num_hidden_layers", "n_routed_experts", "ep_size", "vocab_size"]
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Kimi-K2-Instruct")
        published = row["config"]
        assert body["source"] == row["source_url"]
        full = ModelConfig.from_hf_config(published, name="full")
        assert (full.model_type, full.num_layers, full.num_experts, full.router_experts,
                full.num_heads, full.index_topk) == ("kimi_k2", 61, 384, 384, 64, 0)
        assert (full.n_group, full.topk_group, full.num_experts_per_token,
                full.routed_scaling_factor, full.first_k_dense_replace) == (1, 1, 8, 2.827, 1)
        # every published number is in the cut file unchanged, unless `reduced` names its key
        for key, value in published.items():
            if key not in body["reduced"]:
                assert body[key] == value, key
    cut = ModelConfig.from_hf_config(body, name="cut")
    assert (cut.num_layers, cut.first_k_dense_replace, cut.num_experts, cut.router_experts,
            cut.ep_size, cut.ep_rank, cut.vocab_size) == (6, 1, 12, 384, 32, 0, 20480)
    assert list(ds.held_experts(cut)) == list(range(12))
    shapes = ds.leaf_shapes(cut)
    assert not any(name.startswith("idx_") for name in shapes["layers"])
    n = sum(int(np.prod(s)) for g in shapes.values() for s in g.values())
    assert n == 4_173_177_728  # the issue's arithmetic
    assert ds.latent_width(cut) == 640
    assert family_of(cut).name == "latent" == family_of(ModelConfig.from_hf_config(
        dict(body, model_type="deepseek_v3"), name="v3")).name
    with pytest.raises(ValueError, match="router's width"):
        ModelConfig.from_hf_config(dict(body, ep_size=16), name="bad")


# ------------------------------------------------------------- the engine
ENGINE = dict(block_size=4, num_blocks=64, max_batch=4, max_model_len=64, prefill_chunk=16,
              dtype="float32", decode_steps=2)


@pytest.fixture(scope="module")
def engine():
    from dynamo_tpu.engine import EngineConfig
    from dynamo_tpu.engine.engine import TpuEngine

    register_config(ModelConfig.from_hf_config(HF, name="kimi-engine"))
    return TpuEngine(EngineConfig(model="kimi-engine", **ENGINE))


@pytest.mark.parametrize("flag,kw", [
    ("--host-cache-mb", dict(host_cache_bytes=1 << 20)),
    ("--kv-cache-dtype", dict(cache_dtype="int8")),
    ("--tp", dict(tp=2)),
])
def test_unsupported_engine_options_are_refused_by_flag(flag, kw):
    from dynamo_tpu.engine import EngineConfig
    from dynamo_tpu.engine.engine import TpuEngine

    register_config(ModelConfig.from_hf_config(HF, name="kimi-engine"))
    with pytest.raises(ValueError, match=f"kimi_k2.*{flag}"):
        TpuEngine(EngineConfig(model="kimi-engine", **ENGINE, **kw))


def test_the_cache_is_the_latent_pages_alone(engine):
    L, ps = 3, ENGINE["block_size"]
    assert engine.cache.index is None and len(jax.tree_util.tree_leaves(engine.cache)) == 1
    assert engine.block_nbytes() == L * ps * ds.latent_width(engine.model_config) * 4  # float32 here
    assert engine.device_summary()["cache_kinds"] == "latent:512"
    assert "inject" not in engine.compile_counts()
    assert not any(k.startswith("idx_") for k in engine.params["layers"])

    async def main():
        with pytest.raises(ValueError, match="not K-plus-V pages"):
            await engine.export_prompt_blocks([1] * 8)

    asyncio.run(main())


def test_the_engine_serves_it_with_prefix_reuse_and_counts(engine):
    """Through TpuEngine's normal path (scheduler, block manager, prefix
    cache, unified step, fused decode): greedy tokens equal the reference's
    argmax over its own continuation, a second request reuses the first one's
    sealed pages, the whole-context and expert accounts grow and no
    selector's account exists."""
    from dynamo_tpu.llm.metrics import sparse_model_metrics
    from dynamo_tpu.llm.protocols import PreprocessedRequest, SamplingOptions, StopConditions
    from dynamo_tpu.runtime.engine import Context, collect

    async def gen(tokens, n):
        req = PreprocessedRequest(
            token_ids=list(tokens), stop_conditions=StopConditions(max_tokens=n, ignore_eos=True),
            sampling_options=SamplingOptions()).to_dict()
        out = await collect(await engine.generate(Context(req)))
        return [t for item in out for t in item["token_ids"]]

    async def main():
        sparse_model_metrics.reset()
        rs = np.random.RandomState(5)
        doc = rs.randint(16, 128, 24).tolist()
        first = await gen(doc + rs.randint(16, 128, 5).tolist(), 4)
        hits0 = engine.kv.hit_rate
        prompt = doc + rs.randint(16, 128, 7).tolist()
        got = await gen(prompt, 6)
        assert engine.kv.hit_rate > hits0 and len(first) == 4
        seq = list(prompt)
        for tok in got:  # teacher-forced: each token is the reference's argmax at its position
            logits = ref.forward(engine.params, HF, np.asarray(seq, np.int32))
            assert int(np.argmax(np.asarray(logits[-1]))) == tok
            seq.append(tok)
        mla = sparse_model_metrics.mla
        assert not sparse_model_metrics.dsa and set(mla) >= {"unified"}
        assert all(v[0] >= v[1] > 0 for v in mla.values())
        assert 0 < sparse_model_metrics.moe_local_pairs <= 2 * sparse_model_metrics.moe_routed_tokens
        assert 0 < sparse_model_metrics.moe_experts_read <= min(
            sparse_model_metrics.moe_local_pairs, sparse_model_metrics.moe_experts_held)
        text = sparse_model_metrics.render()
        for name in ("mla_attended_positions_total", "mla_query_tokens_total",
                     "moe_local_pairs_total", "moe_routed_tokens_total",
                     "moe_experts_read_total", "moe_experts_held_total"):
            assert f"dynamo_tpu_{name}" in text
        assert "dsa_" not in text
        counts = engine.dispatch_summary()["model"]
        assert counts["mla"] == {k: list(v) for k, v in mla.items()} and counts["dsa"] == {}
        await engine.close()

    asyncio.run(main())


def test_mla_account_arithmetic(engine):
    from dynamo_tpu.llm.metrics import sparse_model_metrics

    sparse_model_metrics.reset()
    engine._count_dispatch("unit", [0, 20, 5, -1], [4, 3, 6, 1])
    tokens = list(range(4)) + list(range(20, 23)) + list(range(5, 11))
    assert sparse_model_metrics.mla["unit"] == [sum(t + 1 for t in tokens), len(tokens)]
    sparse_model_metrics.reset()
    assert sparse_model_metrics.render() == ""


def test_quantized_draw_has_no_selector_leaves():
    cfg = ModelConfig.from_hf_config(HF, name="q")
    q = ds.init_params_quantized(cfg, jax.random.PRNGKey(1))
    assert q["moe"]["moe_gate"].dtype == jnp.int8 and q["layers"]["w_uk"].dtype == jnp.bfloat16
    assert q["moe"]["moe_gate_scale"].shape == (2, 3, 32)
    assert sorted(k for k in q["layers"] if not k.endswith("_scale")) == sorted(
        ds.leaf_shapes(cfg)["layers"])
    f = ds.dequantize_params(q)
    assert f["layers"]["wo"].dtype == jnp.float32 and ds.quantize_params(q) is q


def test_the_benchmarks_copy_of_the_reference_is_the_reference():
    with open(os.path.join(ROOT, "dynamo_tpu/models/reference/kimi_k2.py")) as a, open(
            os.path.join(ROOT, "chipbench/reference/kimi_k2.py")) as b:
        assert a.read() == b.read()
