"""DeepSeek-V3.2-Exp (models/deepseek_v32.py, ops/sparse_mla.py) against the
plain float32 reference (models/reference/deepseek_v32.py) on seeded random
weights at a small size on the CPU, in float32 under "highest" matmuls.

Tolerances.  LOGITS 2e-5 of the largest reference logit: both sides are
float32 and differ in summation order only (absorbed against materialised
K/V, running against whole softmax, dispatch tables against a dense sum over
experts); measured 2e-7.  A wrong selection, rope pairing or gate moves
logits by 1e-2 or more at this size.  SELECTIONS are compared exactly: the
scores are float32 on both sides and the test inputs sit far from ties,
except where a tie is forced.
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
from dynamo_tpu.models.reference import deepseek_v32 as ref
from dynamo_tpu.ops import dense_mla, rope, sparse_mla

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOGIT_TOL = 2e-5

HF = {
    "model_type": "deepseek_v32", "vocab_size": 128, "hidden_size": 64, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 4, "intermediate_size": 96,
    "q_lora_rank": 32, "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "index_n_heads": 4, "index_head_dim": 16, "index_topk": 8,
    "first_k_dense_replace": 1, "n_group": 4, "topk_group": 2, "num_experts_per_tok": 2,
    "n_routed_experts": 4, "n_routed_experts_published": 16, "ep_size": 4, "ep_rank": 1,
    "n_shared_experts": 1, "moe_intermediate_size": 32, "routed_scaling_factor": 2.5,
    "norm_topk_prob": True, "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
                     "mscale_all_dim": 1, "original_max_position_embeddings": 16, "type": "yarn"},
    "max_position_embeddings": 1024,
}
PS, PP, NPAGES, S = 4, 12, 40, 4  # page size, pages a row, pages, rows


@pytest.fixture(scope="module", autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(autouse=True)
def few_enough_mappings():
    """A program XLA compiled for the CPU stays mapped while a jit cache
    holds it, and this file's eager steps compile thousands of them: a
    process past ``vm.max_map_count`` (65530) dies in whatever maps memory
    next (here: the engine's thread, with a segmentation fault).  So the
    caches are dropped after a test that leaves the process half way there."""
    yield
    if os.path.exists("/proc/self/maps"):
        with open("/proc/self/maps") as f:
            if sum(1 for _ in f) > 30000:
                jax.clear_caches()


@pytest.fixture(scope="module")
def model():
    cfg = ModelConfig.from_hf_config(HF, name="dsv32-test").with_overrides(dtype="float32")
    params = ds.init_params(cfg, jax.random.PRNGKey(0))
    toks = np.random.RandomState(0).randint(0, HF["vocab_size"], size=40).astype(np.int32)
    logits, masks = ref.forward(params, HF, toks)
    return cfg, params, toks, np.asarray(logits), [np.asarray(m) for m in masks]


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


def small_prefill_blocks(monkeypatch):
    """The prompt-chunk kernel's sizes at this file's shapes (as
    test_kimi_k2.py's): key blocks of 2 pages, query tiles of 8 tokens, 2 of
    the 4 heads a program, 16 tokens of a step resident: several of each a
    row, and rows that lie across two."""
    monkeypatch.setattr(dense_mla, "PREFILL_BLOCK_K", 8)
    monkeypatch.setattr(dense_mla, "PREFILL_BLOCK_Q", 8)
    monkeypatch.setattr(dense_mla, "PREFILL_HEADS", 2)
    monkeypatch.setattr(dense_mla, "PREFILL_STEP_TOKENS", 16)


@pytest.fixture(params=["absorbed", "decompressed"])
def form(request, monkeypatch):
    """The form a prompt program attends in.  This file's chunks have 16 or
    32 tokens, far under ``PREFILL_KERNEL_TOKENS``: ``decompressed`` lowers
    the constant to 16 so that every one of them takes the kernel."""
    if request.param == "decompressed":
        monkeypatch.setattr(sparse_mla, "PREFILL_KERNEL_TOKENS", 16)
        small_prefill_blocks(monkeypatch)
    return request.param


# ------------------------------------------------------------- (a) logits
@pytest.mark.parametrize("sealed_prefix", [False, True], ids=["cold", "sealed-prefix"])
def test_chunked_prefill_then_decode_matches_the_reference(model, sealed_prefix, form):
    """Chunks of 16 through the paged latent and indexer caches, then decode
    (the fused program's path and a one-token row riding a mixed step), with
    index_topk 8 far under the context of 40.  ``sealed-prefix``: the first
    16 tokens were computed by ANOTHER request into pages this one shares.
    In both forms of the prompt chunks' attention (``form``)."""
    cfg, params, toks, want, _ = model
    cache = ds.LatentKVCache.create(cfg, NPAGES, PS, dtype=jnp.float32)
    table = np.arange(5, 5 + PP).astype(np.int32)
    kw = dict(block_q=8, block_k=8)
    start = 0
    if sealed_prefix:
        other = table.copy()
        other[4:] = np.arange(30, 30 + PP - 4)  # shares the first 4 pages only
        _, cache, _ = ds.forward_ragged(params, cfg, batch(toks, other, 0, 16, 16), cache, **kw)
        start = 16
    else:
        lg, cache, _ = ds.forward_ragged(params, cfg, batch(toks, table, 0, 16, 16), cache, **kw)
        assert close(lg[0], want[15]) < LOGIT_TOL
        start = 16
    lg, cache, aux = ds.forward_ragged(params, cfg, batch(toks, table, start, 13, 16), cache, **kw)
    assert close(lg[0], want[28]) < LOGIT_TOL
    assert int(aux[1]) == 13 * 2  # real tokens x MoE layers; padding is not counted
    # The decode program is one in both forms: under ``decompressed`` a fused
    # step and a row riding a mixed step once each, behind the kernel's pages.
    for t in range(29, 40 if form == "absorbed" else 31):
        decode = t % 2 == 0
        lg, cache, _ = ds.forward_ragged(
            params, cfg, batch(toks, table, t, 1, S if decode else 16, decode), cache,
            decode=decode, **kw)
        assert close(lg[0], want[t]) < LOGIT_TOL, (t, decode)


# ---------------------------------------------------------- (b) selection
def test_selection_equals_the_references(model, form):
    """S_t of every layer, for t + 1 < index_topk (keeps all) and beyond, from
    a prefill chunk (mask) and from decode rows (positions)."""
    cfg, params, toks, _, masks = model
    cache = ds.LatentKVCache.create(cfg, NPAGES, PS, dtype=jnp.float32)
    table = np.arange(3, 3 + PP).astype(np.int32)
    _, cache, sels = ds.forward_ragged(params, cfg, batch(toks, table, 0, 30, 32), cache,
                                       return_selection=True, block_q=8, block_k=16)
    for l, sel in enumerate(sels):
        got = np.asarray(sel)[:30, :40]
        assert (got == masks[l][:30]).all(), l
        assert got[3].sum() == 4 and got[20].sum() == HF["index_topk"]
    _, cache, sels = ds.forward_ragged(params, cfg, batch(toks, table, 30, 1, S, True), cache,
                                       decode=True, return_selection=True)
    for l, sel in enumerate(sels):
        assert sorted(np.asarray(sel)[0].tolist()) == np.flatnonzero(masks[l][30]).tolist(), l


@pytest.mark.parametrize("k", [1, 3, 5, 16])
def test_select_mask_is_exact_top_k_with_ties_to_the_lowest_index(k):
    """Against a stable sort, on rows with a forced tie across the k-th
    place, all-equal rows, negative zeros and rows with fewer than k live."""
    inf = np.inf
    rows = np.array([
        [0.5, 0.2, 0.5, 0.9, 0.2, 0.2, -inf, 0.2, 0.1, 0.2],
        [1.0] * 10,
        [-0.0, 0.0, -1.0, 0.0, -0.0, 2.0, -inf, -inf, 0.0, -3.0],
        [-inf, -inf, 0.3, -inf, -inf, -inf, 0.3, -inf, -inf, -inf],
        [-inf] * 10,
    ], np.float32)
    got = np.asarray(sparse_mla.select_mask(jnp.asarray(rows) + 0.0, k))
    for r, row in enumerate(rows):
        order = np.argsort(-row, kind="stable")[:k]
        want = np.zeros(10, bool)
        want[order] = True
        want &= row > -inf
        assert (got[r] == want).all(), (r, got[r], want)
    # The decode path's top_k breaks ties the same way.
    vals, idx = jax.lax.top_k(jnp.asarray(rows[0]), min(k, 10))
    live = np.asarray(idx)[np.asarray(vals) > -inf]
    assert sorted(live.tolist()) == np.flatnonzero(got[0]).tolist()


def test_a_forced_tie_selects_the_lowest_positions_in_both_paths(model, form):
    """Zero selector weights make every score 0: S_t must be the first
    index_topk positions, as the reference's stable sort gives."""
    cfg, params, toks, _, _ = model
    tied = dict(params, layers=dict(params["layers"],
                                    idx_wproj=jnp.zeros_like(params["layers"]["idx_wproj"])))
    _, masks = ref.forward(tied, HF, toks[:24])
    cache = ds.LatentKVCache.create(cfg, NPAGES, PS, dtype=jnp.float32)
    table = np.arange(PP).astype(np.int32)
    _, cache, sels = ds.forward_ragged(tied, cfg, batch(toks, table, 0, 23, 32), cache,
                                       return_selection=True, block_q=8, block_k=8)
    assert (np.asarray(sels[1])[:23, :24] == np.asarray(masks[1])[:23]).all()
    assert np.flatnonzero(np.asarray(sels[1])[22]).tolist() == list(range(8))
    _, _, sels = ds.forward_ragged(tied, cfg, batch(toks, table, 23, 1, S, True), cache,
                                   decode=True, return_selection=True)
    assert sorted(np.asarray(sels[1])[0].tolist()) == list(range(8))


# ------------------------------------------------ (b') the one-query kernel
def _decode_case(case, dtype):
    """Random one-query rows over a shared page pool: (args of
    sparse_decode_attention, live rows' kv_lens).  6 rows, 12 pages of 4 a
    row, key blocks of 16 positions (4 pages), index_topk 8."""
    rs = np.random.RandomState(7)
    R, H, Dk, Rv, Hi, di, NP = 6, 4, 128, 64, 4, 16, 96
    f = lambda *shape: jnp.asarray(rs.standard_normal(shape), jnp.float32).astype(dtype)  # noqa: E731
    lat, idx = f(NP, PS, Dk), f(NP, PS, di)
    q, qi = f(R, H, Dk), f(R, Hi, di)
    wi = jnp.asarray(rs.standard_normal((R, Hi)), jnp.float32)
    kv = np.array([0, 5, 23, 48, 0, 32], np.int32)  # dead, < topk, ragged tail, full, dead, whole blocks
    tables = np.stack([rs.permutation(NP)[:PP] for _ in range(R)]).astype(np.int32)
    if case == "scattered-pages":
        tables = np.arange(NP - 1, NP - 1 - R * PP, -1, dtype=np.int32).reshape(R, PP)  # descending
        tables[:, ::2] = (tables[:, ::2] + 37) % NP  # and neighbours far apart
    if case == "tied-scores":
        wi = jnp.zeros_like(wi)  # every score 0.0: the lowest positions win
    if case == "every-row-dead":
        kv[:] = 0
    pos = np.maximum(kv - 1, 0).astype(np.int32)
    return (q, qi, wi, lat, idx, jnp.asarray(pos), jnp.asarray(kv), jnp.asarray(tables)), kv


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5), ("bfloat16", 2e-2)])
@pytest.mark.parametrize("case", ["ragged-rows", "scattered-pages", "tied-scores", "every-row-dead"])
def test_the_decode_kernel_equals_the_xla_path(case, dtype, tol, monkeypatch):
    """``fused_sparse_decode_attention`` (S_t as ``select_mask``'s mask, the
    Pallas kernel under the interpreter) against ``sparse_decode_attention``
    (``top_k``, gathered entries): the same S_t exactly, the same output to
    1e-5 of its largest value in float32.  In bfloat16 the two round p and
    the output at different places (whole softmax against running blocks):
    2e-2 of the largest value, measured 3.4e-3.  Rows: ``kv_len`` 0 (zeros out,
    nothing fetched), 5 (fewer than index_topk 8: keeps everything), 23 (no
    multiple of the 16-position key block nor of the page), 48 (every page),
    32 (whole blocks, then none)."""
    args, kv = _decode_case(case, jnp.dtype(dtype))
    kw = dict(topk=8, sm_scale=0.2, rank_v=64)
    monkeypatch.setattr(sparse_mla, "DECODE_BLOCK_K", 16)
    want, want_sel = sparse_mla.sparse_decode_attention(*args, **kw)
    got, sel = sparse_mla.fused_sparse_decode_attention(*args, return_selection=True, **kw)
    assert got.dtype == want.dtype and got.shape == want.shape
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all()
    assert (got[kv == 0] == 0).all()
    if (kv > 0).any():
        assert np.abs(got - want).max() <= tol * np.abs(want).max()
    for r, n in enumerate(kv):
        mine = sorted(x for x in np.asarray(sel[r]).tolist() if x >= 0)
        assert mine == sorted(x for x in np.asarray(want_sel[r]).tolist() if x >= 0), r
        assert len(mine) == min(8, n)
        if case == "tied-scores":
            assert mine == list(range(min(8, n)))
    none, unasked = sparse_mla.fused_sparse_decode_attention(*args, **kw)
    assert unasked is None and (np.asarray(none, np.float32) == got).all()


@pytest.mark.parametrize("block_k", [4, 16, 1024])
def test_the_decode_kernels_result_does_not_depend_on_its_key_block(block_k, monkeypatch):
    """One page a block, four, and more than a row has (the whole table in
    one block: ``DECODE_BLOCK_K`` at this size).  In float32 the block moves
    the result by rounding noise only, and that is all this test can show.
    ``DECODE_BLOCK_K`` is nonetheless pinned to 1024, and not by arithmetic
    (ops/sparse_mla.py at the constant; PERF.md section 7 (e)): in bfloat16 on
    the chip the block decides where ``p`` rounds, every size lies equally
    far from the XLA path, and the benchmark's ``probe_identical`` (served
    TEXT of one fixed sample, cold prefill against prefix hit, in which all
    but 259 ids print as one glyph) read true at 1024 and 2048 and false at
    256 and 512.  Who changes the constant draws that sample again."""
    assert sparse_mla.DECODE_BLOCK_K == 1024
    args, kv = _decode_case("ragged-rows", jnp.float32)
    kw = dict(topk=8, sm_scale=0.2, rank_v=64)
    monkeypatch.setattr(sparse_mla, "DECODE_BLOCK_K", block_k)
    want, _ = sparse_mla.sparse_decode_attention(*args, **kw)
    got, _ = sparse_mla.fused_sparse_decode_attention(*args, **kw)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() <= 1e-5 * np.abs(np.asarray(want)).max()


def test_off_the_chip_the_decode_kernel_is_interpreted_only_when_asked(monkeypatch):
    """The interpreter is ``DYN_PALLAS_INTERPRET``'s to ask for (conftest.py
    does), never inferred from the backend: without the switch the one-query
    path on the CPU raises, as the dense family's kernels do, and answers
    nothing from another path."""
    args, _ = _decode_case("ragged-rows", jnp.float32)
    kw = dict(topk=8, sm_scale=0.2, rank_v=64)
    monkeypatch.setenv("DYN_PALLAS_INTERPRET", "0")
    with pytest.raises(Exception, match="(?i)interpret|cpu|platform"):
        sparse_mla.fused_sparse_decode_attention(*args, **kw)
    monkeypatch.setenv("DYN_PALLAS_INTERPRET", "1")
    out, _ = sparse_mla.fused_sparse_decode_attention(*args, **kw)
    assert np.isfinite(np.asarray(out)).all()


def test_a_decode_row_riding_a_mixed_step_goes_through_the_kernel(model, monkeypatch, form):
    """Row A (29 tokens cached) decodes one token in the step that prefills
    8 tokens of row B: A's logits equal the reference's and equal what the
    XLA one-query path gives in the kernel's place, whichever form row B's
    chunk attends in."""
    cfg, params, toks, want, _ = model
    cache = ds.LatentKVCache.create(cfg, NPAGES, PS, dtype=jnp.float32)
    ta, tb = np.arange(2, 2 + PP).astype(np.int32), np.arange(39, 39 - PP, -1).astype(np.int32)
    kw = dict(block_q=8, block_k=8)
    _, cache, _ = ds.forward_ragged(params, cfg, batch(toks, ta, 0, 29, 32), cache, **kw)
    _, cache, _ = ds.forward_ragged(params, cfg, batch(toks, tb, 0, 12, 16), cache, **kw)
    tok, pos = np.zeros(16, np.int32), np.zeros(16, np.int32)
    slots = np.full(16, -1, np.int32)
    tok[0], pos[0], slots[0] = toks[29], 29, ta[29 // PS] * PS + 29 % PS
    p = np.arange(12, 20)
    tok[1:9], pos[1:9], slots[1:9] = toks[12:20], p, tb[p // PS] * PS + p % PS
    tables = np.zeros((S, PP), np.int32)
    tables[0], tables[1] = ta, tb
    rb = RaggedBatch(tok, pos, slots, np.array([30, 20, 0, 0], np.int32), tables,
                     np.array([0, 1, 9, 9, 9], np.int32), np.asarray([2], np.int32))
    lg, _, _ = ds.forward_ragged(params, cfg, rb, cache, **kw)
    assert close(lg[0], want[29]) < LOGIT_TOL and close(lg[1], want[19]) < LOGIT_TOL

    def xla_path(*a, return_selection=False, **k):
        o, sel = sparse_mla.sparse_decode_attention(*a, **k)
        return o, (sel if return_selection else None)

    monkeypatch.setattr(ds, "fused_sparse_decode_attention", xla_path)
    lg_xla, _, _ = ds.forward_ragged(params, cfg, rb, cache, **kw)
    assert close(lg, np.asarray(lg_xla)) < 1e-5


# -------------------------------- (b'') the prompt-chunk kernel, under S_t
PROMPT_STEPS = {  # query tokens a row | kv_len a row; index_topk 8, key blocks of 8, 16 resident tokens
    "past-longer-than-topk": ([12], [40]),  # a chunk behind 28 cached positions
    "context-shorter-than-topk": ([5], [7]),  # every position is kept
    "forced-tie": ([11], [37]),  # every score 0.0: the lowest positions win
    "two-prompt-rows-and-a-decode-row": ([9, 1, 21], [30, 17, 48]),
    "a-row-across-two-token-blocks": ([40], [47]),  # the kernel's second grid axis
    "kv-len-off-the-key-block": ([11], [37]),
    "stale-pages-beyond-kv-len": ([9, 1, 21], [30, 17, 46]),  # NaN in every page that is no row's live page
}


def _prompt_step(case, dtype):
    """A step's prompt chunks over a shared pool of latent and indexer pages:
    4 heads of 16 + 8 / 16, rank 32, latent pages of 4 in 128 lanes, 4
    selector heads of 16, 12 pages a row."""
    rs = np.random.RandomState(11)
    H, dn, dr, dv, rank, W, Hi, di, NP = 4, 16, 8, 16, 32, 128, 4, 16, 64
    f = lambda *shape: jnp.asarray(rs.standard_normal(shape), jnp.float32).astype(dtype)  # noqa: E731
    lat, idx = f(NP, PS, W).at[:, :, rank + dr:].set(0), f(NP, PS, di)
    w_uk, w_uv = f(H, rank, dn) * 0.3, f(H, rank, dv) * 0.3
    q_lens, kv = PROMPT_STEPS[case]
    n = len(q_lens)
    T = -(-sum(q_lens) // 16) * 16
    cu = np.full(S + 1, sum(q_lens), np.int32)
    cu[: n + 1] = np.concatenate([[0], np.cumsum(q_lens)])
    kv_lens, pos = np.zeros(S, np.int32), np.zeros(T, np.int32)
    kv_lens[:n] = kv
    for r in range(n):
        pos[cu[r]:cu[r + 1]] = np.arange(kv[r] - q_lens[r], kv[r])
    tables = np.stack([rs.permutation(NP)[:PP] for _ in range(S)]).astype(np.int32)
    q, qi = f(T, H, dn + dr), f(T, Hi, di)
    wi = jnp.asarray(rs.standard_normal((T, Hi)), jnp.float32)
    if case == "forced-tie":
        wi = jnp.zeros_like(wi)
    if case == "stale-pages-beyond-kv-len":
        live = np.zeros(NP, bool)
        for r in range(n):
            live[tables[r, : -(-kv[r] // PS)]] = True
        lat = jnp.where(jnp.asarray(live)[:, None, None], lat, jnp.nan)
        idx = jnp.where(jnp.asarray(live)[:, None, None], idx, jnp.nan)
    rows = tuple(jnp.asarray(a) for a in (pos, kv_lens, tables, cu)) + (jnp.asarray([n], jnp.int32),)
    return (q, w_uk, w_uv, lat, idx, qi, wi) + rows


def _plain_selected_attention(q, lat, w_uk, w_uv, mask, tables, cu, n, sm_scale):
    """Float32, a whole softmax a query over the GATHERED entries of S_t in
    the decompressed form; rows of one token at zero."""
    q, lat, w_uk, w_uv = (np.asarray(a, np.float32) for a in (q, lat, w_uk, w_uv))
    (H, rank, dn), dr = w_uk.shape, q.shape[2] - w_uk.shape[2]
    out = np.zeros(q.shape[:2] + (w_uv.shape[2],), np.float32)
    for r in range(n):
        if cu[r + 1] - cu[r] <= 1:
            continue
        ctx = lat[tables[r]].reshape(-1, lat.shape[-1])
        for t in range(cu[r], cu[r + 1]):
            kept = ctx[np.flatnonzero(mask[t])]
            c, k_rope = kept[:, :rank], kept[:, rank:rank + dr]
            k = np.concatenate([np.einsum("sc,hcn->hsn", c, w_uk),
                                np.broadcast_to(k_rope, (H,) + k_rope.shape)], axis=-1)
            sc = np.einsum("hd,hsd->hs", q[t], k) * sm_scale
            p = np.exp(sc - sc.max(-1, keepdims=True))
            out[t] = np.einsum("hs,hsv->hv", p / p.sum(-1, keepdims=True),
                               np.einsum("sc,hcv->hsv", c, w_uv))
    return out


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5), ("bfloat16", 2e-2)])
@pytest.mark.parametrize("case", list(PROMPT_STEPS))
def test_the_sparse_prefill_kernel_equals_the_xla_loop_and_plain_attention(
    case, dtype, tol, monkeypatch
):
    """``sparse_prefill_selection`` + ``latent_prefill_attention`` (S_t as a
    mask, the decompressed Pallas kernel under the interpreter) against
    ``sparse_prefill_attention`` (the absorbed XLA loop, its output taken per
    head through W^UV) and against plain float32 attention over the gathered
    S_t: the same S_t exactly, the same output to 1e-5 of its largest value
    in float32.  In bfloat16 the forms round at different places (keys and
    values rounded after decompression against the absorbed query): 2e-2.  A
    row of one token, padding tokens and rows past ``num_seqs`` stay at zero.
    The loop multiplies a weight of 0 by whatever a key block's stale pages
    hold, so under ``stale-pages`` it reads those pages as zeros; the kernel
    and the selection read them as they are, NaN."""
    small_prefill_blocks(monkeypatch)
    q, w_uk, w_uv, lat, idx, qi, wi, pos, kv_lens, tables, cu, num = _prompt_step(
        case, jnp.dtype(dtype))
    n, rank, dn = int(num[0]), 32, 16
    rows = (pos, kv_lens, tables, cu, num)
    mask = sparse_mla.sparse_prefill_selection(qi, wi, idx, *rows, topk=8, block_q=8, block_k=16)
    got = dense_mla.latent_prefill_attention(
        q, mask, lat, w_uk, w_uv, *rows[1:], sm_scale=0.2, name=sparse_mla.SCOPES["prefill"])
    assert got.dtype == q.dtype and got.shape == q.shape[:2] + (16,)
    got, mask = np.asarray(got, np.float32), np.asarray(mask)
    assert np.isfinite(got).all()

    q_abs = jnp.concatenate([jnp.einsum("thn,hcn->thc", q[..., :dn], w_uk), q[..., dn:],
                             jnp.zeros(q.shape[:2] + (lat.shape[-1] - rank - 8,), q.dtype)], axis=-1)
    o_lat, loop_mask = sparse_mla.sparse_prefill_attention(
        q_abs, qi, wi, jnp.nan_to_num(lat), jnp.nan_to_num(idx), *rows, topk=8, sm_scale=0.2,
        rank_v=rank, block_q=8, block_k=8, return_mask=True)
    assert (mask == np.asarray(loop_mask)).all()
    loop = np.asarray(jnp.einsum("thc,hcv->thv", o_lat, w_uv), np.float32)
    plain = _plain_selected_attention(q, jnp.nan_to_num(lat), w_uk, w_uv, mask,
                                      np.asarray(tables), np.asarray(cu), n, 0.2)
    assert np.abs(got - plain).max() <= tol * np.abs(plain).max()
    assert np.abs(got - loop).max() <= tol * np.abs(loop).max()

    served = np.zeros(q.shape[0], bool)
    for r in range(n):
        t0, t1 = int(cu[r]), int(cu[r + 1])
        served[t0:t1] = t1 - t0 > 1
        for t in range(t0, t1 if t1 - t0 > 1 else t0):
            kept = np.flatnonzero(mask[t])
            assert len(kept) == min(8, int(pos[t]) + 1) and kept.max() <= int(pos[t])
            if case == "forced-tie":
                assert kept.tolist() == list(range(len(kept)))
    assert not mask[~served].any()
    assert (got[~served] == 0).all() and np.abs(got[served]).min(axis=(1, 2)).min() > 0


def test_a_prompt_programs_token_count_decides_its_form_and_the_counter_follows(
    model, engine, monkeypatch
):
    """One rule, ``prefill_form``: a program below ``PREFILL_KERNEL_TOKENS``
    attends in the absorbed XLA loop, one at or above it in the decompressed
    kernel; the model traces by it and the dispatch counter counts the
    prompt-chunk rows' tokens by it (a row of one token is the one-query
    kernel's and is counted under neither)."""
    from dynamo_tpu.llm.metrics import sparse_model_metrics

    K = sparse_mla.PREFILL_KERNEL_TOKENS
    assert 16 < K <= 512
    assert [sparse_mla.prefill_form(t) for t in (16, K // 2, K, 512, 1024)] == [
        "absorbed", "absorbed", "decompressed", "decompressed", "decompressed"]

    cfg, params, toks, want, _ = model
    calls = []
    kernel, loop = ds.latent_prefill_attention, ds.sparse_prefill_attention
    monkeypatch.setattr(ds, "latent_prefill_attention",
                        lambda q, *a, **k: calls.append(("decompressed", q.shape[0])) or kernel(q, *a, **k))
    monkeypatch.setattr(ds, "sparse_prefill_attention",
                        lambda q, *a, **k: calls.append(("absorbed", q.shape[0])) or loop(q, *a, **k))
    monkeypatch.setattr(sparse_mla, "PREFILL_KERNEL_TOKENS", 32)
    table = np.arange(5, 5 + PP).astype(np.int32)
    cache = ds.LatentKVCache.create(cfg, NPAGES, PS, dtype=jnp.float32)
    _, cache, _ = ds.forward_ragged(params, cfg, batch(toks, table, 0, 16, 16), cache,
                                    block_q=8, block_k=8)
    lg, cache, _ = ds.forward_ragged(params, cfg, batch(toks, table, 16, 20, 32), cache,
                                     block_q=8, block_k=8)
    assert close(lg[0], want[35]) < LOGIT_TOL
    # Traced twice a program: the unrolled dense layer and the scan's body.
    assert calls == [("absorbed", 16)] * 2 + [("decompressed", 32)] * 2
    ds.forward_ragged(params, cfg, batch(toks, table, 36, 1, S, True), cache, decode=True)
    assert len(calls) == 4  # a decode program has neither

    sparse_model_metrics.reset()
    engine._count_dispatch("unified", [0, 30, 7], [12, 1, 3], 16)
    engine._count_dispatch("unified", [24, 9], [20, 1], 32)
    engine._count_dispatch("decode", [40, 41], [2, 2])
    assert sparse_model_metrics.dsa_prefill_tokens == {"absorbed": 15, "decompressed": 20}
    assert sparse_model_metrics.summary()["dsa_prefill_tokens"] == {"absorbed": 15, "decompressed": 20}
    text = sparse_model_metrics.render()
    assert 'dynamo_tpu_dsa_prefill_query_tokens_total{form="absorbed"} 15' in text
    assert 'dynamo_tpu_dsa_prefill_query_tokens_total{form="decompressed"} 20' in text
    sparse_model_metrics.reset()


# ---------------------------------------------------------------- (c) gate
def test_gate_group_limited_choice_bias_for_choice_only_weights_scaled(model):
    cfg, params, _, _, _ = model
    lp = {k: v[0] for k, v in params["moe"].items()}
    x = jax.random.normal(jax.random.PRNGKey(3), (64, 64), jnp.float32)
    # A large bias on expert 5 forces it to be chosen everywhere ...
    lp["router_bias"] = lp["router_bias"].at[5].set(10.0)
    chosen, w = ds.gate(x, lp, cfg)
    rc, rw = ref.gate(lp, HF, x)
    assert (np.sort(np.asarray(chosen), -1) == np.sort(np.asarray(rc), -1)).all()
    assert np.allclose(np.sort(np.asarray(w), -1), np.sort(np.asarray(rw), -1), atol=1e-6)
    chosen, w = np.asarray(chosen), np.asarray(w)
    assert (chosen == 5).any(axis=1).all()
    # ... but its WEIGHT is its sigmoid score, not score + bias.
    s = np.asarray(jax.nn.sigmoid(x @ lp["router"]))
    picked = np.take_along_axis(s, chosen, axis=1)
    assert np.allclose(w, 2.5 * picked / picked.sum(1, keepdims=True), atol=1e-6)
    assert np.allclose(w.sum(1), 2.5, atol=1e-5)  # normalised, then scaled
    # Group limit: the chosen experts lie in at most topk_group of the n_group groups,
    # and those are the groups with the largest sum of their two best biased scores.
    biased = s + np.asarray(lp["router_bias"])
    gs = np.sort(biased.reshape(64, 4, 4), -1)[..., -2:].sum(-1)
    best = np.argsort(-gs, axis=1)[:, :2]
    for t in range(64):
        assert set(chosen[t] // 4) <= set(best[t])


# ------------------------------------------------- (d) the share test (s. 4)
def test_all_shares_add_up_to_the_uncut_layer(model):
    """model-configs section 4: over ALL ep_size shares of the experts, the
    routed parts summed and the shared expert counted once equal the uncut
    reference's whole layer.  Tolerance 1e-5 of the largest output: float32
    sums in another order."""
    cfg, _, _, _, _ = model
    full_hf = dict(HF, n_routed_experts=16, ep_size=1, ep_rank=0)
    full_cfg = ModelConfig.from_hf_config(full_hf, name="dsv32-full").with_overrides(dtype="float32")
    full = ds.init_params(full_cfg, jax.random.PRNGKey(7))
    lp_full = {k: v[0] for k, v in full["moe"].items()}
    x = jax.random.normal(jax.random.PRNGKey(4), (48, 64), jnp.float32)
    whole = np.asarray(ref.moe(lp_full, full_hf, x, list(range(16))))
    shared = np.asarray(ref.ffn(x, lp_full["shared_gate"], lp_full["shared_up"], lp_full["shared_down"]))
    total, pairs = np.zeros_like(whole), 0
    for rank in range(4):
        share_cfg = cfg.with_overrides(ep_rank=rank)
        lp = dict(lp_full)
        for name in ("moe_gate", "moe_up", "moe_down"):
            lp[name] = lp_full[name][rank * 4:(rank + 1) * 4]
        y, here = ds.moe_block(x, lp, share_cfg)
        assert list(ds.held_experts(share_cfg)) == list(range(rank * 4, rank * 4 + 4))
        # the reference given the same share says the same
        assert close(y, np.asarray(ref.moe(lp, dict(HF, ep_rank=rank), x,
                                           ref.held_experts(dict(HF, ep_rank=rank))))) < 1e-5
        total += np.asarray(y) - shared
        pairs += int(np.asarray(here).sum())
    assert close(total + shared, whole) < 1e-5
    assert pairs == 48 * HF["num_experts_per_tok"]  # every routed pair landed on one share


# ---------------------------------------------------------------- (e) rope
def test_yarn_frequencies_and_both_pairings_by_hand():
    sc = {"type": "yarn", "factor": 40, "beta_fast": 32, "beta_slow": 1, "mscale": 1,
          "mscale_all_dim": 1, "original_max_position_embeddings": 4096}
    inv = np.asarray(rope.rope_frequencies(64, 10000.0, sc))
    base = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    # correction dims: 64 ln(4096 / (b 2 pi)) / (2 ln 10000): b=32 -> 10.47 (floor 10), b=1 -> 22.5 (ceil 23)
    assert math.floor(64 * math.log(4096 / (32 * 2 * math.pi)) / (2 * math.log(10000))) == 10
    assert math.ceil(64 * math.log(4096 / (2 * math.pi)) / (2 * math.log(10000))) == 23
    assert np.allclose(inv[:11], base[:11], rtol=1e-6)  # fast dims keep their frequency
    assert np.allclose(inv[23:], base[23:] / 40, rtol=1e-6)  # slow dims are divided by factor
    ramp = (16 - 10) / (23 - 10)  # dim 16 is blended linearly
    assert np.isclose(inv[16], base[16] / 40 * ramp + base[16] * (1 - ramp), rtol=1e-6)
    assert np.allclose(inv, np.asarray(ref.yarn_inv_freq(64, {"rope_theta": 10000.0, "rope_scaling": sc})),
                       rtol=1e-6)
    assert np.isclose(rope.yarn_mscale(sc), 0.1 * math.log(40) + 1) and np.isclose(
        ref.softmax_scale({"qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rope_scaling": sc}),
        192 ** -0.5 * 1.3688879 ** 2, rtol=1e-6)
    # Pairings, by hand: x = (1, 0, 0, 0), position 1, angles (a0, a1).
    x = jnp.asarray([[[1.0, 0.0, 0.0, 0.0]]])
    f = jnp.asarray([0.5, 0.25])
    pos = jnp.asarray([1])
    inter = np.asarray(rope.apply_rope_interleaved(x, pos, f))[0, 0]
    half = np.asarray(rope.apply_rope(x, pos, f))[0, 0]
    assert np.allclose(inter, [math.cos(0.5), math.sin(0.5), 0, 0], atol=1e-6)  # pair (x0, x1)
    assert np.allclose(half, [math.cos(0.5), 0, math.sin(0.5), 0], atol=1e-6)  # pair (x0, x2)
    assert np.allclose(inter, np.asarray(ref.rope_interleaved(x[0], pos, f))[0], atol=1e-6)
    assert np.allclose(half, np.asarray(ref.rope_half(x[0], pos, f))[0], atol=1e-6)


# ------------------------------------------------------- (f) from_hf_config
def test_from_hf_config_reads_the_catalog_row_and_the_cut_file():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(json.loads(l) for l in f if '"DeepSeek-V3.2-Exp"' in l)
        published = row["config"]
    else:  # the keys the catalog row holds, as this PR read them
        published = None
    with open(os.path.join(ROOT, "chipbench/configs/deepseek-v3.2-exp-6l-ep16.json")) as f:
        body = json.load(f)
    if published is not None:
        full = ModelConfig.from_hf_config(published, name="full")
        assert (full.model_type, full.num_layers, full.num_experts, full.router_experts) == (
            "deepseek_v32", 61, 256, 256)
        assert (full.q_lora_rank, full.kv_lora_rank, full.qk_nope_head_dim, full.qk_rope_head_dim,
                full.v_head_dim, full.index_n_heads, full.index_head_dim, full.index_topk) == (
            1536, 512, 128, 64, 128, 64, 128, 2048)
        assert (full.n_group, full.topk_group, full.num_experts_per_token,
                full.routed_scaling_factor, full.first_k_dense_replace) == (8, 4, 8, 2.5, 3)
        # every published number is in the cut file unchanged, unless `reduced` names its key
        for key, value in published.items():
            if key not in body["reduced"]:
                assert body[key] == value, key
    cut = ModelConfig.from_hf_config(body, name="cut")
    assert (cut.num_layers, cut.first_k_dense_replace, cut.num_experts, cut.router_experts,
            cut.ep_size, cut.ep_rank, cut.vocab_size) == (6, 1, 16, 256, 16, 0, 16160)
    assert list(ds.held_experts(cut)) == list(range(16))
    shapes = ds.leaf_shapes(cut)
    n = sum(int(np.prod(s)) for g in shapes.values() for s in g.values())
    assert n == 5_587_117_824  # the 5.59 G parameters of the issue's arithmetic
    assert ds.latent_width(cut) == 640 and family_of(cut).name == "latent"
    with pytest.raises(ValueError, match="router's width"):
        ModelConfig.from_hf_config(dict(body, ep_size=8), name="bad")


# ------------------------------------------- (g) block-moving planes refuse
ENGINE = dict(block_size=4, num_blocks=64, max_batch=4, max_model_len=64, prefill_chunk=16,
              dtype="float32", decode_steps=2)


@pytest.fixture(scope="module")
def engine():
    from dynamo_tpu.engine import EngineConfig
    from dynamo_tpu.engine.engine import TpuEngine

    register_config(ModelConfig.from_hf_config(HF, name="dsv32-engine"))
    # Closed by the one test that runs its loop (the serving test, inside
    # that loop); the others only call methods that never start it.
    return TpuEngine(EngineConfig(model="dsv32-engine", **ENGINE))


@pytest.mark.parametrize("flag,kw", [
    ("--host-cache-mb", dict(host_cache_bytes=1 << 20)),
    ("--kv-cache-dtype", dict(cache_dtype="int8")),
    ("--tp", dict(tp=2)),
    ("--spec-decode", dict(spec_decode={"enable": True})),
    ("--lora", dict(lora={"enable": True})),
])
def test_unsupported_engine_options_are_refused_by_flag(flag, kw):
    from dynamo_tpu.engine import EngineConfig
    from dynamo_tpu.engine.engine import TpuEngine

    register_config(ModelConfig.from_hf_config(HF, name="dsv32-engine"))
    with pytest.raises(ValueError, match=flag):
        TpuEngine(EngineConfig(model="dsv32-engine", **ENGINE, **kw))


def test_a_latent_block_is_sized_from_the_cache_and_never_moved_as_k_plus_v(engine):
    from dynamo_tpu.engine.transfer import transfer_blocks_device

    L, ps = 3, ENGINE["block_size"]
    width = ds.latent_width(engine.model_config) + HF["index_head_dim"]
    assert engine.block_nbytes() == L * ps * width * 4  # float32 pages here
    assert engine.device_summary()["cache_kinds"] == "latent:512,index:64"
    assert "inject" not in engine.compile_counts()

    async def main():
        for call in (engine.export_prompt_blocks([1] * 8),
                     engine.inject_blocks([1] * 8, {}),
                     engine.inject_blocks_from_device([1] * 8, None, 1),
                     engine.freeze_sequence("nobody")):
            with pytest.raises(ValueError, match="not K-plus-V pages"):
                await call
        with pytest.raises(ValueError, match="not K-plus-V pages"):
            await transfer_blocks_device(engine, engine, [1] * 8)

    asyncio.run(main())


def test_the_engine_serves_it_with_prefix_reuse_and_counts(engine):
    """Through TpuEngine's normal path (scheduler, block manager, prefix
    cache, unified step, fused decode): greedy tokens equal the reference's
    argmax over its own continuation, a second request reuses the first one's
    sealed pages, and the selector and expert accounts grow."""
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
        params = ds.dequantize_params(engine.params) if "embed_scale" in engine.params else engine.params
        seq = list(prompt)
        for tok in got:  # teacher-forced: each token is the reference's argmax at its position
            logits, _ = ref.forward(params, HF, np.asarray(seq, np.int32))
            assert int(np.argmax(np.asarray(logits[-1]))) == tok
            seq.append(tok)
        dsa = sparse_model_metrics.dsa
        assert set(dsa) >= {"unified"} and all(0 < v[1] <= v[0] for v in dsa.values())
        assert sum(v[1] for v in dsa.values()) < sum(v[0] for v in dsa.values())  # selection is live
        assert 0 < sparse_model_metrics.moe_local_pairs <= 2 * sparse_model_metrics.moe_routed_tokens
        # an expert is read for a landed pair, and only for one
        assert 0 < sparse_model_metrics.moe_experts_read <= min(
            sparse_model_metrics.moe_local_pairs, sparse_model_metrics.moe_experts_held)
        text = sparse_model_metrics.render()
        for name in ("dsa_context_positions_total", "dsa_selected_positions_total",
                     "moe_local_pairs_total", "moe_routed_tokens_total",
                     "moe_experts_read_total", "moe_experts_held_total"):
            assert f"dynamo_tpu_{name}" in text
        await engine.close()

    asyncio.run(main())


def test_dsa_account_arithmetic(engine):
    from dynamo_tpu.llm.metrics import sparse_model_metrics

    sparse_model_metrics.reset()
    k = HF["index_topk"]  # 8
    engine._count_dispatch("unit", [0, 20, 5, -1], [4, 3, 6, 1])
    want_ctx = sum(t + 1 for t in range(4)) + sum(t + 1 for t in range(20, 23)) + sum(
        t + 1 for t in range(5, 11))
    want_sel = sum(min(k, t + 1) for t in list(range(4)) + list(range(20, 23)) + list(range(5, 11)))
    assert sparse_model_metrics.dsa["unit"] == [want_ctx, want_sel]
    sparse_model_metrics.reset()
    assert sparse_model_metrics.render() == ""


@pytest.mark.parametrize("shape", [(4,), (4, 4), (2, 3, 4)], ids=["a-step", "a-fused-chunk", "stacked"])
def test_expert_account_arithmetic(shape):
    """``add_moe`` with the wider ``aux`` (pairs, tokens, experts read,
    experts held), whatever leads its last axis; ``summary``, ``render`` and
    ``reset`` carry the two new counters beside the old."""
    from dynamo_tpu.llm.metrics import SparseModelMetrics

    m = SparseModelMetrics()
    assert m.render() == ""
    aux = np.arange(int(np.prod(shape)), dtype=np.int32).reshape(shape)
    m.add_moe(aux)
    m.add_moe(aux)
    want = 2 * aux.reshape(-1, 4).sum(axis=0)
    assert [m.moe_local_pairs, m.moe_routed_tokens, m.moe_experts_read, m.moe_experts_held] == list(want)
    summary = m.summary()
    assert (summary["moe_experts_read"], summary["moe_experts_held"]) == (want[2], want[3])
    assert (summary["moe_local_pairs"], summary["moe_routed_tokens"]) == (want[0], want[1])
    text = m.render()
    for name, v in zip(("moe_local_pairs_total", "moe_routed_tokens_total",
                        "moe_experts_read_total", "moe_experts_held_total"), want):
        assert f"\ndynamo_tpu_{name} {v}\n" in text and f"# TYPE dynamo_tpu_{name} counter" in text
    m.reset()
    assert m.render() == "" and m.summary()["moe_experts_read"] == 0 == m.moe_experts_held


def test_the_forward_counts_the_experts_read_on_the_device(model):
    """``aux`` of one step: experts read = held experts with a landed pair of
    a real token, per expert layer; held = experts x expert layers; a padding
    token's pairs read nothing."""
    cfg, params, toks, _, _ = model
    cache = ds.LatentKVCache.create(cfg, NPAGES, PS, dtype=jnp.float32)
    table = np.arange(3, 3 + PP).astype(np.int32)
    layers = cfg.num_layers - cfg.first_k_dense_replace
    reads = []
    for n_real in (16, 1, 0):
        _, _, aux = ds.forward_ragged(params, cfg, batch(toks, table, 0, n_real, 16), cache)
        pairs, tokens, read, held = (int(v) for v in np.asarray(aux))
        assert held == cfg.num_experts * layers and tokens == n_real * layers
        assert read <= min(pairs, held) and (read > 0) == (pairs > 0)
        reads.append(read)
    assert reads[0] > reads[2] == 0  # 16 tokens land pairs; 16 padding tokens read nothing


def test_quantized_draw_and_dequantize_round_trip():
    cfg = ModelConfig.from_hf_config(HF, name="q")
    q = ds.init_params_quantized(cfg, jax.random.PRNGKey(1))
    assert q["moe"]["moe_gate"].dtype == jnp.int8 and q["layers"]["w_uk"].dtype == jnp.bfloat16
    assert q["moe"]["moe_gate_scale"].shape == (2, 4, 32) and q["lm_head_scale"].shape == (128,)
    again = ds.init_params_quantized(cfg, jax.random.PRNGKey(1))
    assert (np.asarray(q["layers"]["wo"]) == np.asarray(again["layers"]["wo"])).all()
    f = ds.dequantize_params(q)
    assert f["moe"]["moe_gate"].dtype == jnp.float32 and "moe_gate_scale" not in f["moe"]
    back = ds.dequantize_params(ds.quantize_params(f))  # within half a step of each channel
    w, w2 = np.asarray(f["moe"]["moe_gate"]), np.asarray(back["moe"]["moe_gate"])
    assert np.abs(w - w2).max() <= 0.5 * np.abs(w).max() / 127 + 1e-9
    assert ds.quantize_params(q) is q


def test_the_benchmarks_copy_of_the_reference_is_the_reference():
    with open(os.path.join(ROOT, "dynamo_tpu/models/reference/deepseek_v32.py")) as a, open(
            os.path.join(ROOT, "chipbench/reference/deepseek_v32.py")) as b:
        assert a.read() == b.read()
