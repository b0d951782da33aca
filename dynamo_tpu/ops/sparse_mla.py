"""DeepSeek sparse attention (DSA) over a paged latent cache, in plain XLA.

Two page arrays share one page table: latent pages ``[NP, ps, Rkv + dr]``
(MLA's normed latent and the one rope key: K and V at once) and indexer pages
``[NP, ps, di]`` (the selector's key).  For every query token t the selector
scores the row's live positions, I[t, s] = sum_j w[t, j] relu(q^I[t, j] .
k^I[s]), keeps S_t = the min(topk, t + 1) positions of largest score (equal
scores: lowest s), and attention in absorbed form runs over S_t only.

- ``sparse_prefill_attention``: rows of MANY query tokens (prefill chunks with
  a cached past).  A device loop over (row, block of ``block_q`` queries)
  walks the row's live key blocks twice: once for the scores, then, S_t being
  a threshold on them, once more for attention under that MASK with a running
  softmax.  Work follows the row's live context, not ``max_model_len``.
- ``sparse_decode_attention``: rows of ONE query token.  Scores over the row's
  pages, ``lax.top_k`` gives S_t as positions, and attention runs over the
  GATHERED entries.

Both give exactly S_t's result.  Nothing here is a Pallas kernel yet: the ops
are XLA's (docs/tracing.md lists the names they get at the benchmark's
shapes), each stage under a ``jax.named_scope`` of ``SCOPES``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG = -1e30  # a masked score: finite, so exp() underflows to 0 without NaN
# The stages' names in a profile (jax.named_scope: an op's metadata, which a
# profile viewer shows); the Pallas kernels that replace them take the same.
SCOPES = {"scores": "dsa_index_scores", "select": "dsa_select",
          "prefill": "mla_sparse_prefill_attention", "decode": "mla_sparse_decode_attention"}


def _sortable(x: jnp.ndarray) -> jnp.ndarray:
    """float32 -> uint32 with the same order (-inf lowest; -0.0 below +0.0,
    which callers avoid by adding 0.0)."""
    b = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    key = jnp.where(b >= 0, b, b ^ jnp.int32(0x7FFFFFFF))
    return jax.lax.bitcast_convert_type(key, jnp.uint32) ^ jnp.uint32(0x80000000)


def select_mask(scores: jnp.ndarray, k: int) -> jnp.ndarray:
    """Exact top-k of each row as a mask: ``scores`` [R, N] float32 with -inf
    where a position may not be chosen.  The k-th largest value is found by a
    32-step bisection on the bits (compare and count: no sort); positions
    above it are kept, and of those EQUAL to it the lowest indices fill the
    rest.  Rows with fewer than k finite scores keep all of them."""
    valid = scores > -jnp.inf
    u = _sortable(scores)

    def bit(i, v):
        cand = v | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = jnp.sum(u >= cand[:, None], axis=-1) >= k
        return jnp.where(enough, cand, v)

    thr = jax.lax.fori_loop(0, 32, bit, jnp.zeros(scores.shape[:1], jnp.uint32))
    above = u > thr[:, None]
    equal = u == thr[:, None]
    room = k - jnp.sum(above, axis=-1, keepdims=True)
    return (above | (equal & (jnp.cumsum(equal, axis=-1) <= room))) & valid


def _index_block(qi, wi, k_idx):
    """I[q, s] of a block: qi [Q, Hi, di], wi [Q, Hi] f32, k_idx [N, di]."""
    s = jnp.einsum("qjd,sd->qjs", qi, k_idx, preferred_element_type=jnp.float32)
    # + 0.0: a sum of -0.0 terms would order below +0.0 in select_mask.
    return jnp.sum(jax.nn.relu(s) * wi[:, :, None], axis=1) + 0.0


def sparse_decode_attention(
    q_abs: jnp.ndarray,  # [S, H, Rkv + dr] absorbed queries
    qi: jnp.ndarray,  # [S, Hi, di] selector queries
    wi: jnp.ndarray,  # [S, Hi] f32 selector head weights (scales folded in)
    lat_pages: jnp.ndarray,  # [NP, ps, Rkv + dr]
    idx_pages: jnp.ndarray,  # [NP, ps, di]
    positions: jnp.ndarray,  # [S] the query's position
    kv_lens: jnp.ndarray,  # [S] live positions of the row (0 = no row)
    tables: jnp.ndarray,  # [S, PP] page ids
    *,
    topk: int,
    sm_scale: float,
    rank_v: int,  # Rkv: the leading dims of an entry that are the value
):
    """One query per row; returns ([S, H, Rkv], S_t as positions [S, k] with
    -1 where fewer than k exist)."""
    S, PP = tables.shape
    ps = lat_pages.shape[1]
    N = PP * ps
    with jax.named_scope(SCOPES["scores"]):
        k_idx = idx_pages[tables].reshape(S, N, idx_pages.shape[-1])
        s = jnp.einsum("sjd,snd->sjn", qi, k_idx, preferred_element_type=jnp.float32)
        scores = jnp.sum(jax.nn.relu(s) * wi[:, :, None], axis=1) + 0.0  # [S, N]
    with jax.named_scope(SCOPES["select"]):
        kpos = jnp.arange(N, dtype=jnp.int32)[None, :]
        live = (kpos <= positions[:, None]) & (kpos < kv_lens[:, None])
        vals, sel = jax.lax.top_k(jnp.where(live, scores, -jnp.inf), min(topk, N))
        ok = vals > -jnp.inf  # [S, k]
    with jax.named_scope(SCOPES["decode"]):
        slots = jnp.take_along_axis(tables, sel // ps, axis=1) * ps + sel % ps
        lat = lat_pages.reshape(-1, lat_pages.shape[-1])[slots]  # [S, k, Rkv + dr]
        sc = jnp.einsum("shd,skd->shk", q_abs, lat, preferred_element_type=jnp.float32)
        sc = jnp.where(ok[:, None, :], sc * sm_scale, NEG)
        p = jnp.exp(sc - jnp.max(sc, axis=-1, keepdims=True)) * ok[:, None, :]
        p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
        out = jnp.einsum("shk,skc->shc", p.astype(lat.dtype), lat[..., :rank_v],
                         preferred_element_type=jnp.float32)
    return out.astype(q_abs.dtype), jnp.where(ok, sel, -1)


def sparse_prefill_attention(
    q_abs: jnp.ndarray,  # [T, H, Rkv + dr]
    qi: jnp.ndarray,  # [T, Hi, di]
    wi: jnp.ndarray,  # [T, Hi] f32
    lat_pages: jnp.ndarray,  # [NP, ps, Rkv + dr]
    idx_pages: jnp.ndarray,  # [NP, ps, di]
    positions: jnp.ndarray,  # [T]
    kv_lens: jnp.ndarray,  # [S]
    tables: jnp.ndarray,  # [S, PP]
    cu_q_lens: jnp.ndarray,  # [S + 1]
    num_seqs: jnp.ndarray,  # [1]
    *,
    topk: int,
    sm_scale: float,
    rank_v: int,
    block_q: int = 64,
    block_k: int = 1024,
    return_mask: bool = False,
):
    """Rows of more than one query token (single-token rows are left at
    zero: ``sparse_decode_attention`` serves them).  Returns [T, H, Rkv], and
    with ``return_mask`` also S_t as a mask [T, PP * ps] over the row's
    logical positions (tests and the parity run read it)."""
    T, H, Dk = q_abs.shape
    S, PP = tables.shape
    ps = lat_pages.shape[1]
    ppk = max(1, min(block_k // ps, PP))  # pages per key block
    bk = ppk * ps
    nkb_max = -(-PP // ppk)
    N = nkb_max * bk
    Bq = block_q
    tables_kb = jnp.pad(tables, ((0, 0), (0, nkb_max * ppk - PP))).reshape(S, nkb_max, ppk)
    pad = lambda a: jnp.pad(a, ((0, Bq),) + ((0, 0),) * (a.ndim - 1))
    q_abs_p, qi_p, wi_p, pos_p = pad(q_abs), pad(qi), pad(wi), pad(positions)

    q_lens = cu_q_lens[1:] - cu_q_lens[:-1]
    rows = jnp.arange(S, dtype=jnp.int32)
    nblk = jnp.where((q_lens > 1) & (rows < num_seqs[0]), -(-q_lens // Bq), 0)
    ends = jnp.cumsum(nblk)
    k_sel = min(topk, N)

    def block(i, carry):
        out, masks = carry
        r = jnp.searchsorted(ends, i, side="right").astype(jnp.int32)
        t0 = cu_q_lens[r] + (i - (ends[r] - nblk[r])) * Bq
        q_ok = t0 + jnp.arange(Bq, dtype=jnp.int32) < cu_q_lens[r + 1]
        qa = jax.lax.dynamic_slice_in_dim(q_abs_p, t0, Bq)
        qib = jax.lax.dynamic_slice_in_dim(qi_p, t0, Bq)
        wib = jax.lax.dynamic_slice_in_dim(wi_p, t0, Bq)
        qpos = jax.lax.dynamic_slice_in_dim(pos_p, t0, Bq)
        kvl = kv_lens[r]
        nkb = -(-kvl // bk)
        pages_r = tables_kb[r]  # [nkb_max, ppk]

        def score_block(kb, scores):
            k_idx = idx_pages[pages_r[kb]].reshape(bk, -1)
            kpos = kb * bk + jnp.arange(bk, dtype=jnp.int32)[None, :]
            live = (kpos <= qpos[:, None]) & (kpos < kvl)
            blk = jnp.where(live, _index_block(qib, wib, k_idx), -jnp.inf)
            return jax.lax.dynamic_update_slice_in_dim(scores, blk, kb * bk, axis=1)

        with jax.named_scope(SCOPES["scores"]):
            scores = jax.lax.fori_loop(
                0, nkb, score_block, jnp.full((Bq, N), -jnp.inf, jnp.float32))
        with jax.named_scope(SCOPES["select"]):
            chosen = select_mask(scores, k_sel)  # [Bq, N]

        def attend_block(kb, st):
            m, l, acc = st
            lat = lat_pages[pages_r[kb]].reshape(bk, Dk)
            sc = jnp.einsum("qhd,sd->qhs", qa, lat, preferred_element_type=jnp.float32)
            mk = jax.lax.dynamic_slice_in_dim(chosen, kb * bk, bk, axis=1)[:, None, :]
            sc = jnp.where(mk, sc * sm_scale, NEG)
            m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
            p = jnp.exp(sc - m_new[..., None]) * mk
            alpha = jnp.exp(m - m_new)
            l = l * alpha + jnp.sum(p, axis=-1)
            pv = jnp.einsum("qhs,sc->qhc", p.astype(lat.dtype), lat[:, :rank_v],
                            preferred_element_type=jnp.float32)
            return m_new, l, acc * alpha[..., None] + pv

        with jax.named_scope(SCOPES["prefill"]):
            m0 = jnp.full((Bq, H), NEG, jnp.float32)
            _, l, acc = jax.lax.fori_loop(
                0, nkb, attend_block,
                (m0, jnp.zeros((Bq, H), jnp.float32), jnp.zeros((Bq, H, rank_v), jnp.float32)))
            res = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(out.dtype)
        old = jax.lax.dynamic_slice_in_dim(out, t0, Bq)
        out = jax.lax.dynamic_update_slice_in_dim(
            out, jnp.where(q_ok[:, None, None], res, old), t0, axis=0)
        if return_mask:
            old_m = jax.lax.dynamic_slice_in_dim(masks, t0, Bq)
            masks = jax.lax.dynamic_update_slice_in_dim(
                masks, jnp.where(q_ok[:, None], chosen, old_m), t0, axis=0)
        return out, masks

    out0 = jnp.zeros((T + Bq, H, rank_v), q_abs.dtype)
    masks0 = jnp.zeros((T + Bq, N) if return_mask else (1, 1), dtype=jnp.bool_)
    out, masks = jax.lax.fori_loop(0, ends[-1], block, (out0, masks0))
    if return_mask:
        return out[:T], masks[:T, : PP * ps]
    return out[:T]
