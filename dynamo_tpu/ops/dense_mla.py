"""Latent attention (MLA) over the WHOLE context of a row, for a model of the
latent family without a selector (``index_topk`` 0: DeepSeek-V3, Kimi-K2),
over the same paged latent cache as ops/sparse_mla.py.

Two forms of the same mathematics (docs/deepseek_v32.md has the operation
counts), chosen by how many queries share a key:

- ``dense_decode_attention``: rows of ONE query token.  ABSORBED form: the
  query carries W^UK, the cached 576-value entry is K and V at once.  The
  one-query Pallas kernel of ops/sparse_mla.py with the bound ``position <
  kv_len`` in place of S_t's mask: no scores, no ``select_mask``, no mask
  operand.  The call is named ``mla_dense_decode_attention``.
- ``dense_prefill_attention``: rows of MANY query tokens (prompt chunks with
  a cached past).  DECOMPRESSED form: a key block's entries become per-head
  keys [W^UK_h c_j ; k^R_j] and values W^UV_h c_j ONCE, and every query block
  of the row's chunk attends to them (one causal pass, a running softmax):
  2 N H (192 + 128) operations a query plus 2 N H 512 x 256 a chunk, where
  absorbed costs 2 N H (576 + 512) a query.  A device loop over (row, key
  block, query block) whose work follows ``kv_lens`` and the rows' query
  counts, not ``max_model_len``.  Plain XLA under the scope
  ``mla_dense_prefill_attention`` (a Pallas kernel would take that name).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import sparse_mla
from .ragged_attention import pallas_interpret
from .sparse_mla import NEG

SCOPES = {"prefill": "mla_dense_prefill_attention", "decode": "mla_dense_decode_attention"}


def dense_decode_attention(
    q_abs: jnp.ndarray,  # [S, H, Rkv + dr] absorbed queries
    lat_pages: jnp.ndarray,  # [NP, ps, Rkv + dr]
    kv_lens: jnp.ndarray,  # [S] live positions of the row (0 = no row)
    tables: jnp.ndarray,  # [S, PP]
    *,
    sm_scale: float,
    rank_v: int,
) -> jnp.ndarray:
    """One query a row attends to every position below the row's ``kv_len``
    (its own included: ``kv_len`` is its position + 1).  [S, H, Rkv]; zeros
    for a row with ``kv_len`` 0.  Compiles for the chip or raises; under the
    Pallas interpreter only where ``DYN_PALLAS_INTERPRET`` asks."""
    with jax.named_scope(SCOPES["decode"]):
        return sparse_mla.latent_decode_attention(
            q_abs, None, lat_pages, kv_lens, tables, sm_scale=sm_scale, rank_v=rank_v,
            block_k=sparse_mla.DECODE_BLOCK_K, interpret=pallas_interpret(),
            name=SCOPES["decode"])


def dense_prefill_attention(
    q: jnp.ndarray,  # [T, H, dn + dr]: per-head queries, rope applied to the last dr
    lat_pages: jnp.ndarray,  # [NP, ps, >= Rkv + dr]: [c | k^R | zero lanes]
    w_uk: jnp.ndarray,  # [H, Rkv, dn]
    w_uv: jnp.ndarray,  # [H, Rkv, dv]
    positions: jnp.ndarray,  # [T]
    kv_lens: jnp.ndarray,  # [S]
    tables: jnp.ndarray,  # [S, PP]
    cu_q_lens: jnp.ndarray,  # [S + 1]
    num_seqs: jnp.ndarray,  # [1]
    *,
    sm_scale: float,
    block_q: int = 128,
    block_k: int = 1024,
) -> jnp.ndarray:
    """Rows of more than one query token (single-token rows are left at
    zero: ``dense_decode_attention`` serves them).  Returns [T, H, dv]."""
    T, H, Dq = q.shape
    Rkv, dn = w_uk.shape[1:]
    dv = w_uv.shape[2]
    dr = Dq - dn
    S, PP = tables.shape
    ps = lat_pages.shape[1]
    ppk = max(1, min(block_k // ps, PP))  # pages per key block
    bk = ppk * ps
    nkb_max = -(-PP // ppk)
    Bq = min(block_q, T)
    tables_kb = jnp.pad(tables, ((0, 0), (0, nkb_max * ppk - PP))).reshape(S, nkb_max, ppk)
    # Heads lead: the per-head products are batched matmuls with no transpose.
    q_h = jnp.pad(q, ((0, Bq), (0, 0), (0, 0))).transpose(1, 0, 2)  # [H, T + Bq, Dq]
    pos_p = jnp.pad(positions, (0, Bq))
    q_lens = cu_q_lens[1:] - cu_q_lens[:-1]
    many = (q_lens > 1) & (jnp.arange(S, dtype=jnp.int32) < num_seqs[0])

    def row(r, state):
        t0, nq, kvl = cu_q_lens[r], q_lens[r], kv_lens[r]
        pages_r = tables_kb[r]  # [nkb_max, ppk]

        def key_block(kb, state):
            lat = lat_pages[pages_r[kb]].reshape(bk, -1)
            c, k_rope = lat[:, :Rkv], lat[:, Rkv:Rkv + dr]
            # Decompressed ONCE a key block, for all of the row's queries.
            k_nope = jnp.einsum("sc,hcn->hsn", c, w_uk)  # [H, bk, dn]
            v = jnp.einsum("sc,hcv->hsv", c, w_uv)  # [H, bk, dv]
            kpos = kb * bk + jnp.arange(bk, dtype=jnp.int32)

            def query_block(qb, state):
                m, l, acc = state
                a = t0 + qb * Bq
                qq = jax.lax.dynamic_slice_in_dim(q_h, a, Bq, axis=1)  # [H, Bq, Dq]
                qpos = jax.lax.dynamic_slice_in_dim(pos_p, a, Bq)
                q_ok = a + jnp.arange(Bq, dtype=jnp.int32) < t0 + nq
                live = ((kpos[None, :] <= qpos[:, None]) & (kpos[None, :] < kvl)
                        & q_ok[:, None])[None]  # [1, Bq, bk]
                sc = (jnp.einsum("hqn,hsn->hqs", qq[..., :dn], k_nope,
                                 preferred_element_type=jnp.float32)
                      + jnp.einsum("hqr,sr->hqs", qq[..., dn:], k_rope,
                                   preferred_element_type=jnp.float32))
                sc = jnp.where(live, sc * sm_scale, NEG)
                m_old = jax.lax.dynamic_slice_in_dim(m, a, Bq, axis=1)  # [H, Bq]
                m_new = jnp.maximum(m_old, jnp.max(sc, axis=-1))
                # x live: a query with nothing live here (another row's, or
                # all keys in its future) must leave its state as it is.
                p = jnp.exp(sc - m_new[..., None]) * live
                alpha = jnp.exp(m_old - m_new)
                l_new = jax.lax.dynamic_slice_in_dim(l, a, Bq, axis=1) * alpha + jnp.sum(p, axis=-1)
                pv = jnp.einsum("hqs,hsv->hqv", p.astype(v.dtype), v,
                                preferred_element_type=jnp.float32)
                acc_new = jax.lax.dynamic_slice_in_dim(acc, a, Bq, axis=1) * alpha[..., None] + pv
                upd = jax.lax.dynamic_update_slice_in_dim
                return upd(m, m_new, a, axis=1), upd(l, l_new, a, axis=1), upd(acc, acc_new, a, axis=1)

            return jax.lax.fori_loop(0, -(-nq // Bq), query_block, state)

        return jax.lax.fori_loop(0, jnp.where(many[r], -(-kvl // bk), 0), key_block, state)

    with jax.named_scope(SCOPES["prefill"]):
        state = (jnp.full((H, T + Bq), NEG, jnp.float32), jnp.zeros((H, T + Bq), jnp.float32),
                 jnp.zeros((H, T + Bq, dv), jnp.float32))
        _, l, acc = jax.lax.fori_loop(0, S, row, state)
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out[:, :T].transpose(1, 0, 2).astype(q.dtype)
