"""Latent attention (MLA) over the WHOLE context of a row, for a model of the
latent family without a selector (``index_topk`` 0: DeepSeek-V3, Kimi-K2),
over the same paged latent cache as ops/sparse_mla.py.

Two forms of the same mathematics (docs/deepseek_v32.md has the operation
counts), chosen by how many queries share a key, each ONE Pallas call:

- ``dense_decode_attention``: rows of ONE query token.  ABSORBED form: the
  query carries W^UK, the cached 576-value entry is K and V at once.  The
  one-query Pallas kernel of ops/sparse_mla.py with the bound ``position <
  kv_len`` in place of S_t's mask: no scores, no ``select_mask``, no mask
  operand.  The call is named ``mla_dense_decode_attention``.
- ``dense_prefill_attention``: rows of MANY query tokens (prompt chunks with
  a cached past).  DECOMPRESSED form: a key block's entries become per-head
  keys [W^UK_h c_j ; k^R_j] and values W^UV_h c_j ONCE, and every query of
  the row's chunk attends to them (one causal pass, a running softmax):
  2 N H (192 + 128) operations a query plus 2 N H 512 x 256 a chunk, where
  absorbed costs 2 N H (576 + 512) a query.  The call is named
  ``mla_dense_prefill_attention``: a program holds ``PREFILL_HEADS`` heads of
  the step's tokens and walks each prompt row's live pages through the page
  table, two key blocks deep; the running softmax (max, sum, float32 output)
  is VMEM scratch from a row's first key block to its last and the output is
  written once.  Work follows ``kv_lens`` and the rows' query counts, not
  ``max_model_len`` (docs/kimi_k2.md has the grid, what is resident and the
  block sizes with their measurements).
- ``latent_prefill_attention``: the same kernel for the model WITH a selector
  (ops/sparse_mla.py, prompt programs of ``PREFILL_KERNEL_TOKENS`` tokens or
  more): S_t arrives as a mask of the step's tokens, one more operand that
  stays in HBM; its (program's tokens x key block) slab is copied beside the
  block's pages and a query attends to what it keeps and to nothing else.
  Whether the operand exists is a static property of the call: without it
  the traced kernel is the dense one, op for op.  The call is then named
  ``mla_sparse_prefill_attention`` (docs/deepseek_v32.md).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

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


# The prompt-chunk kernel's sizes, chosen by its time alone at the cell's
# shape (64 heads, 512 queries against 13k cached positions in 16-token pages
# of 640 lanes; docs/kimi_k2.md has the table).  Positions a key block (its
# pages are copied, then decompressed once for the program's heads); query
# tokens a tile of the inner loop; heads a program; and the tokens of a step
# a program keeps resident (queries, state and output of its heads): a larger
# step (``--prefill-chunk`` plus ``--max-batch`` over 1024; no cell of the
# benchmark has one) is walked in blocks of that many, and a row that lies
# across two of them is decompressed for each.
PREFILL_BLOCK_K = 1024
PREFILL_BLOCK_Q = 256
PREFILL_HEADS = 16
PREFILL_STEP_TOKENS = 1024
# The bias of a (query, key) pair that S_t does not keep: below the running
# maximum's floor NEG (``_prefill_kernel::attend``).
SKIPPED = 2 * NEG


def _prefill_kernel(
    # scalar prefetch (SMEM)
    kv_lens_ref,  # [S] int32
    tables_ref,  # [S, PP] int32
    cu_ref,  # [S + 1] int32
    num_ref,  # [1] int32
    # operands
    q_ref,  # [Hg, TB, dn + tail] VMEM: the program's heads of the step's tokens, zero lanes last
    wuk_ref,  # [Hg, Rkv, dn] VMEM
    wuv_ref,  # [Hg, Rkv, dv] VMEM
    lat_ref,  # [NP, ps, Rkv + tail] HBM, copied page by page
    # then, ONLY with ``selected``: sel_ref [Tp, nkb * C] int8 HBM, S_t over
    # the step's tokens and their rows' positions; the output o_ref [TB, Hg *
    # dv] VMEM; the scratch:
    #   buf [2, ppb, ps, Rkv + tail] in the pages' dtype
    #   kbuf [Hg, C, dn + tail]: a key block's keys, [W^UK_h c | k^R | zero lanes]
    #   vbuf [Hg, C, dv]: its values
    #   m_ref, l_ref [Hg, TB, 1] f32; acc_ref [Hg, TB, dv] f32
    #   sems, DMA semaphores (2,)
    # and ONLY with ``selected``: sbuf [2, TB, C] int8 (S_t of the program's
    # tokens over a key block, copied beside the block's pages), bias_ref
    # [tq, C] f32 and ssems, DMA semaphores (2,)
    *refs,
    selected: bool,
    sm_scale: float,
    page_size: int,
    pages_per_seq: int,
    ppb: int,
    tq: int,
):
    """Program (g, b): heads [g Hg, (g + 1) Hg) of the step's tokens [b TB,
    (b + 1) TB).  For every row of more than one query token that has tokens
    there, the row's live key blocks in order; a block's pages are copied
    while the block before it is computed, decompressed once, and every query
    tile of the row attends to it.  (Grid programs run in order on one core:
    no dimension_semantics.)  Without ``selected`` there is no selector: a
    query attends to every position up to its own, and no mask operand
    exists.  With it a query attends to what S_t keeps of them and to nothing
    else (S_t holds the causal bound and ``kv_len`` already)."""
    if selected:
        sel_ref, o_ref, buf, kbuf, vbuf, m_ref, l_ref, acc_ref, sems, sbuf, bias_ref, ssems = refs
    else:
        o_ref, buf, kbuf, vbuf, m_ref, l_ref, acc_ref, sems = refs
    Hg, TB, _ = q_ref.shape
    dn, dv = wuk_ref.shape[2], wuv_ref.shape[2]
    rank = wuk_ref.shape[1]
    C = ppb * page_size
    base = pl.program_id(1) * TB
    cdt = kbuf.dtype

    # As in ops/sparse_mla.py::_decode_kernel: a short last block copies only
    # the pages the row has, what the buffer held before stays, and a masked
    # weight of 0 times a NaN is a NaN.  So the call starts from zeros.
    @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
    def _():
        buf[...] = jnp.zeros(buf.shape, buf.dtype)

    m_ref[...] = jnp.full(m_ref.shape, NEG, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def row(r, carry):
        t0, t1, kv_len = cu_ref[r], cu_ref[r + 1], kv_lens_ref[r]
        lo, hi = jnp.maximum(t0, base) - base, jnp.minimum(t1, base + TB) - base
        pages = jnp.minimum(pl.cdiv(kv_len, page_size), pages_per_seq)
        nblocks = pl.cdiv(pages, ppb)
        first_pos = kv_len - (t1 - t0)  # the position of the row's first query

        def fetch(block, slot, start):
            first = block * ppb
            live = jnp.minimum(ppb, pages - first)

            def one(t, carry):
                dma = pltpu.make_async_copy(
                    lat_ref.at[tables_ref[r, first + t]], buf.at[slot, t], sems.at[slot])
                if start:
                    dma.start()
                else:
                    dma.wait()
                return carry

            # A loop, not straight-line code as in the one-query kernel: a
            # block's copies are issued under 50 us of matmuls, and unrolled
            # they cost a second of tracing and lowering a program.
            jax.lax.fori_loop(0, live, one, 0)
            if selected:
                dma = pltpu.make_async_copy(
                    sel_ref.at[pl.ds(base, TB), pl.ds(pl.multiple_of(block * C, C), C)],
                    sbuf.at[slot], ssems.at[slot])
                if start:
                    dma.start()
                else:
                    dma.wait()

        def attend(ts, kb, masked: bool):
            """Every head of the program: the query tile at ``ts`` against key
            block kb.  ``masked``: the tile holds tokens of other rows, or the
            block reaches the chunk's first position (the causal comparison);
            with ``selected`` every pair is a masked one, by S_t."""
            rows = pl.ds(ts, tq)
            if selected:
                # Once for the program's heads: 0 where the query is the
                # row's and S_t keeps the key, else SKIPPED, which lies below
                # the state's floor NEG: exp(SKIPPED - m) is 0 even for a
                # query that has seen nothing yet, so it leaves its state as
                # it is with no second select a head.
                tok = ts + jax.lax.broadcasted_iota(jnp.int32, (tq, 1), 0)
                kept = sbuf[jax.lax.rem(kb, 2), rows, :].astype(jnp.int32) != 0
                keep = kept & (tok >= lo) & (tok < hi)
                bias_ref[...] = jnp.where(keep, 0.0, SKIPPED)

            def head(h, carry):
                s = jax.lax.dot_general(q_ref[h, rows, :], kbuf[h], (((1,), (1,)), ((), ())),
                                        preferred_element_type=jnp.float32) * sm_scale  # [tq, C]
                if selected:
                    s = s + bias_ref[...]
                elif masked:
                    tok = ts + jax.lax.broadcasted_iota(jnp.int32, (tq, 1), 0)
                    kpos = kb * C + jax.lax.broadcasted_iota(jnp.int32, (1, C), 1)
                    live = (kpos <= first_pos + (tok + base - t0)) & (tok >= lo) & (tok < hi)
                    s = jnp.where(live, s, NEG)
                m_old = m_ref[h, rows, :]
                m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
                p = jnp.exp(s - m_new)
                if masked and not selected:
                    # A query with nothing live here (another row's, or all
                    # keys in its future) must leave its state as it is.
                    p = jnp.where(live, p, 0.0)
                alpha = jnp.exp(m_old - m_new)
                l_ref[h, rows, :] = l_ref[h, rows, :] * alpha + jnp.sum(p, axis=1, keepdims=True)
                acc_ref[h, rows, :] = acc_ref[h, rows, :] * alpha + jax.lax.dot_general(
                    p.astype(cdt), vbuf[h], (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                m_ref[h, rows, :] = m_new
                return carry

            jax.lax.fori_loop(0, Hg, head, 0)

        def key_block(kb, carry):
            slot = jax.lax.rem(kb, 2)

            @pl.when(kb + 1 < nblocks)
            def _():
                fetch(kb + 1, jax.lax.rem(kb + 1, 2), start=True)

            fetch(kb, slot, start=False)
            lat = buf[slot].reshape(C, buf.shape[-1]).astype(cdt)
            c = lat[:, :rank]

            def decompress(h, carry):
                # Rounded to the compute type as the einsum's result was.
                kbuf[h, :, :dn] = jnp.dot(
                    c, wuk_ref[h].astype(cdt), preferred_element_type=jnp.float32).astype(cdt)
                kbuf[h, :, dn:] = lat[:, rank:]
                vbuf[h] = jnp.dot(
                    c, wuv_ref[h].astype(cdt), preferred_element_type=jnp.float32).astype(cdt)
                return carry

            jax.lax.fori_loop(0, Hg, decompress, 0)
            past = (kb + 1) * C <= first_pos + 1  # every key at or below every query of the row

            def tile(qt, carry):
                ts = pl.multiple_of(qt * tq, tq)
                own = (ts >= lo) & (ts + tq <= hi)  # the row's tokens only
                plain = past & own
                last_pos = first_pos + jnp.minimum(ts + tq, hi) - 1 + base - t0

                # A block wholly in the tile's future is skipped.
                if selected:
                    pl.when(kb * C <= last_pos)(lambda: attend(ts, kb, True))
                else:
                    pl.when(plain)(lambda: attend(ts, kb, False))
                    pl.when(jnp.logical_not(plain) & (kb * C <= last_pos))(
                        lambda: attend(ts, kb, True))
                return carry

            jax.lax.fori_loop(lo // tq, pl.cdiv(hi, tq), tile, 0)
            return carry

        # Rows of one query token or none (the one-query kernel's), rows
        # with no token in this block: nothing copied, nothing computed.
        @pl.when((t1 - t0 > 1) & (hi > lo) & (kv_len > 0))
        def _():
            fetch(0, 0, start=True)
            jax.lax.fori_loop(0, nblocks, key_block, 0)

        return carry

    jax.lax.fori_loop(0, num_ref[0], row, 0)
    for h in range(Hg):  # static: a head is a lane range of the output
        o_ref[:, h * dv:(h + 1) * dv] = (
            acc_ref[h] / jnp.maximum(l_ref[h], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "sm_scale", "block_k", "block_q", "heads", "step_tokens", "interpret", "name"))
def _prefill_call(q, selection, lat_pages, w_uk, w_uv, kv_lens, tables, cu_q_lens, num_seqs, *,
                  sm_scale, block_k, block_q, heads, step_tokens, interpret, name):
    """The kernel's call, with S_t as ``selection`` [T, PP * ps] bool over
    the step's tokens and their rows' positions or, for a model without a
    selector, ``None``: then no mask operand is built and the traced kernel
    is the dense one.  Jitted, so that a process traces the kernel once and a
    program lowers it once, whatever its layers (PERF.md section 6, PR 31)."""
    T, H, Dq = q.shape
    Rkv, dn = w_uk.shape[1:]
    dv = w_uv.shape[2]
    PP = tables.shape[1]
    ps, W = lat_pages.shape[1:]
    tail = W - Rkv  # k^R and the zero lanes up to the stored width
    cdt = jnp.result_type(q.dtype, lat_pages.dtype, w_uk.dtype)
    Hg = next(n for n in range(min(heads, H), 0, -1) if H % n == 0)
    ppb = max(1, min(block_k // ps, PP))  # pages per key block
    C = ppb * ps
    tq = min(block_q, -(-T // 16) * 16)
    TB = min(step_tokens, -(-T // tq) * tq)
    Tp = -(-T // TB) * TB
    selected = selection is not None
    # Heads lead (a head of the program is a leading index); the query's lanes
    # are padded with zeros to the key's: [q^N | q^R | 0] against [W^UK c | k^R | 0].
    q_h = jnp.pad(q.astype(cdt), ((0, Tp - T), (0, 0), (0, dn + tail - Dq))).transpose(1, 0, 2)
    kernel = functools.partial(_prefill_kernel, selected=selected, sm_scale=sm_scale,
                               page_size=ps, pages_per_seq=PP, ppb=ppb, tq=tq)
    operands, in_specs, scratch = [q_h, w_uk, w_uv, lat_pages], [
        pl.BlockSpec((Hg, TB, dn + tail), lambda g, b, *_: (g, b, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec((Hg, Rkv, dn), lambda g, b, *_: (g, 0, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec((Hg, Rkv, dv), lambda g, b, *_: (g, 0, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec(memory_space=pl.ANY),  # pages stay in HBM
    ], [
        pltpu.VMEM((2, ppb, ps, W), lat_pages.dtype),
        pltpu.VMEM((Hg, C, dn + tail), cdt),
        pltpu.VMEM((Hg, C, dv), cdt),
        pltpu.VMEM((Hg, TB, 1), jnp.float32),
        pltpu.VMEM((Hg, TB, 1), jnp.float32),
        pltpu.VMEM((Hg, TB, dv), jnp.float32),
        pltpu.SemaphoreType.DMA((2,)),
    ]
    if selected:
        # One byte a (token, position), whole key blocks wide: the (program's
        # tokens x key block) slab is then one strided copy.  It stays in HBM.
        operands.append(jnp.pad(selection.astype(jnp.int8),
                                ((0, Tp - T), (0, -(-PP // ppb) * C - PP * ps))))
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        scratch += [pltpu.VMEM((2, TB, C), jnp.int8), pltpu.VMEM((tq, C), jnp.float32),
                    pltpu.SemaphoreType.DMA((2,))]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(H // Hg, Tp // TB),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((TB, Hg * dv), lambda g, b, *_: (b, g), memory_space=pltpu.VMEM),
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Tp, H * dv), q.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=96 << 20),
        interpret=interpret,
        name=name,
    )(kv_lens.astype(jnp.int32), tables.astype(jnp.int32), cu_q_lens.astype(jnp.int32),
      num_seqs.astype(jnp.int32), *operands)
    return out[:T].reshape(T, H, dv)


def dense_prefill_attention(
    q: jnp.ndarray,  # [T, H, dn + dr]: per-head queries, rope applied to the last dr
    lat_pages: jnp.ndarray,  # [NP, ps, >= Rkv + dr]: [c | k^R | zero lanes]
    w_uk: jnp.ndarray,  # [H, Rkv, dn]
    w_uv: jnp.ndarray,  # [H, Rkv, dv]
    kv_lens: jnp.ndarray,  # [S]
    tables: jnp.ndarray,  # [S, PP]
    cu_q_lens: jnp.ndarray,  # [S + 1]
    num_seqs: jnp.ndarray,  # [1]
    *,
    sm_scale: float,
) -> jnp.ndarray:
    """Rows of more than one query token (single-token rows are left at
    zero: ``dense_decode_attention`` serves them); a row's queries are its
    last positions (``kv_len`` minus its query count onwards).  Returns
    [T, H, dv].  Compiles for the chip or raises; under the Pallas
    interpreter only where ``DYN_PALLAS_INTERPRET`` asks."""
    return latent_prefill_attention(q, None, lat_pages, w_uk, w_uv, kv_lens, tables, cu_q_lens,
                                    num_seqs, sm_scale=sm_scale, name=SCOPES["prefill"])


def latent_prefill_attention(q, selection, lat_pages, w_uk, w_uv, kv_lens, tables, cu_q_lens,
                             num_seqs, *, sm_scale: float, name: str) -> jnp.ndarray:
    """``dense_prefill_attention`` (its arguments, its result) under S_t:
    ``selection`` [T, PP * ps] bool says which positions of its row a
    step's token attends to (``ops/sparse_mla.py::sparse_prefill_selection``;
    False beyond the token's own position and ``kv_len``), or None: every
    position up to its own.  ``name``: the call's, and its scope's."""
    with jax.named_scope(name):
        return _prefill_call(
            q, selection, lat_pages, w_uk, w_uv, kv_lens, tables, cu_q_lens, num_seqs,
            sm_scale=sm_scale, block_k=PREFILL_BLOCK_K, block_q=PREFILL_BLOCK_Q,
            heads=PREFILL_HEADS, step_tokens=PREFILL_STEP_TOKENS, interpret=pallas_interpret(),
            name=name)
