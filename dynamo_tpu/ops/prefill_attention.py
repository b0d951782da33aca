"""Chunked paged PREFILL attention — our own Pallas TPU kernel.

The decode hot path got its purpose-built kernel (ops/decode_attention.py,
ISSUE 13); prefill — the other half of every request and the dominant cost
at 128k-class context — still rode the stock mixed-generality kernel.
This is the prefill sibling, specialised for the shape the engine's chunk
scheduler dispatches (``ragged_attention`` non-decode path): each row's
queries are the LAST ``cu_q_lens[i+1]-cu_q_lens[i]`` tokens of its
``kv_lens[i]``-token context, whose K/V — the restored/pulled/tiered
prior prefix AND the in-flight chunk itself (written by
``write_kv_ragged`` just before the call) — already sit in paged cache
blocks:

1. **Paged prefix reads with fused dequant**: the prior prefix streams
   straight from the paged KV blocks via double-buffered ``make_async_copy``
   DMA — a restored or cross-worker-pulled prefix never needs a contiguous
   gather — and int8/fp8 pages are scaled by ``kv_scale`` in VMEM right
   before the dots.  The scale is an SMEM scalar operand, so per-layer
   TRACED calibration scales work natively.
2. **Causal chunk masking**: the chunk's own positions are covered by the
   same paged stream; the causal mask ``ctx <= qpos`` (with
   ``qpos = kv_len - q_len + t``) keeps intra-chunk attention exact.
3. **Flash-style online softmax + LSE combine** (the structure proven in
   the decode kernel): the KV axis optionally splits across grid programs,
   each writing an unnormalized partial (o, m, l) reduced host-side by
   log-sum-exp — long prior prefixes parallelize across the chip even when
   the chunk itself is narrow.

Ragged layout without dense padding: q stays in HBM (``memory_space=ANY``)
and each row-program DMAs its own q-blocks in at dynamic token offsets;
partials are DMA'd back out the same way.  A row's tail q-block can spill
past its token range into the NEXT row's region — safe because the TPU
grid runs sequentially in row-major order (rows ascending), so the next
row's own first-block write lands after and overwrites the spill; the last
row's spill goes to the wrapper's padding tail.  The row grid axis must
therefore never be marked ``parallel``.

Contract: identical inputs/outputs to ``ragged_attention``'s XLA fallback
(the byte-identity oracle) — [T, H, D] out, zeros for padding tokens at or
past ``cu_q_lens[num_seqs]``.  The wrapper compiles for the chip unless a
caller asks for the Pallas interpreter (``interpret=True``, or
``DYN_PALLAS_INTERPRET=1`` — the CPU test path).  Selection:
DYN_PREFILL_KERNEL / EngineConfig.prefill_kernel
(ops/ragged_attention.py resolve_prefill_kernel).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Shared hint machinery: the prefill knobs live in the SAME tuned table
# (tools/tune_decode.py sweeps both kernels' families into one entry per
# engine geometry) under their own keys, resolved env > table > default.
from .decode_attention import NEG_INF, pages_per_vmem_budget, resolve_hint
from .ragged_attention import pallas_interpret

SUBLANES, LANES = 8, 128  # one f32 vreg tile on the chip
MAX_BLOCK_CTX = 512  # context positions per compute block (see _default_ppcb)


def _default_ppcb(page_size: int, kv2: int, head_dim: int, itemsize: int) -> int:
    """Pages per compute block from the DYN_PREFILL_NKV_MB budget (default
    4MB) at the PAGE dtype's width — quantized pages land in scratch
    quantized, so int8 packs ~2x the bf16 block."""
    budget = resolve_hint("DYN_PREFILL_NKV_MB", "prefill_nkv_mb", 4) << 20
    # The page bytes are not the only VMEM cost of a compute block: its
    # f32 dequantized copy and the [QB*Gp, C] logits scale with the
    # block's context positions C.  Past C = 512 the chip's compiler ran
    # out of VMEM at llama-3.1-8b widths with int8 pages (compile for a
    # described v5e, PR 21), so C is capped there.
    return min(
        pages_per_vmem_budget(budget, page_size, kv2, head_dim, itemsize),
        max(1, MAX_BLOCK_CTX // page_size),
    )


def _make_kernel(
    *,
    sm_scale: float,
    num_kv: int,
    group: int,
    head_dim: int,
    page_size: int,
    pages_per_seq: int,
    split_pages: int,
    ppcb: int,
    q_block: int,
    window: Optional[int] = None,
):
    """Build the kernel body for a static geometry.  ``group`` is the
    PADDED q-heads-per-kv-head count (a sublane-tile multiple, see the
    wrapper), so every in-kernel reshape is tile-aligned.

    Grid (S, J): program (s, j) computes ALL of row ``s``'s query blocks
    against KV split ``j`` (pages [j*split_pages, (j+1)*split_pages)) and
    writes UNNORMALIZED partials (o, m, l) per token — combined host-side
    by LSE over the split axis.

    ``window``: a query attends to the last ``window`` positions up to its
    own; the caller's table begins at the first page the row's first query's
    window reaches (``fused_prefill_attention``), and a q-block's walk begins
    at the first compute block ITS first query's window reaches.
    """
    C = ppcb * page_size  # context positions per compute block
    QB = q_block

    def kernel(
        # scalar prefetch (SMEM)
        kv_lens_ref,  # [S] int32
        page_indices_ref,  # [S, PP] int32
        cu_q_lens_ref,  # [S+1] int32
        num_seqs_ref,  # [1] int32
        # operands
        q_hbm_ref,  # [Tpad, KV, Gp, D] f32 HBM — DMA'd per q-block
        pages_ref,  # [P, ps, 2KV, D] HBM — DMA'd per compute block
        scale_ref,  # [1, 1] f32 SMEM — kv_scale (traced OK)
        # outputs (HBM — DMA'd per q-block)
        o_ref,  # [J, Tpad, KV, Gp, D] f32 — unnormalized sum(p·V)
        ml_ref,  # [J, Tpad, KV, Gp, LANES] f32 — lane 0 split max, lane 1 sum(exp)
        # scratch
        q_buf,  # [QB, KV, Gp, D] f32
        kv_buf,  # [2, ppcb, ps, 2KV, D] pages dtype
        o_sc,  # [QB, KV, Gp, D] f32
        ml_sc,  # [QB, KV, Gp, LANES] f32
        kv_sems,  # DMA semaphores (2,) — double-buffered page stream
        io_sems,  # DMA semaphores (3,) — q in + o/ml out
    ):
        s = pl.program_id(0)
        j = pl.program_id(1)
        kv_len = kv_lens_ref[s]
        q_start = cu_q_lens_ref[s]
        q_len = cu_q_lens_ref[s + 1] - q_start
        base_page = j * split_pages
        row_pages = pl.cdiv(kv_len, page_size)
        pages_here = jnp.clip(row_pages - base_page, 0, split_pages)
        # The split's coverage END (not just kv_len): the last compute
        # block can reach past split_pages (ppcb granularity) and those
        # positions would otherwise be counted by TWO splits — a
        # double-count the LSE combine cannot undo (same cap as decode).
        split_end = jnp.minimum(kv_len, (base_page + split_pages) * page_size)
        # Rows past num_seqs write nothing: their token region is padding
        # by the cu_q_lens contract and the wrapper masks it to zero.  An
        # ACTIVE row writes every split slab — an empty split (prefix
        # shorter than the split's base) runs zero compute blocks and
        # writes the neutral partial, which vanishes in the combine.
        active = (s < num_seqs_ref[0]) & (q_len > 0)

        def fetch(block, slot, start):
            # One DMA per page: page ids are arbitrary (PagedAttention
            # indirection), so a block's pages share no stride.  wait()
            # recreates the descriptor — standard Pallas pattern.
            for t in range(ppcb):
                idx = base_page + block * ppcb + t
                idx = jnp.clip(idx, 0, pages_per_seq - 1)
                pid = page_indices_ref[s, idx]
                dma = pltpu.make_async_copy(
                    pages_ref.at[pid], kv_buf.at[slot, t], kv_sems.at[slot]
                )
                if start:
                    dma.start()
                else:
                    dma.wait()

        @pl.when(active)
        def _():
            nqb = pl.cdiv(q_len, QB)
            nblocks = pl.cdiv(pages_here, ppcb)
            scale = scale_ref[0, 0]

            def qb_step(qb, carry_unused):
                tok0 = q_start + qb * QB
                # Fetch this q-block (tail blocks over-read into the next
                # row's tokens / the wrapper's zero pad — masked below).
                qdma = pltpu.make_async_copy(
                    q_hbm_ref.at[pl.ds(tok0, QB)], q_buf, io_sems.at[0]
                )
                qdma.start()
                qdma.wait()

                # Per-token causal coordinates, flattened per KV head to
                # [QB*G] rows: row r is token i = r // G of the block.
                ti = (
                    qb * QB
                    + jax.lax.broadcasted_iota(jnp.int32, (QB * group, 1), 0)
                    // group
                )  # in-row token index [QB*G, 1]
                qpos = kv_len - q_len + ti
                valid_q = ti < q_len

                def block_step(b, carry):
                    slot = jax.lax.rem(b, 2)

                    @pl.when(b + 1 < nblocks)
                    def _():
                        fetch(b + 1, jax.lax.rem(b + 1, 2), start=True)

                    fetch(b, slot, start=False)
                    buf = kv_buf[slot].reshape(C, 2 * num_kv, head_dim)
                    # Fused dequant: the ONLY f32 materialization of this
                    # KV block is here in VMEM, one compute block at a time.
                    kvf = buf.astype(jnp.float32) * scale
                    pos = (base_page + b * ppcb) * page_size + (
                        jax.lax.broadcasted_iota(jnp.int32, (1, C), 1)
                    )
                    # Causal + split-coverage + live-query mask [QB*G, C].
                    mask = (pos <= qpos) & (pos < split_end) & valid_q
                    if window is not None:
                        mask &= pos > qpos - window
                    out = []
                    for h in range(num_kv):
                        m_h = carry[3 * h]
                        l_h = carry[3 * h + 1]
                        acc_h = carry[3 * h + 2]
                        k_h = kvf[:, 2 * h, :]  # [C, D]
                        v_h = kvf[:, 2 * h + 1, :]
                        qf = (
                            q_buf[:, h].reshape(QB * group, head_dim)
                            * sm_scale
                        )
                        logits = jax.lax.dot_general(
                            qf,
                            k_h,
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32,
                        )  # [QB*G, C]
                        logits = jnp.where(mask, logits, NEG_INF)
                        m_new = jnp.maximum(
                            m_h, jnp.max(logits, axis=1, keepdims=True)
                        )
                        # Mask the exp explicitly: a fully-masked block has
                        # m_new == m_h and exp(NEG_INF - m) must stay an
                        # exact zero, never a subnormal.
                        p = jnp.where(mask, jnp.exp(logits - m_new), 0.0)
                        alpha = jnp.exp(m_h - m_new)
                        l_new = alpha * l_h + jnp.sum(p, axis=1, keepdims=True)
                        acc_new = alpha * acc_h + jax.lax.dot_general(
                            p,
                            v_h,
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32,
                        )  # [QB*G, D]
                        out.extend((m_new, l_new, acc_new))
                    return tuple(out)

                init = []
                for _h in range(num_kv):
                    init.extend(
                        (
                            jnp.full((QB * group, 1), NEG_INF, jnp.float32),
                            jnp.zeros((QB * group, 1), jnp.float32),
                            jnp.zeros((QB * group, head_dim), jnp.float32),
                        )
                    )

                # A window's walk begins at the block that holds the first
                # position the q-block's first query attends to (the blocks'
                # parity picks the buffer, so any start is as good as 0).
                b0 = 0
                if window is not None:
                    first_pos = kv_len - q_len + qb * QB - (window - 1)
                    b0 = jnp.clip((first_pos - base_page * page_size) // C, 0, nblocks)

                @pl.when(nblocks > b0)
                def _():
                    fetch(b0, jax.lax.rem(b0, 2) if window is not None else 0, start=True)

                # An empty split runs zero trips: the init carry IS the
                # neutral partial (o=0, m=NEG_INF, l=0).
                final = jax.lax.fori_loop(b0, nblocks, block_step, tuple(init))
                lane = jax.lax.broadcasted_iota(
                    jnp.int32, (QB * group, LANES), 1
                )
                for h in range(num_kv):
                    # m and l ride ONE lane-dense slab (lane 0 / lane 1):
                    # a [.., 1]-wide partial is not a shape the chip's
                    # DMA engine can slice.
                    ml_sc[:, h] = jnp.where(
                        lane == 0, final[3 * h], final[3 * h + 1]
                    ).reshape(QB, group, LANES)
                    o_sc[:, h] = final[3 * h + 2].reshape(
                        QB, group, head_dim
                    )
                # Write the block's partials back at the token offset.  The
                # tail block spills up to QB-1 tokens into the next row's
                # region — overwritten by that row's own (later) program;
                # see the module docstring's sequential-grid invariant.
                writes = (
                    pltpu.make_async_copy(
                        o_sc, o_ref.at[j, pl.ds(tok0, QB)], io_sems.at[1]
                    ),
                    pltpu.make_async_copy(
                        ml_sc, ml_ref.at[j, pl.ds(tok0, QB)], io_sems.at[2]
                    ),
                )
                for w in writes:
                    w.start()
                for w in writes:
                    w.wait()
                return carry_unused

            jax.lax.fori_loop(0, nqb, qb_step, 0)

    return kernel


def fused_prefill_attention(
    q: jnp.ndarray,  # [T, num_heads, head_dim] — ragged token run
    pages: jnp.ndarray,  # [num_pages, page_size, 2*kv_heads, head_dim]
    kv_lens: jnp.ndarray,  # [S] int32 context length per row
    page_indices: jnp.ndarray,  # [S, pages_per_seq] int32
    cu_q_lens: jnp.ndarray,  # [S+1] int32 cumulative query lengths
    num_seqs: jnp.ndarray,  # [1] int32 valid rows
    *,
    sm_scale: float,
    kv_scale=None,  # None | float | traced [] scalar — applied IN-KERNEL
    q_block: Optional[int] = None,
    num_kv_splits: Optional[int] = None,
    pages_per_block: Optional[int] = None,
    interpret: Optional[bool] = None,
    window: Optional[int] = None,
) -> jnp.ndarray:
    """Host wrapper: chunked paged prefill attention + LSE split combine.

    ``window``: a layer that keeps the last ``window`` positions only;
    ``page_indices`` and ``kv_lens`` are those of the rows' WINDOW pages (as
    ``fused_decode_attention``), and the call is named
    ``window_prefill_attention`` in the device trace.

    Knobs (env > tuned table > default; tools/tune_decode.py sweeps them):
    - ``DYN_PREFILL_QB`` / prefill_qb: query tokens per compute block.
    - ``DYN_PREFILL_SPLITS`` / prefill_splits: KV-split grid width
      (0 = auto: 1 — the q-block axis already parallelizes a chunk; raise
      it for long restored prefixes, where the KV stream dominates).
    - ``DYN_PREFILL_PPCB`` / prefill_ppcb: pages per compute block
      (default from the DYN_PREFILL_NKV_MB VMEM budget at the PAGE
      dtype's width).
    """
    T, H, D = q.shape
    P, ps, KV2, _ = pages.shape
    KV = KV2 // 2
    G = H // KV
    S, PP = page_indices.shape

    QB = q_block or resolve_hint("DYN_PREFILL_QB", "prefill_qb", 128)
    QB = max(1, min(QB, T))
    ppcb = pages_per_block or resolve_hint(
        "DYN_PREFILL_PPCB",
        "prefill_ppcb",
        _default_ppcb(ps, KV2, D, pages.dtype.itemsize),
    )
    ppcb = max(1, min(ppcb, PP))
    splits = num_kv_splits or resolve_hint(
        "DYN_PREFILL_SPLITS", "prefill_splits", 0
    )
    if splits <= 0:
        splits = 1
    splits = min(splits, pl.cdiv(PP, ppcb))
    split_pages = pl.cdiv(PP, splits)
    splits = pl.cdiv(PP, split_pages)  # drop now-empty tail splits

    interpret = pallas_interpret() if interpret is None else interpret
    # Layout the chip's DMA engine can slice at a dynamic TOKEN offset: the
    # token axis leads (untiled) and the two tiled minor dims are whole,
    # aligned tiles — q as f32 [Tpad, KV, Gp, D] with the q-heads of one
    # KV head padded to the f32 sublane tile (Gp = 8 for qwen's G=7 and
    # llama's G=4; the pad rows are zero queries, sliced off below).  The
    # original [T, H, D] bf16 / [.., H, 1] layouts only ever ran in
    # interpret mode: Mosaic refuses a 28-of-32 sublane slice and a
    # 1-of-128 lane slice.
    Gp = -(-G // SUBLANES) * SUBLANES
    kernel = _make_kernel(
        sm_scale=sm_scale,
        num_kv=KV,
        group=Gp,
        head_dim=D,
        page_size=ps,
        pages_per_seq=PP,
        split_pages=split_pages,
        ppcb=ppcb,
        q_block=QB,
        window=window,
    )
    scale_arr = jnp.asarray(
        1.0 if kv_scale is None else kv_scale, jnp.float32
    ).reshape(1, 1)
    # Pad the token axis by one q-block: tail q-block DMAs over-read past
    # the run, and the LAST row's tail write spills here instead of out of
    # bounds.  Sliced back off after the combine.
    Tpad = T + QB
    q_pad = jnp.pad(
        q.astype(jnp.float32).reshape(T, KV, G, D),
        ((0, QB), (0, 0), (0, Gp - G), (0, 0)),
    )

    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(S, splits),
        in_specs=[
            hbm,  # q: DMA'd per q-block
            hbm,  # pages: DMA'd per compute block
            pl.BlockSpec(memory_space=pltpu.SMEM),  # kv_scale
        ],
        out_specs=(hbm, hbm),  # o / ml partials
        scratch_shapes=[
            pltpu.VMEM((QB, KV, Gp, D), jnp.float32),
            pltpu.VMEM((2, ppcb, ps, KV2, D), pages.dtype),
            pltpu.VMEM((QB, KV, Gp, D), jnp.float32),
            pltpu.VMEM((QB, KV, Gp, LANES), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((3,)),
        ],
    )
    cu = jnp.asarray(cu_q_lens, jnp.int32)
    num = jnp.asarray(num_seqs, jnp.int32)
    o_part, ml_part = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((splits, Tpad, KV, Gp, D), jnp.float32),
            jax.ShapeDtypeStruct((splits, Tpad, KV, Gp, LANES), jnp.float32),
        ),
        compiler_params=pltpu.CompilerParams(
            # Same headroom as the decode kernel / stock path.
            vmem_limit_bytes=64 << 20,
        ),
        interpret=interpret,
        name="fused_prefill_attention" if window is None else "window_prefill_attention",
    )(
        jnp.asarray(kv_lens, jnp.int32),
        jnp.asarray(page_indices, jnp.int32),
        cu,
        num,
        q_pad,
        pages,
        scale_arr,
    )
    # Drop the token / group padding and restore [J, T, H(, D)].
    o_part = o_part[:, :T, :, :G].reshape(splits, T, H, D)
    m = ml_part[:, :T, :, :G, 0].reshape(splits, T, H)
    l = ml_part[:, :T, :, :G, 1].reshape(splits, T, H)
    # Flash-style LSE combine over the split axis.  Neutral partials
    # (o=0, m=NEG_INF, l=0) from empty splits vanish here.
    m_max = jnp.max(m, axis=0)  # [T, H]
    alpha = jnp.exp(m - m_max[None])  # [J, T, H]
    l_tot = jnp.sum(alpha * l, axis=0)
    o_tot = jnp.sum(alpha[..., None] * o_part, axis=0)  # [T, H, D]
    out = o_tot / (l_tot[..., None] + 1e-30)
    # Padding tokens (at/past cu_q_lens[num_seqs]) were never written by an
    # active row: zero them to match the XLA oracle's padding contract.
    valid = jnp.arange(T, dtype=jnp.int32) < cu[num[0]]
    out = jnp.where(valid[:, None, None], out, 0.0)
    return out.astype(q.dtype)
