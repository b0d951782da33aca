"""Fused-dequant ragged paged DECODE attention — our own Pallas TPU kernel.

The stock ``jax.experimental.pallas.ops.tpu.ragged_paged_attention`` kernel
only CASTS quantized (int8/fp8) KV pages up to the query dtype and never
applies ``kv_scale`` in-kernel, so the model folds dequant algebraically
around the call (q pre-scaled, output post-scaled — models/llama.py) and
the decode step's dominant HBM stream still rides a generic mixed
prefill/decode kernel.  This kernel is specialised for the one shape the
fused decode program dispatches — ONE query token per row, identity row map
(``ragged_decode_attention``); whether it beats the stock kernel is not
measured on this machine:

1. **Fused dequant**: int8/fp8 KV pages are DMA'd quantized and scaled by
   ``kv_scale`` in VMEM right before the QK/AV dots — the KV stream is
   read from HBM ONCE at 1 byte/value and never materialized dequantized.
   The scale is an SMEM scalar operand, so per-layer TRACED calibration
   scales work natively (the stock kernel's k_scale/v_scale must be static
   floats, which is why dequant lived outside it).
2. **Work follows the live pages**: a row's program walks
   ``cdiv(live pages, ppcb)`` compute blocks of a few hundred positions
   and starts ONE page copy per page the row has — a short row, and a
   padding row (``kv_lens`` 0), cost what they hold, not what
   ``pages_per_seq`` could hold.
3. **Double-buffered page fetch**: pages DMA HBM→VMEM via
   ``make_async_copy`` two compute-blocks deep; with several blocks a row,
   block ``b+1``'s copies are in flight during block ``b``'s dots
   (PagedAttention page tables, vLLM SOSP 2023 — the repo's paged layout).
4. **Split-KV grid** (Flash-Decoding, Dao et al. 2023) behind
   ``num_kv_splits``: each split writes an unnormalized partial (o, m, l)
   and a log-sum-exp combine reduces them.  Off by default (one split):
   the call declares no parallel grid axis, so splits run one after
   another on the chip's one TensorCore and buy nothing (docs/decode_kernel.md).

Contract: identical inputs/outputs to ``ragged_decode_attention``'s XLA
fallback (the bit-exactness oracle) — [S, H, D] out, zeros for rows past
``num_seqs``.  The wrapper compiles for the chip unless a caller asks for
the Pallas interpreter (``interpret=True``, or ``DYN_PALLAS_INTERPRET=1``
— the CPU test path, ops/ragged_attention.py pallas_interpret).  Selection: DYN_DECODE_KERNEL /
EngineConfig.decode_kernel (ops/ragged_attention.py resolve_decode_kernel).
"""

from __future__ import annotations

import json
import logging
import os
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ragged_attention import pallas_interpret

logger = logging.getLogger(__name__)

NEG_INF = -1e30  # matches ops/ragged_attention.py (bit-compatible masking)

# ------------------------------------------------------------------ tuning
# Block-hint resolution order (every knob): explicit env var > tuned-table
# entry installed at engine init (tools/tune_decode.py) > built-in default.
# The table maps "model|b<batch>|ps<page_size>" -> {nq, nkv_mb, splits,
# ppcb, ...}; engine init installs its own geometry's entry so serving
# picks up sweeps without env plumbing.

_ACTIVE_HINTS: Optional[Dict[str, Any]] = None
_ACTIVE_KEY: Optional[str] = None


def default_table_path() -> str:
    """``DYN_DECODE_TUNE_TABLE``, else ONE path inside the checkout
    (``<repo>/decode_tune.json`` — absent until tools/tune_decode.py is run
    on the chip and its table committed, so the built-in defaults serve).
    Nothing under ``~`` is read."""
    from .. import REPO_ROOT

    return os.environ.get(
        "DYN_DECODE_TUNE_TABLE", os.path.join(REPO_ROOT, "decode_tune.json")
    )


def hint_key(model: str, batch: int, page_size: int) -> str:
    """Tuned-table key for an engine geometry.  Batch is the decode
    dispatch's ROW count (cfg.max_batch — fused decode always dispatches
    full-width), page_size the KV block size."""
    return f"{model}|b{int(batch)}|ps{int(page_size)}"


def load_tuned_table(path: Optional[str] = None) -> Dict[str, Any]:
    p = path or default_table_path()
    try:
        with open(p) as f:
            t = json.load(f)
        return t if isinstance(t, dict) else {}
    except (OSError, ValueError):
        return {}


def install_tuned_hints(
    model: str, batch: int, page_size: int, path: Optional[str] = None
) -> Optional[Dict[str, Any]]:
    """Engine-init hook: load the tuned entry for this geometry (None +
    built-in defaults when no table/key matches).  Never raises — a
    corrupt table must not take a worker down.

    Entries recorded on a DIFFERENT backend are refused: a CPU
    interpret-mode sweep's "winners" are meaningless timings, and
    silently serving a TPU with them would be exactly the perf
    regression the tuner exists to prevent.  (Hand-written entries
    without a ``backend`` field install anywhere.)

    The installed entry is process-global, resolved at TRACE time
    (resolve_hint).  Last install wins — safe because every engine warms
    up (compiling all its programs) immediately after its own install,
    and the zero-new-compiles gate means no decode shape retraces later.
    Two engines CONSTRUCTED concurrently in one process with different
    geometries could cross hints; construct sequentially."""
    global _ACTIVE_HINTS, _ACTIVE_KEY
    key = hint_key(model, batch, page_size)
    entry = load_tuned_table(path).get(key)
    if isinstance(entry, dict):
        rec = entry.get("backend")
        here = jax.default_backend()
        if rec is not None and rec != here:
            logger.warning(
                "decode kernel: ignoring tuned hints for %s — recorded on "
                "%r, running on %r (re-sweep with tools/tune_decode.py)",
                key, rec, here,
            )
            entry = None
    _ACTIVE_HINTS = dict(entry) if isinstance(entry, dict) else None
    _ACTIVE_KEY = key
    if _ACTIVE_HINTS:
        logger.info("decode kernel: tuned hints for %s: %s", key, _ACTIVE_HINTS)
    return _ACTIVE_HINTS


def clear_tuned_hints() -> None:
    global _ACTIVE_HINTS, _ACTIVE_KEY
    _ACTIVE_HINTS = None
    _ACTIVE_KEY = None


def active_hints() -> Optional[Dict[str, Any]]:
    return _ACTIVE_HINTS


def resolve_hint(env_name: str, tuned_key: str, default: int) -> int:
    """env var > installed tuned entry > default (all ints)."""
    v = os.environ.get(env_name)
    if v is not None:
        return int(v)
    if _ACTIVE_HINTS is not None and tuned_key in _ACTIVE_HINTS:
        return int(_ACTIVE_HINTS[tuned_key])
    return default


def pages_per_vmem_budget(
    budget_bytes: int, page_size: int, kv2: int, head_dim: int, itemsize: int
) -> int:
    """Pages whose DOUBLE-BUFFERED scratch fits a VMEM byte budget — the
    one copy of the formula behind both the stock kernel's nkv hint
    (ragged_attention._decode_block_hints, itemsize 2: its VMEM working
    set is in the cast-up bf16 compute dtype regardless of page dtype)
    and the fused kernel's ppcb default (the PAGE dtype's width: pages
    land in scratch quantized, so int8 packs ~2x the bf16 block — the
    fused path's bandwidth win)."""
    return max(
        1, budget_bytes // max(1, 2 * page_size * kv2 * head_dim * itemsize)
    )


MAX_BLOCK_CTX = 512  # context positions per compute block (see _default_ppcb)


def _default_ppcb(page_size: int, kv2: int, head_dim: int, itemsize: int) -> int:
    """Fused-kernel pages per compute block: ``MAX_BLOCK_CTX`` positions,
    or fewer where the DYN_DECODE_NKV_MB budget (default 4MB, at the page
    dtype's width) holds fewer.  A block is the unit a row's work is
    counted in — its trip count is ``cdiv(live pages, ppcb)`` — so it is
    kept to a few hundred positions: a 600-token row then costs two
    blocks, not the 2048 positions the VMEM budget alone would allow
    (the sweep behind the number: PERF.md section 6, PR 26)."""
    budget = resolve_hint("DYN_DECODE_NKV_MB", "nkv_mb", 4) << 20
    return min(
        pages_per_vmem_budget(budget, page_size, kv2, head_dim, itemsize),
        max(1, MAX_BLOCK_CTX // page_size),
    )


# ------------------------------------------------------------------ kernel


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
    window: Optional[int] = None,
):
    """Build the kernel body for a static geometry.

    Grid (S, J): program (s, j) computes row ``s``'s attention over KV
    split ``j`` (pages [j*split_pages, (j+1)*split_pages)) and writes an
    UNNORMALIZED partial (o, m, l) — combined host-side by LSE.  Its
    block loop and its page copies are bounded by the pages the row HAS
    in that split, never by ``split_pages``.

    ``window``: the query attends to the last ``window`` positions of its
    context only (its own among them).  The caller's table then BEGINS at
    the first page the window reaches (``fused_decode_attention``), so the
    walk is over the window's pages and this is one more term of the mask.
    """
    C = ppcb * page_size  # context positions per compute block

    def kernel(
        # scalar prefetch (SMEM)
        kv_lens_ref,  # [S] int32
        page_indices_ref,  # [S, PP] int32
        num_seqs_ref,  # [1] int32
        # operands
        q_ref,  # [1, H, D] VMEM (row s)
        pages_ref,  # [P, ps, 2KV, D] HBM/ANY — DMA'd manually
        scale_ref,  # [1, 1] f32 SMEM — kv_scale (traced OK)
        # outputs (VMEM blocks at (s, j))
        o_ref,  # [1, 1, H, D] f32 — unnormalized sum(p·V)
        m_ref,  # [1, 1, H, 1] f32 — split max
        l_ref,  # [1, 1, H, 1] f32 — split sum(exp)
        # scratch
        kv_buf,  # [2, ppcb, ps, 2KV, D] pages dtype
        sems,  # DMA semaphores (2,)
    ):
        s = pl.program_id(0)
        j = pl.program_id(1)

        # Pages a short row never copies keep whatever the scratch held,
        # and the masked softmax weight 0 times a NaN is a NaN: start the
        # call from zeros.  Later rows then find earlier rows' pages
        # there — finite like the pool.  (Grid programs run in order on
        # one core: no dimension_semantics below.)
        @pl.when((s == 0) & (j == 0))
        def _():
            kv_buf[...] = jnp.zeros(kv_buf.shape, kv_buf.dtype)

        kv_len = kv_lens_ref[s]
        base_page = j * split_pages
        # Pages this split actually covers (tail splits truncate; rows
        # shorter than the split's base contribute nothing).
        # (Capped at the table's width: every page id read below is a
        # table entry, whatever kv_lens claims.)
        row_pages = jnp.minimum(pl.cdiv(kv_len, page_size), pages_per_seq)
        pages_here = jnp.clip(row_pages - base_page, 0, split_pages)
        # The split's coverage END, not just kv_len: the last compute
        # block of a split can reach past split_pages (ppcb granularity),
        # and without this cap those positions would be counted by BOTH
        # this split and the next — a double-count the LSE combine cannot
        # undo.
        split_end = jnp.minimum(kv_len, (base_page + split_pages) * page_size)
        active = (s < num_seqs_ref[0]) & (kv_len > 0) & (pages_here > 0)

        # Inactive programs still own their out blocks: neutral partials
        # (o=0, m=NEG_INF, l=0) vanish in the LSE combine.
        o_ref[0, 0] = jnp.zeros((num_kv * group, head_dim), jnp.float32)
        m_ref[0, 0] = jnp.full((num_kv * group, 1), NEG_INF, jnp.float32)
        l_ref[0, 0] = jnp.zeros((num_kv * group, 1), jnp.float32)

        def fetch(block, slot, start):
            # One DMA per LIVE page of the block: page ids are arbitrary
            # (PagedAttention indirection), so pages can't ride one
            # stride, and the row's last block stops at the row's last
            # page — what lies past it is neither copied nor waited for
            # (the position mask below never reads it).  wait() recreates
            # the descriptor — standard Pallas pattern; the semaphore
            # accounts per-copy.
            first = block * ppcb
            live = jnp.minimum(ppcb, pages_here - first)

            def one(t, carry=None):
                pid = page_indices_ref[s, base_page + first + t]
                dma = pltpu.make_async_copy(
                    pages_ref.at[pid], kv_buf.at[slot, t], sems.at[slot]
                )
                if start:
                    dma.start()
                else:
                    dma.wait()
                return carry

            # A full block (every block of a row but its last) is
            # straight-line code: the scalar core issues its copies back
            # to back, which a counted loop's branch a page does not allow
            # (sweep: PERF.md section 6, PR 26).
            @pl.when(live == ppcb)
            def _():
                for t in range(ppcb):
                    one(t)

            @pl.when(live < ppcb)
            def _():
                jax.lax.fori_loop(0, live, one, 0)

        @pl.when(active)
        def _():
            nblocks = pl.cdiv(pages_here, ppcb)
            fetch(0, 0, start=True)
            scale = scale_ref[0, 0]

            def block_step(b, carry):
                slot = jax.lax.rem(b, 2)

                @pl.when(b + 1 < nblocks)
                def _():
                    fetch(b + 1, jax.lax.rem(b + 1, 2), start=True)

                fetch(b, slot, start=False)
                buf = kv_buf[slot].reshape(C, 2 * num_kv, head_dim)
                # Heads to the front ONCE a block, while the values are
                # still page-dtype wide: slicing head h out of
                # [C, 2KV, D] gathers one sublane from each of C tiles,
                # and 2KV such slices were most of a block's time (sweep:
                # PERF.md section 6, PR 26).  Same values into the same
                # dots — the output is bit-identical.
                buf = jnp.transpose(buf, (1, 0, 2))  # [2KV, C, D]
                # Fused dequant: the ONLY f32 materialization of this KV
                # block is here in VMEM, one compute block at a time.
                kvf = buf.astype(jnp.float32) * scale
                pos = (base_page + b * ppcb) * page_size + (
                    jax.lax.broadcasted_iota(jnp.int32, (1, C), 1)
                )
                mask = pos < split_end  # [1, C]
                if window is not None:
                    mask &= pos >= kv_len - window
                out = []
                for h in range(num_kv):
                    m_h, l_h, acc_h = carry[3 * h], carry[3 * h + 1], carry[3 * h + 2]
                    k_h = kvf[2 * h]  # [C, D]
                    v_h = kvf[2 * h + 1]
                    qf = (
                        q_ref[0, h * group : (h + 1) * group, :].astype(
                            jnp.float32
                        )
                        * sm_scale
                    )  # [G, D]
                    logits = jax.lax.dot_general(
                        qf,
                        k_h,
                        (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    )  # [G, C]
                    logits = jnp.where(mask, logits, NEG_INF)
                    m_new = jnp.maximum(
                        m_h, jnp.max(logits, axis=1, keepdims=True)
                    )  # [G, 1]
                    # Mask the exp explicitly: a fully-masked block has
                    # m_new == m_h and exp(NEG_INF - m) can round to a
                    # nonzero subnormal only through the mask, never here.
                    p = jnp.where(mask, jnp.exp(logits - m_new), 0.0)
                    alpha = jnp.exp(m_h - m_new)  # [G, 1]
                    l_new = alpha * l_h + jnp.sum(p, axis=1, keepdims=True)
                    acc_new = alpha * acc_h + jax.lax.dot_general(
                        p,
                        v_h,
                        (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    )  # [G, D]
                    out.extend((m_new, l_new, acc_new))
                return tuple(out)

            init = []
            for _h in range(num_kv):
                init.extend(
                    (
                        jnp.full((group, 1), NEG_INF, jnp.float32),
                        jnp.zeros((group, 1), jnp.float32),
                        jnp.zeros((group, head_dim), jnp.float32),
                    )
                )
            final = jax.lax.fori_loop(0, nblocks, block_step, tuple(init))
            m_all = jnp.concatenate(
                [final[3 * h] for h in range(num_kv)], axis=0
            )  # [H, 1]
            l_all = jnp.concatenate(
                [final[3 * h + 1] for h in range(num_kv)], axis=0
            )
            o_all = jnp.concatenate(
                [final[3 * h + 2] for h in range(num_kv)], axis=0
            )  # [H, D]
            o_ref[0, 0] = o_all
            m_ref[0, 0] = m_all
            l_ref[0, 0] = l_all

    return kernel


def fused_decode_attention(
    q: jnp.ndarray,  # [S, num_heads, head_dim] — ONE query token per row
    pages: jnp.ndarray,  # [num_pages, page_size, 2*kv_heads, head_dim]
    kv_lens: jnp.ndarray,  # [S] int32 context length per row
    page_indices: jnp.ndarray,  # [S, pages_per_seq] int32
    num_seqs: jnp.ndarray,  # [1] int32 valid rows
    *,
    sm_scale: float,
    kv_scale=None,  # None | float | traced [] scalar — applied IN-KERNEL
    num_kv_splits: Optional[int] = None,
    pages_per_block: Optional[int] = None,
    interpret: Optional[bool] = None,
    window: Optional[int] = None,
) -> jnp.ndarray:
    """Host wrapper: fused-dequant decode attention + LSE split combine.

    ``window``: a layer that keeps the last ``window`` positions only.
    ``page_indices`` and ``kv_lens`` are then those of the row's WINDOW
    pages (the table begins at the first page the window reaches and
    ``kv_lens`` counts from that page's first position), so the walk is over
    a dozen pages whatever the context; the call carries a name of its own in
    the device trace (``window_decode_attention``).

    Knobs (env > tuned table > default; tools/tune_decode.py sweeps them):
    - ``DYN_DECODE_SPLITS`` / splits: KV-split grid width (0 = auto: 1 —
      one program a row walks all the row's blocks; the grid runs in
      order on one TensorCore, so a split is a second serial program and
      a combine, and an empty one still takes its turn).
    - ``DYN_DECODE_FUSED_PPCB`` / ppcb: pages per compute block (default
      ``MAX_BLOCK_CTX`` positions, fewer where the DYN_DECODE_NKV_MB VMEM
      budget at the PAGE dtype's width holds fewer).
    """
    S, H, D = q.shape
    P, ps, KV2, _ = pages.shape
    KV = KV2 // 2
    G = H // KV
    PP = page_indices.shape[1]

    ppcb = pages_per_block or resolve_hint(
        "DYN_DECODE_FUSED_PPCB",
        "ppcb",
        _default_ppcb(ps, KV2, D, pages.dtype.itemsize),
    )
    ppcb = max(1, min(ppcb, PP))
    splits = num_kv_splits or resolve_hint("DYN_DECODE_SPLITS", "splits", 0)
    if splits <= 0:  # auto
        splits = 1
    splits = min(splits, pl.cdiv(PP, ppcb))
    split_pages = pl.cdiv(PP, splits)
    splits = pl.cdiv(PP, split_pages)  # drop now-empty tail splits

    interpret = pallas_interpret() if interpret is None else interpret
    kernel = _make_kernel(
        sm_scale=sm_scale,
        num_kv=KV,
        group=G,
        head_dim=D,
        page_size=ps,
        pages_per_seq=PP,
        split_pages=split_pages,
        ppcb=ppcb,
        window=window,
    )
    scale_arr = jnp.asarray(
        1.0 if kv_scale is None else kv_scale, jnp.float32
    ).reshape(1, 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S, splits),
        in_specs=[
            pl.BlockSpec(
                (1, H, D), lambda s, j, *_: (s, 0, 0), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(memory_space=pl.ANY),  # pages stay in HBM
            pl.BlockSpec(memory_space=pltpu.SMEM),  # kv_scale
        ],
        out_specs=(
            pl.BlockSpec(
                (1, 1, H, D),
                lambda s, j, *_: (s, j, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, 1, H, 1),
                lambda s, j, *_: (s, j, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, 1, H, 1),
                lambda s, j, *_: (s, j, 0, 0),
                memory_space=pltpu.VMEM,
            ),
        ),
        scratch_shapes=[
            pltpu.VMEM((2, ppcb, ps, KV2, D), pages.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    o_part, m_part, l_part = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((S, splits, H, D), jnp.float32),
            jax.ShapeDtypeStruct((S, splits, H, 1), jnp.float32),
            jax.ShapeDtypeStruct((S, splits, H, 1), jnp.float32),
        ),
        compiler_params=pltpu.CompilerParams(
            # Same headroom as the stock path: the default 16MB scoped
            # budget is a compiler default, not the hardware ceiling.
            vmem_limit_bytes=64 << 20,
        ),
        interpret=interpret,
        name="fused_decode_attention" if window is None else "window_decode_attention",
    )(
        jnp.asarray(kv_lens, jnp.int32),
        jnp.asarray(page_indices, jnp.int32),
        jnp.asarray(num_seqs, jnp.int32),
        q,
        pages,
        scale_arr,
    )
    # Flash-Decoding LSE combine over the split axis.  All-masked rows
    # (padding / kv_len 0) have every m == NEG_INF and every l == 0:
    # alpha == 1 but o == 0, so out == 0 — matching the XLA oracle.
    m = m_part[..., 0]  # [S, J, H]
    l = l_part[..., 0]
    m_max = jnp.max(m, axis=1)  # [S, H]
    alpha = jnp.exp(m - m_max[:, None, :])  # [S, J, H]
    l_tot = jnp.sum(alpha * l, axis=1)  # [S, H]
    o_tot = jnp.sum(alpha[..., None] * o_part, axis=1)  # [S, H, D]
    out = o_tot / (l_tot[..., None] + 1e-30)
    return out.astype(q.dtype)
