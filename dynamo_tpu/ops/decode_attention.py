"""Fused-dequant ragged paged DECODE attention — our own Pallas TPU kernel.

The stock ``jax.experimental.pallas.ops.tpu.ragged_paged_attention`` kernel
only CASTS quantized (int8/fp8) KV pages up to the query dtype and never
applies ``kv_scale`` in-kernel, so the model folds dequant algebraically
around the call (q pre-scaled, output post-scaled — models/llama.py) and
the decode step's dominant HBM stream still rides a generic mixed
prefill/decode kernel.  This kernel is specialised for the one shape the
fused decode program dispatches — ONE query token per row, identity row map
(``ragged_decode_attention``); at the benchmark cells' shapes it runs 1.7 to
6 times faster than the stock kernel (chip sweep: docs/decode_kernel.md):

1. **Fused dequant**: int8/fp8 KV pages are DMA'd quantized and reach the
   QK/AV dots as bf16, the narrowest type the matrix units take that
   holds every such value exactly; ``kv_scale`` multiplies the logits
   (with ``sm_scale``) and the program's output, never a cached value
   (``q·(K·s) = (q·K)·s``, ``p·(V·s) = (p·V)·s``) — the KV stream is read
   from HBM ONCE at 1 byte/value and never materialized dequantized.
   int8 pages are read as the 32-bit WORDS they are stored as, so a shift
   pair a byte takes a head's rows out with no transposition
   (``_make_kernel``).  The scale is an SMEM scalar operand, so per-layer
   TRACED calibration scales work natively (the stock kernel's
   k_scale/v_scale must be static floats, which is why dequant lived
   outside it).
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
import math
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

P_PIECES = 3  # bf16 pieces the float32 softmax weights enter the AV dot as

# Operands of the last kernel built in this process ("bf16" | "float32"):
# what /metrics reports beside the kernel's name (llm/metrics.py).
_BUILT_OPERANDS: Optional[str] = None


def operand_dtype(q_dtype, pages_dtype):
    """The type K, V and q enter the dots in: bf16 where it holds every
    value exactly (pages one byte wide, int8 or fp8, or bf16 themselves,
    under a bf16 ``q``) — a product of two such values is exact in the
    float32 accumulator.  Nothing is given up for it: a float32 dot in a
    Mosaic kernel is ONE bf16 pass on a v5e that ROUNDS both operands
    (measured: docs/decode_kernel.md), so handing the matrix units exact
    bf16 is the more precise form, not the cheaper one.  float32 otherwise
    (the tests' oracle shapes)."""
    pages_dtype = jnp.dtype(pages_dtype)
    exact = pages_dtype == jnp.bfloat16 or pages_dtype.itemsize == 1
    if exact and jnp.dtype(q_dtype) == jnp.bfloat16:
        return jnp.dtype(jnp.bfloat16)
    return jnp.dtype(jnp.float32)


def built_operands() -> Optional[str]:
    return _BUILT_OPERANDS


def _bf16_pieces(x, n: int):
    """``n`` bf16 arrays that sum to float32 ``x`` to within 2**(-8n) of
    it: each takes the leading 8 bits of what the others left."""
    pieces = []
    for i in range(n):
        piece = x.astype(jnp.bfloat16)
        pieces.append(piece)
        if i + 1 < n:
            x = x - piece.astype(jnp.float32)
    return pieces


def word_rows(pages_dtype, kv2: int) -> int:
    """32-bit words a cached position's ``2KV`` rows make (the WORD VIEW of
    int8 pages), or 0 where the view does not apply (another page type, or
    ``2KV`` no multiple of 4): ``_make_kernel``."""
    if jnp.dtype(pages_dtype) == jnp.int8 and kv2 % 4 == 0:
        return kv2 // 4
    return 0


def _make_kernel(
    *,
    sm_scale: float,
    operands,
    groups: int,
    rows: int,
    words: int,
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

    ``operands`` (``operand_dtype``): the type of both dots' operands.
    Every scalar factor is applied on the small side: ``sm_scale *
    kv_scale`` to the logits, ``kv_scale`` to the program's output
    (``alpha * acc`` is linear, so the running carry needs nothing else);
    the cached block is converted, never multiplied.

    A block's work is ``groups`` pairs of dots over ``rows`` query rows
    each (``q_ref`` holds them in that order: the wrapper's):

    - ``words`` 0: a group is one K/V head and its ``G`` query heads; the
      block is transposed heads-first while page-dtype wide.
    - ``words`` J > 0 (int8 pages, ``word_rows``): the block is read as
      the 32-bit WORDS it is stored as.  A position's ``2KV`` int8 rows lie
      four to a word, J words a position, so byte ``b`` of all words is
      ``[C * J, D]``: row ``(position, j)`` holds page row ``4 j + b`` — K
      (b even) or V (b odd) of head ``2 j + b // 2``.  ONE shift pair a
      byte yields a dot's operand with NO transposition (the int8
      transposition was two fifths of the kernel's compute; chip sweep,
      docs/decode_kernel.md).  The two groups are the byte pairs (0, 1)
      and (2, 3); a group's J heads share its dots, ``rows`` = J x the
      padded ``G``, and a logit whose column is another head's position is
      masked like one past the row's end (its softmax weight 0 also keeps
      that head's V out of the sum).  The matrix units see every cached
      value once either way; only the small side grows.
    """
    C = ppcb * page_size  # context positions per compute block
    cols = C * max(words, 1)  # columns of a group's logits
    heads = groups * rows
    bf16 = operands == jnp.bfloat16
    AV = (((1,), (0,)), ((), ()))

    def av(p, v):
        """``p @ v`` with float32 ``p`` [rows, cols].  Under bf16 operands
        p is small and meets the SAME exact V as bf16 pieces that sum to
        it, smallest first; the large operand is never split.  The pieces
        are stacked along rows (whole bf16 tiles: the wrapper's padding)
        into ONE dot, so V enters the matrix units once."""
        if not bf16:
            return jax.lax.dot_general(p, v, AV, preferred_element_type=jnp.float32)
        stacked = jax.lax.dot_general(
            jnp.concatenate(_bf16_pieces(p, P_PIECES), axis=0), v, AV,
            preferred_element_type=jnp.float32,
        )  # [P_PIECES * rows, D]
        out = stacked[(P_PIECES - 1) * rows :]
        for n in reversed(range(P_PIECES - 1)):
            out = out + stacked[n * rows : (n + 1) * rows]
        return out

    def kernel(
        # scalar prefetch (SMEM)
        kv_lens_ref,  # [S] int32
        page_indices_ref,  # [S, PP] int32
        num_seqs_ref,  # [1] int32
        # operands
        q_ref,  # [1, heads, D] VMEM (row s), rows in the groups' order
        pages_ref,  # [P, ps, 2KV, D] HBM/ANY — DMA'd manually
        scale_ref,  # [1, 1] f32 SMEM — kv_scale (traced OK)
        # outputs (VMEM blocks at (s, j))
        o_ref,  # [1, 1, heads, D] f32 — unnormalized sum(p·V)
        m_ref,  # [1, 1, heads, 1] f32 — split max
        l_ref,  # [1, 1, heads, 1] f32 — split sum(exp)
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
        o_ref[0, 0] = jnp.zeros((heads, head_dim), jnp.float32)
        m_ref[0, 0] = jnp.full((heads, 1), NEG_INF, jnp.float32)
        l_ref[0, 0] = jnp.zeros((heads, 1), jnp.float32)

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
            kv_scale = scale_ref[0, 0]
            logit_scale = sm_scale * kv_scale
            # The model's q, unscaled, sliced once a program.
            q_all = q_ref[0].astype(operands)  # [heads, D]
            q_groups = [q_all[g * rows : (g + 1) * rows] for g in range(groups)]
            # The block position each logits column stands for, and (word
            # view) whether the column's head is the row's.
            col = jax.lax.broadcasted_iota(jnp.int32, (1, cols), 1)
            if words:
                col_pos = col // words
                own = (
                    jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1) % words
                ) == jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0) // (
                    rows // words
                )
            else:
                col_pos = col

            def block_step(b, carry):
                slot = jax.lax.rem(b, 2)

                @pl.when(b + 1 < nblocks)
                def _():
                    fetch(b + 1, jax.lax.rem(b + 1, 2), start=True)

                fetch(b, slot, start=False)
                if words:
                    # The scratch as the words it holds: a view, no copy.
                    block = kv_buf.bitcast(jnp.int32).reshape(2, cols, head_dim)[slot]

                    def operand(r):  # byte r of every word, sign-extended
                        x = jax.lax.shift_left(block, 24 - 8 * r) if r < 3 else block
                        x = jax.lax.shift_right_arithmetic(x, 24)
                        return x.astype(jnp.float32).astype(operands)

                else:
                    block = kv_buf[slot].reshape(C, -1, head_dim)  # [C, 2KV, D]
                    # Heads to the front ONCE a block, while the values are
                    # still page-dtype wide: slicing head h out of
                    # [C, 2KV, D] gathers one sublane from each of C tiles,
                    # and 2KV such slices were most of a block's time
                    # (sweep: PERF.md section 6, PR 26).
                    block = jnp.transpose(block, (1, 0, 2))  # [2KV, C, D]
                    # In the dots' operand type, UNSCALED: a conversion
                    # alone (none at all for bf16 or float32 pages).
                    block = block.astype(operands)

                    def operand(r):  # page row r of every position
                        return block[r]

                pos = (base_page + b * ppcb) * page_size + col_pos
                mask = pos < split_end  # [1, cols]
                if window is not None:
                    mask &= pos >= kv_len - window
                if words:
                    mask = own & mask  # [rows, cols]
                out = []
                for g in range(groups):
                    m_g, l_g, acc_g = carry[3 * g], carry[3 * g + 1], carry[3 * g + 2]
                    logits = jax.lax.dot_general(
                        q_groups[g],
                        operand(2 * g),  # K [cols, D]
                        (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    ) * logit_scale  # [rows, cols]
                    logits = jnp.where(mask, logits, NEG_INF)
                    m_new = jnp.maximum(
                        m_g, jnp.max(logits, axis=1, keepdims=True)
                    )  # [rows, 1]
                    # Mask the exp explicitly: a fully-masked block has
                    # m_new == m_g and exp(NEG_INF - m) can round to a
                    # nonzero subnormal only through the mask, never here.
                    p = jnp.where(mask, jnp.exp(logits - m_new), 0.0)
                    alpha = jnp.exp(m_g - m_new)  # [rows, 1]
                    l_new = alpha * l_g + jnp.sum(p, axis=1, keepdims=True)
                    acc_new = alpha * acc_g + av(p, operand(2 * g + 1))  # V
                    out.extend((m_new, l_new, acc_new))
                return tuple(out)

            init = []
            for _g in range(groups):
                init.extend(
                    (
                        jnp.full((rows, 1), NEG_INF, jnp.float32),
                        jnp.zeros((rows, 1), jnp.float32),
                        jnp.zeros((rows, head_dim), jnp.float32),
                    )
                )
            final = jax.lax.fori_loop(0, nblocks, block_step, tuple(init))
            m_all = jnp.concatenate(final[0::3], axis=0)  # [heads, 1]
            l_all = jnp.concatenate(final[1::3], axis=0)
            o_all = jnp.concatenate(final[2::3], axis=0)  # [heads, D]
            o_ref[0, 0] = o_all * kv_scale
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
    out = _attend(
        q, pages, kv_lens, page_indices, num_seqs, sm_scale=sm_scale,
        kv_scale=kv_scale, num_kv_splits=num_kv_splits,
        pages_per_block=pages_per_block, interpret=interpret, window=window,
    )
    return out.astype(q.dtype)


def _attend(
    q, pages, kv_lens, page_indices, num_seqs, *, sm_scale, kv_scale=None,
    num_kv_splits=None, pages_per_block=None, interpret=None, window=None,
) -> jnp.ndarray:
    """``fused_decode_attention`` before the result takes ``q``'s type:
    float32 [S, H, D] (what the precision tests compare)."""
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
    global _BUILT_OPERANDS
    operands = operand_dtype(q.dtype, pages.dtype)
    _BUILT_OPERANDS = operands.name.replace("bfloat16", "bf16")
    words = word_rows(pages.dtype, KV2)
    # The kernel's query rows: ``groups`` dots of ``rows`` rows each.  Word
    # view: group i (a byte pair) holds the heads 2j + i, j < words; else a
    # group is one K/V head.  Under bf16 operands G is padded so that a
    # group's rows are whole bf16 tiles of 16 (what lets p's pieces ride ONE
    # dot); the padding is zeros — finite logits — and is dropped below.
    groups, per = (2, words) if words else (KV, 1)
    tile = 16 // math.gcd(per, 16) if operands == jnp.bfloat16 else 1
    gp = -(-G // tile) * tile
    rows = per * gp
    qk = jnp.pad(
        q.reshape(S, per, groups, G, D),
        ((0, 0), (0, 0), (0, 0), (0, gp - G), (0, 0)),
    )
    qk = jnp.transpose(qk, (0, 2, 1, 3, 4)).reshape(S, groups * rows, D)
    HK = groups * rows
    kernel = _make_kernel(
        sm_scale=sm_scale,
        operands=operands,
        groups=groups,
        rows=rows,
        words=words,
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
                (1, HK, D), lambda s, j, *_: (s, 0, 0), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(memory_space=pl.ANY),  # pages stay in HBM
            pl.BlockSpec(memory_space=pltpu.SMEM),  # kv_scale
        ],
        out_specs=(
            pl.BlockSpec(
                (1, 1, HK, D),
                lambda s, j, *_: (s, j, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, 1, HK, 1),
                lambda s, j, *_: (s, j, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, 1, HK, 1),
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
            jax.ShapeDtypeStruct((S, splits, HK, D), jnp.float32),
            jax.ShapeDtypeStruct((S, splits, HK, 1), jnp.float32),
            jax.ShapeDtypeStruct((S, splits, HK, 1), jnp.float32),
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
        qk,
        pages,
        scale_arr,
    )
    # Flash-Decoding LSE combine over the split axis.  All-masked rows
    # (padding / kv_len 0) have every m == NEG_INF and every l == 0:
    # alpha == 1 but o == 0, so out == 0 — matching the XLA oracle.
    m = m_part[..., 0]  # [S, J, HK]
    l = l_part[..., 0]
    m_max = jnp.max(m, axis=1)  # [S, HK]
    alpha = jnp.exp(m - m_max[:, None, :])  # [S, J, HK]
    l_tot = jnp.sum(alpha * l, axis=1)  # [S, HK]
    o_tot = jnp.sum(alpha[..., None] * o_part, axis=1)  # [S, HK, D]
    out = o_tot / (l_tot[..., None] + 1e-30)
    # Back to the model's head order, the padding dropped.
    out = jnp.transpose(out.reshape(S, groups, per, gp, D), (0, 2, 1, 3, 4))
    return out[:, :, :, :G].reshape(S, H, D)
