"""Unified ragged paged attention: mixed prefill + decode in ONE kernel call.

This is the engine's core op from round 2 on.  A step is a flat run of
tokens — any mix of prompt chunks (many tokens of one sequence) and decode
tokens (one token each) — described by ``cu_q_lens`` row boundaries.  One
compiled program per *token-count bucket* covers every batch composition,
which is what keeps XLA recompiles rare (the round-1 design had separate
prefill/decode programs per (batch, seq-len) bucket pair and still hit
cold shapes in production mixes).

Implementations behind one contract (which serves is resolved ONCE, at
engine init, and reported on /metrics — no path here falls back to another):
- the repo's own Pallas kernels (ops/decode_attention.py,
  ops/prefill_attention.py): what ``auto`` picks on a TPU backend;
- ``stock``: ``jax.experimental.pallas.ops.tpu.ragged_paged_attention`` — the
  vLLM-TPU kernel (multi-page async-copy DMA, heads-block grid, online
  softmax in VMEM) on ``impl="tpu"``;
- ``xla``: static-shape gather + masked softmax, the oracle the tests and
  chip_smoke.py compare against.  Memory O(T · window · kv_heads ·
  head_dim) — fine for test shapes and toy head_dims, deliberately not
  used at real widths.
Which is fastest on the current machine: not measured.

Cache layout per layer (kernel contract): ``[num_pages, page_size,
2 * kv_heads, head_dim]`` with K at even combined-head indices and V at odd.
Layout reference: the reference's block storage is also page-major slabs
(lib/llm/src/kv/layer.rs:100-772); the combined-KV interleave is the TPU
kernel's requirement.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def pallas_interpret() -> bool:
    """Whether the repo's own Pallas kernels run under the Pallas
    interpreter when the caller passes no ``interpret=``.  It is something
    a caller ASKS for — ``DYN_PALLAS_INTERPRET=1``, which tests/conftest.py
    sets for the CPU test path — never something inferred from the
    backend: on the serving path the kernels compile or raise."""
    import os

    return os.environ.get("DYN_PALLAS_INTERPRET", "0") not in ("", "0")


def resolve_decode_kernel(value: str = "auto", attn_impl: str = "auto") -> str:
    """Resolve the decode attention kernel selector.

    Order: explicit config value > ``DYN_DECODE_KERNEL`` env > auto.
    - ``pallas_fused``: our fused-dequant split-KV kernel
      (ops/decode_attention.py) — compiled for the chip; under the Pallas
      interpreter only when asked (pallas_interpret).
    - ``stock``: the pre-existing path — the jax pallas
      ragged_paged_attention kernel on TPU, XLA gather fallback elsewhere.
    - ``xla``: force the XLA fallback everywhere (the bit-exactness
      oracle, even on TPU).
    ``auto`` picks pallas_fused on TPU and stock elsewhere, so default
    CPU behaviour (and every pre-existing test stream) is unchanged.

    ``attn_impl`` is the engine's attention backend: an operator forcing
    ``attn_impl="xla"`` (the oracle-numerics debugging contract) must not
    have ``auto`` route decode through the compiled fused kernel — auto
    resolves to ``stock`` there, which honours impl=xla end-to-end.  An
    EXPLICIT pallas_fused (config or env) still wins.
    """
    import os

    # Lazy: config.py is the canonical (dependency-free) home of the
    # kernel list — EngineConfig validation and the CLI choices share it.
    from ..engine.config import DECODE_KERNELS

    # ''/whitespace count as unset at both layers: a deployment template
    # rendering DYN_DECODE_KERNEL= (empty) must not fail worker boot.
    v = ((value or "auto").strip() or "auto").lower()
    if v == "auto":
        v = (
            os.environ.get("DYN_DECODE_KERNEL", "auto").strip() or "auto"
        ).lower()
    if v == "auto":
        v = "stock" if attn_impl == "xla" else (
            "pallas_fused" if on_tpu() else "stock"
        )
    if v not in DECODE_KERNELS:
        # Report the RESOLVED value: with config "auto" the offender is
        # usually a typo'd DYN_DECODE_KERNEL env var, not the config.
        raise ValueError(
            f"unknown decode kernel {v!r} (from config {value!r} / "
            f"DYN_DECODE_KERNEL; expected auto|{'|'.join(DECODE_KERNELS)})"
        )
    return v


def resolve_prefill_kernel(value: str = "auto", attn_impl: str = "auto") -> str:
    """Resolve the prefill attention kernel selector.

    Order: explicit config value > ``DYN_PREFILL_KERNEL`` env > auto.
    - ``pallas``: our chunked paged prefill kernel with in-kernel dequant
      and KV splits (ops/prefill_attention.py) — compiled for the chip;
      under the Pallas interpreter only when asked (pallas_interpret).
    - ``stock``: the pre-existing path — the jax pallas
      ragged_paged_attention kernel on TPU, XLA gather fallback elsewhere.
    - ``xla``: force the XLA fallback everywhere (the byte-identity
      oracle, even on TPU).
    ``auto`` picks pallas on TPU and stock elsewhere, so default CPU
    behaviour (and every pre-existing test stream) is unchanged.

    ``attn_impl`` mirrors resolve_decode_kernel: an operator forcing
    ``attn_impl="xla"`` must not have ``auto`` route prefill through the
    compiled kernel — auto resolves to ``stock`` there, which honours
    impl=xla end-to-end.  An EXPLICIT pallas (config or env) still wins.
    """
    import os

    from ..engine.config import PREFILL_KERNELS

    v = ((value or "auto").strip() or "auto").lower()
    if v == "auto":
        v = (
            os.environ.get("DYN_PREFILL_KERNEL", "auto").strip() or "auto"
        ).lower()
    if v == "auto":
        v = "stock" if attn_impl == "xla" else (
            "pallas" if on_tpu() else "stock"
        )
    if v not in PREFILL_KERNELS:
        # Report the RESOLVED value: with config "auto" the offender is
        # usually a typo'd DYN_PREFILL_KERNEL env var, not the config.
        raise ValueError(
            f"unknown prefill kernel {v!r} (from config {value!r} / "
            f"DYN_PREFILL_KERNEL; expected auto|{'|'.join(PREFILL_KERNELS)})"
        )
    return v


def quantize_for_cache(x: jnp.ndarray, dtype) -> jnp.ndarray:
    """Make already-scaled values representable in a quantized page dtype.

    int8: round-to-nearest + clip (astype truncates toward zero — biased —
    and wraps on overflow).  float8: clip to ±finfo.max (e4m3fn has NO inf,
    so casting past the max saturates to NaN and one NaN K row poisons
    every later attention read of the block).  Shared by the ragged write
    path and the engine's block-inject path so normal-prefill and
    injected/sp-prefilled blocks can never diverge numerically."""
    dtype = jnp.dtype(dtype)
    if jnp.issubdtype(dtype, jnp.integer):
        info = jnp.iinfo(dtype)
        x = jnp.clip(jnp.round(x.astype(jnp.float32)), info.min, info.max)
    elif dtype.itemsize == 1:
        fmax = float(jnp.finfo(dtype).max)
        x = jnp.clip(x.astype(jnp.float32), -fmax, fmax)
    return x.astype(dtype)


def write_kv_ragged(
    pages: jnp.ndarray,  # [num_pages, page_size, 2*kv_heads, head_dim]
    k_new: jnp.ndarray,  # [T, kv_heads, head_dim]
    v_new: jnp.ndarray,  # [T, kv_heads, head_dim]
    slot_mapping: jnp.ndarray,  # [T] int32 flat slot ids; -1 = padding (dropped)
    kv_scale=None,  # quantized cache: store value/scale (float OR traced scalar)
) -> jnp.ndarray:
    """Scatter new K/V rows into their cache slots (one combined scatter)."""
    P, ps, KV2, D = pages.shape
    T = k_new.shape[0]
    # Interleave to the combined layout: [T, KV, 2, D] -> [T, 2KV, D]
    # puts k_h at combined index 2h and v_h at 2h+1.
    comb = jnp.stack([k_new, v_new], axis=2).reshape(T, KV2, D)
    if kv_scale is not None:
        # kv_scale may be a per-layer traced scalar (the layer scan indexes
        # a [L] calibration vector), so no Python != 1.0 fast path here.
        comb = comb.astype(jnp.float32) / kv_scale
    comb = quantize_for_cache(comb, pages.dtype)
    slots = jnp.where(jnp.asarray(slot_mapping) < 0, P * ps, slot_mapping)
    flat = pages.reshape(P * ps, KV2, D)
    flat = flat.at[slots].set(comb, mode="drop")
    return flat.reshape(P, ps, KV2, D)


def _decode_block_hints(pages: jnp.ndarray, page_indices: jnp.ndarray):
    """Pallas block/grid hints for decode-shaped dispatches (every row one
    query token).  The kernel's default KV block spans all of pages_per_seq;
    at long context its double-buffered VMEM scratch exceeds the 16MB scoped
    limit; explicit 16-query blocks + a ~4MB-budget KV block are an earlier
    round's choice (not measured on this machine).  Tunable for hardware sweeps: DYN_DECODE_NQ query block,
    DYN_DECODE_NKV_MB KV block budget — each resolved env var > tuned-table
    entry installed at engine init (tools/tune_decode.py) > the defaults
    above, through the ONE precedence implementation (resolve_hint)."""
    from .decode_attention import pages_per_vmem_budget, resolve_hint

    ps, KV2, hd = pages.shape[1], pages.shape[2], pages.shape[3]
    budget = resolve_hint("DYN_DECODE_NKV_MB", "nkv_mb", 4) << 20
    # itemsize 2: the stock kernel's VMEM working set is in the cast-up
    # bf16 compute dtype regardless of the page dtype (see the helper).
    nkv = pages_per_vmem_budget(budget, ps, KV2, hd, 2)
    nkv = min(page_indices.shape[1], nkv)
    nq = resolve_hint("DYN_DECODE_NQ", "nq", 16)
    return nq, nkv


def ragged_decode_attention(
    q: jnp.ndarray,  # [S, num_heads, head_dim] — ONE query token per row
    pages: jnp.ndarray,  # [num_pages, page_size, 2*kv_heads, head_dim]
    kv_lens: jnp.ndarray,  # [S] int32 context length per row
    page_indices: jnp.ndarray,  # [S, pages_per_seq] int32
    num_seqs: jnp.ndarray,  # [1] int32 valid rows
    *,
    sm_scale: float,
    impl: str = "xla",  # "tpu" | "xla"
    kv_scale: float | None = None,
    kernel: str = "stock",  # "pallas_fused" | "stock" | "xla"
    window: int | None = None,  # see ragged_attention
) -> jnp.ndarray:
    """Decode-specialized attention: every row is exactly ONE query token
    (the fused multi-step decode program's shape — engine/pipeline.py).

    The unified entry (``ragged_attention``) must handle arbitrary
    prefill/decode mixes, which costs it per-token ``cu_q_lens``
    bookkeeping: a searchsorted row lookup and tail-position arithmetic per
    query token.  Here row ``i``'s single query sits at context position
    ``kv_lens[i] - 1`` by construction, so the row map is the identity and
    the causal mask is just ``ctx < kv_len``.

    ``kernel`` selects the implementation (resolve_decode_kernel /
    DYN_DECODE_KERNEL):
    - "pallas_fused": our fused-dequant split-KV decode kernel
      (ops/decode_attention.py) — ``kv_scale`` (static OR traced) is
      applied IN-KERNEL, so quantized pages stream from HBM once at
      1 byte/value.
    - "stock": the pre-existing routing — the jax pallas kernel with the
      decode-tuned block hints on ``impl == "tpu"``, XLA fallback
      otherwise.
    - "xla": force the XLA fallback (the bit-exactness oracle) — a direct
      [S, W] row gather, no searchsorted, no cu_q_lens — numerically
      identical to the unified fallback on decode shapes.
    """
    S, H, D = q.shape
    if kernel == "pallas_fused":
        from .decode_attention import fused_decode_attention

        # Compiles or raises: a fallback here would leave every
        # decode_kernel reporting surface (bench JSON, /metrics info
        # gauge) claiming pallas_fused while another path served.
        return fused_decode_attention(
            q,
            pages,
            kv_lens,
            page_indices,
            num_seqs,
            sm_scale=sm_scale,
            kv_scale=kv_scale,
            window=window,
        )
    if kernel == "xla":
        impl = "xla"
    elif kernel != "stock":
        raise ValueError(f"unknown decode kernel {kernel!r}")
    if impl == "tpu" and window is not None:
        raise ValueError("the stock kernel has no window; use decode_kernel pallas_fused or xla")
    if impl == "tpu":
        from jax.experimental.pallas.ops.tpu.ragged_paged_attention import (
            ragged_paged_attention,
        )

        nq, nkv = _decode_block_hints(pages, page_indices)
        # One token per row: cumulative query lengths are the identity.
        cu = jnp.arange(S + 1, dtype=jnp.int32)
        # The stock kernel's per-row loop needs a context of at least one
        # token; a row without one (the fused decode program's padding
        # rows) attends over one garbage token and is zeroed below, which
        # is what the other two implementations return for it.
        has_ctx = jnp.asarray(kv_lens) > 0
        kv_lens = jnp.maximum(kv_lens, 1)
        # Unit scale for quantized pages without an explicit one — see the
        # matching comment in ragged_attention.
        unit = 1.0 if pages.dtype.itemsize == 1 and kv_scale is None else kv_scale
        out = ragged_paged_attention(
            q,
            pages,
            kv_lens,
            page_indices,
            cu,
            num_seqs,
            sm_scale=sm_scale,
            num_queries_per_block=nq,
            num_kv_pages_per_block=nkv,
            vmem_limit_bytes=64 << 20,
            k_scale=unit,
            v_scale=unit,
        )
        return jnp.where(has_ctx[:, None, None], out, 0)
    if impl != "xla":
        raise ValueError(f"unknown ragged attention impl {impl!r}")

    kv_lens = jnp.asarray(kv_lens)
    page_indices = jnp.asarray(page_indices)
    num_seqs = jnp.asarray(num_seqs)

    ps = pages.shape[1]
    KV = pages.shape[2] // 2
    G = H // KV
    W = page_indices.shape[1] * ps

    ctx = jnp.arange(W, dtype=jnp.int32)
    # Row map is the identity: gather each row's context directly.
    slots = page_indices[:, ctx // ps] * ps + ctx % ps  # [S, W]
    kv = pages.reshape(-1, 2 * KV, D)[slots]  # [S, W, 2KV, D]
    k = kv[:, :, 0::2].astype(jnp.float32)  # [S, W, KV, D]
    v = kv[:, :, 1::2].astype(jnp.float32)
    # The != 1.0 fast path only for PYTHON floats: a traced per-layer
    # scale (the fused kernel's native contract, reachable here through
    # its toy-shape fallback) cannot be compared at trace time.
    if kv_scale is not None and (
        not isinstance(kv_scale, (int, float)) or kv_scale != 1.0
    ):
        k = k * kv_scale
        v = v * kv_scale

    valid = jnp.arange(S, dtype=jnp.int32) < num_seqs[0]
    qf = q.reshape(S, KV, G, D).astype(jnp.float32) * sm_scale
    logits = jnp.einsum("skgd,swkd->skgw", qf, k)  # [S, KV, G, W]
    mask = (ctx[None, :] < kv_lens[:, None]) & valid[:, None]  # [S, W]
    if window is not None:
        mask &= ctx[None, :] >= kv_lens[:, None] - window
    logits = jnp.where(mask[:, None, None, :], logits, NEG_INF)
    m = jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.exp(logits - m) * mask[:, None, None, :]
    out = jnp.einsum("skgw,swkd->skgd", p, v) / (
        jnp.sum(p, axis=-1, keepdims=True) + 1e-30
    )
    return out.reshape(S, H, D).astype(q.dtype)


def ragged_attention(
    q: jnp.ndarray,  # [T, num_heads, head_dim]
    pages: jnp.ndarray,  # [num_pages, page_size, 2*kv_heads, head_dim]
    kv_lens: jnp.ndarray,  # [S] int32 context length per sequence row
    page_indices: jnp.ndarray,  # [S, pages_per_seq] int32
    cu_q_lens: jnp.ndarray,  # [S+1] int32 cumulative query lengths
    num_seqs: jnp.ndarray,  # [1] int32 valid rows of the above
    *,
    sm_scale: float,
    impl: str = "xla",  # "tpu" | "xla"
    kv_scale: float | None = None,  # quantized cache: value = stored * scale
    decode: bool = False,  # static hint: every row is a 1-token decode row
    decode_kernel: str = "stock",  # decode-path kernel (resolve_decode_kernel)
    prefill_kernel: str = "stock",  # non-decode kernel (resolve_prefill_kernel)
    window: int | None = None,  # a layer that keeps the last ``window`` positions
) -> jnp.ndarray:
    """Causal attention of each token against its sequence's paged context.

    ``window``: a query at position t attends to positions j with
    ``0 <= t - j < window``.  The caller hands such a layer the rows' WINDOW
    tables (engine/kv_manager.py): ``page_indices[i]`` begins at the first
    page the window of row i's first query reaches, ``kv_lens[i]`` counts
    from that page's first position, and positions are relative to it in
    every implementation alike, so a window layer WALKS its window's pages
    and masks nothing of the context before them.

    Row i's queries are the LAST (cu_q_lens[i+1]-cu_q_lens[i]) tokens of its
    kv_lens[i]-token context (their K/V must already be written — callers run
    write_kv_ragged first).  Tokens at or past cu_q_lens[num_seqs] are
    padding and produce zeros.

    ``kv_scale`` supports quantized (fp8/int8) page dtypes with one static
    per-tensor scale — the TPU kernel's native k_scale/v_scale contract;
    the write side stores value/scale (write_kv_ragged).

    ``decode=True`` routes to ``ragged_decode_attention``: the fused
    multi-step decode program's shape (one query token per row) skips the
    cu_q_lens generality entirely and always gets the decode-tuned pallas
    block hints.

    ``prefill_kernel`` selects the NON-decode implementation
    (resolve_prefill_kernel / DYN_PREFILL_KERNEL):
    - "pallas": our chunked paged prefill kernel
      (ops/prefill_attention.py) — ``kv_scale`` (static OR traced) is
      applied IN-KERNEL and the prior prefix streams straight from the
      paged blocks.
    - "stock": the pre-existing routing below (jax pallas kernel on
      ``impl == "tpu"``, XLA fallback otherwise).
    - "xla": force the XLA fallback (the byte-identity oracle).
    """
    if decode:
        return ragged_decode_attention(
            q,
            pages,
            kv_lens,
            page_indices,
            num_seqs,
            sm_scale=sm_scale,
            impl=impl,
            kv_scale=kv_scale,
            kernel=decode_kernel,
            window=window,
        )
    if prefill_kernel == "pallas":
        from .prefill_attention import fused_prefill_attention

        # Compiles or raises (see ragged_decode_attention).
        return fused_prefill_attention(
            q,
            pages,
            kv_lens,
            page_indices,
            cu_q_lens,
            num_seqs,
            sm_scale=sm_scale,
            kv_scale=kv_scale,
            window=window,
        )
    if prefill_kernel == "xla":
        impl = "xla"
    elif prefill_kernel != "stock":
        raise ValueError(f"unknown prefill kernel {prefill_kernel!r}")
    if impl == "tpu" and window is not None:
        raise ValueError("the stock kernel has no window; use prefill_kernel pallas or xla")
    if impl == "tpu":
        from jax.experimental.pallas.ops.tpu.ragged_paged_attention import (
            ragged_paged_attention,
        )

        # Block sizing: the kernel replaces BOTH block params with its tuned
        # table whenever EITHER is None — a partial override is silently
        # discarded.  Prefill and mixed shapes run the kernel's tuned table
        # under the raised vmem limit; decode shapes never reach here
        # (routed to ragged_decode_attention above, which passes the
        # repo's decode hints).
        nkv = nq = None
        # Quantized (1-byte) pages: real scaling is folded around this call
        # by the model (q pre-scaled, output post-scaled — models/llama.py),
        # but the kernel only CASTS fp8/int8 K/V up to q's dtype inside its
        # `if k_scale is not None` branch — so a unit scale must be passed
        # or raw quantized values feed the MXU dot and tracing rejects.
        unit = 1.0 if pages.dtype.itemsize == 1 and kv_scale is None else kv_scale
        return ragged_paged_attention(
            q,
            pages,
            kv_lens,
            page_indices,
            cu_q_lens,
            num_seqs,
            sm_scale=sm_scale,
            num_queries_per_block=nq,
            num_kv_pages_per_block=nkv,
            # The default 16MB scoped-vmem budget is a compiler default,
            # not the hardware ceiling; long-context shapes need headroom
            # (vLLM's TPU backend raises it the same way).
            vmem_limit_bytes=64 << 20,
            k_scale=unit,
            v_scale=unit,
        )
    if impl != "xla":
        raise ValueError(f"unknown ragged attention impl {impl!r}")

    # Coerce metadata to jnp: callers may hand numpy arrays outside jit,
    # and mixing numpy containers with traced indices fails inside scan.
    kv_lens = jnp.asarray(kv_lens)
    page_indices = jnp.asarray(page_indices)
    cu_q_lens = jnp.asarray(cu_q_lens)
    num_seqs = jnp.asarray(num_seqs)

    T, H, D = q.shape
    S, PP = page_indices.shape
    ps = pages.shape[1]
    KV = pages.shape[2] // 2
    G = H // KV
    W = PP * ps

    tok = jnp.arange(T, dtype=jnp.int32)
    # Sequence row of each token; padding tokens clamp to the last row and
    # are masked out below.
    seq = jnp.searchsorted(cu_q_lens[1:], tok, side="right").astype(jnp.int32)
    seq = jnp.minimum(seq, S - 1)
    valid = tok < cu_q_lens[num_seqs[0]]
    q_len = cu_q_lens[seq + 1] - cu_q_lens[seq]
    # Global context position of each query token (queries are the tail).
    qpos = kv_lens[seq] - q_len + (tok - cu_q_lens[seq])

    ctx = jnp.arange(W, dtype=jnp.int32)
    slots = page_indices[seq][:, ctx // ps] * ps + ctx % ps  # [T, W]
    kv = pages.reshape(-1, 2 * KV, D)[slots]  # [T, W, 2KV, D]
    k = kv[:, :, 0::2].astype(jnp.float32)  # [T, W, KV, D]
    v = kv[:, :, 1::2].astype(jnp.float32)
    if kv_scale is not None and kv_scale != 1.0:
        k = k * kv_scale
        v = v * kv_scale

    qf = q.reshape(T, KV, G, D).astype(jnp.float32) * sm_scale
    logits = jnp.einsum("tkgd,twkd->tkgw", qf, k)  # [T, KV, G, W]
    mask = (ctx[None, :] <= qpos[:, None]) & valid[:, None]  # [T, W]
    if window is not None:
        mask &= ctx[None, :] > qpos[:, None] - window
    logits = jnp.where(mask[:, None, None, :], logits, NEG_INF)
    m = jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.exp(logits - m) * mask[:, None, None, :]
    out = jnp.einsum("tkgw,twkd->tkgd", p, v) / (
        jnp.sum(p, axis=-1, keepdims=True) + 1e-30
    )
    return out.reshape(T, H, D).astype(q.dtype)
