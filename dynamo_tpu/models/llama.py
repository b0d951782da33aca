"""Llama-family forward pass (dense + Mixtral-style MoE) with paged KV.

This replaces the reference's delegated engines (vLLM/sglang subprocesses —
SURVEY.md §2.8): the model is a pure function over a params pytree, executed
under jit on a device mesh.  TPU-first choices:

- layer weights are *stacked* [L, ...]; prefill/mixed programs run the
  decoder as one ``lax.scan`` (one compiled layer body regardless of depth,
  fast compiles across 7 token buckets), while the fused DECODE program
  unrolls the layer loop with static indices so XLA prefetches layer l+1's
  weights during layer l — decode is weights-bandwidth-bound and a scan's
  dynamic slices block that prefetch (measured ~25% on v5e);
- all shapes static: queries padded per bucket, padding tokens carry slot -1
  (dropped by the cache scatter) and are never read back (masked gather);
- bfloat16 weights/activations (MXU-native), f32 softmax/norm accumulations,
  f32 logits for sampling;
- one forward for prefill (Sq = bucket) and decode (Sq = 1) — same code path,
  attention always reads the paged cache it just wrote.

Tensor-parallel sharding is applied externally via pjit shardings
(parallel/mesh.py): heads shard over the "tp" mesh axis, XLA inserts the ICI
collectives.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from ..ops.quant_matmul import qdot
from ..ops.ragged_attention import ragged_attention, write_kv_ragged
from ..ops.rope import apply_rope, rope_frequencies
from .config import ModelConfig
from .moe import init_moe_params, moe_mlp

Params = Dict[str, Any]


def linear(x: jnp.ndarray, lp: Params, name: str, out_dtype=None) -> jnp.ndarray:
    """``x @ lp[name]``, dispatching on quantization: an int8 weight leaf is
    recognised by its sibling ``name + "_scale"`` (models/quant.py) and runs
    the native int8 MXU path (ops/quant_matmul.qdot)."""
    w = lp[name]
    s = lp.get(name + "_scale")
    if s is None:
        r = x @ w
        return r.astype(out_dtype) if out_dtype is not None else r
    return qdot(x, w, s, out_dtype=out_dtype)


def qkv_proj(x: jnp.ndarray, lp: Params, q_size: int, kv_size: int):
    """q/k/v projections, using the fused wqkv leaf when present
    (models/quant.py fuse_projections — single dot + static splits)."""
    if "wqkv" in lp:
        qkv = linear(x, lp, "wqkv")
        if "bqkv" in lp:
            qkv = qkv + lp["bqkv"]
        return jnp.split(qkv, [q_size, q_size + kv_size], axis=-1)
    q, k, v = linear(x, lp, "wq"), linear(x, lp, "wk"), linear(x, lp, "wv")
    if "bq" in lp:  # Qwen2-style attention biases
        q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
    return q, k, v


def mlp(x: jnp.ndarray, lp: Params) -> jnp.ndarray:
    """SwiGLU FFN, using the fused w_gateup leaf when present."""
    if "w_gateup" in lp:
        gu = linear(x, lp, "w_gateup", jnp.float32)
        F = gu.shape[-1] // 2
        gate = jax.nn.silu(gu[..., :F]).astype(x.dtype)
        up = gu[..., F:].astype(x.dtype)
        return linear(gate * up, lp, "w_down")
    gate = jax.nn.silu(linear(x, lp, "w_gate", jnp.float32)).astype(x.dtype)
    return linear(gate * linear(x, lp, "w_up"), lp, "w_down")


def embed_lookup(params: Params, token_ids: jnp.ndarray, dtype) -> jnp.ndarray:
    """Token embedding gather; int8 embeds dequantize the gathered rows by
    their per-row scale (scale axis = vocab row, shared with the tied head)."""
    e = params["embed"][token_ids]
    s = params.get("embed_scale")
    if s is None:
        return e
    return (e.astype(jnp.float32) * s[token_ids][:, None]).astype(dtype)


def lm_logits(params: Params, h_last: jnp.ndarray) -> jnp.ndarray:
    """Final-norm hidden rows → f32 logits, through lm_head or the tied
    embedding, quantized or not."""
    head = params.get("lm_head")
    if head is not None:
        s = params.get("lm_head_scale")
        if s is None:
            return (h_last @ head).astype(jnp.float32)
        return qdot(h_last, head, s, out_dtype=jnp.float32)
    s = params.get("embed_scale")
    if s is None:
        return (h_last @ params["embed"].T).astype(jnp.float32)
    return qdot(h_last, params["embed"].T, s, out_dtype=jnp.float32)


class PagedKVCache(NamedTuple):
    """Page-major per-layer KV slabs in the TPU ragged-attention layout:
    ``[num_layers, num_pages, page_size, 2*kv_heads, head_dim]`` with K at
    even combined-head indices and V at odd (ops/ragged_attention.py).
    Sequences own pages; a page table maps logical to physical page ids, so
    any physical order works — allocation never moves data."""

    pages: jnp.ndarray

    @classmethod
    def create(
        cls, config: ModelConfig, num_pages: int, page_size: int, dtype=jnp.bfloat16
    ) -> "PagedKVCache":
        shape = (
            config.num_layers,
            num_pages,
            page_size,
            2 * config.num_kv_heads,
            config.head_dim,
        )
        return cls(pages=jnp.zeros(shape, dtype))


class RaggedBatch(NamedTuple):
    """One unified step: a flat token run of mixed prefill chunks and decode
    tokens (static T per bucket; row boundaries via cu_q_lens).

    Padding: tokens at/past cu_q_lens[num_seqs] carry slot -1 (write dropped)
    and produce zero attention; rows at/past num_seqs have kv_len 0.
    """

    token_ids: jnp.ndarray  # [T] int32
    positions: jnp.ndarray  # [T] int32
    slot_mapping: jnp.ndarray  # [T] int32 (-1 = padding)
    kv_lens: jnp.ndarray  # [S] int32
    page_indices: jnp.ndarray  # [S, pages_per_seq] int32
    cu_q_lens: jnp.ndarray  # [S+1] int32
    num_seqs: jnp.ndarray  # [1] int32
    # Batched multi-LoRA (llm/tenancy): per-token resident adapter slot
    # (-1 = base model).  None on LoRA-less engines — a None leaf vanishes
    # from the jit treedef, so existing programs are byte-identical.
    adapter_slots: Any = None  # [T] int32 | None
    # State slots of a family whose recurrent state lives beside the pages
    # (models/mamba2.py): per row (slot its state starts from or -1 for
    # zeros, its live slot, a slot that gets a copy after the step or -1).
    # None for every other family: no operand of their programs.
    state_slots: Any = None  # [S, 3] int32 | None
    # The window layers' side of the step (models/lfm2.py ``sliding_attention``;
    # engine/kv_manager.py's second pool): a row's window table, which BEGINS
    # at the first page the window of the row's first query reaches; its
    # context length counted from that page's first position; each token's
    # slot in the window pool.  None (absent, not empty) for every family
    # without such layers: no operand of their programs.
    window_indices: Any = None  # [S, window pages a row] int32 | None
    window_lens: Any = None  # [S] int32 | None
    window_slots: Any = None  # [T] int32 (-1 = padding) | None


def _dtype(config: ModelConfig):
    return jnp.dtype(config.dtype)


def init_params(config: ModelConfig, key: jax.Array) -> Params:
    """Random-init a full params pytree (jit-friendly; used for benchmarks
    and tests; real checkpoints come through models/loader.py)."""
    dt = _dtype(config)
    D, H, KV, hd, F = (
        config.hidden_size,
        config.num_heads,
        config.num_kv_heads,
        config.head_dim,
        config.intermediate_size,
    )
    L, V = config.num_layers, config.vocab_size
    keys = jax.random.split(key, 12)

    def norm(k, *shape):
        return (jax.random.normal(k, shape, jnp.float32) * 0.02).astype(dt)

    layers: Dict[str, jnp.ndarray] = {
        "attn_norm": jnp.ones((L, D), dt),
        "wq": norm(keys[1], L, D, H * hd),
        "wk": norm(keys[2], L, D, KV * hd),
        "wv": norm(keys[3], L, D, KV * hd),
        "wo": norm(keys[4], L, H * hd, D),
        "mlp_norm": jnp.ones((L, D), dt),
    }
    if config.qkv_bias:
        layers.update(
            {
                "bq": norm(keys[10], L, H * hd),
                "bk": norm(keys[11], L, KV * hd),
                "bv": norm(keys[0], L, KV * hd),
            }
        )
    if config.is_moe:
        layers.update(init_moe_params(config, keys[5], dt))
    else:
        layers.update(
            {
                "w_gate": norm(keys[5], L, D, F),
                "w_up": norm(keys[6], L, D, F),
                "w_down": norm(keys[7], L, F, D),
            }
        )
    params: Params = {
        "embed": norm(keys[8], V, D),
        "layers": layers,
        "final_norm": jnp.ones((D,), dt),
    }
    if not config.tie_word_embeddings:
        params["lm_head"] = norm(keys[9], D, V)
    return params


def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * weight


def forward_ragged(
    params: Params,
    config: ModelConfig,
    rb: RaggedBatch,
    cache: PagedKVCache,
    *,
    attn_impl: str = "xla",  # "tpu" (pallas kernel) | "xla" (gather fallback)
    mesh=None,
    # Quantized (fp8/int8) page-dtype scale: a float, or a [L] per-layer
    # calibration vector.  The scale is folded ALGEBRAICALLY around the
    # attention call — stored = value/scale, q pre-scaled and the output
    # post-scaled by scale — so per-layer values stay fully traceable (the
    # pallas kernel's native k_scale/v_scale only accepts static floats).
    kv_scale=None,
    decode: bool = False,  # static: every row is a single-token decode row
    # Decode-path attention kernel (ops/ragged_attention.py
    # resolve_decode_kernel): "pallas_fused" routes the fused-dequant
    # split-KV kernel, which takes the (possibly traced per-layer)
    # kv_scale IN-KERNEL — the algebraic q/out fold below is skipped for
    # it, so the quantized KV stream is dequantized exactly once, in VMEM.
    decode_kernel: str = "stock",
    # Non-decode (prefill / mixed-chunk) attention kernel
    # (resolve_prefill_kernel): "pallas" routes the chunked paged prefill
    # kernel (ops/prefill_attention.py), which likewise takes kv_scale
    # IN-KERNEL — the algebraic fold is skipped for it too.
    prefill_kernel: str = "stock",
    # Static per-slot rank of the LoRA device bank (llm/tenancy/lora.py);
    # 0 = no LoRA.  Active only when BOTH the params tree carries bank
    # leaves and the batch carries adapter_slots.
    lora_rank: int = 0,
) -> Tuple[jnp.ndarray, PagedKVCache]:
    """Unified mixed prefill+decode forward over a flat ragged token run.

    Returns (logits [S, vocab] f32 — each row's LAST token's logits — and the
    updated cache).  Rows past num_seqs produce garbage logits the caller
    ignores.  One compiled program per token-count bucket serves every
    prefill/decode mix (the round-2 anti-recompile design; see
    ops/ragged_attention.py).

    With ``mesh``, the KV write + attention run under shard_map over the
    "tp" axis: each shard owns its heads' pages, so paged attention is fully
    local per chip and works with the opaque pallas kernel (XLA's auto-SPMD
    cannot partition a pallas call).  Everything else (projections, FFN,
    MoE, logits) auto-shards from the param PartitionSpecs.
    """
    (T,) = rb.token_ids.shape
    H, KV, hd = config.num_heads, config.num_kv_heads, config.head_dim
    inv_freq = rope_frequencies(hd, config.rope_theta, config.rope_scaling)
    scale = hd**-0.5
    L, P_layer, ps = cache.pages.shape[0], cache.pages.shape[1], cache.pages.shape[2]

    ks_vec = (
        None
        if kv_scale is None
        else jnp.asarray(kv_scale, jnp.float32).reshape(-1)  # [1] or [L]
    )

    # The fused decode AND prefill kernels dequantize in-kernel (the scale
    # is an SMEM scalar operand, traced per-layer values included) — the
    # algebraic fold would double-apply it.
    fused_dequant = (
        decode_kernel == "pallas_fused"
        if decode
        else prefill_kernel == "pallas"
    )

    def attn_and_write(q, k, v, s_l, pages, slots, kv_lens, tables, cu, num):
        # s_l: this layer's scale ([] f32) or None.  q·(K·s) == (q·s)·K and
        # softmax(p)·(V·s) == (softmax(p)·V)·s, so scaling q in and the
        # output back out dequantizes exactly without kernel support.
        pages = write_kv_ragged(pages, k, v, slots, kv_scale=s_l)
        if s_l is not None and not fused_dequant:
            q = (q.astype(jnp.float32) * s_l).astype(q.dtype)
        out = ragged_attention(
            q,
            pages,
            kv_lens,
            tables,
            cu,
            num,
            sm_scale=scale,
            impl=attn_impl,
            decode=decode,
            decode_kernel=decode_kernel,
            prefill_kernel=prefill_kernel,
            kv_scale=s_l if fused_dequant else None,
        )
        if s_l is not None and not fused_dequant:
            out = (out.astype(jnp.float32) * s_l).astype(out.dtype)
        return out, pages

    if mesh is not None:
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        heads = P(None, "tp", None)  # [T, heads, hd]
        pages_s = P(None, None, "tp", None)  # [L*pages, page_size, 2KV, hd]
        rep = P()  # ragged metadata + scale: replicated on every shard
        inner = attn_and_write

        def attn_and_write(q, k, v, s_l, pages, slots, kv_lens, tables, cu, num):
            if s_l is None:
                mapped = shard_map(
                    lambda q, k, v, *rest: inner(q, k, v, None, *rest),
                    mesh=mesh,
                    in_specs=(heads, heads, heads, pages_s,
                              rep, rep, rep, rep, rep),
                    out_specs=(heads, pages_s),
                    # Outputs are tp-sharded only — skip the strict
                    # replication check for the dp/ep axes.
                    check_vma=False,
                )
                return mapped(q, k, v, pages, slots, kv_lens, tables, cu, num)
            mapped = shard_map(
                inner,
                mesh=mesh,
                in_specs=(heads, heads, heads, rep, pages_s,
                          rep, rep, rep, rep, rep),
                out_specs=(heads, pages_s),
                check_vma=False,
            )
            return mapped(q, k, v, s_l, pages, slots, kv_lens, tables, cu, num)

    h = embed_lookup(params, rb.token_ids, _dtype(config))  # [T, D]

    # Batched segmented multi-LoRA (S-LoRA on TPU; llm/tenancy/lora.py):
    # all resident adapters' A/B factors live concatenated along a R*r rank
    # axis, and a per-token segment mask zeroes every adapter's columns but
    # the token's own — two dense matmuls serve rows from many adapters in
    # ONE forward, with exact per-row isolation and no gather/scatter.
    # Merge-free: the (possibly int8-quantized) base weights are untouched.
    lora_mask = None
    if (
        lora_rank > 0
        and rb.adapter_slots is not None
        and "lora_a_wq" in params["layers"]
    ):
        Rr = params["layers"]["lora_a_wq"].shape[-1]
        seg = jnp.arange(Rr, dtype=jnp.int32) // lora_rank  # column → slot
        lora_mask = (
            rb.adapter_slots[:, None] == seg[None, :]
        ).astype(_dtype(config))  # [T, R*r]; slot -1 (base) matches nothing

    def lora_delta(x_in, lp, name):
        a = lp.get("lora_a_" + name)
        if lora_mask is None or a is None:
            return None
        xa = (x_in @ a) * lora_mask  # [T, R*r], own-adapter columns only
        return (xa @ lp["lora_b_" + name]).astype(x_in.dtype)

    # The page slab rides the layer scan as a CARRY over a flat
    # layer-merged view [L*P, ps, 2KV, hd]; each layer scatters its rows at
    # a layer offset and attention gathers via offset page indices.  Making
    # it a carry (not xs/ys) lets XLA's while-loop aliasing update the slab
    # in place — per-step HBM traffic is the written rows + gathered
    # context, NOT the whole slab (threading it as xs/ys stacked a full
    # slab copy per step: measured 2.4 GB and ~23 ms/step at the bench pool
    # size before this change).
    def layer(carry, xs):
        h, pages = carry
        lp, l = xs
        x = rms_norm(h, lp["attn_norm"], config.rms_norm_eps)
        q, k, v = qkv_proj(x, lp, H * hd, KV * hd)
        if lora_mask is not None:
            dq, dk, dv = (
                lora_delta(x, lp, "wq"),
                lora_delta(x, lp, "wk"),
                lora_delta(x, lp, "wv"),
            )
            q = q if dq is None else q + dq
            k = k if dk is None else k + dk
            v = v if dv is None else v + dv
        q = q.reshape(T, H, hd)
        k = k.reshape(T, KV, hd)
        v = v.reshape(T, KV, hd)
        q = apply_rope(q, rb.positions, inv_freq)
        k = apply_rope(k, rb.positions, inv_freq)
        slots_l = jnp.where(
            rb.slot_mapping < 0, -1, rb.slot_mapping + l * (P_layer * ps)
        )
        tables_l = rb.page_indices + l * P_layer
        s_l = (
            None
            if ks_vec is None
            else ks_vec[jnp.minimum(l, ks_vec.shape[0] - 1)]
        )
        attn, pages = attn_and_write(
            q, k, v, s_l, pages, slots_l, rb.kv_lens,
            tables_l, rb.cu_q_lens, rb.num_seqs,
        )
        attn_flat = attn.reshape(T, H * hd)
        o = linear(attn_flat, lp, "wo")
        if lora_mask is not None:
            do = lora_delta(attn_flat, lp, "wo")
            o = o if do is None else o + do
        h = h + o
        x = rms_norm(h, lp["mlp_norm"], config.rms_norm_eps)
        if config.is_moe:
            h = h + moe_mlp(x[None], lp, config, mesh)[0]
        else:
            h = h + mlp(x, lp)
        return (h, pages), None

    flat = cache.pages.reshape((L * P_layer,) + cache.pages.shape[2:])
    if decode:
        # Unrolled layer loop for the fused decode program: STATIC layer
        # indices into the stacked weights let XLA prefetch layer l+1's
        # weights during layer l's compute — a scan's dynamic slices block
        # that (measured on v5e at batch 256: an 18-layer FFN chain runs
        # 9.4ms under scan vs 7.0ms unrolled; scan's unroll= option does
        # NOT recover it).  Decode is weights-bandwidth-bound, so this is
        # where prefetch pays; prefill keeps the scan's compact HLO (it is
        # compute-bound at 59-83% MFU and compiles 7 token buckets).
        carry = (h, flat)
        for l in range(L):
            lp = jax.tree_util.tree_map(lambda a: a[l], params["layers"])
            carry, _ = layer(carry, (lp, l))
        h, flat = carry
    else:
        (h, flat), _ = jax.lax.scan(
            layer,
            (h, flat),
            (params["layers"], jnp.arange(L, dtype=jnp.int32)),
        )
    pages = flat.reshape(cache.pages.shape)

    h = rms_norm(h, params["final_norm"], config.rms_norm_eps)
    rows = jnp.clip(rb.cu_q_lens[1:] - 1, 0, T - 1)  # [S] last token per row
    logits = lm_logits(params, h[rows])  # [S, vocab] f32
    return logits, PagedKVCache(pages)


def forward_sp_prefill(
    params: Params,
    config: ModelConfig,
    token_ids: jnp.ndarray,  # [Tg] int32, Tg divisible by the mesh's sp size
    valid_len,  # int or [] int32 — true prompt length (<= Tg; rest padding)
    mesh,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Whole-prompt sequence-parallel prefill for long contexts.

    Tokens shard over the mesh's "sp" axis; every matmul is local to its
    token shard (weights replicated over sp) and attention runs as RING
    attention (ops/ring_attention.py) — per-chip attention memory is
    O((Tg/sp)^2) and K/V blocks move neighbor-to-neighbor over ICI.  The
    reference has no counterpart (SURVEY §5: no sequence parallelism
    anywhere); this is the TPU-native long-context path the north-star
    configs call for.

    Returns (logits [vocab] f32 of the LAST valid token — the first decode
    token's distribution — and kv [L, Tg, 2*KV, hd] combined-interleaved
    pages-layout rows for sealing the prompt into the paged cache).
    """
    from ..ops.ring_attention import ring_attention
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    (Tg,) = token_ids.shape
    H, KV, hd = config.num_heads, config.num_kv_heads, config.head_dim
    inv_freq = rope_frequencies(hd, config.rope_theta, config.rope_scaling)
    scale = hd**-0.5
    valid = jnp.asarray(valid_len, jnp.int32).reshape(())

    # Tokens shard over "sp", heads over "tp": with both axes active each
    # chip rings over its own heads' K/V only (no per-layer all-gather of
    # tp-sharded projections, no redundant attention across tp replicas).
    heads = P("sp", "tp", None)
    ring = shard_map(
        lambda q, k, v, n: ring_attention(q, k, v, n[0], sm_scale=scale),
        mesh=mesh,
        in_specs=(heads, heads, heads, P()),
        out_specs=heads,
        check_vma=False,
    )

    positions = jnp.arange(Tg, dtype=jnp.int32)
    # [Tg, D] — sharded over sp by input spec
    h = embed_lookup(params, token_ids, _dtype(config))

    def layer(carry, lp):
        h = carry
        x = rms_norm(h, lp["attn_norm"], config.rms_norm_eps)
        q, k, v = qkv_proj(x, lp, H * hd, KV * hd)
        q = apply_rope(q.reshape(Tg, H, hd), positions, inv_freq)
        k = apply_rope(k.reshape(Tg, KV, hd), positions, inv_freq)
        v = v.reshape(Tg, KV, hd)
        attn = ring(q, k, v, jnp.asarray([valid], jnp.int32))
        h = h + linear(attn.reshape(Tg, H * hd), lp, "wo")
        x = rms_norm(h, lp["mlp_norm"], config.rms_norm_eps)
        if config.is_moe:
            h = h + moe_mlp(x[None], lp, config, mesh)[0]
        else:
            h = h + mlp(x, lp)
        # pages layout rows: K at even combined-head indices, V at odd
        comb = jnp.stack([k, v], axis=2).reshape(Tg, 2 * KV, hd)
        return h, comb

    h, kv = jax.lax.scan(layer, h, params["layers"])

    h = rms_norm(h, params["final_norm"], config.rms_norm_eps)
    logits = lm_logits(params, h[jnp.clip(valid - 1, 0, Tg - 1)])
    return logits, kv  # kv: [L, Tg, 2KV, hd]
