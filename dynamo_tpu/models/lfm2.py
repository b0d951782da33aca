"""The hybrid family, LFM2's block (``model_type`` ``lfm2_moe``): a layer's
mixer is a gated short convolution or GQA attention with QK-norm, its
feed-forward a dense SwiGLU (the leading ``first_k_dense_replace`` layers)
or sigmoid-gated experts of which this chip holds ``num_experts``.
docs/lfm2.md has the equations; models/reference/lfm2_moe.py is the plain
float32 reference.

``granitemoehybrid`` runs in the same loop with a THIRD mixer kind, the
Mamba-2 scan of models/mamba2.py, whose state lives in slots beside the pages
(``HybridCache.ssm`` / ``.tail``: docs/granite_hybrid.md), attention without
rotation or QK-norm at the configuration's softmax scale, a softmax gate, a
shared SwiGLU beside the experts of every layer and four scalar multipliers,
each a static field of ``ModelConfig`` that adds no op where it is 1.

``exaone_moe`` (docs/k_exaone.md) has attention in every layer; most are a
FOURTH mixer kind, ``sliding_attention``: the same GQA over the last
``sliding_window`` positions only, whose K/V live in a SECOND page pool
(``HybridCache.window``) under a second, short page table a row
(``RaggedBatch.window_*``; engine/kv_manager.py gives the pages back as they
fall behind the window).  Its leaves are a group of their own (``wattn``), so
the full-attention block of the other models is traced as it was.  Rotation by
layer kind, a branch's OUTPUT normed (``post_norm``), a leading dense layer,
one shared expert beside the routed ones: static fields all.

``kimi_linear`` (docs/kimi_linear.md) adds a FIFTH mixer kind, ``kda``: Kimi
Delta Attention (models/kda.py), whose state (a matrix a head and the tail of
three convolutions) lives in the slots Mamba-2's lives in.  Its attention
layers are LATENT (MLA without rotation): the block is the latent family's own
(``deepseek_v32.mla_block``), and ``HybridCache.pages`` then holds latent
entries in that family's layout (leaf group ``mla`` in place of ``attn``).

``jamba`` (docs/jamba.md) adds a SIXTH, ``mamba1``: the Mamba-1 selective scan
(models/mamba1.py; ``mamba`` stays Mamba-2), its state in the same slots, GQA
of ONE K/V head without rotation, and a dense SwiGLU in EVERY layer: no expert
leaves at all.

Beside models/llama.py and models/deepseek_v32.py, sharing ``linear``,
``rms_norm``, ``mlp``, ``embed_lookup``, ``lm_logits``, the attention ops of
the dense family, the latent family's ``gate`` and the dispatch of
models/moe.py.  The layers are unlike, so the loop over them is unrolled with
static indices in both programs, each kind's leaves stacked over its own
layers (``conv`` [Lc, ...], ``attn`` [La, ...], ``dense``, ``moe``).

Two kinds of state under ONE page table (``HybridCache``): K/V pages for the
attention layers, and for the convolution layers one ENTRY a page,
``conv[l, p] = (u_{t-K+1}, ..., u_t)`` with ``t`` the LAST position written
into page ``p`` and K = ``conv_L_cache`` - 1: what a sequence needs to go on
from position ``t + 1``.  A token at position t reads the entry of the page
that holds t - 1 (zeros at t = 0), the tokens of one run read their
predecessors in the run, and only a run's last token in each page writes.  A
sealed block's entry is a function of the prefix alone, so a prefix hit, a
later chunk, a decode step and a resume after preemption are one case, and
the engine's block manager, prefix cache and eviction see page ids only.

The layout's one edge: a prompt that is a whole number of cached blocks
must compute its last token again for the logits, and that token's
predecessors' ``u`` the sealed page no longer holds (its entry ends AT that
token).  Such a hit gives its last block back (``ModelFamily.state_per_page``
-> engine/scheduler.py ``full_hit_recompute``): sixteen tokens computed
again from the block before, against a third more state in every page.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from ..ops.ragged_attention import ragged_attention, write_kv_ragged
from ..ops.rope import apply_rope, rope_frequencies
from . import deepseek_v32 as latent
from . import kda, mamba1, mamba2
from .config import ModelConfig
from .llama import RaggedBatch, embed_lookup, linear, lm_logits, mlp, rms_norm
from .moe import expert_dispatch

Params = Dict[str, Any]

# int8 leaves and the CONTRACTED axis their scale spans (as the latent
# family's table); the taps, the router and its bias and the norms stay in the
# activation dtype.
QUANT_AXES = {
    "layers": {},
    "conv": {"in_proj": 1, "out_proj": 1},
    "attn": {"wqkv": 1, "wo": 1},
    "wattn": {"wqkv": 1, "wo": 1},
    "dense": {"w_gate": 1, "w_up": 1, "w_down": 1},
    "moe": {"moe_gate": 2, "moe_up": 2, "moe_down": 2},
    "top": {"embed": 1, "lm_head": 0},
    "mamba": mamba2.QUANT_AXES,
    "shared": {"w_gate": 1, "w_up": 1, "w_down": 1},
    "kda": kda.QUANT_AXES,
    "mla": {"wq": 1, "wkv_a": 1, "wo": 1},
    "mamba1": mamba1.QUANT_AXES,
}
_ONES = (("op_norm", "ffn_norm", "q_norm", "k_norm", "final_norm", "kv_norm") + mamba2.ONES
         + kda.ONES + mamba1.ONES)
EXPERT_LEAVES = latent.EXPERT_LEAVES


def head_pack(config: ModelConfig) -> int:
    """K/V heads that share one 128-lane row of a page.  The attention
    kernels slice pages by whole lane tiles, and a head of 64 does not lower
    (Mosaic: "slice shape must be aligned to tiling (128)"), so two heads lie
    side by side in a row: the kernels see ``num_kv_heads / pack`` heads of
    ``pack * head_dim`` lanes, a query padded with zeros where its
    neighbour's keys lie, and its own half of the output.  The zeros add
    nothing to a score: the mathematics is the unpacked one."""
    hd, KV = config.head_dim, config.num_kv_heads
    pack = 128 // hd if hd < 128 and 128 % hd == 0 else 1
    return pack if KV % pack == 0 else 1


def attn_lanes(config: ModelConfig) -> int:
    """The head width the attention kernels see (``ModelFamily.attn_lanes``)."""
    return config.head_dim * head_pack(config)


def mamba_layers(config: ModelConfig) -> int:
    return sum(t == "mamba" for t in config.layer_types)


def window_layers(config: ModelConfig) -> int:
    return sum(t == "sliding_attention" for t in config.layer_types)


def kda_layers(config: ModelConfig) -> int:
    return sum(t == "kda" for t in config.layer_types)


def mamba1_layers(config: ModelConfig) -> int:
    return sum(t == "mamba1" for t in config.layer_types)


def latent_attention(config: ModelConfig) -> bool:
    """The full-attention layers are latent (MLA): their pages hold latent
    entries, their leaves are the group ``mla``."""
    return config.kv_lora_rank > 0


def layer_counts(config: ModelConfig) -> Tuple[int, int, int, int]:
    """(convolution, full attention, dense, expert) layers."""
    Lc = sum(t == "conv" for t in config.layer_types)
    Ld = min(config.first_k_dense_replace, config.num_layers)
    La = (config.num_layers - Lc - mamba_layers(config) - window_layers(config)
          - kda_layers(config) - mamba1_layers(config))
    return Lc, La, Ld, config.num_layers - Ld


def window_blocks(config: ModelConfig, page_size: int) -> int:
    """Pages that hold the ``sliding_window - 1`` positions before a block
    boundary: what a row resumed there reads of the window layers."""
    return -(-(config.sliding_window - 1) // page_size)


def window_row_pages(config: ModelConfig, page_size: int, step_tokens: int) -> int:
    """The width of a row's window table: the pages a step of ``step_tokens``
    query tokens can touch, ``ceil((window - 1 + q) / page) + 1``."""
    return -(-(config.sliding_window - 1 + step_tokens) // page_size) + 1


def snapshot_slots(num_pages: int, page_size: int, stride: int) -> int:
    """The snapshot pool's ONE rule: a snapshot for every five resume strides
    the pages can hold (docs/granite_hybrid.md)."""
    return num_pages * page_size // (5 * stride)


class HybridCache(NamedTuple):
    """``pages`` [La, P, ps, 2 * KV / pack, pack * head_dim]: the attention
    layers' K/V in the dense family's layout (K rows even, V rows odd), the
    page dtype the engine was asked for.  ``conv`` [Lc, P, K, D]: the
    convolution layers' entry of each page, in the ACTIVATION dtype whatever
    the K/V pages' (what a hit reads is then what a cold run computed); None
    without such layers.  ``ssm`` [Lm, slots, heads * d_head, d_state] FLOAT32
    and ``tail`` [Lm, d_conv - 1, slots, channels] in the activation dtype:
    the Mamba-2 layers' state by SLOT (models/mamba2.py), the first
    ``max_batch`` slots the running rows', the others snapshots; None without
    such layers (a None leaf is no operand of a program).  A model with KDA
    layers keeps THEIR state in the same two leaves: ``ssm`` [Lk, slots, heads
    * d_key, d_value] float32, ``tail`` [Lk, taps - 1, slots, 3 * heads *
    d_key]; one with Mamba-1 layers ``ssm`` [L1, slots, d_state, inner]
    float32 (the 16 on sublanes), ``tail`` [L1, taps - 1, slots, inner].  With
    latent attention ``pages`` is [La, P, ps, latent width]: the
    latent family's entries (``deepseek_v32.LatentKVCache.latent``).  ``window`` [Lw, Pw,
    ps, 2 * KV / pack, pack * head_dim]: the window layers' K/V, pages of a
    pool of their own in the K/V pages' dtype; None without such layers.  The shapes leave
    the chip's compiler ONE layout for a pool: over [.., heads, d_head,
    d_state] it chose another order than the parameter's for the prompt
    program's matmuls and copied 5 GB into and out of every step, and over
    [.., slots, 3, channels] it moved the slots inward (compile for a
    described v5e, tests/test_tpu_compile.py)."""

    pages: jnp.ndarray
    conv: Any
    ssm: Any = None
    tail: Any = None
    window: Any = None

    @classmethod
    def create(cls, config: ModelConfig, num_pages: int, page_size: int,
               dtype=jnp.bfloat16, state_slots: int = 1, window_pages: int = 0) -> "HybridCache":
        Lc, La, _, _ = layer_counts(config)
        Lm, Lw, Lk = mamba_layers(config), window_layers(config), kda_layers(config)
        pack = head_pack(config)
        act = jnp.dtype(config.dtype)
        if latent_attention(config):
            page = (page_size, latent.latent_width(config))
        else:
            page = (page_size, 2 * config.num_kv_heads // pack, pack * config.head_dim)
        # The slots' two leaves, by the mixer that lives in them (a model has one).
        L1 = mamba1_layers(config)
        if Lk:
            Hk, dk, taps = kda.dims(config)
            state, tail = (Hk * dk, dk), (taps - 1, state_slots, kda.conv_width(config))
        elif L1:
            di, N, K, _ = mamba1.dims(config)
            state, tail = (N, di), (K - 1, state_slots, di)
        else:
            _, Hm, P, N, K = mamba2.dims(config)
            state, tail = (Hm * P, N), (K - 1, state_slots, mamba2.conv_width(config))
        Ls = Lm + Lk + L1
        return cls(
            pages=jnp.zeros((La, num_pages) + page, dtype),
            window=jnp.zeros((Lw, window_pages) + page, dtype) if Lw else None,
            conv=jnp.zeros((Lc, num_pages, config.conv_L_cache - 1, config.hidden_size),
                           act) if Lc else None,
            ssm=jnp.zeros((Ls, state_slots) + state, jnp.float32) if Ls else None,
            tail=jnp.zeros((Ls,) + tail, act) if Ls else None,
        )


def leaf_shapes(config: ModelConfig) -> Dict[str, Dict[str, tuple]]:
    """Every leaf's shape, by group: the one statement of the layout."""
    c = config
    D, H, KV, hd, L, V = (c.hidden_size, c.num_heads, c.num_kv_heads, c.head_dim,
                          c.num_layers, c.vocab_size)
    Lc, La, Ld, Lm = layer_counts(c)
    E, Et, F, Fm = c.num_experts, c.router_experts, c.intermediate_size, c.moe_intermediate_size
    top = {"embed": (V, D), "final_norm": (D,)}
    if not c.tie_word_embeddings:
        top["lm_head"] = (D, V)
    groups = {"top": top, "layers": {"op_norm": (L, D), "ffn_norm": (L, D)}}
    if Lc:
        # in_proj's columns: B, C, x (three parts of D); taps[k] multiplies u_{t-K+k}.
        groups["conv"] = {"in_proj": (Lc, D, 3 * D), "taps": (Lc, c.conv_L_cache, D),
                          "out_proj": (Lc, D, D)}
    # wqkv's columns: q (H heads), k, v (KV heads each).
    norms = {"q_norm": (La, hd), "k_norm": (La, hd)} if c.qk_norm else {}
    if latent_attention(c):
        # The latent family's attention leaves without the compressed query.
        Rkv, dn, dr, dv = c.kv_lora_rank, c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
        groups["mla"] = {"wq": (La, D, H * (dn + dr)), "wkv_a": (La, D, Rkv + dr),
                         "kv_norm": (La, Rkv), "w_uk": (La, H, Rkv, dn),
                         "w_uv": (La, H, Rkv, dv), "wo": (La, H * dv, D)}
    else:
        groups["attn"] = {"wqkv": (La, D, (H + 2 * KV) * hd), **norms, "wo": (La, H * hd, D)}
    Lw = window_layers(c)
    if Lw:
        groups["wattn"] = {"wqkv": (Lw, D, (H + 2 * KV) * hd), "q_norm": (Lw, hd),
                           "k_norm": (Lw, hd), "wo": (Lw, H * hd, D)}
    if Ld:
        groups["dense"] = {"w_gate": (Ld, D, F), "w_up": (Ld, D, F), "w_down": (Ld, F, D)}
    # The bias steers the sigmoid gate's choice; [a | b] = W_1 x is (moe_gate | moe_up).
    bias = {} if c.gate_scoring == "softmax" else {"router_bias": (Lm, Et)}
    if Lm:  # a model whose every feed-forward is dense has no such group
        groups["moe"] = {"router": (Lm, D, Et), **bias, "moe_gate": (Lm, E, D, Fm),
                         "moe_up": (Lm, E, D, Fm), "moe_down": (Lm, E, Fm, D)}
    if mamba_layers(c):
        groups["mamba"] = mamba2.leaf_shapes(c, mamba_layers(c))
    if c.shared_intermediate_size:
        Fs = c.shared_intermediate_size
        groups["shared"] = {"w_gate": (Lm, D, Fs), "w_up": (Lm, D, Fs), "w_down": (Lm, Fs, D)}
    if kda_layers(c):
        groups["kda"] = kda.leaf_shapes(c, kda_layers(c))
    if mamba1_layers(c):
        groups["mamba1"] = mamba1.leaf_shapes(c, mamba1_layers(c))
    return groups


def _draw(config: ModelConfig, key: jax.Array, quant: bool) -> Params:
    return latent._draw(config, key, quant, leaf_shapes(config), QUANT_AXES, _ONES,
                        draws=mamba1.DRAWS if mamba1_layers(config) else
                        {**mamba2.DRAWS, **kda.DRAWS})


def init_params(config: ModelConfig, key: jax.Array) -> Params:
    return jax.jit(lambda k: _draw(config, k, False))(key)


def init_params_quantized(config: ModelConfig, key: jax.Array) -> Params:
    return jax.jit(lambda k: _draw(config, k, True))(key)


def quantize_params(params: Params) -> Params:
    return latent.quantize_params(params, QUANT_AXES)


def dequantize_params(params: Params, dtype="float32") -> Params:
    return latent.dequantize_params(params, dtype, QUANT_AXES)


def _rounded(v: jnp.ndarray, dtype) -> jnp.ndarray:
    """``v`` (float32) rounded to ``dtype``, SAID and not left to a cast that
    XLA keeps or drops by what it fuses it with (models/moe.py
    ``_quantize_gated``): the page entry must hold what the run itself used."""
    fi = jnp.finfo(dtype)
    return jax.lax.reduce_precision(v, fi.nexp, fi.nmant).astype(dtype)


def moe_block(x, lp: Params, config: ModelConfig, real, layer):
    """The routed experts chosen AND held (the shared expert is the layer
    loop's): (y [T, D], pairs of real tokens landed on each held expert [E])."""
    chosen, w = latent.gate(x, lp, config)
    local = chosen - config.ep_rank * config.num_experts
    here = (local >= 0) & (local < config.num_experts) & real[:, None]
    return expert_dispatch(x, local, w, lp, config.num_experts, valid=here, layer=layer)


def mamba_rows(rb: RaggedBatch, live_row, first) -> mamba2.Rows:
    """The step's rows as the Mamba-2 layers read them.  Without
    ``rb.state_slots`` (a caller that holds no slots: the calibration probe,
    a test) row i lives in slot i and starts from zeros at position 0."""
    (T,) = rb.token_ids.shape
    S = rb.kv_lens.shape[0]
    count = jnp.where(live_row, rb.cu_q_lens[1:] - rb.cu_q_lens[:-1], 0)
    row_of = jnp.sum(jnp.arange(T)[:, None] >= rb.cu_q_lens[None, 1:], axis=1)  # S: padding
    if rb.state_slots is None:
        own = jnp.arange(S, dtype=jnp.int32)
        read, write, snap = jnp.where(rb.positions[first] > 0, own, -1), own, own * 0 - 1
    else:
        read, write, snap = (rb.state_slots[:, i] for i in range(3))
    return mamba2.Rows(first, count, rb.num_seqs[0], row_of, read, write, snap)


def forward_ragged(
    params: Params,
    config: ModelConfig,
    rb: RaggedBatch,
    cache: HybridCache,
    *,
    attn_impl: str = "xla",
    kv_scale=None,  # None, a float, or the attention layers' calibrated [La]
    decode: bool = False,
    decode_kernel: str = "stock",
    prefill_kernel: str = "stock",
    drop_state_at_page_boundary: bool = False,  # chip_smoke.py's control, never the engine
    drop_state_at_stride: int = 0,  # the same for the slots: rows starting on a multiple start from zeros
    **_other_families,  # mesh, lora_rank: family.py's check refuses what they stand for
) -> Tuple[jnp.ndarray, HybridCache, Any]:
    """The unified step of models/llama.py for this family: (logits [S, V] of
    each row's last token, the updated cache, aux [4] int32 as the latent
    family's: routed pairs landed on held experts, tokens routed, held experts
    read, experts held, over the step's real tokens and expert layers)."""
    c = config
    rb = jax.tree_util.tree_map(jnp.asarray, rb)  # host arrays when not under jit
    (T,) = rb.token_ids.shape
    S = rb.kv_lens.shape[0]
    D, H, KV, hd, eps = c.hidden_size, c.num_heads, c.num_kv_heads, c.head_dim, c.rms_norm_eps
    dt = jnp.dtype(c.dtype)
    Lc, La, Ld, Lm = layer_counts(c)
    K = c.conv_L_cache - 1
    pack, G = head_pack(c), H // KV
    inv_freq = rope_frequencies(hd, c.rope_theta, None) if c.use_rope else None
    sm_scale = hd**-0.5 if c.attention_multiplier is None else c.attention_multiplier
    P_layer, ps = cache.pages.shape[1:3]
    pos = rb.positions
    real = rb.slot_mapping >= 0  # [T] padding tokens carry slot -1
    ks_vec = None if kv_scale is None else jnp.asarray(kv_scale, jnp.float32).reshape(-1)
    # A model that norms K a head and V not at all (QK-norm with output-side
    # norms: V is as small as the layer's input, K of size 1) gets a GAIN a
    # layer beside its scale, the second half of ``kv_scale``: V is stored
    # times the gain, so that one scale a page serves both, and the call's
    # output divided by it (attention is linear in V).  Static: by the shape.
    n_attn = La + window_layers(c)
    gains = None
    if c.post_norm and ks_vec is not None and ks_vec.shape[0] == 2 * n_attn:
        ks_vec, gains = ks_vec[:n_attn], ks_vec[n_attn:]
    # The fused kernels take the scale themselves (models/llama.py).
    fused_dequant = decode_kernel == "pallas_fused" if decode else prefill_kernel == "pallas"

    # ---- the rows' places in the convolution state, shared by its layers.
    live_row = (jnp.arange(S) < rb.num_seqs[0]) & (rb.cu_q_lens[1:] > rb.cu_q_lens[:-1])
    first = jnp.clip(rb.cu_q_lens[:-1], 0, T - 1)  # a row's first token
    t0 = pos[first]
    if Lc:
        has_tail = live_row & (t0 > 0)
        if drop_state_at_page_boundary:
            has_tail &= t0 % ps != 0
        tail_page = rb.page_indices[jnp.arange(S), jnp.maximum(t0 - 1, 0) // ps]
        # [Lc, S, K, D]: read before any layer writes (a row may write the page it reads).
        tails = jnp.where(has_tail[None, :, None, None], cache.conv[:, tail_page], 0)
        if decode:  # every row is one token, the last of its page so far
            write_page = jnp.where(real, rb.slot_mapping // ps, P_layer)
        else:
            first_at = jnp.where(live_row, rb.cu_q_lens[:-1], T)  # out of range: dropped
            is_first = jnp.zeros((T,), bool).at[first_at].set(True, mode="drop")
            last_at = jnp.where(live_row, rb.cu_q_lens[1:] - 1, T)
            ends_run = jnp.zeros((T,), bool).at[last_at].set(True, mode="drop")
            writes = real & (ends_run | (pos % ps == ps - 1))
            # A run of n tokens ends at most n // ps + 1 pages.
            writers = jnp.nonzero(writes, size=min(T, T // ps + S), fill_value=T)[0]
            write_page = jnp.where(writers < T, rb.slot_mapping[jnp.minimum(writers, T - 1)] // ps,
                                   P_layer)
    if cache.ssm is not None and not decode:
        rows = mamba_rows(rb, live_row, first)
        if drop_state_at_stride:
            rows = rows._replace(read=jnp.where(t0 % drop_state_at_stride == 0, -1, rows.read))

    def short_conv(x, lp, tail):
        """h += W_out (C * conv(B * x)): returns (the mixer's output, the
        entries [writers, K, D] its writing tokens leave)."""
        bcx = linear(x, lp, "in_proj")
        with jax.named_scope("short_conv"):
            b, gate_c, xin = jnp.split(bcx, 3, axis=-1)
            u = _rounded(b.astype(jnp.float32) * xin.astype(jnp.float32), dt)  # [T, D]
            # prev[k - 1] = u_{t-k}: from the run, or from the tail at its start.
            if decode:
                prev = [tail[:, K - k] for k in range(1, K + 1)]
            else:
                at_first = jnp.zeros((T, K, D), dt).at[first_at].set(tail, mode="drop")
                prev, p = [], u
                for k in range(1, K + 1):
                    p = jnp.where(is_first[:, None], at_first[:, K - k],
                                  jnp.concatenate([jnp.zeros((1, D), dt), p[:-1]], axis=0))
                    prev.append(p)
            taps = lp["taps"].astype(jnp.float32)  # [K + 1, D]
            v = taps[K] * u.astype(jnp.float32)
            for k in range(1, K + 1):
                v = v + taps[K - k] * prev[k - 1].astype(jnp.float32)
            y = (gate_c.astype(jnp.float32) * v).astype(dt)
            window = jnp.stack(prev[:K - 1][::-1] + [u], axis=1)  # [T, K, D]: u_{t-K+1} .. u_t
            entry = window if decode else window[jnp.minimum(writers, T - 1)]
        return linear(y, lp, "out_proj"), entry

    def attention(x, lp, a, pages, windowed=False):
        """GQA over the row's pages: the full-attention layers' (``pages``
        the G pool, table and slots ``rb.page_indices`` / ``rb.slot_mapping``)
        or, ``windowed``, the window layers' (the window pool under
        ``rb.window_indices`` / ``rb.window_slots`` / ``rb.window_lens``)."""
        q, k, v = jnp.split(linear(x, lp, "wqkv"), [H * hd, (H + KV) * hd], axis=-1)
        if config.qk_norm:  # static
            q = rms_norm(q.reshape(T, H, hd), lp["q_norm"], eps)
            k = rms_norm(k.reshape(T, KV, hd), lp["k_norm"], eps)
        else:
            q, k = q.reshape(T, H, hd), k.reshape(T, KV, hd)
        if inv_freq is not None and (windowed or c.rope_full_attention):  # static
            q, k = apply_rope(q, pos, inv_freq), apply_rope(k, pos, inv_freq)
        if pack > 1:
            # KV head g lies in half g % pack of row g // pack; its G queries
            # carry zeros in the other halves.
            q = (q.reshape(T, KV // pack, pack, G, 1, hd)
                 * jnp.eye(pack, dtype=dt).reshape(1, 1, pack, 1, pack, 1)
                 ).reshape(T, H, pack * hd)
        k = k.reshape(T, KV // pack, pack * hd)
        v = v.reshape(T, KV // pack, pack * hd)
        # The pool's side of the step: a window layer's scale follows the full
        # layers', its pages are the window pool's under the window table.
        P_pool = cache.window.shape[1] if windowed else P_layer
        at = La + a if windowed else a
        s_a = None if ks_vec is None else ks_vec[jnp.minimum(at, ks_vec.shape[0] - 1)]
        slot_of = rb.window_slots if windowed else rb.slot_mapping
        slots = jnp.where(real, slot_of + a * (P_pool * ps), -1)
        if gains is not None:
            v = (v.astype(jnp.float32) * gains[at]).astype(v.dtype)
        pages = write_kv_ragged(pages, k, v, slots, kv_scale=s_a)
        fold = s_a is not None and not fused_dequant
        if fold:  # models/llama.py: the scale folded around the call
            q = (q.astype(jnp.float32) * s_a).astype(q.dtype)
        lens, table, kw = (
            (rb.window_lens, rb.window_indices, {"window": c.sliding_window}) if windowed
            else (rb.kv_lens, rb.page_indices, {}))
        o = ragged_attention(
            q, pages, lens, table + a * P_pool, rb.cu_q_lens, rb.num_seqs,
            sm_scale=sm_scale, impl=attn_impl, decode=decode, decode_kernel=decode_kernel,
            prefill_kernel=prefill_kernel, kv_scale=s_a if fused_dequant else None, **kw)
        if fold:
            o = (o.astype(jnp.float32) * s_a).astype(o.dtype)
        if gains is not None:
            o = (o.astype(jnp.float32) / gains[at]).astype(o.dtype)
        if pack > 1:
            o = o.reshape(T, KV // pack, pack, G, pack, hd)
            o = jnp.stack([o[:, :, i, :, i] for i in range(pack)], axis=2)
        return linear(o.reshape(T, H * hd), lp, "wo"), pages

    def at_layer(group: str, i) -> Params:
        """Layer i's leaves; the expert leaves whole (``expert_dispatch``
        reads a block of the stacked leaf in place)."""
        return {k: a if k in EXPERT_LEAVES else a[i] for k, a in params[group].items()}

    # Each kind of block is traced and lowered ONCE a program, as a function
    # of its layer's number, and called once a layer: tracing 24 unrolled
    # layers a program made a start with a warm compile cache 253 s where
    # this makes it 119 (chip runs, PR 36).  XLA inlines the calls and folds
    # each constant number into static slices: the compiled program is the
    # unrolled one (no call, no dynamic slice: compile for a described v5e).
    @jax.jit
    def conv_block(x, i, tail):
        return short_conv(x, at_layer("conv", i), tail)

    @jax.jit
    def attn_block(x, a, pages):
        return attention(x, at_layer("attn", a), a, pages)

    @jax.jit
    def window_block(x, w, wpages):
        return attention(x, at_layer("wattn", w), w, wpages, windowed=True)

    if latent_attention(c):
        mla = latent.mla_step(c, rb, decode)

    @jax.jit
    def mla_block(x, a, lat):
        return latent.mla_block(x, at_layer("mla", a), c, rb, mla, a, lat, P_layer)

    @jax.jit
    def moe_layer(x, j):
        return moe_block(x, at_layer("moe", j), c, real, j)

    def residual(h, y):
        # Static: a model whose multiplier is 1 gets no op for it.
        return h + y if c.residual_multiplier == 1.0 else h + c.residual_multiplier * y

    # Where a branch's norm stands (static): on its input, or (``post_norm``)
    # on its output; the other side adds no op.
    def norm_in(h, name, l):
        return h if c.post_norm else rms_norm(h, params["layers"][name][l], eps)

    def norm_out(y, name, l):
        return rms_norm(y, params["layers"][name][l], eps) if c.post_norm else y

    def experts(h, l, pairs, read):
        """``h += experts(norm_2(h))`` (+ the shared MLP where the model has
        one), with the account of the pairs that landed."""
        x = norm_in(h, "ffn_norm", l)
        y, load = moe_layer(x, jnp.int32(l - Ld))
        if "shared" in params:
            y = y + mlp(x, at_layer("shared", l - Ld))
        return (residual(h, norm_out(y, "ffn_norm", l)), pairs + jnp.sum(load),
                read + jnp.sum(load > 0, dtype=jnp.int32))

    @jax.jit
    def mamba_layer(l, m, h, ssm, tail, pairs, read):
        """One Mamba-2 layer with its feed-forward, as a function of its
        number ``l`` and of its place ``m`` among the Mamba-2 layers."""
        x = rms_norm(h, params["layers"]["op_norm"][l], eps)
        lp = at_layer("mamba", m)
        if decode:
            y, ssm, tail = mamba2.step(x, lp, c, ssm, tail, m, real)
        else:
            y, ssm, tail = mamba2.scan(x, lp, c, ssm, tail, m, rows)
        h, pairs, read = experts(residual(h, y), l, pairs, read)
        return h, ssm, tail, pairs, read

    def kda_layer_with(dense: bool):
        @jax.jit
        def kda_layer(l, m, h, ssm, tail, pairs, read):
            """One KDA layer with its feed-forward (``dense``: the leading
            SwiGLU, else the experts), as ``mamba_layer``."""
            x = rms_norm(h, params["layers"]["op_norm"][l], eps)
            lp = at_layer("kda", m)
            if decode:
                y, ssm, tail = kda.step(x, lp, c, ssm, tail, m, real)
            else:
                y, ssm, tail = kda.scan(x, lp, c, ssm, tail, m, rows)
            h = residual(h, y)
            if dense:
                x = norm_in(h, "ffn_norm", l)
                h = h + norm_out(mlp(x, at_layer("dense", l)), "ffn_norm", l)
            else:
                h, pairs, read = experts(h, l, pairs, read)
            return h, ssm, tail, pairs, read

        return kda_layer

    @jax.jit
    def mamba1_layer(l, m, h, ssm, tail, pairs, read):
        """One Mamba-1 layer with its dense feed-forward, as ``mamba_layer``."""
        x = rms_norm(h, params["layers"]["op_norm"][l], eps)
        lp = at_layer("mamba1", m)
        if decode:
            y, ssm, tail = mamba1.step(x, lp, c, ssm, tail, m, real)
        else:
            y, ssm, tail = mamba1.scan(x, lp, c, ssm, tail, m, rows)
        h = residual(h, y)
        h = h + mlp(rms_norm(h, params["layers"]["ffn_norm"][l], eps), at_layer("dense", l))
        return h, ssm, tail, pairs, read

    slot_layers = {("mamba", False): mamba_layer, ("mamba1", True): mamba1_layer,
                   **{("kda", dense): kda_layer_with(dense) for dense in (False, True)}}

    h = embed_lookup(params, rb.token_ids, dt)
    if c.embedding_multiplier != 1.0:
        h = h * jnp.asarray(c.embedding_multiplier, dt)
    pages = cache.pages.reshape((La * P_layer,) + cache.pages.shape[2:])
    wpages = cache.window
    if wpages is not None:
        wpages = wpages.reshape((-1,) + wpages.shape[2:])
    ssm, tail = cache.ssm, cache.tail
    entries = []
    pairs = jnp.zeros((), jnp.int32)
    read = jnp.zeros((), jnp.int32)
    ci = ai = mi = wi = l = 0
    while l < c.num_layers:  # constant layer numbers: see models/llama.py on decode
        kind = c.layer_types[l]
        if kind in ("mamba", "kda", "mamba1"):
            # A run of layers whose state lives in slots, of one mixer and one
            # kind of feed-forward.  The decode program unrolls it (its
            # weights stream: models/llama.py); a prompt program walks it as
            # ONE loop over the layer's number, so its eight token buckets
            # trace and compile one body a run and not one a layer.
            run = 1
            while (l + run < c.num_layers and c.layer_types[l + run] == kind
                   and (l + run < Ld) == (l < Ld)):
                run += 1
            one = slot_layers[kind, l < Ld]
            carry = (h, ssm, tail, pairs, read)
            if decode or run == 1:
                for i in range(run):
                    carry = one(jnp.int32(l + i), jnp.int32(mi + i), *carry)
            else:
                carry = jax.lax.fori_loop(
                    l, l + run, lambda i, cr, d=mi - l: one(i, i + d, *cr), carry)
            h, ssm, tail, pairs, read = carry
            l, mi = l + run, mi + run
            continue
        x = norm_in(h, "op_norm", l)
        if kind == "conv":
            y, entry = conv_block(x, jnp.int32(ci), tails[ci])
            entries.append(entry)
            ci += 1
        elif kind == "sliding_attention":
            y, wpages = window_block(x, jnp.int32(wi), wpages)
            wi += 1
        elif latent_attention(c):
            y, pages = mla_block(x, jnp.int32(ai), pages)
            ai += 1
        else:
            y, pages = attn_block(x, jnp.int32(ai), pages)
            ai += 1
        h = residual(h, norm_out(y, "op_norm", l))
        if l < Ld:
            x = norm_in(h, "ffn_norm", l)
            h = h + norm_out(mlp(x, at_layer("dense", l)), "ffn_norm", l)
        else:
            h, pairs, read = experts(h, l, pairs, read)
        l += 1

    conv = cache.conv
    if Lc:
        with jax.named_scope("short_conv"):
            conv = cache.conv.at[:, write_page].set(jnp.stack(entries), mode="drop")
    h = rms_norm(h, params["final_norm"], eps)
    last = jnp.clip(rb.cu_q_lens[1:] - 1, 0, T - 1)
    logits = lm_logits(params, h[last])
    if c.logits_scaling != 1.0:
        logits = logits / c.logits_scaling
    aux = jnp.stack([pairs, jnp.sum(real, dtype=jnp.int32) * Lm, read,
                     jnp.asarray(c.num_experts * Lm, jnp.int32)])
    if wpages is not None:
        wpages = wpages.reshape(cache.window.shape)
    return logits, HybridCache(pages.reshape(cache.pages.shape), conv, ssm, tail, wpages), aux
