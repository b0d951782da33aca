"""The latent family, DeepSeek-V3's block and its descendants: latent
attention (MLA), a sigmoid gate with group-limited choice, one shared expert
and the routed experts this chip HOLDS; with ``index_topk`` > 0 the learned
sparse selector (DSA) of DeepSeek-V3.2-Exp decides which positions a query
attends to, with ``index_topk`` 0 (``model_type`` ``kimi_k2``,
``deepseek_v3``) there is NO selector: no ``idx_*`` leaf, no indexer page, no
score, and a query attends to its row's whole context (ops/dense_mla.py).
docs/deepseek_v32.md has the equations; models/reference/deepseek_v32.py and
models/reference/kimi_k2.py are the plain float32 references.

Beside models/llama.py and sharing its ``linear``, ``rms_norm``,
``embed_lookup``, ``lm_logits`` and the dispatch of models/moe.py.  The cache
is one or two page arrays under ONE page table (``LatentKVCache``): the
engine's block manager, prefix cache and eviction see page ids only and are
untouched.

Expert parallelism without the exchange: ``config.num_experts`` experts are
held (global ids ``ep_rank * num_experts`` onwards), the router scores and
chooses over all ``config.router_experts``, and this chip adds the part of
the result its own experts give, plus the shared expert.  That partial result
goes on to the next layer, as in the reference.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.rope import (
    apply_rope,
    apply_rope_interleaved,
    rope_frequencies,
    yarn_mscale,
)
from ..ops.dense_mla import (
    dense_decode_attention,
    dense_prefill_attention,
    latent_prefill_attention,
)
from ..ops.sparse_mla import (
    SCOPES as SPARSE_SCOPES,
    fused_sparse_decode_attention,
    prefill_form,
    sparse_prefill_attention,
    sparse_prefill_selection,
)
from .config import ModelConfig
from .llama import RaggedBatch, embed_lookup, linear, lm_logits, mlp, rms_norm
from .moe import expert_dispatch

Params = Dict[str, Any]

# Leaves stored in int8 with a scale over the CONTRACTED axis (the value is
# that axis in the stacked layout); every other leaf stays in the activation
# dtype: w_uk / w_uv (absorbed into q and the output, 33.6 MB a layer),
# the router and its bias, the selector's idx_wk and idx_wproj, the norms.
QUANT_AXES = {
    "layers": {"wq_a": 1, "wq_b": 1, "wkv_a": 1, "wo": 1, "idx_wq_b": 1},
    "dense": {"w_gate": 1, "w_up": 1, "w_down": 1},
    "moe": {"moe_gate": 2, "moe_up": 2, "moe_down": 2,
            "shared_gate": 1, "shared_up": 1, "shared_down": 1},
    "top": {"embed": 1, "lm_head": 0},
}


def latent_width(config: ModelConfig) -> int:
    """Stored width of a latent entry: kv_lora_rank + qk_rope_head_dim (576)
    rounded up to whole 128-lane tiles (640), the tail zero.  The TPU pads a
    minor dimension of 576 to 640 in memory anyway, or else picks a layout
    with the PAGE axis minor, which no gather can use: the compiler then
    copied the whole 3.6 GB array into and out of every step (compile
    rehearsal, PR 28)."""
    return -(-(config.kv_lora_rank + config.qk_rope_head_dim) // 128) * 128


class LatentKVCache(NamedTuple):
    """``latent`` [L, P, ps, latent_width]: the normed latent c_t and the
    rope key k^R_t, K and V at once (then zero padding to whole lanes).
    ``index`` [L, P, ps, index_head_dim]: the selector's key k^I_t; None for a
    model without a selector.  One page id names the same 16 tokens in both."""

    latent: jnp.ndarray
    index: Optional[jnp.ndarray]

    @classmethod
    def create(cls, config: ModelConfig, num_pages: int, page_size: int,
               dtype=jnp.bfloat16) -> "LatentKVCache":
        L = config.num_layers
        return cls(
            latent=jnp.zeros((L, num_pages, page_size, latent_width(config)), dtype),
            index=jnp.zeros((L, num_pages, page_size, config.index_head_dim), dtype)
            if config.index_topk else None,
        )


def leaf_shapes(config: ModelConfig) -> Dict[str, Dict[str, tuple]]:
    """Every leaf's shape, by group: the one statement of the layout."""
    c = config
    D, H, L, V = c.hidden_size, c.num_heads, c.num_layers, c.vocab_size
    Rq, Rkv, dn, dr, dv = (c.q_lora_rank, c.kv_lora_rank, c.qk_nope_head_dim,
                           c.qk_rope_head_dim, c.v_head_dim)
    Hi, di = c.index_n_heads, c.index_head_dim
    Ld = min(c.first_k_dense_replace, L)
    Lm, E, Et = L - Ld, c.num_experts, c.router_experts
    F, Fm = c.intermediate_size, c.moe_intermediate_size
    Fs = Fm * max(1, c.num_shared_experts)
    selector = {"idx_wq_b": (L, Rq, Hi * di), "idx_wk": (L, D, di), "idx_k_norm_w": (L, di),
                "idx_k_norm_b": (L, di), "idx_wproj": (L, D, Hi)} if c.index_topk else {}
    return {
        "top": {"embed": (V, D), "lm_head": (D, V), "final_norm": (D,)},
        "layers": {
            "attn_norm": (L, D), "wq_a": (L, D, Rq), "q_norm": (L, Rq),
            "wq_b": (L, Rq, H * (dn + dr)), "wkv_a": (L, D, Rkv + dr),
            "kv_norm": (L, Rkv), "w_uk": (L, H, Rkv, dn), "w_uv": (L, H, Rkv, dv),
            "wo": (L, H * dv, D), **selector, "mlp_norm": (L, D),
        },
        "dense": {"w_gate": (Ld, D, F), "w_up": (Ld, D, F), "w_down": (Ld, F, D)},
        "moe": {
            "router": (Lm, D, Et), "router_bias": (Lm, Et),
            "moe_gate": (Lm, E, D, Fm), "moe_up": (Lm, E, D, Fm), "moe_down": (Lm, E, Fm, D),
            "shared_gate": (Lm, D, Fs), "shared_up": (Lm, D, Fs), "shared_down": (Lm, Fs, D),
        },
    }


_ONES = ("attn_norm", "q_norm", "kv_norm", "idx_k_norm_w", "mlp_norm", "final_norm")
_S0 = np.float32(0.02 / 73.0)  # uniform int8 has std ~73: N(0, 0.02)-like weights


def _draw(config: ModelConfig, key: jax.Array, quant: bool, shapes=None,
          quant_axes=None, ones=None, draws=None) -> Params:
    """Every leaf from ``key``, each straight into its stored type.  Traced
    under ONE jit (``init_params*``): no eager temporaries, so the 1.2 GB
    expert leaves and the 5.6 GB whole fit beside the pages.  ``shapes``,
    ``quant_axes``, ``ones``: another family's layout in this one's form
    (models/lfm2.py); this family's own by default.  ``draws``: leaves with a
    draw of their own, name -> (key, shape, dtype) -> leaf."""
    dt = jnp.dtype(config.dtype)
    shapes = leaf_shapes(config) if shapes is None else shapes
    axes = QUANT_AXES if quant_axes is None else quant_axes
    ones = _ONES if ones is None else ones
    out: Params = {}
    n = 0
    for group, leaves in shapes.items():
        dst = out if group == "top" else out.setdefault(group, {})
        for name, shape in leaves.items():
            k = jax.random.fold_in(key, n)
            n += 1
            if name in ones:
                dst[name] = jnp.ones(shape, dt)
            elif draws and name in draws:
                dst[name] = draws[name](k, shape, dt)
            elif name == "idx_k_norm_b":
                dst[name] = jnp.zeros(shape, dt)
            elif name == "router_bias":
                # Small and nonzero, so that "the bias steers the choice
                # only" is visible to a test; f32 as the release keeps it.
                dst[name] = jax.random.normal(k, shape, jnp.float32) * 0.01
            elif quant and name in axes[group]:
                dst[name] = jax.random.randint(k, shape, -127, 128, dtype=jnp.int8)
                axis = axes[group][name]
                dst[name + "_scale"] = jnp.full(shape[:axis] + shape[axis + 1:], _S0, jnp.float32)
            else:
                dst[name] = (jax.random.normal(k, shape, jnp.float32) * 0.02).astype(dt)
    return out


def init_params(config: ModelConfig, key: jax.Array) -> Params:
    return jax.jit(lambda k: _draw(config, k, False))(key)


def init_params_quantized(config: ModelConfig, key: jax.Array) -> Params:
    return jax.jit(lambda k: _draw(config, k, True))(key)


def _groups(params: Params):
    """("top", the tree itself) and then each group of stacked leaves."""
    yield "top", params
    for g, leaves in params.items():
        if isinstance(leaves, dict):
            yield g, leaves


def quantize_params(params: Params, quant_axes=QUANT_AXES) -> Params:
    """int8 leaves with their scales from a float tree (no-op when done)."""
    from .quant import _quantize_jnp

    if "embed_scale" in params:
        return params
    out: Params = {}
    for group, leaves in _groups(params):
        dst = out if group == "top" else out.setdefault(group, {})
        for name, leaf in leaves.items():
            if isinstance(leaf, dict):
                continue
            axis = quant_axes[group].get(name)
            if axis is None:
                dst[name] = leaf
            else:
                dst[name], dst[name + "_scale"] = _quantize_jnp(leaf, axis)
    return out


def dequantize_params(params: Params, dtype="float32", quant_axes=QUANT_AXES) -> Params:
    """The float tree a quantized one stands for (the reference's weights)."""
    out: Params = {}
    for group, leaves in _groups(params):
        dst = out if group == "top" else out.setdefault(group, {})
        for name, leaf in leaves.items():
            if isinstance(leaf, dict) or name.endswith("_scale"):
                continue
            s = leaves.get(name + "_scale")
            if s is None:
                dst[name] = leaf
            else:
                axis = quant_axes[group][name]
                dst[name] = (leaf.astype(jnp.float32) * jnp.expand_dims(s, axis)).astype(dtype)
    return out


def held_experts(config: ModelConfig) -> range:
    lo = config.ep_rank * config.num_experts
    return range(lo, lo + config.num_experts)


def gate(x: jnp.ndarray, lp: Params, config: ModelConfig):
    """(chosen ids [T, K], weights [T, K] f32) over ALL the router's experts.
    ``gate_scoring`` "softmax": the K largest logits and a softmax over them.
    Else sigmoid scores; the bias enters the choice only; the best ``topk_group``
    of ``n_group`` groups by the sum of each group's two largest (one group:
    nothing to limit); top-K of what is left; weights normalised over the
    chosen and scaled."""
    T = x.shape[0]
    Et, G, K = config.router_experts, config.n_group, config.num_experts_per_token
    if config.gate_scoring == "softmax":  # static: the K largest logits, softmax over them
        w, chosen = jax.lax.top_k((x @ lp["router"]).astype(jnp.float32), K)
        return chosen, jax.nn.softmax(w, axis=-1)
    s = jax.nn.sigmoid((x @ lp["router"]).astype(jnp.float32))  # [T, Et]
    biased = s + lp["router_bias"].astype(jnp.float32)
    if G > 1:
        group_score = jnp.sum(jax.lax.top_k(biased.reshape(T, G, Et // G), 2)[0], axis=-1)
        keep = jax.lax.top_k(group_score, config.topk_group)[1]  # [T, topk_group]
        group_ok = jnp.any(keep[:, :, None] == jnp.arange(G)[None, None, :], axis=1)  # [T, G]
        biased = jnp.where(jnp.repeat(group_ok, Et // G, axis=-1), biased, -jnp.inf)
    chosen = jax.lax.top_k(biased, K)[1]
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if config.norm_topk_prob:
        total = jnp.sum(w, axis=-1, keepdims=True)
        if config.gate_norm_eps:  # static: a model without it gets no op for it
            total = total + config.gate_norm_eps
        w = w / total
    return chosen, w * config.routed_scaling_factor


EXPERT_LEAVES = tuple(n + tail for n in ("moe_gate", "moe_up", "moe_down") for tail in ("", "_scale"))


def moe_block(x: jnp.ndarray, lp: Params, config: ModelConfig, real=None, layer=None):
    """Shared expert + the routed experts chosen AND held.  Returns (y [T, D],
    how many (token, expert) pairs of REAL tokens landed on each held expert
    [E] int32).  ``real`` [T] bool: False for a padding token, whose pairs are
    no pairs (None: every token is real).  With ``layer`` the expert leaves
    of ``lp`` are the stacked ones [L, E, ...] and it picks the layer: the
    grouped matmul reads a block of the stacked leaf in place, where a slice
    of a layer would be a copy of all its experts."""
    chosen, w = gate(x, lp, config)
    local = chosen - config.ep_rank * config.num_experts
    here = (local >= 0) & (local < config.num_experts)
    if real is not None:
        here &= real[:, None]
    routed, load = expert_dispatch(x, local, w, lp, config.num_experts, valid=here, layer=layer)
    shared = mlp(x, {"w_" + k[len("shared_"):]: v for k, v in lp.items()
                     if k.startswith("shared_")})
    return routed + shared, load


def _rope_head(x, positions, inv_freq, dr: int):
    """Half-split rope on the first ``dr`` dims of the selector's q or k."""
    return jnp.concatenate([apply_rope(x[..., :dr], positions, inv_freq), x[..., dr:]], axis=-1)


def _layer_norm(x, w, b, eps=1e-6):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w + b


def _write(pages, rows, slots):
    """Scatter ``rows`` [T, d] into flat slots of ``pages`` [NP, ps, d];
    slot -1 (padding) is dropped."""
    NP, ps, d = pages.shape
    at = jnp.where(slots < 0, NP * ps, slots)
    return pages.reshape(NP * ps, d).at[at].set(rows.astype(pages.dtype), mode="drop").reshape(
        NP, ps, d)


class MlaStep(NamedTuple):
    """What the latent layers of ONE step share: made once a step by
    ``mla_step`` from the step's rows, static parts included."""

    pos: jnp.ndarray  # [T]
    real: jnp.ndarray  # [T] False for a padding token (slot -1)
    first: jnp.ndarray  # [S] a row's first token (a single-token row's token)
    single: jnp.ndarray  # [S] rows of one query token
    inv_freq: Any  # the rotation's frequencies; None: nothing is rotated
    sm_scale: float
    decode: bool


def mla_step(c: ModelConfig, rb: RaggedBatch, decode: bool) -> MlaStep:
    T = rb.token_ids.shape[0]
    # ``mla_rope`` False (kimi_linear): the "rope" lanes of q and of the
    # latent entry are used as they come out of the projections.
    inv_freq = (rope_frequencies(c.qk_rope_head_dim, c.rope_theta, c.rope_scaling)
                if c.mla_rope else None)
    sm_scale = (c.qk_nope_head_dim + c.qk_rope_head_dim) ** -0.5 * yarn_mscale(c.rope_scaling) ** 2
    real = rb.slot_mapping >= 0
    S = rb.kv_lens.shape[0]
    q_lens = rb.cu_q_lens[1:] - rb.cu_q_lens[:-1]
    first = jnp.clip(rb.cu_q_lens[:-1], 0, T - 1)
    single = (q_lens == 1) & (jnp.arange(S) < rb.num_seqs[0])
    return MlaStep(rb.positions, real, first, single, inv_freq, sm_scale, decode)


def mla_project(x, lp: Params, c: ModelConfig, st: MlaStep, width: int):
    """A latent layer's projections of the step's tokens ``x`` [T, D]: (the
    compressed query, None without ``q_lora_rank``: then ONE projection
    ``wq`` and no ``q_norm``; q [T, H, dn + dr]; its last dr lanes, rotated
    where the model rotates; the cache entry [T, ``width``] = [RMSNorm(c) |
    k^R | zero lanes]; ``absorbed(rows)``: the queries of those tokens in the
    absorbed form, q~ = W^UK^T q^N, which scores the cached entry directly)."""
    T = x.shape[0]
    H, dn, dr, Rkv = c.num_heads, c.qk_nope_head_dim, c.qk_rope_head_dim, c.kv_lora_rank
    if c.q_lora_rank:  # static
        cq = rms_norm(linear(x, lp, "wq_a"), lp["q_norm"], c.rms_norm_eps)
        q = linear(cq, lp, "wq_b").reshape(T, H, dn + dr)
    else:
        cq, q = None, linear(x, lp, "wq").reshape(T, H, dn + dr)
    rotate = (lambda v: v) if st.inv_freq is None else (
        lambda v: apply_rope_interleaved(v, st.pos, st.inv_freq))
    q_rope = rotate(q[..., dn:])
    kv = linear(x, lp, "wkv_a")
    k_rope = rotate(kv[:, None, Rkv:])[:, 0]
    tail = width - Rkv - dr  # zero lanes up to the stored width
    entry = jnp.concatenate(
        [rms_norm(kv[:, :Rkv], lp["kv_norm"], c.rms_norm_eps), k_rope,
         jnp.zeros((T, tail), kv.dtype)], axis=-1)

    def absorbed(rows=slice(None)):
        return jnp.concatenate(
            [jnp.einsum("thn,hcn->thc", q[rows, :, :dn], lp["w_uk"]), q_rope[rows],
             jnp.zeros(q_rope[rows].shape[:2] + (tail,), q.dtype)], axis=-1)

    return cq, q, q_rope, entry, absorbed


def mla_places(rb: RaggedBatch, l, pages_a_layer: int, page_size: int):
    """(flat slots [T], page tables [S, PP]) of layer ``l`` in the latent
    pages of all layers laid end to end."""
    slots = jnp.where(rb.slot_mapping < 0, -1, rb.slot_mapping + l * (pages_a_layer * page_size))
    return slots, rb.page_indices + l * pages_a_layer


def dense_attention(c: ModelConfig, rb: RaggedBatch, st: MlaStep, absorbed, q, q_rope, entry,
                    lp: Params, lat, slots, tables):
    """No selector: a one-token row attends to its whole context in the
    absorbed form (the kernel), a prompt chunk in the decompressed form,
    and a chunk's output needs no W^UV: it is per head already.  Returns (the
    layer's output [T, D], the pages with the step's entries written)."""
    T = q.shape[0]
    S = rb.kv_lens.shape[0]
    H, dn, dv, Rkv = c.num_heads, c.qk_nope_head_dim, c.v_head_dim, c.kv_lora_rank
    lat = _write(lat, entry, slots)
    kw = dict(sm_scale=st.sm_scale, rank_v=Rkv)

    def one_token_rows(_):
        o_lat = dense_decode_attention(
            absorbed(st.first), lat, jnp.where(st.single, rb.kv_lens, 0), tables, **kw)
        return jnp.einsum("shc,hcv->shv", o_lat, lp["w_uv"])  # [S, H, dv]

    if st.decode:
        o = one_token_rows(None)
    else:
        o = dense_prefill_attention(
            jnp.concatenate([q[..., :dn], q_rope], axis=-1), lat, lp["w_uk"], lp["w_uv"],
            rb.kv_lens, tables, rb.cu_q_lens, rb.num_seqs, sm_scale=st.sm_scale)
        o1 = jax.lax.cond(jnp.any(st.single), one_token_rows,
                          lambda _: jnp.zeros((S, H, dv), o.dtype), None)
        o = o.at[jnp.where(st.single, st.first, T)].set(o1, mode="drop")
    return linear(o.reshape(T, H * dv), lp, "wo"), lat


def mla_block(x, lp: Params, c: ModelConfig, rb: RaggedBatch, st: MlaStep, l, lat,
              pages_a_layer: int):
    """One latent attention layer WITHOUT a selector over the flat pages
    ``lat`` [layers * P, ps, width], of which layer ``l``'s are the l-th
    ``pages_a_layer``: this family's layers where ``index_topk`` is 0, and
    the hybrid family's latent layers (models/lfm2.py).  Returns (the layer's
    output [T, D], the pages)."""
    _, q, q_rope, entry, absorbed = mla_project(x, lp, c, st, lat.shape[-1])
    slots, tables = mla_places(rb, l, pages_a_layer, lat.shape[1])
    return dense_attention(c, rb, st, absorbed, q, q_rope, entry, lp, lat, slots, tables)


def forward_ragged(
    params: Params,
    config: ModelConfig,
    rb: RaggedBatch,
    cache: LatentKVCache,
    *,
    decode: bool = False,
    return_selection: bool = False,
    # Blocks of the selector's prompt loop (ops/sparse_mla.py) alone; tests make
    # them small.  The dense path's kernel has its own constants (ops/dense_mla.py).
    block_q: int = 64,
    block_k: int = 1024,
    **_llama_only,  # attn_impl, kernels, kv_scale, mesh, lora_rank: family.py checks them
) -> Tuple[jnp.ndarray, LatentKVCache, Any]:
    """The unified step of models/llama.py for this family: returns (logits
    [S, V] of each row's last token, the updated cache, aux).  ``aux`` is
    [4] int32: (routed pairs that landed on held experts, tokens routed, held
    experts with such a pair: the experts READ, experts held), over the
    step's real tokens and all MoE layers; with ``return_selection`` it
    is instead the list of S_t per layer (decode: positions [S, k]; else a
    mask [T, PP * ps]; None for a model without a selector, whose S_t is
    every position up to t)."""
    c = config
    rb = jax.tree_util.tree_map(jnp.asarray, rb)  # host arrays when not under jit
    (T,) = rb.token_ids.shape
    H, dn, dr, dv, Rkv = c.num_heads, c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim, c.kv_lora_rank
    Hi, di, eps = c.index_n_heads, c.index_head_dim, c.rms_norm_eps
    selector = c.index_topk > 0
    st = mla_step(c, rb, decode)
    pos, real, first, single, inv_freq, sm_scale = st[:6]
    L, P_layer, ps = cache.latent.shape[:3]
    Ld = min(c.first_k_dense_replace, L)
    S = rb.kv_lens.shape[0]

    def attention(x, lp, l, lat, idx):
        if not selector:
            return mla_block(x, lp, c, rb, st, l, lat, P_layer) + (None, None)  # no indexer pages, no S_t
        cq, q, q_rope, entry, absorbed = mla_project(x, lp, c, st, lat.shape[-1])
        slots, tables = mla_places(rb, l, P_layer, ps)
        # A prompt program attends in the form its token count pays for least
        # (ops/sparse_mla.py ``prefill_form``): the absorbed XLA loop, or S_t
        # as a mask and the decompressed kernel, whose output is per head.
        kernel = not decode and prefill_form(T) == "decompressed"
        q_abs = None if kernel else absorbed()
        qi = _rope_head(linear(cq, lp, "idx_wq_b").reshape(T, Hi, di), pos, inv_freq, dr)
        ki = _layer_norm(x @ lp["idx_wk"], lp["idx_k_norm_w"], lp["idx_k_norm_b"])
        ki = _rope_head(ki[:, None, :], pos, inv_freq, dr)[:, 0]
        wi = (x @ lp["idx_wproj"]).astype(jnp.float32) * (Hi**-0.5 * di**-0.5)
        lat, idx = _write(lat, entry, slots), _write(idx, ki, slots)
        kw = dict(topk=c.index_topk, sm_scale=sm_scale, rank_v=Rkv)
        per_head = lambda o_lat: jnp.einsum("thc,hcv->thv", o_lat, lp["w_uv"])  # noqa: E731

        def one_token_rows(_):
            # (o [S, H, Rkv], S_t as positions [S, k] or, unasked, None)
            return fused_sparse_decode_attention(
                absorbed(first) if kernel else q_abs[first], qi[first], wi[first], lat, idx,
                pos[first], jnp.where(single, rb.kv_lens, 0), tables,
                return_selection=return_selection, **kw)

        if decode:
            o_lat, sel = one_token_rows(None)
            return linear(per_head(o_lat).reshape(T, H * dv), lp, "wo"), lat, idx, sel
        if kernel:
            sel = sparse_prefill_selection(
                qi, wi, idx, pos, rb.kv_lens, tables, rb.cu_q_lens, rb.num_seqs,
                topk=c.index_topk, block_q=block_q, block_k=block_k)
            o = latent_prefill_attention(
                jnp.concatenate([q[..., :dn], q_rope], axis=-1), sel, lat, lp["w_uk"], lp["w_uv"],
                rb.kv_lens, tables, rb.cu_q_lens, rb.num_seqs, sm_scale=sm_scale,
                name=SPARSE_SCOPES["prefill"])  # [T, H, dv]
            sel = sel if return_selection else None
        else:
            res = sparse_prefill_attention(
                q_abs, qi, wi, lat, idx, pos, rb.kv_lens, tables, rb.cu_q_lens, rb.num_seqs,
                block_q=block_q, block_k=block_k, return_mask=return_selection, **kw)
            o, sel = res if return_selection else (res, None)  # [T, H, Rkv]
        # Decode rows riding a mixed step: the one-query path, only when the
        # step has any; its output joins the chunks' in the chunks' form.
        k_sel = min(c.index_topk, rb.page_indices.shape[1] * ps)

        def riding_rows(_):
            o1, sel1 = one_token_rows(None)
            return (per_head(o1) if kernel else o1), sel1

        o1, sel1 = jax.lax.cond(
            jnp.any(single), riding_rows,
            lambda _: (jnp.zeros((S,) + o.shape[1:], o.dtype),
                       jnp.full((S, k_sel), -1, jnp.int32) if return_selection else None),
            None)
        at = jnp.where(single, first, T)
        o = o.at[at].set(o1, mode="drop")
        if return_selection:
            hot = jnp.zeros((S, sel.shape[1] + 1), bool).at[
                jnp.arange(S)[:, None], jnp.where(sel1 < 0, sel.shape[1], sel1)].set(True)
            sel = sel.at[at].set(hot[:, :-1], mode="drop")
        if not kernel:
            o = per_head(o)
        return linear(o.reshape(T, H * dv), lp, "wo"), lat, idx, sel

    def layer(h, lat, idx, lp, l, is_moe: bool):
        a, lat, idx, sel = attention(rms_norm(h, lp["attn_norm"], eps), lp, l, lat, idx)
        h = h + a
        x = rms_norm(h, lp["mlp_norm"], eps)
        if is_moe:
            y, load = moe_block(x, lp, c, real, layer=l - Ld)
            counts = jnp.stack([jnp.sum(load), jnp.sum(load > 0, dtype=jnp.int32)])
        else:
            y, counts = mlp(x, lp), jnp.zeros((2,), jnp.int32)
        return h + y, lat, idx, counts, sel

    def at_layer(group: str, i):
        """Layer i's leaves; the expert leaves whole (``moe_block``)."""
        return {k: a if k in EXPERT_LEAVES else a[i] for k, a in params[group].items()}

    h = embed_lookup(params, rb.token_ids, jnp.dtype(c.dtype))
    lat = cache.latent.reshape((L * P_layer,) + cache.latent.shape[2:])
    idx = cache.index.reshape((L * P_layer,) + cache.index.shape[2:]) if selector else None
    counts = jnp.zeros((2,), jnp.int32)  # pairs landed, experts read
    sels = []
    for l in range(Ld):  # the leading dense layers: few, so unrolled
        h, lat, idx, _, sel = layer(h, lat, idx, {**at_layer("layers", l), **at_layer("dense", l)},
                                    l, False)
        sels.append(sel)
    if decode or return_selection:
        # Static layer indices: XLA prefetches layer l+1's weights during
        # layer l (see models/llama.py on the fused decode program).
        for l in range(Ld, L):
            h, lat, idx, p, sel = layer(
                h, lat, idx, {**at_layer("layers", l), **at_layer("moe", l - Ld)}, l, True)
            counts += p
            sels.append(sel)
    elif L > Ld:
        # The scan carries only the layer's number and indexes the stacked
        # leaves itself: slicing params["layers"][Ld:] for ``xs`` copied 1.3 GB
        # of attention weights in every step (slice s8[5,16384,7168], first
        # chip profile of PR 28).
        def body(carry, l):
            h, lat, idx, counts = carry
            lp = {**at_layer("layers", l), **at_layer("moe", l - Ld)}
            h, lat, idx, p, _ = layer(h, lat, idx, lp, l, True)
            return (h, lat, idx, counts + p), None

        (h, lat, idx, counts), _ = jax.lax.scan(
            body, (h, lat, idx, counts), jnp.arange(Ld, L, dtype=jnp.int32))

    h = rms_norm(h, params["final_norm"], eps)
    rows = jnp.clip(rb.cu_q_lens[1:] - 1, 0, T - 1)
    logits = lm_logits(params, h[rows])
    new_cache = LatentKVCache(lat.reshape(cache.latent.shape),
                              idx.reshape(cache.index.shape) if selector else None)
    if return_selection:
        return logits, new_cache, sels
    tokens = jnp.sum(real, dtype=jnp.int32) * (L - Ld)
    held = jnp.asarray(c.num_experts * (L - Ld), jnp.int32)
    return logits, new_cache, jnp.stack([counts[0], tokens, counts[1], held])
