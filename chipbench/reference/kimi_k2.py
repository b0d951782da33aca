"""Plain reference of Kimi-K2-Instruct's forward pass (``model_type``
``kimi_k2``: DeepSeek-V3's block): latent attention (MLA) over the WHOLE
causal context, the sigmoid gate (one group of 384 in the release), a shared
expert and the routed experts HELD here.

Straight ``jax.numpy`` in float32 under ``default_matmul_precision("highest")``:
no cache, no kernels, no batching, one pass over one sequence.  Attention is
written in the DECOMPRESSED per-head form, k_j,h = [W^UK_h c_j ; k^R_j], v_j,h =
W^UV_h c_j, with a full causal softmax: independent of the system's absorbed
one-query path, and of the system's blocks and running softmax in a prompt
chunk.  It follows the DeepSeek-V3 modeling code the release ships with and
its config.json (https://huggingface.co/moonshotai/Kimi-K2-Instruct); ``cfg``
is that config.json as a dict.  It imports nothing of the program under test
and nothing of the other reference: the tests tie the two together on the
same weights.  ``chipbench/reference/kimi_k2.py`` is a copy.

Departures from the release, each also under ``assumed`` in
chipbench/configs/kimi-k2-6l-ep32.json:

1. MLA's rope is INTERLEAVED (x[2i], x[2i+1]) and the softmax scale is
   (qk_nope_head_dim + qk_rope_head_dim)^-0.5 * (0.1 * mscale_all_dim *
   ln(factor) + 1)^2, as that modeling code has them; config.json says neither.
2. YaRN's ramp with ``beta_fast == beta_slow`` (both 1): the two correction
   dimensions are the floor and the ceiling of one number (19 and 20 of 32),
   so the ramp is one step wide; were they equal the release adds 0.001.
3. No FP8: the release's weights are block-FP8.  That is a precision, not
   mathematics; everything here is float32.
4. ``held`` lists the routed experts this chip holds: the router scores and
   chooses over ALL ``n_routed_experts_published`` experts, the sum runs over
   chosen AND held, and that partial result goes on to the next layer (the
   expert-parallel share of model-configs section 4).  ``held=None`` takes
   the share ``cfg`` states (``ep_rank``); an uncut ``cfg`` holds them all.

Parameter tree (leading axis = layer; float arrays of any dtype):
  embed [V, D], lm_head [D, V], final_norm [D]
  layers: attn_norm [L, D], wq_a [L, D, Rq], q_norm [L, Rq],
          wq_b [L, Rq, H*(dn+dr)]  (per head: dn no-rope dims, then dr rope dims),
          wkv_a [L, D, Rkv+dr]     (Rkv latent dims, then the shared rope key),
          kv_norm [L, Rkv], w_uk [L, H, Rkv, dn], w_uv [L, H, Rkv, dv],
          wo [L, H*dv, D], mlp_norm [L, D]   (further leaves are ignored)
  dense (the first_k_dense_replace leading layers): w_gate, w_up [Ld, D, F], w_down [Ld, F, D]
  moe (the rest): router [Lm, D, E_all], router_bias [Lm, E_all],
          moe_gate, moe_up [Lm, E_held, D, Fm], moe_down [Lm, E_held, Fm, D],
          shared_gate, shared_up [Lm, D, Fs], shared_down [Lm, Fs, D]
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def yarn_inv_freq(dim: int, cfg: dict):
    """Inverse frequencies [dim/2] with DeepSeek-V3's YaRN correction."""
    theta = float(cfg.get("rope_theta", 10000.0))
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=F32) / dim))
    sc = cfg.get("rope_scaling")
    if not sc:
        return inv
    orig, factor = sc["original_max_position_embeddings"], float(sc["factor"])

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(corr(sc["beta_fast"])), 0)
    high = min(math.ceil(corr(sc["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=F32) - low) / (high - low), 0, 1)
    return inv / factor * ramp + inv * (1 - ramp)


def softmax_scale(cfg: dict) -> float:
    scale = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    sc = cfg.get("rope_scaling")
    if sc and sc.get("mscale_all_dim"):
        m = 0.1 * sc["mscale_all_dim"] * math.log(sc["factor"]) + 1.0
        scale *= m * m
    return scale


def rope_interleaved(x, pos, inv):
    """x [T, ..., d]: pairs (x[2i], x[2i+1]) turned by pos * inv[i]."""
    ang = pos.astype(F32)[:, None] * inv
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (-1,))
    c, s = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * c - b * s, b * c + a * s], axis=-1).reshape(x.shape)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def ffn(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, F32), tree)


def layer_params(params: dict, cfg: dict, l: int) -> dict:
    """Layer l's leaves, with its FFN's under the same dict."""
    lp = {k: v[l] for k, v in params["layers"].items()}
    dense = cfg["first_k_dense_replace"]
    group, i = (params["dense"], l) if l < dense else (params["moe"], l - dense)
    lp.update({k: v[i] for k, v in group.items()})
    return lp


def attention(lp: dict, cfg: dict, x, pos, q_block=None):
    """MLA, every query over every position up to its own.  Returns [T, D].
    ``q_block`` only bounds memory: queries go through in blocks of that many,
    each against every key, with the same arithmetic."""
    T = x.shape[0]
    H, dn, dr, dv = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                     cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    Rkv, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    inv = yarn_inv_freq(dr, cfg)
    cq = rms_norm(x @ lp["wq_a"], lp["q_norm"], eps)
    q = (cq @ lp["wq_b"]).reshape(T, H, dn + dr)
    q = jnp.concatenate([q[..., :dn], rope_interleaved(q[..., dn:], pos, inv)], axis=-1)
    kv = x @ lp["wkv_a"]
    c = rms_norm(kv[:, :Rkv], lp["kv_norm"], eps)
    k_rope = rope_interleaved(kv[:, Rkv:], pos, inv)  # one rope key for all heads
    k = jnp.concatenate([jnp.einsum("sc,hcn->shn", c, lp["w_uk"]),
                         jnp.broadcast_to(k_rope[:, None, :], (T, H, dr))], axis=-1)
    v = jnp.einsum("sc,hcv->shv", c, lp["w_uv"])
    outs = []
    step = q_block or T
    for a in range(0, T, step):
        b = min(T, a + step)
        s = jnp.einsum("thd,shd->hts", q[a:b], k) * softmax_scale(cfg)
        s = jnp.where(pos[None, :] <= pos[a:b, None], s, -jnp.inf)
        outs.append(jnp.einsum("hts,shv->thv", jax.nn.softmax(s, axis=-1), v).reshape(b - a, H * dv))
    return jnp.concatenate(outs) @ lp["wo"]


def gate(lp: dict, cfg: dict, x):
    """(chosen ids [T, K], weights [T, K]) over ALL the router's experts:
    sigmoid scores, the bias in the CHOICE only, the best ``topk_group`` of
    ``n_group`` groups by the sum of each group's two largest (the release
    has one group: nothing is limited), top K, weights normalised and scaled."""
    E = lp["router"].shape[-1]
    G, Gk, K = cfg.get("n_group", 1), cfg.get("topk_group", 1), cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(x @ lp["router"])  # [T, E]
    biased = s + lp["router_bias"]
    group_score = jnp.sum(jax.lax.top_k(biased.reshape(-1, G, E // G), 2)[0], axis=-1)  # [T, G]
    keep = jax.lax.top_k(group_score, Gk)[1]  # [T, Gk]
    group_ok = jnp.zeros(group_score.shape, bool).at[jnp.arange(x.shape[0])[:, None], keep].set(True)
    allowed = jnp.repeat(group_ok, E // G, axis=-1)
    chosen = jax.lax.top_k(jnp.where(allowed, biased, -jnp.inf), K)[1]  # [T, K]
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if cfg.get("norm_topk_prob", True):
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return chosen, w * cfg.get("routed_scaling_factor", 1.0)


def moe(lp: dict, cfg: dict, x, held):
    """Shared expert plus the routed experts that are chosen AND held.
    ``held[i]`` is the global id of the i-th expert of ``lp['moe_*']``."""
    chosen, w = gate(lp, cfg, x)
    y = ffn(x, lp["shared_gate"], lp["shared_up"], lp["shared_down"])
    for i, e in enumerate(held):
        w_e = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1, keepdims=True)  # [T, 1]
        y = y + w_e * ffn(x, lp["moe_gate"][i], lp["moe_up"][i], lp["moe_down"][i])
    return y


def layer(lp: dict, cfg: dict, h, pos, held, q_block=None):
    """One pre-norm residual block; ``lp`` from ``layer_params``."""
    eps = cfg["rms_norm_eps"]
    h = h + attention(lp, cfg, rms_norm(h, lp["attn_norm"], eps), pos, q_block)
    x = rms_norm(h, lp["mlp_norm"], eps)
    if "router" in lp:
        return h + moe(lp, cfg, x, held)
    return h + ffn(x, lp["w_gate"], lp["w_up"], lp["w_down"])


def held_experts(cfg: dict) -> list:
    n, rank = cfg["n_routed_experts"], cfg.get("ep_rank", 0)
    return list(range(rank * n, (rank + 1) * n))


def forward(params: dict, cfg: dict, tokens, held=None, q_block=None):
    """Logits [T, V] of one sequence."""
    with jax.default_matmul_precision("highest"):
        params = f32(params)
        tokens = jnp.asarray(tokens, jnp.int32)
        pos = jnp.arange(tokens.shape[0], dtype=jnp.int32)
        held = held_experts(cfg) if held is None else list(held)
        h = params["embed"][tokens]
        for l in range(cfg["num_hidden_layers"]):
            h = layer(layer_params(params, cfg, l), cfg, h, pos, held, q_block)
        h = rms_norm(h, params["final_norm"], cfg["rms_norm_eps"])
        return h @ params["lm_head"]
