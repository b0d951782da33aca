"""Plain reference of DeepSeek-V3.2-Exp's forward pass: latent attention (MLA)
under the learned sparse selector (DSA), the sigmoid gate with group-limited
choice, a shared expert and the routed experts HELD here.

Straight ``jax.numpy`` in float32 under ``default_matmul_precision("highest")``:
no cache, no kernels, no batching, one pass over one sequence.  It follows the
release's ``inference/model.py`` and config.json
(https://huggingface.co/deepseek-ai/DeepSeek-V3.2-Exp); ``cfg`` is that
config.json as a dict.  It imports nothing of the program under test: the
program's tests and the chip parity run compare against THIS, on the same
(dequantised) weights.  ``chipbench/reference/deepseek_v32.py`` is a copy.

Departures from the release, each also under ``assumed`` in
chipbench/configs/deepseek-v3.2-exp-6l-ep16.json:

1. The selector's q and k are NOT multiplied by a Hadamard matrix: the
   rotation is orthogonal and changes no dot product q.k.
2. No FP8: the release casts the selector's q and k (and caches k and the
   latent) in FP8 with block scales.  That is a precision, not mathematics;
   everything here is float32.
3. Rope pairing inside the selector is HALF-SPLIT (x[i], x[i + d/2]) over its
   first ``qk_rope_head_dim`` dims, as the release's corrected indexer code
   has it; MLA's rope is INTERLEAVED (x[2i], x[2i+1]).  config.json says
   neither.
4. The selector's key norm is a LayerNorm with weight and bias, eps 1e-6.
5. The multi-token-prediction module is not loaded.
6. ``held`` lists the routed experts this chip holds: the router scores and
   chooses over ALL ``n_routed_experts_published`` experts, the sum runs over
   chosen AND held, and that partial result goes on to the next layer (the
   expert-parallel share of model-configs section 4).  ``held=None`` holds
   every expert: the uncut model.

Parameter tree (leading axis = layer; float arrays of any dtype):
  embed [V, D], lm_head [D, V], final_norm [D]
  layers: attn_norm [L, D], wq_a [L, D, Rq], q_norm [L, Rq],
          wq_b [L, Rq, H*(dn+dr)]  (per head: dn no-rope dims, then dr rope dims),
          wkv_a [L, D, Rkv+dr]     (Rkv latent dims, then the shared rope key),
          kv_norm [L, Rkv], w_uk [L, H, Rkv, dn], w_uv [L, H, Rkv, dv],
          wo [L, H*dv, D], idx_wq_b [L, Rq, Hi*di], idx_wk [L, D, di],
          idx_k_norm_w [L, di], idx_k_norm_b [L, di], idx_wproj [L, D, Hi],
          mlp_norm [L, D]
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
    """Inverse frequencies [dim/2] with DeepSeek's YaRN correction."""
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


def rope_half(x, pos, inv):
    """x [T, ..., d]: pairs (x[i], x[i + d/2]) turned by pos * inv[i]."""
    ang = pos.astype(F32)[:, None] * inv
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (-1,))
    c, s = jnp.cos(ang), jnp.sin(ang)
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * c - b * s, b * c + a * s], axis=-1)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def layer_norm(x, w, b, eps=1e-6):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


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


def index_keys(lp: dict, cfg: dict, x, pos):
    """k^I [T, di]: LayerNorm, then rope on the first dr dims."""
    dr = cfg["qk_rope_head_dim"]
    k = layer_norm(x @ lp["idx_wk"], lp["idx_k_norm_w"], lp["idx_k_norm_b"])
    return jnp.concatenate(
        [rope_half(k[..., :dr], pos, yarn_inv_freq(dr, cfg)), k[..., dr:]], axis=-1)


def index_scores(lp: dict, cfg: dict, x, cq, pos, k):
    """I[t, s] of the queries (x, cq, pos) against every key ``k`` (s > t
    included; ``select`` masks)."""
    T = x.shape[0]
    Hi, di, dr = cfg["index_n_heads"], cfg["index_head_dim"], cfg["qk_rope_head_dim"]
    q = (cq @ lp["idx_wq_b"]).reshape(T, Hi, di)
    q = jnp.concatenate(
        [rope_half(q[..., :dr], pos, yarn_inv_freq(dr, cfg)), q[..., dr:]], axis=-1)
    w = (x @ lp["idx_wproj"]) * Hi**-0.5 * di**-0.5  # [T, Hi]
    return jnp.einsum("tj,tjs->ts", w, jax.nn.relu(jnp.einsum("tjd,sd->tjs", q, k)))


def select(scores, qpos, kpos, topk: int):
    """S_t as a mask [Tq, T]: the min(topk, t+1) positions s <= t of largest
    I[t, s]; equal scores go to the lowest s."""
    Tq, T = scores.shape
    causal = kpos[None, :] <= qpos[:, None]
    masked = jnp.where(causal, scores, -jnp.inf)
    order = jnp.argsort(-masked, axis=-1, stable=True)[:, : min(topk, T)]
    chosen = jnp.zeros((Tq, T), bool).at[jnp.arange(Tq)[:, None], order].set(True)
    return chosen & causal


def attention(lp: dict, cfg: dict, x, pos, selected=None, q_block=None):
    """MLA over S_t.  Returns (output [T, D], the mask S [T, T] used).
    ``q_block`` only bounds memory: queries go through in blocks of that many,
    each against every key, with the same arithmetic."""
    T = x.shape[0]
    H, dn, dr, dv = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                     cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    Rkv, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    inv = yarn_inv_freq(dr, cfg)
    cq = rms_norm(x @ lp["wq_a"], lp["q_norm"], eps)
    q = (cq @ lp["wq_b"]).reshape(T, H, dn + dr)
    q_nope, q_rope = q[..., :dn], rope_interleaved(q[..., dn:], pos, inv)
    kv = x @ lp["wkv_a"]
    c = rms_norm(kv[:, :Rkv], lp["kv_norm"], eps)
    k_rope = rope_interleaved(kv[:, Rkv:], pos, inv)  # one rope key for all heads
    k_nope = jnp.einsum("sc,hcn->shn", c, lp["w_uk"])
    v = jnp.einsum("sc,hcv->shv", c, lp["w_uv"])
    k_idx = index_keys(lp, cfg, x, pos) if selected is None else None
    outs, masks = [], []
    step = q_block or T
    for a in range(0, T, step):
        b = min(T, a + step)
        if selected is None:
            sel = select(index_scores(lp, cfg, x[a:b], cq[a:b], pos[a:b], k_idx),
                         pos[a:b], pos, cfg["index_topk"])
        else:
            sel = selected[a:b]
        s = (jnp.einsum("thn,shn->hts", q_nope[a:b], k_nope)
             + jnp.einsum("thr,sr->hts", q_rope[a:b], k_rope))
        s = jnp.where(sel[None], s * softmax_scale(cfg), -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("hts,shv->thv", p, v).reshape(b - a, H * dv))
        masks.append(sel)
    return jnp.concatenate(outs) @ lp["wo"], jnp.concatenate(masks)


def gate(lp: dict, cfg: dict, x):
    """(chosen ids [T, K], weights [T, K]) over ALL the router's experts."""
    E = lp["router"].shape[-1]
    G, Gk, K = cfg["n_group"], cfg["topk_group"], cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(x @ lp["router"])  # [T, E]
    biased = s + lp["router_bias"]  # the bias steers the CHOICE only
    per_group = biased.reshape(-1, G, E // G)
    group_score = jnp.sum(jax.lax.top_k(per_group, 2)[0], axis=-1)  # [T, G]
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


def layer(lp: dict, cfg: dict, h, pos, held, selected=None, q_block=None):
    """One pre-norm residual block; ``lp`` from ``layer_params``."""
    eps = cfg["rms_norm_eps"]
    a, sel = attention(lp, cfg, rms_norm(h, lp["attn_norm"], eps), pos, selected, q_block)
    h = h + a
    x = rms_norm(h, lp["mlp_norm"], eps)
    if "router" in lp:
        return h + moe(lp, cfg, x, held), sel
    return h + ffn(x, lp["w_gate"], lp["w_up"], lp["w_down"]), sel


def held_experts(cfg: dict) -> list:
    n, rank = cfg["n_routed_experts"], cfg.get("ep_rank", 0)
    return list(range(rank * n, (rank + 1) * n))


def forward(params: dict, cfg: dict, tokens, held=None, selected=None, q_block=None):
    """Logits [T, V] of one sequence and the list of S masks (one [T, T] per
    layer).  ``selected``: a list of masks to FORCE (the system's S_t), which
    separates "chose other positions at a near tie" from "computed them
    wrongly"."""
    with jax.default_matmul_precision("highest"):
        params = f32(params)
        tokens = jnp.asarray(tokens, jnp.int32)
        pos = jnp.arange(tokens.shape[0], dtype=jnp.int32)
        held = held_experts(cfg) if held is None else list(held)
        h = params["embed"][tokens]
        masks = []
        for l in range(cfg["num_hidden_layers"]):
            h, sel = layer(layer_params(params, cfg, l), cfg, h, pos, held,
                           None if selected is None else selected[l], q_block)
            masks.append(sel)
        h = rms_norm(h, params["final_norm"], cfg["rms_norm_eps"])
        return h @ params["lm_head"], masks
