"""Plain reference of LFM2-8B-A1B's forward pass (``model_type`` ``lfm2_moe``):
gated short convolutions among GQA attention layers with QK-norm, dense
SwiGLU feed-forwards in the leading layers and sigmoid-gated experts in the
rest.

Straight ``jax.numpy`` in float32 under ``default_matmul_precision("highest")``:
no cache, no kernels, no batching, one pass over one sequence.  The
convolution is an explicit sum of shifted copies of ``u`` over the whole
sequence, attention a full causal softmax a head, the experts a loop.  It
follows the ``lfm2_moe`` modelling code of ``transformers`` and the release's
config.json (https://huggingface.co/LiquidAI/LFM2-8B-A1B); ``cfg`` is that
config.json as a dict.  It imports nothing of the program under test.
``chipbench/reference/lfm2_moe.py`` is a copy.

Departures from the release and points its config.json does not settle, each
also under ``assumed`` in chipbench/configs/lfm2-8b-a1b.json:

1. The convolution layer's input projection splits into three equal parts in
   the order B, C, x; ``u = B * x`` goes through the taps, C gates the result.
   The taps are ``taps[k]`` [D] for k = 0 .. L-1 with L = ``conv_L_cache``:
   ``v_t = sum_k taps[k] * u_{t-(L-1)+k}`` (a causal depthwise convolution;
   ``u`` is 0 before position 0), no bias (``conv_bias`` false).
2. QK-norm: RMSNorm over each head's values of q and of k (learned weights of
   the head size, eps ``norm_eps``) BEFORE the rotation; the rotation turns
   halves (x[:d/2], x[d/2:]) over the whole head, theta ``rope_theta``.
3. The gate: sigmoid scores; ``use_expert_bias`` adds a bias in the CHOICE
   only; the weights are the chosen scores over (their sum + 1e-6), times
   ``routed_scaling_factor``.
4. The model's last RMSNorm (the release names it ``embedding_norm``) is
   applied at the OUTPUT; the logits are over the embedding's transpose
   (``tie_word_embeddings``; an ``lm_head`` leaf is used where there is one).
5. No bfloat16: that is a precision, not mathematics; everything here is
   float32.
6. ``held`` lists the experts this chip holds: the router scores and chooses
   over ALL experts, the sum runs over chosen AND held (the expert-parallel
   share of model-configs section 4).  ``held=None`` takes the share ``cfg``
   states (``ep_rank``); the published file holds them all.

Parameter tree (leading axis = the layers of that kind, in layer order):
  embed [V, D], final_norm [D], (lm_head [D, V])
  layers: op_norm [L, D], ffn_norm [L, D]
  conv (``layer_types`` "conv"): in_proj [Lc, D, 3D], taps [Lc, conv_L_cache, D], out_proj [Lc, D, D]
  attn ("full_attention"): wqkv [La, D, (H + 2 KV) * hd] (q's heads, then k's, then v's),
          q_norm [La, hd], k_norm [La, hd], wo [La, H * hd, D]
  dense (the num_dense_layers leading layers): w_gate, w_up [Ld, D, F], w_down [Ld, F, D]
  moe (the rest): router [Lm, D, E_all], router_bias [Lm, E_all],
          moe_gate, moe_up [Lm, E_held, D, Fm], moe_down [Lm, E_held, Fm, D]
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def ffn(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, F32), tree)


def rope_halves(x, pos, theta: float):
    """x [T, heads, d]: (x[i], x[i + d/2]) turned by pos * theta^(-2i/d)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = pos.astype(F32)[:, None, None] * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * c - b * s, b * c + a * s], axis=-1)


def short_conv(lp: dict, cfg: dict, x):
    """The gated short convolution over one whole sequence: [T, D]."""
    T, L = x.shape[0], cfg.get("conv_L_cache", 3)
    b, c, xin = jnp.split(x @ lp["in_proj"], 3, axis=-1)
    u = b * xin
    v = jnp.zeros_like(u)
    for k in range(L):
        back = L - 1 - k  # taps[k] multiplies u_{t-back}
        shifted = jnp.concatenate([jnp.zeros((back, u.shape[1]), F32), u[: T - back]], axis=0)
        v = v + lp["taps"][k] * shifted
    return (c * v) @ lp["out_proj"]


def attention(lp: dict, cfg: dict, x, pos, q_block=None):
    """GQA with QK-norm, every query over every position up to its own.
    ``q_block`` only bounds memory: queries go through in blocks of that
    many, each against every key, with the same arithmetic."""
    T = x.shape[0]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or cfg["hidden_size"] // H
    eps, theta = cfg.get("norm_eps", 1e-5), float(cfg.get("rope_theta", 1000000.0))
    q, k, v = jnp.split(x @ lp["wqkv"], [H * hd, (H + KV) * hd], axis=-1)
    q = rope_halves(rms_norm(q.reshape(T, H, hd), lp["q_norm"], eps), pos, theta)
    k = rope_halves(rms_norm(k.reshape(T, KV, hd), lp["k_norm"], eps), pos, theta)
    v = v.reshape(T, KV, hd)
    k, v = jnp.repeat(k, H // KV, axis=1), jnp.repeat(v, H // KV, axis=1)  # head h reads K/V head h // (H/KV)
    outs = []
    step = q_block or T
    for a in range(0, T, step):
        e = min(T, a + step)
        s = jnp.einsum("thd,shd->hts", q[a:e], k) * hd ** -0.5
        s = jnp.where(pos[None, :] <= pos[a:e, None], s, -jnp.inf)
        outs.append(jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v).reshape(e - a, H * hd))
    return jnp.concatenate(outs) @ lp["wo"]


def gate(lp: dict, cfg: dict, x):
    """(chosen ids [T, K], weights [T, K]) over ALL the router's experts."""
    s = jax.nn.sigmoid(x @ lp["router"])  # [T, E]
    chosen = jax.lax.top_k(s + lp["router_bias"], cfg["num_experts_per_tok"])[1]
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if cfg.get("norm_topk_prob", True):
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
    return chosen, w * cfg.get("routed_scaling_factor", 1.0)


def moe(lp: dict, cfg: dict, x, held):
    """The routed experts that are chosen AND held (no shared expert).
    ``held[i]`` is the global id of the i-th expert of ``lp['moe_*']``."""
    chosen, w = gate(lp, cfg, x)
    y = jnp.zeros_like(x)
    for i, e in enumerate(held):
        w_e = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1, keepdims=True)  # [T, 1]
        y = y + w_e * ffn(x, lp["moe_gate"][i], lp["moe_up"][i], lp["moe_down"][i])
    return y


def held_experts(cfg: dict) -> list:
    n, rank = cfg["num_experts"], cfg.get("ep_rank", 0)
    return list(range(rank * n, (rank + 1) * n))


def layer_params(params: dict, cfg: dict, l: int) -> dict:
    """Layer l's leaves under one dict: its norms, its mixer's, its feed-forward's."""
    kinds = cfg["layer_types"]
    mixer = "conv" if kinds[l] == "conv" else "attn"
    i = sum(k == kinds[l] for k in kinds[:l])
    dense = cfg.get("num_dense_layers", 0)
    group, j = ("dense", l) if l < dense else ("moe", l - dense)
    lp = {k: v[l] for k, v in params["layers"].items()}
    lp.update({k: v[i] for k, v in params[mixer].items()})
    lp.update({k: v[j] for k, v in params[group].items()})
    return lp


def layer(lp: dict, cfg: dict, h, pos, kind: str, held, q_block=None):
    """One pre-norm residual block of ``kind`` ("conv" or "full_attention");
    ``lp`` from ``layer_params``."""
    eps = cfg.get("norm_eps", 1e-5)
    x = rms_norm(h, lp["op_norm"], eps)
    h = h + (short_conv(lp, cfg, x) if kind == "conv" else attention(lp, cfg, x, pos, q_block))
    x = rms_norm(h, lp["ffn_norm"], eps)
    if "router" in lp:
        return h + moe(lp, cfg, x, held)
    return h + ffn(x, lp["w_gate"], lp["w_up"], lp["w_down"])


def forward(params: dict, cfg: dict, tokens, held=None, q_block=None):
    """Logits [T, V] of one sequence."""
    with jax.default_matmul_precision("highest"):
        params = f32(params)
        tokens = jnp.asarray(tokens, jnp.int32)
        pos = jnp.arange(tokens.shape[0], dtype=jnp.int32)
        held = held_experts(cfg) if held is None else list(held)
        h = params["embed"][tokens]
        for l, kind in enumerate(cfg["layer_types"]):
            h = layer(layer_params(params, cfg, l), cfg, h, pos, kind, held, q_block)
        h = rms_norm(h, params["final_norm"], cfg.get("norm_eps", 1e-5))
        head = params["lm_head"] if "lm_head" in params else params["embed"].T
        return h @ head
