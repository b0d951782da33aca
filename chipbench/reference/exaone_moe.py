"""Plain reference of K-EXAONE-236B-A23B's forward pass (``model_type``
``exaone_moe``): GQA attention in every layer, of which the
``sliding_attention`` layers attend to the last ``sliding_window`` positions
only and the ``full_attention`` layers to the whole context; a leading dense
SwiGLU layer, then sigmoid-gated routed experts beside one shared expert; a
branch's OUTPUT is normed.

Straight ``jax.numpy`` in float32 under ``default_matmul_precision("highest")``:
no cache, no kernels, no batching, one pass over one sequence.  The window is a
MASK over the full score matrix (nothing is dropped, no page, no table), the
experts a loop.  Attention is computed in blocks of queries (``q_block``) so
that the pass fits at the published widths; a block changes no number.  It
follows the release's config.json
(https://huggingface.co/LGAI-EXAONE/K-EXAONE-236B-A23B) and, where that file is
silent, EXAONE 4.0's published convention; ``cfg`` is that config.json as a
dict.  It imports nothing of the program under test.
``chipbench/reference/exaone_moe.py`` is a copy.

Departures from the release and points its config.json does not settle, each
also under ``assumed`` in chipbench/configs/k-exaone-236b-a23b-8l-ep8.json:

1. QK-norm: an RMSNorm with a learned weight of ``head_dim`` over each head
   of q and of k, before the rotation (the family's convention).
2. Norm placement: ``h = h + RMSNorm_1(Attn(h))``, ``h = h + RMSNorm_2(MLP(h))``
   (the branch's output is normed, its input is not), eps ``rms_norm_eps``;
   a final RMSNorm before the head.
3. A ``sliding_attention`` layer rotates q and k (halves x[:d/2], x[d/2:]
   over the whole head, theta ``rope_parameters.rope_theta``, default type);
   a ``full_attention`` layer of a model that has window layers does NOT
   rotate (EXAONE 4.0 applies rotation in the window layers only).
4. The window's edge: a query at position t attends to positions j with
   ``0 <= t - j < sliding_window``: ``sliding_window`` positions with its own
   (the release library's mask convention).
5. The gate: ``s = sigmoid(W_r x)`` in float32 over ALL experts; the
   ``num_experts_per_tok`` largest of ``s + b`` (``b`` a selection bias that
   enters the choice only) are chosen; their weights are ``s`` of the chosen
   divided by their sum (``norm_topk_prob``) times ``routed_scaling_factor``.
   ``n_group`` 1: no groups.
6. An expert is ``W_2 (silu(a) * b)`` with ``[a | b] = W_1 x``: ``moe_gate``
   is a's half of W_1, ``moe_up`` b's; the shared expert the same at width
   ``num_shared_experts * moe_intermediate_size``, added for every token.
7. The multi-token-prediction module (``num_nextn_predict_layers``) is a
   draft head: the main model's logits do not depend on it; left out.
8. No bfloat16: everything here is float32.
9. ``held`` lists the experts this chip holds: the router scores and chooses
   over ALL experts, the sum runs over chosen AND held.  ``held=None`` takes
   the share ``cfg`` states (``ep_rank``); the shared expert is counted by
   EVERY share (``shared=False`` leaves it out of one, so shares add up).

Controls (the tests' and chip_smoke.py's, never the reference): ``window``
overrides ``sliding_window`` (None as stated; 0 for no window at all).

Parameter tree (leading axis = the layers of that kind, in layer order):
  embed [V, D], final_norm [D], lm_head [D, V]
  layers: op_norm [L, D] (after the mixer), ffn_norm [L, D] (after the MLP)
  attn ("full_attention") and wattn ("sliding_attention"), each:
        wqkv [La, D, (H + 2 KV) * hd] (q's heads, then k's, then v's),
        q_norm, k_norm [La, hd], wo [La, H * hd, D]
  dense (the first ``first_k_dense_replace`` layers): w_gate, w_up [Ld, D, F], w_down [Ld, F, D]
  moe (the others): router [Lm, D, E_all], router_bias [Lm, E_all],
        moe_gate, moe_up [Lm, E_held, D, Fm], moe_down [Lm, E_held, Fm, D]
  shared: w_gate, w_up [Lm, D, Fs], w_down [Lm, Fs, D]
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
    """Rotate the pairs (x[i], x[i + d/2]) of each head by pos * theta^(-2i/d)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = pos[:, None].astype(F32) * inv  # [T, d/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def dims(cfg: dict):
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return H, KV, cfg.get("head_dim") or cfg["hidden_size"] // H


def dense_layers(cfg: dict) -> int:
    kinds = cfg.get("mlp_layer_types")
    return cfg.get("first_k_dense_replace", sum(k == "dense" for k in kinds or ()))


def attention(lp: dict, cfg: dict, x, pos, kind: str, q_block=None, window=None):
    """GQA with QK-norm; ``kind`` "sliding_attention" rotates and masks to the
    window, "full_attention" does neither (where the model has window
    layers).  ``q_block`` only bounds memory."""
    T = x.shape[0]
    H, KV, hd = dims(cfg)
    eps = cfg.get("rms_norm_eps", 1e-5)
    theta = float((cfg.get("rope_parameters") or {}).get("rope_theta", 1000000.0))
    sliding = kind == "sliding_attention"
    mixes = "sliding_attention" in cfg["layer_types"]
    q, k, v = jnp.split(x @ lp["wqkv"], [H * hd, (H + KV) * hd], axis=-1)
    q = rms_norm(q.reshape(T, H, hd), lp["q_norm"], eps)
    k = rms_norm(k.reshape(T, KV, hd), lp["k_norm"], eps)
    if sliding or not mixes:
        q, k = rope_halves(q, pos, theta), rope_halves(k, pos, theta)
    v = v.reshape(T, KV, hd)
    k, v = jnp.repeat(k, H // KV, axis=1), jnp.repeat(v, H // KV, axis=1)
    W = cfg["sliding_window"] if window is None else window
    outs = []
    step = q_block or T
    for a in range(0, T, step):
        e = min(T, a + step)
        s = jnp.einsum("thd,shd->hts", q[a:e], k) * hd ** -0.5
        back = pos[a:e, None] - pos[None, :]  # t - j
        keep = back >= 0
        if sliding and W:
            keep &= back < W
        s = jnp.where(keep, s, -jnp.inf)
        outs.append(jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v).reshape(e - a, H * hd))
    return jnp.concatenate(outs) @ lp["wo"]


def gate(lp: dict, cfg: dict, x):
    """(chosen ids [T, K], weights [T, K]) over ALL the router's experts."""
    s = jax.nn.sigmoid(x @ lp["router"])
    chosen = jax.lax.top_k(s + lp["router_bias"], cfg["num_experts_per_tok"])[1]
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if cfg.get("norm_topk_prob", True):
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return chosen, w * cfg.get("routed_scaling_factor", 1.0)


def moe(lp: dict, cfg: dict, x, held, shared: bool = True):
    """The routed experts that are chosen AND held, and the shared expert.
    ``held[i]`` is the global id of the i-th expert of ``lp['moe_*']``."""
    chosen, w = gate(lp, cfg, x)
    has_shared = shared and "w_gate" in lp
    y = ffn(x, lp["w_gate"], lp["w_up"], lp["w_down"]) if has_shared else jnp.zeros_like(x)
    for i, e in enumerate(held):
        w_e = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1, keepdims=True)  # [T, 1]
        y = y + w_e * ffn(x, lp["moe_gate"][i], lp["moe_up"][i], lp["moe_down"][i])
    return y


def held_experts(cfg: dict) -> list:
    n, rank = cfg["num_experts"], cfg.get("ep_rank", 0)
    return list(range(rank * n, (rank + 1) * n))


def layer_params(params: dict, cfg: dict, l: int) -> dict:
    """Layer l's leaves under one dict: its norms, its mixer's, its MLP's
    (``dense_mlp`` says which)."""
    kinds = cfg["layer_types"]
    group = "wattn" if kinds[l] == "sliding_attention" else "attn"
    i = sum(k == kinds[l] for k in kinds[:l])
    Ld = dense_layers(cfg)
    lp = {k: v[l] for k, v in params["layers"].items()}
    lp.update({k: v[i] for k, v in params[group].items()})
    if l < Ld:
        lp.update({k: v[l] for k, v in params["dense"].items()})
    else:
        lp.update({k: v[l - Ld] for k, v in params["moe"].items()})
        lp.update({k: v[l - Ld] for k, v in params.get("shared", {}).items()})
    lp["dense_mlp"] = l < Ld
    return lp


def layer(lp: dict, cfg: dict, h, pos, kind: str, held, q_block=None, shared: bool = True,
          window=None):
    """One residual block whose branches' OUTPUTS are normed."""
    eps = cfg.get("rms_norm_eps", 1e-5)
    h = h + rms_norm(attention(lp, cfg, h, pos, kind, q_block, window), lp["op_norm"], eps)
    y = (ffn(h, lp["w_gate"], lp["w_up"], lp["w_down"]) if lp["dense_mlp"]
         else moe(lp, cfg, h, held, shared))
    return h + rms_norm(y, lp["ffn_norm"], eps)


def forward(params: dict, cfg: dict, tokens, held=None, q_block=None, window=None):
    """Logits [T, V] of one sequence."""
    with jax.default_matmul_precision("highest"):
        params = f32(params)
        tokens = jnp.asarray(tokens, jnp.int32)
        pos = jnp.arange(tokens.shape[0], dtype=jnp.int32)
        held = held_experts(cfg) if held is None else list(held)
        h = params["embed"][tokens]
        for l, kind in enumerate(cfg["layer_types"]):
            h = layer(layer_params(params, cfg, l), cfg, h, pos, kind, held, q_block, window=window)
        h = rms_norm(h, params["final_norm"], cfg.get("rms_norm_eps", 1e-5))
        return h @ params["lm_head"]
