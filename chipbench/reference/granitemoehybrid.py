"""Plain reference of granite-4.0-h-small's forward pass (``model_type``
``granitemoehybrid``): Mamba-2 layers among GQA attention layers without
positional embedding, every layer's feed-forward softmax-gated experts beside
an always-on shared SwiGLU, four scalar multipliers.

Straight ``jax.numpy`` in float32 under ``default_matmul_precision("highest")``:
no cache, no kernels, no batching, one pass over one sequence.  The Mamba-2
recurrence is written ONE TOKEN AT A TIME (a loop over positions that carries
the state ``S`` [heads, d_head, d_state]; no chunked form), the convolution an
explicit sum of shifted copies, attention a full causal softmax a head, the
experts a loop.  It follows the ``granitemoehybrid`` modelling code of
``transformers`` and the release's config.json
(https://huggingface.co/ibm-granite/granite-4.0-h-small); ``cfg`` is that
config.json as a dict.  It imports nothing of the program under test.
``chipbench/reference/granitemoehybrid.py`` is a copy.

Departures from the release and points its config.json does not settle, each
also under ``assumed`` in chipbench/configs/granite-4.0-h-small-10l-ep2.json:

1. The Mamba-2 input projection's columns are z (``mamba_n_heads`` x
   ``mamba_d_head``), xBC (the same plus 2 x ``mamba_d_state``: u, B, C, one
   group) and dt (a head), in this order, without bias.  ``conv_w[k]``
   multiplies xBC_{t-(K-1)+k} with K = ``mamba_d_conv`` (causal, depthwise,
   zeros before position 0), ``conv_b`` is added, then silu.
2. ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)`` a head;
   ``S_t = exp(dt_t A) S_{t-1} + dt_t u_t B_t^T``; ``y_t = S_t C_t + D u_t``;
   ``y = RMSNorm(y * silu(z))`` over all inner channels with learned weights
   (the gate BEFORE the norm, one group), eps ``rms_norm_eps``.
3. Attention has NO rotation (``position_embedding_type`` "nope") and NO
   QK-norm; the softmax scale is ``attention_multiplier`` (1/128).
4. An expert is ``W_2 (silu(a) * b)`` with ``[a | b] = W_1 x``: ``moe_gate``
   is a's half of W_1, ``moe_up`` b's.  The gate takes the
   ``num_experts_per_tok`` largest of the router's logits and a softmax over
   THOSE; the shared SwiGLU (width ``shared_intermediate_size``) is added for
   every token.
5. ``h_0 = embedding_multiplier * E[token]``; every residual branch is scaled
   by ``residual_multiplier``; logits are ``E^T norm(h) / logits_scaling``.
6. No bfloat16: everything here is float32.
7. ``held`` lists the experts this chip holds: the router scores and chooses
   over ALL experts, the sum runs over chosen AND held.  ``held=None`` takes
   the share ``cfg`` states (``ep_rank``); the shared SwiGLU is counted by
   EVERY share (adding two shares up counts it twice: ``shared=False`` leaves
   it out of one).

Parameter tree (leading axis = the layers of that kind, in layer order):
  embed [V, D], final_norm [D], (lm_head [D, V])
  layers: op_norm [L, D], ffn_norm [L, D]
  mamba (``layer_types`` "mamba"): in_proj [Lm, D, 2 di + 2 N + Hm], conv_w [Lm, K, di + 2 N],
          conv_b [Lm, di + 2 N], A_log, D, dt_bias [Lm, Hm], norm_w [Lm, di], out_proj [Lm, di, D]
  attn ("attention"): wqkv [La, D, (H + 2 KV) * hd] (q's heads, then k's, then v's), wo [La, H * hd, D]
  moe: router [L, D, E_all], moe_gate, moe_up [L, E_held, D, F], moe_down [L, E_held, F, D]
  shared: w_gate, w_up [L, D, Fs], w_down [L, Fs, D]
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


def mamba2(lp: dict, cfg: dict, x, state=None, drop_du: bool = False):
    """The Mamba-2 mixer over one whole sequence [T, D], a token at a time.
    ``state``: the float type the carried state is rounded to after every
    token (None: float32 as stated); ``drop_du``: without the ``D u_t`` term.
    Both are the tests' controls, never the reference."""
    T = x.shape[0]
    Hm, P, N, K = (cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"],
                   cfg["mamba_d_conv"])
    di = Hm * P
    z, xbc, dt = jnp.split(x @ lp["in_proj"], [di, 2 * di + 2 * N], axis=-1)
    v = jnp.zeros_like(xbc) + lp["conv_b"]
    for k in range(K):
        back = K - 1 - k  # conv_w[k] multiplies xBC_{t-back}
        shifted = jnp.concatenate([jnp.zeros((back, xbc.shape[1]), F32), xbc[: T - back]], axis=0)
        v = v + lp["conv_w"][k] * shifted
    act = jax.nn.silu(v)
    u, B, C = act[:, :di].reshape(T, Hm, P), act[:, di:di + N], act[:, di + N:]
    dt = jax.nn.softplus(dt + lp["dt_bias"])  # [T, Hm]
    A = -jnp.exp(lp["A_log"])  # [Hm]

    def token(S, inp):
        u_t, B_t, C_t, dt_t = inp
        S = (jnp.exp(dt_t * A)[:, None, None] * S
             + (dt_t[:, None] * u_t)[:, :, None] * B_t[None, None, :])
        if state is not None:  # said as a rounding: XLA drops a pair of casts
            S = jax.lax.reduce_precision(S, jnp.finfo(state).nexp, jnp.finfo(state).nmant)
        return S, jnp.einsum("hpn,n->hp", S, C_t)

    _, y = jax.lax.scan(token, jnp.zeros((Hm, P, N), F32), (u, B, C, dt))
    if not drop_du:
        y = y + lp["D"][None, :, None] * u
    g = y.reshape(T, di) * jax.nn.silu(z)
    return rms_norm(g, lp["norm_w"], cfg.get("rms_norm_eps", 1e-5)) @ lp["out_proj"]


def attention(lp: dict, cfg: dict, x, pos, q_block=None):
    """GQA without rotation, every query over every position up to its own,
    softmax scale ``attention_multiplier``.  ``q_block`` only bounds memory."""
    T = x.shape[0]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or cfg["hidden_size"] // H
    q, k, v = jnp.split(x @ lp["wqkv"], [H * hd, (H + KV) * hd], axis=-1)
    q, k, v = q.reshape(T, H, hd), k.reshape(T, KV, hd), v.reshape(T, KV, hd)
    k, v = jnp.repeat(k, H // KV, axis=1), jnp.repeat(v, H // KV, axis=1)
    outs = []
    step = q_block or T
    for a in range(0, T, step):
        e = min(T, a + step)
        s = jnp.einsum("thd,shd->hts", q[a:e], k) * cfg["attention_multiplier"]
        s = jnp.where(pos[None, :] <= pos[a:e, None], s, -jnp.inf)
        outs.append(jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v).reshape(e - a, H * hd))
    return jnp.concatenate(outs) @ lp["wo"]


def gate(lp: dict, cfg: dict, x):
    """(chosen ids [T, K], weights [T, K]): the K largest logits over ALL the
    router's experts, softmax over those."""
    w, chosen = jax.lax.top_k(x @ lp["router"], cfg["num_experts_per_tok"])
    return chosen, jax.nn.softmax(w, axis=-1)


def moe(lp: dict, cfg: dict, x, held, shared: bool = True):
    """The routed experts that are chosen AND held, and the shared SwiGLU.
    ``held[i]`` is the global id of the i-th expert of ``lp['moe_*']``."""
    chosen, w = gate(lp, cfg, x)
    y = ffn(x, lp["w_gate"], lp["w_up"], lp["w_down"]) if shared else jnp.zeros_like(x)
    for i, e in enumerate(held):
        w_e = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1, keepdims=True)  # [T, 1]
        y = y + w_e * ffn(x, lp["moe_gate"][i], lp["moe_up"][i], lp["moe_down"][i])
    return y


def held_experts(cfg: dict) -> list:
    n, rank = cfg["num_local_experts"], cfg.get("ep_rank", 0)
    return list(range(rank * n, (rank + 1) * n))


def layer_params(params: dict, cfg: dict, l: int) -> dict:
    """Layer l's leaves under one dict: its norms, its mixer's, its feed-forward's."""
    kinds = cfg["layer_types"]
    mixer = "mamba" if kinds[l] == "mamba" else "attn"
    i = sum(k == kinds[l] for k in kinds[:l])
    lp = {k: v[l] for k, v in params["layers"].items()}
    lp.update({k: v[i] for k, v in params[mixer].items()})
    lp.update({k: v[l] for k, v in params["moe"].items()})
    lp.update({k: v[l] for k, v in params["shared"].items()})
    return lp


def layer(lp: dict, cfg: dict, h, pos, kind: str, held, q_block=None, shared: bool = True, **controls):
    """One pre-norm residual block of ``kind`` ("mamba" or "attention");
    ``lp`` from ``layer_params``."""
    eps, r = cfg.get("rms_norm_eps", 1e-5), cfg.get("residual_multiplier", 1.0)
    x = rms_norm(h, lp["op_norm"], eps)
    h = h + r * (mamba2(lp, cfg, x, **controls) if kind == "mamba"
                 else attention(lp, cfg, x, pos, q_block))
    return h + r * moe(lp, cfg, rms_norm(h, lp["ffn_norm"], eps), held, shared)


def forward(params: dict, cfg: dict, tokens, held=None, q_block=None, **controls):
    """Logits [T, V] of one sequence."""
    with jax.default_matmul_precision("highest"):
        params = f32(params)
        tokens = jnp.asarray(tokens, jnp.int32)
        pos = jnp.arange(tokens.shape[0], dtype=jnp.int32)
        held = held_experts(cfg) if held is None else list(held)
        h = cfg.get("embedding_multiplier", 1.0) * params["embed"][tokens]
        for l, kind in enumerate(cfg["layer_types"]):
            h = layer(layer_params(params, cfg, l), cfg, h, pos, kind, held, q_block, **controls)
        h = rms_norm(h, params["final_norm"], cfg.get("rms_norm_eps", 1e-5))
        head = params["lm_head"] if "lm_head" in params else params["embed"].T
        return (h @ head) / cfg.get("logits_scaling", 1.0)
