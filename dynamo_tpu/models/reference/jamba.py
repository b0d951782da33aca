"""Plain reference of AI21-Jamba2-3B's forward pass (``model_type`` ``jamba``):
Mamba-1 selective-scan layers among GQA attention layers without positional
embedding, a dense SwiGLU in every layer, a tied head.

Straight ``jax.numpy`` in float32 under ``default_matmul_precision("highest")``:
no cache, no kernels, no batching, one pass over one sequence.  The recurrence
is written ONE TOKEN AT A TIME (a loop over positions that carries the state
``S`` [d_state, inner]), the convolution an explicit sum of shifted copies,
attention a full causal softmax a head.  It follows ``JambaMambaMixer``'s slow
path in the ``jamba`` modelling code of ``transformers`` (``modeling_jamba.py``)
and the release's config.json
(https://huggingface.co/ai21labs/AI21-Jamba2-3B); ``cfg`` is that config.json
as a dict.  It imports nothing of the program under test.
``chipbench/reference/jamba.py`` is a copy.

Departures from ``modeling_jamba.py``: none in the mathematics (the fused
``use_mamba_kernels`` fast path is absent; the slow path is what is written
here).  Points of layout and points the config.json does not settle, each also
under ``assumed`` in chipbench/configs/jamba2-3b.json:

1. Layer l is attention where ``l % attn_layer_period == attn_layer_offset``
   and Mamba-1 elsewhere.  With ``num_experts`` 1 every layer's feed-forward is
   the dense SwiGLU ``W_down (silu(W_gate x) * (W_up x))``.
2. ``[u | z] = W_in x`` (``mamba_expand`` x hidden each, u first, no bias);
   ``c_t = silu(b + sum_k w_k * u_{t-(K-1)+k})`` with K = ``mamba_d_conv``
   (causal, depthwise, zeros before position 0); ``[dt | B | C] = W_x c_t``
   (``mamba_dt_rank`` | ``mamba_d_state`` | ``mamba_d_state``), each through an
   RMSNorm with learned weights (eps ``rms_norm_eps``); ``dt = softplus(W_dt dt
   + b_dt)``; ``A = -exp(A_log)``;
   ``S_t = exp(dt_t A) * S_{t-1} + dt_t B_t c_t``; ``y_t = C_t . S_t + D c_t``;
   ``out = W_out (y_t * silu(z_t))``.
3. ``A_log`` is stored [d_state, inner], the TRANSPOSE of the release's
   [inner, d_state] (the state's own layout on the chip: the 16 on sublanes).
4. Attention has NO rotation and no positional embedding of any kind, no bias,
   no QK-norm; the softmax scale is head_dim ** -0.5 with head_dim =
   hidden_size / num_attention_heads.
5. ``logits = E^T RMSNorm(h)`` through the tied embedding.
6. No bfloat16: everything here is float32.

Parameter tree (leading axis = the layers of that kind, in layer order):
  embed [V, D], final_norm [D]
  layers: op_norm [L, D], ffn_norm [L, D]
  mamba1: in_proj [Lm, D, 2 di], conv_w [Lm, K, di], conv_b [Lm, di], x_proj [Lm, di, R + 2 N],
          dt_norm [Lm, R], b_norm, c_norm [Lm, N], dt_proj [Lm, R, di], dt_bias [Lm, di],
          A_log [Lm, N, di], D [Lm, di], out_proj [Lm, di, D]
  attn: wqkv [La, D, (H + 2 KV) * hd] (q's heads, then k's, then v's), wo [La, H * hd, D]
  dense: w_gate, w_up [L, D, F], w_down [L, F, D]
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


def layer_kinds(cfg: dict) -> list:
    return ["attention" if l % cfg["attn_layer_period"] == cfg["attn_layer_offset"] else "mamba1"
            for l in range(cfg["num_hidden_layers"])]


def mamba1(lp: dict, cfg: dict, x, state=None, inner_norms: bool = True):
    """The Mamba-1 mixer over one whole sequence [T, D], a token at a time.
    ``state``: the float type the carried state is rounded to after every
    token (None: float32 as stated); ``inner_norms`` False: without the three
    RMSNorms on dt, B and C.  Both are the tests' controls, never the
    reference."""
    T = x.shape[0]
    N, K, R = cfg["mamba_d_state"], cfg["mamba_d_conv"], cfg["mamba_dt_rank"]
    di = cfg["mamba_expand"] * cfg["hidden_size"]
    eps = cfg.get("rms_norm_eps", 1e-6)
    u, z = jnp.split(x @ lp["in_proj"], [di], axis=-1)
    v = jnp.zeros_like(u) + lp["conv_b"]
    for k in range(K):
        back = K - 1 - k  # conv_w[k] multiplies u_{t-back}
        shifted = jnp.concatenate([jnp.zeros((back, di), F32), u[: T - back]], axis=0)
        v = v + lp["conv_w"][k] * shifted
    c = jax.nn.silu(v)  # [T, di]
    dt, B, C = jnp.split(c @ lp["x_proj"], [R, R + N], axis=-1)
    if inner_norms:
        dt, B, C = (rms_norm(dt, lp["dt_norm"], eps), rms_norm(B, lp["b_norm"], eps),
                    rms_norm(C, lp["c_norm"], eps))
    dt = jax.nn.softplus(dt @ lp["dt_proj"] + lp["dt_bias"])  # [T, di]
    A = -jnp.exp(lp["A_log"])  # [N, di]

    def token(S, inp):
        c_t, B_t, C_t, dt_t = inp
        S = jnp.exp(dt_t[None, :] * A) * S + (dt_t * c_t)[None, :] * B_t[:, None]
        if state is not None:  # said as a rounding: XLA drops a pair of casts
            S = jax.lax.reduce_precision(S, jnp.finfo(state).nexp, jnp.finfo(state).nmant)
        return S, jnp.sum(C_t[:, None] * S, axis=0)

    _, y = jax.lax.scan(token, jnp.zeros((N, di), F32), (c, B, C, dt))
    y = y + lp["D"] * c
    return (y * jax.nn.silu(z)) @ lp["out_proj"]


def attention(lp: dict, cfg: dict, x, pos, q_block=None):
    """GQA without rotation, every query over every position up to its own,
    softmax scale head_dim ** -0.5.  ``q_block`` only bounds memory."""
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
        s = jnp.einsum("thd,shd->hts", q[a:e], k) * hd**-0.5
        s = jnp.where(pos[None, :] <= pos[a:e, None], s, -jnp.inf)
        outs.append(jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v).reshape(e - a, H * hd))
    return jnp.concatenate(outs) @ lp["wo"]


def layer_params(params: dict, cfg: dict, l: int) -> dict:
    """Layer l's leaves under one dict: its norms, its mixer's, its feed-forward's."""
    kinds = layer_kinds(cfg)
    mixer = "mamba1" if kinds[l] == "mamba1" else "attn"
    i = sum(k == kinds[l] for k in kinds[:l])
    lp = {k: v[l] for k, v in params["layers"].items()}
    lp.update({k: v[i] for k, v in params[mixer].items()})
    lp.update({k: v[l] for k, v in params["dense"].items()})
    return lp


def layer(lp: dict, cfg: dict, h, pos, kind: str, q_block=None, **controls):
    """One pre-norm residual block of ``kind`` ("mamba1" or "attention");
    ``lp`` from ``layer_params``."""
    eps = cfg.get("rms_norm_eps", 1e-6)
    x = rms_norm(h, lp["op_norm"], eps)
    h = h + (mamba1(lp, cfg, x, **controls) if kind == "mamba1"
             else attention(lp, cfg, x, pos, q_block))
    return h + ffn(rms_norm(h, lp["ffn_norm"], eps), lp["w_gate"], lp["w_up"], lp["w_down"])


def forward(params: dict, cfg: dict, tokens, q_block=None, **controls):
    """Logits [T, V] of one sequence."""
    with jax.default_matmul_precision("highest"):
        params = f32(params)
        tokens = jnp.asarray(tokens, jnp.int32)
        pos = jnp.arange(tokens.shape[0], dtype=jnp.int32)
        h = params["embed"][tokens]
        for l, kind in enumerate(layer_kinds(cfg)):
            h = layer(layer_params(params, cfg, l), cfg, h, pos, kind, q_block, **controls)
        h = rms_norm(h, params["final_norm"], cfg.get("rms_norm_eps", 1e-6))
        return h @ params["embed"].T
