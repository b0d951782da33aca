"""Plain reference of Kimi-Linear-48B-A3B-Instruct's forward pass
(``model_type`` ``kimi_linear``): Kimi Delta Attention (KDA) layers, three to
one latent attention (MLA) layer WITHOUT rotation; a leading dense SwiGLU, then
sigmoid-gated experts (one group, a selection bias) beside one shared expert.

Straight ``jax.numpy`` in float32 under ``default_matmul_precision("highest")``:
no cache, no kernels, no batching, one pass over one sequence.  The KDA
recurrence is written ONE TOKEN AT A TIME (a loop over positions that carries
the state ``S`` [heads, d_key, d_value]; no chunked form, no triangular solve),
the convolutions explicit sums of shifted copies, MLA a full causal softmax a
head in the DECOMPRESSED form (k_j,h = [W^UK_h c_j ; k^R_j], v_j,h = W^UV_h
c_j), the experts a loop.  It follows the layer equations of ISSUE 53
(docs/kimi_linear.md) and the release's config.json
(https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct); ``cfg`` is
that config.json as a dict.  It imports nothing of the program under test.
``chipbench/reference/kimi_linear.py`` is a copy.

Points the config.json does not settle, each also under ``assumed`` in
chipbench/configs/kimi-linear-48b-a3b-8l-ep8.json:

1. KDA's projections have no bias.  ``wqkv``'s columns are q, k, v
   (``num_heads`` x ``head_dim`` each); each passes its own causal depthwise
   convolution of ``short_conv_kernel_size`` taps without bias (``conv_w[k]``
   multiplies the input at t-(K-1)+k; zeros before position 0), then silu.
   A head's q and k are divided by their length (sqrt(sum of squares + 1e-6))
   and q is scaled by ``head_dim`` ** -0.5.
2. The decay is a factor a CHANNEL: ``g_t = -exp(A_log[h]) * softplus(W_f2
   (W_f1 x_t) + dt_bias)``, W_f1 hidden -> ``head_dim``, W_f2 ``head_dim`` ->
   heads x ``head_dim``, no bias; ``A_log`` a head, ``dt_bias`` a channel.
   ``beta_t = sigmoid(W_b x_t)`` a head.  ``w_low``'s columns are W_f1, W_g1
   and W_b, in this order.
3. ``S' = diag(exp(g_t)) S_{t-1}; S_t = S' + beta_t k_t (v_t - S'^T k_t)^T;
   o_t = S_t^T q_t`` (S: key rows, value columns).
4. ``y_t = W_o concat_h(RMSNorm_d(o_t; w) * sigmoid(W_g2 (W_g1 x_t) + b))``:
   the norm a head with ONE learned weight of ``head_dim``, eps
   ``rms_norm_eps``; W_g1 hidden -> ``head_dim``, W_g2 with bias.
5. MLA: ``q = W_q x`` (no compressed query: ``q_lora_rank`` null), ``[c | k^R]
   = W_kva x``, the key of a head ``[W^UK_h RMSNorm(c) | k^R]``, the value
   ``W^UV_h RMSNorm(c)``; NOTHING is rotated (``mla_use_nope``); softmax scale
   (``qk_nope_head_dim`` + ``qk_rope_head_dim``) ** -0.5.
6. The gate: sigmoid scores over ALL experts, a selection bias that enters the
   CHOICE only (one group), the ``num_experts_per_token`` largest, their
   scores renormalised (``moe_renormalize``) and times
   ``routed_scaling_factor``.  An expert is ``W_2 (silu(a) * b)`` with
   ``[a | b] = W_1 x``.
7. No bfloat16: everything here is float32.
8. ``held`` lists the experts this chip holds: the router scores and chooses
   over ALL experts, the sum runs over chosen AND held.  ``held=None`` takes
   the share ``cfg`` states (``ep_rank``); the shared expert is counted by
   EVERY share (``shared=False`` leaves it out of one).

Parameter tree (leading axis = the layers of that kind, in layer order):
  embed [V, D], final_norm [D], lm_head [D, V]
  layers: op_norm [L, D], ffn_norm [L, D]
  kda (``kda_layers``): wqkv [Lk, D, 3 H d], conv_w [Lk, K, 3 H d], w_low [Lk, D, 2 d + H],
          wf_b [Lk, d, H d], dt_bias [Lk, H d], A_log [Lk, H], wg_b [Lk, d, H d],
          wg_bias [Lk, H d], norm_w [Lk, d], wo [Lk, H d, D]
  mla (``full_attn_layers``): wq [La, D, Ha (dn + dr)], wkv_a [La, D, Rkv + dr], kv_norm [La, Rkv],
          w_uk [La, Ha, Rkv, dn], w_uv [La, Ha, Rkv, dv], wo [La, Ha dv, D]
  dense (the first_k_dense_replace leading layers): w_gate, w_up [Ld, D, F], w_down [Ld, F, D]
  moe (the rest): router [Lm, D, E_all], router_bias [Lm, E_all],
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


def kda_state_step(S, q_t, k_t, v_t, g_t, beta_t):
    """One token of the delta rule, all heads: ``S`` [H, d_key, d_value];
    q, k, v, g [H, d]; beta [H].  Returns (S_t, o_t [H, d_value])."""
    S = jnp.exp(g_t)[:, :, None] * S
    u = beta_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", S, k_t))
    S = S + k_t[:, :, None] * u[:, None, :]
    return S, jnp.einsum("hkv,hk->hv", S, q_t)


def kda(lp: dict, cfg: dict, x, state=None, decay=None):
    """The KDA mixer over one whole sequence [T, D], a token at a time.
    ``state`` / ``decay``: the float type the carried state, or ``g``, is
    rounded to (None: float32 as stated).  The tests' controls, never the
    reference."""
    T = x.shape[0]
    lin = cfg["linear_attn_config"]
    H, d, K = lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]
    eps = cfg.get("rms_norm_eps", 1e-5)
    qkv = x @ lp["wqkv"]
    v = jnp.zeros_like(qkv)
    for k in range(K):
        back = K - 1 - k  # conv_w[k] multiplies qkv_{t-back}
        shifted = jnp.concatenate([jnp.zeros((back, qkv.shape[1]), F32), qkv[: T - back]], axis=0)
        v = v + lp["conv_w"][k] * shifted
    q, k, v = (a.reshape(T, H, d) for a in jnp.split(jax.nn.silu(v), 3, axis=-1))
    unit = lambda a: a / jnp.sqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)  # noqa: E731
    q, k = unit(q) * d**-0.5, unit(k)
    f, og, b = jnp.split(x @ lp["w_low"], [d, 2 * d], axis=-1)
    dt = jax.nn.softplus(f @ lp["wf_b"] + lp["dt_bias"]).reshape(T, H, d)
    g = -jnp.exp(lp["A_log"])[None, :, None] * dt  # [T, H, d] <= 0
    beta = jax.nn.sigmoid(b)  # [T, H]
    round_to = lambda a, t: a if t is None else jax.lax.reduce_precision(  # noqa: E731
        a, jnp.finfo(t).nexp, jnp.finfo(t).nmant)  # said as a rounding: XLA drops a pair of casts
    g = round_to(g, decay)

    def token(S, inp):
        S, o = kda_state_step(S, *inp)
        return round_to(S, state), o

    _, o = jax.lax.scan(token, jnp.zeros((H, d, d), F32), (q, k, v, g, beta))
    o = rms_norm(o, lp["norm_w"], eps)  # a head: [T, H, d] over d
    gate = jax.nn.sigmoid(og @ lp["wg_b"] + lp["wg_bias"])
    return (o.reshape(T, H * d) * gate) @ lp["wo"]


def mla(lp: dict, cfg: dict, x, pos, q_block=None):
    """Latent attention without rotation, every query over every position up
    to its own, decompressed.  ``q_block`` only bounds memory."""
    T = x.shape[0]
    H, dn, dr, dv = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                     cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    Rkv, eps = cfg["kv_lora_rank"], cfg.get("rms_norm_eps", 1e-5)
    q = (x @ lp["wq"]).reshape(T, H, dn + dr)
    kv = x @ lp["wkv_a"]
    c = rms_norm(kv[:, :Rkv], lp["kv_norm"], eps)
    k = jnp.concatenate([jnp.einsum("sc,hcn->shn", c, lp["w_uk"]),
                         jnp.broadcast_to(kv[:, None, Rkv:], (T, H, dr))], axis=-1)
    v = jnp.einsum("sc,hcv->shv", c, lp["w_uv"])
    outs = []
    step = q_block or T
    for a in range(0, T, step):
        e = min(T, a + step)
        s = jnp.einsum("thd,shd->hts", q[a:e], k) * (dn + dr) ** -0.5
        s = jnp.where(pos[None, :] <= pos[a:e, None], s, -jnp.inf)
        outs.append(jnp.einsum("hts,shv->thv", jax.nn.softmax(s, axis=-1), v).reshape(e - a, H * dv))
    return jnp.concatenate(outs) @ lp["wo"]


def gate(lp: dict, cfg: dict, x):
    """(chosen ids [T, K], weights [T, K]) over ALL the router's experts:
    sigmoid scores, the bias in the CHOICE only, one group."""
    s = jax.nn.sigmoid(x @ lp["router"])
    chosen = jax.lax.top_k(s + lp["router_bias"], cfg["num_experts_per_token"])[1]
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if cfg.get("moe_renormalize", True):
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return chosen, w * cfg.get("routed_scaling_factor", 1.0)


def moe(lp: dict, cfg: dict, x, held, shared: bool = True):
    """The routed experts that are chosen AND held, and the shared expert.
    ``held[i]`` is the global id of the i-th expert of ``lp['moe_*']``."""
    chosen, w = gate(lp, cfg, x)
    y = ffn(x, lp["w_gate"], lp["w_up"], lp["w_down"]) if shared else jnp.zeros_like(x)
    for i, e in enumerate(held):
        w_e = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1, keepdims=True)  # [T, 1]
        y = y + w_e * ffn(x, lp["moe_gate"][i], lp["moe_up"][i], lp["moe_down"][i])
    return y


def held_experts(cfg: dict) -> list:
    n, rank = cfg["num_experts"], cfg.get("ep_rank", 0)
    return list(range(rank * n, (rank + 1) * n))


def layer_kinds(cfg: dict) -> list:
    """"kda" or "mla" a layer, from the 1-based lists of ``linear_attn_config``."""
    full = set(cfg["linear_attn_config"]["full_attn_layers"])
    return ["mla" if l in full else "kda" for l in range(1, cfg["num_hidden_layers"] + 1)]


def layer_params(params: dict, cfg: dict, l: int) -> dict:
    """Layer l's leaves under one dict: its norms, its mixer's, its feed-forward's
    (the shared expert's keep their names: the dense layers have no ``router``)."""
    kinds = layer_kinds(cfg)
    i = sum(k == kinds[l] for k in kinds[:l])
    lp = {k: v[l] for k, v in params["layers"].items()}
    lp.update({k: v[i] for k, v in params[kinds[l]].items()})
    dense = cfg.get("first_k_dense_replace", 0)
    if l < dense:
        lp.update({k: v[l] for k, v in params["dense"].items()})
    else:
        lp.update({k: v[l - dense] for k, v in params["shared"].items()})
        lp.update({k: v[l - dense] for k, v in params["moe"].items()})
    return lp


def layer(lp: dict, cfg: dict, h, pos, kind: str, held, q_block=None, shared: bool = True,
          **controls):
    """One pre-norm residual block of ``kind`` ("kda" or "mla"); ``lp`` from
    ``layer_params``."""
    eps = cfg.get("rms_norm_eps", 1e-5)
    x = rms_norm(h, lp["op_norm"], eps)
    h = h + (kda(lp, cfg, x, **controls) if kind == "kda" else mla(lp, cfg, x, pos, q_block))
    x = rms_norm(h, lp["ffn_norm"], eps)
    if "router" in lp:
        return h + moe(lp, cfg, x, held, shared)
    return h + ffn(x, lp["w_gate"], lp["w_up"], lp["w_down"])


def forward(params: dict, cfg: dict, tokens, held=None, q_block=None, **controls):
    """Logits [T, V] of one sequence."""
    with jax.default_matmul_precision("highest"):
        params = f32(params)
        tokens = jnp.asarray(tokens, jnp.int32)
        pos = jnp.arange(tokens.shape[0], dtype=jnp.int32)
        held = held_experts(cfg) if held is None else list(held)
        h = params["embed"][tokens]
        for l, kind in enumerate(layer_kinds(cfg)):
            h = layer(layer_params(params, cfg, l), cfg, h, pos, kind, held, q_block, **controls)
        h = rms_norm(h, params["final_norm"], cfg.get("rms_norm_eps", 1e-5))
        return h @ params["lm_head"]
