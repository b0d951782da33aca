"""Mixture-of-experts FFN with capacity-based dispatch (GShard/Switch style).

The reference only *configures* expert parallelism for TRT-LLM
(examples/tensorrt_llm/configs/llm_api_config.yaml:24-26); here MoE runs
natively.  TPU-first design: token→expert dispatch is expressed as dense
einsums against one-hot dispatch/combine tensors with a fixed per-expert
capacity — fully static shapes, shardable over an "ep" mesh axis (experts
dimension), with the all-to-all realised by XLA when expert and token
shardings differ.  Overflowing tokens (beyond capacity) fall through the
residual connection — standard Switch behaviour.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from .config import ModelConfig


def init_moe_params(config: ModelConfig, key: jax.Array, dt) -> Dict[str, jnp.ndarray]:
    L, D = config.num_layers, config.hidden_size
    E, F = config.num_experts, config.moe_intermediate_size or config.intermediate_size
    keys = jax.random.split(key, 4)

    def norm(k, *shape):
        return (jax.random.normal(k, shape, jnp.float32) * 0.02).astype(dt)

    return {
        "router": norm(keys[0], L, D, E),
        "moe_gate": norm(keys[1], L, E, D, F),
        "moe_up": norm(keys[2], L, E, D, F),
        "moe_down": norm(keys[3], L, E, F, D),
    }


def moe_mlp(
    x: jnp.ndarray,  # [B, Sq, D]
    lp: Dict[str, jnp.ndarray],  # this layer's params (leading L stripped)
    config: ModelConfig,
    capacity_factor: float | None = None,
) -> jnp.ndarray:
    """Gather/scatter dispatch: per-expert token-index tables [E, C] instead
    of one-hot dispatch tensors, so memory is O(E·C·D) activations + O(T·K·E)
    routing ints (no [T, E, C] one-hots).

    capacity_factor None = dropless (C = T, the worst case of every token
    routing to one expert): inference must not drop tokens, and dropless also
    keeps prefill/decode bit-consistent.  Bounded capacity is opt-in for
    throughput experiments; overflowing tokens fall through the residual.
    """
    B, Sq, D = x.shape
    T = B * Sq
    E, K = config.num_experts, config.num_experts_per_token
    capacity = T if capacity_factor is None else max(1, int(capacity_factor * T * K / E))

    xt = x.reshape(T, D)
    router_logits = (xt @ lp["router"]).astype(jnp.float32)  # [T, E]
    weights, chosen = jax.lax.top_k(router_logits, K)  # [T, K]
    weights = jax.nn.softmax(weights, axis=-1)  # renormalise over chosen

    yt = expert_dispatch(xt, chosen, weights, lp, E, capacity)
    return yt.reshape(B, Sq, D)


def expert_dispatch(
    xt: jnp.ndarray,  # [T, D]
    chosen: jnp.ndarray,  # [T, K] expert id of each assignment, LOCAL to lp's E experts
    weights: jnp.ndarray,  # [T, K] f32 combine weights
    lp: Dict[str, jnp.ndarray],  # moe_gate / moe_up / moe_down [E, ...] (+ scales)
    E: int,
    capacity: int,
    valid: jnp.ndarray | None = None,  # [T, K] False = not an expert held here: skipped
) -> jnp.ndarray:
    """sum_k weights[t, k] * FFN_{chosen[t, k]}(xt[t]) through per-expert
    token-index tables [E, C].  With ``valid`` (expert parallelism: the
    router chose over more experts than ``lp`` holds) the assignments that
    land elsewhere add nothing."""
    T, D = xt.shape
    K = chosen.shape[1]
    # Queue position of each (t, k) assignment within its expert.
    flat_e = chosen.reshape(T * K)  # expert id per assignment
    if valid is not None:
        flat_e = jnp.where(valid.reshape(T * K), flat_e, E)  # E: no such expert
    flat_t = jnp.repeat(jnp.arange(T, dtype=jnp.int32), K)  # token per assignment
    flat_w = weights.reshape(T * K)
    onehot_e = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)  # [T*K, E]; all zero for id E
    pos = (jnp.cumsum(onehot_e, axis=0) - 1)[jnp.arange(T * K), jnp.minimum(flat_e, E - 1)]
    overflow = (pos >= capacity) | (flat_e >= E)
    pos_safe = jnp.where(overflow, capacity, pos)  # OOB rows dropped by scatter

    # dispatch_idx[e, c] = source token index (T = padding row).
    dispatch_idx = jnp.full((E, capacity), T, jnp.int32)
    dispatch_idx = dispatch_idx.at[flat_e, pos_safe].set(flat_t, mode="drop")
    gate_w = jnp.zeros((E, capacity), jnp.float32)
    gate_w = gate_w.at[flat_e, pos_safe].set(flat_w, mode="drop")

    from ..ops.quant_matmul import expert_linear

    x_pad = jnp.concatenate([xt, jnp.zeros((1, D), xt.dtype)], axis=0)
    xe = x_pad[dispatch_idx]  # [E, C, D]
    gate = jax.nn.silu(
        expert_linear(xe, lp, "moe_gate", jnp.float32)
    ).astype(xt.dtype)
    up = expert_linear(xe, lp, "moe_up")
    ye = expert_linear(gate * up, lp, "moe_down")  # [E, C, D]

    # Combine: weighted scatter-add back to token rows.
    ye_w = ye.astype(jnp.float32) * gate_w[..., None]
    yt = jnp.zeros((T + 1, D), jnp.float32)
    yt = yt.at[dispatch_idx.reshape(-1)].add(ye_w.reshape(-1, D), mode="drop")
    return yt[:T].astype(xt.dtype)
