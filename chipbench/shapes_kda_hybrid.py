"""Bytes and operations one decode step of a hybrid model with Kimi Delta
Attention layers has to move (``kimi_linear``): KDA mixers whose state (a
matrix a head and the tail of three convolutions) lives in slots, latent
attention (MLA) layers whose pages hold one 576-value entry a position, a
leading dense SwiGLU and then sigmoid-gated experts of which this chip holds a
share beside one shared expert (no JAX).

``model`` is the HF-style object of a configuration file with the
``kimi_linear`` keys (``linear_attn_config``, the MLA keys, ``num_experts`` /
``num_experts_published``); ``serve`` its serve flags.  As in ``shapes.py``
these are the algorithm's needs, not what the program happens to do: experts
that no row chose, padding rows, the 64 zero lanes of a stored entry and the
per-channel scales do not count, and a row's state is read ONCE and written
ONCE a layer.
"""

from __future__ import annotations

from chipbench.shapes import weight_bytes_per_el
from chipbench.shapes_mla_dsa import _float_el, cache_el as _cache_el

_STATE_EL = 4  # the KDA state is float32 whatever the activation type


def layer_counts(model: dict) -> dict:
    lin, n = model["linear_attn_config"], model["num_hidden_layers"]
    dense = min(model.get("first_k_dense_replace", 0), n)
    return {"kda": len(lin["kda_layers"]), "mla": len(lin["full_attn_layers"]),
            "dense": dense, "moe": n - dense}


def kda_dims(model: dict) -> tuple:
    """(heads, head size of keys and values, taps, channels through the taps)."""
    lin = model["linear_attn_config"]
    h, d = lin["num_heads"], lin["head_dim"]
    return h, d, lin["short_conv_kernel_size"], 3 * h * d


def mixer_weight_elements(model: dict) -> dict:
    """Elements of ONE layer's mixer by how they are stored: ``quant`` (the
    large projections), ``float`` (the taps, the two low-rank pairs with W_b
    and the gate's bias, the norm; W^UK, W^UV and the latent's norm) and
    ``f32`` (A_log, dt_bias)."""
    dm, ha = model["hidden_size"], model["num_attention_heads"]
    h, d, k, c = kda_dims(model)
    rkv, dn, dr, dv = (model["kv_lora_rank"], model["qk_nope_head_dim"],
                       model["qk_rope_head_dim"], model["v_head_dim"])
    return {"kda": {"quant": dm * c + h * d * dm,
                    "float": k * c + dm * (2 * d + h) + 2 * d * h * d + h * d + d,
                    "f32": h + h * d},
            "mla": {"quant": dm * ha * (dn + dr) + dm * (rkv + dr) + ha * dv * dm,
                    "float": rkv + ha * rkv * (dn + dv), "f32": 0}}


def router_width(model: dict) -> int:
    return model.get("num_experts_published", model["num_experts"] * model.get("ep_size", 1))


def experts_touched(model: dict, rows: float) -> float:
    """Expected number of the held experts that ``rows`` tokens choose at
    least once, each choosing ``num_experts_per_token`` of the router's evenly."""
    p = model["num_experts_per_token"] / router_width(model)
    return model["num_experts"] * (1.0 - (1.0 - p) ** max(rows, 0.0))


def expert_elements(model: dict) -> int:
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def fixed_weight_bytes(model: dict, serve: dict) -> float:
    """The weights OUTSIDE the routed experts, read once a step whatever its
    rows: every mixer, the two norms a layer and the last one, the dense MLP,
    the shared expert, router and selection bias of every expert layer, and
    the output head (the lookup of a row a sequence is left out)."""
    dm, n = model["hidden_size"], layer_counts(model)
    q, f = weight_bytes_per_el(serve), _float_el(serve)
    per = mixer_weight_elements(model)
    total = sum(n[k] * (per[k]["quant"] * q + per[k]["float"] * f + per[k]["f32"] * 4)
                for k in ("kda", "mla"))
    total += (2 * model["num_hidden_layers"] + 1) * dm * f
    total += n["dense"] * 3 * dm * model["intermediate_size"] * q
    shared = model.get("num_shared_experts", 0) * expert_elements(model)
    total += n["moe"] * (shared * q + dm * router_width(model) * f + router_width(model) * 4)
    return total + dm * model["vocab_size"] * q


def decode_weight_bytes(model: dict, serve: dict, rows: float) -> float:
    """``fixed_weight_bytes`` plus, in every expert layer, the held experts some row chose."""
    return fixed_weight_bytes(model, serve) + (
        layer_counts(model)["moe"] * experts_touched(model, rows) * expert_elements(model)
        * weight_bytes_per_el(serve))


def latent_bytes_per_token(model: dict, serve: dict) -> int:
    """The latent entry of one cached position over the MLA layers ALONE."""
    return (layer_counts(model)["mla"]
            * (model["kv_lora_rank"] + model["qk_rope_head_dim"]) * _cache_el(serve))


def state_bytes_per_row(model: dict, serve: dict) -> int:
    """A decoding row's slot over the KDA layers, READ once AND WRITTEN once:
    the state (float32) and the taps' tail (the activation type)."""
    h, d, k, c = kda_dims(model)
    slot = h * d * d * _STATE_EL + (k - 1) * c * _float_el(serve)
    return 2 * layer_counts(model)["kda"] * slot


def decode_step_bytes(model: dict, serve: dict, rows: float, held_tokens: float) -> float:
    """Least bytes from HBM for one decode step of ``rows`` rows that hold
    ``held_tokens`` positions between them."""
    return (decode_weight_bytes(model, serve, rows)
            + held_tokens * latent_bytes_per_token(model, serve)
            + rows * state_bytes_per_row(model, serve))


def decode_step_ops(model: dict, rows: float, held_tokens: float) -> float:
    """Multiply-adds x 2 of one decode step: each row through every mixer's
    projections, the dense MLP, the shared expert, the router and its
    ``num_experts_per_token`` experts an expert layer, the head, and its
    state's decay, read and update in the KDA layers (four multiply-adds a
    state element); each held position scored and weighed by every head of the
    MLA layers in the absorbed form."""
    dm, n = model["hidden_size"], layer_counts(model)
    per = mixer_weight_elements(model)
    h, d, _, _ = kda_dims(model)
    per_row = sum(n[k] * (per[k]["quant"] + per[k]["float"]) for k in ("kda", "mla"))
    per_row += n["kda"] * 4 * h * d * d
    per_row += n["dense"] * 3 * dm * model["intermediate_size"]
    per_row += n["moe"] * ((model.get("num_shared_experts", 0) + model["num_experts_per_token"])
                           * expert_elements(model) + dm * router_width(model))
    per_row += dm * model["vocab_size"]
    rkv, dr = model["kv_lora_rank"], model["qk_rope_head_dim"]
    attn = model["num_attention_heads"] * (2 * rkv + dr) * n["mla"]
    return 2.0 * (rows * per_row + held_tokens * attn)
