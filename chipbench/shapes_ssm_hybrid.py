"""Bytes and operations one decode step of a hybrid model with Mamba-2 layers
has to move (``granitemoehybrid``): Mamba-2 mixers whose scan state lives in
slots, GQA attention layers, and in EVERY layer softmax-gated experts of which
this chip holds a share beside an always-on shared SwiGLU (no JAX).

``model`` is the HF-style object of a configuration file with the
``granitemoehybrid`` keys (``layer_types`` of "mamba" / "attention",
``mamba_*``); ``serve`` its serve flags.  As in ``shapes.py`` these are the
algorithm's needs, not what the program happens to do: experts that no row
chose, padding rows and the per-channel scales do not count.
"""

from __future__ import annotations

from chipbench.shapes import weight_bytes_per_el
from chipbench.shapes_mla_dsa import _float_el, cache_el as _cache_el

_STATE_EL = 4  # the scan state is float32 whatever the activation type


def layer_counts(model: dict) -> dict:
    kinds = model["layer_types"]
    mamba = sum(k == "mamba" for k in kinds)
    return {"mamba": mamba, "attn": len(kinds) - mamba, "moe": len(kinds)}


def head_dim(model: dict) -> int:
    return model.get("head_dim") or model["hidden_size"] // model["num_attention_heads"]


def mamba_dims(model: dict) -> tuple:
    """(inner width, heads, head size, state size, taps, channels through the taps)."""
    hm, p, n = model["mamba_n_heads"], model["mamba_d_head"], model["mamba_d_state"]
    return hm * p, hm, p, n, model["mamba_d_conv"], hm * p + 2 * n * model.get("mamba_n_groups", 1)


def mixer_weight_elements(model: dict) -> dict:
    """Elements of ONE layer's mixer by how they are stored: ``quant`` (the
    projections), ``float`` (the taps with their bias, the gated norm) and
    ``f32`` (A_log, D, dt_bias)."""
    d, h, hd = model["hidden_size"], model["num_attention_heads"], head_dim(model)
    kv = model.get("num_key_value_heads", h)
    di, hm, _, _, k, c = mamba_dims(model)
    return {"mamba": {"quant": d * (di + c + hm) + di * d, "float": (k + 1) * c + di, "f32": 3 * hm},
            "attn": {"quant": d * (h + 2 * kv) * hd + h * hd * d, "float": 0, "f32": 0}}


def router_width(model: dict) -> int:
    return model.get("num_local_experts_published",
                     model["num_local_experts"] * model.get("ep_size", 1))


def experts_touched(model: dict, rows: float) -> float:
    """Expected number of the held experts that ``rows`` tokens choose at
    least once, each choosing ``num_experts_per_tok`` of the router's evenly."""
    p = model["num_experts_per_tok"] / router_width(model)
    return model["num_local_experts"] * (1.0 - (1.0 - p) ** max(rows, 0.0))


def expert_elements(model: dict) -> int:
    return 3 * model["hidden_size"] * model["intermediate_size"]


def shared_elements(model: dict) -> int:
    return 3 * model["hidden_size"] * model.get("shared_intermediate_size", 0)


def fixed_weight_bytes(model: dict, serve: dict) -> float:
    """The weights OUTSIDE the experts, read once a step whatever its rows:
    every mixer, the two norms a layer and the last one, the shared SwiGLU and
    the router of every layer, and the output head (the tied embedding read as
    the head; the lookup of a row a sequence is left out)."""
    d, n = model["hidden_size"], layer_counts(model)
    q, f = weight_bytes_per_el(serve), _float_el(serve)
    per = mixer_weight_elements(model)
    total = sum(n[k] * (per[k]["quant"] * q + per[k]["float"] * f + per[k]["f32"] * 4)
                for k in ("mamba", "attn"))
    total += (2 * len(model["layer_types"]) + 1) * d * f
    total += n["moe"] * (shared_elements(model) * q + d * router_width(model) * f)
    return total + d * model["vocab_size"] * q


def decode_weight_bytes(model: dict, serve: dict, rows: float) -> float:
    """``fixed_weight_bytes`` plus, in every layer, the held experts some row chose."""
    return fixed_weight_bytes(model, serve) + (
        layer_counts(model)["moe"] * experts_touched(model, rows) * expert_elements(model)
        * weight_bytes_per_el(serve))


def kv_bytes_per_token(model: dict, serve: dict) -> int:
    """K and V of one cached position over the ATTENTION layers."""
    kv = model.get("num_key_value_heads", model["num_attention_heads"])
    return 2 * layer_counts(model)["attn"] * kv * head_dim(model) * _cache_el(serve)


def state_bytes_per_row(model: dict, serve: dict) -> int:
    """A decoding row's slot over the Mamba-2 layers, READ AND WRITTEN: the
    scan state (float32) and the taps' tail (the activation type)."""
    _, hm, p, n, k, c = mamba_dims(model)
    slot = hm * p * n * _STATE_EL + (k - 1) * c * _float_el(serve)
    return 2 * layer_counts(model)["mamba"] * slot


def decode_step_bytes(model: dict, serve: dict, rows: float, held_tokens: float) -> float:
    """Least bytes from HBM for one decode step of ``rows`` rows that hold
    ``held_tokens`` positions between them."""
    return (decode_weight_bytes(model, serve, rows)
            + held_tokens * kv_bytes_per_token(model, serve)
            + rows * state_bytes_per_row(model, serve))


def decode_step_ops(model: dict, rows: float, held_tokens: float) -> float:
    """Multiply-adds x 2 of one decode step: each row through every mixer's
    projections, the shared SwiGLU, the router and its ``num_experts_per_tok``
    experts a layer, the head, and its state's update and readout in the
    Mamba-2 layers (two multiply-adds a state element); each held position
    scored and weighed by every query head of the attention layers."""
    d, n = model["hidden_size"], layer_counts(model)
    per = mixer_weight_elements(model)
    _, hm, p, ns, _, _ = mamba_dims(model)
    per_row = sum(n[k] * per[k]["quant"] for k in ("mamba", "attn"))
    per_row += n["mamba"] * 2 * hm * p * ns
    per_row += n["moe"] * (shared_elements(model) + d * router_width(model)
                           + model["num_experts_per_tok"] * expert_elements(model))
    per_row += d * model["vocab_size"]
    attn = 2 * model["num_attention_heads"] * head_dim(model) * n["attn"]
    return 2.0 * (rows * per_row + held_tokens * attn)
