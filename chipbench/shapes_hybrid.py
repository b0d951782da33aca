"""Bytes and operations one decode step of a hybrid model has to move: gated
short convolutions among GQA attention layers, dense then sparse
feed-forwards, every held expert on this chip (no JAX).

``model`` is the HF-style object of a configuration file with the
``lfm2_moe`` keys (``layer_types``); ``serve`` its serve flags.  As in
``shapes.py`` these are the algorithm's needs, not what the program happens to
do: experts that no row chose, padding rows, the per-channel scales and the
zero halves of a packed query do not count.
"""

from __future__ import annotations

from chipbench.shapes import weight_bytes_per_el
from chipbench.shapes_mla_dsa import _float_el, cache_el as _cache_el

# The convolution's page entries are kept in the activation type.
_state_el = _float_el


def layer_counts(model: dict) -> dict:
    """Layers by kind: ``conv``, ``attn``, ``dense`` (the leading
    ``num_dense_layers`` feed-forwards) and ``moe``."""
    kinds = model["layer_types"]
    dense = min(model.get("num_dense_layers", 0), len(kinds))
    conv = sum(k == "conv" for k in kinds)
    return {"conv": conv, "attn": len(kinds) - conv, "dense": dense, "moe": len(kinds) - dense}


def head_dim(model: dict) -> int:
    return model.get("head_dim") or model["hidden_size"] // model["num_attention_heads"]


def mixer_weight_elements(model: dict) -> dict:
    """Elements of ONE layer's mixer by how they are stored: ``quant`` (the
    projections) and ``float`` (the taps; the QK-norm weights)."""
    d, h, hd = model["hidden_size"], model["num_attention_heads"], head_dim(model)
    kv = model.get("num_key_value_heads", h)
    return {"conv": {"quant": d * 3 * d + d * d, "float": model.get("conv_L_cache", 3) * d},
            "attn": {"quant": d * (h + 2 * kv) * hd + h * hd * d, "float": 2 * hd}}


def router_width(model: dict) -> int:
    return model.get("num_experts_published", model["num_experts"] * model.get("ep_size", 1))


def experts_touched(model: dict, rows: float) -> float:
    """Expected number of the held experts that ``rows`` tokens choose at
    least once, each choosing ``num_experts_per_tok`` of the router's evenly."""
    p = model["num_experts_per_tok"] / router_width(model)
    return model["num_experts"] * (1.0 - (1.0 - p) ** max(rows, 0.0))


def expert_elements(model: dict) -> int:
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def fixed_weight_bytes(model: dict, serve: dict) -> float:
    """The weights OUTSIDE the experts, read once a step whatever its rows:
    every mixer, the two norms a layer and the last one, the dense
    feed-forwards, the routers with their biases, and the output head (the
    tied embedding read as the head; the lookup of a row a sequence is left
    out)."""
    d, n = model["hidden_size"], layer_counts(model)
    q, f = weight_bytes_per_el(serve), _float_el(serve)
    per = mixer_weight_elements(model)
    total = sum(n[k] * (per[k]["quant"] * q + per[k]["float"] * f) for k in ("conv", "attn"))
    total += (2 * len(model["layer_types"]) + 1) * d * f
    total += n["dense"] * 3 * d * model["intermediate_size"] * q
    total += n["moe"] * (d * router_width(model) * f + router_width(model) * 4)
    return total + d * model["vocab_size"] * q


def decode_weight_bytes(model: dict, serve: dict, rows: float) -> float:
    """``fixed_weight_bytes`` plus, in every expert layer, the held experts
    some row chose."""
    return fixed_weight_bytes(model, serve) + (
        layer_counts(model)["moe"] * experts_touched(model, rows) * expert_elements(model)
        * weight_bytes_per_el(serve))


def kv_bytes_per_token(model: dict, serve: dict) -> int:
    """K and V of one cached position over the ATTENTION layers."""
    kv = model.get("num_key_value_heads", model["num_attention_heads"])
    return 2 * layer_counts(model)["attn"] * kv * head_dim(model) * _cache_el(serve)


def state_bytes_per_row(model: dict, serve: dict) -> int:
    """A decoding row's page entries over the convolution layers: the
    ``conv_L_cache - 1`` positions it reads and the same it writes back."""
    k = model.get("conv_L_cache", 3) - 1
    return 2 * layer_counts(model)["conv"] * k * model["hidden_size"] * _state_el(serve)


def decode_step_bytes(model: dict, serve: dict, rows: float, held_tokens: float) -> float:
    """Least bytes from HBM for one decode step of ``rows`` rows that hold
    ``held_tokens`` positions between them."""
    return (decode_weight_bytes(model, serve, rows)
            + held_tokens * kv_bytes_per_token(model, serve)
            + rows * state_bytes_per_row(model, serve))


def decode_step_ops(model: dict, rows: float, held_tokens: float) -> float:
    """Multiply-adds x 2 of one decode step: each row through every mixer's
    projections, the dense feed-forwards, the router and its
    ``num_experts_per_tok`` experts a layer, the head; each held position
    scored and weighed by every query head of the attention layers."""
    d, n = model["hidden_size"], layer_counts(model)
    per = mixer_weight_elements(model)
    per_row = sum(n[k] * per[k]["quant"] for k in ("conv", "attn"))
    per_row += n["dense"] * 3 * d * model["intermediate_size"]
    per_row += n["moe"] * (d * router_width(model)
                           + model["num_experts_per_tok"] * expert_elements(model))
    per_row += d * model["vocab_size"]
    attn = 2 * model["num_attention_heads"] * head_dim(model) * n["attn"]
    return 2.0 * (rows * per_row + held_tokens * attn)
