"""Bytes and operations of a model whose attention layers are of two kinds
(``exaone_moe``): ``sliding_attention`` layers that keep and attend to the last
``sliding_window`` positions only, ``full_attention`` layers that keep every
position; a leading dense SwiGLU layer, then sigmoid-gated experts of which
this chip holds a share beside one shared expert (no JAX).

``model`` is the HF-style object of a configuration file with the
``exaone_moe`` keys; ``serve`` its serve flags.  As in ``shapes.py`` these are
the algorithm's needs, not what the program happens to do: THE NEED OF A
WINDOW LAYER IS COUNTED FROM THE WINDOW, whatever implements it (a walk over
the whole context under a mask would be measured against the same need);
experts that no row chose, padding rows and the per-channel scales do not
count.
"""

from __future__ import annotations

from chipbench.shapes import weight_bytes_per_el
from chipbench.shapes_mla_dsa import _float_el, cache_el as _cache_el


def layer_counts(model: dict) -> dict:
    kinds = model["layer_types"]
    window = sum(k == "sliding_attention" for k in kinds)
    dense = model.get("first_k_dense_replace",
                      sum(k == "dense" for k in model.get("mlp_layer_types", ())))
    return {"window": window, "full": len(kinds) - window, "dense": dense,
            "moe": len(kinds) - dense}


def head_dim(model: dict) -> int:
    return model.get("head_dim") or model["hidden_size"] // model["num_attention_heads"]


def kv_heads(model: dict) -> int:
    return model.get("num_key_value_heads", model["num_attention_heads"])


def attention_elements(model: dict) -> int:
    """One layer's projections (either kind): q, k, v and the output's."""
    d, h, hd = model["hidden_size"], model["num_attention_heads"], head_dim(model)
    return d * (h + 2 * kv_heads(model)) * hd + h * hd * d


def router_width(model: dict) -> int:
    return model.get("num_experts_published", model["num_experts"] * model.get("ep_size", 1))


def experts_touched(model: dict, rows: float) -> float:
    """Expected number of the held experts that ``rows`` tokens choose at
    least once, each choosing ``num_experts_per_tok`` of the router's evenly."""
    p = model["num_experts_per_tok"] / router_width(model)
    return model["num_experts"] * (1.0 - (1.0 - p) ** max(rows, 0.0))


def expert_elements(model: dict) -> int:
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def shared_elements(model: dict) -> int:
    return model.get("num_shared_experts", 0) * expert_elements(model)


def dense_elements(model: dict) -> int:
    return 3 * model["hidden_size"] * model["intermediate_size"]


def fixed_weight_bytes(model: dict, serve: dict) -> float:
    """The weights OUTSIDE the routed experts, read once a step whatever its
    rows: every attention layer's projections and its two head norms, the two
    norms a layer and the last one, the dense MLPs, the shared expert, the
    router and its bias of every expert layer, and the output head's slice
    (untied; the lookup of a row a sequence is left out)."""
    d, n = model["hidden_size"], layer_counts(model)
    q, f = weight_bytes_per_el(serve), _float_el(serve)
    layers = len(model["layer_types"])
    total = layers * (attention_elements(model) * q + 2 * head_dim(model) * f)
    total += (2 * layers + 1) * d * f
    total += n["dense"] * dense_elements(model) * q
    total += n["moe"] * (shared_elements(model) * q + d * router_width(model) * f
                         + router_width(model) * 4)
    return total + d * model["vocab_size"] * q


def decode_weight_bytes(model: dict, serve: dict, rows: float) -> float:
    """``fixed_weight_bytes`` plus, in every expert layer, the held experts some row chose."""
    return fixed_weight_bytes(model, serve) + (
        layer_counts(model)["moe"] * experts_touched(model, rows) * expert_elements(model)
        * weight_bytes_per_el(serve))


def kv_bytes_per_position(model: dict, serve: dict) -> int:
    """K and V of one cached position in ONE attention layer."""
    return 2 * kv_heads(model) * head_dim(model) * _cache_el(serve)


def window_positions(model: dict, held_tokens: float, rows: float) -> float:
    """Positions the window layers read for ``rows`` decoding rows that hold
    ``held_tokens`` positions between them: min(t, window) a row, taken at the
    rows' mean context (exact where every row is past the window or none is)."""
    if rows <= 0:
        return 0.0
    return rows * min(held_tokens / rows, model["sliding_window"])


def decode_step_bytes(model: dict, serve: dict, rows: float, held_tokens: float) -> float:
    """Least bytes from HBM for one decode step of ``rows`` rows that hold
    ``held_tokens`` positions between them: every position in the full
    layers, min(t, window) in the window layers."""
    n, kv = layer_counts(model), kv_bytes_per_position(model, serve)
    return (decode_weight_bytes(model, serve, rows)
            + n["full"] * held_tokens * kv
            + n["window"] * window_positions(model, held_tokens, rows) * kv)


def decode_step_ops(model: dict, rows: float, held_tokens: float) -> float:
    """Multiply-adds x 2 of one decode step: each row through every attention
    layer's projections, the dense MLPs, the shared expert, the router and its
    ``num_experts_per_tok`` experts an expert layer, and the head; each
    attended position scored and weighed by every query head."""
    d, n = model["hidden_size"], layer_counts(model)
    per_row = len(model["layer_types"]) * attention_elements(model)
    per_row += n["dense"] * dense_elements(model)
    per_row += n["moe"] * (shared_elements(model) + d * router_width(model)
                           + model["num_experts_per_tok"] * expert_elements(model))
    per_row += d * model["vocab_size"]
    pair = 2 * model["num_attention_heads"] * head_dim(model)
    attended = (n["full"] * held_tokens
                + n["window"] * window_positions(model, held_tokens, rows))
    return 2.0 * (rows * per_row + attended * pair)


def window_decode_call_need_s(model: dict, serve: dict, rows: float, held_tokens: float,
                              peaks: dict) -> float:
    """Least time of ONE window layer's decode call: the K and V of
    min(t, window) positions a decoding row, over the HBM bandwidth."""
    return (window_positions(model, held_tokens, rows) * kv_bytes_per_position(model, serve)
            / peaks["hbm_bytes_per_s"])


def window_prefill_pairs(model: dict, prompt_len: int, hit: int) -> float:
    """(query, attended position) pairs ONE window layer computes for a
    prompt of ``prompt_len`` tokens behind a prefix hit of ``hit``: the query
    at position t attends to min(t + 1, window) positions."""
    w = model["sliding_window"]
    short_lo, short_hi = min(hit, w - 1), min(prompt_len, w - 1)  # positions t with t + 1 < w
    short = (short_hi * (short_hi + 1) - short_lo * (short_lo + 1)) / 2.0
    return short + (prompt_len - hit - (short_hi - short_lo)) * w


def window_prefill_flops(model: dict, prompt_len: int, hit: int) -> float:
    """Operations of ONE window layer's attention for that prompt: scores and
    values, 4 x heads x head size a pair."""
    return 4.0 * model["num_attention_heads"] * head_dim(model) * window_prefill_pairs(
        model, prompt_len, hit)
