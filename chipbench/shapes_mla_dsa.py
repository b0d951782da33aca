"""Bytes and operations one decode step of a latent-attention model with a
learned sparse selector and held experts has to move (no JAX).

``model`` is the HF-style object of a configuration file with the
``deepseek_v32`` keys; ``serve`` its serve flags.  As in ``shapes.py`` these
are the algorithm's needs, not what the program happens to do: the 64 zero
lanes a stored latent entry carries, expert tables that read every held expert
whatever was routed, and padding rows do not count.
"""

from __future__ import annotations

from chipbench.shapes import _BYTES, weight_bytes_per_el


def _float_el(serve: dict) -> int:
    return _BYTES[serve.get("dtype", "bfloat16")]


def cache_el(serve: dict) -> int:
    return _BYTES[serve.get("kv_cache_dtype") or serve.get("dtype", "bfloat16")]


def layer_weight_elements(model: dict) -> dict:
    """Elements of one layer's leaves by how they are stored: ``quant`` (the
    large matrices, ``weight_quant``) and ``float`` (W^UK and W^UV, the
    selector's key and head-weight projections: the activation type)."""
    d, h = model["hidden_size"], model["num_attention_heads"]
    ql, rkv = model["q_lora_rank"], model["kv_lora_rank"]
    dn, dr, dv = model["qk_nope_head_dim"], model["qk_rope_head_dim"], model["v_head_dim"]
    hi, di = model["index_n_heads"], model["index_head_dim"]
    quant = d * ql + ql * h * (dn + dr) + d * (rkv + dr) + h * dv * d + ql * hi * di
    flt = h * rkv * dn + h * rkv * dv + d * di + d * hi
    return {"quant": quant, "float": flt}


def experts_touched(model: dict, rows: float) -> float:
    """Expected number of the held experts that ``rows`` tokens choose at
    least once, each token choosing ``num_experts_per_tok`` of the router's
    experts evenly (group limits left out: they move the choice, not its
    count)."""
    held = model["n_routed_experts"]
    total = model.get("n_routed_experts_published", held * model.get("ep_size", 1))
    p = model["num_experts_per_tok"] / total
    return held * (1.0 - (1.0 - p) ** max(rows, 0.0))


def decode_weight_bytes(model: dict, serve: dict, rows: float) -> float:
    """Weight bytes one decode step of ``rows`` rows has to read: every
    layer's attention and selector projections, the dense FFN of the leading
    layers, and on the others the router, the shared expert and the held
    experts some row chose; the output head.  The embedding is a gather of a
    row per sequence and is left out."""
    d = model["hidden_size"]
    layers, dense = model["num_hidden_layers"], min(
        model["first_k_dense_replace"], model["num_hidden_layers"])
    moe = layers - dense
    q, f = weight_bytes_per_el(serve), _float_el(serve)
    per = layer_weight_elements(model)
    total = layers * (per["quant"] * q + per["float"] * f)
    total += dense * 3 * d * model["intermediate_size"] * q
    expert = 3 * d * model["moe_intermediate_size"]
    routed_total = model.get("n_routed_experts_published",
                             model["n_routed_experts"] * model.get("ep_size", 1))
    total += moe * (d * routed_total * f
                    + (model.get("n_shared_experts", 0) + experts_touched(model, rows))
                    * expert * q)
    return total + d * model["vocab_size"] * q


def cache_bytes(model: dict, serve: dict, context_tokens: float, selected_tokens: float) -> float:
    """Cached bytes a step reads over all layers: the selector's key of every
    position a row holds (each is scored) and the latent entry, K and V at
    once, of every position it keeps."""
    el = cache_el(serve)
    per_scored = model["index_head_dim"] * el
    per_kept = (model["kv_lora_rank"] + model["qk_rope_head_dim"]) * el
    return model["num_hidden_layers"] * (context_tokens * per_scored + selected_tokens * per_kept)


def decode_step_bytes(model: dict, serve: dict, rows: float, context_tokens: float,
                      selected_tokens: float) -> float:
    return (decode_weight_bytes(model, serve, rows)
            + cache_bytes(model, serve, context_tokens, selected_tokens))


def decode_attention_flops(model: dict, context_tokens: float, selected_tokens: float) -> float:
    """Multiply-adds x 2 of the selector's scores over the scored positions
    and of absorbed attention (scores and values) over the kept ones, all
    layers: the part of a step's operations that grows with the context."""
    hi, di = model["index_n_heads"], model["index_head_dim"]
    h, rkv, dr = model["num_attention_heads"], model["kv_lora_rank"], model["qk_rope_head_dim"]
    per_scored = 2 * hi * di
    per_kept = 2 * h * (rkv + dr) + 2 * h * rkv
    return model["num_hidden_layers"] * (context_tokens * per_scored + selected_tokens * per_kept)
