"""Bytes a step has to move, from a configuration's shapes (no JAX).

``config`` is the HF-style ``model`` object of a configuration file; ``serve``
its serve flags.  These are the algorithm's needs, not what the program
happens to do: padding and recomputation do not count.
"""

from __future__ import annotations

_BYTES = {"int8": 1, "bfloat16": 2, "float16": 2, "float32": 4}


def weight_bytes_per_el(serve: dict) -> int:
    return 1 if serve.get("weight_quant") == "int8" else _BYTES[serve.get("dtype", "bfloat16")]


def kv_bytes_per_token(model: dict, serve: dict) -> int:
    """K and V of one token over all layers, in the cache's type."""
    head_dim = model.get("head_dim") or model["hidden_size"] // model["num_attention_heads"]
    kv_heads = model.get("num_key_value_heads", model["num_attention_heads"])
    el = _BYTES[serve.get("kv_cache_dtype") or serve.get("dtype", "bfloat16")]
    return 2 * model["num_hidden_layers"] * kv_heads * head_dim * el


def decode_weight_bytes(model: dict, serve: dict) -> float:
    """Weight bytes one decode step has to read, whatever its rows: every
    layer's attention projections, the dense FFN and the output head.  The
    embedding table is a gather of a row per sequence and is left out.  A sparse
    FFN reads only the experts its rows touch: not written yet, so refused."""
    h = model["hidden_size"]
    heads = model["num_attention_heads"]
    head_dim = model.get("head_dim") or h // heads
    kv_heads = model.get("num_key_value_heads", heads)
    ffn = model["intermediate_size"]
    el = weight_bytes_per_el(serve)
    attn = h * heads * head_dim + 2 * h * kv_heads * head_dim + heads * head_dim * h
    if model.get("num_local_experts"):
        raise NotImplementedError("decode bytes of a sparse FFN: add them with the first such cell")
    mlp = 3 * h * ffn
    head = h * model["vocab_size"]
    return (model["num_hidden_layers"] * (attn + mlp) + head) * el


def decode_step_bytes(model: dict, serve: dict, kv_tokens: float) -> float:
    """Least bytes from HBM for one decode step: the weights once, and the
    cached K and V of every token the rows attend to."""
    return decode_weight_bytes(model, serve) + kv_tokens * kv_bytes_per_token(model, serve)
