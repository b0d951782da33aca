"""Bytes and operations a prompt step and a decode step of a hybrid model with
Mamba-1 layers have to move (``jamba``): selective-scan mixers whose state
(``[d_state, inner]`` float32 a layer a sequence, and the taps' tail) lives in
slots, GQA attention layers over K/V pages, a dense SwiGLU in EVERY layer, a
tied head (no JAX).

``model`` is the HF-style object of a configuration file with the ``jamba``
keys (``attn_layer_period`` / ``attn_layer_offset``, the ``mamba_*`` keys);
``serve`` its serve flags.  As in ``shapes.py`` these are the algorithm's
needs, not what the program happens to do: padding rows and padding tokens do
not count, and a decoding row's state is read ONCE and written ONCE a layer.
The mixer's leaves are never int8 (models/mamba1.py: the release's card keeps
the Mamba blocks out of quantization), whatever ``weight_quant`` says.
"""

from __future__ import annotations

from chipbench.shapes import weight_bytes_per_el
from chipbench.shapes_mla_dsa import _float_el, cache_el as _cache_el

_STATE_EL = 4  # the scan state is float32 whatever the activation type
# Multiply-adds x 2 of ONE element-update of the recurrence: the decay's
# product dt A (1), its product with the state and the sum (2), the input's
# product B (dt c) (1; dt c once a channel), the read-out's product with C and
# its sum (2).  The ``exp`` is a transcendental and is not counted.
_UPDATE_OPS = 6


def layer_counts(model: dict) -> dict:
    n, period, offset = (model["num_hidden_layers"], model["attn_layer_period"],
                         model["attn_layer_offset"])
    attn = sum(l % period == offset for l in range(n))
    return {"mamba1": n - attn, "attn": attn, "dense": n}


def mamba1_dims(model: dict) -> tuple:
    """(inner width, state size, taps, the step size's rank)."""
    return (model["mamba_expand"] * model["hidden_size"], model["mamba_d_state"],
            model["mamba_d_conv"], model["mamba_dt_rank"])


def head_dim(model: dict) -> int:
    return model.get("head_dim") or model["hidden_size"] // model["num_attention_heads"]


def mixer_weight_elements(model: dict) -> dict:
    """Elements of ONE layer's mixer by how they are stored: ``matmul`` (the
    leaves a token is multiplied by: for Mamba-1 W_in, W_x, W_dt, W_out, never
    int8; for attention wqkv and wo, int8 under ``weight_quant``), ``float``
    (taps and their bias, the three inner norms) and ``f32`` (A_log, D,
    dt_bias)."""
    dm = model["hidden_size"]
    di, n, k, r = mamba1_dims(model)
    h, kv, hd = model["num_attention_heads"], model["num_key_value_heads"], head_dim(model)
    return {"mamba1": {"matmul": dm * 2 * di + di * (r + 2 * n) + r * di + di * dm,
                       "float": k * di + di + r + 2 * n, "f32": n * di + 2 * di},
            "attn": {"matmul": dm * (h + 2 * kv) * hd + h * hd * dm, "float": 0, "f32": 0}}


def weight_bytes(model: dict, serve: dict) -> float:
    """Every weight a step reads once, whatever its rows: the mixers, the two
    norms a layer and the last one, every layer's SwiGLU and the tied head
    (the lookup of a row a token is left out)."""
    dm, n = model["hidden_size"], layer_counts(model)
    q, f = weight_bytes_per_el(serve), _float_el(serve)
    per = mixer_weight_elements(model)
    total = n["mamba1"] * (per["mamba1"]["matmul"] * f + per["mamba1"]["float"] * f
                           + per["mamba1"]["f32"] * 4)
    total += n["attn"] * per["attn"]["matmul"] * q
    total += (2 * model["num_hidden_layers"] + 1) * dm * f
    total += n["dense"] * 3 * dm * model["intermediate_size"] * q
    return total + dm * model["vocab_size"] * q


def kv_bytes_per_token(model: dict, serve: dict) -> int:
    """K and V of one cached position over the attention layers ALONE."""
    return (layer_counts(model)["attn"] * 2 * model["num_key_value_heads"] * head_dim(model)
            * _cache_el(serve))


def state_bytes_per_row(model: dict, serve: dict) -> int:
    """A decoding row's slot over the Mamba-1 layers, READ once AND WRITTEN
    once: the state (float32) and the taps' tail (the activation type)."""
    di, n, k, _ = mamba1_dims(model)
    slot = n * di * _STATE_EL + (k - 1) * di * _float_el(serve)
    return 2 * layer_counts(model)["mamba1"] * slot


def scan_updates_per_token(model: dict) -> int:
    """Element-updates of the recurrence one token costs over the Mamba-1
    layers: ``d_state x inner`` a layer."""
    di, n, _, _ = mamba1_dims(model)
    return layer_counts(model)["mamba1"] * n * di


def decode_step_bytes(model: dict, serve: dict, rows: float, held_tokens: float) -> float:
    """Least bytes from HBM for one decode step of ``rows`` rows that hold
    ``held_tokens`` positions between them."""
    return (weight_bytes(model, serve) + held_tokens * kv_bytes_per_token(model, serve)
            + rows * state_bytes_per_row(model, serve))


def token_ops(model: dict) -> float:
    """Multiply-adds x 2 one token costs outside attention's scores: every
    mixer's projections, every SwiGLU, and the recurrence."""
    dm, n = model["hidden_size"], layer_counts(model)
    per = mixer_weight_elements(model)
    matmul = sum(n[k] * per[k]["matmul"] for k in ("mamba1", "attn"))
    matmul += n["dense"] * 3 * dm * model["intermediate_size"]
    return 2.0 * matmul + _UPDATE_OPS * scan_updates_per_token(model)


def attention_ops(model: dict, attended: float) -> float:
    """Multiply-adds x 2 of ``attended`` (query, position) pairs: scored and
    weighed by every query head of the attention layers."""
    return 2.0 * attended * layer_counts(model)["attn"] * model["num_attention_heads"] * 2 * head_dim(model)


def head_ops(model: dict, rows: float) -> float:
    """The tied head over the ``rows`` last tokens a step computes logits of."""
    return 2.0 * rows * model["hidden_size"] * model["vocab_size"]


def decode_step_ops(model: dict, rows: float, held_tokens: float) -> float:
    """One decode step: each row one token through everything and the head,
    each held position attended once."""
    return rows * token_ops(model) + attention_ops(model, held_tokens) + head_ops(model, rows)


def prompt_step_ops(model: dict, tokens: float, cached: float, rows: float = 1.0) -> float:
    """One prompt step of ``tokens`` tokens in ``rows`` rows behind ``cached``
    positions already in the pages: a token at place i of its row attends to
    ``cached + i + 1`` positions (``cached`` taken a row, the tokens spread
    evenly over the rows)."""
    per_row = tokens / max(rows, 1.0)
    attended = tokens * cached + rows * per_row * (per_row + 1) / 2
    return tokens * token_ops(model) + attention_ops(model, attended) + head_ops(model, rows)
