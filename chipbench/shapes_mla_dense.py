"""Bytes and operations of decode with latent attention over the WHOLE
context (no selector) and held experts (no JAX).

``model`` is the HF-style object of a configuration file of the latent family
without ``index_*`` keys (``kimi_k2``, ``deepseek_v3``); ``serve`` its serve
flags.  The arithmetic is ``shapes_mla_dsa``'s with a selector of width zero
that keeps every position: no indexer weights, no indexer key to score, and
every position a row holds is read as its 576-value latent entry.  As there,
these are the algorithm's needs: the 64 zero lanes of a stored entry, experts
that no row chose and padding rows do not count.
"""

from __future__ import annotations

from chipbench import shapes_mla_dsa


def _no_selector(model: dict) -> dict:
    return dict(model, index_n_heads=0, index_head_dim=0)


def layer_weight_elements(model: dict) -> dict:
    """Elements of one layer's attention leaves: ``quant`` (wq_a, wq_b, wkv_a,
    wo) and ``float`` (W^UK and W^UV)."""
    return shapes_mla_dsa.layer_weight_elements(_no_selector(model))


def decode_weight_bytes(model: dict, serve: dict, rows: float) -> float:
    """Weight bytes one decode step of ``rows`` rows has to read (of the
    routed experts only those some row chose)."""
    return shapes_mla_dsa.decode_weight_bytes(_no_selector(model), serve, rows)


def entry_bytes(model: dict, serve: dict) -> int:
    """One cached position in one layer: the latent and the rope key."""
    return (model["kv_lora_rank"] + model["qk_rope_head_dim"]) * shapes_mla_dsa.cache_el(serve)


def decode_step_bytes(model: dict, serve: dict, rows: float, held_tokens: float) -> float:
    """Least bytes from HBM for one decode step: the weights once and, in
    every layer, the entry of every position the rows hold."""
    return (decode_weight_bytes(model, serve, rows)
            + model["num_hidden_layers"] * held_tokens * entry_bytes(model, serve))


def attention_flops_per_position(model: dict) -> int:
    """Multiply-adds x 2 of ONE query against ONE cached position in ONE
    layer in the absorbed form: H scores over 576 values, H values over 512."""
    h, rkv, dr = model["num_attention_heads"], model["kv_lora_rank"], model["qk_rope_head_dim"]
    return 2 * h * (rkv + dr) + 2 * h * rkv


def decode_attention_flops(model: dict, held_tokens: float) -> float:
    """The part of a decode step's operations that grows with the context."""
    return model["num_hidden_layers"] * held_tokens * attention_flops_per_position(model)


def kernel_call_need_s(model: dict, serve: dict, held_tokens: float, peaks: dict) -> float:
    """Least time of ONE call of the one-query kernel (one layer, every
    decoding row): the larger of its entries' bytes over the HBM bandwidth
    and of its operations over the bf16 peak."""
    return max(held_tokens * entry_bytes(model, serve) / peaks["hbm_bytes_per_s"],
               held_tokens * attention_flops_per_position(model) / peaks["bf16_flops"])


def prefill_query_flops_per_position(model: dict) -> int:
    """Multiply-adds x 2 of ONE prompt query against ONE position in ONE
    layer in the decompressed form: H scores over the key's 128 + 64 lanes, H
    values over 128."""
    return 2 * model["num_attention_heads"] * (
        model["qk_nope_head_dim"] + model["qk_rope_head_dim"] + model["v_head_dim"])


def decompress_flops_per_position(model: dict) -> int:
    """Multiply-adds x 2 that turn ONE position's latent into its per-head
    keys and values (W^UK and W^UV) in ONE layer."""
    return 2 * model["num_attention_heads"] * model["kv_lora_rank"] * (
        model["qk_nope_head_dim"] + model["v_head_dim"])


def prefill_request_flops(model: dict, serve: dict, prompt_len: int, hit: int) -> float:
    """Least operations of ONE layer's prompt attention for a request whose
    first ``hit`` positions are a prefix hit, counted from below: the query at
    position p attends to the p + 1 positions up to itself (a hit's positions
    are attended to, their queries not computed), and the row's context is
    decompressed once a chunk of ``prefill_chunk`` tokens, the fewest calls
    that can hold the ``prompt_len - hit`` computed tokens."""
    hit = max(0, min(int(hit), int(prompt_len) - 1))
    attended = (prompt_len * (prompt_len + 1) - hit * (hit + 1)) // 2
    chunk = int(serve["prefill_chunk"])
    decompressed = sum(min(end, prompt_len)
                       for end in range(hit + chunk, prompt_len + chunk, chunk))
    return (attended * prefill_query_flops_per_position(model)
            + decompressed * decompress_flops_per_position(model))
