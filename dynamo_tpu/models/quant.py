"""Int8 weight quantization (W8A8-dynamic) for the native JAX engine.

The reference's published baseline serves a *quantized-weights* checkpoint —
``neuralmagic/DeepSeek-R1-Distill-Llama-70B-FP8-dynamic``
(/root/reference/examples/llm/benchmarks/README.md) — with FP8 execution
delegated to vLLM.  This build owns its engine, so it owns quantization.
v5e has no fp8 MXU; its native low-precision path is int8 (~2x bf16 peak,
half the HBM bytes), so the TPU-first mapping of "FP8-dynamic" is:

- **weights**: symmetric per-output-channel int8, quantized once at load
  (``w_q = round(w / s)``, ``s = max|w| / 127`` along the input axis);
- **activations**: symmetric per-token (per-row) int8, quantized
  *dynamically* inside the forward (``a = max|x| / 127`` per row);
- **matmul**: native int8 x int8 ``dot_general`` accumulating int32 on the
  MXU, rescaled by ``a * s`` in f32 afterwards.

Measured on v5e (tools/quant_microbench.py): decode-geometry FFN chain
1.31 ms vs bf16's 2.26 ms (1.73x; int8 bytes stream at ~720 GB/s — at the
HBM roofline), prefill 360 vs 193 TFLOP/s (1.87x).  Weight-only int8
("w8a16", dequantize-then-bf16-matmul) measured *slower* than bf16 — XLA
materializes the dequantized weights instead of fusing the convert into the
dot — so it is deliberately not offered.

int32 accumulation is exact: the largest contraction here (F=28672 for 70B)
bounds |acc| <= 28672 * 127 * 127 ~ 4.6e8 < 2^31.

Quantized leaves live in the same params pytree: each weight ``name`` gains
a sibling ``name + "_scale"`` (f32, the weight's output-channel axis), and
the forward dispatches on the scale leaf's presence — no config plumbing
through model code.  Norms, biases and the MoE router (tiny,
routing-accuracy-critical) stay in bf16.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import numpy as np

# Weight leaves that quantize, with the axis that is the *input* (contracted)
# axis of the per-layer matmul — scales are taken over it, leaving the output
# channel axis.  Shapes are the stacked [L, ...] layouts of models/llama.py.
_LAYER_QUANT_AXES = {
    "wq": 1,  # [L, D, H*hd]   -> scale [L, H*hd]
    "wk": 1,  # [L, D, KV*hd]
    "wv": 1,  # [L, D, KV*hd]
    "wo": 1,  # [L, H*hd, D]   -> scale [L, D]
    "w_gate": 1,  # [L, D, F]
    "w_up": 1,  # [L, D, F]
    "w_down": 1,  # [L, F, D]
    "moe_gate": 2,  # [L, E, D, F] -> scale [L, E, F]
    "moe_up": 2,  # [L, E, D, F]
    "moe_down": 2,  # [L, E, F, D] -> scale [L, E, D]
    # Fused leaves (fuse_projections): same [L, in, out] layout, scales on
    # the concatenated output axis — quantize/dequantize must handle trees
    # in EITHER layout (engine params are fused by default single-shard).
    "wqkv": 1,  # [L, D, (H+2KV)*hd]
    "w_gateup": 1,  # [L, D, 2F]
}

# Top-level leaves.  embed [V, D] scales per vocab row (axis 1) — the same
# per-row scale serves both the lookup (dequantize the gathered row) and the
# tied lm_head (embed.T's output-channel axis IS the vocab row).
_TOP_QUANT_AXES = {"embed": 1, "lm_head": 0}  # lm_head [D, V] -> scale [V]


def quantize_array_np(w: np.ndarray, axis: int) -> Tuple[np.ndarray, np.ndarray]:
    """Symmetric per-channel int8 quantization in numpy (load path: keeps
    full-size f32 transients off the device and bounded to one tensor)."""
    wf = np.asarray(w, np.float32)
    amax = np.max(np.abs(wf), axis=axis)
    scale = np.maximum(amax / 127.0, 1e-12).astype(np.float32)
    # Clip before the int8 cast (matching ops/quant_matmul.quantize_rows):
    # rint(w/s) can land on ±127.0000x in float32 even though |w| <= amax
    # exactly, and an unclipped cast would wrap +127.x to -128.
    q = np.clip(
        np.rint(wf / np.expand_dims(scale, axis)), -127, 127
    ).astype(np.int8)
    return q, scale


def is_quantized(params: Dict[str, Any]) -> bool:
    return "embed_scale" in params or any(
        k.endswith("_scale") for k in params.get("layers", {})
    )


def quantize_params(params: Dict[str, Any]) -> Dict[str, Any]:
    """Quantize a loaded (bf16) params tree in place of a new tree.  Used
    when params were built outside the loader (tests, pre-loaded trees);
    checkpoints quantize tensor-at-a-time in models/loader.py instead."""
    import jax.numpy as jnp

    if is_quantized(params):
        return params
    out: Dict[str, Any] = {}
    for name, leaf in params.items():
        if name == "layers":
            continue
        axis = _TOP_QUANT_AXES.get(name)
        if axis is None:
            out[name] = leaf
        else:
            q, s = _quantize_jnp(leaf, axis)
            out[name], out[name + "_scale"] = q, s
    layers: Dict[str, Any] = {}
    for name, leaf in params["layers"].items():
        if name.startswith("lora_"):
            # Multi-LoRA device banks (llm/tenancy/lora.py) stay in float:
            # adapters are merge-free deltas applied AROUND the (possibly
            # int8) base projections, so quantizing them would re-calibrate
            # nothing and lose the low-rank factors' dynamic range — and
            # slots are rewritten at promotion time, which would invalidate
            # any per-slot scale immediately.
            layers[name] = leaf
            continue
        axis = _LAYER_QUANT_AXES.get(name)
        if axis is None:
            layers[name] = leaf
        else:
            q, s = _quantize_jnp(leaf, axis)
            layers[name], layers[name + "_scale"] = q, s
    out["layers"] = layers
    return out


def _quantize_jnp(w, axis: int):
    import jax.numpy as jnp

    wf = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(wf), axis=axis)
    scale = jnp.maximum(amax / 127.0, 1e-12).astype(jnp.float32)
    # Same clip-before-cast as quantize_array_np / quantize_rows: float32
    # round-off at exactly ±127 must not wrap to -128.
    q = jnp.clip(
        jnp.round(wf / jnp.expand_dims(scale, axis)), -127, 127
    ).astype(jnp.int8)
    return q, scale


def dequantize_params(params: Dict[str, Any], dtype="float32") -> Dict[str, Any]:
    """Exact f32/bf16 tree from a quantized one — the reference forward for
    golden-token quality gates compares against THIS (so the only difference
    under test is the engine's int8 execution, not the rounding of weights)."""
    import jax.numpy as jnp

    def deq(group: Dict[str, Any], axes: Dict[str, int]) -> Dict[str, Any]:
        out = {}
        for name, leaf in group.items():
            if name.endswith("_scale") or name == "layers":
                continue
            axis = axes.get(name)
            if axis is not None and name + "_scale" in group:
                s = jnp.expand_dims(group[name + "_scale"], axis)
                out[name] = (leaf.astype(jnp.float32) * s).astype(dtype)
            else:
                out[name] = leaf
        return out

    out = deq(params, _TOP_QUANT_AXES)
    out["layers"] = deq(params["layers"], _LAYER_QUANT_AXES)
    return out


def fuse_projections(params: Dict[str, Any]) -> Dict[str, Any]:
    """Concatenate q|k|v and gate|up along their output axes: 7 matmuls per
    dense layer become 5, and the fused dots share one activation
    quantization (decode launches fewer kernels per layer — measured on the
    per-layer overhead the r5 cost breakdown attributes).

    SINGLE-SHARD ONLY (engine applies it when mesh is None): a tp-sharded
    fused output axis would split across q/k/v segment boundaries and force
    resharding at the static split.  Works for quantized and bf16 trees;
    MoE experts keep their layout.  The forward dispatches on the fused
    leaf names (models/llama.py)."""
    import jax.numpy as jnp

    layers = dict(params["layers"])
    if "wq" in layers and "wqkv" not in layers:
        layers["wqkv"] = jnp.concatenate(
            [layers.pop("wq"), layers.pop("wk"), layers.pop("wv")], axis=-1
        )
        if "wq_scale" in layers:
            layers["wqkv_scale"] = jnp.concatenate(
                [layers.pop("wq_scale"), layers.pop("wk_scale"),
                 layers.pop("wv_scale")], axis=-1,
            )
        if "bq" in layers:
            layers["bqkv"] = jnp.concatenate(
                [layers.pop("bq"), layers.pop("bk"), layers.pop("bv")],
                axis=-1,
            )
    if "w_gate" in layers and "w_gateup" not in layers:
        layers["w_gateup"] = jnp.concatenate(
            [layers.pop("w_gate"), layers.pop("w_up")], axis=-1
        )
        if "w_gate_scale" in layers:
            layers["w_gateup_scale"] = jnp.concatenate(
                [layers.pop("w_gate_scale"), layers.pop("w_up_scale")],
                axis=-1,
            )
    return dict(params, layers=layers)


def init_params_quantized(config, key) -> Dict[str, Any]:
    """Random-init a quantized tree DIRECTLY in int8 — full-depth 8B bf16
    random-init would not fit single-chip HBM, which is the point of
    quantizing.  Distribution mimics init_params' N(0, 0.02): uniform int8
    (std ~73) with a constant scale of 0.02/73 per output channel.

    ONE jitted call draws every leaf from ``key``, each straight into its
    stored type: drawn eagerly, every ``randint`` first materialised its
    uint32 bits, four times the leaf (the 10.4 GiB of temporaries that
    dropped the Mixtral cell in PR 23).  The values are those the eager
    draw gave: same keys, same ops."""
    import jax

    return jax.jit(lambda k: _draw_quantized(config, k))(key)


def _draw_quantized(config, key) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(config.dtype)
    D, H, KV, hd, F = (
        config.hidden_size,
        config.num_heads,
        config.num_kv_heads,
        config.head_dim,
        config.intermediate_size,
    )
    L, V, E = config.num_layers, config.vocab_size, config.num_experts
    keys = iter(jax.random.split(key, 24))
    s0 = np.float32(0.02 / 73.0)

    def q(*shape):
        return jax.random.randint(next(keys), shape, -127, 128, dtype=jnp.int8)

    def s(*shape):
        return jnp.full(shape, s0, jnp.float32)

    layers: Dict[str, Any] = {
        "attn_norm": jnp.ones((L, D), dt),
        "wq": q(L, D, H * hd), "wq_scale": s(L, H * hd),
        "wk": q(L, D, KV * hd), "wk_scale": s(L, KV * hd),
        "wv": q(L, D, KV * hd), "wv_scale": s(L, KV * hd),
        "wo": q(L, H * hd, D), "wo_scale": s(L, D),
        "mlp_norm": jnp.ones((L, D), dt),
    }
    if config.qkv_bias:
        layers.update(
            {
                "bq": jnp.zeros((L, H * hd), dt),
                "bk": jnp.zeros((L, KV * hd), dt),
                "bv": jnp.zeros((L, KV * hd), dt),
            }
        )
    if config.is_moe:
        Fm = config.moe_intermediate_size or F
        layers.update(
            {
                "router": (jax.random.normal(next(keys), (L, D, E), jnp.float32) * 0.02).astype(dt),
                "moe_gate": q(L, E, D, Fm), "moe_gate_scale": s(L, E, Fm),
                "moe_up": q(L, E, D, Fm), "moe_up_scale": s(L, E, Fm),
                "moe_down": q(L, E, Fm, D), "moe_down_scale": s(L, E, D),
            }
        )
    else:
        layers.update(
            {
                "w_gate": q(L, D, F), "w_gate_scale": s(L, F),
                "w_up": q(L, D, F), "w_up_scale": s(L, F),
                "w_down": q(L, F, D), "w_down_scale": s(L, D),
            }
        )
    params: Dict[str, Any] = {
        "embed": q(V, D),
        "embed_scale": s(V),
        "layers": layers,
        "final_norm": jnp.ones((D,), dt),
    }
    if not config.tie_word_embeddings:
        params["lm_head"] = q(D, V)
        params["lm_head_scale"] = s(V)
    return params
