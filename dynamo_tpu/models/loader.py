"""HF safetensors checkpoint → stacked params pytree.

The reference's model loading happens inside vLLM/sglang; its own code only
resolves paths + metadata (ModelDeploymentCard, lib/llm/src/model_card/
create.rs).  Here we load weights natively: HF llama/mixtral layouts map onto
the stacked-[L, ...] tree that models/llama.py consumes (torch [out, in]
linears transpose to [in, out] matmul layout).

Memory notes: tensors stream from safetensors one at a time; per-layer
tensors accumulate as numpy then stack.  The tree this returns is STAGED ON
THE HOST (numpy arrays; the GGUF int8 branch: CPU-device arrays) — no
accelerator holds a whole tensor.  The engine places it: shard by shard via
parallel.shard_tree with a mesh, onto the one device without.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List

import numpy as np

from .config import ModelConfig

_LAYER_MAP = {
    "input_layernorm.weight": ("attn_norm", False),
    # Qwen2-style attention biases ([out] vectors, no transpose).
    "self_attn.q_proj.bias": ("bq", False),
    "self_attn.k_proj.bias": ("bk", False),
    "self_attn.v_proj.bias": ("bv", False),
    "self_attn.q_proj.weight": ("wq", True),
    "self_attn.k_proj.weight": ("wk", True),
    "self_attn.v_proj.weight": ("wv", True),
    "self_attn.o_proj.weight": ("wo", True),
    "post_attention_layernorm.weight": ("mlp_norm", False),
    "mlp.gate_proj.weight": ("w_gate", True),
    "mlp.up_proj.weight": ("w_up", True),
    "mlp.down_proj.weight": ("w_down", True),
    # Mixtral MoE router: torch [E, D] → transpose → router [D, E].
    "block_sparse_moe.gate.weight": ("router", True),
}

# Mixtral expert sub-keys: block_sparse_moe.experts.{e}.{w}.weight.
# w1 = gate proj [F, D], w2 = down proj [D, F], w3 = up proj [F, D];
# all transpose into the [in, out] matmul layout moe_mlp consumes
# (models/moe.py: moe_gate/moe_up [E, D, F], moe_down [E, F, D]).
_EXPERT_MAP = {"w1": "moe_gate", "w2": "moe_down", "w3": "moe_up"}


def _iter_safetensors(path: str):
    from safetensors import safe_open

    files = sorted(
        f for f in os.listdir(path) if f.endswith(".safetensors")
    )
    if not files:
        raise FileNotFoundError(f"no .safetensors files under {path}")
    for fname in files:
        with safe_open(os.path.join(path, fname), framework="numpy") as f:
            for key in f.keys():
                yield key, f.get_tensor(key)


def load_params(
    config: ModelConfig, path: str, dtype: Any = None, quant: str | None = None
) -> Dict[str, Any]:
    """Load a HF llama-family checkpoint directory into the params tree.
    A ``.gguf`` path loads through the GGUF container instead.

    ``quant="int8"`` quantizes weight tensors ONE AT A TIME on the host
    (models/quant.py axes) before they reach the device — a full-depth 8B
    checkpoint in bf16 (~16GB) would not fit single-chip HBM, which is the
    point of quantizing.  Matches the reference baseline's quantized-weights
    workload (examples/llm/benchmarks/README.md: ``...-FP8-dynamic``)."""
    import jax.numpy as jnp

    if quant not in (None, "int8"):
        raise ValueError(f"unknown weight quant {quant!r} (supported: int8)")
    if path.endswith(".gguf"):
        from .gguf import load_params_gguf
        from .quant import quantize_params

        if not quant:
            return load_params_gguf(config, path, dtype)
        # Quantizing: keep the full bf16 tree OFF the accelerator — load and
        # quantize on the host CPU device, then move only the int8 tree over
        # (the HF branch below gets the same guarantee tensor-at-a-time).
        import jax

        cpu = jax.devices("cpu")[0]
        with jax.default_device(cpu):
            return quantize_params(load_params_gguf(config, path, dtype))

    from .quant import _LAYER_QUANT_AXES, _TOP_QUANT_AXES, quantize_array_np

    dt = jnp.dtype(dtype or config.dtype)
    L, E = config.num_layers, config.num_experts
    per_layer: Dict[str, List[Any]] = {}
    # Per-layer quantization scales, same [L] slots as per_layer.
    per_scale: Dict[str, List[Any]] = {}
    # MoE expert tensors: name → [L][E] grid, stacked to [L, E, ...] at the end.
    per_expert: Dict[str, List[List[Any]]] = {}
    per_expert_scale: Dict[str, List[List[Any]]] = {}
    params: Dict[str, Any] = {"layers": {}}

    def put_layer(name: str, idx: int, value: np.ndarray) -> None:
        if quant and name in _LAYER_QUANT_AXES:
            # Stacked axis is 0, so the per-tensor quant axis is one less.
            q, s = quantize_array_np(value, _LAYER_QUANT_AXES[name] - 1)
            per_scale.setdefault(name, [None] * L)[idx] = s
            value = q
        per_layer.setdefault(name, [None] * L)[idx] = value

    def put_top(name: str, value: np.ndarray) -> None:
        if quant and name in _TOP_QUANT_AXES:
            q, s = quantize_array_np(value, _TOP_QUANT_AXES[name])
            params[name] = np.asarray(q)
            params[name + "_scale"] = np.asarray(s)
        else:
            params[name] = np.asarray(value, dt)

    for key, tensor in _iter_safetensors(path):
        if key == "model.embed_tokens.weight":
            put_top("embed", tensor)
        elif key == "model.norm.weight":
            params["final_norm"] = np.asarray(tensor, dt)
        elif key == "lm_head.weight":
            put_top("lm_head", tensor.T)
        elif key.startswith("model.layers."):
            rest = key[len("model.layers.") :]
            idx_str, sub = rest.split(".", 1)
            if sub.startswith("block_sparse_moe.experts."):
                if not config.is_moe:
                    raise ValueError(
                        f"config {config.name!r} is dense but checkpoint has "
                        f"MoE expert tensors ({key})"
                    )
                e_rest = sub[len("block_sparse_moe.experts.") :]
                e_str, w_key = e_rest.split(".", 1)
                name = _EXPERT_MAP.get(w_key.removesuffix(".weight"))
                if name is None:
                    continue
                value = tensor.T
                if quant and name in _LAYER_QUANT_AXES:
                    # Stacked axes are [L, E], so quant axis is two less.
                    q, s = quantize_array_np(value, _LAYER_QUANT_AXES[name] - 2)
                    sgrid = per_expert_scale.setdefault(
                        name, [[None] * E for _ in range(L)]
                    )
                    sgrid[int(idx_str)][int(e_str)] = s
                    value = q
                grid = per_expert.setdefault(name, [[None] * E for _ in range(L)])
                grid[int(idx_str)][int(e_str)] = value
                continue
            mapped = _LAYER_MAP.get(sub)
            if mapped is None:
                continue  # rotary inv_freq buffers etc.
            name, transpose = mapped
            put_layer(name, int(idx_str), tensor.T if transpose else tensor)

    for name, tensors in per_layer.items():
        missing = [i for i, t in enumerate(tensors) if t is None]
        if missing:
            raise ValueError(f"checkpoint missing {name} for layers {missing}")
        stacked = np.stack(tensors)
        if name in per_scale:
            params["layers"][name] = np.asarray(stacked)  # int8 as-is
            params["layers"][name + "_scale"] = np.asarray(
                np.stack(per_scale[name])
            )
        else:
            params["layers"][name] = np.asarray(stacked, dt)

    for name, grid in per_expert.items():
        missing = [
            (i, e) for i in range(L) for e in range(E) if grid[i][e] is None
        ]
        if missing:
            raise ValueError(f"checkpoint missing {name} for (layer, expert) {missing[:8]}")
        stacked = np.stack([np.stack(row) for row in grid])
        if name in per_expert_scale:
            params["layers"][name] = np.asarray(stacked)  # int8 as-is
            params["layers"][name + "_scale"] = np.asarray(
                np.stack([np.stack(row) for row in per_expert_scale[name]])
            )
        else:
            params["layers"][name] = np.asarray(stacked, dt)

    if config.is_moe:
        # Fail at load, not at first forward's KeyError (a dense checkpoint
        # loaded into an MoE config would otherwise silently drop experts).
        needed = {"router", "moe_gate", "moe_up", "moe_down"}
        absent = needed - set(params["layers"])
        if absent:
            raise ValueError(
                f"config {config.name!r} is MoE ({E} experts) but checkpoint is "
                f"missing {sorted(absent)} (block_sparse_moe.gate/experts tensors)"
            )
    if "embed" not in params:
        raise ValueError("checkpoint has no model.embed_tokens.weight")
    if config.tie_word_embeddings:
        params.pop("lm_head", None)
        params.pop("lm_head_scale", None)
    return params


def save_params_hf(params: Dict[str, Any], path: str) -> None:
    """Write params back out in HF naming (testing/interchange helper)."""
    from safetensors.numpy import save_file

    os.makedirs(path, exist_ok=True)
    # NB: safetensors silently mis-serialises non-contiguous arrays — every
    # tensor (especially transposes) must be made contiguous first.
    out: Dict[str, np.ndarray] = {
        "model.embed_tokens.weight": np.ascontiguousarray(params["embed"]),
        "model.norm.weight": np.ascontiguousarray(params["final_norm"]),
    }
    if "lm_head" in params:
        out["lm_head.weight"] = np.ascontiguousarray(np.asarray(params["lm_head"]).T)
    inv = {v[0]: (k, v[1]) for k, v in _LAYER_MAP.items()}
    inv_expert = {v: k for k, v in _EXPERT_MAP.items()}
    for name, stacked in params["layers"].items():
        arr = np.asarray(stacked)
        if name in inv_expert:
            hf_w = inv_expert[name]
            for i in range(arr.shape[0]):
                for e in range(arr.shape[1]):
                    out[
                        f"model.layers.{i}.block_sparse_moe.experts.{e}.{hf_w}.weight"
                    ] = np.ascontiguousarray(arr[i, e].T)
            continue
        if name not in inv:
            continue
        hf_sub, transpose = inv[name]
        for i in range(arr.shape[0]):
            t = arr[i].T if transpose else arr[i]
            out[f"model.layers.{i}.{hf_sub}"] = np.ascontiguousarray(t)
    save_file(out, os.path.join(path, "model.safetensors"))
