"""Mesh + sharding rules (the scaling-book recipe: pick a mesh, annotate
shardings, let XLA insert collectives).

Axes:
- "dp"  — data parallel: distinct batch rows (request-level; the serving tier
          usually does DP via multiple engine replicas instead, matching the
          reference's replica model, but in-engine dp is supported).
- "tp"  — tensor parallel: attention heads / FFN hidden / vocab. Collectives
          (all-reduce after wo/w_down, all-gather for logits) ride ICI.
- "ep"  — expert parallel for MoE: experts dimension. Folded onto "tp" when
          not given its own axis.

KV cache shards over "tp" on the kv_heads axis, so paged attention is fully
local per chip (each chip owns its heads' cache); block tables/ids are
replicated host metadata.

Reference counterpart: `--tensor-parallel-size` and friends
(launch/dynamo-run/src/flags.rs:63; SURVEY.md §2.7) — there they configure an
external engine; here they parameterise the mesh.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.config import ModelConfig


@dataclass(frozen=True)
class MeshConfig:
    dp: int = 1
    tp: int = 1
    ep: int = 1  # expert parallel; 1 = fold experts onto tp
    sp: int = 1  # sequence parallel (ring attention, long-context prefill)

    @property
    def num_devices(self) -> int:
        return self.dp * self.tp * self.ep * self.sp


def make_mesh(
    cfg: MeshConfig, devices: Optional[Sequence[jax.Device]] = None
) -> Mesh:
    n = cfg.num_devices
    if devices is None:
        # The default backend's devices and no other: on the CPU backend
        # these are the virtual devices tests and dry runs ask for
        # (--xla_force_host_platform_device_count); a TPU backend with too
        # few chips raises rather than quietly meshing CPU devices.
        devices = jax.devices()
    if len(devices) < n:
        raise ValueError(
            f"need {n} {jax.default_backend()} devices for {cfg}, "
            f"have {len(devices)}"
        )
    # sp adjacent to tp: K/V ring hops between sp neighbors stay one ICI
    # hop for standard torus topologies.
    grid = np.array(devices[:n]).reshape(cfg.dp, cfg.ep, cfg.sp, cfg.tp)
    return Mesh(grid, ("dp", "ep", "sp", "tp"))


def param_pspecs(config: ModelConfig) -> Any:
    """PartitionSpec tree matching models.llama.init_params structure.

    Column-parallel (wq/wk/wv/w_gate/w_up): shard output features on tp.
    Row-parallel (wo/w_down): shard input features on tp → XLA all-reduces
    the partial sums.  Vocab shards on tp for embed and lm_head.  MoE experts
    shard on ep (plus tp on the expert FFN hidden dim).
    """
    layers = {
        "attn_norm": P(),
        "wq": P(None, None, "tp"),
        "wk": P(None, None, "tp"),
        "wv": P(None, None, "tp"),
        "wo": P(None, "tp", None),
        # Qwen2 attention biases: shard with their projections' outputs.
        "bq": P(None, "tp"),
        "bk": P(None, "tp"),
        "bv": P(None, "tp"),
        "mlp_norm": P(),
        # dense FFN
        "w_gate": P(None, None, "tp"),
        "w_up": P(None, None, "tp"),
        "w_down": P(None, "tp", None),
        # MoE
        "router": P(),
        "moe_gate": P(None, "ep", None, "tp"),
        "moe_up": P(None, "ep", None, "tp"),
        "moe_down": P(None, "ep", "tp", None),
        # int8 weight-quant scales (models/quant.py): a scale lives on its
        # weight's OUTPUT-channel axis and shards with it; row-parallel
        # weights (wo/w_down/moe_down) have replicated outputs.
        "wq_scale": P(None, "tp"),
        "wk_scale": P(None, "tp"),
        "wv_scale": P(None, "tp"),
        "wo_scale": P(),
        "w_gate_scale": P(None, "tp"),
        "w_up_scale": P(None, "tp"),
        "w_down_scale": P(),
        "moe_gate_scale": P(None, "ep", "tp"),
        "moe_up_scale": P(None, "ep", "tp"),
        "moe_down_scale": P(None, "ep", None),
    }
    specs = {
        "embed": P("tp", None),
        "embed_scale": P("tp"),  # per-vocab-row, shards with embed
        "layers": layers,
        "final_norm": P(),
        "lm_head": P(None, "tp"),
        "lm_head_scale": P("tp"),
    }
    return specs


def pages_pspec() -> P:
    """PagedKVCache slabs [L, pages, page_size, 2*kv_heads, head_dim]: the
    combined K/V head axis shards on tp (tp | kv_heads keeps each K/V pair
    on one shard)."""
    return P(None, None, None, "tp", None)


def _trim(spec: P, ndim: int) -> P:
    parts = list(spec) + [None] * ndim
    return P(*parts[:ndim])


def _spec_for_path(specs: Any, path: Sequence[Any]) -> P:
    """Walk a spec tree along a tree_map_with_path key path; P() if absent."""
    spec = specs
    for key in path:
        # DictKey.key / SequenceKey.idx / GetAttrKey.name (namedtuples)
        k = getattr(key, "key", None)
        if k is None:
            k = getattr(key, "idx", None)
        if k is None:
            k = getattr(key, "name", None)
        if isinstance(spec, dict):
            spec = spec.get(k, P())
        elif isinstance(spec, tuple) and not isinstance(spec, P):
            spec = getattr(spec, k) if isinstance(k, str) else spec[k]
    return spec if isinstance(spec, P) else P()


def sharding_tree(tree: Any, specs: Any, mesh: Mesh) -> Any:
    """NamedSharding pytree matching ``tree``'s structure (for use as jit
    in_shardings/out_shardings), pruning spec entries the tree lacks (e.g.
    MoE specs on a dense model, lm_head on tied embeddings)."""

    def to_sharding(path, leaf):
        spec = _spec_for_path(specs, path)
        return NamedSharding(mesh, _trim(spec, getattr(leaf, "ndim", 0)))

    return jax.tree_util.tree_map_with_path(to_sharding, tree)


def shard_tree(tree: Any, specs: Any, mesh: Mesh) -> Any:
    """Place a pytree's arrays onto the mesh per the spec tree.

    Multi-process: the mesh spans devices this process cannot address, so
    each leaf is assembled from the full per-host copy via
    ``jax.make_array_from_callback`` (every process holds identical host
    values — same init seed / same checkpoint)."""
    shardings = sharding_tree(tree, specs, mesh)
    if jax.process_count() > 1:
        from .distributed import global_array

        return jax.tree_util.tree_map(global_array, tree, shardings)
    return jax.tree_util.tree_map(jax.device_put, tree, shardings)
