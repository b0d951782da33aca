"""Model architecture configs for the native JAX engine.

The reference ships no model code of its own — architecture is whatever the
wrapped engine (vLLM/sglang) loads from HF config.json; its
``ModelDeploymentCard`` (lib/llm/src/model_card/model.rs:15-201) carries only
serving metadata.  The TPU build executes models natively, so the architecture
config lives here, convertible from a HF ``config.json``.

Dense Llama-family (Llama 2/3, DeepSeek-R1-Distill-Llama) plus Mixtral-style
MoE fields.  All shapes chosen to map well onto the MXU: head_dim multiples of
128 where the checkpoints allow, bfloat16 activations by default.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass, field, replace
from typing import Any, Dict, Optional


# model_types of the latent family (models/deepseek_v32.py): DeepSeek-V3's
# block; deepseek_v32 adds the learned sparse selector.
LATENT_MODEL_TYPES = ("deepseek_v32", "deepseek_v3", "kimi_k2")
# model_types of the dense GQA block of models/llama.py (an absent key too).
LLAMA_MODEL_TYPES = ("llama", "qwen2", "mistral", "mixtral")
# The hybrid family (models/lfm2.py): a layer's mixer is a gated short
# convolution (lfm2_moe), a Mamba-2 scan (granitemoehybrid) or GQA attention;
# dense then sparse feed-forwards.  exaone_moe: attention in every layer, of
# which most keep a window of the last positions only (docs/k_exaone.md).
# kimi_linear: Kimi Delta Attention layers (models/kda.py, state in slots as
# Mamba-2's) among LATENT attention layers without rotation, whose pages are
# the latent family's (docs/kimi_linear.md).  jamba: Mamba-1 selective-scan
# layers (models/mamba1.py, state in the same slots) among GQA layers of ONE
# K/V head without rotation, a dense SwiGLU in every layer (docs/jamba.md).
HYBRID_MODEL_TYPES = ("lfm2_moe", "granitemoehybrid", "exaone_moe", "kimi_linear", "jamba")


@dataclass(frozen=True)
class ModelConfig:
    name: str
    vocab_size: int
    hidden_size: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    intermediate_size: int
    rope_theta: float = 500000.0
    rope_scaling: Optional[Dict[str, Any]] = None
    rms_norm_eps: float = 1e-5
    max_position: int = 131072
    tie_word_embeddings: bool = False
    dtype: str = "bfloat16"  # activation/weight dtype (string: jax-free config)
    # MoE (Mixtral / DeepSeek-V2-style shared+routed experts; 0 experts = dense)
    num_experts: int = 0
    num_experts_per_token: int = 0
    num_shared_experts: int = 0
    moe_intermediate_size: int = 0
    eos_token_ids: tuple = ()
    # Qwen2-style attention: q/k/v projections carry biases.
    qkv_bias: bool = False
    # The model family (models/family.py picks init, cache and forward from
    # it): "llama" covers the dense GQA block and the Mixtral-style MoE.
    model_type: str = "llama"
    # The latent family (deepseek_v32, and without a selector deepseek_v3 and
    # kimi_k2): latent attention (MLA), the sigmoid gate and, where
    # index_topk > 0, the learned sparse selector (DSA); all 0 for every
    # other family.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    first_k_dense_replace: int = 0
    n_group: int = 1
    topk_group: int = 1
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    # num_experts counts the routed experts HELD here (ids ep_rank *
    # num_experts ...); router_experts is the router's published width.
    router_experts: int = 0
    ep_size: int = 1
    ep_rank: int = 0
    # Added to the sum of the chosen scores before the gate's weights are
    # normalised by it (lfm2_moe: 1e-6); 0 adds nothing to the program.
    gate_norm_eps: float = 0.0
    # The hybrid family (lfm2_moe): each layer's mixer, "conv" or
    # "full_attention", and the short convolution's length (its state is the
    # conv_L_cache - 1 positions before a token); () and 0 elsewhere.
    layer_types: tuple = ()
    conv_L_cache: int = 0
    qk_norm: bool = False  # an RMSNorm a head over q and k before the rotation
    # granitemoehybrid (docs/granite_hybrid.md): four scalar multipliers, each
    # static and without an op where it is 1 (attention_multiplier None: the
    # softmax scale is head_dim ** -0.5); attention without rotation; the
    # gate's scoring ("sigmoid", or "softmax" over the chosen logits); a
    # shared SwiGLU of its own width beside the experts of every layer; the
    # Mamba-2 mixer's sizes (models/mamba2.py), all 0 elsewhere.
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: Optional[float] = None
    logits_scaling: float = 1.0
    use_rope: bool = True
    gate_scoring: str = "sigmoid"
    shared_intermediate_size: int = 0
    mamba_n_heads: int = 0
    mamba_d_head: int = 0
    mamba_d_state: int = 0
    mamba_d_conv: int = 0
    # exaone_moe (docs/k_exaone.md): a "sliding_attention" layer attends to
    # the last ``sliding_window`` positions (the query's own among them) and
    # keeps no more; the full-attention layers of a model that mixes both do
    # not rotate (``rope_full_attention`` False); a branch's OUTPUT is normed
    # (``post_norm``: h + norm(f(h))) where the other models norm its input.
    sliding_window: int = 0
    rope_full_attention: bool = True
    post_norm: bool = False
    # kimi_linear (docs/kimi_linear.md): a "kda" layer's heads, its head size
    # (keys and values alike) and its taps (models/kda.py), all 0 elsewhere;
    # its attention layers are latent (the MLA keys above with q_lora_rank 0:
    # one q projection) and ``mla_rope`` False: nothing is rotated.
    kda_n_heads: int = 0
    kda_head_dim: int = 0
    kda_conv: int = 0
    mla_rope: bool = True
    # jamba (docs/jamba.md): a "mamba1" layer's inner width is ``mamba_expand``
    # x hidden_size and its step size comes through a bottleneck of
    # ``mamba_dt_rank`` (models/mamba1.py; ``mamba_d_state`` / ``mamba_d_conv``
    # as above), both 0 elsewhere.
    mamba_expand: int = 0
    mamba_dt_rank: int = 0

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def q_size(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_size(self) -> int:
        return self.num_kv_heads * self.head_dim

    def with_overrides(self, **kw) -> "ModelConfig":
        return replace(self, **kw)

    @classmethod
    def from_hf_config(cls, cfg: Dict[str, Any], name: str = "") -> "ModelConfig":
        """Convert a HuggingFace ``config.json`` dict: llama/mixtral style
        (``LLAMA_MODEL_TYPES`` or no ``model_type``), one of
        ``LATENT_MODEL_TYPES`` or of ``HYBRID_MODEL_TYPES``.  Any other
        ``model_type`` is refused by name: its keys read as a dense llama
        would be another model under its name."""
        model_type = cfg.get("model_type")
        if model_type in LATENT_MODEL_TYPES:
            return cls._from_latent(cfg, name)
        if model_type == "granitemoehybrid":
            return cls._from_granite_hybrid(cfg, name)
        if model_type == "exaone_moe":
            return cls._from_exaone_moe(cfg, name)
        if model_type == "kimi_linear":
            return cls._from_kimi_linear(cfg, name)
        if model_type == "jamba":
            return cls._from_jamba(cfg, name)
        if model_type in HYBRID_MODEL_TYPES:
            return cls._from_hybrid(cfg, name)
        if model_type is not None and model_type not in LLAMA_MODEL_TYPES:
            raise ValueError(
                f"model_type {model_type!r} is not supported; known: "
                f"{sorted(LLAMA_MODEL_TYPES + LATENT_MODEL_TYPES + HYBRID_MODEL_TYPES)}"
            )
        num_heads = cfg["num_attention_heads"]
        head_dim = cfg.get("head_dim") or cfg["hidden_size"] // num_heads
        # Qwen2 checkpoints carry q/k/v biases but don't always write an
        # explicit attention_bias flag.
        qkv_bias = bool(
            cfg.get("attention_bias", cfg.get("model_type") == "qwen2")
        )
        eos = cfg.get("eos_token_id", ())
        if isinstance(eos, int):
            eos = (eos,)
        return cls(
            name=name or cfg.get("_name_or_path", "hf-model"),
            vocab_size=cfg["vocab_size"],
            hidden_size=cfg["hidden_size"],
            num_layers=cfg["num_hidden_layers"],
            num_heads=num_heads,
            num_kv_heads=cfg.get("num_key_value_heads", num_heads),
            head_dim=head_dim,
            intermediate_size=cfg["intermediate_size"],
            rope_theta=cfg.get("rope_theta", 10000.0),
            rope_scaling=cfg.get("rope_scaling"),
            rms_norm_eps=cfg.get("rms_norm_eps", 1e-5),
            max_position=cfg.get("max_position_embeddings", 8192),
            tie_word_embeddings=cfg.get("tie_word_embeddings", False),
            num_experts=cfg.get("num_local_experts", 0),
            num_experts_per_token=cfg.get("num_experts_per_tok", 0),
            moe_intermediate_size=cfg.get("intermediate_size", 0)
            if cfg.get("num_local_experts")
            else 0,
            eos_token_ids=tuple(eos),
            qkv_bias=qkv_bias,
        )

    @classmethod
    def _from_latent(cls, cfg: Dict[str, Any], name: str) -> "ModelConfig":
        """DeepSeek-V3's keys; with ``index_*`` (DeepSeek-V3.2-Exp) the
        selector's, without them ``index_topk`` 0: no selector.
        ``n_routed_experts`` counts the experts held here; a file cut to one
        chip's share states the published router width beside it
        (``n_routed_experts_published``) with ``ep_size``/``ep_rank``; the
        uncut config holds them all."""
        held = cfg["n_routed_experts"]
        ep_size = cfg.get("ep_size", 1)
        total = cfg.get("n_routed_experts_published", held * ep_size)
        if held * ep_size != total:
            raise ValueError(
                f"n_routed_experts {held} x ep_size {ep_size} is not the "
                f"router's width {total}"
            )
        ep_rank = cfg.get("ep_rank", 0)
        if not 0 <= ep_rank < ep_size:
            raise ValueError(f"ep_rank {ep_rank} outside ep_size {ep_size}")
        n_group, topk_group = cfg.get("n_group", 1), cfg.get("topk_group", 1)
        if total % n_group or topk_group > n_group:
            raise ValueError("n_group must divide the router's width")
        eos = cfg.get("eos_token_id", ())
        if isinstance(eos, int):
            eos = (eos,)
        return cls(
            name=name or cfg.get("_name_or_path", "hf-model"),
            model_type=cfg["model_type"],
            vocab_size=cfg["vocab_size"],
            hidden_size=cfg["hidden_size"],
            num_layers=cfg["num_hidden_layers"],
            num_heads=cfg["num_attention_heads"],
            num_kv_heads=cfg.get("num_key_value_heads", cfg["num_attention_heads"]),
            head_dim=cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
            intermediate_size=cfg["intermediate_size"],
            rope_theta=cfg.get("rope_theta", 10000.0),
            rope_scaling=cfg.get("rope_scaling"),
            rms_norm_eps=cfg.get("rms_norm_eps", 1e-6),
            max_position=cfg.get("max_position_embeddings", 163840),
            tie_word_embeddings=cfg.get("tie_word_embeddings", False),
            num_experts=held,
            num_experts_per_token=cfg["num_experts_per_tok"],
            num_shared_experts=cfg.get("n_shared_experts", 0),
            moe_intermediate_size=cfg["moe_intermediate_size"],
            eos_token_ids=tuple(eos),
            q_lora_rank=cfg["q_lora_rank"],
            kv_lora_rank=cfg["kv_lora_rank"],
            qk_nope_head_dim=cfg["qk_nope_head_dim"],
            qk_rope_head_dim=cfg["qk_rope_head_dim"],
            v_head_dim=cfg["v_head_dim"],
            index_n_heads=cfg.get("index_n_heads", 0),
            index_head_dim=cfg.get("index_head_dim", 0),
            index_topk=cfg.get("index_topk", 0),
            first_k_dense_replace=cfg["first_k_dense_replace"],
            n_group=n_group,
            topk_group=topk_group,
            routed_scaling_factor=cfg.get("routed_scaling_factor", 1.0),
            norm_topk_prob=cfg.get("norm_topk_prob", True),
            router_experts=total,
            ep_size=ep_size,
            ep_rank=ep_rank,
        )

    @classmethod
    def _from_hybrid(cls, cfg: Dict[str, Any], name: str) -> "ModelConfig":
        """``lfm2_moe``'s keys (docs/lfm2.md).  ``num_experts`` counts the
        experts held here, as ``_from_latent`` reads ``n_routed_experts``:
        the published file holds them all (``ep_size`` 1)."""
        L = cfg["num_hidden_layers"]
        layer_types = tuple(cfg["layer_types"])
        if len(layer_types) != L or set(layer_types) - {"conv", "full_attention"}:
            raise ValueError(
                f"layer_types must name {L} layers, each 'conv' or 'full_attention'")
        if cfg.get("conv_bias", False):
            raise ValueError("conv_bias true is not supported (the release has none)")
        if cfg.get("conv_L_cache", 3) < 2:
            raise ValueError("conv_L_cache must be at least 2")
        if not cfg.get("use_expert_bias", True):
            raise ValueError("use_expert_bias false is not supported (the release has it)")
        held = cfg["num_experts"]
        ep_size = cfg.get("ep_size", 1)
        total = cfg.get("num_experts_published", held * ep_size)
        ep_rank = cfg.get("ep_rank", 0)
        if held * ep_size != total or not 0 <= ep_rank < ep_size:
            raise ValueError(
                f"num_experts {held} x ep_size {ep_size} (ep_rank {ep_rank}) is not the "
                f"router's width {total}")
        num_heads = cfg["num_attention_heads"]
        eos = cfg.get("eos_token_id", ())
        if isinstance(eos, int):
            eos = (eos,)
        return cls(
            name=name or cfg.get("_name_or_path", "hf-model"),
            model_type=cfg["model_type"],
            vocab_size=cfg["vocab_size"],
            hidden_size=cfg["hidden_size"],
            num_layers=L,
            num_heads=num_heads,
            num_kv_heads=cfg.get("num_key_value_heads", num_heads),
            head_dim=cfg.get("head_dim") or cfg["hidden_size"] // num_heads,
            intermediate_size=cfg["intermediate_size"],
            rope_theta=cfg.get("rope_theta", 1000000.0),
            rms_norm_eps=cfg.get("norm_eps", 1e-5),
            max_position=cfg.get("max_position_embeddings", 128000),
            tie_word_embeddings=cfg.get("tie_word_embeddings", True),
            num_experts=held,
            num_experts_per_token=cfg["num_experts_per_tok"],
            moe_intermediate_size=cfg["moe_intermediate_size"],
            eos_token_ids=tuple(eos),
            first_k_dense_replace=cfg.get("num_dense_layers", 0),
            routed_scaling_factor=cfg.get("routed_scaling_factor", 1.0),
            norm_topk_prob=cfg.get("norm_topk_prob", True),
            router_experts=total,
            ep_size=ep_size,
            ep_rank=ep_rank,
            gate_norm_eps=1e-6,
            layer_types=layer_types,
            conv_L_cache=cfg.get("conv_L_cache", 3),
            qk_norm=True,
        )

    @classmethod
    def _from_granite_hybrid(cls, cfg: Dict[str, Any], name: str) -> "ModelConfig":
        """``granitemoehybrid``'s keys (docs/granite_hybrid.md).
        ``num_local_experts`` counts the experts held here; a file cut to one
        chip's share states the router's width beside it
        (``num_local_experts_published``) with ``ep_size``/``ep_rank``."""
        L = cfg["num_hidden_layers"]
        layer_types = tuple(cfg["layer_types"])
        if len(layer_types) != L or set(layer_types) - {"mamba", "attention"}:
            raise ValueError(f"layer_types must name {L} layers, each 'mamba' or 'attention'")
        for key, want in (("mamba_n_groups", 1), ("mamba_proj_bias", False),
                          ("mamba_conv_bias", True), ("attention_bias", False),
                          ("position_embedding_type", "nope"), ("hidden_act", "silu"),
                          ("normalization_function", "rmsnorm")):
            if cfg.get(key, want) != want:
                raise ValueError(f"{key} {cfg[key]!r} is not supported (the release has {want!r})")
        Hm, P = cfg["mamba_n_heads"], cfg["mamba_d_head"]
        if Hm * P != cfg.get("mamba_expand", 2) * cfg["hidden_size"]:
            raise ValueError("mamba_n_heads x mamba_d_head is not mamba_expand x hidden_size")
        held = cfg["num_local_experts"]
        ep_size = cfg.get("ep_size", 1)
        total = cfg.get("num_local_experts_published", held * ep_size)
        ep_rank = cfg.get("ep_rank", 0)
        if held * ep_size != total or not 0 <= ep_rank < ep_size:
            raise ValueError(
                f"num_local_experts {held} x ep_size {ep_size} (ep_rank {ep_rank}) is not the "
                f"router's width {total}")
        num_heads = cfg["num_attention_heads"]
        eos = cfg.get("eos_token_id", ())
        if isinstance(eos, int):
            eos = (eos,)
        return cls(
            name=name or cfg.get("_name_or_path", "hf-model"),
            model_type=cfg["model_type"],
            vocab_size=cfg["vocab_size"],
            hidden_size=cfg["hidden_size"],
            num_layers=L,
            num_heads=num_heads,
            num_kv_heads=cfg.get("num_key_value_heads", num_heads),
            head_dim=cfg.get("head_dim") or cfg["hidden_size"] // num_heads,
            intermediate_size=cfg["intermediate_size"],
            rope_theta=cfg.get("rope_theta", 10000.0),
            rms_norm_eps=cfg.get("rms_norm_eps", 1e-5),
            max_position=cfg.get("max_position_embeddings", 131072),
            tie_word_embeddings=cfg.get("tie_word_embeddings", True),
            num_experts=held,
            num_experts_per_token=cfg["num_experts_per_tok"],
            moe_intermediate_size=cfg["intermediate_size"],
            eos_token_ids=tuple(eos),
            router_experts=total,
            ep_size=ep_size,
            ep_rank=ep_rank,
            layer_types=layer_types,
            embedding_multiplier=float(cfg.get("embedding_multiplier", 1.0)),
            residual_multiplier=float(cfg.get("residual_multiplier", 1.0)),
            attention_multiplier=float(cfg["attention_multiplier"]),
            logits_scaling=float(cfg.get("logits_scaling", 1.0)),
            use_rope=False,
            gate_scoring="softmax",
            shared_intermediate_size=cfg.get("shared_intermediate_size", 0),
            mamba_n_heads=Hm,
            mamba_d_head=P,
            mamba_d_state=cfg["mamba_d_state"],
            mamba_d_conv=cfg["mamba_d_conv"],
        )

    @classmethod
    def _from_exaone_moe(cls, cfg: Dict[str, Any], name: str) -> "ModelConfig":
        """``exaone_moe``'s keys (docs/k_exaone.md).  ``num_experts`` counts
        the experts held here; a file cut to one chip's share states the
        router's width beside it (``num_experts_published``) with
        ``ep_size``/``ep_rank``.  The multi-token-prediction module
        (``num_nextn_predict_layers``) is a draft head and is not served."""
        L = cfg["num_hidden_layers"]
        layer_types = tuple(cfg["layer_types"])
        if len(layer_types) != L or set(layer_types) - {"sliding_attention", "full_attention"}:
            raise ValueError(
                f"layer_types must name {L} layers, each 'sliding_attention' or 'full_attention'")
        window = int(cfg.get("sliding_window") or 0)
        if "sliding_attention" in layer_types and window < 1:
            raise ValueError("sliding_window must be at least 1 where a layer keeps a window")
        mlp_types = tuple(cfg.get("mlp_layer_types") or ())
        dense = cfg.get("first_k_dense_replace", sum(t == "dense" for t in mlp_types))
        if mlp_types and mlp_types != ("dense",) * dense + ("sparse",) * (L - dense):
            raise ValueError(
                f"mlp_layer_types must be {dense} 'dense' layers (first_k_dense_replace) and "
                f"then 'sparse' ones over {L} layers")
        for key, want in (("n_group", 1), ("topk_group", 1), ("scoring_func", "sigmoid"),
                          ("hidden_act", "silu")):
            if cfg.get(key, want) != want:
                raise ValueError(f"{key} {cfg[key]!r} is not supported (the release has {want!r})")
        rope = cfg.get("rope_parameters") or {}
        if rope.get("rope_type", "default") != "default":
            raise ValueError(f"rope_type {rope['rope_type']!r} is not supported")
        held = cfg["num_experts"]
        ep_size = cfg.get("ep_size", 1)
        total = cfg.get("num_experts_published", held * ep_size)
        ep_rank = cfg.get("ep_rank", 0)
        if held * ep_size != total or not 0 <= ep_rank < ep_size:
            raise ValueError(
                f"num_experts {held} x ep_size {ep_size} (ep_rank {ep_rank}) is not the "
                f"router's width {total}")
        num_heads = cfg["num_attention_heads"]
        eos = cfg.get("eos_token_id", ())
        if isinstance(eos, int):
            eos = (eos,)
        return cls(
            name=name or cfg.get("_name_or_path", "hf-model"),
            model_type=cfg["model_type"],
            vocab_size=cfg["vocab_size"],
            hidden_size=cfg["hidden_size"],
            num_layers=L,
            num_heads=num_heads,
            num_kv_heads=cfg.get("num_key_value_heads", num_heads),
            head_dim=cfg.get("head_dim") or cfg["hidden_size"] // num_heads,
            intermediate_size=cfg["intermediate_size"],
            rope_theta=float(rope.get("rope_theta", cfg.get("rope_theta", 1000000.0))),
            rms_norm_eps=cfg.get("rms_norm_eps", 1e-5),
            max_position=cfg.get("max_position_embeddings", 262144),
            tie_word_embeddings=cfg.get("tie_word_embeddings", False),
            num_experts=held,
            num_experts_per_token=cfg["num_experts_per_tok"],
            num_shared_experts=cfg.get("num_shared_experts", 0),
            moe_intermediate_size=cfg["moe_intermediate_size"],
            eos_token_ids=tuple(eos),
            first_k_dense_replace=dense,
            routed_scaling_factor=float(cfg.get("routed_scaling_factor", 1.0)),
            norm_topk_prob=cfg.get("norm_topk_prob", True),
            router_experts=total,
            ep_size=ep_size,
            ep_rank=ep_rank,
            layer_types=layer_types,
            qk_norm=True,
            shared_intermediate_size=cfg.get("num_shared_experts", 0) * cfg["moe_intermediate_size"],
            sliding_window=window,
            rope_full_attention="sliding_attention" not in layer_types,
            post_norm=True,
        )

    @classmethod
    def _from_kimi_linear(cls, cfg: Dict[str, Any], name: str) -> "ModelConfig":
        """``kimi_linear``'s keys (docs/kimi_linear.md).  ``linear_attn_config``
        names the layers of each kind, counted from 1.  ``num_experts`` counts
        the experts held here; a file cut to one chip's share states the
        router's width beside it (``num_experts_published``) with
        ``ep_size``/``ep_rank``."""
        L = cfg["num_hidden_layers"]
        lin = cfg["linear_attn_config"]
        kda, full = set(lin["kda_layers"]), set(lin["full_attn_layers"])
        if kda & full or kda | full != set(range(1, L + 1)):
            raise ValueError(
                f"linear_attn_config: kda_layers and full_attn_layers must name each of the "
                f"layers 1..{L} once")
        for key, want, why in (
                ("mla_use_nope", True, "the latent layers rotate nothing"),
                ("q_lora_rank", None, "one q projection, no compressed query"),
                ("num_expert_group", 1, "the gate chooses in one group"),
                ("topk_group", 1, "the gate chooses in one group"),
                ("num_nextn_predict_layers", 0, "a draft head is not served"),
                ("moe_router_activation_func", "sigmoid", "the gate scores with a sigmoid"),
                ("moe_layer_freq", 1, "every layer past the dense ones has experts"),
                ("rope_scaling", None, "nothing is rotated"),
                ("hidden_act", "silu", "SwiGLU")):
            if cfg.get(key, want) != want:
                raise ValueError(f"{key} {cfg[key]!r} is not supported ({why}: {want!r})")
        held = cfg["num_experts"]
        ep_size = cfg.get("ep_size", 1)
        total = cfg.get("num_experts_published", held * ep_size)
        ep_rank = cfg.get("ep_rank", 0)
        if held * ep_size != total or not 0 <= ep_rank < ep_size:
            raise ValueError(
                f"num_experts {held} x ep_size {ep_size} (ep_rank {ep_rank}) is not the "
                f"router's width {total}")
        eos = cfg.get("eos_token_id", ())
        if isinstance(eos, int):
            eos = (eos,)
        shared = cfg.get("num_shared_experts", 0)
        return cls(
            name=name or cfg.get("_name_or_path", "hf-model"),
            model_type=cfg["model_type"],
            vocab_size=cfg["vocab_size"],
            hidden_size=cfg["hidden_size"],
            num_layers=L,
            num_heads=cfg["num_attention_heads"],
            num_kv_heads=cfg.get("num_key_value_heads", cfg["num_attention_heads"]),
            head_dim=cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
            intermediate_size=cfg["intermediate_size"],
            rope_theta=float(cfg.get("rope_theta", 10000.0)),
            rms_norm_eps=cfg.get("rms_norm_eps", 1e-5),
            max_position=cfg.get("model_max_length", cfg.get("max_position_embeddings", 1048576)),
            tie_word_embeddings=cfg.get("tie_word_embeddings", False),
            num_experts=held,
            num_experts_per_token=cfg["num_experts_per_token"],
            num_shared_experts=shared,
            moe_intermediate_size=cfg["moe_intermediate_size"],
            eos_token_ids=tuple(eos),
            kv_lora_rank=cfg["kv_lora_rank"],
            qk_nope_head_dim=cfg["qk_nope_head_dim"],
            qk_rope_head_dim=cfg["qk_rope_head_dim"],
            v_head_dim=cfg["v_head_dim"],
            first_k_dense_replace=cfg.get("first_k_dense_replace", 0),
            routed_scaling_factor=float(cfg.get("routed_scaling_factor", 1.0)),
            norm_topk_prob=cfg.get("moe_renormalize", True),
            router_experts=total,
            ep_size=ep_size,
            ep_rank=ep_rank,
            layer_types=tuple("kda" if l in kda else "full_attention" for l in range(1, L + 1)),
            use_rope=False,
            shared_intermediate_size=shared * cfg["moe_intermediate_size"],
            kda_n_heads=lin["num_heads"],
            kda_head_dim=lin["head_dim"],
            kda_conv=lin["short_conv_kernel_size"],
            mla_rope=False,
        )

    @classmethod
    def _from_jamba(cls, cfg: Dict[str, Any], name: str) -> "ModelConfig":
        """``jamba``'s keys (docs/jamba.md).  Layer l is attention where
        ``l % attn_layer_period == attn_layer_offset`` and Mamba-1 elsewhere;
        with ``num_experts`` 1 every layer's feed-forward is the dense SwiGLU
        (``expert_layer_*`` then say nothing)."""
        L = cfg["num_hidden_layers"]
        period, offset = cfg["attn_layer_period"], cfg["attn_layer_offset"]
        if period < 1 or not 0 <= offset < period:
            raise ValueError(f"attn_layer_offset {offset} outside attn_layer_period {period}")
        for key, want, why in (
                ("num_experts", 1, "experts beside Mamba-1 layers are not served"),
                ("mamba_proj_bias", False, "the mixer's projections carry no bias"),
                ("mamba_conv_bias", True, "the taps carry a bias"),
                ("sliding_window", None, "the attention layers keep every position"),
                ("hidden_act", "silu", "SwiGLU")):
            if cfg.get(key, want) != want:
                raise ValueError(f"{key} {cfg[key]!r} is not supported ({why}: {want!r})")
        D = cfg["hidden_size"]
        rank = cfg.get("mamba_dt_rank", "auto")
        num_heads = cfg["num_attention_heads"]
        eos = cfg.get("eos_token_id", ())
        if isinstance(eos, int):
            eos = (eos,)
        return cls(
            name=name or cfg.get("_name_or_path", "hf-model"),
            model_type=cfg["model_type"],
            vocab_size=cfg["vocab_size"],
            hidden_size=D,
            num_layers=L,
            num_heads=num_heads,
            num_kv_heads=cfg.get("num_key_value_heads", num_heads),
            head_dim=cfg.get("head_dim") or D // num_heads,
            intermediate_size=cfg["intermediate_size"],
            rms_norm_eps=cfg.get("rms_norm_eps", 1e-6),
            max_position=cfg.get("max_position_embeddings", 262144),
            tie_word_embeddings=cfg.get("tie_word_embeddings", True),
            eos_token_ids=tuple(eos),
            first_k_dense_replace=L,
            layer_types=tuple(
                "full_attention" if l % period == offset else "mamba1" for l in range(L)),
            use_rope=False,
            mamba_d_state=cfg.get("mamba_d_state", 16),
            mamba_d_conv=cfg.get("mamba_d_conv", 4),
            mamba_expand=cfg.get("mamba_expand", 2),
            mamba_dt_rank=-(-D // 16) if rank == "auto" else rank,
        )

    @classmethod
    def from_local_path(cls, path: str, name: str = "") -> "ModelConfig":
        with open(os.path.join(path, "config.json")) as f:
            return cls.from_hf_config(json.load(f), name=name or os.path.basename(path))


_REGISTRY: Dict[str, ModelConfig] = {}


def register_config(cfg: ModelConfig) -> ModelConfig:
    _REGISTRY[cfg.name] = cfg
    return cfg


def get_config(name: str) -> ModelConfig:
    if name in _REGISTRY:
        return _REGISTRY[name]
    if os.path.isdir(name):
        return ModelConfig.from_local_path(name)
    raise KeyError(f"unknown model config: {name!r}; known: {sorted(_REGISTRY)}")


# ---------------------------------------------------------------------------
# Presets.  llama-3.1-8b matches DeepSeek-R1-Distill-Llama-8B (the north-star
# model, BASELINE.md): same architecture, distilled weights.
# ---------------------------------------------------------------------------

register_config(
    ModelConfig(
        name="llama-3.1-8b",
        vocab_size=128256,
        hidden_size=4096,
        num_layers=32,
        num_heads=32,
        num_kv_heads=8,
        head_dim=128,
        intermediate_size=14336,
        rope_theta=500000.0,
        eos_token_ids=(128001, 128008, 128009),
    )
)

register_config(
    ModelConfig(
        name="llama-3.1-70b",
        vocab_size=128256,
        hidden_size=8192,
        num_layers=80,
        num_heads=64,
        num_kv_heads=8,
        head_dim=128,
        intermediate_size=28672,
        rope_theta=500000.0,
        eos_token_ids=(128001, 128008, 128009),
    )
)

register_config(
    ModelConfig(
        name="mixtral-8x7b",
        vocab_size=32000,
        hidden_size=4096,
        num_layers=32,
        num_heads=32,
        num_kv_heads=8,
        head_dim=128,
        intermediate_size=14336,
        rope_theta=1e6,
        num_experts=8,
        num_experts_per_token=2,
        moe_intermediate_size=14336,
        eos_token_ids=(2,),
    )
)

register_config(
    ModelConfig(
        name="qwen2.5-7b",
        vocab_size=152064,
        hidden_size=3584,
        num_layers=28,
        num_heads=28,
        num_kv_heads=4,
        head_dim=128,
        intermediate_size=18944,
        rope_theta=1e6,
        tie_word_embeddings=False,
        qkv_bias=True,
        eos_token_ids=(151643, 151645),
    )
)

# Tiny configs for CPU tests / CI — shapes still MXU-friendly multiples.
register_config(
    ModelConfig(
        name="debug-tiny",
        vocab_size=256,
        hidden_size=64,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        intermediate_size=128,
        rope_theta=10000.0,
        max_position=2048,
        eos_token_ids=(0,),
    )
)

register_config(
    ModelConfig(
        name="debug-tiny-moe",
        vocab_size=256,
        hidden_size=64,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        intermediate_size=128,
        rope_theta=10000.0,
        max_position=2048,
        num_experts=4,
        num_experts_per_token=2,
        moe_intermediate_size=128,
        eos_token_ids=(0,),
    )
)
