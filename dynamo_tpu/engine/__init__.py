"""The native TPU engine: paged KV block manager, continuous-batching
scheduler, and the jitted device step loop (SURVEY.md §7 stage 4 — the piece
the reference outsources to vLLM/sglang)."""

from .config import (  # noqa: F401
    EngineConfig,
    LoraConfig,
    QosSchedConfig,
    SpecDecodeConfig,
)
from .kv_manager import KvBlockManager  # noqa: F401
from .scheduler import Scheduler, SequenceState  # noqa: F401


def build_tpu_engine(args):
    """CLI factory (``run out=tpu`` — reference: launch/dynamo-run engine
    selection, lib.rs:198-453).  Imports jax lazily."""
    from .engine import TpuEngine
    from .phases import SetupAccount

    # What came before this line is the process's ``import`` (engine/phases.py).
    setup = SetupAccount(from_process_start=True)
    arch = getattr(args, "arch", None)
    checkpoint = getattr(args, "checkpoint", None)
    model_config_path = getattr(args, "model_config", None)
    if checkpoint:
        # Resolve BEFORE anything else, like the reference's dynamo-run (launch/
        # dynamo-run/src/lib.rs:125-130): local dirs pass through, names/repo-ids
        # acquire via models/hub.py (HF snapshot or the pre-staged offline cache).
        from ..models.hub import resolve_model

        args.checkpoint_source = checkpoint  # pre-resolution spec (registry)
        checkpoint = resolve_model(checkpoint)
        args.checkpoint = checkpoint  # tokenizer discovery reads it too
    if (
        checkpoint
        and not arch
        and not checkpoint.endswith(".gguf")
        and not model_config_path
    ):
        # The checkpoint's own config.json is the architecture source of truth (as the MDC).
        from ..models.config import ModelConfig, register_config

        arch = register_config(ModelConfig.from_local_path(checkpoint)).name
    if checkpoint and checkpoint.endswith(".gguf") and not arch:
        # GGUF carries its own architecture metadata (reference: lib/llm/src/gguf/*).
        from ..models.config import register_config
        from ..models.gguf import GGUFFile

        arch = register_config(GGUFFile(checkpoint).to_model_config()).name
    if model_config_path:
        import json

        from ..models.config import ModelConfig, register_config

        with open(model_config_path) as f:
            cfg_json = json.load(f)
        arch = register_config(
            ModelConfig.from_hf_config(cfg_json, name=cfg_json.get("_name", "custom"))
        ).name

    lora_section, lora_adapters = _lora_section(args)
    cfg = EngineConfig(
        model=arch or "debug-tiny",
        block_size=getattr(args, "block_size", 16),
        num_blocks=getattr(args, "num_blocks", 256),
        max_batch=getattr(args, "max_batch", 8),
        max_model_len=getattr(args, "max_model_len", 1024),
        prefill_chunk=getattr(args, "prefill_chunk", 512),
        tp=getattr(args, "tp", 1),
        dp=getattr(args, "dp", 1),
        ep=getattr(args, "ep", 1),
        sp=getattr(args, "sp", 1),
        sp_prefill_min=getattr(args, "sp_prefill_min", 1024),
        dtype=getattr(args, "dtype", "bfloat16"),
        decode_steps=getattr(args, "decode_steps", 4),
        pipeline_depth=getattr(args, "pipeline_depth", 2),
        cache_dtype=getattr(args, "cache_dtype", None),
        kv_scale=getattr(args, "kv_scale", 1.0),
        checkpoint_path=getattr(args, "checkpoint", None),
        attn_impl=getattr(args, "attn_impl", "auto"),
        decode_kernel=getattr(args, "decode_kernel", "auto"),
        prefill_kernel=getattr(args, "prefill_kernel", "auto"),
        weight_quant=getattr(args, "weight_quant", None),
        host_cache_bytes=(getattr(args, "host_cache_mb", 0) or 0) << 20,
        disk_cache_bytes=(getattr(args, "disk_cache_mb", 0) or 0) << 20,
        disk_cache_dir=getattr(args, "disk_cache_dir", None),
        object_store_bytes=(getattr(args, "object_store_mb", 0) or 0) << 20,
        object_store_dir=getattr(args, "object_store_dir", None),
        spec_decode=_spec_decode_section(args),
        lora=lora_section,
        qos=_qos_sched_section(),
    )
    if getattr(args, "kv_pull_mb", None) is not None:
        cfg.kv_pull_max_bytes = int(args.kv_pull_mb) << 20
    engine = TpuEngine(cfg, setup=setup)
    _load_adapters(engine, lora_adapters, getattr(args, "model", None))
    return engine


def _spec_decode_section(args) -> dict:
    """Layered spec_decode section: RuntimeConfig (file/DYN_SPEC_DECODE__*
    env) under explicit --spec-* CLI flags."""
    from ..runtime.config import RuntimeConfig

    section = dict(RuntimeConfig.from_layers().spec_decode)
    if getattr(args, "spec_decode", None) is not None:
        section["enable"] = bool(args.spec_decode)
    if getattr(args, "spec_k", None) is not None:
        section["k"] = int(args.spec_k)
    if getattr(args, "spec_ngram_max", None) is not None:
        section["ngram_max"] = int(args.spec_ngram_max)
    if getattr(args, "spec_ngram_min", None) is not None:
        section["ngram_min"] = int(args.spec_ngram_min)
    return section


def _qos_sched_section() -> dict:
    """Scheduler half of the layered ``qos`` config section (file /
    DYN_QOS__* env): WFQ tenant weights + the batch starvation bound.  The
    edge half (quotas, brownout) is consumed by the CLI's HttpService
    wiring instead."""
    from ..runtime.config import RuntimeConfig

    section = RuntimeConfig.from_layers().qos or {}
    known = ("tenant_weights", "default_weight", "batch_every")
    return {k: section[k] for k in known if k in section}


def _lora_section(args):
    """Layered multi-LoRA section (llm/tenancy): RuntimeConfig ``lora``
    (file / DYN_LORA__* env) under explicit --lora* CLI flags.  Returns
    ``(LoraConfig-kwargs, {name: spec})`` — the adapters map merges the
    config section's ``adapters`` with every repeatable ``--lora NAME=SPEC``
    flag, and any adapter at all implies ``enable``."""
    from ..runtime.config import RuntimeConfig

    section = dict(RuntimeConfig.from_layers().lora)
    adapters = dict(section.pop("adapters", None) or {})
    for spec in getattr(args, "lora", None) or []:
        name, sep, path = spec.partition("=")
        if not sep or not name or not path:
            raise SystemExit(f"--lora expects NAME=PATH, got {spec!r}")
        adapters[name] = path
    if getattr(args, "lora_max_adapters", None) is not None:
        section["max_adapters"] = int(args.lora_max_adapters)
    if getattr(args, "lora_rank", None) is not None:
        section["rank"] = int(args.lora_rank)
    if adapters:
        section["enable"] = True
    return section, adapters


def _load_adapters(engine, adapters: dict, base_model) -> None:
    """Host-register the configured adapters (no restart needed later —
    this is just the boot-time convenience path).  ``random[:seed]`` specs
    build synthetic adapters (tests / loadgen multi-tenant replay); other
    specs resolve like checkpoints (local dir or HF repo —
    models/hub.resolve_adapter).  On any LoRA-enabled engine the
    served-model allowlist is pinned to base+adapters so unknown names 404
    (llm/tenancy satellite) instead of silently running the base model —
    also when NO boot adapters exist (register_adapter adds to the pinned
    set later): without the allowlist the engine's only fallback identity
    is cfg.model, the ARCHITECTURE name, and a served name that differs
    from it would 404 all base traffic."""
    if adapters:
        from ..llm.tenancy.lora import LoraAdapter, load_lora_adapter
        from ..models.hub import resolve_adapter

        for name, spec in sorted(adapters.items()):
            if isinstance(spec, str) and spec.startswith("random"):
                _, _, seed = spec.partition(":")
                adapter = LoraAdapter.random(
                    engine.model_config,
                    name,
                    rank=min(4, engine.cfg.lora.rank),
                    seed=int(seed or 0),
                )
            else:
                adapter = load_lora_adapter(
                    resolve_adapter(spec), engine.model_config, name=name
                )
            engine.register_adapter(adapter)
    if base_model and (adapters or engine.cfg.lora.enable):
        engine.set_served_models([base_model, *adapters])
