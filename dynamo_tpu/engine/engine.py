"""TpuEngine: the native JAX engine behind the AsyncEngine interface.

This is the component the reference delegates to vLLM/sglang subprocesses
(lib/engines/* — SURVEY.md §2.8); here it is in-process and TPU-native.
Round-2 architecture, shaped by measurement on real hardware:

- ONE unified step program per token-count bucket: a flat ragged run of
  tokens mixing prompt chunks and decode tokens (models/llama.py
  forward_ragged over ops/ragged_attention.py).  Decode rows ride along in
  every prefill step, so prefills never starve ITL, and the compile count
  stays tiny (the round-1 separate prefill/decode bucket grid still hit
  cold shapes in production mixes — a single cold XLA compile costs ~15s).
- a fused multi-step decode program (``decode_steps`` iterations per
  dispatch, sampled tokens fed forward ON DEVICE) for the steady state;
- an asynchronous decode PIPELINE: up to ``pipeline_depth`` fused dispatches
  in flight, with the token carry staying on device between dispatches and
  host readback overlapped (fetch and step cost: not measured on this
  machine).  Stop conditions are
  applied with bounded lag; over-decoded tokens are discarded host-side and
  never land in sealed KV blocks (block sealing happens host-side only for
  accepted tokens).
- KV cache lives in HBM as donated jit operands — scatters update in place;
- KV events (stored/removed, chained hashes) and ForwardPassMetrics are
  emitted exactly as the reference's C-API hooks do
  (lib/bindings/c/src/lib.rs:51-296), feeding the KV-aware router.
"""

from __future__ import annotations

import asyncio
import logging
import time
from collections import deque
from functools import partial
from typing import Any, AsyncIterator, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..llm.kv_router.protocols import ForwardPassMetrics, KvCacheEvent
from ..llm.protocols import FinishReason, LLMEngineOutput, PreprocessedRequest
from ..models.config import ModelConfig, get_config
from ..models.family import RaggedBatch, family_of
from ..ops.sampling import SamplingParams, sample_tokens
from ..parallel.mesh import (
    MeshConfig,
    make_mesh,
    param_pspecs,
    shard_tree,
    sharding_tree,
)
from ..runtime.engine import AsyncEngine, Context, ResponseStream
from .config import EngineConfig
from .kv_manager import KvBlockManager
from .scheduler import Scheduler, SequenceState

logger = logging.getLogger(__name__)




from .migrate import MigrationMixin
from .offload import HostOffloadMixin
from .phases import PhaseAccount, SetupAccount
from .pipeline import _FINISHED, DecodePipelineMixin
from .spec import AcceptanceController, SpecDecodeMixin
from .transfer import KvTransferMixin, _scales_close, transfer_blocks_device  # noqa: F401 — compat re-export


class TpuEngine(
    KvTransferMixin, HostOffloadMixin, DecodePipelineMixin, SpecDecodeMixin,
    MigrationMixin, AsyncEngine,
):
    """Token-in/token-out engine (ExecutionContext equivalent)."""

    def __init__(
        self,
        cfg: EngineConfig,
        event_callback: Optional[Callable[[KvCacheEvent], None]] = None,
        params: Any = None, setup: Optional[SetupAccount] = None,
    ):
        self.cfg = cfg
        from .xla_cache import setup_compilation_cache
        self.setup = setup if setup is not None else SetupAccount()  # the start's account
        self.compile_cache_dir = setup_compilation_cache()
        self.model_config: ModelConfig = get_config(cfg.model).with_overrides(
            dtype=cfg.dtype
        )
        if cfg.tp > 1 and self.model_config.num_kv_heads % cfg.tp != 0:
            # pages_pspec shards the combined 2*kv_heads axis over tp; a tp
            # that doesn't divide num_kv_heads would split a K/V pair of one
            # head across shards (XLA's divisibility check alone would let
            # e.g. tp == 2*num_kv_heads through).
            raise ValueError(
                f"tp={cfg.tp} must divide num_kv_heads="
                f"{self.model_config.num_kv_heads} (KV pages shard by head)"
            )
        model_config = self.model_config
        # Everything the engine needs of the model: drawn by model_type, so
        # no site below names a family (models/family.py).
        fam = self.family = family_of(model_config)
        fam.check(model_config, cfg)
        # The family's own host-side account of a dispatch (lengths the
        # scheduler holds; no device sync), or None.
        self._count_dispatch = (
            partial(fam.count_dispatch, model_config) if fam.count_dispatch else None
        )
        attn_impl = cfg.attn_impl
        if attn_impl == "auto":
            from ..ops.ragged_attention import on_tpu

            # The kernels need whole 128-lane heads: a toy head_dim (the
            # debug models) takes the XLA path — resolved HERE, from the
            # model's own shape, so every reporting surface names the path
            # that really serves (there is no fallback further down).
            lanes = fam.attn_lanes(model_config) if fam.attn_lanes else model_config.head_dim
            attn_impl = "tpu" if on_tpu() and lanes % 128 == 0 else "xla"
        self.attn_impl = attn_impl
        # Kernel selectors (config > DYN_DECODE_KERNEL/DYN_PREFILL_KERNEL
        # env > auto), resolved and validated BEFORE anything is allocated.
        from ..ops.ragged_attention import (
            pallas_interpret,
            resolve_decode_kernel,
            resolve_prefill_kernel,
        )

        decode_kernel = resolve_decode_kernel(
            cfg.decode_kernel, attn_impl=attn_impl
        )
        self.decode_kernel = decode_kernel
        prefill_kernel = resolve_prefill_kernel(
            cfg.prefill_kernel, attn_impl=attn_impl
        )
        self.prefill_kernel = prefill_kernel
        kv2_shard = 2 * model_config.num_kv_heads // max(1, cfg.tp)
        compiled_kernels = attn_impl == "tpu" or (
            not pallas_interpret()
            and (decode_kernel == "pallas_fused" or prefill_kernel == "pallas")
        )
        if (
            compiled_kernels
            and jnp.dtype(cfg.cache_dtype).itemsize == 1
            and kv2_shard < 4
        ):
            # One KV head per shard with 1-byte pages: the page's combined
            # K/V axis (2) is below the int8 sublane packing (4) and both
            # the repo's kernels and the stock one are refused by the
            # chip's compiler (qwen2.5-7b at tp=4, compile rehearsal PR 21).
            raise ValueError(
                f"{cfg.model} at tp={cfg.tp} leaves {kv2_shard // 2} KV "
                f"head(s) per shard; with {cfg.cache_dtype} pages the "
                "attention kernels need at least 2 — use a smaller tp or "
                "bfloat16 pages"
            )
        # What the family keeps beside the K/V pages (recurrent state in slots,
        # window layers' pages: engine/resume.py) is ONE object under the block
        # manager: the scheduler and the step builder ask it, and the cache is
        # made with its pools' sizes.
        self.kv = KvBlockManager(
            cfg.num_blocks, cfg.block_size, event_callback=event_callback,
            enable_prefix_caching=cfg.enable_prefix_caching,
            beside=fam.beside(model_config, cfg) if fam.beside else None,
        )
        self.scheduler = Scheduler(cfg, self.kv)
        pools = self.kv.beside.cache_kw
        # (The fused program finds a window layer's first block from these.)
        win_pages, win_tokens = pools.get("window_pages", 0), model_config.sliding_window
        # Draft-free speculative decoding (engine/spec.py): None = off.
        self._spec_ctl = (
            AcceptanceController(cfg.spec_decode)
            if cfg.spec_decode.enable
            else None
        )
        self._queues: Dict[str, asyncio.Queue] = {}
        self._contexts: Dict[str, Any] = {}
        self._wake = asyncio.Event()
        self._closed = False
        self._loop_task: Optional[asyncio.Task] = None
        # Serialises device-state access: step functions donate the cache
        # buffers, so export/import must never observe a mid-step cache.
        self._device_lock = asyncio.Lock()
        self._rng = jax.random.PRNGKey(cfg.seed)
        self._steps = 0
        # (``warmup_s`` is the start's account's: ``self.setup.warm_s()``.)
        self._device_static: Optional[Dict[str, Any]] = None  # device_summary
        # Multi-host: leader broadcasts every dispatch over this plane so
        # followers keep their device queues in SPMD lockstep (multihost.py).
        self._publisher = None
        self._mirror_carry: Any = None
        # Host KV offload tier (engine/host_cache.py).
        self.host_kv = None
        self.disk_kv = None
        # Durable object-store tier (engine/object_store.py): the only
        # tier that OUTLIVES this process — never removed at close().
        self.object_kv = None
        self._offload_queue: List[Tuple[int, Any]] = []
        self._offload_task: Optional[asyncio.Task] = None
        # Cross-worker prefix pull hook (llm/kv_router/pull.py): the serving
        # layer wires a PrefixPuller; None = pulls disabled.
        self._prefix_puller = None
        # KV integrity plane (engine/integrity.py): negative cache of
        # checksum-failed hashes (always on — the wire plane needs it even
        # without tiers) + the optional self-corruption reporter the
        # serving layer wires to feed the health watchdog.
        from .integrity import CorruptionCache

        self.integrity = CorruptionCache(ttl_s=cfg.kv_corrupt_ttl_s)
        self._integrity_reporter = None
        if cfg.host_cache_bytes > 0:
            # Multi-process: every host keeps a PER-HOST SHARDED tier — it
            # stores only the shards its own devices hold (gathers and
            # restores ride the leader→follower mirror plane, so all
            # processes run the same device programs in the same order).
            from .host_cache import HostKvStore

            self.host_kv = HostKvStore(cfg.host_cache_bytes)
            if cfg.disk_cache_bytes > 0:
                if jax.process_count() > 1:
                    # Per-host sharded tiers hold dict shards the disk
                    # container refuses; multi-host overflow keeps the
                    # pre-tier drop behaviour.
                    logger.warning(
                        "disk KV tier disabled: multi-process runs keep "
                        "per-host sharded host tiers only"
                    )
                else:
                    import os as _os
                    import tempfile as _tempfile

                    from .disk_cache import DiskKvStore

                    # The per-PID default is deliberate: block hashes do
                    # not encode params identity, so a STABLE shared dir
                    # could restore a previous (differently-seeded) run's
                    # KV under valid hashes.  Engine-owned dirs are
                    # removed at close(); only an EXPLICIT disk_cache_dir
                    # (operator owns params stability) survives restarts
                    # and benefits from the re-index.
                    self._disk_dir_owned = cfg.disk_cache_dir is None
                    d = cfg.disk_cache_dir or _os.path.join(
                        _tempfile.gettempdir(),
                        f"dynamo_tpu_kv_{_os.getpid()}",
                    )
                    fsync = cfg.disk_fsync or _os.environ.get(
                        "DYN_DISK_FSYNC", ""
                    ) not in ("", "0", "false")
                    self.disk_kv = DiskKvStore(
                        cfg.disk_cache_bytes, d, fsync=fsync
                    )
                    self.host_kv.on_evict = self._demote_to_disk
                    if cfg.object_store_bytes > 0:
                        from .object_store import ObjectKvStore

                        ofsync = cfg.object_store_fsync or _os.environ.get(
                            "DYN_OBJSTORE_FSYNC", ""
                        ) not in ("", "0", "false")
                        self.object_kv = ObjectKvStore(
                            cfg.object_store_bytes,
                            cfg.object_store_dir,
                            fsync=ofsync,
                        )
                        self.disk_kv.on_evict = self._demote_to_objstore
            # HBM eviction of a block a lower tier retains emits a
            # tier-tagged event instead of Removed (kv_manager).
            self.kv.tier_lookup = self._tier_of
        # Per-dispatch trace: (kind, wall_s, rows, device_tokens); the
        # pipeline records dispatch and fetch separately since they
        # overlap.  Bounded: a long-lived server must not grow it forever.
        self.step_trace: deque = deque(maxlen=65536)
        # The loop's account of its own time, always on (engine/phases.py).
        self.phases = PhaseAccount()
        self._phase = self.phases.phase
        # Prefill-chunk accounting (pipeline._run_unified): cumulative
        # chunk count / wall / prompt tokens plus a bounded per-chunk wall
        # trace for the latency quantiles on /metrics
        # (dynamo_tpu_prefill_chunk_seconds).
        self.prefill_chunks = 0
        self.prefill_wall_s = 0.0
        self.prefill_tokens = 0
        self._prefill_chunk_trace: deque = deque(maxlen=4096)
        # Deferred token fetches (FIFO).  Prompt-completing unified steps
        # and speculative verification steps start their token D2H
        # asynchronously, park their rows (awaiting_fetch), and keep the
        # loop dispatching; accepts happen at harvest points once the
        # round trip has overlapped with real work (what a blocking
        # fetch costs: not measured on this machine).
        self._pending_fetches: List[Tuple] = []
        # Request ids with fused-pipeline dispatches potentially in flight
        # (maintained DYNAMICALLY across each _decode_pipeline session —
        # continuous admission adds ids as sequences join, retirement
        # removes them once the write barrier passes); live migration's
        # freeze waits until its sequence leaves this set.
        self._pipeline_members: set = set()
        # Continuous-batching pipeline health (engine/pipeline.py): how
        # often fused sessions start/drain, and how much membership churn
        # the in-loop paths absorbed without a drain.  Exported on /metrics
        # as dynamo_tpu_engine_dispatch_* (llm/metrics.py).
        self.pipeline_sessions = 0       # _decode_pipeline runs begun
        self.pipeline_rebuilds = 0       # sessions drained by a rebuild event
        self.continuous_admissions = 0   # sequences admitted in-loop
        self.continuous_retired = 0      # rows retired in-loop (no drain)
        # The first token's path through an iteration (docs/
        # decode_pipeline.md): first-token fetches applied on landing or at
        # a harvest point, and prompt steps enqueued ahead of or behind a
        # fused chunk of the same iteration.  One increment a prompt step.
        self.first_harvest = {"landed": 0, "iteration": 0}
        self.prompt_step_order = {"ahead": 0, "behind": 0}
        self.pipeline_wall_s = 0.0       # cumulative fused-session wall
        # Of pipeline_wall_s, the part the loop spent in a harvest:* phase
        # (nothing of its own to do but wait for the device): what
        # host_gap_frac is made from.  Unbounded like pipeline_wall_s — never
        # derived from the BOUNDED step_trace, whose eviction after 65k
        # entries would drift the ratio on a long-lived server.
        self.pipeline_waited_s = 0.0
        # Decode-stall watchdog (r5 diagnosed a ~3-minute decode_wait hang
        # with NO engine-side detector): a token fetch / device dispatch
        # that exceeds the threshold trips a loud log with the recent
        # dispatch trace, bumps this counter (dynamo_tpu_engine_stall_total
        # on /metrics) and surfaces in dispatch_summary() so the health
        # watchdog's straggler path can see a wedged device even while the
        # worker still answers probes.  Config decode_stall_s; None
        # resolves DYN_DECODE_STALL_S; 0 = off (default).
        import os as _os

        self._stall_threshold_s = float(
            cfg.decode_stall_s
            if cfg.decode_stall_s is not None
            else _os.environ.get("DYN_DECODE_STALL_S", "0") or 0
        )
        self.decode_stalls = 0  # fetches that exceeded the threshold
        self.last_stall: Optional[Dict[str, Any]] = None
        # Injectable pace hook: awaited before every device-op await
        # (pipeline._pace) when set.  None (the default) is a single attr
        # check — zero hot-path cost.  Tests use it to throttle decode
        # deterministically (e.g. so a migration's copy loop provably
        # outpaces the sequence on slow containers) instead of racing
        # wall-clock sleeps.  Contract: the hook is awaited OUTSIDE the
        # device lock, so it may BLOCK indefinitely — barrier hooks (the
        # migration copy-round gate in tests/test_migration.py) cannot
        # deadlock the KV copy/export plane, which takes the lock only
        # between paced ops.
        self.pace_hook: Optional[Callable[[], Any]] = None
        # Multi-tenancy (llm/tenancy): LoRA adapter registry (None = LoRA
        # disabled), optional served-model allowlist (unknown names →
        # ModelNotFoundError → 404 at the edge), and the deserialized
        # grammar-automaton LRU (requests ship automata by content hash).
        self._lora_registry = None
        self._served_models: Optional[set] = None
        from collections import OrderedDict as _OD

        self._grammar_lru: "Any" = _OD()

        # --- device state -------------------------------------------------
        mesh_cfg = MeshConfig(dp=cfg.dp, tp=cfg.tp, ep=cfg.ep, sp=cfg.sp)
        self.mesh = make_mesh(mesh_cfg) if mesh_cfg.num_devices > 1 else None
        # In a multi-process (multi-host) run, host-side step inputs must be
        # assembled into replicated GLOBAL arrays before they can feed a jit
        # over the global mesh.
        self._rep_sharding = None
        if jax.process_count() > 1:
            from jax.sharding import NamedSharding, PartitionSpec

            if self.mesh is None:
                raise ValueError(
                    "multi-process run needs a device mesh (dp*tp*ep == "
                    f"global devices, got {mesh_cfg.num_devices})"
                )
            self._rep_sharding = NamedSharding(self.mesh, PartitionSpec())
        # With a mesh, parameters and KV pages are CREATED sharded (the
        # initialiser is jitted with out_shardings; a checkpoint is staged
        # on the host and placed shard by shard), so no device ever holds
        # the whole model — chip 0 of a tp=4 host has a quarter of the HBM
        # the model needs.
        def _place(tree):
            if self.mesh is not None:
                return shard_tree(
                    tree, param_pspecs(self.model_config), self.mesh
                )
            return jax.device_put(tree, jax.devices()[0])
        self.setup.enter("build:params")  # the HOST's wall: the device fills them behind it
        if params is None:
            if cfg.checkpoint_path:
                from ..models.loader import load_params

                # Host-staged (numpy / CPU-device) tensors: placed here.
                params = _place(
                    load_params(
                        self.model_config,
                        cfg.checkpoint_path,
                        quant=cfg.weight_quant,
                    )
                )
            else:
                if cfg.weight_quant:
                    # Direct int8 init — full-depth random bf16 would OOM
                    # the chip before it could be quantized.
                    init = fam.init_params_quantized
                else:
                    init = fam.init_params
                init = partial(init, self.model_config)
                key = jax.random.PRNGKey(cfg.seed)
                if self.mesh is None:
                    params = init(key)
                else:
                    params = jax.jit(
                        init,
                        out_shardings=sharding_tree(
                            jax.eval_shape(init, key),
                            param_pspecs(self.model_config),
                            self.mesh,
                        ),
                    )(key)
        else:
            if cfg.weight_quant:
                params = fam.quantize_params(params)  # no-op if already quantized
            if self.mesh is not None:
                params = _place(params)
        if (
            cfg.fuse_projections
            and fam.fuse_projections is not None
            and not self.model_config.is_moe
            and self.mesh is None  # single-shard only (see fuse_projections)
        ):
            params = fam.fuse_projections(params)
        self.setup.enter("build:other")
        if cfg.lora.enable:
            # Fixed-shape multi-LoRA device banks (llm/tenancy/lora.py): R
            # resident slots × rank-r A/B factors per attention projection,
            # zero-initialized (an all-zero slot is exactly the base model).
            # Added AFTER quantize/fuse so the base tree is final — adapters are
            # merge-free and never touch it.  The leaves live in params["layers"]
            # so the layer scan slices them per layer like any other stacked weight.
            if self.mesh is not None:
                raise ValueError(
                    "lora.enable requires a single-shard engine in this "
                    "build (tp/dp/ep/sp == 1): the adapter banks have no "
                    "PartitionSpecs yet"
                )
            from ..llm.tenancy.lora import bank_leaves

            dt = jnp.dtype(cfg.dtype)
            for name, leaf in bank_leaves(
                self.model_config, cfg.lora.max_adapters, cfg.lora.rank
            ).items():
                params["layers"][name] = jnp.asarray(leaf, dt)
        self.setup.enter("build:cache")
        # A family with state slots gets its pools here, beside the pages.  (Frames of
        # this file are in the call stacks of every Mosaic kernel's lowered text, which keys the
        # compile cache: moving ``warmup`` and what it calls costs every configuration one cold start.)
        make_cache = partial(
            fam.create_cache, self.model_config, cfg.num_blocks, cfg.block_size,
            dtype=jnp.dtype(cfg.cache_dtype),
            **pools,
        )
        if self.mesh is None:
            cache = make_cache()
        else:
            cache = jax.jit(
                make_cache,
                out_shardings=sharding_tree(
                    jax.eval_shape(make_cache),
                    fam.cache_pspec(),
                    self.mesh,
                ),
            )()
        self.params = params
        self.cache = cache
        self.setup.enter("build:other")
        # Quantized-scale resolution AFTER sharding: the calibration probe runs
        # over the (possibly tp/dp-sharded) params on the engine's own mesh — a
        # single-device probe would materialize the whole model on one chip,
        # OOMing exactly the tp>1 configurations quantized KV exists for.
        if jnp.dtype(cfg.cache_dtype).itemsize == 1:
            if isinstance(cfg.kv_scale, str):
                if cfg.kv_scale != "auto":
                    raise ValueError(f"unknown kv_scale {cfg.kv_scale!r}")
                self.kv_scale = self._calibrate_kv_scales(params)
            elif isinstance(cfg.kv_scale, (list, tuple, np.ndarray)):
                self.kv_scale = np.asarray(cfg.kv_scale, np.float32)
            else:
                self.kv_scale = float(cfg.kv_scale)
        else:
            self.kv_scale = None

        bs = cfg.block_size
        from ..ops.decode_attention import install_tuned_hints

        install_tuned_hints(cfg.model, cfg.max_batch, cfg.block_size)
        logger.info(
            "decode kernel: %s, prefill kernel: %s (attn_impl=%s)",
            decode_kernel, prefill_kernel, attn_impl,
        )
        S = cfg.max_batch
        mesh = self.mesh
        # Quantized (1-byte) KV pages: a static scale, or per-layer scales
        # calibrated at init (kv_scale == "auto"; resolved above, before
        # sharding).  Arrays fold into the forward algebraically
        # (models/llama.py), so they stay fully traced.
        kv_scale = self.kv_scale
        # Static LoRA bank geometry (0 = disabled): captured by the jitted
        # closures, so constrained/LoRA rows run the SAME compiled programs
        # as base rows — the whole point of the per-row design.
        lora_rank = cfg.lora.rank if cfg.lora.enable else 0
        self._lora_rank = lora_rank

        def _step(params, cache, rb, samp):
            logits, cache, aux = fam.forward(
                params, model_config, rb, cache, attn_impl=attn_impl,
                mesh=mesh, kv_scale=kv_scale, lora_rank=lora_rank,
                prefill_kernel=prefill_kernel,
            )
            out = sample_tokens(
                logits,
                samp.seeds,
                samp.steps,
                samp.temperature,
                samp.top_k,
                samp.top_p,
                samp.freq_penalty,
                samp.pres_penalty,
                samp.counts,
                samp.need_logprobs,
                samp.mask_words,
                samp.any_mask,
            )
            if aux is not None:
                out = out._replace(aux=aux)
            return out, cache

        T_steps = cfg.decode_steps

        def _multi(params, cache, tok0, steps0, counts0, pos0, tables, limits, samp):
            """``decode_steps`` fused decode iterations: one dispatch, the
            sampled token feeds the next step ON DEVICE, and the final token
            carry is returned un-fetched so the next dispatch can chain to it
            without a host round trip.

            ``pos0[s]`` is -1 for padding rows; ``limits[s]`` is the
            allocated KV capacity — steps whose position reaches it skip the
            cache write (their tokens are discarded host-side).  Output-token
            counts (penalties) and per-row rng stream positions advance ON
            DEVICE across the fused steps.
            """
            cu = jnp.arange(S + 1, dtype=jnp.int32)
            num = jnp.full((1,), S, jnp.int32)
            active = pos0 >= 0
            if win_pages:
                # The chunk's window tables ride with the K/V tables: they begin
                # at the block the window of the chunk's FIRST position reaches
                # and cover every position the chunk writes (pipeline.py).
                tables, wtab = tables
                wbase = jnp.maximum(pos0 + 1 - win_tokens, 0) // bs

            def body(carry, _):
                cache, tok, pos, steps, counts = carry
                posc = jnp.maximum(pos, 0)
                slot = (
                    tables[jnp.arange(S), posc // bs] * bs + posc % bs
                )
                writable = active & (posc < limits)
                slot = jnp.where(writable, slot, -1)
                kv_lens = jnp.where(active, jnp.minimum(pos + 1, limits), 0)
                window = {} if not win_pages else dict(
                    window_indices=wtab,
                    window_lens=jnp.maximum(kv_lens - wbase * bs, 0),
                    window_slots=jnp.where(
                        writable, wtab[jnp.arange(S), posc // bs - wbase] * bs + posc % bs, -1),
                )
                rb = RaggedBatch(
                    token_ids=tok,
                    positions=posc,
                    slot_mapping=slot,
                    # Padding rows have no context (0): a decode kernel returns
                    # them zeros and the fused one does no work for them.
                    kv_lens=kv_lens,
                    page_indices=tables,
                    cu_q_lens=cu,
                    num_seqs=num,
                    # Decode rows: one token per row, so the per-row slots
                    # (llm/tenancy multi-LoRA) are the per-token slots.
                    adapter_slots=samp.adapter_slots,
                    **window,
                )
                logits, cache, aux = fam.forward(
                    params, model_config, rb, cache, attn_impl=attn_impl,
                    mesh=mesh, kv_scale=kv_scale, decode=True,
                    decode_kernel=decode_kernel, lora_rank=lora_rank,
                )
                out = sample_tokens(
                    logits,
                    samp.seeds,
                    steps,
                    samp.temperature,
                    samp.top_k,
                    samp.top_p,
                    samp.freq_penalty,
                    samp.pres_penalty,
                    counts,
                    samp.need_logprobs,
                    samp.mask_words,
                    samp.any_mask,
                )
                if aux is not None:
                    out = out._replace(aux=aux)
                nxt = out.tokens
                counts = counts.at[jnp.arange(S), nxt].add(
                    active.astype(counts.dtype)
                )
                carry = (
                    cache,
                    nxt,
                    jnp.where(active, pos + 1, pos),
                    jnp.where(active, steps + 1, steps),
                    counts,
                )
                return carry, out

            (cache, last, _, steps_f, counts_f), outs = jax.lax.scan(
                body,
                (cache, tok0, pos0, steps0, counts0),
                None,
                length=T_steps,
            )
            # outs: SampleOut of [decode_steps, ...]; (last, steps_f,
            # counts_f) is the ON-DEVICE carry the next dispatch chains to.
            return outs, last, steps_f, counts_f, cache

        # Batched block gather (host offload) and donated in-place page
        # scatter (KV imports; callers bucket the page count to bound
        # recompiles): the family owns the layout.  A family whose blocks
        # cannot be moved yet has neither, and the planes that need them
        # refuse at their entry (_require_block_moves).
        _gather, _inject = fam.gather_pages, fam.inject_pages

        donate = (1,)
        if _inject is None:
            self._step_fn = jax.jit(_step, donate_argnums=donate)
            self._multi_fn = jax.jit(_multi, donate_argnums=donate)
            self._inject_fn = self._gather_fn = None
        elif self.mesh is None:
            self._step_fn = jax.jit(_step, donate_argnums=donate)
            self._multi_fn = jax.jit(_multi, donate_argnums=donate)
            self._inject_fn = jax.jit(_inject, donate_argnums=(0,))
        else:
            cache_sh = sharding_tree(cache, fam.cache_pspec(), self.mesh)
            self._step_fn = jax.jit(
                _step, donate_argnums=donate, out_shardings=(None, cache_sh)
            )
            self._multi_fn = jax.jit(
                _multi,
                donate_argnums=donate,
                out_shardings=(None, None, None, None, cache_sh),
            )
            self._inject_fn = jax.jit(
                _inject, donate_argnums=(0,), out_shardings=cache_sh
            )
        if _gather is not None:
            self._gather_fn = jax.jit(_gather)  # host offload (no donation)

        if cfg.sp > 1:
            def _sp(params, toks, valid):
                return fam.forward_sp_prefill(
                    params, model_config, toks, valid, mesh
                )

            self._sp_fn = jax.jit(_sp)
        else:
            self._sp_fn = None
        # Cached all-zeros penalty-counts buffer (see _sampling_arrays).
        self._zero_counts = jnp.zeros(
            (S, self.model_config.vocab_size), jnp.int16
        )
        # Cached all-zeros grammar-mask buffer ([S, ceil(V/32)] packed
        # bits): rides every unconstrained step cond-skipped, so the
        # common path pays no H2D for the tenancy machinery.
        self._mask_w = (self.model_config.vocab_size + 31) // 32
        self._zero_mask = jnp.zeros((S, self._mask_w), jnp.uint32)
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            if jax.process_count() == 1:
                rep = NamedSharding(self.mesh, PartitionSpec())
                self._zero_counts = jax.device_put(self._zero_counts, rep)
                self._zero_mask = jax.device_put(self._zero_mask, rep)
            else:
                self._zero_counts = self._prep(
                    np.zeros((S, self.model_config.vocab_size), np.int16)
                )
                self._zero_mask = self._prep(
                    np.zeros((S, self._mask_w), np.uint32)
                )
        if cfg.lora.enable:
            from ..llm.tenancy.lora import AdapterRegistry

            self._lora_registry = AdapterRegistry(
                cfg.lora.max_adapters,
                cfg.lora.rank,
                self._lora_apply,
                promote_timeout_s=cfg.lora.promote_timeout_s,
            )

    def _calibrate_kv_scales(self, params) -> np.ndarray:
        """Per-layer quantization scales from a probe forward: run a short
        deterministic token run through the model with a throwaway bf16
        cache, take each layer's max |K/V|, and map it to the target
        dtype's representable max.  Runs on the engine's own mesh (sharded params +
        sharded probe cache), so tp>1 models that don't fit one chip calibrate fine;
        multi-host deployments pass the calibrated vector explicitly via kv_scale."""
        self.setup.enter("build:calibrate")  # its fetch is the first wait for the weights
        if jax.process_count() > 1:
            raise ValueError(
                "kv_scale='auto' calibrates on one process; run calibration "
                "single-host and pass the resulting scales explicitly"
            )
        cfg, mc = self.cfg, self.model_config
        # Probe length bounded so nb (+1 slack) fits a single row's table.
        T = min(128, (cfg.max_blocks_per_seq - 1) * cfg.block_size)
        nb = (T + cfg.block_size - 1) // cfg.block_size + 1
        fam = self.family
        probe = fam.create_cache(mc, nb, cfg.block_size, dtype=jnp.bfloat16,
                                 **self.kv.beside.probe_kw(nb))
        windowed = getattr(probe, "window", None) is not None  # a second K/V array: nb pages too
        if self.mesh is not None:
            probe = shard_tree(probe, fam.cache_pspec(), self.mesh)
        toks = ((np.arange(T) * 2654435761) % mc.vocab_size).astype(np.int32)
        pos = np.arange(T, dtype=np.int32)
        S = cfg.max_batch
        # Table width = the probe's own nb pages, NOT max_blocks_per_seq:
        # the XLA fallback materializes [T, width*bs, 2KV, hd] f32, which
        # at long-context configs would be tens of GB.
        tables = np.zeros((S, nb), np.int32)
        tables[0, :nb] = np.arange(nb)
        cu = np.zeros((S + 1,), np.int32)
        cu[1:] = T
        rb = RaggedBatch(
            token_ids=toks,
            positions=pos,
            slot_mapping=pos,  # consecutive slots in blocks 0..nb
            kv_lens=np.asarray([T] + [0] * (S - 1), np.int32),
            page_indices=tables,
            cu_q_lens=cu,
            num_seqs=np.asarray([1], np.int32),
            **(dict(window_indices=tables, window_slots=pos,
                    window_lens=np.asarray([T] + [0] * (S - 1), np.int32)) if windowed else {}),
        )
        _, probe = jax.jit(
            lambda p, c: fam.forward(
                p, mc, rb, c, attn_impl="xla", mesh=self.mesh
            )[:2]
        )(params, probe)
        # [L, nb, ps, 2KV, hd] → per-layer max |value| over everything else.
        maxabs = np.asarray(
            jnp.max(
                jnp.abs(probe.pages.astype(jnp.float32)), axis=(1, 2, 3, 4)
            )
        )
        if windowed:
            # The window layers' scales follow the full layers'.  This family
            # norms K a head and V not at all, so a layer's scale is K's and V
            # is stored times a GAIN that brings it to K's size: the scales,
            # then the gains (models/lfm2.py; K rows even, V rows odd).
            both = jnp.concatenate([probe.pages, probe.window]).astype(jnp.float32)
            k_max = np.asarray(jnp.max(jnp.abs(both[:, :, :, 0::2]), axis=(1, 2, 3, 4)))
            v_max = np.asarray(jnp.max(jnp.abs(both[:, :, :, 1::2]), axis=(1, 2, 3, 4)))
            gains = np.maximum(k_max, 1e-6) / np.maximum(v_max, 1e-6)
        dt = jnp.dtype(cfg.cache_dtype)
        if jnp.issubdtype(dt, jnp.integer):
            qmax = float(jnp.iinfo(dt).max)
        else:
            qmax = float(jnp.finfo(dt).max)  # e4m3 → 448
        scales = np.maximum(maxabs / qmax, 1e-6).astype(np.float32)
        if windowed:
            scales = np.concatenate([np.maximum(k_max / qmax, 1e-6), gains]).astype(np.float32)
        logger.info("calibrated per-layer kv scales (dtype %s): min %.4g max %.4g",
                    dt, scales.min(), scales.max())
        self.setup.enter("build:other")

        return scales

    def _kv_scale_repr(self):
        """JSON-safe scale for transfer payloads: None, float, or list."""
        if self.kv_scale is None:
            return None
        a = np.asarray(self.kv_scale, np.float32).reshape(-1)
        return [float(x) for x in a] if a.size > 1 else float(a[0])

    # ------------------------------------------------------------ multi-host
    def attach_publisher(self, publisher) -> None:
        """Leader side: broadcast every device dispatch to the followers
        (engine/multihost.py StepPublisher)."""
        self._publisher = publisher

    def _prep(self, tree: Any) -> Any:
        """Host arrays → replicated global arrays when multi-process."""
        if self._rep_sharding is None:
            return tree
        from ..parallel.distributed import global_array

        return jax.tree_util.tree_map(
            lambda x: global_array(x, self._rep_sharding), tree
        )

    async def run_warmup(self) -> Dict[str, int]:
        """warmup() that keeps followers in lockstep (use in serving paths;
        plain warmup() is fine single-process)."""
        async with self._device_lock:
            if self._publisher is not None:
                await self._publisher.publish("warmup")
            return await asyncio.to_thread(self.warmup)

    async def mirror_step(self, kind: str, payload: Tuple) -> None:
        """Follower side: replay one leader dispatch (same jitted fns, same
        global arrays, same order → SPMD lockstep)."""
        if kind == "warmup":
            await asyncio.to_thread(self.warmup)
        elif kind == "unified":
            rb, samp = payload

            def run_u():
                _, self.cache = self._step_fn(
                    self.params,
                    self.cache,
                    self._prep(rb),
                    self._prep(samp),
                )

            async with self._device_lock:
                await asyncio.to_thread(run_u)
        elif kind == "multi":
            tok0, pos0, tables, limits, samp = payload
            carry = self._mirror_carry if tok0 is None else None

            def run_m():
                samp_d = self._prep(samp)
                if carry is None:
                    tok, steps0, counts0 = (
                        self._prep(tok0), samp_d.steps, samp_d.counts
                    )
                else:
                    tok, steps0, counts0 = carry
                _, last, steps_f, counts_f, self.cache = self._multi_fn(
                    self.params,
                    self.cache,
                    tok,
                    steps0,
                    counts0,
                    *self._prep((pos0, tables, limits)),
                    samp_d,
                )
                return (last, steps_f, counts_f)

            async with self._device_lock:
                self._mirror_carry = await asyncio.to_thread(run_m)
        elif kind == "inject":
            page_ids, comb_p = payload

            def run_i():
                self.cache = self._inject_fn(
                    self.cache, *self._prep((page_ids, comb_p))
                )

            async with self._device_lock:
                await asyncio.to_thread(run_i)
        elif kind == "offload":
            ids, hashes = payload
            async with self._device_lock:
                await asyncio.to_thread(self._offload_store, ids, hashes)
            # Followers record host-tier drops too (no event callback to
            # publish to, but the transition list must not grow forever).
            self._flush_tier_events()
        elif kind == "restore_host":
            page_ids, hashes = payload
            async with self._device_lock:
                await asyncio.to_thread(self._restore_inject, page_ids, hashes)
            self._flush_tier_events()
        else:
            raise ValueError(f"unknown mirror step kind {kind!r}")

    def _require_block_moves(self, what: str) -> None:
        """Planes that move whole KV blocks (export/import, migration, host
        and disk tiers) need the family's gather/inject; a latent cache has
        none yet, and mis-sizing a block silently is worse than refusing."""
        if self._inject_fn is None:
            raise ValueError(
                f"{what} is not supported for model_type "
                f"{self.model_config.model_type} ({self.cfg.model}): its cache "
                f"({self.device_summary()['cache_kinds']} bytes a token a layer) is "
                "not K-plus-V pages; serve it without --host-cache-mb/"
                "--disk-cache-mb, KV export/import, prefix pulls and live migration"
            )

    # ---------------------------------------------------------------- warmup
    def compile_counts(self) -> Dict[str, int]:
        """Compiled-program count per jitted entry (cache sizes).  The
        benchmark (``dynamo_tpu_engine_compiled_programs``) and the tests
        assert these do not grow after ``warmup()``."""
        counts = {
            "step": self._step_fn._cache_size(),
            "multi": self._multi_fn._cache_size(),
            "join": self._join_fn._cache_size()}  # (no line more: see make_cache)
        if self._inject_fn is not None:
            counts["inject"] = self._inject_fn._cache_size()
        return counts

    def device_summary(self) -> Dict[str, Any]:
        """What this process runs on and what warmup cost, as the serving
        process itself sees it (``/metrics`` dynamo_tpu_engine_* and the
        CLI's start-up line): a result that does not name its device
        cannot be compared with anything."""
        from .xla_cache import cache_entries, cache_events, compile_stages

        if self._device_static is None:
            # Fixed for the life of the process: looked up once, not on
            # every /metrics scrape (dispatch_summary carries this).
            from importlib import metadata

            from .. import native

            devs = jax.devices()
            try:
                libtpu = metadata.version("libtpu")
            except metadata.PackageNotFoundError:
                libtpu = "absent"
            self._device_static = {
                "jax": jax.__version__,
                "libtpu": libtpu,
                "platform": devs[0].platform,
                "device_kind": devs[0].device_kind,
                "device_count": len(devs),
                "model": self.cfg.model,
                "num_layers": self.model_config.num_layers,
                "weight_quant": self.cfg.weight_quant or "none",
                "cache_dtype": str(self.cfg.cache_dtype),
                "attn_impl": self.attn_impl,
                # Each page array of the cache and its bytes a token a layer.
                "cache_kinds": ",".join(
                    f"{k}:{v}" for k, v in
                    self.family.cache_kinds(self.model_config, self.cache).items()
                ),
                "decode_kernel": self.decode_kernel,
                "prefill_kernel": self.prefill_kernel,
                "hasher": native.hasher(),
            }
        # CPU devices report no memory statistics (None).
        stats = [d.memory_stats() or {} for d in jax.local_devices()]
        return {
            **self._device_static,
            "warmup_s": round(self.setup.warm_s(), 3), "setup": self.setup.summary(),
            "compile_counts": self.compile_counts(),
            "compile_cache_dir": self.compile_cache_dir or "",
            "compile_cache_entries": cache_entries(self.compile_cache_dir),
            "compile_cache_hits": cache_events["hits"], "compile_cache_misses": cache_events["misses"],
            "jax_compile": {stage: dict(row) for stage, row in compile_stages.items()},
            "hbm_bytes_in_use": [s.get("bytes_in_use", 0) for s in stats],
            "hbm_bytes_limit": [s.get("bytes_limit", 0) for s in stats],
        }

    def reachable_token_buckets(self) -> List[int]:
        """Every token bucket the scheduler can hand _run_unified: up to
        max_batch decode rows ride alongside up to prefill_chunk prompt
        tokens in one step (decode rows don't consume the prefill budget),
        so totals range 1..prefill_chunk + max_batch."""
        hi = self.cfg.bucket_tokens(self.cfg.prefill_chunk + self.cfg.max_batch)
        buckets, b = [], self.cfg.bucket_tokens(1)
        while b < hi:
            buckets.append(b)
            b *= 2
        buckets.append(hi)
        return buckets

    def _warm_operands(self) -> Tuple[List[Tuple], Optional[Tuple]]:
        """What warm-up hands each program after ``params`` and ``cache``: a
        unified step per reachable token bucket, then the fused decode
        program's operands (None at ``decode_steps`` 1).  All carry slot/pos =
        -1 so cache writes are dropped (write_kv_ragged) and contents are
        untouched."""
        cfg = self.cfg
        S, PP = cfg.max_batch, cfg.max_blocks_per_seq
        samp = self._sampling_arrays([])  # greedy defaults, cached counts
        steps = []
        for T in self.reachable_token_buckets():
            cu = np.zeros((S + 1,), np.int32)
            cu[1:] = T  # one row owns every token; others empty
            rb = RaggedBatch(
                token_ids=np.zeros((T,), np.int32),
                positions=np.zeros((T,), np.int32),
                slot_mapping=np.full((T,), -1, np.int32),  # writes dropped
                # kv_len == q_len: the ragged contract (and the pallas
                # kernel's validation) requires q_len <= kv_len per row.
                kv_lens=np.asarray([T] + [0] * (S - 1), np.int32),
                page_indices=np.zeros((S, PP), np.int32),
                cu_q_lens=cu,
                num_seqs=np.asarray([1], np.int32),
                adapter_slots=np.full((T,), -1, np.int32) if self._lora_rank else None,
                # Nothing beside the pages is read or written either: the
                # family's own operands of a step without rows (engine/resume.py).
                **self.kv.beside.operands((), S, T),
            )
            steps.append((rb, samp))
        multi = None
        if cfg.decode_steps > 1:
            multi = (
                np.zeros((S,), np.int32),
                samp.steps,
                samp.counts,
                np.full((S,), -1, np.int32),  # every row inactive
                # (the family's second table beside the K/V tables, where it has one)
                self._warm_tables(np.zeros((S, PP), np.int32)),
                # (A line kept: frames of this file key the compile cache, see ``make_cache``.)
                np.zeros((S,), np.int32),
                samp,
            )
        return steps, multi

    def _warm_tables(self, tables: np.ndarray) -> Any:
        """The fused decode program's table operand at warm-up: the K/V
        tables, with what the family's chunks take beside them where it has
        such an operand (``Beside.chunk_operand``, as ``dispatch_chunk`` pairs
        them).  (This method keeps the lines of the one it replaced: what
        follows it is in the call stacks that key the compile cache.)"""
        beside = self.kv.beside.chunk_operand((), self.cfg.max_batch)
        return tables if beside is None else (tables, beside)


    def warmup(self) -> Dict[str, int]:
        """Pre-compile every device program the serving loop can dispatch —
        one unified step per reachable token bucket plus the fused decode
        program — so no cold XLA compile (~15s on TPU) ever lands inside a
        request.  Returns compile_counts.
        """
        cfg = self.cfg
        self.setup.enter("warm:walk")  # the operands, then (behind the side-by-side pass) the walk
        steps, multi = self._warm_operands()
        self._compile_side_by_side(steps, multi)
        for ops in steps:
            out, self.cache = self._step_fn(self.params, self.cache, *self._prep(ops))
        if multi is not None:
            tokens, steps_h, counts, *rows = multi
            rows = self._prep(tuple(rows))
            _, last, steps_f, counts_f, self.cache = self._multi_fn(
                self.params, self.cache, self._prep(tokens), self._prep(steps_h), counts, *rows
            )
            # Chain once more with the DEVICE carry: pipeline dispatches 2+
            # feed the previous outputs back in, and committed device arrays
            # key a different executable-cache entry than the uncommitted
            # numpy first dispatch.
            _, last, _, _, self.cache = self._multi_fn(
                self.params, self.cache, last, steps_f, counts_f, *rows
            )
            # Fetch: warmup must not return with compiles/executions still
            # queued (the first real request would absorb them).
            np.asarray(self._warm_join(out, last, steps_f, counts_f, rows))
        else:
            np.asarray(out.tokens)
        if self._sp_fn is not None:
            self.setup.enter("warm:sp")
            # Every reachable sp-prefill token bucket (pow2, sp multiple, sp_prefill_min..
            # max_model_len) — a cold whole-model compile must never land inside a request.
            lo = max(cfg.sp, 1 << (max(1, cfg.sp_prefill_min) - 1).bit_length())
            hi = max(lo, 1 << (cfg.max_model_len - 1).bit_length())
            t = lo
            while True:
                Tg = t + (-t) % cfg.sp
                logits_sp, _ = self._sp_fn(
                    self.params,
                    np.zeros((Tg,), np.int32),
                    np.asarray(Tg, np.int32),
                )
                np.asarray(logits_sp)  # real fetch (see above)
                if t >= hi:
                    break
                t *= 2
        self.setup.enter("serve:listen")  # (until ``mark_ready``: cli.py, the service accepting)
        return self.compile_counts()

    def _compile_side_by_side(self, steps: List[Tuple], multi: Optional[Tuple]) -> None:
        """Before ``warmup`` walks its programs one after another, compile
        them SIDE BY SIDE into the persistent compilation cache, where there
        is one and no mesh (under a mesh the walk's operands are global
        arrays): each program is lowered with the operands the walk will call
        it with (the walk then finds every executable in the process's own
        cache and lowers nothing again: 0.1-0.3 s in every cell, PERF.md
        section 5, PR 58) and compiled on a thread of its own (XLA compiles
        outside the interpreter lock).  A cold start of nine programs of half a
        minute each then takes about as long as the slowest (PERF.md section 6,
        PR 44, has both readings of every cell)."""
        if not self.compile_cache_dir or self.mesh is not None:
            return
        from concurrent.futures import ThreadPoolExecutor

        self.setup.enter("warm:lower")
        # Lowered one after another on this thread (tracing holds the
        # interpreter lock anyway, and traces that interleave do not always
        # lower to the same text: one run in six missed the cache for three
        # programs; my chip run, PR 44), compiled side by side.
        lowered = [self._step_fn.lower(self.params, self.cache, *ops) for ops in steps]
        if multi is not None:
            lowered.append(self._multi_fn.lower(self.params, self.cache, *multi))
        self.setup.enter("warm:compile")
        with ThreadPoolExecutor(len(lowered)) as pool:
            for done in [pool.submit(low.compile) for low in lowered]:
                done.result()
        # (How long each half took is the account's: ``warm:lower``, ``warm:compile``.)
        self.setup.enter("warm:walk")

    # ----------------------------------------------------------- tenancy API
    def register_adapter(self, adapter) -> None:
        """Host-register a LoraAdapter (llm/tenancy/lora.py) — no engine
        restart, no recompile; promotion to a device slot happens lazily on
        first request."""
        if self._lora_registry is None:
            raise RuntimeError(
                "LoRA serving is disabled (EngineConfig.lora.enable)"
            )
        self._lora_registry.register(adapter, self.model_config)
        if self._served_models is not None:
            self._served_models.add(adapter.name)

    def unregister_adapter(self, name: str) -> None:
        if self._lora_registry is not None:
            self._lora_registry.unregister(name)
            # Keep the allowlist in lockstep: a name left behind would let
            # requests for the removed adapter silently run the base model.
            if self._served_models is not None:
                self._served_models.discard(name)

    def adapter_names(self) -> List[str]:
        return self._lora_registry.names() if self._lora_registry else []

    def set_served_models(self, names) -> None:
        """Optional allowlist of model names this engine serves (base +
        adapters).  When set, a request naming anything else fails with
        ModelNotFoundError (the 404 model_not_found body at the edge)
        instead of silently running the base model."""
        self._served_models = set(names) if names is not None else None

    async def _lora_apply(self, slot: int, adapter) -> None:
        """Registry promotion hook: write one slot's (rank-padded) factors
        into the device banks.  Functional .at[].set under the device lock —
        in-flight dispatches keep their old param tree; the registry
        guarantees the slot has no live rows."""
        from ..llm.tenancy.lora import LORA_TARGETS, padded_factors

        r = self.cfg.lora.rank
        lo, hi = slot * r, (slot + 1) * r

        def run():
            layers = self.params["layers"]
            for tgt in LORA_TARGETS:
                a, b = padded_factors(adapter, self.model_config, tgt, r)
                dt = layers[f"lora_a_{tgt}"].dtype
                layers[f"lora_a_{tgt}"] = (
                    layers[f"lora_a_{tgt}"].at[:, :, lo:hi].set(jnp.asarray(a, dt))
                )
                layers[f"lora_b_{tgt}"] = (
                    layers[f"lora_b_{tgt}"].at[:, lo:hi, :].set(jnp.asarray(b, dt))
                )

        async with self._device_lock:
            await asyncio.to_thread(run)

    def _grammar_automaton(self, g: Dict[str, Any]):
        """Deserialize (or LRU-hit) a request's token-mask automaton and fix
        its mask geometry to this engine's vocab/eos.

        Hash-first wire protocol (llm/tenancy): the preprocessor ships a
        hash-only stub by default; a content-hash LRU hit resolves it with
        zero table bytes on the wire, a miss raises GrammarCacheMissError
        (prologue kind ``grammar_miss``) and the preprocessor re-sends the
        full edge table exactly once."""
        from ..llm.metrics import tenancy_metrics
        from ..llm.tenancy.grammar import (
            GrammarCacheMissError,
            TokenMaskAutomaton,
        )

        key = g.get("hash")
        automaton = self._grammar_lru.pop(key, None) if key else None
        if automaton is None:
            if g.get("stub") or "edges" not in g:
                tenancy_metrics.grammar_hash_misses_total += 1
                raise GrammarCacheMissError(str(key))
            automaton = TokenMaskAutomaton.from_dict(g)
        elif g.get("stub"):
            tenancy_metrics.grammar_hash_hits_total += 1
        self._grammar_lru[automaton.hash] = automaton  # LRU refresh/insert
        while len(self._grammar_lru) > 32:
            self._grammar_lru.pop(next(iter(self._grammar_lru)))
        automaton.set_mask_context(
            self.model_config.vocab_size, self.model_config.eos_token_ids
        )
        return automaton

    def _resolve_adapter(self, pre: PreprocessedRequest) -> Optional[str]:
        """Adapter name for this request, or None for the base model.
        Raises ModelNotFoundError for names nobody serves (satellite: never
        silently fall through to the base model)."""
        from ..llm.metrics import tenancy_metrics
        from ..llm.protocols import ModelNotFoundError

        name = pre.annotations.get("adapter")
        if not isinstance(name, str) or not name:
            name = None
        if name is None and pre.model:
            if self._lora_registry is not None and self._lora_registry.has(
                pre.model
            ):
                name = pre.model
            elif self._served_models is not None:
                if pre.model not in self._served_models:
                    tenancy_metrics.adapter_not_found_total += 1
                    raise ModelNotFoundError(pre.model)
            elif (
                self._lora_registry is not None
                and pre.model != self.cfg.model
            ):
                # LoRA-enabled engines serve many logical models by NAME, so
                # a name that is neither the base model nor a registered
                # adapter is a routing mistake — fail it rather than
                # silently running the base model.  (LoRA-less engines keep
                # the historical behaviour: the model field is advisory.)
                tenancy_metrics.adapter_not_found_total += 1
                raise ModelNotFoundError(pre.model)
        if name is not None and (
            self._lora_registry is None or not self._lora_registry.has(name)
        ):
            tenancy_metrics.adapter_not_found_total += 1
            raise ModelNotFoundError(name)
        return name

    # ------------------------------------------------------------ public API
    async def generate(self, request: Context) -> ResponseStream:
        if self._closed:
            raise RuntimeError("engine is closed")
        pre = PreprocessedRequest.from_dict(request.data)
        if len(pre.token_ids) > self.cfg.max_model_len:
            raise ValueError(
                f"prompt length {len(pre.token_ids)} exceeds max_model_len "
                f"{self.cfg.max_model_len}"
            )
        # Multi-tenancy resolution (llm/tenancy) BEFORE admission: the
        # adapter decides the KV salt, which must root the block-hash chain
        # from the very first sealed block.
        adapter = self._resolve_adapter(pre)
        automaton = None
        if pre.grammar:
            from ..llm.metrics import tenancy_metrics

            automaton = self._grammar_automaton(pre.grammar)
            tenancy_metrics.grammar_requests_total += 1
        if adapter is not None:
            from ..llm.tenancy.lora import kv_salt_for_adapter

            pre.annotations.setdefault("kv_salt", kv_salt_for_adapter(adapter))
        # Tenant salt (llm/tenancy): every pre-admission KV preparation
        # below hashes with it, so a tenant request can only ever see —
        # and seal — blocks under its own chain.
        salt = pre.annotations.get("kv_salt") or None
        # Distributed tracing (runtime/tracing.py): the context arrives via
        # annotations.trace (preprocessor / disagg item / migration resume)
        # or the service-transport header (request.ctx.trace); None keeps
        # every instrumentation point below a single attr check.
        from ..runtime.tracing import parse_trace as _parse_trace
        from ..runtime.tracing import span as _trace_span

        trace = _parse_trace(pre.annotations.get("trace")) or getattr(
            request.ctx, "trace", None
        )
        self._ensure_loop()
        prepared = 0
        if self.host_kv is not None and (
            len(self.host_kv)
            or (self.disk_kv is not None and len(self.disk_kv))
            or (self.object_kv is not None and len(self.object_kv))
        ):
            # Pull any evicted prefix blocks back from the host/disk tiers
            # BEFORE admission, so the scheduler sees them as prefix-cache
            # hits (the reference's restore-ahead-of-prefill TTFT win).
            # The tiers index blocks by the (salted) hashes they sealed
            # under, so tenant restores hit exactly their own blocks.
            from ..llm.metrics import kv_tier_metrics

            t0 = time.perf_counter()
            with _trace_span(trace, "engine.kv_restore", "engine") as rs:
                restored = await self._restore_from_host(
                    list(pre.token_ids), salt
                )
                rs.set(restored_tokens=restored)
            prepared += restored
            if restored:
                kv_tier_metrics.restore_latency_ms.observe(
                    (time.perf_counter() - t0) * 1e3
                )
                kv_tier_metrics.restore_hits_total += 1
            else:
                kv_tier_metrics.restore_misses_total += 1
        if self._prefix_puller is not None and pre.annotations.get("kv_pull"):
            # Cross-worker prefix pull (llm/kv_router/pull.py): the router
            # stamped a peer that holds a strictly longer prefix than any
            # local tier; pull the sealed delta blocks over the transfer
            # plane instead of recomputing prefill.  Bounded by the
            # configured byte/latency budgets; ANY failure degrades to
            # local prefill (the disagg degraded-mode shape).
            with _trace_span(trace, "engine.kv_pull", "engine") as ps:
                pulled = await self._prefix_puller.pull(
                    list(pre.token_ids), salt, pre.annotations["kv_pull"],
                    trace=trace,
                )
                ps.set(pulled_tokens=pulled)
            prepared += pulled
        if (
            self._sp_fn is not None
            and len(pre.token_ids) >= self.cfg.sp_prefill_min
            and jax.process_count() == 1
            and salt is None
        ):
            # Long prompt: one sequence-parallel whole-prompt pass seals the
            # complete blocks ahead of admission (ring attention over "sp").
            # DELIBERATELY single-process: sp prefill is scoped to dedicated
            # disagg PREFILL WORKERS (cli run --disagg prefill --sp N), each
            # a single-host engine owning its own sp mesh — decode fleets
            # scale across hosts via dp/tp while prefill workers ring over
            # their local slice and ship blocks through the KV transfer
            # plane (the reference's disagg split, docs/architecture.md).
            prepared += await self._sp_prefill(list(pre.token_ids))
        seq = SequenceState.from_request(request.id, pre, self.cfg)
        if trace is not None:
            from ..runtime.tracing import SeqTrace

            # Marks the row for the queue-wait (scheduler._record_admission)
            # and prefill (pipeline._trace_first_token) spans.
            seq.trace = SeqTrace(trace)
        if automaton is not None:
            seq.grammar = automaton
            # Resumed sequences (llm/migration splice, seeded crash
            # recovery) fold already-delivered OUTPUT into the prompt: the
            # automaton state is the start state advanced through those
            # tokens (every delivered token was mask-admissible, so the
            # walk only fails on a corrupt resume — a request error).
            state: Optional[int] = automaton.start
            for t in seq.prompt[seq.orig_prompt_len:]:
                state = automaton.advance(state, int(t))
                if state is None:
                    raise ValueError(
                        "resume stream violates its grammar constraint"
                    )
            seq.grammar_state = state
        if adapter is not None:
            from ..llm.metrics import tenancy_metrics
            from ..llm.protocols import ModelNotFoundError

            seq.adapter = adapter
            try:
                # Resolve to a resident device slot (async H2D promotion,
                # LRU eviction of idle residents).  The ref pins the slot
                # until _finish — a running row's slot is never rewritten.
                seq.adapter_slot = await self._lora_registry.acquire(adapter)
            except KeyError:
                tenancy_metrics.adapter_not_found_total += 1
                raise ModelNotFoundError(adapter) from None
            tenancy_metrics.adapter_requests_total += 1
        if prepared:
            # PIN the just-sealed prefix until admission: the sealed blocks
            # sit in the reuse pool, where a concurrent request's
            # allocations could LRU-evict them before allocate_sequence
            # matches — silently wasting the whole sp/restore pass.  The
            # scheduler releases the pin when admission lands (or the
            # request is rejected/cancelled).
            seq.pin_ids = self._pin_prefix(list(pre.token_ids), salt)
        queue: asyncio.Queue = asyncio.Queue()
        self._queues[request.id] = queue
        self._contexts[request.id] = request.ctx
        self.scheduler.add(seq)
        self._wake.set()
        # Hop account (docs/tracing.md): a colocated edge reads the queue
        # entry off the in-process context.  A negative value was put there
        # upstream (disagg remote prefill): this sequence's account is void.
        if request.ctx.t_enqueue < 0.0:
            seq.t_first_chunk = -1.0
        else:
            request.ctx.t_enqueue = seq.enqueue_t
        # Server-side seed resolution (llm/qos satellite): UNSEEDED sampled
        # requests get their engine-assigned seed stamped onto the first
        # stream item, so the routed client's _StreamGuard can build a
        # byte-identical resume request after a mid-stream crash —
        # previously only explicit-seed streams were crash-resumable.
        # Greedy (temperature 0) streams are seed-independent and stay
        # unstamped: their output must not vary with the request id
        # (recorder replay and A/B comparisons rely on that), and resume
        # determinism never needed a seed for them.  Resumed requests
        # always carry an explicit seed, so they are never re-stamped.
        samp_opts = pre.sampling_options
        stamp_seed = (
            samp_opts.seed is None and (samp_opts.temperature or 0.0) > 0.0
        )

        async def gen() -> AsyncIterator[Dict[str, Any]]:
            needs_stamp = stamp_seed
            try:
                while True:
                    item = await queue.get()
                    if item is _FINISHED:
                        return
                    if needs_stamp and isinstance(item, dict):
                        item["resolved_seed"] = int(seq.sampling_seed)
                        needs_stamp = False
                    yield item
            finally:
                self._queues.pop(request.id, None)
                self._contexts.pop(request.id, None)

        return ResponseStream(gen(), request.ctx)

    def set_event_callback(
        self, callback: Optional[Callable[[KvCacheEvent], None]]
    ) -> None:
        """Attach/replace the KV event sink (e.g. a KvEventPublisher) after
        construction — the CLI builds the engine before the runtime exists."""
        self.kv._event_callback = callback

    def metrics(self) -> ForwardPassMetrics:
        return ForwardPassMetrics(
            request_active_slots=self.scheduler.num_running,
            request_total_slots=self.cfg.max_batch,
            kv_active_blocks=self.kv.active_blocks,
            kv_total_blocks=self.kv.num_blocks,
            num_requests_waiting=self.scheduler.num_waiting,
            gpu_cache_usage_perc=self.kv.usage,
            gpu_prefix_cache_hit_rate=self.kv.hit_rate,
        )

    async def close(self) -> None:
        self._closed = True
        self._wake.set()
        if self._loop_task is not None:
            await self._loop_task
            self._loop_task = None
        if self._offload_task is not None:
            self._offload_task.cancel()
            try:
                await self._offload_task
            except asyncio.CancelledError:
                pass
            self._offload_task = None
        if self._publisher is not None:
            await self._publisher.close()
            self._publisher = None
        if self.disk_kv is not None and getattr(self, "_disk_dir_owned", False):
            # Engine-owned (defaulted) disk-tier dir: remove it so worker
            # restarts don't leak a dead budget's worth of block files.
            import shutil

            shutil.rmtree(self.disk_kv.directory, ignore_errors=True)
            self.disk_kv = None
        # The object-store tier is deliberately NOT removed: it is the
        # durable rung — a respawned worker pointed at the same dir boots
        # warm from it (scale-from-zero; docs/kv_tiering.md).
        self.object_kv = None
        # Fail whatever is still in flight so no generate() stream hangs.
        self._fail_all()

    # --------------------------------------------------- KV export / import
    #
    # TPU counterpart of the reference's block_copy.cu + NIXL transfer
    # (lib/llm/src/kernels/block_copy.cu, kv/layer.rs:100-772): whole pages
    # move between workers as host-staged arrays (msgpack binary over the
    # service plane; ICI device-to-device when workers share a pod slice).
    # Imported pages are sealed under their chained hashes, so the decode
    # scheduler sees remote-prefilled prompts as ordinary prefix-cache hits.





    def estimate_prefix_hit(
        self, token_ids: List[int], salt: Optional[str] = None
    ) -> int:
        """Tokens of ``token_ids`` already resident locally (router input).
        ``salt`` must match the requesting tenant's (llm/tenancy) or the
        estimate is structurally zero."""
        from ..tokens import hash_token_blocks

        blocks = hash_token_blocks(token_ids, self.cfg.block_size, salt)
        return len(self.kv.match_prefix(blocks)) * self.cfg.block_size

    # ------------------------------------------------------------ tiered KV
    def _tier_of(self, seq_hash: int) -> Optional[str]:
        """Cheapest LOWER tier still holding ``seq_hash`` (HBM excluded —
        the caller is usually deciding what HBM eviction means)."""
        if self.host_kv is not None and self.host_kv.contains(seq_hash):
            return "host"
        if self.disk_kv is not None and self.disk_kv.contains(seq_hash):
            return "disk"
        if self.object_kv is not None and self.object_kv.contains(seq_hash):
            return "objstore"
        return None

    def _demote_to_disk(self, seq_hash: int, block) -> bool:
        """HostKvStore.on_evict hook: push an evicted host-tier block down
        to disk.  Runs inside the host store's eviction loop (often off the
        event loop) — record-only, events flush later.  The host tier's
        offload-time checksum is CARRIED into the disk envelope (and
        verified by the put), so a bit that rotted in host RAM is refused
        here instead of laundered into a valid-looking file."""
        if self.disk_kv is None:
            return False
        return self.disk_kv.put(
            seq_hash, block, checksum=self.host_kv.checksum(seq_hash)
        )

    def _demote_to_objstore(self, seq_hash: int, path: str) -> bool:
        """DiskKvStore.on_evict hook: re-wrap an evicted disk envelope as
        a durable object.  Runs inside the disk store's eviction loop
        (under its lock, often off the event loop) — record-only, events
        flush later.  The envelope is parsed and its carried CRC
        re-verified at ingest, so disk rot is refused here instead of
        persisted for the whole fleet to trust."""
        if self.object_kv is None:
            return False
        return self.object_kv.ingest_kvblk(seq_hash, path)

    def set_integrity_reporter(self, reporter) -> None:
        """Attach ``reporter(plane: str)`` called on every LOCAL-tier
        corruption detection (disk/host).  The serving layer wires it to
        feed the health watchdog's corruption ledger with this worker's
        own id — a worker whose own media keeps flipping bits is as
        quarantine-worthy as a donor shipping poison.  None detaches."""
        self._integrity_reporter = reporter

    def _record_corruption(
        self,
        plane: str,
        seq_hash: Optional[int],
        chain: Optional[List[int]] = None,
        donor: Optional[int] = None,
    ) -> None:
        """Corruption quarantine, one entry point for every plane:
        count it, negative-cache the hash (TTL — restore/pull loops must
        not thrash on it), drop the block and every CHAINED DESCENDANT
        still held by the local tiers (their contents may be fine, but
        their chain passes through poison — the radix index must stop
        advertising the whole run), attribute a wire donor to the health
        ledger, and report local-tier rot to the serving layer.

        The caller flushes tier events afterwards (this may run in a
        thread; event emission must happen on the loop)."""
        from ..llm.metrics import kv_integrity_metrics

        kv_integrity_metrics.corrupt_total[plane] += 1
        logger.warning(
            "KV corruption detected on plane %r (block %s): dropped before "
            "scatter; falling back to recompute",
            plane, f"{seq_hash:#x}" if seq_hash is not None else "?",
        )
        if seq_hash is not None:
            self.integrity.ban(seq_hash)
            dropped = 0
            descendants: List[int] = []
            if chain:
                try:
                    descendants = chain[chain.index(seq_hash) + 1:]
                except ValueError:
                    descendants = []
            for d in [seq_hash, *descendants]:
                hit = False
                if self.host_kv is not None and self.host_kv.drop(d):
                    hit = True
                if self.disk_kv is not None and self.disk_kv.drop(d):
                    hit = True
                if self.object_kv is not None and self.object_kv.drop(d):
                    hit = True
                if hit and d != seq_hash:
                    dropped += 1
            kv_integrity_metrics.descendants_dropped_total += dropped
        if donor is not None:
            from ..runtime.health import kv_corruption

            kv_corruption.record(donor)
        elif plane != "wire" and self._integrity_reporter is not None:
            try:
                self._integrity_reporter(plane)
            except Exception:  # noqa: BLE001 — reporting must never break serving
                logger.warning("integrity reporter failed", exc_info=True)

    def _flush_tier_events(self) -> None:
        """Publish tier transitions recorded by the host/disk stores since
        the last flush.  Must run on the event loop (the KvEventPublisher
        binds futures to it); every threaded tier mutation's caller flushes
        after the thread returns.  A hash still sealed in HBM publishes
        nothing — the router's view stays 'hbm' until HBM eviction."""
        if self.host_kv is None:
            return
        # Each store's "demote" means "the NEXT tier down took it" — the
        # tier tag depends on which store recorded the transition, so the
        # drains stay separate.
        tagged: List[Tuple[str, str, int]] = [
            ("disk", kind, h) for kind, h in self.host_kv.drain_transitions()
        ]
        if self.disk_kv is not None:
            tagged += [
                ("objstore", kind, h)
                for kind, h in self.disk_kv.drain_transitions()
            ]
        if self.object_kv is not None:
            tagged += [
                ("", kind, h)
                for kind, h in self.object_kv.drain_transitions()
            ]
        demoted: Dict[str, List[int]] = {}
        removed: List[int] = []
        for next_tier, kind, h in tagged:
            if h in self.kv._by_hash:
                continue  # HBM still holds it: best tier unchanged
            if kind == "demote":
                demoted.setdefault(next_tier, []).append(h)
            elif self._tier_of(h) is not None:
                continue  # another tier still holds it
            else:
                removed.append(h)
        for tier, hashes in demoted.items():
            self.kv.emit_tiered(tier, hashes)
        self.kv.emit_removed(removed)

    def local_prefix_blocks(
        self, token_ids: List[int], salt: Optional[str] = None,
        blocks: Optional[List[Any]] = None,
    ) -> int:
        """Leading complete blocks restorable from ANY local tier (HBM,
        host, disk) — what a cross-worker pull must strictly beat before
        moving bytes (llm/kv_router/pull.py).  ``blocks`` lets a caller
        that already hashed the chain skip the second O(prompt) walk."""
        from ..tokens import hash_token_blocks

        if blocks is None:
            blocks = hash_token_blocks(token_ids, self.cfg.block_size, salt)
        n = 0
        for tb in blocks:
            h = tb.sequence_hash
            if h in self.kv._by_hash or self._tier_of(h) is not None:
                n += 1
            else:
                break
        return n

    def set_prefix_puller(self, puller) -> None:
        """Attach the cross-worker prefix puller (llm/kv_router/pull.py);
        None detaches.  The serving layer owns peer discovery — the engine
        only calls ``puller.pull(tokens, salt, hint)`` at admission."""
        self._prefix_puller = puller

    def block_nbytes(self) -> int:
        """Host-side bytes of one KV block in the stored representation."""
        return int(
            sum(a.nbytes for a in jax.tree_util.tree_leaves(self.cache))
            // max(1, self.cfg.num_blocks)
        )

    def kv_tier_summary(self) -> Dict[str, Any]:
        """Per-tier bytes/blocks gauges for /metrics (llm/metrics.py
        kv_tier_metrics source) and the edge SLO publication."""
        bb = self.block_nbytes()
        out: Dict[str, Any] = {
            "hbm": {
                "blocks": len(self.kv._by_hash),
                "bytes": len(self.kv._by_hash) * bb,
            },
            "prefix_hit_rate": self.kv.hit_rate,
        }
        if self.host_kv is not None:
            out["host"] = {
                "blocks": len(self.host_kv),
                "bytes": self.host_kv.used_bytes,
            }
        if self.disk_kv is not None:
            out["disk"] = {
                "blocks": len(self.disk_kv),
                "bytes": self.disk_kv.used_bytes,
            }
        if self.object_kv is not None:
            out["objstore"] = {
                "blocks": len(self.object_kv),
                "bytes": self.object_kv.used_bytes,
            }
        return out

    # -------------------------------------------------------------- the loop
    def _ensure_loop(self) -> None:
        if self._loop_task is None or self._loop_task.done():
            self._loop_task = asyncio.get_running_loop().create_task(self._run_loop())
        if self.host_kv is not None and (
            self._offload_task is None or self._offload_task.done()
        ):
            self._offload_task = asyncio.get_running_loop().create_task(
                self._offload_pump()
            )

    async def _run_loop(self) -> None:
        # Where this loop does a session's work it is in the session's
        # phases (engine/phases.py); its idle wait for a request is in none.
        while not self._closed:
            with self._phase("retire"):
                self._cancel_stopped()
            try:
                while (
                    self._pending_fetches
                    and self._pending_fetches[0][1].done()
                ):
                    # Completed background fetches apply for free — parked
                    # rows resume without the loop ever blocking on D2H.
                    await self._harvest_pending()
            except asyncio.CancelledError:
                raise
            except Exception:
                # Same engine-fatal contract as the step path below: a
                # failed D2H must fail all streams, never strand them.
                logger.exception("deferred fetch failed")
                self._fail_all()
                return
            with self._phase("schedule"):
                plan = self.scheduler.schedule()
                for seq in self.scheduler.take_rejected():
                    self._finish(seq, FinishReason.ERROR)
            if plan is None:
                if self._pending_fetches:
                    try:
                        await self._harvest_pending(all_pending=True)
                    except asyncio.CancelledError:
                        raise
                    except Exception:
                        logger.exception("deferred fetch failed")
                        self._fail_all()
                        return
                    continue
                if self.scheduler.num_waiting and not self.scheduler.num_running:
                    # e.g. decode just preempted everyone back to waiting:
                    # retry admission immediately (terminates: each pass
                    # admits or rejects at least one waiting sequence).
                    with self._phase("yield"):
                        await asyncio.sleep(0)
                    continue
                # Idle: running is empty (running sequences always yield
                # work), so sleep until a new request arrives.
                self._wake.clear()
                await self._wake.wait()
                continue
            try:
                did_work = False
                # Speculation first: drafted rows verify multiple tokens
                # per round trip on the unified ragged program (spec.py);
                # an empty draft set (proposer misses, adaptive-k benched,
                # or expected gain below the fused pipeline's) falls
                # through to the fused paths unchanged.
                drafts = (
                    self._spec_propose(plan)
                    if self._spec_ctl is not None
                    else {}
                )
                if drafts:
                    await self._run_spec_unified(plan, drafts)
                    did_work = True
                if not did_work and plan.session:
                    if self._pending_fetches:
                        # Parked rows must not sit out a whole fused
                        # pipeline run — fold them in first.
                        await self._harvest_pending(all_pending=True)
                        continue
                    # The plan's decode rows start the session; its prompts
                    # and the waiting queue are picked up inside it.
                    did_work = await self._decode_pipeline(
                        [
                            seq
                            for seq, start, _ in plan.items
                            if start >= len(seq.prompt)
                        ]
                    )
                if not did_work:
                    # Not a session's plan (prompts only, decode_steps 1, a
                    # grammar-constrained row resident), or the session
                    # dispatched nothing for want of KV headroom for a
                    # fused window: one unified step advances every row of
                    # the plan, and finishes free blocks.
                    await self._run_unified(plan)
            except asyncio.CancelledError:
                raise
            except Exception:  # engine-fatal: fail all inflight requests
                logger.exception("engine step failed")
                self._fail_all()
                return
            with self._phase("yield"):
                self._steps += 1
                await asyncio.sleep(0)  # let ingress/egress run between steps

    def _cancel_stopped(self) -> None:
        for seq in list(self.scheduler.running) + list(self.scheduler.waiting):
            ctx = self._contexts.get(seq.request_id)
            if ctx is not None and ctx.is_stopped and not seq.finished:
                seq.finished = True
                self.scheduler.remove(seq)
                self._finish(seq, FinishReason.CANCELLED)

    def _fail_all(self) -> None:
        self._pending_fetches.clear()  # drop in-flight token fetches
        self._pipeline_members = set()
        for seq in list(self.scheduler.running) + list(self.scheduler.waiting):
            seq.awaiting_fetch = False
            self.scheduler.remove(seq)
            self._finish(seq, FinishReason.ERROR)

    # ------------------------------------------------------------ batch build
    def _next_rng(self) -> jax.Array:
        self._rng, sub = jax.random.split(self._rng)
        return sub




    # ------------------------------------------------------ unified step path



    # -------------------------------------------------- fused decode pipeline



    # ------------------------------------------------------------ per-token

    # ------------------------------------------------------- host KV offload










    def _note_prefill_chunk(self, wall_s: float, tokens: int) -> None:
        """Account one prefill chunk (called by pipeline._run_unified for
        every unified step that advanced prompt tokens): cumulative
        counters for rates, the bounded trace for the
        dynamo_tpu_prefill_chunk_seconds quantiles."""
        self.prefill_chunks += 1
        self.prefill_wall_s += wall_s
        self.prefill_tokens += tokens
        self._prefill_chunk_trace.append(wall_s)

    def prefill_summary(self) -> Dict[str, Any]:
        """Prefill-chunk latency breakdown: cumulative counters (unbounded,
        safe for rate math) plus p50/p99 over the bounded per-chunk trace
        window (gauges, like step_summary)."""
        times = sorted(self._prefill_chunk_trace)
        m = len(times)
        return {
            "chunks": self.prefill_chunks,
            "wall_s": round(self.prefill_wall_s, 4),
            "prompt_tokens": self.prefill_tokens,
            "p50_ms": round(times[m // 2] * 1e3, 2) if m else 0.0,
            "p99_ms": (
                round(times[min(m - 1, int(m * 0.99))] * 1e3, 2) if m else 0.0
            ),
        }

    def step_summary(self) -> Dict[str, Any]:
        """Aggregate the dispatch trace: counts, wall time, and latency
        percentiles per step kind (the VERDICT r1 profiling ask)."""
        out: Dict[str, Any] = {}
        for kind in sorted({k for k, *_ in self.step_trace}):
            times = sorted(t for k, t, _, _ in self.step_trace if k == kind)
            toks = sum(n for k, _, _, n in self.step_trace if k == kind)
            m = len(times)
            out[kind] = {
                "dispatches": m,
                "wall_s": round(sum(times), 4),
                "device_tokens": toks,
                "p50_ms": round(times[m // 2] * 1e3, 2),
                "p99_ms": round(times[min(m - 1, int(m * 0.99))] * 1e3, 2),
            }
        return out

    def dispatch_summary(self) -> Dict[str, Any]:
        """Machine-readable decode-pipeline health: the per-kind dispatch
        trace (step_summary — over the BOUNDED trace window, so its counts
        and percentiles are gauges, not counters) plus session/rebuild/
        churn counters and the fused-loop host-gap fraction — what the
        planner and the benchmark read off ``/metrics`` (llm/metrics.py
        engine_dispatch_metrics).

        ``host_gap_frac`` is scoped to fused decode sessions: the fraction
        of pipeline wall in which the loop was NOT in a ``harvest:*`` phase
        (engine/phases.py), that is, doing work of its own (retiring,
        merging, admitting, building and enqueueing steps, emitting tokens,
        yielding to the HTTP side) instead of waiting for the device — the
        host-side share the continuous pipeline exists to shrink.  Both
        terms accumulate unbounded (never derived from the bounded trace)
        and a second of a session is counted once.  0.0 when no session has
        run.  ``phases`` is the account itself: histogram rows of the loop's
        tiling phases and of the worker threads' device calls."""
        from ..ops.decode_attention import built_operands

        wall = self.pipeline_wall_s
        gap = (
            min(1.0, max(0.0, wall - self.pipeline_waited_s) / wall)
            if wall > 0
            else 0.0
        )
        return {
            "kinds": self.step_summary(),
            "decode_kernel": self.decode_kernel,
            # What the fused kernel's dots take, as the kernel built in
            # this process chose from its q and pages (None: it serves no
            # program here).
            "decode_kernel_operands": (
                built_operands() if self.decode_kernel == "pallas_fused" else None
            ),
            "prefill_kernel": self.prefill_kernel,
            "device": self.device_summary(),
            "prefill": self.prefill_summary(),
            # The family's own accounts (selector or whole-context latent
            # attention, held experts), or None: models/family.py ``counts``.
            "model": self.family.counts() if self.family.counts else None,
            "pipeline": {
                "sessions": self.pipeline_sessions,
                "rebuilds": self.pipeline_rebuilds,
                "continuous_admissions": self.continuous_admissions,
                "continuous_retired": self.continuous_retired,
                "first_harvest": dict(self.first_harvest),
                "prompt_step": dict(self.prompt_step_order),
                "joins": dict(self.pipeline_joins),
                "wall_s": round(wall, 4),
                "host_gap_frac": round(gap, 4),
                # Stall-watchdog surface (DYN_DECODE_STALL_S): the health
                # watchdog's straggler path reads this off the same
                # summary the planner already consumes.
                "stalls": self.decode_stalls,
                "last_stall": self.last_stall,
            },
            "phases": self.phases.summary(),
        }


