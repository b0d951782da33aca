"""Engine configuration knobs.

The reference exposes these through engine flags (`launch/dynamo-run/src/
flags.rs`: --context-length, --kv-cache-block-size, --tensor-parallel-size)
and vLLM config YAML; here they parameterise the native engine directly.
Bucketing fields exist because XLA compiles one program per shape: batch and
prefill-length buckets are powers of two, so a handful of compilations cover
every workload mix.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional


# Canonical decode-kernel names (ops/ragged_attention.resolve_decode_kernel
# and the CLI --decode-kernel choices both derive from this — ONE list, so
# a new kernel cannot be reachable from the env but not the config/CLI).
# Lives here because config.py is the dependency-free bottom of the import
# graph; ops/ and cli import it lazily.
DECODE_KERNELS = ("pallas_fused", "stock", "xla")

# Canonical prefill-kernel names (ops/ragged_attention.resolve_prefill_kernel
# and the CLI share this the same way).
PREFILL_KERNELS = ("pallas", "stock", "xla")


def _pow2_buckets(lo: int, hi: int) -> List[int]:
    out, v = [], lo
    while v < hi:
        out.append(v)
        v *= 2
    out.append(hi)
    return sorted(set(out))


@dataclass
class SpecDecodeConfig:
    """Draft-free speculative decoding (engine/spec.py).

    The proposer is prompt-lookup (Saxena 2023): the last ``ngram_min..
    ngram_max`` tokens of a sequence are matched against its own
    prompt+output history and the continuation of the most recent match is
    proposed as a draft.  Drafts verify through the EXISTING unified ragged
    program — one single-token row per draft position, so per-position
    logits and the per-(seed, step) sampler come for free — and the longest
    prefix matching the seeded sample stream is accepted (greedy ≡ argmax
    match; temperature>0 ≡ exactly the tokens non-speculative decoding
    would have sampled).  Speculation on/off is token-for-token identical.
    """

    enable: bool = False
    # Suffix n-gram lengths tried longest-first against the history.
    ngram_min: int = 2
    ngram_max: int = 4
    # Draft-length ceiling per sequence per dispatch (the adaptive
    # controller moves each sequence's k inside [k_min, k]).
    k: int = 8
    k_min: int = 1
    # EWMA smoothing of per-dispatch acceptance (accepted/drafted).
    ewma_alpha: float = 0.3
    # Below this EWMA the sequence's proposer is benched ...
    accept_floor: float = 0.15
    # ... until this many more tokens have been committed, then re-probes
    # at k_min (templated traffic often turns repetitive mid-stream).
    cooldown_tokens: int = 64
    # Proposer matching window: only the last ``lookback`` history tokens
    # are scanned (0 = unlimited).  Bounds per-proposal cost at long
    # contexts; recent history is where templated repetition lives.
    lookback: int = 2048
    # Engagement bar vs the fused pipeline (plans that would otherwise run
    # as a fused session, ``StepPlan.session``): speculate
    # when the expected committed tokens per round trip reach
    # ``pipeline_margin * n_decode * decode_steps``.  A verification step
    # streams the weights ONCE for all its rows where a fused chunk
    # streams them ``decode_steps`` times, so a verify step costs well
    # under half a chunk — 0.5 is conservative; raise toward 1.0 to be
    # stricter about leaving the pipeline.
    pipeline_margin: float = 0.5

    def __post_init__(self) -> None:
        if self.ngram_min < 1 or self.ngram_max < self.ngram_min:
            raise ValueError(
                f"spec_decode ngram range [{self.ngram_min}, {self.ngram_max}]"
                " must satisfy 1 <= ngram_min <= ngram_max"
            )
        if self.k < 1 or self.k_min < 1 or self.k_min > self.k:
            raise ValueError(
                f"spec_decode k range [{self.k_min}, {self.k}] must satisfy"
                " 1 <= k_min <= k"
            )
        if not 0.0 < self.ewma_alpha <= 1.0:
            raise ValueError("spec_decode ewma_alpha must be in (0, 1]")
        if self.pipeline_margin <= 0.0:
            raise ValueError("spec_decode pipeline_margin must be > 0")

    @classmethod
    def normalize(cls, v: Any) -> "SpecDecodeConfig":
        """Accept the config section in any layered-config shape: an
        instance, a dict (file/env layers), a bare bool, or None."""
        if v is None:
            return cls()
        if isinstance(v, cls):
            return v
        if isinstance(v, bool):
            return cls(enable=v)
        if isinstance(v, dict):
            known = set(cls.__dataclass_fields__)
            bad = set(v) - known
            if bad:
                raise ValueError(f"unknown spec_decode keys: {sorted(bad)}")
            return cls(**v)
        raise ValueError(f"bad spec_decode section: {v!r}")


@dataclass
class LoraConfig:
    """Batched multi-LoRA serving (llm/tenancy/lora.py — S-LoRA).

    ``max_adapters`` resident DEVICE slots of rank ceiling ``rank`` are
    allocated as fixed-shape banks at engine init, so registering /
    promoting / evicting adapters never changes a compiled program's shape
    — hot-swap is a host→device column write.  The host-side registry can
    hold arbitrarily many adapters; only the resident set is bounded.
    """

    enable: bool = False
    # Resident device slots (concurrent distinct adapters in one batch).
    max_adapters: int = 4
    # Per-slot rank ceiling; adapters with smaller rank zero-pad up.
    rank: int = 8
    # How long acquire() waits for a pinned slot to free before failing
    # the request (all residents actively serving sequences).
    promote_timeout_s: float = 30.0

    def __post_init__(self) -> None:
        if self.max_adapters < 1:
            raise ValueError("lora max_adapters must be >= 1")
        if self.rank < 1:
            raise ValueError("lora rank must be >= 1")

    @classmethod
    def normalize(cls, v: Any) -> "LoraConfig":
        """Accept the section in any layered-config shape (see
        SpecDecodeConfig.normalize)."""
        if v is None:
            return cls()
        if isinstance(v, cls):
            return v
        if isinstance(v, bool):
            return cls(enable=v)
        if isinstance(v, dict):
            known = set(cls.__dataclass_fields__)
            bad = set(v) - known
            if bad:
                raise ValueError(f"unknown lora keys: {sorted(bad)}")
            return cls(**v)
        raise ValueError(f"bad lora section: {v!r}")


@dataclass
class QosSchedConfig:
    """Scheduler-side QoS (engine/scheduler.py WfqQueue; llm/qos.py has the
    edge half).  Defaults reproduce pre-QoS behaviour exactly for
    single-tenant traffic: equal weights collapse WFQ to per-tenant FIFO,
    and FIFO within one tenant.
    """

    # Tenant → WFQ weight (share of admission work while backlogged).
    tenant_weights: Dict[str, float] = field(default_factory=dict)
    default_weight: float = 1.0
    # Batch-class starvation bound: at most this many consecutive
    # interactive admissions while batch is backlogged before one batch
    # admission is forced.
    batch_every: int = 4

    def __post_init__(self) -> None:
        if self.default_weight <= 0:
            raise ValueError("qos default_weight must be > 0")
        if self.batch_every < 1:
            raise ValueError("qos batch_every must be >= 1")
        for name, w in self.tenant_weights.items():
            if float(w) <= 0:
                raise ValueError(f"qos tenant weight {name!r} must be > 0")

    @classmethod
    def normalize(cls, v: Any) -> "QosSchedConfig":
        """Accept the section in any layered-config shape (see
        SpecDecodeConfig.normalize)."""
        if v is None:
            return cls()
        if isinstance(v, cls):
            return v
        if isinstance(v, dict):
            known = set(cls.__dataclass_fields__)
            bad = set(v) - known
            if bad:
                raise ValueError(f"unknown qos keys: {sorted(bad)}")
            return cls(**v)
        raise ValueError(f"bad qos section: {v!r}")


@dataclass
class EngineConfig:
    model: str = "debug-tiny"
    block_size: int = 16
    num_blocks: int = 256  # HBM KV blocks (per replica)
    max_batch: int = 8  # decode slots
    max_model_len: int = 1024  # context limit per sequence
    prefill_chunk: int = 512  # max tokens prefillled per device step
    # mesh
    dp: int = 1
    tp: int = 1
    ep: int = 1
    # sequence parallel (ring attention): long prompts >= sp_prefill_min
    # tokens prefill in ONE whole-prompt pass sharded over the "sp" axis
    # instead of serial prefill_chunk steps (models/llama.py
    # forward_sp_prefill).  Best fit: dedicated (disagg) prefill workers.
    sp: int = 1
    sp_prefill_min: int = 1024
    dtype: str = "bfloat16"
    # KV cache dtype; defaults to dtype.  Quantized page dtypes halve KV
    # memory (2x context capacity).  kv_scale: a static float, "auto"
    # (per-layer scales calibrated from a probe forward at engine start —
    # engine._calibrate_kv_scales), or a per-layer sequence.  "int8"
    # REQUIRES calibration/a real scale (stored values are value/kv_scale
    # rounded to integers — at the 1.0 default, normal sub-unit activations
    # all round to 0).  Accuracy evidence: tests/test_quantized_kv.py.
    cache_dtype: Optional[str] = None
    kv_scale: Any = 1.0
    # Weight quantization: "int8" = W8A8-dynamic (per-output-channel int8
    # weights quantized at load, per-token dynamic int8 activations, native
    # MXU int8 dots — models/quant.py, ops/quant_matmul.py).  Halves weight
    # HBM (a full-depth 7-8B model fits one 16 GB v5e chip: chip_smoke.py;
    # its speed against bf16 is not measured on this machine).  The
    # TPU mapping of the reference baseline's FP8-dynamic checkpoint
    # (examples/llm/benchmarks/README.md).  None = bf16 weights.
    weight_quant: Optional[str] = None
    # Fuse q|k|v and gate|up projection weights at engine init (7 matmuls
    # per dense layer -> 5; fused dots share one activation quantization).
    # Applied on single-shard meshes only — a tp-sharded fused axis would
    # split across segment boundaries (models/quant.py fuse_projections).
    fuse_projections: bool = True
    seed: int = 0
    # derived buckets
    batch_buckets: List[int] = field(default_factory=list)
    prefill_buckets: List[int] = field(default_factory=list)
    enable_prefix_caching: bool = True
    checkpoint_path: Optional[str] = None  # safetensors dir; None = random init
    # Attention backend: auto (tpu on a TPU backend at head_dim % 128 == 0,
    # else xla — resolved at engine init and reported, never a fallback
    # further down) | tpu | xla.
    attn_impl: str = "auto"
    # Decode-path attention kernel (ops/ragged_attention.py
    # resolve_decode_kernel; env override DYN_DECODE_KERNEL):
    #   auto         — pallas_fused on TPU, stock elsewhere
    #   pallas_fused — our fused-dequant split-KV Pallas decode kernel
    #                  (ops/decode_attention.py)
    #   stock        — the jax pallas ragged kernel with tuned decode
    #                  hints on TPU, XLA fallback elsewhere (pre-kernel
    #                  behaviour)
    #   xla          — force the XLA fallback (bit-exactness oracle)
    decode_kernel: str = "auto"
    # Prefill-path attention kernel (ops/ragged_attention.py
    # resolve_prefill_kernel; env override DYN_PREFILL_KERNEL):
    #   auto   — pallas on TPU, stock elsewhere
    #   pallas — our chunked paged Pallas prefill kernel with in-kernel
    #            dequant + KV splits (ops/prefill_attention.py)
    #   stock  — the jax pallas ragged kernel on TPU, XLA fallback
    #            elsewhere (pre-kernel behaviour)
    #   xla    — force the XLA fallback (byte-identity oracle)
    prefill_kernel: str = "auto"
    # Decode-stall watchdog threshold in seconds (engine/pipeline.py
    # _await_device): a token fetch / device dispatch exceeding it logs the
    # dispatch trace loudly and bumps dynamo_tpu_engine_stall_total.
    # None resolves the DYN_DECODE_STALL_S env var; 0 disables (default).
    decode_stall_s: Optional[float] = None
    # Decode iterations fused into one device dispatch (lax.scan feeding
    # sampled tokens forward in HBM).  >1 amortises host→device dispatch
    # latency at the cost of token-delivery granularity (the trade is not
    # measured on this machine).
    decode_steps: int = 4
    # Fused decode dispatches kept in flight before their token fetch is
    # awaited (the sampled-token carry stays ON DEVICE between dispatches, so
    # chunk k+1 runs while chunk k's tokens stream back).  Hides the full
    # device→host round trip behind compute; stop conditions are applied with
    # up to pipeline_depth*decode_steps tokens of lag (over-decoded tokens
    # are discarded host-side and never corrupt sealed KV blocks).
    pipeline_depth: int = 2
    # Host (CPU RAM) KV offload tier: sealed blocks are write-behind copied
    # to host so HBM eviction keeps contents; prompts restore evicted
    # prefixes with one scatter instead of recomputing (engine/host_cache.py;
    # reference kv/storage.rs + block_copy.cu).  0 disables.
    host_cache_bytes: int = 0
    # Seconds between offload pump cycles (device gather + async D2H).
    host_offload_interval: float = 0.05
    # Disk KV tier (engine/disk_cache.py): host-tier LRU eviction DEMOTES
    # blocks to hash-named files under ``disk_cache_dir`` instead of
    # dropping them; restores promote disk→host→HBM.  Requires
    # host_cache_bytes > 0 (demotion feeds it); single-process only.
    # 0 disables.
    disk_cache_bytes: int = 0
    # Directory for the disk tier's block files; None resolves to a
    # per-process dir under the system temp root.
    disk_cache_dir: Optional[str] = None
    # fsync the block file before the atomic rename (DYN_DISK_FSYNC=1 also
    # enables).  os.replace is rename-atomic, but a power loss can persist
    # a renamed file whose payload pages never hit the platter; default
    # OFF because the read-side checksum already turns that torn payload
    # into a recompute, never a wrong scatter (docs/kv_tiering.md has the
    # durability-vs-latency tradeoff).
    disk_fsync: bool = False
    # Object-store KV tier (engine/object_store.py): disk-tier LRU
    # eviction DEMOTES blocks into a durable object layout instead of
    # dropping them, and hot chains can be persisted there explicitly
    # (persist_hashes / the autopilot warming policy), so a
    # scale-from-zero worker pointed at the same ``object_store_dir``
    # boots warm.  Requires disk_cache_bytes > 0 (the demotion ladder
    # feeds it) and an EXPLICIT directory: the store outlives the
    # process by design, so the operator owns params stability — there
    # is deliberately no per-PID default to fall back to.  0 disables.
    object_store_bytes: int = 0
    object_store_dir: Optional[str] = None
    # fsync each object part before the atomic publish (durability knob,
    # same tradeoff as disk_fsync; DYN_OBJSTORE_FSYNC=1 also enables).
    object_store_fsync: bool = False
    # KV integrity plane (engine/integrity.py): seconds a checksum-failed
    # block hash stays negative-cached.  While banned, restore/promotion
    # treat the hash as a miss and cross-worker pulls skip it, so a donor
    # still holding the corrupt copy cannot be re-pulled in a loop; after
    # the TTL a healthy copy becomes reachable again.
    kv_corrupt_ttl_s: float = 30.0
    # Cross-worker prefix pull (llm/kv_router/pull.py): when the router's
    # index says a peer holds a strictly longer prefix than every local
    # tier, the engine pulls the sealed delta blocks over the KV transfer
    # plane instead of recomputing prefill.  Budgets bound the worst case:
    # a pull never moves more than ``kv_pull_max_bytes`` and never waits
    # longer than ``kv_pull_timeout_s`` — past either, local prefill runs
    # (the disagg degraded-mode shape; the request is never lost).
    kv_pull_max_bytes: int = 64 << 20
    kv_pull_timeout_s: float = 5.0
    # Draft-free speculative decoding section (SpecDecodeConfig; accepts a
    # dict / bool from layered configs).  Engine-level default; requests
    # opt out per call via sampling_options.spec_decode=false (nvext).
    spec_decode: Any = None
    # Batched multi-LoRA section (LoraConfig; accepts dict/bool).  Requests
    # select an adapter via the OpenAI ``model`` field; rows without one run
    # the base model unchanged.
    lora: Any = None
    # Scheduler QoS section (QosSchedConfig; accepts dict): WFQ tenant
    # weights + the batch-class starvation bound.  Defaults are exact-FIFO
    # for single-tenant traffic.
    qos: Any = None

    def __post_init__(self) -> None:
        if not self.batch_buckets:
            self.batch_buckets = _pow2_buckets(1, self.max_batch)
        if not self.prefill_buckets:
            self.prefill_buckets = _pow2_buckets(
                min(self.block_size, self.prefill_chunk), self.prefill_chunk
            )
        if self.cache_dtype is None:
            self.cache_dtype = self.dtype
        self.spec_decode = SpecDecodeConfig.normalize(self.spec_decode)
        self.lora = LoraConfig.normalize(self.lora)
        self.qos = QosSchedConfig.normalize(self.qos)
        if self.disk_cache_bytes > 0 and self.host_cache_bytes <= 0:
            raise ValueError(
                "disk_cache_bytes requires host_cache_bytes > 0 (the disk "
                "tier is fed by host-tier demotion)"
            )
        if self.object_store_bytes > 0:
            if self.disk_cache_bytes <= 0:
                raise ValueError(
                    "object_store_bytes requires disk_cache_bytes > 0 (the "
                    "object tier is fed by disk-tier demotion)"
                )
            if self.object_store_dir is None:
                raise ValueError(
                    "object_store_bytes requires an explicit "
                    "object_store_dir: the store outlives the process, so "
                    "the operator must own the directory (and the params "
                    "stability its hashes assume)"
                )
        if self.decode_kernel not in ("auto",) + DECODE_KERNELS:
            raise ValueError(
                f"unknown decode_kernel {self.decode_kernel!r} "
                f"(auto|{'|'.join(DECODE_KERNELS)})"
            )
        if self.prefill_kernel not in ("auto",) + PREFILL_KERNELS:
            raise ValueError(
                f"unknown prefill_kernel {self.prefill_kernel!r} "
                f"(auto|{'|'.join(PREFILL_KERNELS)})"
            )
        if self.weight_quant not in (None, "int8"):
            # One check covering every load path (checkpoint / random-init /
            # externally supplied params).
            raise ValueError(
                f"unknown weight_quant {self.weight_quant!r} (supported: int8)"
            )

    @property
    def max_blocks_per_seq(self) -> int:
        return (self.max_model_len + self.block_size - 1) // self.block_size

    def bucket_batch(self, n: int) -> int:
        for b in self.batch_buckets:
            if n <= b:
                return b
        return self.batch_buckets[-1]

    def bucket_prefill(self, n: int) -> int:
        for b in self.prefill_buckets:
            if n <= b:
                return b
        return self.prefill_buckets[-1]

    @property
    def max_step_tokens(self) -> int:
        """Token capacity of one unified (ragged) step: a full prefill
        budget plus a decode token for every batch slot."""
        n = self.prefill_chunk + self.max_batch
        return 1 << (n - 1).bit_length()

    def bucket_tokens(self, n: int) -> int:
        """Power-of-two token-count bucket for the unified ragged step."""
        b = max(16, 1 << (max(1, n) - 1).bit_length())
        return min(b, self.max_step_tokens)
