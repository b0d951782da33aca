"""dynalint registries: taint sources/sinks/sanitizers, wire-schema
classes and exemptions, resource lifetimes, compile-stability scopes.

The dataflow rules (DYN1xx/2xx/3xx/5xx/6xx) are only as good as their
model of *this* codebase; that model lives here, in one reviewable place,
instead of being scattered through rule logic.  Registry groups:

- **Taint** (DYN2xx): which expressions produce wire-controlled data
  (sources), which calls neutralize it (sanitizers), and which calls/format
  positions must never receive it raw (sinks).
- **Wire schema** (DYN3xx): which dataclasses cross process boundaries,
  which of their fields are deliberately exempt from a check, and the
  frozen field prefixes of the jit-pytree classes whose treedef must stay
  byte-stable.
- **Snapshot threading** (DYN304): the explicit SequenceState →
  SequenceSnapshot coverage map — every engine-consumed decode-state field
  either travels in the snapshot or is consciously exempted here.
- **Resource lifetimes** (DYN5xx): the acquire/release/transfer model of
  every handle-shaped resource (KV blocks, adapter slots, mux stream ids,
  hub leases, row slots, tmp ``.kvblk`` files) plus the device-lock
  dispatch/blocking-I/O discipline.
- **Compile stability & determinism** (DYN6xx): which functions are jit
  hot paths (dtype/shape discipline applies) and which classes/modules are
  deterministic cores (injectable clocks + seeded RNG only).

Every entry is a claim that someone thought about the case; deleting an
entry re-surfaces the finding, so the registries are self-auditing: stale
entries (naming fields/classes that no longer exist) are themselves
reported by the schema pass.
"""

from __future__ import annotations

# ---------------------------------------------------------------------------
# DYN2xx taint model
# ---------------------------------------------------------------------------

# Dict keys whose values are wire-controlled wherever they are read:
# request bodies, nvext extensions, hub-delivered registration payloads.
# Reading `<anything>.get("model")` / `<anything>["model"]` taints.
TAINT_SOURCE_KEYS = {
    "model",
    "nvext",
    "tenant",
    "adapter",
    "priority",
    "x-tenant",
    "x-priority",
    "x-api-key",
    "worker_id",
    "metadata",
}

# Keys that carry CREDENTIALS (secret material): stronger taint — reaching
# a log line is already a finding (DYN202), not just a label.
CREDENTIAL_KEYS = {
    "x-api-key",
    "authorization",
    "api_key",
    "bearer",
}

# Parameters that are wire-controlled by naming convention at the HTTP /
# hub edge (`headers` is the aiohttp-style mapping every edge handler
# threads through).
TAINT_SOURCE_PARAMS = {
    "headers": "wire",
}

# Attribute reads that produce wire data regardless of the base object.
TAINT_SOURCE_ATTRS = {
    "headers": "wire",
}

# Calls whose RESULT is wire-controlled (beyond what summaries derive).
# resolve_tenant: x-tenant / nvext.tenant / model pass through verbatim
# (credentials are hashed inside, but the common paths are raw wire).
TAINT_SOURCE_CALLS = {
    "resolve_tenant": "wire",
}

# Calls that neutralize taint: hashing, numeric coercion, Prometheus label
# escaping, and the project's own credential digest.  A sanitizer's return
# value is clean no matter what went in.
SANITIZER_TAILS = {
    "escape_label",
    "hash_credential",
    "safe_key_component",
    "bounded_label",
    "_credential_tenant",
    "sha256",
    "sha1",
    "md5",
    "blake2b",
    "crc32",
    "hexdigest",
    "normalize_priority",
    "int",
    "float",
    "bool",
    "len",
    "round",
    "abs",
    "hash",
    "id",
    "ord",
}

# Lock-shaped names (DYN101 protection detection in callgraph.py AND
# DYN102 acquire/release matching in rules_race.py read THIS tuple — one
# list, so the two rules can never disagree about what counts as a lock).
LOCKISH = ("lock", "mutex", "sem")

# Prometheus-client metric objects: `<metric>.labels(...)` is a label sink.
LABEL_SINK_TAILS = {"labels"}

# Logging sinks: `logger.<x>(...)`.
LOG_SINK_TAILS = {"debug", "info", "warning", "error", "exception", "critical"}
LOG_RECEIVERS = {"logger", "logging", "log", "LOGGER"}

# Hub-key sinks: the FIRST positional argument is a key/subject in the
# shared control-plane namespace; wire data formatted into it un-escaped
# can escape its prefix ("tenant/x" vs "tenant/../quarantine").
HUB_KEY_SINK_TAILS = {
    "kv_put",
    "kv_get",
    "kv_get_prefix",
    "kv_delete",
    "kv_list",
    "watch",
    "watch_prefix",
    "q_push",
    "q_pop",
    "q_len",
    "queue_push",
    "publish",
    "subscribe",
}

# Hub key/subject BUILDERS (DYN401): the sanctioned constructors every hub
# key/subject must route through so the shard map (runtime/transports/
# shard.py) can own routing — an ad-hoc f-string/concatenation at a hub
# sink bypasses the routing contract (and the staleness/park accounting
# keyed on it) and is a finding.  Each entry names a helper that builds
# its keys via hub_key/hub_prefix/hub_subject (or IS one of them).
HUB_KEY_BUILDER_TAILS = {
    # canonical builders (runtime/transports/shard.py)
    "hub_key",
    "hub_prefix",
    "hub_subject",
    # discovery plane (runtime/component.py)
    "instance_key",
    "instance_prefix",
    "endpoint_path",
    "subject",  # Namespace.subject / Component.subject
    # health plane (runtime/health.py)
    "quarantine_key",
    # model discovery / cards (llm/discovery.py, llm/model_card.py)
    "model_key",
    "model_prefix",
    "mdc_key",
    # deployments (deploy/api_store.py)
    "deployment_key",
    # planner actuation (planner/actuate.py)
    "target_key",
    "role_key",
    "directive_key",
    # disaggregated serving (llm/disagg/)
    "disagg_config_key",
    "prefill_queue_name",
    # bulk data plane rendezvous (runtime/transports/bulk.py)
    "bulk_addr_key",
    "bulk_ticket_key",
    "bulk_sink_key",
    "bulk_sink_prefix",
}

# ---------------------------------------------------------------------------
# DYN402 bulk-payload model
# ---------------------------------------------------------------------------

# Hub sinks whose payload argument lands on the control plane (DYN402): a
# bulk payload (KV block export, migration copy stream) published through
# one of these rides every hub shard hop, head-of-line-blocks lease renewals
# and watches, and counts against the shard's publish_bytes budget.  Bulk
# bytes belong on the direct worker<->worker plane (runtime/transports/
# bulk.py, docs/bulk_plane.md); the hub carries rendezvous + control only.
BULK_SINK_TAILS = {
    "publish",
    "q_push",
    "kv_put",
}

# Calls whose RESULT is a bulk payload by construction: publishing one
# through a hub sink is a finding regardless of size (export_prompt_blocks
# returns the full per-block KV byte planes).  Extend when a new producer
# of multi-KiB block payloads appears.
BULK_PAYLOAD_PRODUCER_TAILS = {
    "export_prompt_blocks",
}

# Documented threshold (docs/bulk_plane.md): payloads at or above this are
# bulk by definition.  The AST checker cannot size runtime values — it
# flags the *shapes* above — but the threshold anchors the rule text and
# the bulk plane's own routing decision.
BULK_THRESHOLD_BYTES = 64 * 1024

# Calls that are *safe enough* in a label position for DYN204 even though
# they are not sanitizers (they render numbers).
LABEL_SAFE_CALLS = SANITIZER_TAILS | {"min", "max", "sum", "format"}

# (path, symbol) pairs exempt from DYN204 — each entry documents why the
# interpolated value is provably not wire-controlled.  Keep EMPTY unless
# an escape-at-render fix is genuinely wrong (for internal strings the
# escape is the identity, so the bar for exempting is high; the one real
# hazard is double-escaping a value a helper already escaped — fix THAT
# by making the helper hand raw values to the render).
LABEL_HYGIENE_EXEMPT: set = set()

# ---------------------------------------------------------------------------
# DYN3xx wire-schema model
# ---------------------------------------------------------------------------

# Classes checked even without a to_dict/from_dict pair, and classes with
# serialization helpers that are deliberately NOT wire schemas.
WIRE_CLASS_EXTRA: set = set()
WIRE_CLASS_EXEMPT = {
    # Engine-internal report types whose dicts never cross a version
    # boundary (rebuilt from source every run) go here if they ever trip
    # DYN301.  Empty today: every to_dict class in dynamo_tpu is wire.
}

# (class, field): fields deliberately absent from to_dict / from_dict.
WIRE_FIELD_EXEMPT = {
    # ModelDeploymentCard.tokenizer_obj style in-memory handles would go
    # here; none exist on current wire classes.
}

# Classes that adopted omit-when-absent for OPTIONAL fields (wire compat:
# pre-existing consumers must never see keys they predate).  A class also
# auto-adopts the moment its to_dict emits any field conditionally.
OMIT_WHEN_ABSENT_CLASSES = {
    "PreprocessedRequest",
    "SequenceSnapshot",
    # Planner signal plane (planner/signals.py): the SLO percentiles and
    # the autopilot inputs (fleet_prefix_hit_rate, restore_pct, host_gap)
    # ship only when an edge measured them — pre-autopilot planners (and
    # replay fixtures) keep the original wire shape.
    "SignalSnapshot",
    # Distributed tracing (runtime/tracing.py): ``sampled`` ships only when
    # False — pre-tracing consumers (and the common sampled case) keep the
    # minimal {trace_id, span_id} wire shape.  The trace context itself
    # rides omit-when-absent keys on carriers that already adopted the
    # idiom: annotations.trace, the service-transport header, disagg queue
    # items / kv_import chunks, kv_export pull requests, migration
    # blocks/commit payloads and SequenceSnapshot.trace.
    "TraceContext",
}

# (class, field): Optional fields that MAY ship unconditionally despite
# the class adopting omit-when-absent — grandfathered keys consumers
# already rely on being present.
OMIT_WHEN_ABSENT_EXEMPT = {
    # "model" predates the convention: recorded streams and pre-tenancy
    # consumers read the key unconditionally (None means base model).
    ("PreprocessedRequest", "model"),
}

# Wire-optional keys where a client-sent explicit ``null`` satisfies
# ``setdefault`` and silently skips the rewrite path (the PR 8
# ``"nvext": null`` bug class) — DYN305 flags setdefault on these.
NULLABLE_WIRE_KEYS = {
    "nvext",
    "annotations",
    "sampling_options",
    "stop_conditions",
}

# jit-pytree NamedTuples whose treedef must stay byte-stable: the FROZEN
# field prefix (wire/compile compatibility) — new fields must append after
# it with defaults, never reorder or insert (DYN306).
TREEDEF_FROZEN_PREFIX = {
    "SamplingParams": (
        "seeds",
        "steps",
        "temperature",
        "top_k",
        "top_p",
        "freq_penalty",
        "pres_penalty",
        "counts",
        "need_logprobs",
    ),
    "RaggedBatch": (
        "token_ids",
        "positions",
        "slot_mapping",
        "kv_lens",
        "page_indices",
        "cu_q_lens",
        "num_seqs",
    ),
}

# ---------------------------------------------------------------------------
# DYN304: SequenceState -> SequenceSnapshot threading map
# ---------------------------------------------------------------------------

# Decode-state fields the sampler/pipeline consumes and HOW each travels in
# the snapshot ("field" or "field.sub" of SequenceSnapshot).  A new
# SequenceState field must land in exactly one of these two tables or
# DYN304 fails the gate — the PR 6 bug class (grammar/adapter added to the
# state but not the snapshot ⇒ migrated streams silently diverged).
SNAPSHOT_STATE_CLASS = "SequenceState"
SNAPSHOT_CLASS = "SequenceSnapshot"

SNAPSHOT_COVERED = {
    "request_id": "request_id",
    "prompt": "token_ids",
    "output": "token_ids",  # folded: snapshot ships prompt+output
    "orig_prompt_len": "orig_prompt_len",
    "sampling_temperature": "sampling.temperature",
    "sampling_top_k": "sampling.top_k",
    "sampling_top_p": "sampling.top_p",
    "sampling_seed": "sampling.seed",
    "freq_penalty": "sampling.frequency_penalty",
    "pres_penalty": "sampling.presence_penalty",
    "logprobs": "sampling.logprobs",
    "spec_enabled": "sampling.spec_decode",
    "max_new_tokens": "stop.max_tokens",
    "min_new_tokens": "stop.min_tokens",
    "stop_token_ids": "stop.stop_token_ids",
    "ignore_eos": "stop.ignore_eos",
    "spec_k": "spec.k",
    "spec_ewma": "spec.ewma",
    "spec_bench_until": "spec.bench_until",
    "spec_next_try": "spec.next_try",
    "spec_miss": "spec.miss",
    "kv_salt": "kv_salt",
    "adapter": "adapter",
    "grammar": "grammar",
    "tenant": "tenant",
    "priority": "priority",
    # Tracing continuity: only the CONTEXT travels (trace_id/span_id wire
    # dict) — timing anchors are source-local; the target opens fresh
    # spans under the same trace_id (docs/tracing.md).
    "trace": "trace",
}

# Fields that deliberately do NOT travel, with the reason recorded:
SNAPSHOT_EXEMPT = {
    # KV/block bookkeeping: the target re-derives all of it when the
    # transferred blocks admit as a prefix hit.
    "block_seq": "rebuilt from token_ids on the target",
    "block_ids": "target-side allocation",
    "num_computed": "target-side admission state",
    "num_cached_prompt": "target-side admission metric",
    "num_sealed_blocks": "target-side sealing cursor",
    "pin_ids": "pre-admission pin never outlives the source scheduler",
    "beside": "target-side holdings of what the family keeps beside the pages (engine/resume.py: "
              "a live slot, window pages), taken at its own admission from the target's pools",
    # Transient scheduler/engine flags that must NOT travel:
    "awaiting_fetch": "in-flight fetch is quiesced before freeze",
    "riding_chain": "set only while awaiting_fetch is: quiesced before freeze",
    "frozen": "migration-local flag",
    "finished": "finished sequences are not migrated",
    "enqueue_t": "per-queue latency bookkeeping",
    # Hop-account stamps (docs/tracing.md): this engine's clock and this
    # engine's hops; a resumed sequence counts as incomplete on the target.
    "t_admit": "hop-account stamp of the source engine",
    "t_first_chunk": "hop-account stamp of the source engine",
    "t_last_chunk": "hop-account stamp of the source engine",
    "t_fetch_done": "hop-account stamp of the source engine",
    "t_first_token": "hop-account stamp of the source engine",
    "t_join": "hop-account stamp of the source engine",
    "hops_folded": "source-side fold idempotency flag",
    # Tenancy handles resolved per engine:
    "adapter_slot": "target resolves its own resident slot",
    "adapter_released": "source-side release idempotency flag",
    "grammar_state": "re-derived by advancing through resumed output",
}

# DYN304's second face (the generalization the SignalSnapshot autopilot
# fields forced): wire SNAPSHOT classes with more than one PRODUCER.  Each
# registered producer ("Class.method") must pass every field of the
# snapshot class explicitly at its construction site, or carry a
# per-producer exemption naming why the default is correct THERE.  The bug
# class: a field added to the snapshot and populated by the production
# collector but not the sim's — seeded replays then exercise a policy
# against permanently-absent signals and the sim silently stops being a
# model of the fleet.
WIRE_SNAPSHOT_PRODUCERS = {
    "SignalSnapshot": {
        "SignalCollector.snapshot": set(),
        "SimCluster.snapshot": {
            # the sim models one fleet without a real edge/engine plane;
            # these edge-derived signals stay at their absent defaults
            # (policies reading them must already tolerate None edges)
            "hit_isl_blocks",
            "hit_overlap_blocks",
            "edge_brownout_rung",
            "restore_pct",
            "host_gap",
        },
    },
}

# ---------------------------------------------------------------------------
# DYN5xx resource-lifetime model
# ---------------------------------------------------------------------------

# Each entry declares one resource class as the rule sees it:
#
# - ``acquire``: call tails that mint a handle (the call's result).
# - ``release``: call tails that return the handle to its pool.
# - ``transfer``: call tails that move OWNERSHIP somewhere else (sealing a
#   block into the prefix cache, os.replace-ing a tmp file into place) —
#   they satisfy the lifetime obligation exactly like a release.
# - ``receivers``: when set, the acquire only matches on these receiver
#   attribute names (``self.admission.acquire`` yes, ``self._lock.acquire``
#   no) — generic tails need the hint, unambiguous tails don't.
# - ``handleless``: the protocol pairs by RECEIVER, not by a returned
#   handle (admission slots, adapter refcounts keyed by name).  Handleless
#   resources are only checked when acquire and release appear in the SAME
#   function — cross-function protocols stay out of scope, like DYN102.
# - ``flag_dropped``: a bare-statement acquire whose result is discarded is
#   itself a finding (the handle is unreleasable without it).
#
# ``external`` lists tails implemented OUTSIDE the corpus (os.*) which the
# DYN504 staleness check must not demand a local definition for.
LIFETIME_RESOURCES = {
    "kv_blocks": dict(
        acquire={"allocate_sequence", "acquire_prefix", "allocate_block",
                 "_pin_prefix"},
        release={"free_sequence"},
        transfer={"seal_block"},
        receivers=None,
        handleless=False,
        flag_dropped=True,
    ),
    "adapter_slot": dict(
        acquire={"acquire"},
        release={"release"},
        transfer=set(),
        receivers={"_lora_registry", "lora_registry", "adapters",
                   "adapter_registry"},
        handleless=True,
        flag_dropped=False,
    ),
    "admission_slot": dict(
        acquire={"acquire"},
        release={"release"},
        transfer=set(),
        receivers={"admission", "_admission", "admission_controller"},
        handleless=True,
        flag_dropped=False,
    ),
    "mux_stream": dict(
        acquire={"open_stream"},
        release={"release"},
        transfer=set(),
        receivers=None,
        handleless=False,
        flag_dropped=True,
    ),
    "hub_lease": dict(
        acquire={"lease_grant"},
        release={"lease_revoke"},
        # The hub serving loop mints leases FOR remote clients: shipping
        # the id over the wire (``send``) hands the renew/revoke
        # obligation to the client side.
        transfer={"send"},
        receivers=None,
        handleless=False,
        flag_dropped=True,
    ),
    "row_slot": dict(
        acquire={"assign"},
        release={"free", "retire"},
        transfer=set(),
        receivers={"slots", "_slots", "row_slots"},
        handleless=False,
        flag_dropped=False,
    ),
    "tmp_kvblk": dict(
        acquire={"_tmp_path"},
        release={"remove", "unlink"},
        transfer={"replace", "rename"},
        receivers=None,
        handleless=False,
        flag_dropped=True,
        external={"remove", "unlink", "replace", "rename"},
    ),
}

# Call tails whose handle may be passed WITHOUT transferring ownership —
# pure builtins that cannot retain a reference.  (Used for alias
# propagation: a value built from the handle through these stays an alias.)
PURE_BUILTIN_TAILS = {
    "len", "zip", "enumerate", "list", "tuple", "set", "frozenset",
    "sorted", "reversed", "min", "max", "sum", "any", "all", "str",
    "repr", "range", "print", "isinstance", "bool", "int", "float",
    "iter", "next", "hash", "map", "filter",
}

# Custody sinks: passing a tracked handle to one of these MOVES ownership
# out of the function (into a container that outlives the frame, or into
# another task), so DYN501 stands down.  Every other call BORROWS the
# handle — the scatter/ping/publish idioms pass block ids around freely
# while the function keeps the release obligation; treating those as
# escapes would blind the rule to exactly the historical leaks
# (transfer.py scatter, the health-probe ping).
CUSTODY_SINK_TAILS = {
    "append", "appendleft", "add", "extend", "insert",
    "put", "put_nowait", "push",
    "create_task", "ensure_future",
    "setdefault", "update",
}

# Device-lock discipline (DYN502/DYN503 — the PR 11 lock-split class).
# Jitted dispatch entry points (``self.<tail>(...)`` or
# ``asyncio.to_thread(self.<tail>, ...)``) must run under ``_device_lock``
# so a concurrent dispatch can never interleave donated-buffer reuse;
# blocking host I/O must NOT run under it, or every decode step queues
# behind a disk write.
DEVICE_DISPATCH_TAILS = {"_step_fn", "_multi_fn", "_inject_fn", "_gather_fn"}
DEVICE_LOCK_NAME = "_device_lock"
# Functions sanctioned to dispatch without the lock: startup-only warmup
# compilation runs before the serving loop exists (single task, no
# concurrent dispatch possible).
DEVICE_LOCK_EXEMPT_FUNCS = {"warmup", "_warm_join"}
# Functions whose CONTRACT is "caller holds _device_lock" (sync bodies run
# via asyncio.to_thread under the caller's lock).  Their bodies check as
# locked; every reference to them OUTSIDE the lock is itself a DYN502
# finding, so the contract is enforced at both ends.
DEVICE_LOCK_REQUIRED_FUNCS = {"_offload_store", "_restore_inject"}

# Blocking host I/O that must never run under the device lock.
HOST_BLOCKING_DOTTED = {
    "time.sleep",
    "os.fsync",
    "os.replace",
    "os.remove",
    "os.rename",
    "os.unlink",
    "shutil.copyfile",
    "shutil.move",
}
HOST_BLOCKING_TAILS = {"write_bytes", "read_bytes", "write_text", "read_text"}
HOST_BLOCKING_BARE = {"open"}

# ---------------------------------------------------------------------------
# DYN6xx compile-stability & determinism model
# ---------------------------------------------------------------------------

# Hot-path scope for DYN601: every function in these paths (prefix match)
# plus these function names (the names make fixtures/tests expressible and
# are validated for staleness by DYN604).
HOT_PATH_PATHS = ("dynamo_tpu/ops/", "dynamo_tpu/engine/pipeline.py")
HOT_PATH_FUNCTIONS = {
    "ragged_decode_attention",
    "ragged_attention",
    "write_kv_ragged",
    "fused_prefill_attention",
    "resolve_prefill_kernel",
}

# Array constructors whose result dtype depends on jax's weak-type /
# x64-flag defaults when no dtype is given.  Shape constructors are always
# ambiguous without a dtype; array/asarray only when fed a Python literal
# (an ndarray argument carries its own dtype).
SHAPE_CONSTRUCTOR_TAILS = {"zeros", "ones", "empty", "full", "arange"}
LITERAL_CONSTRUCTOR_TAILS = {"array", "asarray"}
ARRAY_NAMESPACES = ("jnp", "jax.numpy")
DTYPE_NAME_TAILS = {
    "float64", "float32", "float16", "bfloat16",
    "float8_e4m3fn", "float8_e5m2",
    "int64", "int32", "int16", "int8",
    "uint64", "uint32", "uint16", "uint8",
    "bool_", "complex64",
}

# DYN602: jit-traced dispatch sites — a raw per-request ``len(...)`` in an
# argument keys a fresh executable per length; route it through the
# power-of-two padding idiom (``1 << (n - 1).bit_length()``) or a
# registered bucket helper first.
TRACED_DISPATCH_TAILS = DEVICE_DISPATCH_TAILS
BUCKET_HELPER_TAILS = {"bit_length", "next_pow2", "pad_bucket", "round_up"}

# DYN603: deterministic cores — decision logic whose outputs must be a
# function of its inputs so tests/sim/replay stay exact.  Wall clocks are
# injected (``clock=time.monotonic`` default parameter, called as
# ``self._clock()``); RNG is seeded (``random.Random(seed)``).  Registered
# by class name and by module path.
DETERMINISTIC_CORE_CLASSES = {
    "DecisionEngine",   # planner/policy.py — scaling decisions
    "BrownoutLadder",   # llm/qos.py — degradation rungs
    "WfqQueue",         # engine/scheduler.py — virtual-time fairness
    "TimedWindow",      # llm/metrics.py — the PR 8 wall-clock bug class
    "AdapterRegistry",  # llm/tenancy/lora.py — promotion deadlines
    "DefaultWorkerSelector",  # llm/kv_router/scheduler.py — tie-breaks
    "RetryPolicy",      # runtime/resilience.py — backoff jitter
}
DETERMINISTIC_CORE_PATHS = ("dynamo_tpu/planner/sim.py",)

# Raw time sources forbidden inside deterministic cores (calls only —
# referencing ``time.monotonic`` as an injectable default is the idiom).
RAW_CLOCK_DOTTED = {
    "time.time", "time.monotonic", "time.perf_counter",
    "time.time_ns", "time.monotonic_ns", "time.perf_counter_ns",
    "datetime.now", "datetime.utcnow",
    "datetime.datetime.now", "datetime.datetime.utcnow",
}
# RNG namespaces forbidden unseeded; constructors that take an explicit
# seed argument are the sanctioned form.
RAW_RNG_PREFIXES = ("random.", "np.random.", "numpy.random.")
SEEDED_RNG_TAILS = {"Random", "default_rng"}
