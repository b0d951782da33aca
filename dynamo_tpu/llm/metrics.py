"""HTTP-edge Prometheus metrics.

Reference semantics: lib/llm/src/http/service/metrics.rs:57-128,319 —
``{prefix}_http_service_{requests_total, inflight_requests,
request_duration_seconds, time_to_first_token_seconds,
inter_token_latency_seconds}`` with status labels
``success | client_drop | rejected | error``, and a RAII ``InflightGuard``
that records duration + status when dropped.
"""

from __future__ import annotations

import time
from collections import deque
from typing import Any, Deque, Dict, Optional, Tuple

from prometheus_client import (
    CollectorRegistry,
    Counter,
    Gauge,
    Histogram,
    generate_latest,
)

from ..labels import escape_label

REQUEST_BUCKETS = (0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0)
TOKEN_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5)


class Status:
    SUCCESS = "success"
    CLIENT_DROP = "client_drop"
    REJECTED = "rejected"
    ERROR = "error"


class RollingWindow:
    """Bounded rolling sample window with percentile queries.

    Histograms answer "distribution since process start"; the planner's
    SLO loop needs "distribution right now" — a window of the most recent
    observations, cheap to query at scrape/publish time."""

    def __init__(self, maxlen: int = 2048):
        self._xs: Deque[float] = deque(maxlen=maxlen)

    def observe(self, x: float) -> None:
        self._xs.append(x)

    def percentile(self, p: float) -> float:
        if not self._xs:
            return 0.0
        xs = sorted(self._xs)
        return xs[min(len(xs) - 1, int(len(xs) * p))]

    def __len__(self) -> int:
        return len(self._xs)


class TimedWindow:
    """Sample window bounded by AGE, not count — the brownout ladder's
    latency input (llm/qos.py).  A count-bounded window (RollingWindow)
    holds a spike's samples until enough NEW traffic pushes them out: at
    zero traffic it never drains, so pressure reads high forever and the
    ladder can never recover.  Here samples expire after ``max_age_s``
    regardless of traffic, so "the spike ended" is observable."""

    def __init__(self, max_age_s: float = 10.0, maxlen: int = 4096,
                 clock=time.monotonic):
        self.max_age_s = max_age_s
        self._clock = clock
        self._xs: Deque[Tuple[float, float]] = deque(maxlen=maxlen)

    def observe(self, x: float) -> None:
        self._xs.append((self._clock(), x))

    def _prune(self) -> None:
        horizon = self._clock() - self.max_age_s
        while self._xs and self._xs[0][0] < horizon:
            self._xs.popleft()

    def percentile(self, p: float) -> Optional[float]:
        """p-quantile of the live samples, or None when the window is
        empty (signal absent — distinct from 'fast')."""
        self._prune()
        if not self._xs:
            return None
        xs = sorted(x for _, x in self._xs)
        return xs[min(len(xs) - 1, int(len(xs) * p))]

    def __len__(self) -> int:
        self._prune()
        return len(self._xs)


class Metrics:
    def __init__(self, prefix: str = "dynamo_tpu"):
        self.registry = CollectorRegistry()
        ns = f"{prefix}_http_service"
        self.requests_total = Counter(
            f"{ns}_requests_total",
            "Total requests by model/endpoint/status",
            ["model", "endpoint", "request_type", "status"],
            registry=self.registry,
        )
        self.inflight = Gauge(
            f"{ns}_inflight_requests",
            "Currently in-flight requests",
            ["model", "endpoint"],
            registry=self.registry,
        )
        self.request_duration = Histogram(
            f"{ns}_request_duration_seconds",
            "End-to-end request duration",
            ["model", "endpoint"],
            buckets=REQUEST_BUCKETS,
            registry=self.registry,
        )
        self.ttft = Histogram(
            f"{ns}_time_to_first_token_seconds",
            "Time to first token (streaming)",
            ["model", "endpoint"],
            buckets=REQUEST_BUCKETS,
            registry=self.registry,
        )
        self.itl = Histogram(
            f"{ns}_inter_token_latency_seconds",
            "Inter-token latency (streaming)",
            ["model", "endpoint"],
            buckets=TOKEN_BUCKETS,
            registry=self.registry,
        )
        self.output_tokens = Counter(
            f"{ns}_output_tokens_total",
            "Total output tokens produced",
            ["model", "endpoint"],
            registry=self.registry,
        )
        # Rolling-window percentile gauges (the planner's SLO input): the
        # histograms above accumulate since start; these answer "now".
        self.ttft_p50_gauge = Gauge(
            f"{ns}_ttft_p50_seconds",
            "Rolling-window TTFT p50",
            ["model", "endpoint"],
            registry=self.registry,
        )
        self.ttft_p95_gauge = Gauge(
            f"{ns}_ttft_p95_seconds",
            "Rolling-window TTFT p95",
            ["model", "endpoint"],
            registry=self.registry,
        )
        self.itl_p50_gauge = Gauge(
            f"{ns}_itl_p50_seconds",
            "Rolling-window inter-token-latency p50",
            ["model", "endpoint"],
            registry=self.registry,
        )
        self.itl_p95_gauge = Gauge(
            f"{ns}_itl_p95_seconds",
            "Rolling-window inter-token-latency p95",
            ["model", "endpoint"],
            registry=self.registry,
        )
        # (model, endpoint) → (ttft window, itl window)
        self._windows: Dict[Tuple[str, str], Tuple[RollingWindow, RollingWindow]] = {}
        # Age-bounded TTFT window across all models: the brownout ladder's
        # latency signal (llm/qos.py) — must DRAIN when the spike ends,
        # which the count-bounded windows above deliberately do not.
        self.ttft_recent = TimedWindow(max_age_s=10.0)

    def recent_ttft_p95_ms(self) -> Optional[float]:
        """p95 TTFT over the last ``ttft_recent.max_age_s`` seconds, or
        None when no request produced a first token in that span."""
        p = self.ttft_recent.percentile(0.95)
        return None if p is None else p * 1e3

    def window(self, model: str, endpoint: str) -> Tuple[RollingWindow, RollingWindow]:
        key = (model, endpoint)
        if key not in self._windows:
            self._windows[key] = (RollingWindow(), RollingWindow())
        return self._windows[key]

    def guard(self, model: str, endpoint: str, request_type: str) -> "InflightGuard":
        return InflightGuard(self, model, endpoint, request_type)

    def _update_quantile_gauges(self) -> None:
        for (model, endpoint), (ttft_w, itl_w) in self._windows.items():
            self.ttft_p50_gauge.labels(model, endpoint).set(ttft_w.percentile(0.5))
            self.ttft_p95_gauge.labels(model, endpoint).set(ttft_w.percentile(0.95))
            self.itl_p50_gauge.labels(model, endpoint).set(itl_w.percentile(0.5))
            self.itl_p95_gauge.labels(model, endpoint).set(itl_w.percentile(0.95))

    def edge_slo_snapshot(self) -> Dict[str, float]:
        """Merged-over-models rolling percentiles in ms (what the edge
        publishes to the planner on the ``slo_metrics`` subject)."""
        ttft_all = RollingWindow(maxlen=4096)
        itl_all = RollingWindow(maxlen=4096)
        for ttft_w, itl_w in self._windows.values():
            for x in ttft_w._xs:
                ttft_all.observe(x)
            for x in itl_w._xs:
                itl_all.observe(x)
        return {
            "ttft_p50_ms": ttft_all.percentile(0.5) * 1e3,
            "ttft_p95_ms": ttft_all.percentile(0.95) * 1e3,
            "itl_p50_ms": itl_all.percentile(0.5) * 1e3,
            "itl_p95_ms": itl_all.percentile(0.95) * 1e3,
            "ttft_samples": float(len(ttft_all)),
            "itl_samples": float(len(itl_all)),
        }

    def render(self) -> bytes:
        self._update_quantile_gauges()
        return generate_latest(self.registry)


class SpecDecodeMetrics:
    """Speculative-decoding counters + derived gauges (engine/spec.py).

    Module-level singleton rendered as Prometheus text and appended to the
    ``/metrics`` exposition (same pattern as runtime.resilience.metrics /
    planner.pmetrics) — dependency-free so the engine layer can update it
    without touching the prometheus_client registry."""

    def __init__(self):
        self.drafted_total = 0  # draft tokens submitted for verification
        self.accepted_total = 0  # draft tokens accepted
        self.emitted_total = 0  # tokens committed by spec dispatches (incl. bonus)
        self.dispatches_total = 0  # unified verification dispatches
        self.fallback_total = 0  # plans where spec stood down for the fused pipeline

    @property
    def acceptance_rate(self) -> float:
        return (
            self.accepted_total / self.drafted_total
            if self.drafted_total
            else 0.0
        )

    @property
    def tokens_per_dispatch(self) -> float:
        return (
            self.emitted_total / self.dispatches_total
            if self.dispatches_total
            else 0.0
        )

    def reset(self) -> None:
        self.__init__()

    def snapshot(self) -> Dict[str, float]:
        return {
            "drafted_total": float(self.drafted_total),
            "accepted_total": float(self.accepted_total),
            "emitted_total": float(self.emitted_total),
            "dispatches_total": float(self.dispatches_total),
            "fallback_total": float(self.fallback_total),
            "acceptance_rate": self.acceptance_rate,
            "tokens_per_dispatch": self.tokens_per_dispatch,
        }

    def render(self, prefix: str = "dynamo_tpu") -> str:
        ns = f"{prefix}_spec_decode"
        lines = []

        def emit(name: str, kind: str, help_: str, value) -> None:
            lines.append(f"# HELP {ns}_{name} {help_}")
            lines.append(f"# TYPE {ns}_{name} {kind}")
            lines.append(f"{ns}_{name} {value}")

        emit("drafted_tokens_total", "counter",
             "Draft tokens submitted for in-step verification",
             self.drafted_total)
        emit("accepted_tokens_total", "counter",
             "Draft tokens accepted (sampled-stream match)",
             self.accepted_total)
        emit("emitted_tokens_total", "counter",
             "Tokens committed by speculative dispatches (incl. the bonus "
             "sample)", self.emitted_total)
        emit("dispatches_total", "counter",
             "Unified verification dispatches", self.dispatches_total)
        emit("fallback_total", "counter",
             "Plans where speculation stood down for the fused pipeline",
             self.fallback_total)
        emit("acceptance_rate", "gauge",
             "accepted/drafted since start", round(self.acceptance_rate, 6))
        emit("tokens_per_dispatch", "gauge",
             "Committed tokens per verification dispatch",
             round(self.tokens_per_dispatch, 6))
        return "\n".join(lines) + "\n"


spec_metrics = SpecDecodeMetrics()


class MigrationMetrics:
    """Live-sequence-migration counters (llm/migration).

    Module-level singleton rendered as Prometheus text and appended to the
    ``/metrics`` exposition (same pattern as ``spec_metrics``): the worker
    process updates plain attributes; no registry dependency."""

    def __init__(self):
        self.started_total = 0       # migrate_out attempts begun
        self.completed_total = 0     # cutovers that landed
        self.rolled_back_total = 0   # phase-2 failures (source kept authority)
        self.aborted_total = 0       # phase-1 aborts (seq finished / target cold)
        self.migrated_in_total = 0   # commits accepted on the target side
        self.blocks_total = 0        # KV blocks pushed (phase 1 + final delta)
        self.bytes_total = 0         # payload bytes pushed
        self.cutover_pause_ms = RollingWindow(maxlen=512)  # freeze→cutover wall

    def reset(self) -> None:
        self.__init__()

    def snapshot(self) -> Dict[str, float]:
        return {
            "started_total": float(self.started_total),
            "completed_total": float(self.completed_total),
            "rolled_back_total": float(self.rolled_back_total),
            "aborted_total": float(self.aborted_total),
            "migrated_in_total": float(self.migrated_in_total),
            "blocks_total": float(self.blocks_total),
            "bytes_total": float(self.bytes_total),
            "cutover_pause_ms_p50": self.cutover_pause_ms.percentile(0.5),
            "cutover_pause_ms_p95": self.cutover_pause_ms.percentile(0.95),
        }

    def render(self, prefix: str = "dynamo_tpu") -> str:
        ns = f"{prefix}_migration"
        lines = []

        def emit(name: str, kind: str, help_: str, value) -> None:
            lines.append(f"# HELP {ns}_{name} {help_}")
            lines.append(f"# TYPE {ns}_{name} {kind}")
            lines.append(f"{ns}_{name} {value}")

        emit("started_total", "counter",
             "Live migrations begun (source side)", self.started_total)
        emit("completed_total", "counter",
             "Live migrations cut over successfully", self.completed_total)
        emit("rolled_back_total", "counter",
             "Migrations rolled back in the final-delta phase "
             "(source stayed authoritative)", self.rolled_back_total)
        emit("aborted_total", "counter",
             "Migrations abandoned in the copy phase", self.aborted_total)
        emit("migrated_in_total", "counter",
             "Migration commits accepted (target side)",
             self.migrated_in_total)
        emit("kv_blocks_total", "counter",
             "KV blocks pushed by migrations", self.blocks_total)
        emit("kv_bytes_total", "counter",
             "KV payload bytes pushed by migrations", self.bytes_total)
        emit("cutover_pause_ms_p50", "gauge",
             "Rolling p50 of the freeze-to-cutover pause",
             round(self.cutover_pause_ms.percentile(0.5), 3))
        emit("cutover_pause_ms_p95", "gauge",
             "Rolling p95 of the freeze-to-cutover pause",
             round(self.cutover_pause_ms.percentile(0.95), 3))
        return "\n".join(lines) + "\n"


migration_metrics = MigrationMetrics()


class TenancyMetrics:
    """Multi-tenancy counters (llm/tenancy): grammar-constrained decoding +
    batched multi-LoRA.  Module-level singleton rendered as Prometheus text
    and appended to ``/metrics`` (same pattern as ``spec_metrics``)."""

    def __init__(self):
        # structured output
        self.grammar_requests_total = 0   # requests carrying a constraint
        self.grammar_compiles_total = 0   # automaton compiles (cache misses)
        self.grammar_cache_hits_total = 0
        self.grammar_masked_rows_total = 0  # device rows sampled under a mask
        self.grammar_violations_total = 0   # defensive: inadmissible accepts
        # hash-first wire protocol (engine content-hash LRU)
        self.grammar_hash_hits_total = 0    # stubs resolved with zero bytes
        self.grammar_hash_misses_total = 0  # stubs that forced a full resend
        self.grammar_full_resends_total = 0  # preprocessor-side fallbacks
        self.grammar_stub_dispatches_total = 0  # stubs accepted first try
        # multi-LoRA
        self.adapters_registered = 0      # gauge: host-pool size
        self.adapter_promotions = 0       # host→device slot writes
        self.adapter_evictions = 0        # resident slots reclaimed
        self.adapter_requests_total = 0   # requests routed to an adapter
        self.adapter_not_found_total = 0  # unknown-model rejections

    def reset(self) -> None:
        self.__init__()

    def snapshot(self) -> Dict[str, float]:
        return {k: float(v) for k, v in vars(self).items()}

    def render(self, prefix: str = "dynamo_tpu") -> str:
        ns = f"{prefix}_tenancy"
        lines = []

        def emit(name: str, kind: str, help_: str, value) -> None:
            lines.append(f"# HELP {ns}_{name} {help_}")
            lines.append(f"# TYPE {ns}_{name} {kind}")
            lines.append(f"{ns}_{name} {value}")

        emit("grammar_requests_total", "counter",
             "Requests with a structured-output constraint",
             self.grammar_requests_total)
        emit("grammar_compiles_total", "counter",
             "Token-mask automaton compiles (cache misses)",
             self.grammar_compiles_total)
        emit("grammar_cache_hits_total", "counter",
             "Constraint compile-cache hits", self.grammar_cache_hits_total)
        emit("grammar_masked_rows_total", "counter",
             "Device rows sampled under a grammar mask",
             self.grammar_masked_rows_total)
        emit("grammar_violations_total", "counter",
             "Accepted tokens the mask should have forbidden (defensive; "
             "always 0)", self.grammar_violations_total)
        emit("grammar_hash_hits_total", "counter",
             "Hash-only grammar stubs resolved from the engine LRU",
             self.grammar_hash_hits_total)
        emit("grammar_hash_misses_total", "counter",
             "Hash-only grammar stubs that forced a full-table resend",
             self.grammar_hash_misses_total)
        emit("grammar_full_resends_total", "counter",
             "Constrained dispatches that fell back to the full edge table",
             self.grammar_full_resends_total)
        emit("grammar_stub_dispatches_total", "counter",
             "Constrained dispatches served hash-only end to end",
             self.grammar_stub_dispatches_total)
        emit("lora_adapters_registered", "gauge",
             "Adapters in the host pool", self.adapters_registered)
        emit("lora_promotions_total", "counter",
             "Adapter host-to-device slot promotions", self.adapter_promotions)
        emit("lora_evictions_total", "counter",
             "Resident adapter slots reclaimed", self.adapter_evictions)
        emit("lora_requests_total", "counter",
             "Requests served through a LoRA adapter",
             self.adapter_requests_total)
        emit("lora_model_not_found_total", "counter",
             "Requests naming an unregistered model/adapter",
             self.adapter_not_found_total)
        return "\n".join(lines) + "\n"


tenancy_metrics = TenancyMetrics()


class QosMetrics:
    """QoS/overload-control counters (llm/qos.py): per-tenant quota sheds,
    brownout rung + transitions, priority sheds.  Module-level singleton
    rendered as Prometheus text and appended to ``/metrics`` (same pattern
    as ``spec_metrics``)."""

    def __init__(self):
        self.brownout_rung = 0  # gauge: current ladder rung
        self.brownout_transitions_total = 0
        self.quota_shed_total = 0       # 429s from tenant token buckets
        self.batch_shed_total = 0       # rung-3 batch-class sheds
        self.interactive_shed_total = 0  # rung-4 interactive overflow 503s
        self.capped_requests_total = 0  # rung-1 max_tokens caps applied
        self.spec_standdowns_total = 0  # rung-2 spec-decode opt-outs applied
        # tenant → sheds (bounded: the render sorts and truncates)
        self.shed_by_tenant: Dict[str, int] = {}

    def shed_tenant(self, tenant: str) -> None:
        if len(self.shed_by_tenant) < 256 or tenant in self.shed_by_tenant:
            self.shed_by_tenant[tenant] = self.shed_by_tenant.get(tenant, 0) + 1

    def reset(self) -> None:
        self.__init__()

    def snapshot(self) -> Dict[str, float]:
        return {
            k: float(v) for k, v in vars(self).items() if isinstance(v, (int, float))
        }

    def render(self, prefix: str = "dynamo_tpu") -> str:
        ns = f"{prefix}_qos"
        lines = []

        def emit(name: str, kind: str, help_: str, value) -> None:
            lines.append(f"# HELP {ns}_{name} {help_}")
            lines.append(f"# TYPE {ns}_{name} {kind}")
            lines.append(f"{ns}_{name} {value}")

        emit("brownout_rung", "gauge",
             "Current brownout ladder rung (0=normal .. 4=shed-interactive)",
             self.brownout_rung)
        emit("brownout_transitions_total", "counter",
             "Brownout rung transitions", self.brownout_transitions_total)
        emit("quota_shed_total", "counter",
             "Requests shed by tenant token buckets (429)",
             self.quota_shed_total)
        emit("batch_shed_total", "counter",
             "Batch-class requests shed by brownout rung >= 3",
             self.batch_shed_total)
        emit("interactive_shed_total", "counter",
             "Interactive requests shed at rung 4 (admission saturated)",
             self.interactive_shed_total)
        emit("capped_requests_total", "counter",
             "Requests with max_tokens capped by brownout rung >= 1",
             self.capped_requests_total)
        emit("spec_standdowns_total", "counter",
             "Requests with spec-decode stood down by brownout rung >= 2",
             self.spec_standdowns_total)
        lines.append(f"# HELP {ns}_shed_by_tenant_total Sheds per tenant")
        lines.append(f"# TYPE {ns}_shed_by_tenant_total counter")
        for tenant, n in sorted(self.shed_by_tenant.items()):
            # Tenant ids come off the wire (x-tenant header): escape the
            # Prometheus label syntax so a crafted id cannot inject rows
            # into the exposition.  (Credential-sourced ids are already
            # hashed at resolution — llm/qos.py resolve_tenant.)
            safe = escape_label(tenant)
            lines.append(f'{ns}_shed_by_tenant_total{{tenant="{safe}"}} {n}')
        return "\n".join(lines) + "\n"


qos_metrics = QosMetrics()


class EngineDispatchMetrics:
    """Decode-pipeline dispatch health (engine/pipeline.py): per-kind
    dispatch counts/wall/percentiles from the engine's step_trace, plus the
    continuous-batching session gauges (sessions, rebuilds, in-loop
    admissions/retirements, fused-loop host-gap fraction).

    The engine owns the trace, so this singleton holds a SOURCE callable
    (``engine.dispatch_summary``) wired by whoever colocates an engine with
    the HTTP edge (cli ``run in=http out=tpu`` — same pattern as the
    brownout ladder's ``kv_usage_fn``); rendered as Prometheus text and
    appended to ``/metrics`` like the other module singletons.  Without a
    source it renders nothing, so remote-engine edges are unaffected."""

    def __init__(self):
        self._source = None

    def set_source(self, source) -> None:
        """``source() -> engine.dispatch_summary()`` dict, or None to
        detach."""
        self._source = source

    def reset(self) -> None:
        self.__init__()

    def host_gap_frac(self) -> Optional[float]:
        """The colocated engine's fused-decode host-gap fraction, or None
        without a wired source (remote-engine edge) — the planner-side
        drift signal (EdgeSloPublisher ``host_gap``)."""
        if self._source is None:
            return None
        try:
            s = self._source()
        except Exception:  # noqa: BLE001 — engine mid-teardown
            return None
        gap = (s.get("pipeline") or {}).get("host_gap_frac")
        return float(gap) if isinstance(gap, (int, float)) else None

    def render(self, prefix: str = "dynamo_tpu") -> str:
        if self._source is None:
            return ""
        try:
            s = self._source()
        except Exception:  # engine mid-teardown: drop this scrape's section
            return ""
        ns = f"{prefix}_engine_dispatch"
        # Per-kind stats come from the engine's BOUNDED step_trace window
        # (deque maxlen) — they can shrink as old entries evict, so they
        # are gauges, never counters (a decreasing counter breaks rate()).
        lines = [
            f"# HELP {ns}_window_dispatches Device dispatches per step "
            "kind over the bounded trace window",
            f"# TYPE {ns}_window_dispatches gauge",
        ]
        kinds = sorted(s.get("kinds", {}).items())
        for kind, v in kinds:
            lines.append(
                f'{ns}_window_dispatches{{kind="{escape_label(kind)}"}} '
                f'{v["dispatches"]}'
            )
        lines.append(f"# HELP {ns}_window_wall_seconds Wall per step kind "
                     "over the bounded trace window")
        lines.append(f"# TYPE {ns}_window_wall_seconds gauge")
        for kind, v in kinds:
            lines.append(f'{ns}_window_wall_seconds{{kind="'
                         f'{escape_label(kind)}"}} {v["wall_s"]}')
        for q in ("p50", "p99"):
            lines.append(f"# HELP {ns}_{q}_ms {q} dispatch latency per "
                         "step kind (over the bounded trace window)")
            lines.append(f"# TYPE {ns}_{q}_ms gauge")
            for kind, v in kinds:
                lines.append(f'{ns}_{q}_ms{{kind="{escape_label(kind)}"}} '
                             f'{v[f"{q}_ms"]}')
        pipe = s.get("pipeline", {})

        def emit(name: str, kind: str, help_: str, value) -> None:
            lines.append(f"# HELP {ns}_{name} {help_}")
            lines.append(f"# TYPE {ns}_{name} {kind}")
            lines.append(f"{ns}_{name} {value}")

        emit("pipeline_sessions_total", "counter",
             "Fused decode pipeline sessions begun",
             pipe.get("sessions", 0))
        emit("pipeline_rebuilds_total", "counter",
             "Sessions drained by a rebuild event (incompatible change)",
             pipe.get("rebuilds", 0))
        emit("continuous_admissions_total", "counter",
             "Sequences admitted into a live fused session (no drain)",
             pipe.get("continuous_admissions", 0))
        emit("continuous_retired_total", "counter",
             "Rows retired from a live fused session (no drain)",
             pipe.get("continuous_retired", 0))
        emit("pipeline_wall_seconds_total", "counter",
             "Cumulative fused-session wall time",
             pipe.get("wall_s", 0.0))
        emit("host_gap_frac", "gauge",
             "Fraction of fused-session wall in which the loop was in no "
             "harvest:* phase (doing work of its own, not waiting for the "
             "device)", pipe.get("host_gap_frac", 0.0))
        # The loop's account of its own time (engine/phases.py; the table
        # of phases: docs/tracing.md).  Two histograms, kept apart: the
        # loop's phases tile its time, the worker threads' calls lie inside
        # them.  OUTSIDE the _dispatch ns, like the counters below.
        acct = s.get("phases") or {}
        les = [repr(le) for le in acct.get("le", ())] + ["+Inf"]
        for table, name, label, help_ in (
            ("loop", "loop_phase", "phase",
             "Engine loop time by phase; over a fused session the phases "
             "sum to its wall"),
            ("calls", "device_call", "call",
             "Worker-thread calls inside the loop's phases: the jitted "
             "call that enqueues a program, the token fetch's copy"),
        ):
            hn = f"{prefix}_engine_{name}_seconds"
            lines.append(f"# HELP {hn} {help_}")
            lines.append(f"# TYPE {hn} histogram")
            for value, row in (acct.get(table) or {}).items():
                seen = 0
                for le, n in zip(les, row["buckets"]):
                    seen += n
                    lines.append(
                        f'{hn}_bucket{{{label}="{escape_label(value)}",'
                        f'le="{escape_label(le)}"}} {seen}'
                    )
                lines.append(
                    f'{hn}_sum{{{label}="{escape_label(value)}"}} {row["sum"]}'
                )
                lines.append(
                    f'{hn}_count{{{label}="{escape_label(value)}"}} '
                    f'{row["count"]}'
                )
        # The first token's path through a session's iteration (engine/
        # pipeline.py _decode_pipeline; docs/decode_pipeline.md).  OUTSIDE
        # the _dispatch ns, like the stall counter below.
        for series, name, label, help_ in (
            ("pipeline_first_harvest", "first_harvest", "at",
             "First-token fetches applied when they landed (during the "
             "wait for a fused chunk) or at an iteration's harvest point"),
            ("pipeline_prompt_step", "prompt_step", "order",
             "In-session prompt steps enqueued ahead of or behind a fused "
             "chunk of the same iteration"),
            ("engine_joins", "joins", "how",
             "Rows that joined a fused decode chain: on the device, behind "
             "their last prompt chunk with the chain unbroken, or at a "
             "chain-break merge from host state"),
        ):
            lines.append(f"# HELP {prefix}_{series}_total {help_}")
            lines.append(f"# TYPE {prefix}_{series}_total counter")
            for value, n in (pipe.get(name) or {}).items():
                lines.append(
                    f'{prefix}_{series}_total'
                    f'{{{label}="{escape_label(value)}"}} {n}'
                )
        # Decode-stall watchdog (decode_stall_s / DYN_DECODE_STALL_S;
        # engine/pipeline.py _await_device).  OUTSIDE the _dispatch ns —
        # the alert rule keys on this exact name.
        lines.append(f"# HELP {prefix}_engine_stall_total Token fetches "
                     "that exceeded the decode-stall threshold")
        lines.append(f"# TYPE {prefix}_engine_stall_total counter")
        lines.append(f"{prefix}_engine_stall_total {pipe.get('stalls', 0)}")
        # Which decode kernel serves this engine (info-style gauge).
        kern = s.get("decode_kernel", "")
        if kern:
            lines.append(f"# HELP {ns}_decode_kernel_info Active decode "
                         "attention kernel (DYN_DECODE_KERNEL)")
            lines.append(f"# TYPE {ns}_decode_kernel_info gauge")
            lines.append(
                f'{ns}_decode_kernel_info{{kernel="{escape_label(kern)}"}} 1'
            )
        # What its dots take (set where the kernel is built:
        # ops/decode_attention.py operand_dtype).
        operands = s.get("decode_kernel_operands", "")
        if operands:
            lines.append(f"# HELP {ns}_decode_kernel_operands_info Operand "
                         "type of the fused decode kernel's two dots")
            lines.append(f"# TYPE {ns}_decode_kernel_operands_info gauge")
            lines.append(
                f'{ns}_decode_kernel_operands_info'
                f'{{operands="{escape_label(operands)}"}} 1'
            )
        pkern = s.get("prefill_kernel", "")
        if pkern:
            lines.append(f"# HELP {ns}_prefill_kernel_info Active prefill "
                         "attention kernel (DYN_PREFILL_KERNEL)")
            lines.append(f"# TYPE {ns}_prefill_kernel_info gauge")
            lines.append(
                f'{ns}_prefill_kernel_info{{kernel="{escape_label(pkern)}"}} 1'
            )
        # What the engine runs on and what warmup cost (engine.
        # device_summary) — OUTSIDE the _dispatch ns, like the stall counter.
        dev = s.get("device")
        if dev:
            en = f"{prefix}_engine"
            labels = ",".join(
                f'{k}="{escape_label(str(dev[k]))}"'
                for k in (
                    "jax", "libtpu", "platform", "device_kind",
                    "device_count", "model", "num_layers", "weight_quant",
                    "cache_dtype", "cache_kinds", "attn_impl", "hasher",
                    "compile_cache_dir",
                )
            )
            lines.append(f"# HELP {en}_info Versions, device, model and "
                         "attention backend of the serving process")
            lines.append(f"# TYPE {en}_info gauge")
            lines.append(f"{en}_info{{{labels}}} 1")
            lines.append(f"# HELP {en}_warmup_seconds Wall of engine warmup "
                         "(the warm:* phases of the start's account)")
            lines.append(f"# TYPE {en}_warmup_seconds gauge")
            lines.append(f"{en}_warmup_seconds {dev['warmup_s']}")
            # The start's account (engine/phases.py SETUP_PHASES): the
            # phases tile process start to ready; static once ready.
            setup = dev.get("setup")
            if setup:
                lines.append(f"# HELP {en}_setup_phase_seconds Wall of one "
                             "phase of the start; the phases tile process "
                             "start to ready (docs/tracing.md)")
                lines.append(f"# TYPE {en}_setup_phase_seconds gauge")
                for phase, row in setup["phases"].items():
                    lines.append(
                        f'{en}_setup_phase_seconds{{phase="{escape_label(phase)}"}} '
                        f"{row['sum']}"
                    )
                lines.append(f"# HELP {en}_setup_seconds Process start to "
                             "ready: the sum of the start's phases closed so far")
                lines.append(f"# TYPE {en}_setup_seconds gauge")
                lines.append(f"{en}_setup_seconds {setup['seconds']}")
                lines.append(f"# HELP {prefix}_process_start_time_seconds "
                             "Start of the process since the epoch, as the "
                             "operating system has it")
                lines.append(f"# TYPE {prefix}_process_start_time_seconds gauge")
                lines.append(f"{prefix}_process_start_time_seconds "
                             f"{setup['process_start_time']}")
            lines.append(f"# HELP {en}_compiled_programs Compiled programs "
                         "per jitted entry (must not grow after warmup)")
            lines.append(f"# TYPE {en}_compiled_programs gauge")
            for fn, n in sorted(dev["compile_counts"].items()):
                lines.append(
                    f'{en}_compiled_programs{{fn="{escape_label(fn)}"}} {n}'
                )
            lines.append(f"# HELP {en}_compile_cache_entries Files in the "
                         "persistent compilation cache directory")
            lines.append(f"# TYPE {en}_compile_cache_entries gauge")
            lines.append(
                f"{en}_compile_cache_entries {dev['compile_cache_entries']}"
            )
            for key, what in (
                ("compile_cache_hits", "executables read back from the "
                 "persistent compilation cache"),
                ("compile_cache_misses", "executables compiled anew and "
                 "written to the persistent compilation cache"),
            ):
                lines.append(f"# HELP {en}_{key} Events of this process: "
                             f"{what} (0 without a cache directory)")
                lines.append(f"# TYPE {en}_{key} gauge")
                lines.append(f"{en}_{key} {dev[key]}")
            # JAX's own seconds by stage for the whole life of the process
            # (engine/xla_cache.py): work on several threads, not wall.
            stages = dev.get("jax_compile")
            if stages:
                for key, what in (("seconds", "Seconds of work"), ("events", "Events")):
                    name = f"{en}_jax_compile_{key}_total"
                    lines.append(f"# HELP {name} {what} JAX reported by "
                                 "stage of compiling (work on several threads, "
                                 "not wall; backend_compile holds cache_retrieval)")
                    lines.append(f"# TYPE {name} counter")
                    for stage, row in stages.items():
                        lines.append(
                            f'{name}{{stage="{escape_label(stage)}"}} {row[key]}'
                        )
            for key in ("hbm_bytes_in_use", "hbm_bytes_limit"):
                lines.append(f"# HELP {en}_{key} Device memory_stats() "
                             "per local device (0 where not reported)")
                lines.append(f"# TYPE {en}_{key} gauge")
                for i, v in enumerate(dev[key]):
                    lines.append(
                        f'{en}_{key}{{device="{escape_label(str(i))}"}} {v}'
                    )
        # Prefill-chunk latency summary (engine.prefill_summary): cumulative
        # _sum/_count are true counters; the quantiles come from the
        # bounded per-chunk trace window (gauges in counter clothing, same
        # caveat as the per-kind stats above).  OUTSIDE the _dispatch ns —
        # the CI gate and loadgen scrape key on this exact name.
        pf = s.get("prefill", {})
        if pf:
            pn = f"{prefix}_prefill_chunk_seconds"
            lines.append(f"# HELP {pn} Wall of ENQUEUEING a prefill chunk's "
                         "asynchronous dispatch (not the chunk's device time)")
            lines.append(f"# TYPE {pn} summary")
            for q, key in (("0.5", "p50_ms"), ("0.99", "p99_ms")):
                lines.append(
                    f'{pn}{{quantile="{escape_label(q)}"}} '
                    f"{pf.get(key, 0.0) / 1e3}"
                )
            lines.append(f"{pn}_sum {pf.get('wall_s', 0.0)}")
            lines.append(f"{pn}_count {pf.get('chunks', 0)}")
            lines.append(
                f"# HELP {prefix}_prefill_tokens_total Prompt tokens "
                "computed by prefill chunks")
            lines.append(f"# TYPE {prefix}_prefill_tokens_total counter")
            lines.append(
                f"{prefix}_prefill_tokens_total {pf.get('prompt_tokens', 0)}"
            )
        return "\n".join(lines) + "\n"


engine_dispatch_metrics = EngineDispatchMetrics()


class SparseModelMetrics:
    """Counters of the latent family with its held experts
    (models/deepseek_v32.py; docs/tracing.md).  The attention's account is
    host arithmetic on lengths the scheduler holds: the selector's where the
    model has one (``add_dsa``), else that of attention over the whole
    context (``add_mla``); the expert account rides home with the sampled
    tokens (``add_moe``).  The engine reaches them through its model family
    only (models/family.py ``count_dispatch`` / ``count_aux`` / ``counts``).
    Renders nothing until a model that has them ran."""

    def __init__(self):
        self.dsa: Dict[str, list] = {}  # dispatch kind -> [context, selected]
        self.dsa_prefill_tokens: Dict[str, int] = {}  # attention form -> query tokens
        self.mla: Dict[str, list] = {}  # dispatch kind -> [attended, query tokens]
        self.moe_local_pairs = 0
        self.moe_routed_tokens = 0
        self.moe_experts_read = 0
        self.moe_experts_held = 0
        self.conv_row_starts = {"zero": 0, "tail": 0}  # prompt-chunk rows by their start
        self.conv_tokens = 0
        self.kda_tokens: Dict[str, int] = {}  # form -> tokens
        self.mamba1_tokens: Dict[str, int] = {}  # form -> tokens

    def reset(self) -> None:
        self.__init__()

    def add_kda(self, form: str, ns) -> None:
        """Add a dispatch's tokens to the KDA layers' account
        (models/kda.py) under the form they went through: ``scan`` (the
        chunked form, a unified step's rows) or ``step`` (one token a row: a
        fused decode chunk counts its steps)."""
        self._add_tokens(self.kda_tokens, form, ns)

    def add_mamba1(self, form: str, ns) -> None:
        """The same account for the Mamba-1 layers (models/mamba1.py)."""
        self._add_tokens(self.mamba1_tokens, form, ns)

    @staticmethod
    def _add_tokens(acc: Dict[str, int], form: str, ns) -> None:
        acc[form] = acc.get(form, 0) + sum(max(0, int(n)) for n in ns)

    def add_dsa(self, kind: str, topk: int, starts, ns, prefill_form: Optional[str] = None) -> None:
        """Add a dispatch's query tokens to the selector's account: the token
        at position t has t + 1 context positions and keeps min(topk, t + 1).
        ``starts[i]``, ``ns[i]``: first position and token count of row i.
        ``prefill_form``: the form in which the dispatched PROMPT program
        attends (ops/sparse_mla.py ``prefill_form``); the tokens of its rows
        of more than one token (the others are the one-query kernel's) are
        counted under it."""
        k = topk
        acc = self.dsa.setdefault(kind, [0, 0])
        for start, n in zip(starts, ns):
            start, n = int(start), int(n)
            if n <= 0 or start < 0:
                continue
            if prefill_form is not None and n > 1:
                self.dsa_prefill_tokens[prefill_form] = (
                    self.dsa_prefill_tokens.get(prefill_form, 0) + n)
            acc[0] += n * start + n * (n + 1) // 2
            full = max(0, min(n, start + n - k + 1)) if start + n >= k else 0
            part = n - full  # tokens with t + 1 < k keep all t + 1
            acc[1] += full * k + part * start + part * (part + 1) // 2

    def add_mla(self, kind: str, starts, ns) -> None:
        """Add a dispatch's query tokens to the account of latent attention
        WITHOUT a selector: the token at position t attends to all t + 1
        positions its row holds.  Arguments as ``add_dsa``."""
        acc = self.mla.setdefault(kind, [0, 0])
        for start, n in zip(starts, ns):
            start, n = int(start), int(n)
            if n <= 0 or start < 0:
                continue
            acc[0] += n * start + n * (n + 1) // 2
            acc[1] += n

    def add_conv(self, kind: str, starts, ns) -> None:
        """Add a dispatch to the account of the short-convolution layers
        (models/lfm2.py): its tokens, and its PROMPT-CHUNK rows by where
        their first token's convolution started: from zeros (position 0) or
        from a page's entry (a prefix hit or a later chunk).  A row of a
        ``unified`` dispatch is a prompt chunk if it has several tokens or
        starts at 0: a single token further on is, by the lengths the host
        holds, a decode row riding the step.  Arguments as ``add_dsa``."""
        for start, n in zip(starts, ns):
            start, n = int(start), int(n)
            if n <= 0 or start < 0:
                continue
            self.conv_tokens += n
            if kind == "unified" and (n > 1 or start == 0):
                self.conv_row_starts["zero" if start == 0 else "tail"] += 1

    def summary(self) -> Dict[str, Any]:
        """The accounts as ``dispatch_summary()["model"]``."""
        return {"dsa": {k: list(v) for k, v in self.dsa.items()},
                "dsa_prefill_tokens": dict(self.dsa_prefill_tokens),
                "mla": {k: list(v) for k, v in self.mla.items()},
                "conv_row_starts": dict(self.conv_row_starts),
                "conv_tokens": self.conv_tokens,
                "kda_tokens": dict(self.kda_tokens),
                "mamba1_tokens": dict(self.mamba1_tokens),
                "moe_local_pairs": self.moe_local_pairs,
                "moe_routed_tokens": self.moe_routed_tokens,
                "moe_experts_read": self.moe_experts_read,
                "moe_experts_held": self.moe_experts_held}

    def add_moe(self, aux) -> None:
        """``aux``: int array [..., 4] of (pairs on held experts, tokens
        routed, held experts with such a pair, experts held), each summed
        over a step's expert layers."""
        a = aux.reshape(-1, 4).sum(axis=0)
        self.moe_local_pairs += int(a[0])
        self.moe_routed_tokens += int(a[1])
        self.moe_experts_read += int(a[2])
        self.moe_experts_held += int(a[3])

    def render(self, prefix: str = "dynamo_tpu") -> str:
        if not (self.dsa or self.mla or self.moe_routed_tokens or self.mamba1_tokens):
            return ""
        lines = []
        if self.conv_tokens:
            name = f"{prefix}_conv_row_starts_total"
            lines += [f"# HELP {name} Prompt-chunk rows dispatched to the short-convolution "
                      "layers, by whether their first token started from zeros (position 0) "
                      "or from a page's entry (a prefix hit or a later chunk)",
                      f"# TYPE {name} counter"]
            lines += [f'{name}{{state="{escape_label(k)}"}} {v}'
                      for k, v in self.conv_row_starts.items()]
            name = f"{prefix}_conv_tokens_total"
            lines += [f"# HELP {name} Tokens dispatched through the short-convolution layers "
                      "(a fused decode chunk counts its steps)",
                      f"# TYPE {name} counter", f"{name} {self.conv_tokens}"]
        for mixer, layers, acc in (("kda", "Kimi Delta Attention", self.kda_tokens),
                                   ("mamba1", "Mamba-1", self.mamba1_tokens)):
            if not acc:
                continue
            name = f"{prefix}_{mixer}_tokens_total"
            lines += [f"# HELP {name} Tokens dispatched through the {layers} layers, "
                      "by form: scan (the chunked form of a unified step) or step (one token a "
                      "row; a fused decode chunk counts its steps)",
                      f"# TYPE {name} counter"]
            lines += [f'{name}{{form="{escape_label(k)}"}} {v}' for k, v in sorted(acc.items())]
        for acc, series in (
            (self.dsa, (
                ("dsa_context_positions_total",
                 "Positions s <= t summed over the query tokens dispatched"),
                ("dsa_selected_positions_total",
                 "min(index_topk, t + 1) summed over the query tokens dispatched"))),
            (self.mla, (
                ("mla_attended_positions_total",
                 "Positions s <= t a query token attends to (no selector), summed over the "
                 "query tokens dispatched"),
                ("mla_query_tokens_total",
                 "Query tokens dispatched to latent attention without a selector"))),
        ):
            if not acc:
                continue
            for i, (name, help_) in enumerate(series):
                lines.append(f"# HELP {prefix}_{name} {help_}")
                lines.append(f"# TYPE {prefix}_{name} counter")
                for kind, v in sorted(acc.items()):
                    lines.append(f'{prefix}_{name}{{kind="{escape_label(kind)}"}} {v[i]}')
        if self.dsa_prefill_tokens:
            name = f"{prefix}_dsa_prefill_query_tokens_total"
            lines += [f"# HELP {name} Query tokens of prompt-chunk rows (more than one token) "
                      "dispatched to sparse latent attention, by the form the dispatched "
                      "program attends in: decompressed (the Pallas call "
                      "mla_sparse_prefill_attention) or absorbed (the XLA loop)",
                      f"# TYPE {name} counter"]
            lines += [f'{name}{{form="{escape_label(k)}"}} {v}'
                      for k, v in sorted(self.dsa_prefill_tokens.items())]
        for name, help_, v in (
            ("moe_local_pairs_total",
             "Routed (token, expert) pairs that landed on experts held here",
             self.moe_local_pairs),
            ("moe_routed_tokens_total",
             "Tokens routed, counted once per expert layer", self.moe_routed_tokens),
            ("moe_experts_read_total",
             "Held experts with a landed pair of a real token: the experts whose weights a "
             "step reads, summed over expert layers and steps", self.moe_experts_read),
            ("moe_experts_held_total",
             "Experts held, summed over the same expert layers and steps",
             self.moe_experts_held),
        ):
            lines.append(f"# HELP {prefix}_{name} {help_}")
            lines.append(f"# TYPE {prefix}_{name} counter")
            lines.append(f"{prefix}_{name} {v}")
        return "\n".join(lines) + "\n"


sparse_model_metrics = SparseModelMetrics()


class SsmMetrics:
    """The state slots' account (engine/resume.py ``SlotState``;
    docs/granite_hybrid.md, docs/tracing.md): where admitted requests' state
    came from, what became of block-level hits, and the snapshots' fate.
    Renders nothing until a family with state slots admitted a request."""

    def __init__(self):
        # ONE count a request admitted, or admitted again after preemption.
        self.request_starts = {"zero": 0, "snapshot": 0}
        # Tokens of block-level hits kept, and cut back for want of a snapshot.
        self.hit_tokens = {"resumed": 0, "given_back": 0}
        self.snapshots = {"taken": 0, "no_slot": 0, "evicted": 0}
        self.slots_in_use = {"live": 0, "snapshot": 0}

    def reset(self) -> None:
        self.__init__()

    def add_start(self, matched_tokens: int, resumed_tokens: int) -> None:
        self.request_starts["snapshot" if resumed_tokens else "zero"] += 1
        self.hit_tokens["resumed"] += resumed_tokens
        self.hit_tokens["given_back"] += matched_tokens - resumed_tokens

    def render(self, prefix: str = "dynamo_tpu") -> str:
        if not sum(self.request_starts.values()):
            return ""
        lines = []
        for name, kind, label, help_, acc in (
            ("ssm_request_starts_total", "counter", "state",
             "Requests admitted (or admitted again after preemption) to a family with state "
             "slots, by where their recurrent state started: zeros or a snapshot",
             self.request_starts),
            ("ssm_hit_tokens_total", "counter", "outcome",
             "Tokens of block-level prefix hits: resumed from a snapshot, or given back "
             "(computed again) for want of one", self.hit_tokens),
            ("ssm_snapshots_total", "counter", "outcome",
             "Snapshots of the recurrent state at a resume stride: taken, not taken for want "
             "of a slot, or dropped (pool full, or their block evicted)", self.snapshots),
            ("ssm_slots_in_use", "gauge", "kind",
             "State slots in use: live (running rows) and snapshot", self.slots_in_use),
        ):
            lines += [f"# HELP {prefix}_{name} {help_}", f"# TYPE {prefix}_{name} {kind}"]
            lines += [f'{prefix}_{name}{{{label}="{escape_label(k)}"}} {v}' for k, v in acc.items()]
        return "\n".join(lines) + "\n"


ssm_metrics = SsmMetrics()


class SwaMetrics:
    """The window layers' account (engine/resume.py ``WindowPages``, the second
    page pool; docs/k_exaone.md, docs/tracing.md): host arithmetic
    at dispatch and admission, from lengths the scheduler holds.  Renders
    nothing until a family with window layers dispatched a row."""

    def __init__(self):
        # Window pages held by the rows of each dispatch, and those rows.
        self.window_pages = 0
        self.window_rows = 0
        self.pool_pages = {"live": 0, "retained": 0, "free": 0}
        # Tokens of block-level hits kept, and cut back for want of window pages.
        self.hit_tokens = {"resumed": 0, "cut": 0}
        # Positions a query token attends to in a window layer and in a full
        # layer, summed over the query tokens dispatched (as the mla account).
        self.attended = {"window": 0, "full": 0}
        self.query_tokens = 0

    def reset(self) -> None:
        self.__init__()

    def add_rows(self, pages_held) -> None:
        """``pages_held``: the window pages each row of a dispatch holds."""
        self.window_rows += len(pages_held)
        self.window_pages += sum(pages_held)

    def add_hit(self, matched_tokens: int, resumed_tokens: int) -> None:
        self.hit_tokens["resumed"] += resumed_tokens
        self.hit_tokens["cut"] += matched_tokens - resumed_tokens

    def add_queries(self, window: int, starts, ns) -> None:
        """A dispatch's query tokens: the token at position t attends to
        t + 1 positions in a full layer and to min(t + 1, window) in a window
        layer.  ``starts[i]``, ``ns[i]``: first position and token count of
        row i (a fused chunk counts its steps)."""
        for start, n in zip(starts, ns):
            start, n = int(start), int(n)
            if n <= 0 or start < 0:
                continue
            self.query_tokens += n
            self.attended["full"] += n * start + n * (n + 1) // 2
            short = max(0, min(n, window - 1 - start))  # tokens with t + 1 < window
            self.attended["window"] += (
                short * start + short * (short + 1) // 2 + (n - short) * window)

    def render(self, prefix: str = "dynamo_tpu") -> str:
        if not self.window_rows:
            return ""
        lines = []
        for name, kind, help_, v in (
            ("kv_window_pages_total", "counter",
             "Window pages held by the rows of each dispatch, summed over dispatches",
             self.window_pages),
            ("kv_window_rows_total", "counter",
             "Rows of those dispatches", self.window_rows),
            ("swa_query_tokens_total", "counter",
             "Query tokens dispatched to a model with window layers", self.query_tokens),
        ):
            lines += [f"# HELP {prefix}_{name} {help_}", f"# TYPE {prefix}_{name} {kind}",
                      f"{prefix}_{name} {v}"]
        for name, kind, label, help_, acc in (
            ("kv_window_pool_pages", "gauge", "state",
             "Pages of the window pool: live (a running row's), retained (before a resume "
             "point, with its block's hash, evictable) and free", self.pool_pages),
            ("swa_hit_tokens_total", "counter", "outcome",
             "Tokens of block-level prefix hits: resumed where the window pages before the "
             "point are held, or cut back (computed again) for want of them", self.hit_tokens),
            ("swa_attended_positions_total", "counter", "kind",
             "Positions a query token attends to in a window layer (min(t + 1, window)) and "
             "in a full layer (t + 1), summed over the query tokens dispatched", self.attended),
        ):
            lines += [f"# HELP {prefix}_{name} {help_}", f"# TYPE {prefix}_{name} {kind}"]
            lines += [f'{prefix}_{name}{{{label}="{escape_label(k)}"}} {v}' for k, v in acc.items()]
        return "\n".join(lines) + "\n"


swa_metrics = SwaMetrics()


class RequestHopMetrics:
    """The always-on per-request TTFT/TPOT hop account (docs/tracing.md):
    sums and counts of the intervals between the O(1) stamps a request
    collects on its way through the edge and the engine.  Two folds per
    request — ``fold_engine`` (engine/pipeline.py ``_finish``) and
    ``fold_edge`` (llm/trace_service.py ``EdgeRequestTrace.finish``) — add
    plain floats to a fixed table; names and text exist only in
    ``render``.  Module-level singleton appended to ``/metrics`` (same
    pattern as ``spec_metrics``): ``{prefix}_request_hop_seconds_sum`` /
    ``_count`` per ``hop`` and ``{prefix}_request_hop_incomplete_total``
    per ``side``.  A stamp is 0.0 until taken; a request that ends with
    an engine stamp missing or out of order (cancelled before its first
    token, error, preempted and prefilled again, resumed or migrated
    stream, remote prefill) counts as incomplete and adds to no hop."""

    # In request order; ``edge_pre`` .. ``edge_emit`` sum to ``server_ttft``.
    HOPS = (
        "edge_pre", "queue_wait", "prefill_wait", "prefill_run",
        "first_fetch_device", "first_fetch_harvest", "edge_handoff",
        "edge_emit", "server_ttft", "join_wait",
    )
    (EDGE_PRE, QUEUE_WAIT, PREFILL_WAIT, PREFILL_RUN, FIRST_FETCH_DEVICE,
     FIRST_FETCH_HARVEST, EDGE_HANDOFF, EDGE_EMIT, SERVER_TTFT,
     JOIN_WAIT) = range(len(HOPS))

    def __init__(self):
        self.sums = [0.0] * len(self.HOPS)
        self.counts = [0] * len(self.HOPS)
        self.incomplete_engine = 0
        self.incomplete_edge = 0

    def reset(self) -> None:
        self.__init__()

    # The one clock of the account, the span plane's (runtime/tracing.py).
    now = staticmethod(time.perf_counter)

    def fold_engine(self, t_enqueue: float, t_admit: float,
                    t_first_chunk: float, t_last_chunk: float,
                    t_fetch_done: float, t_first_token: float,
                    t_join: float) -> bool:
        """One finished sequence's engine hops; False = incomplete."""
        if not (0.0 < t_enqueue <= t_admit <= t_first_chunk <= t_last_chunk
                <= t_fetch_done <= t_first_token):
            self.incomplete_engine += 1
            return False
        s, c = self.sums, self.counts
        s[self.QUEUE_WAIT] += t_admit - t_enqueue
        s[self.PREFILL_WAIT] += t_first_chunk - t_admit
        s[self.PREFILL_RUN] += t_last_chunk - t_first_chunk
        s[self.FIRST_FETCH_DEVICE] += t_fetch_done - t_last_chunk
        s[self.FIRST_FETCH_HARVEST] += t_first_token - t_fetch_done
        for i in range(self.QUEUE_WAIT, self.FIRST_FETCH_HARVEST + 1):
            c[i] += 1
        if t_join > 0.0:  # 0.0: never rode a fused dispatch
            # A row that joined on the device rides its first chunk BEFORE
            # its first token is home: it waited for no join.
            s[self.JOIN_WAIT] += max(0.0, t_join - t_first_token)
            c[self.JOIN_WAIT] += 1
        return True

    def fold_edge(self, t_edge: float, t_enqueue: float,
                  t_first_token: float, t_edge_item: float,
                  t_edge_sent: float) -> bool:
        """One finished request's edge hops.  The two hops that cross into
        the engine need its stamps on the shared in-process context; an
        edge in front of a remote engine reports ``edge_emit`` and
        ``server_ttft`` alone.  False = incomplete: no engine item with a
        token reached the Backend operator, or no event was written."""
        if not 0.0 < t_edge_item <= t_edge_sent:
            self.incomplete_edge += 1
            return False
        s, c = self.sums, self.counts
        s[self.SERVER_TTFT] += t_edge_sent - t_edge
        c[self.SERVER_TTFT] += 1
        s[self.EDGE_EMIT] += t_edge_sent - t_edge_item
        c[self.EDGE_EMIT] += 1
        if t_edge <= t_enqueue <= t_first_token <= t_edge_item:
            s[self.EDGE_PRE] += t_enqueue - t_edge
            c[self.EDGE_PRE] += 1
            s[self.EDGE_HANDOFF] += t_edge_item - t_first_token
            c[self.EDGE_HANDOFF] += 1
        return True

    def render(self, prefix: str = "dynamo_tpu") -> str:
        ns = f"{prefix}_request_hop"
        lines = [
            f"# HELP {ns}_seconds_sum Seconds requests spent in each hop "
            "of the TTFT/TPOT account (mean = growth of sum over growth "
            "of count)",
            f"# TYPE {ns}_seconds_sum counter",
        ]
        for hop, v in zip(self.HOPS, self.sums):
            lines.append(
                f'{ns}_seconds_sum{{hop="{escape_label(hop)}"}} {v}')
        lines.append(f"# HELP {ns}_seconds_count Requests folded into "
                     "each hop of the account")
        lines.append(f"# TYPE {ns}_seconds_count counter")
        for hop, n in zip(self.HOPS, self.counts):
            lines.append(
                f'{ns}_seconds_count{{hop="{escape_label(hop)}"}} {n}')
        lines.append(f"# HELP {ns}_incomplete_total Requests that ended "
                     "with a stamp missing or out of order (added to no hop)")
        lines.append(f"# TYPE {ns}_incomplete_total counter")
        lines.append(
            f'{ns}_incomplete_total{{side="engine"}} {self.incomplete_engine}')
        lines.append(
            f'{ns}_incomplete_total{{side="edge"}} {self.incomplete_edge}')
        return "\n".join(lines) + "\n"


request_hop_metrics = RequestHopMetrics()


class KvTierMetrics:
    """Tiered-KV-cache counters + gauges (docs/kv_tiering.md): per-tier
    bytes/blocks, restore/demote/promote/pull activity, restore + pull
    latency percentiles.  Module-level singleton rendered as Prometheus
    text and appended to ``/metrics`` (same pattern as ``spec_metrics``).

    Counters are updated inline by the engine/puller; the per-tier
    bytes/blocks GAUGES come from a source callable
    (``engine.kv_tier_summary`` — wired like EngineDispatchMetrics by
    whoever colocates an engine with the HTTP edge), so remote-engine
    edges render counters only."""

    def __init__(self):
        self._source = None
        # restore path (host/disk → HBM ahead of admission)
        self.restore_hits_total = 0      # requests that restored ≥1 block
        self.restore_misses_total = 0    # tiered restore attempts, 0 blocks
        self.restored_blocks_total = 0   # host→HBM scatters
        self.promoted_blocks_total = 0   # disk→host promotions
        self.prefetched_blocks_total = 0  # promotions driven by kv_prefetch
        # cross-worker pull (llm/kv_router/pull.py)
        self.pulls_started_total = 0
        self.pulls_completed_total = 0
        self.pulls_failed_total = 0      # any degraded-to-local outcome
        self.pulled_blocks_total = 0
        self.pulled_bytes_total = 0
        self.restore_latency_ms = RollingWindow(maxlen=1024)
        self.pull_latency_ms = RollingWindow(maxlen=512)

    def set_source(self, source) -> None:
        """``source() -> engine.kv_tier_summary()`` dict, or None."""
        self._source = source

    def reset(self) -> None:
        self.__init__()

    def tier_summary(self) -> Dict[str, object]:
        """The engine's per-tier gauges ({} without a wired source) —
        shared by render() and the edge SLO publication."""
        if self._source is None:
            return {}
        try:
            return self._source() or {}
        except Exception:  # noqa: BLE001 — engine mid-teardown
            return {}

    def snapshot(self) -> Dict[str, float]:
        out = {
            k: float(v) for k, v in vars(self).items() if isinstance(v, (int, float))
        }
        out["restore_latency_ms_p50"] = self.restore_latency_ms.percentile(0.5)
        out["restore_latency_ms_p99"] = self.restore_latency_ms.percentile(0.99)
        out["pull_latency_ms_p50"] = self.pull_latency_ms.percentile(0.5)
        out["pull_latency_ms_p99"] = self.pull_latency_ms.percentile(0.99)
        return out

    def render(self, prefix: str = "dynamo_tpu") -> str:
        ns = f"{prefix}_kv_tier"
        lines = []

        def emit(name: str, kind: str, help_: str, value) -> None:
            lines.append(f"# HELP {ns}_{name} {help_}")
            lines.append(f"# TYPE {ns}_{name} {kind}")
            lines.append(f"{ns}_{name} {value}")

        summary = self.tier_summary()
        tiers = [t for t in ("hbm", "host", "disk", "objstore") if t in summary]
        if tiers:
            lines.append(f"# HELP {ns}_blocks Sealed KV blocks per tier")
            lines.append(f"# TYPE {ns}_blocks gauge")
            for t in tiers:  # bounded constant label set
                lines.append(
                    f'{ns}_blocks{{tier="{escape_label(t)}"}} '
                    f'{summary[t]["blocks"]}'
                )
            lines.append(f"# HELP {ns}_bytes KV bytes per tier")
            lines.append(f"# TYPE {ns}_bytes gauge")
            for t in tiers:
                lines.append(
                    f'{ns}_bytes{{tier="{escape_label(t)}"}} '
                    f'{summary[t]["bytes"]}'
                )
            emit("prefix_hit_rate", "gauge",
                 "Engine prefix-cache hit rate (matched/looked-up blocks)",
                 round(float(summary.get("prefix_hit_rate", 0.0)), 6))
        emit("restore_hits_total", "counter",
             "Requests that restored >=1 prefix block from a lower tier",
             self.restore_hits_total)
        emit("restore_misses_total", "counter",
             "Tiered restore attempts that found nothing restorable",
             self.restore_misses_total)
        emit("restored_blocks_total", "counter",
             "Blocks scattered host->HBM ahead of admission",
             self.restored_blocks_total)
        emit("promoted_blocks_total", "counter",
             "Blocks promoted disk->host", self.promoted_blocks_total)
        emit("prefetched_blocks_total", "counter",
             "disk->host promotions driven by the kv_prefetch plane",
             self.prefetched_blocks_total)
        emit("pulls_started_total", "counter",
             "Cross-worker prefix pulls attempted", self.pulls_started_total)
        emit("pulls_completed_total", "counter",
             "Cross-worker prefix pulls that landed blocks",
             self.pulls_completed_total)
        emit("pulls_failed_total", "counter",
             "Pulls degraded to local prefill (timeout/refusal/error)",
             self.pulls_failed_total)
        emit("pulled_blocks_total", "counter",
             "Blocks imported by cross-worker pulls", self.pulled_blocks_total)
        emit("pulled_bytes_total", "counter",
             "Bytes imported by cross-worker pulls", self.pulled_bytes_total)
        emit("restore_latency_ms_p50", "gauge",
             "Rolling p50 of tier-restore latency",
             round(self.restore_latency_ms.percentile(0.5), 3))
        emit("restore_latency_ms_p99", "gauge",
             "Rolling p99 of tier-restore latency",
             round(self.restore_latency_ms.percentile(0.99), 3))
        emit("pull_latency_ms_p50", "gauge",
             "Rolling p50 of cross-worker pull latency",
             round(self.pull_latency_ms.percentile(0.5), 3))
        emit("pull_latency_ms_p99", "gauge",
             "Rolling p99 of cross-worker pull latency",
             round(self.pull_latency_ms.percentile(0.99), 3))
        return "\n".join(lines) + "\n"


kv_tier_metrics = KvTierMetrics()

# The integrity plane's verification boundaries (engine/integrity.py):
# ``disk`` = .kvblk envelope reads, ``host`` = host-tier entries verified
# before the HBM scatter (plus demotion-time re-verification), ``wire`` =
# transfer-plane payloads (cross-worker pull, migration push, disagg
# import) verified before sealing, ``objstore`` = durable-object envelope
# reads (engine/object_store.py).
INTEGRITY_PLANES = ("disk", "host", "wire", "objstore")


class KvIntegrityMetrics:
    """KV integrity-plane counters (docs/kv_tiering.md §integrity):
    per-plane verified/corrupt, plus the quarantine machinery's activity
    — negative-cache hits, chained-descendant drops, recompute fallbacks,
    and corruption-attributed worker quarantines.  Module-level singleton
    rendered as Prometheus text and appended to ``/metrics``."""

    def __init__(self):
        self.verified_total: Dict[str, int] = {p: 0 for p in INTEGRITY_PLANES}
        self.corrupt_total: Dict[str, int] = {p: 0 for p in INTEGRITY_PLANES}
        # blocks dropped from the tiers because their chain passes through
        # a corrupt block (the corrupt block itself is not counted here)
        self.descendants_dropped_total = 0
        # restore/promotion/pull attempts skipped on a negative-cached hash
        self.negative_cache_hits_total = 0
        # corruption events that degraded a live request to recompute
        # (the disagg degraded-mode shape — never a drop, never a wrong token)
        self.recomputed_total = 0
        # watchdog quarantines attributed to repeated KV corruption
        self.quarantined_total = 0

    def reset(self) -> None:
        self.__init__()

    def corrupt_sum(self) -> int:
        return sum(self.corrupt_total.values())

    def verified_sum(self) -> int:
        return sum(self.verified_total.values())

    def snapshot(self) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for p in INTEGRITY_PLANES:
            out[f"verified_{p}_total"] = float(self.verified_total[p])
            out[f"corrupt_{p}_total"] = float(self.corrupt_total[p])
        out["descendants_dropped_total"] = float(self.descendants_dropped_total)
        out["negative_cache_hits_total"] = float(self.negative_cache_hits_total)
        out["recomputed_total"] = float(self.recomputed_total)
        out["quarantined_total"] = float(self.quarantined_total)
        return out

    def render(self, prefix: str = "dynamo_tpu") -> str:
        ns = f"{prefix}_kv_integrity"
        lines = []

        def per_plane(name: str, help_: str, values: Dict[str, int]) -> None:
            lines.append(f"# HELP {ns}_{name} {help_}")
            lines.append(f"# TYPE {ns}_{name} counter")
            for p in INTEGRITY_PLANES:  # bounded constant label set
                lines.append(
                    f'{ns}_{name}{{plane="{escape_label(p)}"}} {values[p]}'
                )

        def emit(name: str, help_: str, value: int) -> None:
            lines.append(f"# HELP {ns}_{name} {help_}")
            lines.append(f"# TYPE {ns}_{name} counter")
            lines.append(f"{ns}_{name} {value}")

        per_plane("verified_total",
                  "KV blocks whose checksum verified at this plane's boundary",
                  self.verified_total)
        per_plane("corrupt_total",
                  "KV blocks that FAILED checksum verification at this plane",
                  self.corrupt_total)
        emit("descendants_dropped_total",
             "Tier blocks dropped because their chain passes through a "
             "corrupt block", self.descendants_dropped_total)
        emit("negative_cache_hits_total",
             "Restore/promotion/pull attempts skipped on a negative-cached "
             "(recently corrupt) hash", self.negative_cache_hits_total)
        emit("recomputed_total",
             "Corruption events degraded to local recompute (streams stay "
             "byte-identical)", self.recomputed_total)
        emit("quarantined_total",
             "Worker quarantines attributed to repeated KV corruption",
             self.quarantined_total)
        return "\n".join(lines) + "\n"


kv_integrity_metrics = KvIntegrityMetrics()


class BulkMetrics:
    """Bulk data-plane counters (docs/bulk_plane.md): bytes and transfers
    moved peer-to-peer (off the hub control plane), resumes after peer
    connection drops, and fallbacks onto the hub path.  Module-level
    singleton rendered as Prometheus text and appended to ``/metrics``;
    ``loadgen.py`` folds ``snapshot()`` into its run summary."""

    def __init__(self):
        self.bytes_total = 0
        self.transfers_total = 0
        # bulk attempts that fell back to the hub path (dead peer, expired
        # ticket, rendezvous outage) — the stream survives either way
        self.fallbacks_total = 0
        # reconnects that continued from the last verified chunk
        self.resumes_total = 0

    def reset(self) -> None:
        self.__init__()

    def snapshot(self) -> Dict[str, float]:
        return {
            "bytes_total": float(self.bytes_total),
            "transfers_total": float(self.transfers_total),
            "fallbacks_total": float(self.fallbacks_total),
            "resumes_total": float(self.resumes_total),
        }

    def render(self, prefix: str = "dynamo_tpu") -> str:
        ns = f"{prefix}_bulk"
        lines = []

        def emit(name: str, help_: str, value: int) -> None:
            lines.append(f"# HELP {ns}_{name} {help_}")
            lines.append(f"# TYPE {ns}_{name} counter")
            lines.append(f"{ns}_{name} {value}")

        emit("bytes_total",
             "Payload bytes moved over the peer-to-peer bulk plane "
             "(KV pulls, migration copies, span batches)", self.bytes_total)
        emit("transfers_total",
             "Completed bulk transfers (fetch + push)", self.transfers_total)
        emit("fallbacks_total",
             "Bulk attempts that fell back to the hub path (stream "
             "survives; bytes ride the control plane)", self.fallbacks_total)
        emit("resumes_total",
             "Transfers resumed from the last verified chunk after a peer "
             "connection drop", self.resumes_total)
        return "\n".join(lines) + "\n"


bulk_metrics = BulkMetrics()


class ObjstoreMetrics:
    """Durable object-store tier counters (engine/object_store.py): put/get
    traffic in blocks and bytes plus byte-budgeted GC evictions.  Module-level
    singleton rendered as Prometheus text and appended to ``/metrics``."""

    def __init__(self):
        self.puts_total = 0
        self.put_bytes_total = 0
        self.gets_total = 0
        self.get_bytes_total = 0
        # objects evicted by the byte-budgeted GC (coldest-first); corrupt
        # drops are counted on the integrity plane, not here
        self.gc_evictions_total = 0

    def reset(self) -> None:
        self.__init__()

    def snapshot(self) -> Dict[str, float]:
        return {
            "puts_total": float(self.puts_total),
            "put_bytes_total": float(self.put_bytes_total),
            "gets_total": float(self.gets_total),
            "get_bytes_total": float(self.get_bytes_total),
            "gc_evictions_total": float(self.gc_evictions_total),
        }

    def render(self, prefix: str = "dynamo_tpu") -> str:
        ns = f"{prefix}_objstore"
        lines = []

        def emit(name: str, help_: str, value: int) -> None:
            lines.append(f"# HELP {ns}_{name} {help_}")
            lines.append(f"# TYPE {ns}_{name} counter")
            lines.append(f"{ns}_{name} {value}")

        emit("puts_total",
             "Objects published to the durable store (demotions + explicit "
             "persists)", self.puts_total)
        emit("put_bytes_total",
             "Envelope bytes published to the durable store",
             self.put_bytes_total)
        emit("gets_total",
             "Objects read back from the durable store (restores + "
             "promotions)", self.gets_total)
        emit("get_bytes_total",
             "Envelope bytes read back from the durable store",
             self.get_bytes_total)
        emit("gc_evictions_total",
             "Objects evicted by the byte-budgeted GC (coldest-first)",
             self.gc_evictions_total)
        return "\n".join(lines) + "\n"


objstore_metrics = ObjstoreMetrics()


class InflightGuard:
    """Tracks one request: inflight gauge, duration, TTFT, ITL, final status.

    Must be closed with ``finish(status)``; a guard dropped without an explicit
    status records ``error`` (the reference's RAII Drop behaviour).
    """

    def __init__(self, metrics: Metrics, model: str, endpoint: str, request_type: str):
        self._m = metrics
        self.model = model
        self.endpoint = endpoint
        self.request_type = request_type
        self._start = time.monotonic()
        self._last_token_t: Optional[float] = None
        self._finished = False
        metrics.inflight.labels(model, endpoint).inc()

    def on_token(self, n_tokens: int = 1) -> None:
        now = time.monotonic()
        ttft_w, itl_w = self._m.window(self.model, self.endpoint)
        if self._last_token_t is None:
            self._m.ttft.labels(self.model, self.endpoint).observe(now - self._start)
            ttft_w.observe(now - self._start)
            self._m.ttft_recent.observe(now - self._start)
        else:
            self._m.itl.labels(self.model, self.endpoint).observe(now - self._last_token_t)
            itl_w.observe(now - self._last_token_t)
        self._last_token_t = now
        self._m.output_tokens.labels(self.model, self.endpoint).inc(n_tokens)

    def finish(self, status: str) -> None:
        if self._finished:
            return
        self._finished = True
        self._m.inflight.labels(self.model, self.endpoint).dec()
        self._m.request_duration.labels(self.model, self.endpoint).observe(
            time.monotonic() - self._start
        )
        self._m.requests_total.labels(
            self.model, self.endpoint, self.request_type, status
        ).inc()

    def __del__(self):
        if not self._finished:
            try:
                self.finish(Status.ERROR)
            except Exception:  # noqa: BLE001 — interpreter teardown
                pass
