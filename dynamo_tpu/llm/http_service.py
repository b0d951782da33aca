"""OpenAI-compatible HTTP frontend (aiohttp).

Reference semantics: lib/llm/src/http/service/{service_v2,openai}.rs — routes
``/v1/chat/completions``, ``/v1/completions``, ``/v1/models``, ``/metrics``,
``/health``; every downstream engine streams, ``stream=false`` responses are
aggregated at the edge (aggregator.rs); a client disconnect mid-stream calls
``stop_generating`` and records status ``client_drop``; Prometheus metrics via
``InflightGuard`` (metrics.rs:319).

The ``ModelManager`` maps model name → chat/completion pipelines
(http/service.rs:59-120); engines are added statically or by the hub model
watcher (discovery.py).
"""

from __future__ import annotations

import asyncio
import json
import logging
import time
from typing import Any, Callable, Dict, Optional

from aiohttp import web

from ..labels import bounded_label
from ..runtime.client import NoInstancesError, RemoteEngineError
from ..runtime.engine import AsyncEngine, Context
from ..runtime.resilience import (
    AdmissionController,
    AdmissionRejected,
    Deadline,
    DeadlineExceededError,
)
from ..runtime.resilience import metrics as resilience_metrics
from .metrics import Metrics, Status, qos_metrics
from .openai import SSE_DONE, aggregate_chunks, sse_encode
from .protocols import ModelNotFoundError
from .qos import (
    BATCH,
    BrownoutSignals,
    QosController,
    QosShed,
    RUNG_CAP_TOKENS,
    RUNG_SHED_INTERACTIVE,
    RUNG_SPEC_STANDDOWN,
    resolve_priority,
    resolve_tenant,
)
from .tenancy.lora import AdapterCapacityError
from .trace_service import EdgeRequestTrace

logger = logging.getLogger(__name__)


class _TracedGuard:
    """Metrics InflightGuard wrapper that mirrors token/finish callbacks to
    the request's EdgeRequestTrace — one wrapper covers every status path
    in the handlers without touching them individually."""

    __slots__ = ("_guard", "_ert")

    def __init__(self, guard, ert: EdgeRequestTrace):
        self._guard = guard
        self._ert = ert

    def on_token(self, *args, **kwargs) -> None:
        self._ert.on_first_token()
        self._guard.on_token(*args, **kwargs)

    def finish(self, status) -> None:
        self._guard.finish(status)
        self._ert.finish(str(status))


class ModelManager:
    """Model name → engine registry (chat + completion separately)."""

    def __init__(self):
        self._chat: Dict[str, AsyncEngine] = {}
        self._completion: Dict[str, AsyncEngine] = {}

    def add_chat_model(self, name: str, engine: AsyncEngine) -> None:
        self._chat[name] = engine

    def add_completion_model(self, name: str, engine: AsyncEngine) -> None:
        self._completion[name] = engine

    def remove_model(self, name: str) -> None:
        self._chat.pop(name, None)
        self._completion.pop(name, None)

    def chat_engine(self, name: str) -> Optional[AsyncEngine]:
        return self._chat.get(name)

    def completion_engine(self, name: str) -> Optional[AsyncEngine]:
        return self._completion.get(name)

    def model_names(self) -> list:
        return sorted(set(self._chat) | set(self._completion))

    def has_model(self, name: str) -> bool:
        return name in self._chat or name in self._completion


class HttpError(Exception):
    def __init__(self, status: int, message: str):
        super().__init__(message)
        self.status = status
        self.message = message


class HttpService:
    """The OpenAI ingress service."""

    def __init__(
        self,
        host: str = "0.0.0.0",
        port: int = 8000,
        metrics_prefix: str = "dynamo_tpu",
        model_manager: Optional[ModelManager] = None,
        max_inflight: Optional[int] = None,
        admission_queue: int = 0,
        admission_timeout_s: float = 1.0,
        default_deadline_s: Optional[float] = None,
        qos: Optional[QosController] = None,
        kv_usage_fn=None,
        tracing=None,
        trace_aggregator=None,
        hub=None,
    ):
        self.host = host
        self.port = port
        self.models = model_manager or ModelManager()
        self.metrics = Metrics(metrics_prefix)
        self._metrics_prefix = metrics_prefix
        # Admission control (disabled unless max_inflight is set): beyond
        # the in-flight cap requests wait in a bounded FIFO; overflow sheds
        # 429, wait-timeout sheds 503 — latency stays bounded instead of
        # collapsing under burst.  Batch-class requests may only occupy the
        # front half of the queue (llm/qos.py priority classes).
        self.admission = AdmissionController(
            max_inflight=max_inflight,
            max_queue=admission_queue,
            queue_timeout_s=admission_timeout_s,
        )
        # QoS/overload control (llm/qos.py): per-tenant token buckets + the
        # brownout degradation ladder.  None = disabled (zero behaviour
        # change).  ``kv_usage_fn`` optionally feeds the ladder a KV-
        # pressure signal when an engine/collector is colocated.
        self.qos = qos
        self._kv_usage_fn = kv_usage_fn
        self._qos_task: Optional[asyncio.Task] = None
        # Per-request wall-clock budget (None = unbounded, the previous
        # behaviour); exhaustion maps to 504 below.
        self.default_deadline_s = default_deadline_s
        # Distributed request tracing (runtime/tracing.py): ``tracing`` is
        # a TraceSampler (None = edge never samples, zero cost);
        # ``trace_aggregator`` serves assembled traces at /traces (wired by
        # the CLI — a hub subscription for routed fleets, a direct exporter
        # sink when the engine is colocated).
        self.tracing = tracing
        self.trace_aggregator = trace_aggregator
        # Control-plane client (HubClient or ShardedHubClient): /health
        # reports per-shard connectivity so a one-shard outage is visible
        # at the edge before it pages as anything else.  None = the edge
        # runs hub-less (tests, colocated engines) — zero change.
        self.hub = hub
        self.app = web.Application()
        self.app.router.add_post("/v1/chat/completions", self._chat_completions)
        self.app.router.add_post("/v1/completions", self._completions)
        self.app.router.add_get("/v1/models", self._list_models)
        self.app.router.add_get("/metrics", self._metrics)
        self.app.router.add_get("/health", self._health)
        self.app.router.add_get("/live", self._health)
        self.app.router.add_get("/traces", self._traces_recent)
        self.app.router.add_get("/traces/{trace_id}", self._trace_get)
        self._runner: Optional[web.AppRunner] = None

    # -- lifecycle ----------------------------------------------------------

    async def start(self) -> "HttpService":
        self._runner = web.AppRunner(self.app, access_log=None)
        await self._runner.setup()
        site = web.TCPSite(self._runner, self.host, self.port)
        await site.start()
        for s in site._server.sockets:  # resolve port 0
            self.port = s.getsockname()[1]
            break
        logger.info("HTTP service listening on %s:%s", self.host, self.port)
        if self.qos is not None and self.qos.ladder is not None:
            self._qos_task = asyncio.get_running_loop().create_task(
                self._qos_tick_loop()
            )
        return self

    async def close(self) -> None:
        if self._qos_task is not None:
            self._qos_task.cancel()
            try:
                await self._qos_task
            except asyncio.CancelledError:
                pass
            self._qos_task = None
        if self._runner is not None:
            await self._runner.cleanup()
            self._runner = None

    async def _qos_tick_loop(self) -> None:
        """Drive the brownout ladder off live edge signals.  The ladder
        itself is pure (llm/qos.py BrownoutLadder.tick); this loop only
        samples queue depth, rolling TTFT and (optionally) KV usage on the
        configured interval and publishes the rung to metrics."""
        ladder = self.qos.ladder
        while True:
            await asyncio.sleep(self.qos.config.tick_s)
            kv_usage = 0.0
            if self._kv_usage_fn is not None:
                try:
                    kv_usage = float(self._kv_usage_fn())
                except asyncio.CancelledError:
                    raise
                except Exception:  # noqa: BLE001 — signal source is optional
                    logger.warning("qos kv_usage_fn failed", exc_info=True)
            # TTFT from the AGE-bounded window (None = no first token in
            # the last few seconds): the count-bounded planner windows
            # would hold a spike's samples long after it ended — at zero
            # traffic forever — and the ladder could never recover.
            ttft_p95_ms = self.metrics.recent_ttft_p95_ms()
            before = ladder.rung
            ladder.tick(
                BrownoutSignals(
                    queue_depth=float(self.admission.queued),
                    kv_usage=kv_usage,
                    ttft_p95_ms=ttft_p95_ms,
                )
            )
            qos_metrics.brownout_rung = ladder.rung
            if ladder.rung != before:
                qos_metrics.brownout_transitions_total += 1
                logger.warning(
                    "brownout rung %d -> %d (queue=%d ttft_p95=%sms)",
                    before, ladder.rung, self.admission.queued,
                    "%.0f" % ttft_p95_ms if ttft_p95_ms is not None else "-",
                )

    async def run(
        self,
        shutdown: Optional[asyncio.Event] = None,
        on_listening: Optional[Callable[[], None]] = None,
    ) -> None:
        """``on_listening`` is called once the socket accepts (the colocated
        engine closes its start's account there: engine/phases.py)."""
        await self.start()
        if on_listening is not None:
            on_listening()
        try:
            if shutdown is None:
                await asyncio.Event().wait()
            else:
                await shutdown.wait()
        finally:
            await self.close()

    # -- handlers -----------------------------------------------------------

    async def _health(self, request: web.Request) -> web.Response:
        body = {"status": "ok", "models": self.models.model_names()}
        if self.qos is not None and self.qos.ladder is not None:
            body["brownout"] = self.qos.ladder.state()
        if self.hub is not None:
            # Sharded client → per-shard connectivity; plain HubClient →
            # one synthetic shard so the schema is the same either way.
            shard_health = getattr(self.hub, "shard_health", None)
            if shard_health is not None:
                shards = shard_health()
            else:
                shards = [{
                    "shard": getattr(self.hub, "address", ""),
                    "connected": bool(getattr(self.hub, "connected", False)),
                }]
            body["hub_shards"] = shards
            if not all(s["connected"] for s in shards):
                body["status"] = "degraded"
        return web.json_response(body)

    async def _metrics(self, request: web.Request) -> web.Response:
        # Planner decisions/state ride along when a planner runs in this
        # process (module-level singleton, same pattern as resilience), as
        # do the engine's speculative-decoding gauges when the engine is
        # colocated (llm/metrics.py spec_metrics).
        from ..planner.pmetrics import metrics as planner_metrics
        from ..runtime.health import health_metrics
        from .metrics import (
            bulk_metrics,
            engine_dispatch_metrics,
            sparse_model_metrics,
            kv_integrity_metrics,
            kv_tier_metrics,
            migration_metrics,
            objstore_metrics,
            request_hop_metrics,
            spec_metrics,
            ssm_metrics,
            swa_metrics,
            tenancy_metrics,
        )

        from ..runtime.tracing import tracing_metrics
        from ..runtime.transports.shard import shard_metrics

        body = (
            self.metrics.render()
            + resilience_metrics.render(self._metrics_prefix).encode()
            + tracing_metrics.render(self._metrics_prefix).encode()
            + planner_metrics.render(self._metrics_prefix).encode()
            + spec_metrics.render(self._metrics_prefix).encode()
            + migration_metrics.render(self._metrics_prefix).encode()
            + tenancy_metrics.render(self._metrics_prefix).encode()
            + health_metrics.render(self._metrics_prefix).encode()
            + qos_metrics.render(self._metrics_prefix).encode()
            + engine_dispatch_metrics.render(self._metrics_prefix).encode()
            + sparse_model_metrics.render(self._metrics_prefix).encode()
            + ssm_metrics.render(self._metrics_prefix).encode()
            + swa_metrics.render(self._metrics_prefix).encode()
            + request_hop_metrics.render(self._metrics_prefix).encode()
            + kv_tier_metrics.render(self._metrics_prefix).encode()
            + kv_integrity_metrics.render(self._metrics_prefix).encode()
            + objstore_metrics.render(self._metrics_prefix).encode()
            + bulk_metrics.render(self._metrics_prefix).encode()
            + shard_metrics.render(self._metrics_prefix).encode()
        )
        return web.Response(body=body, content_type="text/plain")

    async def _traces_recent(self, request: web.Request) -> web.Response:
        """``/traces?recent=N``: the aggregator's most recent assemblies."""
        if self.trace_aggregator is None:
            return _error_response(404, "tracing aggregator not configured")
        try:
            n = int(request.query.get("recent", 20))
        except (TypeError, ValueError):
            n = 20
        return web.json_response({"traces": self.trace_aggregator.recent(n)})

    async def _trace_get(self, request: web.Request) -> web.Response:
        """``/traces/{id}``: one assembled trace + its per-hop rollup."""
        if self.trace_aggregator is None:
            return _error_response(404, "tracing aggregator not configured")
        tid = request.match_info["trace_id"]
        trace = self.trace_aggregator.get(tid)
        if trace is None:
            return _error_response(404, f"trace {tid!r} not assembled here")
        return web.json_response(trace)

    async def _list_models(self, request: web.Request) -> web.Response:
        now = int(time.time())
        return web.json_response(
            {
                "object": "list",
                "data": [
                    {"id": name, "object": "model", "created": now, "owned_by": "dynamo_tpu"}
                    for name in self.models.model_names()
                ],
            }
        )

    async def _chat_completions(self, request: web.Request) -> web.StreamResponse:
        return await self._handle_openai(request, chat=True)

    async def _completions(self, request: web.Request) -> web.StreamResponse:
        return await self._handle_openai(request, chat=False)

    async def _handle_openai(self, request: web.Request, chat: bool) -> web.StreamResponse:
        endpoint = "chat_completions" if chat else "completions"
        try:
            body = await request.json()
        except (json.JSONDecodeError, UnicodeDecodeError):
            return _error_response(400, "invalid JSON body")
        model = body.get("model")
        if not isinstance(model, str) or not model:
            return _error_response(400, "missing 'model'")
        engine = (
            self.models.chat_engine(model) if chat else self.models.completion_engine(model)
        )
        if engine is None:
            # Label with a CONSTANT, not the wire string: every junk model
            # name would otherwise mint a fresh label value — an unbounded-
            # cardinality bomb on requests that cost us nothing else
            # (dynalint DYN201).  The 404 body still names the model.
            self.metrics.requests_total.labels(
                "unknown", endpoint, "stream", Status.REJECTED
            ).inc()
            return _model_not_found(model)
        # Past the served-model check the name is bounded (it resolved to
        # an engine) — not a cardinality hazard.  bounded_label is the
        # auditable identity marker: prometheus_client escapes at
        # exposition itself, so pre-escaping here would double-escape AND
        # split the rejected series from the success path's raw labels.
        model_label = bounded_label(model)

        # Tracing (runtime/tracing.py): the sampling decision is made once
        # here — forced (x-trace / nvext.trace) beats the head rate — and
        # the handle shadows the request even when unsampled so tail-keep
        # can promote an error/SLO-violating request's edge spans later.
        ert = EdgeRequestTrace(self.tracing, request.headers, body)

        # QoS (llm/qos.py): resolve tenant + priority, charge the tenant's
        # quota, apply the brownout rung — all BEFORE a slot is consumed.
        priority = resolve_priority(request.headers, body)
        tenant: Optional[str] = None
        if self.qos is not None:
            tenant = resolve_tenant(request.headers, body)
            if (
                self.qos.rung >= RUNG_SHED_INTERACTIVE
                and self.admission.saturated
            ):
                # Rung 4: admission is saturated — shed instead of queueing
                # (never sheds below the in-flight cap).  Checked BEFORE
                # the quota charge: a shed request consumed no capacity
                # and must not drain the tenant's bucket.
                qos_metrics.interactive_shed_total += 1
                qos_metrics.shed_tenant(tenant)
                self.metrics.requests_total.labels(
                    model_label, endpoint, "stream", Status.REJECTED
                ).inc()
                ert.finish(Status.REJECTED, model=model, endpoint=endpoint)
                return _error_response(
                    503,
                    "server in brownout (interactive overflow)",
                    retry_after_s=self.admission.estimate_retry_after(),
                )
            try:
                self.qos.admit(
                    tenant, priority, self.admission.estimate_retry_after()
                )
            except QosShed as e:
                if e.reason == "quota":
                    qos_metrics.quota_shed_total += 1
                else:
                    qos_metrics.batch_shed_total += 1
                qos_metrics.shed_tenant(tenant)
                self.metrics.requests_total.labels(
                    model_label, endpoint, "stream", Status.REJECTED
                ).inc()
                ert.finish(Status.REJECTED, model=model, endpoint=endpoint)
                return _error_response(
                    e.status, e.message, retry_after_s=e.retry_after_s
                )
            rung = self.qos.rung
            if rung >= RUNG_CAP_TOKENS:
                qos_metrics.capped_requests_total += 1
            if rung >= RUNG_SPEC_STANDDOWN:
                qos_metrics.spec_standdowns_total += 1
            if rung and ert.active:
                # Brownout rewrites are invisible in the response body —
                # record WHICH rung shaped this request on its trace.
                ert.event("brownout_rewrite", rung=rung)
            body = self.qos.shape(body)
            if tenant != model:
                # Thread the RESOLVED identity to the scheduler's WFQ
                # (preprocessor: nvext.tenant → annotations.tenant) — a
                # model-named tenant is the scheduler's own fallback, so
                # only header/credential identities need the stamp.
                # Without it, two API keys sharing a model land in one
                # WFQ flow and noisy-neighbor isolation never engages.
                nvext = body.get("nvext")
                if not isinstance(nvext, dict):
                    nvext = {}
                    body["nvext"] = nvext
                nvext["tenant"] = tenant
        if priority == BATCH or "x-priority" in request.headers:
            # Thread the resolved class to the scheduler (the preprocessor
            # reads nvext.priority into PreprocessedRequest.priority).
            # NOT setdefault: a client-sent ``"nvext": null`` would satisfy
            # it and the batch class would silently run as interactive —
            # bypassing batch-first preemption and the rung-3 shed.
            nvext = body.get("nvext")
            if not isinstance(nvext, dict):
                nvext = {}
                body["nvext"] = nvext
            nvext["priority"] = priority

        # Admission control guards everything that costs engine work; cheap
        # 400/404s above never consume a slot.  Batch-class requests only
        # queue in their reserved fraction (resilience.AdmissionController).
        ert.admission_started()
        try:
            await self.admission.acquire(priority)
        except AdmissionRejected as e:
            if self.qos is not None and tenant is not None:
                # The quota was charged above, but this request was shed
                # before consuming any capacity — credit it back.
                self.qos.quotas.refund(tenant)
            self.metrics.requests_total.labels(
                model_label, endpoint, "stream", Status.REJECTED
            ).inc()
            ert.finish(Status.REJECTED, model=model, endpoint=endpoint)
            # The drain-rate estimate says when a slot frees; a deepening
            # brownout says the estimate is optimistic — back clients off
            # harder the further down the ladder the edge already is.
            retry = e.retry_after_s
            if self.qos is not None and self.qos.rung:
                retry *= 1 + self.qos.rung
            return _error_response(e.status, e.message, retry_after_s=retry)
        except BaseException:
            # Handler cancelled (client gone) or failed while QUEUED: the
            # admission wait it died in is exactly the datum the trace
            # exists to capture — record before propagating.
            ert.finish(Status.ERROR, model=model, endpoint=endpoint)
            raise
        ert.admission_done()
        try:
            return await self._admitted_openai(
                request, body, engine, model, endpoint, ert
            )
        finally:
            self.admission.release()
            # Belt for paths no guard.finish covered (handler cancellation,
            # unexpected escapes): finish is idempotent, so completed
            # requests — already closed by _TracedGuard — are untouched.
            ert.finish(Status.ERROR, model=model, endpoint=endpoint)

    async def _admitted_openai(
        self,
        request: web.Request,
        body: Dict[str, Any],
        engine: AsyncEngine,
        model: str,
        endpoint: str,
        ert: EdgeRequestTrace,
    ) -> web.StreamResponse:
        stream_mode = bool(body.get("stream", False))
        guard = self.metrics.guard(model, endpoint, "stream" if stream_mode else "unary")
        # The caller made the ONE sampling decision for this request; a
        # second EdgeRequestTrace here would mint a new trace id and
        # double-count the sampler metrics.
        ert.model, ert.endpoint = model, endpoint
        # Every guard.finish path (success, error, client drop) also closes
        # the edge trace — one wrapper instead of N call sites.
        guard = _TracedGuard(guard, ert)
        # Request-id correlation (reference: context id propagated in
        # headers): a caller-supplied x-request-id becomes the PREFIX of the
        # engine context id (logs, recorder streams, KV events), uniquified
        # with a server suffix — request ids key the engine's response
        # queues, so a client-chosen id must never collide with a
        # concurrent request's (that would cross-deliver tokens).  The full
        # unique id is echoed on every response, success or error.
        rid = request.headers.get("x-request-id")
        if rid:
            import uuid as _uuid

            ctx = Context.with_id(body, f"{rid}-{_uuid.uuid4().hex[:8]}")
        else:
            ctx = Context(body)
        # Per-request deadline: caller's x-deadline-s header (or body
        # "deadline_s") wins, else the service default; None = unbounded.
        deadline_s = _requested_deadline(request, body, self.default_deadline_s)
        if deadline_s is not None:
            ctx.ctx.deadline = Deadline.after(deadline_s)
        ert.ctx = ctx.ctx
        if ert.tc is not None:
            # Downstream propagation: the preprocessor stamps this onto
            # ``annotations.trace``; the service transport ships it in the
            # request header — one trace from edge to decode chunk.
            ctx.ctx.trace = ert.tc
        try:
            stream = await engine.generate(ctx)
        except ModelNotFoundError as e:
            # Engine-level rejection (llm/tenancy): the edge routed by name,
            # but the engine serves a model/adapter allowlist — an unknown
            # name 404s instead of silently running the base model.
            guard.finish(Status.REJECTED)
            return _model_not_found(e.model, rid=ctx.id)
        except AdapterCapacityError as e:
            # Transient: every resident LoRA slot is pinned by running
            # sequences — back off and retry, don't treat as server sickness.
            guard.finish(Status.REJECTED)
            return _error_response(503, str(e), rid=ctx.id, retry_after_s=1.0)
        except RemoteEngineError as e:
            if e.kind == ModelNotFoundError.error_kind:
                guard.finish(Status.REJECTED)
                return _model_not_found(model, rid=ctx.id)
            if e.kind == AdapterCapacityError.error_kind:
                guard.finish(Status.REJECTED)
                return _error_response(
                    503, str(e), rid=ctx.id, retry_after_s=1.0
                )
            guard.finish(Status.ERROR)
            logger.exception("engine rejected request")
            return _error_response(500, str(e), rid=ctx.id)
        except ValueError as e:
            # Request-shape errors (bad sampling params, oversize prompt)
            # are the client's fault: 400, not 500.  Logged with traceback:
            # an internal ValueError misclassified here must still be
            # visible server-side.
            guard.finish(Status.REJECTED)
            logger.warning("request rejected: %s", e, exc_info=True)
            return _error_response(400, str(e), rid=ctx.id)
        except (DeadlineExceededError, asyncio.TimeoutError) as e:
            guard.finish(Status.ERROR)
            logger.warning("request %s deadline exceeded at dispatch", ctx.id)
            return _error_response(504, str(e) or "deadline exceeded", rid=ctx.id)
        except NoInstancesError as e:
            # No live worker right now — transient capacity problem, not an
            # internal fault: 503 so clients retry, never 500.
            guard.finish(Status.REJECTED)
            logger.warning("no instances for %s: %s", model, e)
            return _error_response(503, str(e), rid=ctx.id, retry_after_s=1.0)
        except asyncio.CancelledError:
            raise
        except Exception as e:  # noqa: BLE001 — edge boundary
            guard.finish(Status.ERROR)
            logger.exception("engine rejected request")
            return _error_response(500, str(e), rid=ctx.id)

        if stream_mode:
            return await self._stream_response(request, stream, ctx, guard)
        return await self._unary_response(stream, ctx, guard)

    async def _unary_response(self, stream, ctx: Context, guard) -> web.Response:
        # The edge is the enforcement point of last resort for deadlines:
        # engines behind a routed Client already honour them, but a local
        # pipeline streams unbounded — bound every chunk wait here.
        deadline = getattr(ctx.ctx, "deadline", None)
        chunks = []
        try:
            it = stream.__aiter__()
            while True:
                try:
                    if deadline is not None:
                        chunk = await deadline.bound(it.__anext__(), "response")
                    else:
                        chunk = await it.__anext__()
                except StopAsyncIteration:
                    break
                if "__annotations__" in chunk:
                    continue
                if chunk.get("choices") or chunk.get("usage"):
                    guard.on_token(0)
                chunks.append(chunk)
            full = aggregate_chunks(chunks)
        except asyncio.CancelledError:
            ctx.stop_generating()
            guard.finish(Status.CLIENT_DROP)
            raise
        except DeadlineExceededError as e:
            # Abandoning the request must also stop upstream generation —
            # otherwise the engine keeps burning batch slots on a response
            # nobody will read, exactly when the server is already slow.
            ctx.stop_generating()
            guard.finish(Status.ERROR)
            logger.warning("request %s deadline exceeded mid-generation", ctx.id)
            return _error_response(504, str(e) or "deadline exceeded", rid=ctx.id)
        except NoInstancesError as e:
            guard.finish(Status.REJECTED)
            return _error_response(503, str(e), rid=ctx.id, retry_after_s=1.0)
        except Exception as e:  # noqa: BLE001
            guard.finish(Status.ERROR)
            logger.exception("stream failed")
            return _error_response(500, str(e), rid=ctx.id)
        guard.finish(Status.SUCCESS)
        headers = {"x-request-id": ctx.id}
        trace = getattr(ctx.ctx, "trace", None)
        if trace is not None:
            headers["x-trace-id"] = trace.trace_id
        return web.json_response(full, headers=headers)

    async def _stream_response(
        self, request: web.Request, stream, ctx: Context, guard
    ) -> web.StreamResponse:
        headers = {
            "Content-Type": "text/event-stream",
            "Cache-Control": "no-cache",
            "Connection": "keep-alive",
            "x-request-id": ctx.id,
        }
        trace = getattr(ctx.ctx, "trace", None)
        if trace is not None:
            # The trace id is the lookup key for /traces/{id}; loadgen's
            # --trace-report reads it off this header.  Omitted when
            # untraced — the response byte stream itself never changes.
            headers["x-trace-id"] = trace.trace_id
        resp = web.StreamResponse(status=200, headers=headers)
        await resp.prepare(request)
        deadline = getattr(ctx.ctx, "deadline", None)
        status = Status.SUCCESS
        try:
            it = stream.__aiter__()
            while True:
                try:
                    if deadline is not None:
                        chunk = await deadline.bound(it.__anext__(), "stream")
                    else:
                        chunk = await it.__anext__()
                except StopAsyncIteration:
                    break
                if "__annotations__" in chunk:
                    await resp.write(
                        b"event: annotation\n" + sse_encode(chunk["__annotations__"])
                    )
                    continue
                await resp.write(sse_encode(chunk))
                # AFTER the write: the first call latches t_edge_sent, the
                # end of the hop account's server-side TTFT.
                guard.on_token()
            await resp.write(SSE_DONE)
        except (ConnectionResetError, asyncio.CancelledError):  # dynalint: disable=DYN003
            # Client went away: aiohttp cancels this handler on disconnect.
            # Deliberately absorb it — upstream generation must be stopped
            # and the CLIENT_DROP metric recorded before the handler exits.
            ctx.stop_generating()
            status = Status.CLIENT_DROP
        except DeadlineExceededError:
            # headers are already on the wire (200); all we can do is stop
            # generation and end the SSE stream with a typed error event
            ctx.stop_generating()
            status = Status.ERROR
            try:
                await resp.write(
                    b"event: error\n"
                    + sse_encode({"error": "deadline exceeded", "code": 504})
                )
            except (ConnectionResetError, RuntimeError):
                pass
        except Exception:  # noqa: BLE001
            status = Status.ERROR
            logger.exception("stream failed")
            try:
                await resp.write(
                    b"event: error\n" + sse_encode({"error": "stream failed"})
                )
            except (ConnectionResetError, RuntimeError):
                pass
        finally:
            guard.finish(status)
            await stream.aclose()
        try:
            await resp.write_eof()
        except (ConnectionResetError, RuntimeError):
            pass
        return resp


def _requested_deadline(
    request: web.Request, body: Dict[str, Any], default_s: Optional[float]
) -> Optional[float]:
    raw = request.headers.get("x-deadline-s") or body.get("deadline_s")
    if raw is not None:
        try:
            value = float(raw)
            if value > 0:
                return value
        except (TypeError, ValueError):
            pass
    return default_s


_ERROR_TYPES = {
    429: "overloaded_error",
    503: "overloaded_error",
    504: "timeout_error",
}


def _error_response(
    status: int,
    message: str,
    rid: Optional[str] = None,
    retry_after_s: Optional[float] = None,
    code: Optional[Any] = None,
    param: Optional[str] = None,
) -> web.Response:
    headers = {}
    if rid:
        headers["x-request-id"] = rid
    if retry_after_s is not None:
        headers["Retry-After"] = str(max(1, int(retry_after_s)))
    error: Dict[str, Any] = {
        "message": message,
        "type": _ERROR_TYPES.get(status, "invalid_request_error"),
        # OpenAI uses string codes ("model_not_found"); the numeric status
        # stays the default for errors without one (established behaviour).
        "code": status if code is None else code,
    }
    if param is not None:
        error["param"] = param
    return web.json_response(
        {"error": error},
        status=status,
        headers=headers or None,
    )


def _model_not_found(model: str, rid: Optional[str] = None) -> web.Response:
    """The OpenAI ``model_not_found`` 404 body (llm/tenancy satellite: a
    request naming an unregistered model/adapter must fail loudly, never
    silently fall through to the base model)."""
    return _error_response(
        404,
        f"The model {model!r} does not exist or is not served here",
        rid=rid,
        code="model_not_found",
        param="model",
    )
