"""CLI launcher — reference: launch/dynamo-run (``dynamo-run in=… out=…``),
components/http (standalone frontend), plus the hub (docker-compose
etcd+NATS replacement).

Usage:
  python -m dynamo_tpu.cli hub  [--host H] [--port P]
  python -m dynamo_tpu.cli run  in=http out=echocore [--port 8000] [--model echo]
  python -m dynamo_tpu.cli run  in=text out=tpu --checkpoint DIR    # chat REPL
  python -m dynamo_tpu.cli run  in=stdin out=tpu ...                # one prompt
  python -m dynamo_tpu.cli run  in=batch:FILE.jsonl out=tpu ...     # batch eval
  python -m dynamo_tpu.cli run  in=dyn://ns.comp.ep out=echocore --hub HOST:PORT \
        [--model NAME]            # worker: serve engine at endpoint + register model
  python -m dynamo_tpu.cli http --hub HOST:PORT [--port 8000]   # discovery frontend
"""

from __future__ import annotations

import argparse
import asyncio
import json
import logging
import os
import signal
import sys
from typing import Optional

from .llm.backend import Backend
from .llm.discovery import ModelWatcher, make_tokenizer, register_model
from .llm.engines import EchoEngineCore, EchoEngineFull
from .llm.http_service import HttpService
from .llm.preprocessor import OpenAIPreprocessor
from .runtime.component import DistributedRuntime, parse_endpoint_path
from .runtime.config import RuntimeConfig
from .runtime.pipeline import build_pipeline
from .runtime.transports.hub import HubServer

logger = logging.getLogger(__name__)


def _build_engine(out: str, args):
    """out= engine factory.  TPU JAX engine registers here as out=tpu."""
    if out == "echocore":
        return EchoEngineCore(), "core"
    if out == "echofull":
        return EchoEngineFull(), "full"
    if out == "tpu":
        from .engine import build_tpu_engine  # deferred: imports jax

        return build_tpu_engine(args), "core"
    raise SystemExit(f"unknown out= engine: {out!r}")


def _tokenizer_spec(args) -> dict:
    tok = getattr(args, "tokenizer", None)
    if tok:
        if tok.endswith(".gguf"):
            return {"kind": "gguf", "file": tok}
        if tok.endswith(".model"):
            # Explicit sentencepiece file (the pre-r5 error message pointed
            # sp-only checkpoints at --tokenizer).
            return {"kind": "sp", "file": tok}
        if os.path.isdir(tok):
            return {"kind": "hf", "dir": tok}
        return {"kind": "hf", "file": tok}
    ckpt = getattr(args, "checkpoint", None)
    if ckpt:
        # build_tpu_engine resolved the checkpoint spec to a local path;
        # serve its own tokenizer + chat template when it ships one.  The
        # ORIGINAL spec rides along so a frontend on another host (which
        # cannot see this worker's filesystem) can re-resolve it.
        from .models.hub import tokenizer_spec

        spec = tokenizer_spec(ckpt)
        if spec is not None:
            source = getattr(args, "checkpoint_source", None)
            if source:
                spec["source"] = source
            return spec
    return {"kind": "byte"}


async def _run_hub(args) -> None:
    server = await HubServer(
        host=args.host, port=args.port, persist_path=args.persist
    ).start()
    print(f"hub listening on {server.address}", flush=True)
    await _wait_forever()


def _edge_tracing():
    """Edge-side tracing surfaces (runtime/tracing.py, docs/tracing.md):
    the TraceSampler (head + forced + tail-keep sampling decisions) and a
    TraceAggregator serving /traces.  Returns (sampler, aggregator, cfg)
    — (None, None, cfg) when the ``tracing`` config section disables the
    plane, which removes every per-request cost at the edge."""
    from .llm.trace_service import TraceAggregator
    from .runtime.tracing import TraceSampler, TracingConfig

    cfg = TracingConfig.from_config(RuntimeConfig.from_layers().tracing)
    if not cfg.enabled:
        return None, None, cfg
    return TraceSampler(cfg), TraceAggregator(ttl_s=cfg.ttl_s), cfg


def _edge_qos(args):
    """QosController for the HTTP edge from the layered ``qos`` config
    section under explicit --qos-*/--brownout flags (llm/qos.py).  Returns
    None when neither quotas nor the brownout ladder are enabled — zero
    behaviour change by default."""
    from .llm.qos import QosConfig, QosController

    section = dict(RuntimeConfig.from_layers().qos)
    for key in ("tenant_weights", "default_weight", "batch_every"):
        section.pop(key, None)  # scheduler half (engine/__init__.py)
    if getattr(args, "qos_rate", None) is not None:
        section["rate"] = args.qos_rate
    if getattr(args, "qos_burst", None) is not None:
        section["burst"] = args.qos_burst
    if getattr(args, "brownout", False) and not section.get("brownout"):
        # The explicit flag wins over an absent/disabled config value, but
        # a configured brownout DICT (custom thresholds) is kept as-is.
        section["brownout"] = True
    cfg = QosConfig.from_dict(section)
    if cfg.rate is None and cfg.brownout is None:
        return None
    return QosController(cfg)


async def _run_http_frontend(args) -> None:
    from .runtime.client import RouterMode

    runtime = await DistributedRuntime.connect(args.hub)
    # CLI flags win over the layered config's `resilience` section
    # (DYN_RESILIENCE__HTTP_MAX_INFLIGHT=64 etc.), which wins over defaults.
    res = RuntimeConfig.from_layers().resilience
    raw_inflight = res.get("http_max_inflight")
    qos_ctl = _edge_qos(args)
    sampler, aggregator, tracing_cfg = _edge_tracing()
    service = HttpService(
        host=args.host,
        port=args.port,
        max_inflight=(
            args.max_inflight
            if args.max_inflight is not None
            else int(raw_inflight) if raw_inflight else None
        ),
        admission_queue=(
            args.admission_queue
            if args.admission_queue
            else int(res.get("http_admission_queue", 0))
        ),
        admission_timeout_s=(
            args.admission_timeout_s
            if args.admission_timeout_s != 1.0
            else float(res.get("http_admission_timeout_s", 1.0))
        ),
        default_deadline_s=(
            args.deadline_s
            if args.deadline_s is not None
            else res.get("request_deadline_s")
        ),
        qos=qos_ctl,
        tracing=sampler,
        trace_aggregator=aggregator,
        hub=runtime.hub,
    )
    mode = RouterMode(getattr(args, "router", "round_robin"))
    watcher = await ModelWatcher(runtime, service.models, router_mode=mode).start()
    await service.start()
    # Publish the edge's rolling TTFT/ITL percentiles on the namespace's
    # slo_metrics subject — the planner's SLO input (planner/signals.py).
    from .planner.signals import EdgeSloPublisher

    ns = RuntimeConfig.from_layers().namespace
    slo_pub = await EdgeSloPublisher(
        runtime.namespace(ns), service.metrics, qos=qos_ctl
    ).start()
    exporter = None
    bulk_ingest = None
    if aggregator is not None:
        # Span plane (docs/tracing.md): workers publish span batches on the
        # namespace's ``traces`` subject — the aggregator subscribes and
        # assembles them with the edge's own spans (client.route, the
        # edge.request root), which export straight into it in-process.
        from .runtime.tracing import SpanExporter

        await aggregator.start(runtime.namespace(ns))
        from .runtime.transports.bulk import bulk_enabled

        if bulk_enabled():
            # Bulk span ingest (docs/bulk_plane.md): worker exporters push
            # batches straight here instead of fanning through the hub's
            # pub/sub plane; the subscription above stays live as the
            # fallback path (and the A/B oracle).
            from .llm.trace_service import start_bulk_ingest

            bulk_ingest = await start_bulk_ingest(aggregator, runtime)
        exporter = await SpanExporter(
            [aggregator],
            interval_s=tracing_cfg.export_interval_s,
            proc="edge",
        ).start()
    print(f"OpenAI frontend on http://{service.host}:{service.port}", flush=True)
    try:
        await _wait_forever()
    finally:
        if exporter is not None:
            await exporter.stop()
        if bulk_ingest is not None:
            await bulk_ingest.close()
        if aggregator is not None:
            await aggregator.stop()
        await slo_pub.stop()
        await watcher.stop()
        await service.close()
        await runtime.close()


async def _run(args) -> None:
    inp = args.inp
    engine, level = _build_engine(args.out, args)
    # The engine's account of its start (engine/phases.py): it ends where the
    # HTTP service accepts.  Engines without one (echo) have nothing to close.
    setup = getattr(engine, "setup", None)
    tokenizer = make_tokenizer(_tokenizer_spec(args))

    # Multi-host: followers only replay the leader's dispatch stream; the
    # leader broadcasts every dispatch before enqueueing its own.
    nnodes = getattr(args, "nnodes", 1)
    if nnodes > 1:
        from .engine.multihost import StepPublisher, follower_serve

        if not hasattr(engine, "mirror_step"):
            raise SystemExit("--nnodes > 1 requires out=tpu")
        if getattr(args, "node_rank", 0) > 0:
            leader_host = args.coordinator.rsplit(":", 1)[0]
            print(
                f"follower node {args.node_rank}/{nnodes} replaying "
                f"{leader_host}:{args.step_port}",
                flush=True,
            )
            await follower_serve(engine, f"{leader_host}:{args.step_port}")
            return
        # Bind to the coordinator's interface, not 0.0.0.0: the step plane
        # carries pickled frames, so exposure must stay inside the
        # deployment's trust domain (plus DYN_STEP_TOKEN auth — multihost.py).
        # The advertised coordinator name may not be locally bindable (VIP /
        # NAT / port-forward); fall back to 0.0.0.0 then — auth still holds.
        # OSError: the name isn't locally bindable (VIP/NAT).  TimeoutError:
        # it bound, but to an interface followers can't reach (e.g. a
        # 127.0.1.1 /etc/hosts alias) — followers keep retrying for 120s
        # (follower_serve), so the 0.0.0.0 retry still catches them.
        step_host = args.coordinator.rsplit(":", 1)[0] if args.coordinator else "0.0.0.0"
        first = StepPublisher(step_host, args.step_port, nnodes - 1)
        try:
            publisher = await first.start(timeout=60.0)
        except (OSError, asyncio.TimeoutError):
            # abort, not close: a 'close' broadcast would make any
            # already-connected follower exit permanently instead of
            # reconnecting to the rebound publisher.
            await first.abort()
            # NB: with no DYN_STEP_TOKEN this wildcard rebind refuses to
            # start (StepPublisher.start) — the fallback is only available
            # to authenticated deployments.
            print(
                f"step plane: cannot serve followers on {step_host}, "
                "falling back to 0.0.0.0 (firewall the port; requires "
                "DYN_STEP_TOKEN)",
                flush=True,
            )
            publisher = await StepPublisher(
                "0.0.0.0", args.step_port, nnodes - 1
            ).start()
        engine.attach_publisher(publisher)

    warm = getattr(args, "warmup", None)
    if (inp == "http" if warm is None else warm) and hasattr(
        engine, "run_warmup"
    ):
        # No cold XLA compile may land inside a request.
        await engine.run_warmup()

    if getattr(args, "record", None):
        # Tap every request/response stream to JSONL (reference:
        # recorder.rs) — replayable via runtime.recorder.replay_into.
        # Wrapped HERE so every input mode records (in=http included).
        from .runtime.recorder import RecordingEngine, StreamRecorder

        recorder = StreamRecorder(args.record)
        engine = RecordingEngine(engine, recorder)
        print(f"recording streams to {args.record}", flush=True)

    # One grammar compile cache for EVERY core-level pipeline on this
    # tokenizer (base model and adapter aliases alike): constraint →
    # automaton indexing is the expensive step (llm/tenancy/grammar.py),
    # and per-pipeline caches would recompile the same schema per name.
    grammar_compiler = None
    if level == "core":
        from .llm.tenancy.grammar import GrammarCompiler

        grammar_compiler = GrammarCompiler(tokenizer)

    def _console_pipeline():
        if level == "core":
            return build_pipeline(
                [
                    OpenAIPreprocessor(
                        tokenizer, args.model,
                        grammar_compiler=grammar_compiler,
                    ),
                    Backend(tokenizer),
                ],
                engine,
            )
        return engine

    if inp == "http":
        # Colocated engine: feed its live KV usage to the brownout ladder.
        kv_usage_fn = (
            (lambda: engine.metrics().gpu_cache_usage_perc)
            if hasattr(engine, "metrics")
            else None
        )
        # ... and its decode-dispatch health to /metrics
        # (dynamo_tpu_engine_dispatch_*; llm/metrics.py).
        if hasattr(engine, "dispatch_summary"):
            from .llm.metrics import engine_dispatch_metrics

            engine_dispatch_metrics.set_source(engine.dispatch_summary)
        # ... and its KV tier gauges (dynamo_tpu_kv_tier_*; also rides the
        # edge SLO publication as the fleet prefix-hit-rate signal).
        if hasattr(engine, "kv_tier_summary"):
            from .llm.metrics import kv_tier_metrics

            kv_tier_metrics.set_source(engine.kv_tier_summary)
        # Colocated tracing (docs/tracing.md): edge and engine share this
        # process, so the exporter feeds the aggregator directly — no hub
        # hop; /traces serves assembled timelines immediately.
        sampler, aggregator, _tcfg = _edge_tracing()
        exporter = None
        if aggregator is not None:
            from .runtime.tracing import SpanExporter

            exporter = await SpanExporter(
                [aggregator], interval_s=_tcfg.export_interval_s
            ).start()
        service = HttpService(
            host=args.host, port=args.port,
            qos=_edge_qos(args), kv_usage_fn=kv_usage_fn,
            tracing=sampler, trace_aggregator=aggregator,
        )
        pipeline = _console_pipeline()
        service.models.add_chat_model(args.model, pipeline)
        service.models.add_completion_model(args.model, pipeline)
        if hasattr(engine, "device_summary"):
            # One line saying what this process serves on (chip_smoke.py
            # and operators read it; /metrics carries the same facts).
            print(
                "engine " + json.dumps(engine.device_summary(), sort_keys=True),
                flush=True,
            )
        # LoRA adapters (llm/tenancy) serve as additional MODEL NAMES on
        # the same resident engine: each gets its own preprocessor that
        # stamps the adapter id + KV salt (one grammar compile cache shared
        # across all of them — same tokenizer).
        adapters = (
            engine.adapter_names() if hasattr(engine, "adapter_names") else []
        )
        if adapters and level == "core":
            for name in adapters:
                apipe = build_pipeline(
                    [
                        OpenAIPreprocessor(
                            tokenizer, name, adapter=name,
                            grammar_compiler=grammar_compiler,
                        ),
                        Backend(tokenizer),
                    ],
                    engine,
                )
                service.models.add_chat_model(name, apipe)
                service.models.add_completion_model(name, apipe)
        print(
            f"serving {args.model!r}"
            + (f" + adapters {adapters}" if adapters else "")
            + f" on http://{args.host}:{args.port}",
            flush=True,
        )
        try:
            await service.run(
                _stop_event(), on_listening=setup.mark_ready if setup else None
            )
        finally:
            if exporter is not None:
                await exporter.stop()
            close = getattr(engine, "close", None)
            if close is not None:
                await close()
    elif inp == "none":
        # Start the engine with no input surface (reference Input::None,
        # opt.rs:40-43: externally-coordinated deployments — here, e.g., a
        # warm spare or a follower-style process someone attaches to later).
        print(f"engine up (in=none), model {args.model!r}; ctrl-C to exit", flush=True)
        try:
            await _wait_forever()
        finally:
            close = getattr(engine, "close", None)
            if close is not None:
                await close()
    elif inp in ("text", "stdin") or inp.startswith("batch:"):
        # Console modes (reference: dynamo-run in=text|stdin|batch:FILE,
        # launch/dynamo-run/src/opt.rs:23-38) — same pipeline as in=http.
        from .llm.console import run_batch, run_stdin_prompt, run_text_chat

        pipeline = _console_pipeline()
        try:
            if inp == "text":
                await run_text_chat(pipeline, args.model, args)
            elif inp == "stdin":
                await run_stdin_prompt(pipeline, args.model, args)
            else:
                await run_batch(
                    pipeline, args.model, inp[len("batch:"):], args
                )
        finally:
            close = getattr(engine, "close", None)
            if close is not None:
                await close()
    elif inp.startswith("dyn://"):
        if not args.hub:
            raise SystemExit("worker mode requires --hub HOST:PORT")
        role = getattr(args, "disagg", None)
        if role and not hasattr(engine, "inject_blocks"):
            raise SystemExit(
                f"--disagg {role} requires the native TPU engine (out=tpu), "
                f"not out={args.out}"
            )
        runtime = await DistributedRuntime.connect(args.hub)
        ns, comp, ep = parse_endpoint_path(inp)
        endpoint = runtime.namespace(ns).component(comp).endpoint(ep)
        # Span plane (docs/tracing.md): ONE exporter per worker process —
        # the process-global collector holds every role's spans (engine
        # queue/prefill/decode, disagg, migration, kv donor), and batches
        # publish on the namespace's ``traces`` subject for the edge-side
        # aggregator.  Nothing to drain when tracing is disabled or no
        # request is sampled; the hub client re-arms publishes across hub
        # restarts like every other publisher.
        from .runtime.tracing import TRACES_TOPIC, SpanExporter, TracingConfig

        trace_exporter = None
        tcfg = TracingConfig.from_config(RuntimeConfig.from_layers().tracing)
        if tcfg.enabled:
            # Honor ``tracing.ring`` here too: workers are the span-heaviest
            # processes (decode chunks), and only the edge's TraceSampler
            # otherwise applies the capacity.
            from .runtime.tracing import collector as trace_collector

            if tcfg.ring != trace_collector._ring.maxlen:
                trace_collector.set_capacity(tcfg.ring)
            namespace = runtime.namespace(ns)

            async def _publish_spans(payload):
                await namespace.publish(TRACES_TOPIC, payload)

            span_sink = _publish_spans
            from .runtime.transports.bulk import BulkRendezvous, bulk_enabled

            if bulk_enabled():
                # Bulk span export (docs/bulk_plane.md): batches push
                # directly to the edge aggregator's bulk sink; the hub
                # publish above stays wired as the fallback rung.
                from .llm.trace_service import make_bulk_span_sink

                span_sink = make_bulk_span_sink(
                    BulkRendezvous(runtime.hub, lease=runtime.primary_lease),
                    _publish_spans,
                )
            trace_exporter = await SpanExporter(
                [span_sink],
                interval_s=tcfg.export_interval_s,
                proc=f"worker-{runtime.worker_id}",
            ).start()
        roles = WorkerRoles(args, runtime, endpoint, engine, _tokenizer_spec(args))
        if role == "prefill":
            await roles.start_prefill()
        else:
            await roles.start_decode(disagg=role == "decode")
        flipper = None
        if role in ("decode", "prefill"):
            # Planner role flips (planner/actuate.py LocalActuator →
            # planner/roles/{worker_id}) work BOTH directions on the same
            # resident engine: decode→prefill migrates live sequences out
            # then starts a queue-drain loop; prefill→decode finishes the
            # in-flight queue item then brings up the full decode surface
            # (kv_import endpoint included).
            from .planner.actuate import RoleFlipWatcher

            async def _switch_decode() -> None:
                await roles.start_decode(disagg=True)

            flipper = await RoleFlipWatcher(
                runtime.hub,
                runtime.worker_id,
                role,
                drain={
                    "decode": roles.stop_decode,
                    "prefill": roles.stop_prefill,
                },
                switch={
                    "prefill": roles.start_prefill,
                    "decode": _switch_decode,
                },
            ).start()
        if setup is not None:
            setup.mark_ready()  # a worker is ready where its endpoint serves
        print(
            f"worker serving {inp} (model {args.model!r}"
            + (f", disagg={role}" if role else "")
            + ")",
            flush=True,
        )
        try:
            await _wait_forever()
        finally:
            if flipper is not None:
                await flipper.stop()
            await roles.shutdown()
            if trace_exporter is not None:
                # Final flush ships the last spans before the hub client
                # closes (best-effort: a dead hub just counts an error).
                await trace_exporter.stop()
            await runtime.close()
    else:
        raise SystemExit(f"unknown in= input: {inp!r}")


class WorkerRoles:
    """Role lifecycle for one dyn:// worker: start/stop the decode and
    prefill roles on a single resident engine (weights never reload across
    flips).  The decode role's stop hook drains via LIVE MIGRATION first
    (llm/migration): sequences move to a peer in O(KV transfer) instead of
    being waited out in O(sequence length), which is what makes planner
    scale-down/flip actuation cheap."""

    def __init__(self, args, runtime, endpoint, engine, tokenizer_spec):
        self.args = args
        self.runtime = runtime
        self.endpoint = endpoint
        self.engine = engine
        self.tokenizer_spec = tokenizer_spec
        self._handles: dict = {}
        # The decode role's MigratableWorker (None while in prefill role).
        self.migratable = None

    # -- decode role --------------------------------------------------------

    async def start_decode(self, disagg: bool) -> None:
        args, runtime, endpoint, engine = (
            self.args, self.runtime, self.endpoint, self.engine,
        )
        h: dict = {"serveds": []}
        served_engine = engine
        metadata: dict = {"role": "decode"} if disagg else {}
        if disagg:
            from .llm.disagg import (
                KV_IMPORT_ENDPOINT,
                DisaggConfig,
                DisaggDecodeWorker,
                DisaggregatedRouter,
                PrefillQueue,
            )

            server = await runtime.service_server()
            import_ep = endpoint.component.endpoint(KV_IMPORT_ENDPOINT)
            disagg_router = await DisaggregatedRouter(
                args.model,
                DisaggConfig(
                    max_local_prefill_length=args.max_local_prefill,
                ),
            ).watch_config(runtime.hub)
            h["router"] = disagg_router
            worker = DisaggDecodeWorker(
                engine,
                PrefillQueue(runtime.hub, args.model),
                disagg_router,
                import_address=server.address,
                import_path=import_ep.path,
            )
            h["serveds"].append(
                await import_ep.serve_endpoint(worker.kv_import_handler)
            )
            stats_ep = endpoint.component.endpoint("disagg_stats")
            h["serveds"].append(
                await stats_ep.serve_endpoint(worker.stats_handler)
            )
            h["disagg"] = worker
            served_engine = worker
        if hasattr(engine, "inject_blocks"):  # native TPU engine
            # Live-migration surface: peers (and the planner's drain path)
            # move running sequences here preemption-free.  The instance
            # metadata advertises the capability so target discovery
            # (llm/migration/coordinator.py) finds this worker.
            from .llm.migration import (
                MIGRATE_IN_ENDPOINT,
                MIGRATE_OUT_ENDPOINT,
                MigratableWorker,
            )

            mig = MigratableWorker(engine, serve=served_engine)
            mig_in = endpoint.component.endpoint(MIGRATE_IN_ENDPOINT)
            mig_out = endpoint.component.endpoint(MIGRATE_OUT_ENDPOINT)
            h["serveds"].append(
                await mig_in.serve_endpoint(mig.migrate_in_handler)
            )
            h["serveds"].append(
                await mig_out.serve_endpoint(mig.migrate_out_handler)
            )
            metadata["migrate"] = {
                "import_path": mig_in.path,
                "out_path": mig_out.path,
                "generate_path": endpoint.path,
            }
            served_engine = mig
            h["mig"] = mig
            self.migratable = mig
        h["serveds"].append(
            await endpoint.serve_endpoint(
                served_engine, metadata=metadata or None
            )
        )
        h["metadata"] = metadata
        kv_block_size = 16
        if hasattr(engine, "set_event_callback"):  # native TPU engine
            from .llm.kv_router.publisher import (
                KvEventPublisher,
                KvMetricsPublisher,
            )

            kv_block_size = engine.cfg.block_size
            engine.set_event_callback(
                KvEventPublisher(endpoint.component, runtime.worker_id)
            )
            h["metrics_pub"] = await KvMetricsPublisher(
                endpoint.component, runtime.worker_id, engine.metrics
            ).start()
            # Fleet-wide prefix reuse (docs/kv_tiering.md): serve this
            # worker's sealed blocks to peers at kv_export, pull a deeper
            # peer prefix at admission (router-stamped kv_pull hints), and
            # — when the disk tier is on — consume the router's
            # kv_prefetch plane to warm predicted prefixes disk→host.
            from .llm.kv_router.pull import (
                KV_EXPORT_ENDPOINT,
                KvPrefetchConsumer,
                PrefixPuller,
                make_client_exporter,
                make_kv_export_handler,
            )

            export_ep = endpoint.component.endpoint(KV_EXPORT_ENDPOINT)
            h["serveds"].append(
                await export_ep.serve_endpoint(make_kv_export_handler(engine))
            )
            pull_client = await export_ep.client()
            h["pull_client"] = pull_client
            engine.set_prefix_puller(
                PrefixPuller(engine, make_client_exporter(pull_client))
            )
            # KV integrity self-reporting (docs/kv_tiering.md §integrity):
            # this worker's OWN disk/host corruption detections feed the
            # watchdog's ledger under its worker id — a sick local medium
            # earns the same quarantine path as a donor shipping poison.
            from .runtime.health import kv_corruption

            wid = runtime.worker_id
            engine.set_integrity_reporter(
                lambda plane, _wid=wid: kv_corruption.record(_wid)
            )
            if getattr(engine, "disk_kv", None) is not None:
                h["prefetch"] = await KvPrefetchConsumer(
                    endpoint.component, engine
                ).start()
            from .llm.metrics import kv_tier_metrics

            kv_tier_metrics.set_source(engine.kv_tier_summary)
        from .runtime.transports.bulk import bulk_enabled

        if bulk_enabled() and hasattr(engine, "inject_blocks"):
            # Bulk data plane (docs/bulk_plane.md, DYN_BULK_PLANE): run this
            # worker's peer-to-peer stream server, register its address for
            # hub rendezvous, and repoint the bulk producers (prefix pull
            # exporter, migration copy stream) at it.  Every producer keeps
            # its hub-path transport wired underneath as the fallback rung,
            # so a dead bulk peer costs a fallback tick, never a stream.
            from .llm.kv_router.pull import (
                KV_EXPORT_ENDPOINT,
                PrefixPuller,
                make_bulk_export_source,
                make_bulk_exporter,
                make_client_exporter,
            )
            from .runtime.transports.bulk import (
                BulkRendezvous,
                BulkServer,
                bulk_addr_key,
            )

            bulk_srv = BulkServer(
                getattr(runtime, "_host", "127.0.0.1"),
                worker_id=runtime.worker_id,
                hub=runtime.hub,
            )
            bulk_srv.register_source(
                KV_EXPORT_ENDPOINT, make_bulk_export_source(engine)
            )
            if h.get("mig") is not None:
                from .llm.migration import MIGRATE_IN_ENDPOINT
                from .llm.migration.worker import make_migrate_in_sink

                bulk_srv.register_sink(
                    MIGRATE_IN_ENDPOINT, make_migrate_in_sink(h["mig"])
                )
            await bulk_srv.start()
            await runtime.register_key(
                bulk_addr_key(runtime.worker_id),
                {
                    "address": bulk_srv.address,
                    "worker_id": str(runtime.worker_id),
                },
            )
            rendezvous = BulkRendezvous(
                runtime.hub, lease=runtime.primary_lease
            )
            if h.get("mig") is not None:
                h["mig"].bulk = rendezvous
            if h.get("pull_client") is not None and hasattr(
                engine, "set_prefix_puller"
            ):
                engine.set_prefix_puller(
                    PrefixPuller(
                        engine,
                        make_bulk_exporter(
                            rendezvous,
                            make_client_exporter(h["pull_client"]),
                            max_bytes=engine.cfg.kv_pull_max_bytes,
                        ),
                    )
                )
            h["bulk_srv"] = bulk_srv
        await register_model(
            runtime,
            args.model,
            endpoint.path,
            tokenizer=self.tokenizer_spec,
            kv_block_size=kv_block_size,
        )
        # LoRA adapters (llm/tenancy) register as additional model names on
        # the SAME endpoint: the frontend's watcher builds adapter-stamping
        # pipelines for them, tenant KV salting keeps router overlap exact,
        # and the engine's served-model allowlist 404s anything else.
        for adapter in (
            engine.adapter_names() if hasattr(engine, "adapter_names") else []
        ):
            await register_model(
                runtime,
                adapter,
                endpoint.path,
                tokenizer=self.tokenizer_spec,
                kv_block_size=kv_block_size,
                lora={"adapter": adapter, "base": args.model},
            )
        self._handles["decode"] = h

    async def stop_decode(self) -> None:
        h = self._handles.pop("decode", None)
        if h is None:
            return
        if h.get("mig") is not None:
            # Close the drain race at BOTH ends.  (1) Accept-time gate:
            # refuse migrate-in from here on — even a peer holding a stale
            # hub snapshot that still advertises us gets refused when its
            # push arrives, so mutual drains are impossible regardless of
            # metadata propagation timing.  (2) De-advertise the migrate
            # capability so fresh target discovery stops picking us.
            from .llm.migration import drain_via_migration

            h["mig"].stop_accepting()
            try:
                md = {
                    k: v
                    for k, v in (h.get("metadata") or {}).items()
                    if k != "migrate"
                }
                await self.endpoint.update_metadata(md)
            except asyncio.CancelledError:
                raise
            except Exception:  # noqa: BLE001 — best effort; drain anyway
                logger.warning("could not de-advertise migrate capability",
                               exc_info=True)
            # Drain via migration: live sequences hand off to a peer in
            # O(transfer); anything that could not move (no peer, rollback)
            # simply keeps decoding here until it finishes.
            await drain_via_migration(
                h["mig"],
                self.runtime.hub,
                self.endpoint.instance_prefix,
                self.runtime.worker_id,
            )
        if h.get("disagg") is not None:
            await h["disagg"].drain(timeout=10.0)
        for served in reversed(h["serveds"]):
            await served.stop()
        if h.get("bulk_srv") is not None:
            # De-advertise BEFORE closing so a rendezvous racing the close
            # resolves to nothing (a caller falls back) instead of dialing
            # a dead address until its resume budget runs out.
            from .runtime.transports.bulk import bulk_addr_key

            await self.runtime.unregister_key(
                bulk_addr_key(self.runtime.worker_id)
            )
            await h["bulk_srv"].close()
        if h.get("prefetch") is not None:
            await h["prefetch"].stop()
        if hasattr(self.engine, "set_prefix_puller"):
            self.engine.set_prefix_puller(None)
        if hasattr(self.engine, "set_integrity_reporter"):
            self.engine.set_integrity_reporter(None)
        if h.get("pull_client") is not None:
            await h["pull_client"].close()
        if h.get("metrics_pub") is not None:
            await h["metrics_pub"].stop()
        if h.get("router") is not None:
            await h["router"].stop()
        await self.runtime.unregister_key(
            f"models/{self.args.model}/{self.runtime.worker_id}"
        )
        for adapter in (
            self.engine.adapter_names()
            if hasattr(self.engine, "adapter_names")
            else []
        ):
            await self.runtime.unregister_key(
                f"models/{adapter}/{self.runtime.worker_id}"
            )
        self.migratable = None

    # -- prefill role -------------------------------------------------------

    async def start_prefill(self) -> None:
        # Dedicated prefill worker: drains the queue; serves no endpoint.
        # It still registers a lease-bound heartbeat under its endpoint
        # path (metadata role=prefill) so the planner's SignalCollector
        # sees prefill-pool membership and its death is observable —
        # nothing routes to this path.
        from .llm.disagg import PrefillQueue, PrefillWorkerLoop

        ploop = await PrefillWorkerLoop(
            self.engine, PrefillQueue(self.runtime.hub, self.args.model)
        ).start()
        await self.runtime.register_key(
            self.endpoint.instance_key(self.runtime.worker_id),
            {
                "address": "",
                "path": self.endpoint.path,
                "worker_id": self.runtime.worker_id,
                "metadata": {"role": "prefill"},
            },
        )
        self._handles["prefill"] = {"ploop": ploop}

    async def stop_prefill(self) -> None:
        h = self._handles.pop("prefill", None)
        if h is None:
            return
        # Finish the in-flight queue item (bounded), then stop pulling;
        # a cancel that lands mid-dequeue requeues at-least-once.
        await h["ploop"].drain(timeout=10.0)
        await self.runtime.unregister_key(
            self.endpoint.instance_key(self.runtime.worker_id)
        )

    async def shutdown(self) -> None:
        await self.stop_decode()
        await self.stop_prefill()


async def _run_model_cmd(args) -> None:
    """llmctl equivalent (reference: launch/llmctl/src/main.rs:26-124)."""
    from .llm.discovery import MODEL_PREFIX, model_prefix

    runtime = await DistributedRuntime.connect(args.hub)
    try:
        if args.verb == "add":
            key = await register_model(
                runtime,
                args.name,
                args.endpoint,
                model_type=args.type,
                tokenizer={"kind": "hf", "file": args.tokenizer}
                if args.tokenizer
                else {"kind": "byte"},
                kv_block_size=args.block_size,
                static=True,
            )
            print(f"registered {args.name} -> {args.endpoint} ({key})")
        elif args.verb == "list":
            kvs = await runtime.hub.kv_get_prefix(MODEL_PREFIX)
            for key, entry in sorted(kvs.items()):
                print(f"{entry['name']}\t{entry['model_type']}\t{entry['endpoint']}")
            if not kvs:
                print("(no models registered)")
        elif args.verb == "remove":
            kvs = await runtime.hub.kv_get_prefix(model_prefix(args.name))
            for key in kvs:
                await runtime.hub.kv_delete(key)
            print(f"removed {len(kvs)} registration(s) for {args.name}")
    finally:
        await runtime.close()


async def _run_metrics(args) -> None:
    """Namespace metrics aggregator (reference: components/metrics)."""
    from .llm.metrics_service import MetricsAggregatorService

    runtime = await DistributedRuntime.connect(args.hub)
    component = runtime.namespace(args.namespace).component(args.component)
    service = await MetricsAggregatorService(
        component, host=args.host, port=args.port
    ).start()
    print(f"metrics aggregator on http://{args.host}:{args.port}/metrics", flush=True)
    try:
        await _wait_forever()
    finally:
        await service.stop()
        await runtime.close()


async def _run_mock_worker(args) -> None:
    """Synthetic metrics/KV-event publisher (reference: mock_worker.rs)."""
    from .llm.metrics_service import MockWorker

    runtime = await DistributedRuntime.connect(args.hub)
    component = runtime.namespace(args.namespace).component(args.component)
    worker = await MockWorker(
        component, runtime.worker_id, interval=args.interval
    ).start()
    print(f"mock worker {runtime.worker_id} publishing", flush=True)
    try:
        await _wait_forever()
    finally:
        await worker.stop()
        await runtime.close()


async def _run_operator(args) -> None:
    """In-cluster reconcile loop (reference: the Go operator binary) —
    drives BOTH CRDs: deployments and model caches (the reference's
    dynamonimdeployment + dynamonimrequest controller pair)."""
    from .deploy.controller import KubeApi, Reconciler
    from .deploy.model_cache import ModelCacheReconciler

    kube = KubeApi(namespace=args.namespace, base=args.api_server)
    print(
        f"operator reconciling {args.namespace}/dynamotpudeployments "
        f"+ dynamotpumodelcaches (watch-triggered, {args.poll_interval}s "
        f"resync)",
        flush=True,
    )
    try:
        # Both controllers run watch-triggered with periodic resync; a
        # failing watch degrades each to pure polling independently.
        await asyncio.gather(
            Reconciler(kube).run(poll_interval=args.poll_interval),
            ModelCacheReconciler(kube).run(poll_interval=args.poll_interval),
        )
    finally:
        await kube.close()


def _run_prepare(args) -> None:
    """Pre-stage a checkpoint into the model cache (the model-cache Job's
    entrypoint; also useful interactively for offline deployments)."""
    import shutil

    if args.cache:
        os.environ["DYN_MODEL_CACHE"] = args.cache
    from .models.hub import ALIASES, cache_dir, resolve_model

    path = resolve_model(args.model, revision=args.revision)
    # A remote spec resolves into huggingface_hub's OWN cache (ephemeral in
    # a fetch pod) — copy the serving artifacts into DYN_MODEL_CACHE so the
    # PVC actually holds them (the entire point of the fetch Job).
    spec_local = os.path.isdir(args.model) or args.model.endswith(".gguf")
    cd = os.path.abspath(cache_dir())
    if not spec_local and not os.path.abspath(path).startswith(cd + os.sep):
        repo = ALIASES.get(args.model.lower(), args.model)
        staged = os.path.join(cd, repo.replace("/", "--"))
        os.makedirs(staged, exist_ok=True)
        for f in sorted(os.listdir(path)):
            src = os.path.join(path, f)  # may symlink into the blob store
            dst = os.path.join(staged, f)
            if os.path.isfile(src) and not os.path.exists(dst):
                shutil.copyfile(src, dst)  # copyfile resolves symlinks
        path = staged
    print(path, flush=True)


async def _run_api_store(args) -> None:
    """Deployment-management REST API (reference: api-store FastAPI app)."""
    from .deploy.api_store import ApiStore
    from .runtime.transports.hub import HubClient

    hub = await HubClient(args.hub).connect()
    reconciler = None
    if args.kube:
        from .deploy.controller import KubeApi, Reconciler

        # Distinct manager identity: the operator's orphan sweep must never
        # treat api-store children as its own (and vice versa).
        reconciler = Reconciler(
            KubeApi(namespace=args.namespace), manager="api-store"
        )
    token = args.token or os.environ.get("DYN_API_TOKEN") or None
    if token is None and args.host not in ("127.0.0.1", "localhost", "::1"):
        print(
            "api-store WARNING: binding a non-loopback address with no "
            "--token/DYN_API_TOKEN — any network peer can create/delete "
            "deployments",
            flush=True,
        )
    store = await ApiStore(
        hub, reconciler, host=args.host, port=args.port, token=token
    ).start()
    print(f"api-store on http://{args.host}:{store.port}", flush=True)
    try:
        await _wait_forever()
    finally:
        await store.close()
        if reconciler is not None:
            await reconciler.kube.close()
        await hub.close()


def _stop_event() -> asyncio.Event:
    """An event SIGINT/SIGTERM set, so the caller's ``finally`` runs and
    the process exits 0 instead of dying in the default handler."""
    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        try:
            loop.add_signal_handler(sig, stop.set)
        except NotImplementedError:
            pass
    return stop


async def _wait_forever() -> None:
    await _stop_event().wait()


def main(argv: Optional[list] = None) -> None:
    # DYN_LOG / DYN_LOG_FORMAT / DYN_LOG_FILE (reference logging.rs)
    from .runtime.logging_config import setup_logging

    setup_logging()
    parser = argparse.ArgumentParser(prog="dynamo-tpu")
    sub = parser.add_subparsers(dest="cmd", required=True)

    p_hub = sub.add_parser("hub", help="run the control-plane hub")
    p_hub.add_argument("--host", default="0.0.0.0")
    p_hub.add_argument("--port", type=int, default=6650)
    p_hub.add_argument(
        "--persist", default=None,
        help="snapshot file: durable KV + queues survive hub restart",
    )

    p_http = sub.add_parser("http", help="standalone OpenAI frontend w/ discovery")
    p_http.add_argument("--hub", required=True)
    p_http.add_argument("--host", default="0.0.0.0")
    p_http.add_argument("--port", type=int, default=8000)
    p_http.add_argument(
        "--router",
        default="round_robin",
        choices=["random", "round_robin", "kv"],
        help="worker selection policy (kv = cache-aware)",
    )
    # Admission control / deadlines (runtime/resilience.py); defaults keep
    # both disabled, matching the previous behaviour.
    p_http.add_argument(
        "--max-inflight", type=int, default=None, dest="max_inflight",
        help="in-flight request cap (unset = unlimited)",
    )
    p_http.add_argument(
        "--admission-queue", type=int, default=0, dest="admission_queue",
        help="bounded wait queue beyond the cap; overflow sheds 429",
    )
    p_http.add_argument(
        "--admission-timeout-s", type=float, default=1.0,
        dest="admission_timeout_s",
        help="max queue wait before shedding 503",
    )
    p_http.add_argument(
        "--deadline-s", type=float, default=None, dest="deadline_s",
        help="default per-request deadline (504 on exhaustion)",
    )
    # QoS / overload control (llm/qos.py); defaults keep both disabled.
    p_http.add_argument(
        "--qos-rate", type=float, default=None, dest="qos_rate",
        help="per-tenant sustained requests/s (token bucket; unset = off)",
    )
    p_http.add_argument(
        "--qos-burst", type=float, default=None, dest="qos_burst",
        help="per-tenant burst allowance (default 2x rate)",
    )
    p_http.add_argument(
        "--brownout", action="store_true",
        help="enable the brownout degradation ladder (docs/qos.md)",
    )

    p_run = sub.add_parser("run", help="in=… out=… launcher")
    p_run.add_argument("inout", nargs=2, metavar="in=/out=")
    p_run.add_argument("--hub", default=None)
    p_run.add_argument("--host", default="0.0.0.0")
    p_run.add_argument("--port", type=int, default=8000)
    p_run.add_argument("--model", default="echo")
    # Console input modes (in=text/stdin/batch:FILE) sampling defaults.
    p_run.add_argument("--max-tokens", type=int, default=None, dest="max_tokens")
    p_run.add_argument("--temperature", type=float, default=None)
    p_run.add_argument("--tokenizer", default=None, help="path to tokenizer.json")
    p_run.add_argument("--model-config", default=None, help="model config json (out=tpu)")
    # out=tpu engine knobs (reference: launch/dynamo-run/src/flags.rs)
    p_run.add_argument("--arch", default=None, help="model architecture name or HF dir (out=tpu)")
    p_run.add_argument("--checkpoint", default=None, help="safetensors dir (out=tpu)")
    p_run.add_argument("--tp", type=int, default=1, help="tensor parallel size")
    p_run.add_argument("--dp", type=int, default=1, help="data parallel size")
    p_run.add_argument("--ep", type=int, default=1, help="expert parallel size")
    p_run.add_argument(
        "--sp", type=int, default=1,
        help="sequence parallel size (ring-attention long-prompt prefill)",
    )
    p_run.add_argument(
        "--sp-prefill-min", type=int, default=1024, dest="sp_prefill_min",
        help="prompts at least this long use the sp whole-prompt prefill",
    )
    p_run.add_argument("--block-size", type=int, default=16, dest="block_size")
    p_run.add_argument("--num-blocks", type=int, default=256, dest="num_blocks")
    p_run.add_argument("--max-batch", type=int, default=8, dest="max_batch")
    p_run.add_argument("--max-model-len", type=int, default=1024, dest="max_model_len")
    p_run.add_argument("--prefill-chunk", type=int, default=512, dest="prefill_chunk")
    p_run.add_argument(
        "--dtype", default="bfloat16",
        help="weight/activation dtype (bfloat16 on TPU; float32 for CPU runs)",
    )
    p_run.add_argument(
        "--decode-steps", type=int, default=4, dest="decode_steps",
        help="decode iterations fused into one device dispatch",
    )
    p_run.add_argument(
        "--pipeline-depth", type=int, default=2, dest="pipeline_depth",
        help="fused decode dispatches kept in flight",
    )
    p_run.add_argument(
        "--kv-cache-dtype", default=None, dest="cache_dtype",
        help="KV page dtype (e.g. float8_e4m3fn halves KV memory)",
    )
    p_run.add_argument(
        "--host-cache-mb", type=int, default=0, dest="host_cache_mb",
        help="host (CPU RAM) KV tier budget in MiB: sealed blocks survive "
        "HBM eviction and restore as prefix hits (0 = off)",
    )
    p_run.add_argument(
        "--disk-cache-mb", type=int, default=0, dest="disk_cache_mb",
        help="disk KV tier budget in MiB: host-tier eviction demotes "
        "blocks to hash-named files instead of dropping them "
        "(requires --host-cache-mb; docs/kv_tiering.md)",
    )
    p_run.add_argument(
        "--disk-cache-dir", default=None, dest="disk_cache_dir",
        help="directory for the disk KV tier's block files "
        "(default: a per-process dir under the system temp root)",
    )
    p_run.add_argument(
        "--object-store-mb", type=int, default=0, dest="object_store_mb",
        help="durable object-store KV tier budget in MiB: disk-tier "
        "eviction and explicit persists land in a fleet-shared object "
        "layout that outlives the worker, so a scale-from-zero replica "
        "boots warm (requires --disk-cache-mb and --object-store-dir; "
        "docs/kv_tiering.md)",
    )
    p_run.add_argument(
        "--object-store-dir", default=None, dest="object_store_dir",
        help="object layout root for the durable KV tier (required with "
        "--object-store-mb: the store outlives the process, so there is "
        "no per-process default)",
    )
    p_run.add_argument(
        "--kv-pull-mb", type=int, default=None, dest="kv_pull_mb",
        help="cross-worker prefix pull byte budget in MiB (the router "
        "hints a peer holding a deeper prefix; the engine pulls the "
        "delta over the KV transfer plane instead of recomputing)",
    )
    p_run.add_argument(
        "--kv-scale",
        type=lambda s: s if s == "auto" else float(s),
        default=1.0,
        dest="kv_scale",
        help="quantized KV pages: a static scale, or 'auto' to calibrate "
        "per-layer scales from a probe forward at startup",
    )
    p_run.add_argument(
        "--attn-impl",
        default="auto",
        choices=["auto", "tpu", "xla"],
        dest="attn_impl",
        help="attention backend: tpu = the Pallas kernels, xla = the "
        "gather fallback (the oracle); auto picks tpu on a TPU backend at "
        "head_dim % 128 == 0",
    )
    from .engine.config import DECODE_KERNELS, PREFILL_KERNELS

    p_run.add_argument(
        "--decode-kernel",
        default="auto",
        choices=["auto", *DECODE_KERNELS],
        dest="decode_kernel",
        help="decode-path attention kernel (ops/decode_attention.py): "
        "pallas_fused = our fused-dequant split-KV kernel, stock = the "
        "jax pallas ragged kernel with tuned hints, xla = the "
        "bit-exactness oracle.  auto resolves DYN_DECODE_KERNEL, then "
        "pallas_fused on TPU / stock elsewhere",
    )
    p_run.add_argument(
        "--prefill-kernel",
        default="auto",
        choices=["auto", *PREFILL_KERNELS],
        dest="prefill_kernel",
        help="prefill-path attention kernel (ops/prefill_attention.py): "
        "pallas = our chunked paged kernel, stock = the jax pallas ragged "
        "kernel, xla = the byte-identity oracle.  auto resolves "
        "DYN_PREFILL_KERNEL, then pallas on TPU / stock elsewhere",
    )
    p_run.add_argument(
        "--weight-quant",
        default=None,
        choices=["int8"],
        dest="weight_quant",
        help="int8 = W8A8-dynamic weights (models/quant.py): what lets a "
        "7-8B model fit one 16 GB chip.  Default: the --dtype weights",
    )
    p_run.add_argument(
        "--warmup",
        action=argparse.BooleanOptionalAction,
        default=None,
        help="compile every reachable device program before serving "
        "(engine.warmup).  Default: on for in=http, off otherwise",
    )
    p_run.add_argument(
        "--spec-decode",
        action="store_true",
        default=None,
        dest="spec_decode",
        help="enable draft-free speculative decoding (n-gram prompt "
        "lookup, verified in-step; engine/spec.py — token streams are "
        "identical to non-speculative decoding)",
    )
    p_run.add_argument(
        "--spec-k", type=int, default=None, dest="spec_k",
        help="max draft tokens per sequence per dispatch",
    )
    p_run.add_argument(
        "--spec-ngram-min", type=int, default=None, dest="spec_ngram_min",
        help="shortest suffix n-gram tried by the proposer",
    )
    p_run.add_argument(
        "--spec-ngram-max", type=int, default=None, dest="spec_ngram_max",
        help="longest suffix n-gram tried by the proposer",
    )
    p_run.add_argument(
        "--lora",
        action="append",
        default=None,
        metavar="NAME=SPEC",
        help="serve a LoRA adapter under model name NAME (repeatable; "
        "llm/tenancy).  SPEC is a local PEFT directory, a HF repo id, or "
        "'random[:seed]' for a synthetic adapter.  Requests select the "
        "adapter via the OpenAI 'model' field; unknown names 404.",
    )
    p_run.add_argument(
        "--lora-max-adapters", type=int, default=None,
        dest="lora_max_adapters",
        help="resident device adapter slots (distinct adapters per batch)",
    )
    p_run.add_argument(
        "--lora-rank", type=int, default=None, dest="lora_rank",
        help="per-slot rank ceiling (smaller-rank adapters zero-pad up)",
    )
    p_run.add_argument(
        "--record", default=None,
        help="capture every request/response stream to this JSONL file "
        "(replayable — runtime/recorder.py)",
    )
    p_run.add_argument(
        "--disagg",
        default=None,
        choices=["decode", "prefill"],
        help="disaggregated role for this worker (requires --hub)",
    )
    p_run.add_argument(
        "--max-local-prefill",
        type=int,
        default=512,
        dest="max_local_prefill",
        help="prefills longer than this (minus prefix hit) go remote",
    )
    # multi-host scale-out (reference: MultiNodeConfig, engines.rs:40-105)
    p_run.add_argument(
        "--nnodes", type=int, default=1, help="total hosts in this engine"
    )
    p_run.add_argument(
        "--node-rank", type=int, default=0, dest="node_rank",
        help="this host's rank (0 = leader)",
    )
    p_run.add_argument(
        "--coordinator", default="",
        help="host:port of rank 0's jax.distributed coordinator",
    )
    p_run.add_argument(
        "--step-port", type=int, default=6651, dest="step_port",
        help="leader port for the follower dispatch stream",
    )
    p_run.add_argument(
        "--cpu-devices", type=int, default=None, dest="cpu_devices",
        help="TEST ONLY: use N virtual CPU devices per process",
    )
    # QoS / overload control for in=http (llm/qos.py; defaults disabled).
    p_run.add_argument(
        "--qos-rate", type=float, default=None, dest="qos_rate",
        help="per-tenant sustained requests/s (token bucket; unset = off)",
    )
    p_run.add_argument(
        "--qos-burst", type=float, default=None, dest="qos_burst",
        help="per-tenant burst allowance (default 2x rate)",
    )
    p_run.add_argument(
        "--brownout", action="store_true",
        help="enable the brownout degradation ladder (docs/qos.md)",
    )

    p_model = sub.add_parser("model", help="model registry (llmctl equivalent)")
    p_model.add_argument("verb", choices=["add", "list", "remove"])
    p_model.add_argument("name", nargs="?", default=None)
    p_model.add_argument("endpoint", nargs="?", default=None, help="dyn://ns.comp.ep")
    p_model.add_argument("--hub", required=True)
    p_model.add_argument("--type", default="both", choices=["chat", "completion", "both"])
    p_model.add_argument("--tokenizer", default=None)
    p_model.add_argument("--block-size", type=int, default=16, dest="block_size")

    p_metrics = sub.add_parser("metrics", help="namespace metrics aggregator")
    p_metrics.add_argument("--hub", required=True)
    p_metrics.add_argument("--namespace", default="dynamo")
    p_metrics.add_argument("--component", default="TpuWorker")
    p_metrics.add_argument("--host", default="0.0.0.0")
    p_metrics.add_argument("--port", type=int, default=9091)

    p_deploy = sub.add_parser(
        "deploy", help="render k8s manifests from a DynamoTpuDeployment CR"
    )
    p_deploy.add_argument("verb", choices=["render", "preview"])
    p_deploy.add_argument("-f", "--file", required=True, dest="cr_file")

    p_mock = sub.add_parser("mock-worker", help="synthetic metrics/KV events")
    p_mock.add_argument("--hub", required=True)
    p_mock.add_argument("--namespace", default="dynamo")
    p_mock.add_argument("--component", default="TpuWorker")
    p_mock.add_argument("--interval", type=float, default=0.5)

    p_prep = sub.add_parser(
        "prepare",
        help="pre-stage a model checkpoint into the cache "
             "(model-cache Job entrypoint)",
    )
    p_prep.add_argument("model")
    p_prep.add_argument("--cache", default=None,
                        help="destination dir (overrides DYN_MODEL_CACHE)")
    p_prep.add_argument("--revision", default=None)

    p_op = sub.add_parser(
        "operator",
        help="k8s controller: reconcile DynamoTpuDeployment + "
             "DynamoTpuModelCache CRs in-cluster",
    )
    p_op.add_argument("--namespace", default="default")
    p_op.add_argument("--poll-interval", type=float, default=10.0,
                      dest="poll_interval")
    p_op.add_argument("--api-server", default=None, dest="api_server",
                      help="override the in-cluster API server URL")

    p_store = sub.add_parser(
        "api-store",
        help="deployment-management REST API over the hub store",
    )
    p_store.add_argument("--hub", required=True)
    # Loopback by default: the store can create/delete k8s objects (with
    # --kube), so exposure beyond localhost is opt-in and should come with
    # --token (r4 advisory).
    p_store.add_argument("--host", default="127.0.0.1")
    p_store.add_argument("--port", type=int, default=7070)
    p_store.add_argument(
        "--kube", action="store_true",
        help="also reconcile created deployments against the k8s API",
    )
    p_store.add_argument("--namespace", default="default")
    p_store.add_argument(
        "--token", default=None,
        help="bearer token required on every request (default: "
        "DYN_API_TOKEN env; unset = unauthenticated)",
    )

    args = parser.parse_args(argv)
    if args.cmd == "model" and args.verb in ("add", "remove") and not args.name:
        parser.error(f"model {args.verb} requires a model name")
    if args.cmd == "model" and args.verb == "add" and not args.endpoint:
        parser.error("model add requires an endpoint path")
    if args.cmd == "run":
        kv = dict(part.split("=", 1) for part in args.inout)
        if "in" not in kv or "out" not in kv:
            raise SystemExit("run requires in=… out=…")
        args.inp, args.out = kv["in"], kv["out"]
        if args.nnodes > 1 or args.cpu_devices:
            # Must run before anything initializes a jax backend.
            from .parallel.distributed import MultiHostConfig, init_multihost

            init_multihost(
                MultiHostConfig(
                    coordinator=args.coordinator,
                    nnodes=args.nnodes,
                    node_rank=args.node_rank,
                    cpu_devices=args.cpu_devices,
                )
            )

    if args.cmd == "deploy":
        import yaml

        from .deploy import render_to_yaml, shell_preview

        with open(args.cr_file) as f:
            cr = yaml.safe_load(f)
        print(
            render_to_yaml(cr) if args.verb == "render" else shell_preview(cr)
        )
        return

    try:
        if args.cmd == "hub":
            asyncio.run(_run_hub(args))
        elif args.cmd == "http":
            asyncio.run(_run_http_frontend(args))
        elif args.cmd == "model":
            asyncio.run(_run_model_cmd(args))
        elif args.cmd == "prepare":
            _run_prepare(args)
        elif args.cmd == "metrics":
            asyncio.run(_run_metrics(args))
        elif args.cmd == "mock-worker":
            asyncio.run(_run_mock_worker(args))
        elif args.cmd == "operator":
            asyncio.run(_run_operator(args))
        elif args.cmd == "api-store":
            asyncio.run(_run_api_store(args))
        else:
            asyncio.run(_run(args))
    except KeyboardInterrupt:
        pass


if __name__ == "__main__":
    main()
