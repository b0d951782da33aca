"""Example LLM serving components — the SDK counterpart of the reference's
examples/llm/components/{frontend,processor,worker}.py, built on the native
TPU engine instead of vLLM.

Services:
- ``TpuWorker``  — native JAX engine serving token-in/token-out, publishing
  KV events + metrics (1 TPU chip by default).
- ``Processor``  — tokenizes OpenAI requests and routes token requests to
  workers (round-robin here; the HTTP frontend's --router kv does KV-aware
  routing in the main serving path).
- ``Frontend``   — entry service; in this deployment the OpenAI HTTP edge
  runs via ``--http-port`` on the runner, so Frontend only anchors the graph.
"""

from __future__ import annotations

import asyncio
from typing import Any, AsyncIterator, Dict

from dynamo_tpu.engine import EngineConfig
from dynamo_tpu.llm.backend import Backend
from dynamo_tpu.llm.discovery import make_tokenizer, register_model
from dynamo_tpu.llm.preprocessor import OpenAIPreprocessor
from dynamo_tpu.runtime.engine import Context
from dynamo_tpu.runtime.pipeline import build_pipeline
from dynamo_tpu.sdk import async_on_start, depends, dynamo_endpoint, service


@service(namespace="examples", resources={"tpu": 1})
class TpuWorker:
    """Native engine worker (reference: components/worker.py VllmWorker)."""

    def __init__(self, config: Dict[str, Any] | None = None):
        self.config = config or {}
        self.engine = None

    @async_on_start
    async def boot(self) -> None:
        from dynamo_tpu.engine.engine import TpuEngine
        from dynamo_tpu.llm.kv_router.publisher import (
            KvEventPublisher,
            KvMetricsPublisher,
        )

        cfg = EngineConfig(
            model=self.config.get("model", "debug-tiny"),
            block_size=int(self.config.get("block_size", 16)),
            num_blocks=int(self.config.get("num_blocks", 256)),
            max_batch=int(self.config.get("max_batch", 8)),
            max_model_len=int(self.config.get("max_model_len", 1024)),
            tp=int(self.config.get("tp", 1)),
        )
        # Off the event loop: building the engine compiles its initialisers
        # (seconds on a busy host), and a loop blocked past the hub's 10 s
        # lease TTL loses the lease before register_model can use it.
        self.engine = await asyncio.to_thread(TpuEngine, cfg)
        component = self.runtime.namespace("examples").component("TpuWorker")
        self.engine.set_event_callback(
            KvEventPublisher(component, self.runtime.worker_id)
        )
        self._metrics_pub = await KvMetricsPublisher(
            component, self.runtime.worker_id, self.engine.metrics
        ).start()
        await register_model(
            self.runtime,
            self.config.get("served_model_name", "example-model"),
            "examples.TpuWorker.generate",  # ns.component.endpoint
            tokenizer={"kind": "byte"},
            kv_block_size=cfg.block_size,
        )

    @dynamo_endpoint
    async def generate(self, request: Context) -> AsyncIterator[Dict]:
        stream = await self.engine.generate(request)
        async for item in stream:
            yield item


@service(namespace="examples")
class Processor:
    """Tokenize + forward (reference: components/processor.py)."""

    worker = depends(TpuWorker, endpoint="generate")

    def __init__(self, config: Dict[str, Any] | None = None):
        self.config = config or {}
        tokenizer = make_tokenizer({"kind": "byte"})
        model = self.config.get("served_model_name", "example-model")
        self._stages = [OpenAIPreprocessor(tokenizer, model), Backend(tokenizer)]

    @dynamo_endpoint
    async def chat(self, request: Context) -> AsyncIterator[Dict]:
        pipeline = build_pipeline(list(self._stages), self.worker.client)
        stream = await pipeline.generate(request)
        async for item in stream:
            yield item


@service(namespace="examples")
class Frontend:
    """Graph entry (reference: components/frontend.py — there it spawns the
    HTTP binary; here the runner's --http-port serves the OpenAI edge)."""

    processor = depends(Processor, endpoint="chat")

    @dynamo_endpoint
    async def health(self, request: Context) -> AsyncIterator[Dict]:
        yield {"ok": True}


@service(namespace="examples")
class PlannerService:
    """SLA planner riding the worker graph (dynamo_tpu/planner): watches
    the TpuWorker component's metrics topics and emits scale/flip
    decisions — dry-run by default inside the example graph."""

    def __init__(self, config: Dict[str, Any] | None = None):
        self.config = config or {}
        self.planner = None

    @async_on_start
    async def boot(self) -> None:
        from dynamo_tpu.planner import (
            DecisionEngine,
            LocalActuator,
            Planner,
            PolicyConfig,
            SignalCollector,
            SloTargets,
        )

        component = self.runtime.namespace("examples").component("TpuWorker")
        collector = await SignalCollector(
            component, model=self.config.get("served_model_name")
        ).start()
        self._collector = collector
        self.planner = await Planner(
            collector,
            DecisionEngine(
                SloTargets.from_dict(self.config),
                PolicyConfig.from_dict(self.config),
            ),
            LocalActuator(self.runtime.hub),
            interval_s=float(self.config.get("interval_s", 2.0)),
            dry_run=bool(self.config.get("dry_run", True)),
        ).start()

    @dynamo_endpoint
    async def status(self, request: Context) -> AsyncIterator[Dict]:
        from dynamo_tpu.planner import planner_metrics

        yield planner_metrics.state()
