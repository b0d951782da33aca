"""Distributed request tracing tests (runtime/tracing.py +
llm/trace_service.py; ISSUE 15, docs/tracing.md).

The load-bearing properties:

- OVERHEAD CONTRACT: tracing on vs off is byte-identical streams with zero
  new XLA compiles; decode records at CHUNK granularity only (one span per
  fused dispatch), never per token; untraced requests cost one attr check
  per instrumentation point.
- ONE TRACE PER REQUEST across every hop: the acceptance smoke routes one
  seeded request through a 2-worker fleet with disagg remote prefill, a
  cross-worker KV pull at the prefill engine, and one mid-stream migration
  — and the aggregator assembles a SINGLE trace whose spans come from the
  client, both engines, the disagg planes, the KV donor and the migration,
  with a gap-free TTFT decomposition.
- Sampling semantics (head rate / forced / tail-keep), ring bounds,
  aggregator TTL + orphan accounting, /traces endpoint shapes, metrics.

Engine economics: the smoke shares four warm engines and uses the
injectable pace hook (engine.pace_hook) to decide the migrate-vs-decode
race deterministically; it carries ``slow`` so tier-1 keeps the cheap
gates (tools/ci.sh's tracing step runs everything).
"""

import asyncio
import json

import pytest
from aiohttp import ClientSession

from dynamo_tpu.llm.protocols import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.llm.trace_service import (
    EdgeRequestTrace,
    TraceAggregator,
    ttft_decomposition,
)
from dynamo_tpu.runtime.engine import Context, collect
from dynamo_tpu.runtime.tracing import (
    NOOP_SPAN,
    SpanCollector,
    SpanExporter,
    TraceContext,
    TraceSampler,
    TracingConfig,
    collector,
    parse_trace,
    span,
    tracing_metrics,
)

pytestmark = pytest.mark.tracing

CFG = dict(
    model="debug-tiny",
    block_size=4,
    num_blocks=128,
    max_batch=4,
    max_model_len=512,
    prefill_chunk=64,
    dtype="float32",
    decode_steps=2,
    pipeline_depth=2,
)


@pytest.fixture(autouse=True)
def _reset_tracing_state():
    """Tests share the process-global collector + metrics singletons."""
    collector.drain()
    tracing_metrics.reset()
    yield
    collector.drain()
    tracing_metrics.reset()


def _req(tokens, max_tokens=16, seed=1234, temperature=0.9, annotations=None):
    d = PreprocessedRequest(
        token_ids=list(tokens),
        stop_conditions=StopConditions(max_tokens=max_tokens, ignore_eos=True),
        sampling_options=SamplingOptions(temperature=temperature, seed=seed),
    ).to_dict()
    if annotations:
        d["annotations"] = dict(annotations)
    return d


def _tokens(items):
    return [t for i in items for t in i.get("token_ids", [])]


# ------------------------------------------------------------- wire context


def test_trace_context_wire_roundtrip_omit_when_absent():
    tc = TraceContext.new()
    d = tc.to_dict()
    # The common (sampled) context keeps the minimal wire shape.
    assert set(d) == {"trace_id", "span_id"}
    rt = TraceContext.from_dict(d)
    assert rt == tc and rt.sampled

    off = TraceContext("t", "s", sampled=False)
    d2 = off.to_dict()
    assert d2["sampled"] is False  # omitted only when default (True)
    assert TraceContext.from_dict(d2).sampled is False


def test_parse_trace_tolerates_garbage():
    assert parse_trace(None) is None
    assert parse_trace("not a dict") is None
    assert parse_trace({"span_id": "x"}) is None  # missing trace_id
    assert parse_trace({"trace_id": "t", "span_id": "s", "sampled": False}) is None
    tc = parse_trace({"trace_id": "t", "span_id": "s"})
    assert tc is not None and tc.trace_id == "t" and tc.sampled


# ------------------------------------------------------------ span plumbing


def test_collector_ring_bounds_and_drop_accounting():
    c = SpanCollector(maxlen=4)
    tc = TraceContext.new()
    for i in range(6):
        c.record(tc, f"s{i}", "t", 0.0, 1.0)
    assert len(c) == 4  # bounded: oldest evicted
    assert tracing_metrics.spans_dropped_total == 2
    assert tracing_metrics.spans_recorded_total == 6
    drained = c.drain()
    assert [s["name"] for s in drained] == ["s2", "s3", "s4", "s5"]
    assert len(c) == 0
    # Unsampled context / None: nothing recorded, nothing allocated.
    assert c.record(None, "x", "t", 0.0, 1.0) is None
    assert c.record(TraceContext("a", "b", sampled=False), "x", "t", 0, 1) is None
    assert len(c) == 0


def test_span_helper_noop_off_trace_and_parenting():
    assert span(None, "n", "c") is NOOP_SPAN
    assert span(TraceContext("t", "s", sampled=False), "n", "c") is NOOP_SPAN
    # NOOP surface: chainable, context-manageable, free.
    with span(None, "n", "c") as s:
        s.set(a=1).event("e")

    sink = SpanCollector(maxlen=8)
    tc = TraceContext.new()
    with span(tc, "child", "comp", sink=sink) as h:
        h.set(k="v")
        h.event("marker", n=3)
    sink.record(tc, "root", "comp", 0.0, 1.0, parent_id=None)
    child, root = sink.drain()
    assert child["parent_id"] == tc.span_id  # default parents to the ctx
    assert child["attrs"] == {"k": "v"}
    assert child["events"][0]["name"] == "marker"
    assert root["parent_id"] is None and root["span_id"] == tc.span_id


def test_span_records_error_attr_on_exception():
    sink = SpanCollector(maxlen=4)
    tc = TraceContext.new()
    with pytest.raises(ValueError):
        with span(tc, "op", "c", sink=sink):
            raise ValueError("boom")
    (s,) = sink.drain()
    assert s["attrs"]["error"] == "ValueError"


# ----------------------------------------------------------------- sampling


def test_sampler_head_rate_and_forced():
    s = TraceSampler(TracingConfig(sample=0.0), rng=lambda: 0.0)
    assert s.decide({}, {}) is None  # rate 0: only forced traces
    s = TraceSampler(TracingConfig(sample=0.5), rng=lambda: 0.4)
    assert s.decide({}, {}) is not None
    assert tracing_metrics.traces_sampled_total == 1
    s = TraceSampler(TracingConfig(sample=0.5), rng=lambda: 0.6)
    assert s.decide({}, {}) is None

    s = TraceSampler(TracingConfig(sample=0.0))
    assert s.decide({"x-trace": "1"}, {}) is not None
    assert s.decide({}, {"nvext": {"trace": True}}) is not None
    assert tracing_metrics.traces_forced_total == 2
    for off in ("0", "false", "no", "off", ""):
        assert s.decide({"x-trace": off}, {}) is None
    # Disabled plane: even forced requests stay untraced.
    s = TraceSampler(TracingConfig(enabled=False))
    assert s.decide({"x-trace": "1"}, {}) is None


def test_sampler_tail_eligibility():
    s = TraceSampler(TracingConfig(tail_keep=True, tail_slo_ttft_ms=100.0))
    assert s.tail_eligible(error=True, ttft_ms=None)
    assert s.tail_eligible(error=False, ttft_ms=150.0)  # SLO violation
    assert not s.tail_eligible(error=False, ttft_ms=50.0)
    s = TraceSampler(TracingConfig(tail_keep=False))
    assert not s.tail_eligible(error=True, ttft_ms=None)
    s = TraceSampler(TracingConfig(tail_keep=True))  # no SLO configured
    assert not s.tail_eligible(error=False, ttft_ms=10_000.0)


def test_edge_tail_keep_materializes_edge_spans():
    sampler = TraceSampler(TracingConfig(sample=0.0, tail_keep=True))
    ert = EdgeRequestTrace(sampler, {}, {})
    assert not ert.active  # head said no
    ert.admission_started()
    ert.admission_done()
    ert.on_first_token()
    ert.finish("error")
    spans = collector.drain()
    names = {s["name"] for s in spans}
    assert names == {"edge.request", "edge.admission_wait"}
    root = next(s for s in spans if s["name"] == "edge.request")
    assert root["parent_id"] is None
    assert any(e["name"] == "tail_kept" for e in root["events"])
    assert any(e["name"] == "first_token" for e in root["events"])
    assert tracing_metrics.tail_kept_total == 1
    # A successful head-unsampled request leaves nothing behind.
    ert2 = EdgeRequestTrace(sampler, {}, {})
    ert2.finish("success")
    assert collector.drain() == []
    # Deliberate shedding never tail-keeps: an overload storm of 429/503s
    # must not turn over the ring and evict the sampled traces.
    ert3 = EdgeRequestTrace(sampler, {}, {})
    ert3.finish("rejected")
    assert collector.drain() == []
    # finish is idempotent (guard.finish + handler paths may both fire).
    ert.finish("error")
    assert collector.drain() == []


# --------------------------------------------------------------- aggregator


def _span(tid, name="n", component="c", start=0.0, dur=1.0, parent="p",
          events=None, proc="pid-x"):
    s = {
        "trace_id": tid, "span_id": f"{tid}-{name}", "parent_id": parent,
        "name": name, "component": component, "proc": proc,
        "start_ms": start, "dur_ms": dur,
    }
    if events:
        s["events"] = events
    return s


def test_aggregator_ttl_orphans_and_capacity():
    now = [0.0]
    agg = TraceAggregator(ttl_s=10.0, max_traces=8, clock=lambda: now[0])
    agg.ingest({"proc": "p", "spans": [_span("a")]})  # rootless
    now[0] = 5.0
    agg.ingest({"proc": "p", "spans": [_span("b", parent=None)]})  # rooted
    assert agg.get("a") is not None
    now[0] = 11.0  # a's TTL expired; b still fresh
    agg.ingest({"proc": "p", "spans": [_span("c", parent=None)]})
    assert agg.get("a") is None
    assert agg.orphan_spans_total == 1  # expired WITHOUT a root
    assert agg.get("b") is not None
    now[0] = 30.0
    agg._prune()
    assert agg.get("b") is None
    assert agg.orphan_spans_total == 1  # rooted traces evict silently
    assert agg.evicted_total == 3

    # Capacity bound evicts oldest-touched first.
    agg2 = TraceAggregator(ttl_s=1e9, max_traces=2, clock=lambda: now[0])
    for tid in ("t1", "t2", "t3"):
        agg2.ingest({"proc": "p", "spans": [_span(tid, parent=None)]})
    assert agg2.get("t1") is None
    assert agg2.get("t2") is not None and agg2.get("t3") is not None
    # recent(): newest first, root metadata surfaced; 0 means none (the
    # naive list[-0:] slice would be the WHOLE table).
    recent = agg2.recent(5)
    assert [r["trace_id"] for r in recent] == ["t3", "t2"]
    assert recent[0]["root"] == "n" and recent[0]["spans"] == 1
    assert agg2.recent(0) == []
    stats = agg2.stats()
    assert stats["traces"] == 2 and stats["evicted"] == 1


async def test_aggregator_stop_detaches_metrics_source():
    agg = TraceAggregator()
    assert tracing_metrics._aggregator_source == agg.stats
    await agg.stop()
    assert tracing_metrics._aggregator_source is None
    # A NEWER aggregator's registration survives an older one's stop.
    agg2 = TraceAggregator()
    agg3 = TraceAggregator()
    await agg2.stop()
    assert tracing_metrics._aggregator_source == agg3.stats
    await agg3.stop()


async def test_exporter_drains_to_sinks_and_survives_sink_errors():
    got = []

    class _Boom:
        def ingest(self, payload):
            raise RuntimeError("sink down")

    exp = SpanExporter([_Boom(), got.append], interval_s=60.0)
    tc = TraceContext.new()
    collector.record(tc, "s1", "c", 0.0, 1.0)
    n = await exp.flush()
    assert n == 1
    assert len(got) == 1 and got[0]["spans"][0]["name"] == "s1"
    assert tracing_metrics.export_errors_total == 1  # bad sink counted
    assert tracing_metrics.export_batches_total == 1
    assert await exp.flush() == 0  # ring drained
    await exp.stop(final_flush=False)


# ------------------------------------------------------- TTFT decomposition


def test_ttft_decomposition_hops_and_gap_accounting():
    tid = "t"
    spans = [
        _span(tid, "edge.request", "edge", 1000.0, 500.0, parent=None),
        _span(tid, "edge.admission_wait", "edge", 1000.0, 50.0),
        _span(tid, "edge.preprocess", "edge", 1050.0, 50.0),
        _span(tid, "client.route", "client", 1100.0, 100.0),
        # 50 ms hole here: 1200 -> 1250 covered by nothing.
        _span(tid, "engine.queue_wait", "engine", 1250.0, 50.0),
        _span(
            tid, "engine.prefill", "engine", 1300.0, 100.0,
            events=[{"name": "first_token", "t_ms": 1400.0}],
        ),
        # First decode dispatch overlaps the first-token accept; the
        # second is entirely post-TTFT.
        _span(tid, "engine.decode_chunk", "engine", 1350.0, 40.0),
        _span(tid, "engine.decode_chunk", "engine", 1440.0, 40.0),
        # A migrated trace's RESUME admission records post-first-token
        # queue/prefill spans — they must not inflate the TTFT hops.
        _span(tid, "engine.queue_wait", "engine", 1500.0, 30.0),
        _span(
            tid, "engine.prefill", "engine", 1530.0, 60.0,
            events=[{"name": "first_token", "t_ms": 1590.0}],
        ),
    ]
    r = ttft_decomposition(spans)
    assert r["ttft_ms"] == 400.0  # earliest first_token wins
    assert r["unattributed_ms"] == 50.0  # exactly the constructed hole
    assert r["hops"] == {
        "edge_queue": 50.0,
        "preprocess": 50.0,
        "route": 100.0,
        "engine_queue": 50.0,  # resume queue_wait clipped out entirely
        "prefill_or_pull": 100.0,  # resume prefill clipped out entirely
        "first_decode": 40.0,  # only the FIRST decode chunk, in-window
    }
    # No root: hops still roll up unclipped, no window math.
    r2 = ttft_decomposition(spans[1:])
    assert "ttft_ms" not in r2 and r2["hops"]["route"] == 100.0


# ------------------------------------------------------------------ metrics


def test_metrics_render_and_aggregator_gauges():
    tracing_metrics.spans_recorded_total = 3
    tracing_metrics.traces_forced_total = 2
    agg = TraceAggregator()
    agg.ingest({"proc": "p", "spans": [_span("m", parent=None)]})
    out = tracing_metrics.render("dynamo_tpu")
    assert "dynamo_tpu_tracing_spans_recorded_total 3" in out
    assert "dynamo_tpu_tracing_traces_forced_total 2" in out
    assert "dynamo_tpu_tracing_aggregator_traces 1" in out
    assert "dynamo_tpu_tracing_aggregator_orphan_spans_total 0" in out
    # Detached source: gauges disappear, counters stay.
    tracing_metrics.set_aggregator_source(None)
    out2 = tracing_metrics.render("dynamo_tpu")
    assert "aggregator_traces" not in out2


# ------------------------------------------------------------ HTTP surfaces


async def test_http_edge_traces_endpoints_and_headers():
    from dynamo_tpu.llm import (
        Backend,
        ByteTokenizer,
        EchoEngineCore,
        HttpService,
        OpenAIPreprocessor,
    )
    from dynamo_tpu.runtime import build_pipeline

    sampler = TraceSampler(TracingConfig(sample=0.0))
    agg = TraceAggregator()
    exporter = SpanExporter([agg], interval_s=60.0)
    service = HttpService(
        host="127.0.0.1", port=0, tracing=sampler, trace_aggregator=agg
    )
    tok = ByteTokenizer()
    pipeline = build_pipeline(
        [OpenAIPreprocessor(tok, "echo"), Backend(tok)], EchoEngineCore()
    )
    service.models.add_completion_model("echo", pipeline)
    await service.start()
    base = f"http://127.0.0.1:{service.port}"
    try:
        async with ClientSession() as http:
            # Untraced request: byte stream has no x-trace-id header.
            async with http.post(
                f"{base}/v1/completions",
                json={"model": "echo", "prompt": "abc", "max_tokens": 8,
                      "stream": True},
            ) as r:
                assert r.status == 200 and "x-trace-id" not in r.headers
                plain_body = await r.text()
            # Forced via header: same bytes + the trace id to look up.
            async with http.post(
                f"{base}/v1/completions",
                json={"model": "echo", "prompt": "abc", "max_tokens": 8,
                      "stream": True},
                headers={"x-trace": "1"},
            ) as r:
                assert r.status == 200
                tid = r.headers["x-trace-id"]
                traced_body = await r.text()
            def _texts(body):
                # Request ids differ per request by design; the STREAMED
                # CONTENT (chunk texts + finish reasons) must not.
                return [
                    [
                        (c.get("text"), c.get("finish_reason"))
                        for c in json.loads(line[6:]).get("choices", [])
                    ]
                    for line in body.splitlines()
                    if line.startswith("data: ") and line != "data: [DONE]"
                ]

            assert _texts(traced_body) == _texts(plain_body)
            await exporter.flush()
            async with http.get(f"{base}/traces/{tid}") as r:
                assert r.status == 200
                trace = await r.json()
            assert trace["trace_id"] == tid
            names = {s["name"] for s in trace["spans"]}
            assert "edge.request" in names and "edge.preprocess" in names
            assert "edge.admission_wait" in names
            assert "rollup" in trace and "hops" in trace["rollup"]
            async with http.get(f"{base}/traces?recent=5") as r:
                recent = (await r.json())["traces"]
            assert any(t["trace_id"] == tid for t in recent)
            async with http.get(f"{base}/traces/nope") as r:
                assert r.status == 404
            # tracing counters ride /metrics.
            async with http.get(f"{base}/metrics") as r:
                metrics_body = await r.text()
            assert "dynamo_tpu_tracing_traces_forced_total 1" in metrics_body
            assert "dynamo_tpu_tracing_aggregator_traces" in metrics_body
    finally:
        await exporter.stop(final_flush=False)
        await service.close()


async def test_http_traces_404_without_aggregator():
    from dynamo_tpu.llm import HttpService

    service = HttpService(host="127.0.0.1", port=0)
    await service.start()
    try:
        async with ClientSession() as http:
            async with http.get(
                f"http://127.0.0.1:{service.port}/traces"
            ) as r:
                assert r.status == 404
    finally:
        await service.close()


# ------------------------------------- engine: byte identity + zero compiles


def test_engine_byte_identical_and_zero_new_compiles_with_tracing():
    """The overhead contract on a real engine: the SAME seeded request with
    tracing on produces the same bytes, compiles nothing new, and records
    decode at CHUNK granularity (strictly fewer decode spans than tokens)."""
    from dynamo_tpu.engine import EngineConfig
    from dynamo_tpu.engine.engine import TpuEngine

    async def main():
        eng = TpuEngine(EngineConfig(**CFG))
        try:
            prompt = list(range(1, 18))
            req = _req(prompt, max_tokens=24, seed=77)
            want = _tokens(await collect(await eng.generate(Context(dict(req)))))
            assert len(want) == 24
            # Second untraced pass: warms the PREFIX-HIT admission shape the
            # traced pass will take (the first pass sealed the prompt), so
            # the compile snapshot below isolates tracing's contribution.
            warm2 = _tokens(await collect(await eng.generate(Context(dict(req)))))
            assert warm2 == want
            counts = dict(eng.compile_counts())
            collector.drain()

            tc = TraceContext.new()
            treq = _req(prompt, max_tokens=24, seed=77,
                        annotations={"trace": tc.to_dict()})
            ctx = Context(dict(treq))
            ctx.ctx.trace = tc
            got = _tokens(await collect(await eng.generate(ctx)))
            assert got == want  # byte-identical with tracing on
            assert eng.compile_counts() == counts  # zero new compiles

            spans = collector.drain()
            assert spans and {s["trace_id"] for s in spans} == {tc.trace_id}
            names = [s["name"] for s in spans]
            assert "engine.queue_wait" in names
            prefill = next(s for s in spans if s["name"] == "engine.prefill")
            assert any(
                e["name"] == "first_token" for e in prefill["events"]
            )
            chunks = [s for s in spans if s["name"] == "engine.decode_chunk"]
            # Chunk granularity: >= 1 span, strictly fewer than tokens
            # (each fused dispatch covers decode_steps tokens).
            assert 1 <= len(chunks) < 24
            assert all(c["attrs"]["steps"] >= 1 for c in chunks)

            # The hop account's stamps as spans (ISSUE 25), same trace.
            assert {"engine.prefill_wait", "engine.prefill_run",
                    "engine.first_fetch"} <= set(names)

            # Tracing OFF on the same engine records nothing at all.
            got2 = _tokens(
                await collect(await eng.generate(Context(dict(req))))
            )
            assert got2 == want and len(collector) == 0
        finally:
            await eng.close()

    asyncio.run(main())


def test_engine_byte_identical_and_zero_new_compiles_with_the_hop_account(
    monkeypatch,
):
    """The always-on hop account (ISSUE 25) under the same gate: with its
    clock and its fold taken away the same seeded request streams the same
    bytes from the same compiled programs as with them — the account is
    floats on host objects and nothing else."""
    from dynamo_tpu.engine import EngineConfig
    from dynamo_tpu.engine.engine import TpuEngine
    from dynamo_tpu.llm.metrics import RequestHopMetrics, request_hop_metrics

    async def main():
        eng = TpuEngine(EngineConfig(**CFG))
        try:
            req = _req(list(range(1, 18)), max_tokens=24, seed=77)
            request_hop_metrics.reset()
            want = _tokens(await collect(await eng.generate(Context(dict(req)))))
            again = _tokens(await collect(await eng.generate(Context(dict(req)))))
            assert again == want and len(want) == 24
            queue_wait = RequestHopMetrics.QUEUE_WAIT
            assert request_hop_metrics.counts[queue_wait] == 2  # it was on
            counts = dict(eng.compile_counts())

            monkeypatch.setattr(request_hop_metrics, "now", lambda: 0.0)
            monkeypatch.setattr(
                request_hop_metrics, "fold_engine", lambda *a: False
            )
            got = _tokens(await collect(await eng.generate(Context(dict(req)))))
            assert got == want  # byte-identical without the account
            assert eng.compile_counts() == counts  # and no new program
            assert request_hop_metrics.counts[queue_wait] == 2
            assert len(collector) == 0  # unsampled: the span ring stays empty
        finally:
            request_hop_metrics.reset()
            await eng.close()

    asyncio.run(main())


# ----------------------------------------------- acceptance smoke (fleet)


@pytest.mark.slow  # 4 warm engines + two full fleet passes: ci.sh's tracing
# step runs it (no `slow` filter there); tier-1 keeps the cheap gates.
async def test_single_trace_across_disagg_pull_and_migration():
    """The ISSUE 15 CPU smoke: ONE seeded request through a 2-worker fleet
    with disagg remote prefill, a cross-worker KV pull (at the prefill
    engine, from a donor), and one mid-stream migration — assembles into a
    SINGLE trace with spans from >= 3 components, a gap-free TTFT
    decomposition, byte-identical streams and an unchanged compile count
    vs the identical untraced pass."""
    from dynamo_tpu.engine import EngineConfig
    from dynamo_tpu.engine.engine import TpuEngine
    from dynamo_tpu.llm.disagg import (
        DisaggConfig,
        DisaggDecodeWorker,
        DisaggregatedRouter,
        PrefillQueue,
        PrefillWorkerLoop,
    )
    from dynamo_tpu.llm.kv_router.pull import (
        PrefixPuller,
        make_kv_export_handler,
    )
    from dynamo_tpu.llm.migration import MigratableWorker, request_migrate_out
    from dynamo_tpu.runtime import DistributedRuntime, HubServer

    cfg = dict(CFG, num_blocks=192)
    d_eng = TpuEngine(EngineConfig(**cfg))  # KV donor (+ control runs)
    p_eng = TpuEngine(EngineConfig(**cfg))  # prefill worker engine
    a_eng = TpuEngine(EngineConfig(**cfg))  # decode worker A (migration src)
    b_eng = TpuEngine(EngineConfig(**cfg))  # worker B (migration target)
    engines = (d_eng, p_eng, a_eng, b_eng)

    async def _prewarm(eng):
        toks = list(range(200, 216))
        await collect(
            await eng.generate(Context(_req(toks, max_tokens=4, seed=1)))
        )
        payload = await eng.export_prompt_blocks(toks)
        await eng.inject_blocks(toks, payload)

    for eng in engines:
        await _prewarm(eng)
    # Warm ALL inject scatter shapes (1..chunk_blocks) on the import-side
    # engines: migration push chunks (B) track the copy cursor vs decode
    # progress, and disagg kv_import chunks (A) track the prefill engine's
    # sealing frontier — both are timing-dependent, so the traced pass must
    # find every candidate shape compiled or the zero-new-compiles gate
    # would race those cursors.
    for toks, chunks in (
        (list(range(240, 256)), (1, 2)),
        (list(range(260, 276)), (3,)),
    ):
        await collect(
            await d_eng.generate(Context(_req(toks, max_tokens=1)))
        )
        start = 0
        for n in chunks:
            payload = await d_eng.export_prompt_blocks(
                toks, start_block=start, max_blocks=n
            )
            await a_eng.inject_blocks(toks, payload)
            await b_eng.inject_blocks(toks, payload)
            start += n

    # Prefill engine pulls its hinted prefix from the donor (the donor-side
    # kv_export handler records the kv.export span under the request trace).
    donor_handler = make_kv_export_handler(d_eng)

    async def donor_exporter(worker_id, data):
        async for item in donor_handler(Context(dict(data))):
            return (item or {}).get("payload")

    p_eng.set_prefix_puller(PrefixPuller(p_eng, donor_exporter))

    hub = await HubServer().start()
    a_rt = await DistributedRuntime.connect(hub.address)
    b_rt = await DistributedRuntime.connect(hub.address)
    p_rt = await DistributedRuntime.connect(hub.address)
    client_rt = await DistributedRuntime.connect(hub.address)
    ploop = None
    client = None
    try:
        # -- worker A: disagg decode + migratable, served over the wire ----
        ns = "tr"
        a_comp = a_rt.namespace(ns).component("w")
        a_server = await a_rt.service_server()
        import_ep = a_comp.endpoint("kv_import")
        router = DisaggregatedRouter(
            "tiny",
            DisaggConfig(max_local_prefill_length=16, max_prefill_queue_size=8),
        )
        disagg = DisaggDecodeWorker(
            a_eng,
            PrefillQueue(a_rt.hub, "tiny"),
            router,
            import_address=a_server.address,
            import_path=import_ep.path,
        )
        await import_ep.serve_endpoint(disagg.kv_import_handler)
        a_mig = MigratableWorker(a_eng, serve=disagg, chunk_blocks=4)
        a_gen = a_comp.endpoint("gen")
        a_in = a_comp.endpoint("migrate_in")
        a_out = a_comp.endpoint("migrate_out")
        await a_in.serve_endpoint(a_mig.migrate_in_handler)
        await a_out.serve_endpoint(a_mig.migrate_out_handler)
        a_meta = {
            "migrate": {
                "import_path": a_in.path,
                "out_path": a_out.path,
                "generate_path": a_gen.path,
            }
        }
        await a_gen.serve_endpoint(a_mig, metadata=a_meta)
        a_info = {
            "address": a_server.address,
            "path": a_gen.path,
            "worker_id": a_rt.worker_id,
            "metadata": a_meta,
        }

        # -- worker B: plain migratable target ----------------------------
        b_comp = b_rt.namespace(ns).component("w")
        b_server = await b_rt.service_server()
        b_mig = MigratableWorker(b_eng, chunk_blocks=4)
        b_gen = b_comp.endpoint("gen")
        b_in = b_comp.endpoint("migrate_in")
        await b_in.serve_endpoint(b_mig.migrate_in_handler)
        await b_gen.serve_endpoint(
            b_mig,
            metadata={
                "migrate": {
                    "import_path": b_in.path,
                    "generate_path": b_gen.path,
                }
            },
        )
        b_target = {
            "worker_id": b_rt.worker_id,
            "address": b_server.address,
            "import_path": b_in.path,
            "generate_path": b_gen.path,
        }

        # -- prefill worker loop ------------------------------------------
        # adaptive_chunks off: chunk growth between the passes would land
        # pass 2's kv_import in a NEW power-of-two inject bucket and fail
        # the zero-new-compiles gate for a bandwidth reason, not a tracing
        # one (the contract under test is tracing's overhead).
        ploop = await PrefillWorkerLoop(
            p_eng, PrefillQueue(p_rt.hub, "tiny"), chunk_blocks=4,
            adaptive_chunks=False,
        ).start()

        client = await (
            client_rt.namespace(ns).component("w").endpoint("gen").client()
        )
        await client.wait_for_instances(5)

        async def run_once(prompt, seed, trace_ctx):
            """One request through the full gauntlet: remote prefill (48 >
            16 local cap) with a donor pull at the prefill engine, then a
            deterministic mid-stream migration A -> B."""
            ann = {"kv_pull": {"worker_id": 0, "blocks": 3}}
            if trace_ctx is not None:
                ann["trace"] = trace_ctx.to_dict()
            req = _req(prompt, max_tokens=24, seed=seed, annotations=ann)
            ctx = Context(dict(req))
            if trace_ctx is not None:
                ctx.ctx.trace = trace_ctx
            import time as _time

            t0 = _time.perf_counter()
            stream = await client.generate(ctx, worker_id=a_rt.worker_id)
            items = []

            async def consume():
                async for it in stream:
                    items.append(it)

            task = asyncio.create_task(consume())
            deadline = asyncio.get_running_loop().time() + 30.0
            while len(_tokens(items)) < 5:
                assert asyncio.get_running_loop().time() < deadline
                await asyncio.sleep(0.01)
            # Deterministic migrate-vs-decode race (the migration deflake
            # idiom): throttle A's decode so the copy loop provably wins.
            done = asyncio.Event()

            async def pace():
                if not done.is_set():
                    await asyncio.sleep(0.02)

            a_eng.pace_hook = pace
            try:
                resp = await request_migrate_out(
                    a_info, b_target, request_id=ctx.id
                )
            finally:
                done.set()
                a_eng.pace_hook = None
            assert resp["ok"] and resp["migrated"] == [ctx.id]
            await task
            if trace_ctx is not None:
                collector.record(
                    trace_ctx, "driver.request", "driver",
                    t0, _time.perf_counter(), parent_id=None,
                )
            return _tokens(items)

        # Pass 1 (UNTRACED): warms every fleet shape and is the compile /
        # byte baseline for "tracing off".
        prompt1 = list(range(301, 349))  # 12 blocks; donor holds the first 3
        await collect(
            await d_eng.generate(Context(_req(prompt1[:12], max_tokens=1)))
        )
        out1 = await run_once(prompt1, seed=5151, trace_ctx=None)
        assert len(out1) == 24
        assert len(collector) == 0  # untraced pass recorded nothing

        # Controls + compile snapshot AFTER the untraced pass.
        prompt2 = list(range(401, 449))
        await collect(
            await d_eng.generate(Context(_req(prompt2[:12], max_tokens=1)))
        )
        control2 = _tokens(
            await collect(
                await d_eng.generate(
                    Context(_req(prompt2, max_tokens=24, seed=5252))
                )
            )
        )
        engine_names = {
            id(d_eng): "donor", id(p_eng): "prefill",
            id(a_eng): "A", id(b_eng): "B",
        }
        compile_counts = {
            id(e): dict(e.compile_counts()) for e in engines
        }

        # Pass 2 (TRACED): same shapes, fresh prompt so the donor pull and
        # remote prefill genuinely fire again.
        tc = TraceContext.new()
        out2 = await run_once(prompt2, seed=5252, trace_ctx=tc)

        # Byte-identity: the traced, pulled, remote-prefilled, migrated
        # stream equals the plain warm-engine control.
        assert out2 == control2
        # Zero new compiles with tracing on.
        for e in engines:
            assert dict(e.compile_counts()) == compile_counts[id(e)], (
                engine_names[id(e)]
            )

        # -- assembly: ONE trace across every hop -------------------------
        agg = TraceAggregator()
        await SpanExporter([agg], interval_s=60.0).flush()
        trace = agg.get(tc.trace_id)
        assert trace is not None
        comps = set(trace["components"])
        assert len(comps) >= 3
        assert {"driver", "client", "engine", "disagg", "migration"} <= comps
        assert "disagg-prefill" in comps  # prefill worker's transfer plane
        assert "kv_donor" in comps  # the cross-worker pull's donor side
        names = {s["name"] for s in trace["spans"]}
        assert "disagg.remote_prefill_wait" in names
        assert "engine.kv_pull" in names  # prefill engine pulled the prefix
        assert "kv.export" in names
        assert "migrate.copy" in names and "migrate.cutover" in names
        assert "client.splice" in names
        assert "engine.prefill" in names and "engine.queue_wait" in names
        # Spans from more than one engine process-context: A's disagg +
        # B's resume both recorded engine spans under the one trace.
        prefills = [s for s in trace["spans"] if s["name"] == "engine.prefill"]
        assert len(prefills) >= 2  # source admission + migrated resume

        # -- gap-free TTFT decomposition ----------------------------------
        rollup = trace["rollup"]
        assert rollup["ttft_ms"] > 0
        assert "prefill_or_pull" in rollup["hops"]
        assert "engine_queue" in rollup["hops"]
        # "Gap-free": the TTFT window is covered by hop spans up to small
        # seams (queue-depth RPC, transfer handoff) — bar at 25% + floor.
        assert rollup["unattributed_ms"] <= max(
            0.25 * rollup["ttft_ms"], 75.0
        ), rollup
    finally:
        if client is not None:
            await client.close()
        if ploop is not None:
            await ploop.stop()
        for eng in engines:
            await eng.close()
        for rt in (client_rt, p_rt, b_rt, a_rt):
            await rt.close()
        await hub.close()
        tracing_metrics.set_aggregator_source(None)
