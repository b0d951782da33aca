"""The always-on per-request TTFT/TPOT hop account (llm/metrics.py
``RequestHopMetrics``; docs/tracing.md) on a tiny engine behind the real HTTP
edge, CPU.

- The account closes: hops ``edge_pre`` .. ``edge_emit`` sum to
  ``server_ttft`` and every count equals the requests served.
- The budget holds: clock reads and folds per request are a constant that
  depends neither on the answer's length nor on ``decode_steps``.
- Incomplete requests grow ``hop_incomplete_total`` and no hop.
- A forced-trace request's new spans are its own hops; an unsampled one
  leaves the span ring empty.
"""

import asyncio

import pytest
from aiohttp import ClientSession

from dynamo_tpu.engine import EngineConfig
from dynamo_tpu.engine.engine import TpuEngine
from dynamo_tpu.llm import Backend, ByteTokenizer, HttpService, OpenAIPreprocessor
from dynamo_tpu.llm.metrics import RequestHopMetrics, request_hop_metrics
from dynamo_tpu.llm.trace_service import TraceAggregator
from dynamo_tpu.runtime import build_pipeline
from dynamo_tpu.runtime.tracing import (
    SpanExporter,
    TraceSampler,
    TracingConfig,
    collector,
    tracing_metrics,
)

pytestmark = pytest.mark.tracing

H = RequestHopMetrics
TTFT_HOPS = range(H.EDGE_PRE, H.EDGE_EMIT + 1)
CFG = dict(
    model="debug-tiny", block_size=4, num_blocks=128, max_batch=4,
    max_model_len=512, prefill_chunk=16, dtype="float32", pipeline_depth=2,
)


@pytest.fixture(autouse=True)
def _fresh_account():
    request_hop_metrics.reset()
    collector.drain()
    tracing_metrics.reset()
    yield
    request_hop_metrics.reset()
    collector.drain()
    tracing_metrics.reset()


class Served:
    """A tiny engine behind the HTTP edge, as ``run in=http out=tpu`` wires
    them: one process, one event loop, one clock."""

    def __init__(self, decode_steps: int = 4):
        self.decode_steps = decode_steps

    async def __aenter__(self):
        self.engine = TpuEngine(EngineConfig(decode_steps=self.decode_steps, **CFG))
        self.agg = TraceAggregator()
        self.exporter = SpanExporter([self.agg], interval_s=60.0)
        self.service = HttpService(
            host="127.0.0.1", port=0, trace_aggregator=self.agg,
            tracing=TraceSampler(TracingConfig(sample=0.0)),
        )
        tok = ByteTokenizer()
        self.service.models.add_completion_model(
            "tiny", build_pipeline([OpenAIPreprocessor(tok, "tiny"), Backend(tok)],
                                   self.engine))
        await self.service.start()
        self.base = f"http://127.0.0.1:{self.service.port}"
        self.http = ClientSession()
        return self

    async def __aexit__(self, *exc):
        await self.http.close()
        await self.exporter.stop(final_flush=False)
        await self.service.close()
        await self.engine.close()

    async def complete(self, i: int, max_tokens: int = 8, headers=None, stream=True):
        body = {"model": "tiny", "prompt": f"request {i} " * 4, "max_tokens": max_tokens,
                "stream": stream, "ignore_eos": True, "seed": 11 + i, "temperature": 0.8}
        async with self.http.post(f"{self.base}/v1/completions", json=body,
                                  headers=headers or {}) as r:
            assert r.status == 200
            return r.headers.get("x-trace-id"), await r.text()

    async def metrics(self) -> str:
        async with self.http.get(f"{self.base}/metrics") as r:
            return await r.text()


def _series(text: str, name: str) -> dict:
    out = {}
    for line in text.splitlines():
        if line.startswith(name + "{"):
            label = line[line.index('="') + 2:line.index('"}')]
            out[label] = float(line.rsplit(" ", 1)[1])
    return out


# ------------------------------------------------------------ (a) it closes


async def test_the_account_closes_and_every_count_is_the_requests_served():
    n = 6
    async with Served() as s:
        await s.complete(0)  # alone first: later ones overlap, queue and join
        await asyncio.gather(*[s.complete(i, max_tokens=12) for i in range(1, n)])
        text = await s.metrics()
    sums = _series(text, "dynamo_tpu_request_hop_seconds_sum")
    counts = _series(text, "dynamo_tpu_request_hop_seconds_count")
    assert list(sums) == list(H.HOPS) == list(counts)
    assert all(counts[h] == n for h in H.HOPS), counts
    parts = sum(sums[H.HOPS[i]] for i in TTFT_HOPS)
    assert abs(parts - sums["server_ttft"]) < 1e-6 * n
    assert all(sums[h] >= 0.0 for h in H.HOPS) and sums["server_ttft"] > 0.0
    assert _series(text, "dynamo_tpu_request_hop_incomplete_total") == {
        "engine": 0.0, "edge": 0.0}
    assert len(collector) == 0  # nothing sampled: the span ring stays empty


async def test_the_account_closes_when_first_tokens_are_applied_on_landing(monkeypatch):
    """A long answer keeps one fused session alive while short prompts join
    it; the loop applies a first token when its fetch lands (ISSUE 29), so
    ``t_first_token`` may come moments after ``t_fetch_done`` and never
    before it: no fold is out of order, no hop negative, the eight still
    sum to ``server_ttft``."""
    folds = []
    fold = request_hop_metrics.fold_engine

    def fold_engine(*stamps):
        folds.append(stamps)
        return fold(*stamps)

    monkeypatch.setattr(request_hop_metrics, "fold_engine", fold_engine)
    n = 0
    async with Served() as s:
        long_answer = asyncio.ensure_future(s.complete(0, max_tokens=400))
        while not long_answer.done():
            await s.complete(n + 1, max_tokens=2)
            n += 1
        await long_answer
        landed = s.engine.first_harvest["landed"]
        text = await s.metrics()
    assert landed >= 1, (s.engine.first_harvest, n)
    assert len(folds) == n + 1
    for stamps in folds:
        t_last_chunk, t_fetch_done, t_first_token = stamps[3:6]
        assert 0.0 < t_last_chunk <= t_fetch_done <= t_first_token, stamps
    sums = _series(text, "dynamo_tpu_request_hop_seconds_sum")
    counts = _series(text, "dynamo_tpu_request_hop_seconds_count")
    assert all(counts[H.HOPS[i]] == n + 1 for i in TTFT_HOPS), counts
    assert all(sums[h] >= 0.0 for h in H.HOPS), sums
    parts = sum(sums[H.HOPS[i]] for i in TTFT_HOPS)
    assert abs(parts - sums["server_ttft"]) < 1e-6 * (n + 1)
    assert _series(text, "dynamo_tpu_request_hop_incomplete_total") == {
        "engine": 0.0, "edge": 0.0}


def test_fold_arithmetic_on_hand_written_stamps():
    m = RequestHopMetrics()
    assert m.fold_engine(1.0, 1.5, 1.75, 2.0, 2.5, 2.625, 3.0)
    assert m.fold_edge(0.5, 1.0, 2.625, 2.75, 4.0)
    assert m.sums == [0.5, 0.5, 0.25, 0.25, 0.5, 0.125, 0.125, 1.25, 3.5, 0.375]
    assert m.counts == [1] * len(H.HOPS)
    assert sum(m.sums[i] for i in TTFT_HOPS) == m.sums[H.SERVER_TTFT]
    # never rode a fused dispatch: every TTFT hop, no join_wait
    assert m.fold_engine(1.0, 1.5, 1.75, 2.0, 2.5, 2.625, 0.0)
    assert m.counts[H.JOIN_WAIT] == 1 and m.counts[H.QUEUE_WAIT] == 2
    # a remote engine left no stamps on the context: the edge's own two only
    assert m.fold_edge(0.5, 0.0, 0.0, 2.75, 4.0)
    assert m.counts[H.EDGE_EMIT] == m.counts[H.SERVER_TTFT] == 2
    assert m.counts[H.EDGE_PRE] == m.counts[H.EDGE_HANDOFF] == 1


def test_a_device_joined_row_counts_a_join_wait_of_zero():
    """Joined on the device, a row rides its first fused dispatch BEFORE its
    first token is home (``t_join`` < ``t_first_token``): the hop's count
    grows, its sum does not, and the TTFT hops are what they were."""
    m = RequestHopMetrics()
    assert m.fold_engine(1.0, 1.5, 1.75, 2.0, 2.5, 2.625, 2.125)
    assert m.counts[H.JOIN_WAIT] == 1 and m.sums[H.JOIN_WAIT] == 0.0
    assert m.fold_engine(1.0, 1.5, 1.75, 2.0, 2.5, 2.625, 3.0)  # a chain break
    assert m.counts[H.JOIN_WAIT] == 2 and m.sums[H.JOIN_WAIT] == 0.375
    assert m.sums[H.FIRST_FETCH_HARVEST] == 0.25 and m.counts[H.QUEUE_WAIT] == 2


async def test_the_account_closes_when_rows_join_on_the_device(monkeypatch):
    """A long answer keeps one chain alive while short prompts join it on the
    device (ISSUE 46): each is folded complete with ``t_join`` ahead of
    ``t_first_token``, ``join_wait`` counts every one of them and adds
    nothing, and the eight TTFT hops still sum to ``server_ttft``."""
    folds = []
    fold = request_hop_metrics.fold_engine

    def fold_engine(*stamps):
        folds.append(stamps)
        return fold(*stamps)

    monkeypatch.setattr(request_hop_metrics, "fold_engine", fold_engine)
    n = 0
    async with Served() as s:
        long_answer = asyncio.ensure_future(s.complete(0, max_tokens=400))
        while not s.engine._pipeline_members:
            await asyncio.sleep(0.002)
        while not long_answer.done():
            await s.complete(n + 1, max_tokens=6)
            n += 1
        await long_answer
        joins = dict(s.engine.pipeline_joins)
        text = await s.metrics()
    early = [st for st in folds if 0.0 < st[6] < st[5]]
    assert joins["device"] >= 1 and len(early) == joins["device"], (joins, len(early), n)
    sums = _series(text, "dynamo_tpu_request_hop_seconds_sum")
    counts = _series(text, "dynamo_tpu_request_hop_seconds_count")
    assert all(c == n + 1 for c in counts.values()), counts
    late = sum(st[6] - st[5] for st in folds if st[6] >= st[5])
    assert sums["join_wait"] == pytest.approx(late, abs=1e-9)
    parts = sum(sums[H.HOPS[i]] for i in TTFT_HOPS)
    assert abs(parts - sums["server_ttft"]) < 1e-6 * (n + 1)
    assert _series(text, "dynamo_tpu_request_hop_incomplete_total") == {
        "engine": 0.0, "edge": 0.0}


@pytest.mark.parametrize("stamps", [
    (1.0, 1.5, 1.75, 2.0, 2.5, 0.0, 0.0),    # ended before its first token
    (1.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0),     # cancelled in the queue
    (3.0, 1.5, 1.75, 2.0, 2.5, 2.625, 0.0),  # queued again after a preemption
    (0.0, 1.5, 1.75, 2.0, 2.5, 2.625, 0.0),  # resumed stream: no fresh prompt
    (1.0, 1.5, -1.0, 2.0, 2.5, 2.625, 0.0),  # voided upstream (remote prefill)
], ids=["no-first-token", "in-queue", "preempted", "resumed", "remote-prefill"])
def test_a_stamp_missing_or_out_of_order_is_incomplete(stamps):
    m = RequestHopMetrics()
    assert not m.fold_engine(*stamps)
    assert m.incomplete_engine == 1 and m.counts == [0] * len(H.HOPS)
    assert not m.fold_edge(0.5, 1.0, 0.0, 0.0, 0.0)
    assert m.incomplete_edge == 1 and m.sums == [0.0] * len(H.HOPS)


# ---------------------------------------------------------- (b) the budget


def _count_calls(monkeypatch) -> dict:
    calls = {"clock": 0, "fold": 0}
    clock = request_hop_metrics.now

    def now():
        calls["clock"] += 1
        return clock()

    def counted(fn):
        def fold(*a):
            calls["fold"] += 1
            return fn(*a)
        return fold

    monkeypatch.setattr(request_hop_metrics, "now", now)
    monkeypatch.setattr(request_hop_metrics, "fold_engine",
                        counted(request_hop_metrics.fold_engine))
    monkeypatch.setattr(request_hop_metrics, "fold_edge",
                        counted(request_hop_metrics.fold_edge))
    return calls


@pytest.mark.parametrize("decode_steps", [1, 4])
async def test_clock_reads_and_folds_are_constant_per_request(monkeypatch, decode_steps):
    """One request at a time, so that no fetch is shared: the count must not
    know how many tokens, stream events or engine steps a request took."""
    r = 3
    per_length = {}
    async with Served(decode_steps) as s:
        await s.complete(99, max_tokens=64)  # every program compiled
        calls = _count_calls(monkeypatch)
        for max_tokens in (8, 64):
            calls.update(clock=0, fold=0)
            for i in range(r):
                await s.complete(i, max_tokens=max_tokens)
            per_length[max_tokens] = dict(calls)
    assert per_length[8] == per_length[64]
    assert per_length[8] == {"clock": 3 * r, "fold": 2 * r}  # same at 1 and 4
    assert sum(per_length[8].values()) <= 12 * r
    assert request_hop_metrics.counts[H.SERVER_TTFT] == 1 + 2 * r


# ------------------------------------------------------- (c) incomplete ones


async def test_cancelled_before_the_first_token_is_incomplete_on_both_sides():
    async with Served() as s:
        await s.complete(0)
        before = list(request_hop_metrics.counts)
        gate = asyncio.Event()
        s.engine.pace_hook = gate.wait  # park the engine before any dispatch
        task = asyncio.ensure_future(s.complete(1))
        for _ in range(200):
            await asyncio.sleep(0.01)
            if s.engine._contexts:
                break
        (ctx,) = s.engine._contexts.values()
        ctx.stop_generating()  # cancelled while its prompt is being computed
        s.engine.pace_hook = None
        gate.set()
        _, body = await task  # the stream ends with a finish chunk, no token
        assert '"cancelled"' in body
        text = await s.metrics()
    assert request_hop_metrics.counts == before
    assert _series(text, "dynamo_tpu_request_hop_incomplete_total") == {
        "engine": 1.0, "edge": 1.0}


async def test_engine_error_is_incomplete_and_adds_to_no_hop():
    async with Served() as s:
        await s.complete(0)
        before = list(request_hop_metrics.counts)
        gate = asyncio.Event()
        s.engine.pace_hook = gate.wait
        task = asyncio.ensure_future(s.complete(1))
        for _ in range(200):
            await asyncio.sleep(0.01)
            if s.engine.scheduler.num_running:
                break
        s.engine._fail_all()  # what an engine-fatal step error does
        await task
        s.engine.pace_hook = None
        gate.set()
    assert request_hop_metrics.counts == before
    assert request_hop_metrics.incomplete_engine == 1
    assert request_hop_metrics.incomplete_edge == 1


def test_a_remotely_prefilled_sequence_is_voided_through_the_context():
    """``DisaggWorker`` marks the in-process context before it asks the
    engine: the local account of such a request would describe a suffix."""
    from dynamo_tpu.llm.protocols import (
        PreprocessedRequest, SamplingOptions, StopConditions)
    from dynamo_tpu.runtime.engine import Context, collect

    async def main():
        eng = TpuEngine(EngineConfig(decode_steps=4, **CFG))
        try:
            for void in (False, True):
                ctx = Context(PreprocessedRequest(
                    token_ids=list(range(1, 30)),
                    stop_conditions=StopConditions(max_tokens=6, ignore_eos=True),
                    sampling_options=SamplingOptions(temperature=0.0),
                ).to_dict())
                if void:
                    ctx.ctx.t_enqueue = -1.0
                items = await collect(await eng.generate(ctx))
                assert sum(len(i.get("token_ids", [])) for i in items) == 6
                assert request_hop_metrics.counts[H.QUEUE_WAIT] == 1
                assert request_hop_metrics.incomplete_engine == int(void)
                assert (ctx.ctx.t_enqueue > 0.0) is not void
        finally:
            await eng.close()

    asyncio.run(main())


# ------------------------------------------------- (d) the stamps as spans


async def test_forced_trace_shows_its_own_hops_as_spans_under_one_trace_id():
    async with Served() as s:
        await s.complete(0)  # unsampled: compiles, and records no span
        assert len(collector) == 0
        request_hop_metrics.reset()
        tid, _ = await s.complete(1, max_tokens=12, headers={"x-trace": "1"})
        await s.exporter.flush()
        async with s.http.get(f"{s.base}/traces/{tid}") as r:
            assert r.status == 200
            trace = await r.json()
    assert request_hop_metrics.counts == [1] * len(H.HOPS)
    spans = {}
    for sp in trace["spans"]:
        assert sp["trace_id"] == tid
        spans.setdefault(sp["name"], sp)
    root = spans["edge.request"]
    ms = [1e3 * v for v in request_hop_metrics.sums]
    want = {
        "engine.queue_wait": ms[H.QUEUE_WAIT],
        "engine.prefill_wait": ms[H.PREFILL_WAIT],
        "engine.prefill_run": ms[H.PREFILL_RUN],
        "engine.first_fetch": ms[H.FIRST_FETCH_DEVICE] + ms[H.FIRST_FETCH_HARVEST],
        "edge.handoff": ms[H.EDGE_HANDOFF],
        "edge.emit": ms[H.EDGE_EMIT],
    }
    for name, dur in want.items():
        assert spans[name]["dur_ms"] == pytest.approx(dur, abs=2e-3), name
        assert spans[name]["parent_id"] == root["span_id"], name
    fetch = spans["engine.first_fetch"]
    (ev,) = fetch["events"]
    assert ev["name"] == "fetch_done"
    assert ev["t_ms"] - fetch["start_ms"] == pytest.approx(ms[H.FIRST_FETCH_DEVICE], abs=2e-3)
    assert root["attrs"]["ttft_ms"] == pytest.approx(ms[H.SERVER_TTFT], abs=2e-3)
    # the older spans are still there, beside the new ones
    assert {"engine.prefill", "edge.preprocess", "engine.decode_chunk"} <= set(spans)
