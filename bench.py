"""Engine decode throughput on the chip (no BENCHMARK.json cells yet — this
is the pre-cell headline script; ROADMAP queue 3 item 1 replaces it).

Runs the full native serving path — scheduler, paged KV manager, jitted
forward+sampling steps, token streaming — on the flagship architecture
(llama-3.1-8b = DeepSeek-R1-Distill-Llama-8B shapes) and prints ONE JSON
line: {"metric", "value", "unit", "vs_baseline"}.

Layer count auto-scales to fit single-chip HBM (the decoder is a lax.scan,
so per-layer cost is architecture-identical; throughput is normalised to
tokens/sec at the benchmarked depth and also reported per-layer-adjusted in
stderr for tracking).  The reference publishes only relative improvements
(BASELINE.md; BASELINE.json published={}), so vs_baseline is the ratio
against our own recorded target of 1.0 until absolute reference numbers
exist.

Env knobs: BENCH_MODEL, BENCH_LAYERS, BENCH_REQUESTS, BENCH_ISL, BENCH_OSL.

Every JSON line names the device it ran on (``device``: platform,
device_kind, count).  There is no fallback: on a CPU backend the script
exits non-zero unless ``--cpu-smoke`` ASKS for the tiny CPU configuration,
whose metric name says so and whose device metrics (MFU) are null.
"""

from __future__ import annotations

import asyncio
import json
import os
import sys
import time

import jax

CPU_SMOKE = "--cpu-smoke" in sys.argv[1:]

# Published per-chip peaks, keyed by jax's ``device_kind``.  Source: Google
# Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 393 TOP/s int8, 819 GB/s
# HBM bandwidth, 16 GB HBM).  A device that is not here is an error.
DEVICE_PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12, "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
    },
}


def device_info() -> dict:
    d = jax.devices()
    return {
        "platform": d[0].platform,
        "device_kind": d[0].device_kind,
        "count": len(d),
    }


def device_peaks() -> dict:
    kind = jax.devices()[0].device_kind
    if kind not in DEVICE_PEAKS:
        raise SystemExit(
            f"bench: no published peaks for device_kind {kind!r} "
            f"(known: {sorted(DEVICE_PEAKS)}); add it to DEVICE_PEAKS with "
            "its source"
        )
    return DEVICE_PEAKS[kind]


def emit(result: dict) -> None:
    """The ONE stdout JSON line, naming the device it was measured on."""
    result["device"] = device_info()
    if CPU_SMOKE:
        result["metric"] += "_cpu_smoke"
    print(json.dumps(result))


def _engine_config():
    from dynamo_tpu.engine.config import EngineConfig

    if jax.default_backend() == "cpu":
        if not CPU_SMOKE:
            raise SystemExit(
                "bench: JAX found no accelerator (backend cpu).  This "
                "benchmark measures the chip; `--cpu-smoke` runs the tiny "
                "CPU configuration, labelled as such."
            )
        return (
            EngineConfig(
                model="debug-tiny",
                block_size=4,
                num_blocks=256,
                max_batch=8,
                max_model_len=256,
                prefill_chunk=128,
                dtype="float32",
            ),
            {"isl": 32, "osl": 16, "requests": 8},
        )
    model = os.environ.get("BENCH_MODEL", "llama-3.1-8b")
    layers = int(os.environ.get("BENCH_LAYERS", "0"))
    isl = int(os.environ.get("BENCH_ISL", "128"))
    osl = int(os.environ.get("BENCH_OSL", "64"))
    # Decode is weights-bound, so tok/s should scale nearly linearly with
    # batch (the scaling itself: not measured on this machine).
    max_batch = int(os.environ.get("BENCH_MAX_BATCH", "256"))
    max_model_len = max(256, 1 << (isl + osl + 16 - 1).bit_length())
    # Tight KV budgeting for large batches: the pool is num_blocks ~
    # max_batch * ceil(max_model_len/16), so trimming ctx to the workload
    # (isl+osl+slack) is what lets batch 512 fit beside full-depth weights.
    max_model_len = int(os.environ.get("BENCH_CTX", str(max_model_len)))
    # Weight quantization (round 5): int8 weights + int8 KV fit the FULL
    # 32-layer 8B model on one v5e chip — no more truncated geometry.  The
    # reference's own baseline workload is a quantized-weights checkpoint
    # (FP8-dynamic; BASELINE.md), so this is the matching configuration.
    # BENCH_QUANT=none benchmarks the bf16 path (auto-truncated to fit).
    quant = os.environ.get("BENCH_QUANT", "int8")
    quant = None if quant in ("", "none", "0") else quant
    # KV page dtype decoupled for A/B runs (default: int8 alongside int8
    # weights — full-depth KV capacity; bf16 otherwise).
    kv_dtype = os.environ.get("BENCH_KV", "int8" if quant else "")
    kv_dtype = "" if kv_dtype in ("", "none", "0") else kv_dtype
    cfg = EngineConfig(
        model=model,
        block_size=16,
        num_blocks=max_batch * ((max_model_len + 15) // 16) + 64,
        max_batch=max_batch,
        # Paged attention gathers max_model_len of context per step, so keep
        # the window tight to the workload (power-of-two padded).
        max_model_len=max_model_len,
        prefill_chunk=512,
        # 8-step fused chunks with an 8-deep pipeline: an earlier round's
        # choice whose sweep is gone; not measured on this machine.
        decode_steps=int(os.environ.get("BENCH_DECODE_STEPS", "8")),
        pipeline_depth=int(os.environ.get("BENCH_PIPELINE_DEPTH", "8")),
        weight_quant=quant,
        cache_dtype=kv_dtype or None,
        kv_scale="auto" if kv_dtype in ("int8", "float8_e4m3fn") else 1.0,
    )
    return cfg, {
        "isl": int(os.environ.get("BENCH_ISL", "128")),
        "osl": int(os.environ.get("BENCH_OSL", "64")),
        "requests": int(os.environ.get("BENCH_REQUESTS", str(max_batch))),
        "layers": layers,
    }


async def _run(engine, isl: int, osl: int, n: int, vocab: int):
    from dynamo_tpu.llm.protocols import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.runtime.engine import Context, collect

    async def one(i: int) -> int:
        prompt = [(i * 7919 + j * 104729) % vocab for j in range(isl)]
        req = PreprocessedRequest(
            token_ids=prompt,
            stop_conditions=StopConditions(max_tokens=osl, ignore_eos=True),
            sampling_options=SamplingOptions(temperature=0.0),
        )
        stream = await engine.generate(Context(req.to_dict()))
        items = await collect(stream)
        return sum(len(it["token_ids"]) for it in items)

    counts = await asyncio.gather(*[one(i) for i in range(n)])
    return sum(counts)


def _spec_prompts(kind: str, isl: int, n: int, vocab: int):
    """Speculation-mode workloads.  ``repetitive``: short-period templated
    prompts (period-8 pattern per request) — greedy decode of such traffic
    degenerates into loops the n-gram proposer mines; ``random``: the
    default pseudo-random prompts with per-request jittered ISL — no
    exploitable structure, the non-regression side of the claim."""
    prompts = []
    for i in range(n):
        if kind == "repetitive":
            pattern = [(i * 131 + j * 17 + 3) % vocab for j in range(8)]
            prompts.append((pattern * ((isl + 7) // 8))[:isl])
        else:
            isl_i = max(8, isl // 2 + (i * 2654435761) % isl)  # random ISL
            prompts.append(
                [(i * 7919 + j * 104729 + 13) % vocab for j in range(isl_i)]
            )
    return prompts


async def _spec_run(engine, prompts, osl: int, temperature: float):
    """Run one speculation-mode pass; returns (tokens, wall_s, streams)."""
    from dynamo_tpu.llm.protocols import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.runtime.engine import Context, collect

    async def one(i: int, prompt):
        req = PreprocessedRequest(
            token_ids=prompt,
            stop_conditions=StopConditions(max_tokens=osl, ignore_eos=True),
            sampling_options=SamplingOptions(
                temperature=temperature, seed=i * 7 + 1
            ),
        )
        stream = await engine.generate(Context(req.to_dict()))
        items = await collect(stream)
        return [t for it in items for t in it["token_ids"]]

    t0 = time.perf_counter()
    streams = await asyncio.gather(
        *[one(i, p) for i, p in enumerate(prompts)]
    )
    dt = time.perf_counter() - t0
    return sum(len(s) for s in streams), dt, streams


def _spec_bench(cfg, model_cfg) -> None:
    """BENCH_SPEC=1: measure draft-free speculative decoding.

    Two workloads (repetitive templated prompts under greedy; random
    prompts under seeded temperature sampling), each run spec-off then
    spec-on with a fresh engine at otherwise identical config.  Asserts
    token-identical streams between the modes (the exact-stream acceptance
    claim, ON HARDWARE), then prints one JSON line: the repetitive-workload
    speedup as the headline, the random-workload ratio (non-regression
    bar: >= 0.97), and the acceptance-rate / tokens-per-dispatch gauges.
    Env: BENCH_SPEC_ISL / BENCH_SPEC_OSL / BENCH_SPEC_REQUESTS /
    BENCH_SPEC_K."""
    import dataclasses

    from dynamo_tpu.engine.engine import TpuEngine
    from dynamo_tpu.llm.metrics import spec_metrics

    isl = int(os.environ.get("BENCH_SPEC_ISL", "128"))
    osl = int(os.environ.get("BENCH_SPEC_OSL", "64"))
    # Low-concurrency default: speculation trades batch rows for per-seq
    # speed (each draft token is an extra row of the unified step), so its
    # regime is requests << max_batch — at saturation the fused pipeline
    # is already optimal and the engine correctly stands down.
    n = int(
        os.environ.get("BENCH_SPEC_REQUESTS", str(max(2, cfg.max_batch // 8)))
    )
    k = int(os.environ.get("BENCH_SPEC_K", "8"))
    vocab = model_cfg.vocab_size
    results: dict = {}
    streams: dict = {}
    async def one_mode(mode: str) -> None:
        # One asyncio.run per engine: its queues/events bind to the loop.
        cfg_m = dataclasses.replace(
            cfg, spec_decode={"enable": mode == "on", "k": k}
        )
        engine = TpuEngine(cfg_m)
        engine.warmup()
        try:
            for kind, temp in (("repetitive", 0.0), ("random", 0.7)):
                spec_metrics.reset()
                prompts = _spec_prompts(kind, isl, n, vocab)
                # Warm pass (host paths + prefix-cache state parity), then
                # the timed pass.
                await _spec_run(engine, prompts, 4, temp)
                toks, dt, out = await _spec_run(engine, prompts, osl, temp)
                results[(kind, mode)] = toks / dt
                streams[(kind, mode)] = out
                snap = spec_metrics.snapshot()
                print(
                    f"bench[spec]: {kind}/{mode} {toks} tokens in {dt:.2f}s "
                    f"({toks / dt:.1f} tok/s) acceptance="
                    f"{snap['acceptance_rate']:.3f} tok/dispatch="
                    f"{snap['tokens_per_dispatch']:.2f} "
                    f"dispatches={int(snap['dispatches_total'])}",
                    file=sys.stderr,
                )
                if kind == "repetitive":
                    results[("acceptance", mode)] = snap["acceptance_rate"]
                    results[("tok_per_dispatch", mode)] = snap[
                        "tokens_per_dispatch"
                    ]
        finally:
            await engine.close()

    for mode in ("off", "on"):
        asyncio.run(one_mode(mode))
    for kind in ("repetitive", "random"):
        if streams[(kind, "on")] != streams[(kind, "off")]:
            raise RuntimeError(
                f"speculation changed the {kind} token streams — the "
                "exact-stream acceptance invariant is broken"
            )
    print("bench[spec]: token streams identical on/off", file=sys.stderr)
    rep = results[("repetitive", "on")] / results[("repetitive", "off")]
    rnd = results[("random", "on")] / results[("random", "off")]
    emit(
        {
            "metric": "spec_decode_speedup_repetitive",
            "value": round(rep, 3),
            "unit": "x",
            "vs_baseline": round(rep, 3),
            "random_ratio": round(rnd, 3),
            "repetitive_tok_s": {
                "off": round(results[("repetitive", "off")], 2),
                "on": round(results[("repetitive", "on")], 2),
            },
            "random_tok_s": {
                "off": round(results[("random", "off")], 2),
                "on": round(results[("random", "on")], 2),
            },
            "acceptance_rate": round(results[("acceptance", "on")], 4),
            "tokens_per_dispatch": round(
                results[("tok_per_dispatch", "on")], 2
            ),
        }
    )


def _churn_bench(cfg, model_cfg) -> None:
    """BENCH_CHURN=1: continuous-batching churn trace — rows finishing at
    staggered lengths plus late arrivals landing inside a live fused
    session — run with in-loop admission/retirement ON (default) and OFF
    (``_continuous_decode = False``, the legacy drain-on-any-change
    control).  Asserts byte-identical token streams and zero new compiles,
    then prints one JSON line with rebuild counts, in-loop churn counters,
    host-gap fraction and per-kind dispatch percentiles — the CI smoke
    (tools/ci.sh) bars on it.  Env: BENCH_CHURN_ISL / BENCH_CHURN_REQUESTS.
    """
    from dynamo_tpu.engine.engine import TpuEngine
    from dynamo_tpu.llm.protocols import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.runtime.engine import Context, collect

    isl = int(os.environ.get("BENCH_CHURN_ISL", "24"))
    n = int(os.environ.get("BENCH_CHURN_REQUESTS", "10"))
    vocab = model_cfg.vocab_size
    results: dict = {}

    async def run_mode(continuous: bool) -> None:
        engine = TpuEngine(cfg)
        engine._continuous_decode = continuous
        compiles0 = engine.warmup()
        try:

            async def one(i: int, osl: int, late: bool):
                if late:
                    # Land INSIDE a live fused session, not merely "later":
                    # wait until the pipeline actually has members (both
                    # modes use the same trigger, so the traces compare).
                    for _ in range(2000):
                        if engine._pipeline_members:
                            break
                        await asyncio.sleep(0.002)
                prompt = [(i * 7919 + j * 104729) % vocab for j in range(isl)]
                req = PreprocessedRequest(
                    token_ids=prompt,
                    stop_conditions=StopConditions(
                        max_tokens=osl, ignore_eos=True
                    ),
                    sampling_options=SamplingOptions(
                        temperature=0.9, seed=i + 1
                    ),
                )
                items = await collect(
                    await engine.generate(Context(req.to_dict()))
                )
                return [t for it in items for t in it["token_ids"]]

            jobs = []
            for i in range(n):
                # Staggered budgets: short rows retire while long ones keep
                # the session alive; the back half arrives late.
                late = i >= (n + 1) // 2
                osl = (16 + 8 * (i % 3)) if not late else (6 + 3 * (i % 4))
                jobs.append(one(i, osl, late))
            t0 = time.perf_counter()
            streams = await asyncio.gather(*jobs)
            dt = time.perf_counter() - t0
            results[continuous] = {
                "streams": streams,
                "tok_s": sum(len(s) for s in streams) / dt,
                "compiles_stable": engine.compile_counts() == compiles0,
                "summary": engine.dispatch_summary(),
                # Which decode kernel actually served the run — the CI
                # smoke asserts the fused path under DYN_DECODE_KERNEL.
                "decode_kernel": engine.decode_kernel,
            }
        finally:
            await engine.close()

    for mode in (True, False):
        # One asyncio.run per engine: its queues/events bind to the loop.
        asyncio.run(run_mode(mode))
    on, off = results[True], results[False]
    if on["streams"] != off["streams"]:
        raise RuntimeError(
            "continuous batching changed the token streams — the "
            "exact-stream equivalence invariant is broken"
        )
    if on["decode_kernel"] != off["decode_kernel"]:
        raise RuntimeError(
            "churn modes resolved different decode kernels: "
            f"{on['decode_kernel']} vs {off['decode_kernel']}"
        )
    print(
        "bench[churn]: token streams identical on/off "
        f"(decode_kernel={on['decode_kernel']})",
        file=sys.stderr,
    )
    pipe_on, pipe_off = on["summary"]["pipeline"], off["summary"]["pipeline"]
    for mode, r, pipe in (("on", on, pipe_on), ("off", off, pipe_off)):
        print(
            f"bench[churn]: continuous={mode} {r['tok_s']:.1f} tok/s "
            f"sessions={pipe['sessions']} rebuilds={pipe['rebuilds']} "
            f"admissions={pipe['continuous_admissions']} "
            f"retired={pipe['continuous_retired']} "
            f"host_gap={pipe['host_gap_frac']}",
            file=sys.stderr,
        )
    emit(
        {
            "metric": "continuous_decode_rebuilds",
            "decode_kernel": on["decode_kernel"],
            "value": pipe_on["rebuilds"],
            "unit": "rebuilds",
            "vs_baseline": round(
                pipe_on["rebuilds"] / max(1, pipe_off["rebuilds"]), 3
            ),
            "rebuilds": {
                "continuous": pipe_on["rebuilds"],
                "forced": pipe_off["rebuilds"],
            },
            "sessions": {
                "continuous": pipe_on["sessions"],
                "forced": pipe_off["sessions"],
            },
            "continuous_admissions": pipe_on["continuous_admissions"],
            "continuous_retired": pipe_on["continuous_retired"],
            "host_gap_frac": pipe_on["host_gap_frac"],
            "compile_counts_stable": bool(
                on["compiles_stable"] and off["compiles_stable"]
            ),
            "dispatch": {
                k: {
                    "dispatches": v["dispatches"],
                    "p50_ms": v["p50_ms"],
                    "p99_ms": v["p99_ms"],
                }
                for k, v in on["summary"]["kinds"].items()
            },
            "tok_s": {
                "continuous": round(on["tok_s"], 2),
                "forced": round(off["tok_s"], 2),
            },
        }
    )


def _prefix_bench(cfg, model_cfg) -> None:
    """BENCH_PREFIX=1: tiered-KV prefix-reuse ladder (docs/kv_tiering.md).

    A shared-system-prompt / multi-turn trace (every session's turn-2
    prompt extends its turn-1 prompt+output, and all sessions share one
    system prefix) replayed through four tier configurations — tiers OFF /
    host-only / host+disk (tiny host budget forces demotion) / cross-worker
    PULL (a fresh engine pulls the prefix a donor computed) — reporting
    per-mode TTFT and the fraction of second-occurrence prefill compute
    skipped via prefix hits.  Bars (tools/ci.sh prefix smoke): host and
    host+disk skip >= 90% of complete-block prefill, the pull serves a
    prefix its engine never computed, ALL modes' streams are
    byte-identical, and no mode compiles anything after its priming
    session.  Env: BENCH_PREFIX_SESSIONS / BENCH_PREFIX_SYS.
    """
    from dynamo_tpu.engine.engine import TpuEngine
    from dynamo_tpu.llm.kv_router.pull import PrefixPuller
    from dynamo_tpu.llm.protocols import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.runtime.engine import Context

    sessions = int(os.environ.get("BENCH_PREFIX_SESSIONS", "5"))
    bs = 4
    sys_len = int(os.environ.get("BENCH_PREFIX_SYS", "40"))
    ctx_len, osl, extra = 12, 9, 3
    vocab = model_cfg.vocab_size
    base = dict(
        model=cfg.model,
        block_size=bs,
        num_blocks=48,  # small pool → sessions evict each other
        max_batch=4,
        max_model_len=256,
        prefill_chunk=64,
        dtype=cfg.dtype,
        host_offload_interval=0.01,
    )
    shared_sys = [(7 * j + 13) % vocab for j in range(sys_len)]

    def _user(i: int, n: int, off: int = 0):
        return [(i * 7919 + (off + j) * 104729) % vocab for j in range(n)]

    async def _gen(engine, tokens, max_tokens, annotations=None):
        req = PreprocessedRequest(
            token_ids=list(tokens),
            stop_conditions=StopConditions(
                max_tokens=max_tokens, ignore_eos=True
            ),
            sampling_options=SamplingOptions(temperature=0.0),
            annotations=dict(annotations or {}),
        ).to_dict()
        t0 = time.perf_counter()
        stream = await engine.generate(Context(req))
        out, ttft = [], None
        async for item in stream:
            if ttft is None:
                ttft = (time.perf_counter() - t0) * 1e3
            out.extend(item.get("token_ids") or [])
        return out, ttft

    async def run_mode(mode: str, tmpdir: str) -> dict:
        over: dict = {}
        if mode == "off" or mode == "pull":
            over["host_cache_bytes"] = 0
        elif mode == "host":
            over["host_cache_bytes"] = 256 << 20
        elif mode == "disk":
            over["host_cache_bytes"] = 1  # resized to blocks below
            over["disk_cache_bytes"] = 256 << 20
            over["disk_cache_dir"] = tmpdir
        from dynamo_tpu.engine.config import EngineConfig

        mode_cfg = EngineConfig(**{**base, **over})
        engine = TpuEngine(mode_cfg)
        donor = None
        if mode == "disk":
            # Tiny host window (4 blocks): almost everything demotes to
            # disk, so second-occurrence restores exercise disk→host→HBM.
            engine.host_kv.capacity_bytes = 4 * engine.block_nbytes()
        if mode == "pull":
            donor = TpuEngine(EngineConfig(**{**base, "host_cache_bytes": 0}))

            async def exporter(worker_id, data):
                return await donor.export_prompt_blocks(
                    data["token_ids"],
                    start_block=data.get("start_block", 0),
                    max_blocks=data.get("max_blocks", 0),
                    salt=data.get("salt"),
                )

            engine.set_prefix_puller(PrefixPuller(engine, exporter))
        # Warmup covers every unified token bucket; the priming session
        # below covers the tier paths (gather/inject/restore pads) warmup
        # does not reach.  "Zero new compiles" is measured after both.
        engine.warmup()
        if donor is not None:
            donor.warmup()
        try:
            streams, ttfts = [], []
            skipped = total = 0

            async def session(i: int, measured: bool):
                nonlocal skipped, total
                t1 = shared_sys + _user(i, ctx_len)
                serve = donor if mode == "pull" else engine
                out1, _ = await _gen(serve, t1, osl)
                await serve.drain_offload()
                # Evict: filler prompts churn the ENGINE's small HBM pool
                # between the turns (in pull mode the engine is the cold
                # target — the donor keeps its cache, as a remote peer
                # would).
                for f in range(6):
                    await _gen(engine, _user(1000 + i * 11 + f, 32), 1)
                    await engine.drain_offload()
                t2 = t1 + out1 + _user(i, extra, off=900)
                hint = None
                if mode == "pull":
                    blocks = donor.estimate_prefix_hit(t2) // bs
                    hint = {"kv_pull": {"worker_id": 0, "blocks": blocks}}
                lk0, mt0 = engine.kv.lookup_blocks, engine.kv.matched_blocks
                out2, ttft = await _gen(engine, t2, osl, annotations=hint)
                if measured:
                    streams.append(out2)
                    ttfts.append(ttft)
                    skipped += engine.kv.matched_blocks - mt0
                    total += engine.kv.lookup_blocks - lk0

            compiles_ref: list = []

            async def drive():
                await session(-1, False)  # priming: compiles inject/restore
                compiles_ref.append(engine.compile_counts())
                for i in range(sessions):
                    await session(i, True)

            await drive()
            stable = engine.compile_counts() == compiles_ref[0]
            ttfts_s = sorted(ttfts)
            return {
                "streams": streams,
                "ttft_ms_p50": round(ttfts_s[len(ttfts_s) // 2], 2),
                "skip_frac": round(skipped / total, 4) if total else 0.0,
                "compile_stable": stable,
                "pulled_blocks": (
                    engine.kv.matched_blocks if mode == "pull" else 0
                ),
            }
        finally:
            await engine.close()
            if donor is not None:
                await donor.close()

    import tempfile

    results: dict = {}
    with tempfile.TemporaryDirectory() as tmpdir:
        for mode in ("off", "host", "disk", "pull"):
            results[mode] = asyncio.run(run_mode(mode, tmpdir))
            r = results[mode]
            print(
                f"bench[prefix]: {mode:5s} ttft_p50={r['ttft_ms_p50']}ms "
                f"skip={r['skip_frac']} compile_stable={r['compile_stable']}",
                file=sys.stderr,
            )
    identical = all(
        results[m]["streams"] == results["off"]["streams"]
        for m in ("host", "disk", "pull")
    )
    if not identical:
        raise RuntimeError(
            "tiered/pulled prefix streams diverged from the no-tier "
            "control — the exact-stream equivalence invariant is broken"
        )
    print("bench[prefix]: streams identical across all modes", file=sys.stderr)
    emit(
        {
            "metric": "prefix_reuse_skip_frac",
            "value": results["host"]["skip_frac"],
            "unit": "frac",
            "vs_baseline": 0.0,
            "modes": {
                m: {k: v for k, v in r.items() if k != "streams"}
                for m, r in results.items()
            },
            "identical": identical,
            "compile_stable": all(
                r["compile_stable"] for r in results.values()
            ),
            "pull_served_blocks": results["pull"]["pulled_blocks"],
        }
    )


def main() -> None:
    from dynamo_tpu.engine.engine import TpuEngine
    from dynamo_tpu.models import get_config

    cfg, wl = _engine_config()
    model_cfg = get_config(cfg.model)
    layers = wl.get("layers") or 0
    if layers <= 0 and cfg.model == "llama-3.1-8b" and not cfg.weight_quant:
        # bf16 fallback: fit single-chip HBM by truncating depth
        # (~0.5 GB/layer bf16 + embed/head ~1 GB + KV).  The int8 default
        # runs FULL depth — no truncation.
        mem = jax.devices()[0].memory_stats()["bytes_limit"]
        layers = max(2, min(32, int((mem * 0.7 - (2 << 30)) / (520 << 20))))
    if layers and layers != model_cfg.num_layers:
        get_config(cfg.model)  # ensure registered
        import dynamo_tpu.models.config as mc

        mc.register_config(model_cfg.with_overrides(name=cfg.model + "-bench", num_layers=layers))
        cfg.model = cfg.model + "-bench"
        model_cfg = get_config(cfg.model)

    print(
        f"bench: model={cfg.model} layers={model_cfg.num_layers} "
        f"quant={cfg.weight_quant or 'bf16'} kv={cfg.cache_dtype} "
        f"backend={jax.default_backend()}",
        file=sys.stderr,
    )
    if os.environ.get("BENCH_SPEC"):
        # Speculative-decoding mode: repetitive + random workloads, spec
        # off vs on, stream-identity asserted (see _spec_bench).
        _spec_bench(cfg, model_cfg)
        return
    if os.environ.get("BENCH_CHURN"):
        # Continuous-batching churn mode: staggered finishes + late
        # arrivals, continuous vs forced-rebuild (see _churn_bench).
        _churn_bench(cfg, model_cfg)
        return
    if os.environ.get("BENCH_PREFIX"):
        # Tiered-KV prefix-reuse ladder: tiers off / host / host+disk /
        # cross-worker pull over a shared-prefix multi-turn trace
        # (see _prefix_bench).
        _prefix_bench(cfg, model_cfg)
        return
    engine = TpuEngine(cfg)

    # Pre-compile EVERY dispatchable program (each reachable unified token
    # bucket + the fused decode pipeline) so zero XLA compiles land in the
    # timed window — round 2 lost 14.5s of a 17.5s wall to one cold bucket.
    t0 = time.perf_counter()
    compiles = engine.warmup()
    cold_s = time.perf_counter() - t0
    print(
        f"bench: warmup compiled {compiles} "
        f"(buckets {engine.reachable_token_buckets()}) "
        f"in {cold_s:.1f}s",
        file=sys.stderr,
    )
    ms = jax.devices()[0].memory_stats()  # None on a CPU device
    if ms:
        print(
            f"bench: device memory {ms['bytes_in_use']/2**30:.2f} GiB"
            f" in use / {ms['bytes_limit']/2**30:.2f} GiB limit",
            file=sys.stderr,
        )
    if os.environ.get("BENCH_WARM_CHECK"):
        # Persistent-compilation-cache diagnostic (instead of the throughput
        # bench): a SECOND engine — fresh jit closures, as a restarted
        # worker would have — must warm up from the on-disk cache in a
        # fraction of the first warmup's time.  The first engine is closed
        # and dropped before the second is built so HBM holds one copy of
        # the weights at a time.
        import gc

        asyncio.run(engine.close())
        del engine
        gc.collect()
        engine2 = TpuEngine(cfg)
        t0 = time.perf_counter()
        engine2.warmup()
        warm_s = time.perf_counter() - t0
        asyncio.run(engine2.close())
        print(
            f"bench: warm-restart warmup {warm_s:.1f}s "
            f"(first start {cold_s:.1f}s, persistent XLA cache)",
            file=sys.stderr,
        )
        emit(
            {
                "metric": "warm_restart_warmup_s",
                "value": round(warm_s, 1),
                "unit": "s",
                "vs_baseline": round(cold_s / warm_s, 2) if warm_s else 0.0,
            }
        )
        return

    extras: dict = {}

    async def bench() -> float:
        # Short warm pass at the timed run's concurrency (host-path warmup;
        # all device programs are already compiled above).
        await _run(engine, wl["isl"], 4, wl["requests"], model_cfg.vocab_size)
        baseline_compiles = engine.compile_counts()
        # Scope the trace, session counters AND host-gap accounting to the
        # timed window together — mixed warm-pass counters would make the
        # JSON's pipeline block internally inconsistent.
        engine.reset_dispatch_stats()
        t0 = time.perf_counter()
        total = await _run(
            engine, wl["isl"], wl["osl"], wl["requests"], model_cfg.vocab_size
        )
        dt = time.perf_counter() - t0
        after = engine.compile_counts()
        if after != baseline_compiles:
            raise RuntimeError(
                f"XLA compile inside the timed window: {baseline_compiles} "
                f"-> {after} (warmup must cover every reachable shape)"
            )
        print(f"bench: compile counts stable at {after}", file=sys.stderr)
        summary = engine.step_summary()
        dispatch = engine.dispatch_summary()
        await engine.close()
        print(
            f"bench: {total} output tokens in {dt:.2f}s "
            f"({wl['requests']} reqs, isl={wl['isl']} osl={wl['osl']})",
            file=sys.stderr,
        )
        device_s = sum(v["wall_s"] for v in summary.values())
        print(
            f"bench: dispatch summary {json.dumps(summary)}", file=sys.stderr
        )
        print(
            f"bench: host gap {dt - device_s:.2f}s of {dt:.2f}s wall "
            f"({100 * (dt - device_s) / dt:.0f}%)",
            file=sys.stderr,
        )
        # End-to-end decode utilisation: 2 * params * tokens / (wall *
        # peak), prefill inside the window — not a kernel's roofline share.
        # Peak: the rate the matmuls run at (int8 under weight_quant).
        # null on a CPU smoke: a CPU run has no device metric.
        c = model_cfg
        p_layer = c.hidden_size * (c.q_size + 2 * c.kv_size + c.q_size) + (
            3 * c.hidden_size * c.intermediate_size
        )
        n_params = c.num_layers * p_layer + 2 * c.vocab_size * c.hidden_size
        peak = None
        if not CPU_SMOKE:
            peaks = device_peaks()
            peak = peaks["int8_ops" if cfg.weight_quant else "bf16_flops"]
        mfu = 2 * n_params * total / (dt * peak) if peak else None
        print(
            f"bench: ~{n_params/1e9:.2f}B params, decode utilisation "
            + (f"{mfu*100:.2f}% of {peak/1e12:.0f}T" if peak else "not measured"),
            file=sys.stderr,
        )
        # Attention-time share (analytic HBM-byte attribution): decode is
        # bandwidth-bound, so the expected step-time split is the byte
        # split — weights streamed once per fused step vs KV context
        # gathered per row at the mean decode context.  Lets BENCH_r06
        # attribute MFU movement to the attention kernel (fused dequant
        # reads quantized KV at 1 byte/value) vs the matmul path instead
        # of hand-waving from the headline number.
        import numpy as _np
        rows = min(wl["requests"], cfg.max_batch)
        mean_ctx = wl["isl"] + wl["osl"] / 2.0
        kv_itemsize = _np.dtype(cfg.cache_dtype).itemsize
        w_itemsize = 1 if cfg.weight_quant else 2
        kv_bytes = rows * mean_ctx * 2 * c.kv_size * c.num_layers * kv_itemsize
        w_bytes = n_params * w_itemsize
        attn_share = kv_bytes / (kv_bytes + w_bytes)
        print(
            f"bench: attention share (byte model) {attn_share*100:.1f}% "
            f"(kv {kv_bytes/1e6:.0f}MB vs weights {w_bytes/1e6:.0f}MB per "
            f"step, kernel={dispatch.get('decode_kernel')})",
            file=sys.stderr,
        )
        # Prefill side of the byte model (ISSUE 19): a chunk streams the
        # weights once and reads the PRIOR prefix KV from paged cache —
        # mean prefix over a full prompt's chunk sequence is isl/2.  The
        # share says when the paged-prefix read (what the Pallas prefill
        # kernel fuses dequant into) starts to dominate the chunk, which
        # happens at 128k-class context, not at bench-sized prompts.
        pf_kv_bytes = (
            wl["isl"] / 2.0 * 2 * c.kv_size * c.num_layers * kv_itemsize
        )
        pf_share = pf_kv_bytes / (pf_kv_bytes + w_bytes)
        # Prefill MFU + per-chunk latency from the engine's chunk trace
        # (engine.prefill_summary via dispatch_summary) — attributable to
        # the prefill kernel the same way decode MFU is to the decode one.
        pf = dispatch.get("prefill", {})
        pf_wall = pf.get("wall_s", 0.0)
        pf_tokens = pf.get("prompt_tokens", 0)
        pf_mfu = (
            2 * n_params * pf_tokens / (pf_wall * peak)
            if peak and pf_wall else None
        )
        print(
            "bench: prefill utilisation "
            + (f"{pf_mfu*100:.2f}%" if pf_mfu is not None else "not measured")
            + f" ({pf_tokens} prompt "
            f"tokens over {pf.get('chunks', 0)} chunks in {pf_wall:.2f}s, "
            f"chunk p50 {pf.get('p50_ms', 0.0)}ms p99 {pf.get('p99_ms', 0.0)}"
            f"ms, kernel={dispatch.get('prefill_kernel')})",
            file=sys.stderr,
        )
        # Machine-readable trajectory (ISSUE 11): until now only tok/s was
        # parseable and the ROADMAP quoted MFU/host-gap by hand from stderr.
        extras.update(
            {
                "decode_mfu": None if mfu is None else round(mfu, 4),
                "decode_kernel": dispatch.get("decode_kernel"),
                "prefill_mfu": None if pf_mfu is None else round(pf_mfu, 4),
                "prefill_kernel": dispatch.get("prefill_kernel"),
                "prefill": pf,
                "attention": {
                    "share_est": round(attn_share, 4),
                    "kv_bytes_per_step": int(kv_bytes),
                    "weight_bytes_per_step": int(w_bytes),
                    "prefill_share_est": round(pf_share, 4),
                    "prefill_kv_bytes_per_chunk": int(pf_kv_bytes),
                },
                "host_gap_frac": round(max(0.0, dt - device_s) / dt, 4),
                "dispatch": {
                    k: {
                        "dispatches": v["dispatches"],
                        "p50_ms": v["p50_ms"],
                        "p99_ms": v["p99_ms"],
                    }
                    for k, v in summary.items()
                },
                "pipeline": dispatch["pipeline"],
            }
        )
        return total / dt

    tps = asyncio.run(bench())
    # vs_baseline: against BENCH_PRIOR_TPS when the caller supplies a prior
    # measured on the same device and workload; none is built in (default 0
    # → 1.0): no earlier number was measured on this machine.
    prior = float(os.environ.get("BENCH_PRIOR_TPS", "0"))
    emit(
        {
            "metric": "engine_output_tokens_per_sec",
            "value": round(tps, 2),
            "unit": "tokens/s",
            "vs_baseline": round(tps / prior, 3) if prior > 0 else 1.0,
            **extras,
        }
    )


if __name__ == "__main__":
    main()
