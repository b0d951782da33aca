"""TpuEngine integration tests on CPU: generation end-to-end, determinism
across batist compositions, prefix-cache reuse, KV events, cancellation,
preemption, and the KV block manager's reuse pool."""

import asyncio

import pytest

from dynamo_tpu.engine import EngineConfig, KvBlockManager
from dynamo_tpu.engine.engine import TpuEngine
from dynamo_tpu.llm.kv_router.protocols import KvCacheRemoveData, KvCacheStoreData
from dynamo_tpu.llm.protocols import PreprocessedRequest, SamplingOptions, StopConditions
from dynamo_tpu.runtime.engine import Context, collect
from dynamo_tpu.tokens import hash_token_blocks

CFG = dict(
    model="debug-tiny",
    block_size=4,
    num_blocks=64,
    max_batch=4,
    max_model_len=128,
    prefill_chunk=32,
    dtype="float32",
)


def _req(tokens, max_tokens=8, **kw):
    return PreprocessedRequest(
        token_ids=list(tokens),
        stop_conditions=StopConditions(max_tokens=max_tokens, ignore_eos=True),
        sampling_options=SamplingOptions(**kw),
    ).to_dict()


async def _generate(engine, tokens, max_tokens=8, **kw):
    stream = await engine.generate(Context(_req(tokens, max_tokens, **kw)))
    out = await collect(stream)
    toks = [t for item in out for t in item["token_ids"]]
    assert out[-1]["finish_reason"] is not None
    return toks, out[-1]


def test_engine_generates_deterministically():
    async def main():
        engine = TpuEngine(EngineConfig(**CFG))
        toks1, final = await _generate(engine, [1, 2, 3, 4, 5], max_tokens=6)
        assert len(toks1) == 6
        assert final["finish_reason"] == "length"
        assert final["usage"]["completion_tokens"] == 6
        # Same prompt again (now prefix-cached) → identical greedy output.
        toks2, _ = await _generate(engine, [1, 2, 3, 4, 5], max_tokens=6)
        assert toks1 == toks2
        await engine.close()

    asyncio.run(main())


def test_engine_concurrent_requests_match_serial():
    async def main():
        engine = TpuEngine(EngineConfig(**CFG))
        prompts = [[1, 2, 3], [9, 8, 7, 6], [5, 5, 5, 5, 5], [11, 12]]
        serial = []
        for p in prompts:
            toks, _ = await _generate(engine, p, max_tokens=5)
            serial.append(toks)
        await engine.close()

        engine2 = TpuEngine(EngineConfig(**CFG))
        results = await asyncio.gather(
            *[_generate(engine2, p, max_tokens=5) for p in prompts]
        )
        concurrent = [r[0] for r in results]
        assert concurrent == serial
        await engine2.close()

    asyncio.run(main())


def test_engine_prefix_cache_hit_rate():
    async def main():
        engine = TpuEngine(EngineConfig(**CFG))
        prompt = list(range(1, 17))  # 4 full blocks
        await _generate(engine, prompt, max_tokens=2)
        assert engine.kv.hit_rate == 0.0
        await _generate(engine, prompt + [99], max_tokens=2)
        m = engine.metrics()
        assert m.gpu_prefix_cache_hit_rate > 0.4  # 4 of the 2nd req's blocks hit
        await engine.close()

    asyncio.run(main())


def test_engine_emits_kv_events():
    async def main():
        events = []
        engine = TpuEngine(EngineConfig(**CFG), event_callback=events.append)
        prompt = list(range(1, 10))  # 2 full blocks of 4 + 1 tail
        await _generate(engine, prompt, max_tokens=3)
        stored = [e for e in events if isinstance(e.data, KvCacheStoreData)]
        assert len(stored) >= 2
        # Chained hashes must match tokens-module hashing of the prompt.
        expected = hash_token_blocks(prompt, 4)
        got = [b.block_hash for e in stored for b in e.data.blocks]
        assert got[:2] == [tb.sequence_hash for tb in expected[:2]]
        # Parent chain: first block's parent is None, second's is first's hash.
        assert stored[0].data.parent_hash is None
        assert stored[1].data.parent_hash == expected[0].sequence_hash
        await engine.close()

    asyncio.run(main())


def test_engine_eviction_emits_removed():
    async def main():
        events = []
        cfg = dict(CFG)
        cfg["num_blocks"] = 8  # tiny pool to force eviction
        engine = TpuEngine(EngineConfig(**cfg), event_callback=events.append)
        for base in range(0, 60, 20):
            await _generate(engine, [base + i for i in range(12)], max_tokens=2)
        removed = [e for e in events if isinstance(e.data, KvCacheRemoveData)]
        assert removed, "expected eviction events from the tiny pool"
        await engine.close()

    asyncio.run(main())


def test_engine_cancellation():
    async def main():
        engine = TpuEngine(EngineConfig(**CFG))
        ctx = Context(_req([1, 2, 3], max_tokens=10_000))
        stream = await engine.generate(ctx)
        got = 0
        async for _item in stream:
            got += 1
            if got == 3:
                ctx.stop_generating()
        assert 3 <= got < 100
        # Engine must have released the sequence's blocks.
        for _ in range(20):
            if engine.scheduler.num_running == 0:
                break
            await asyncio.sleep(0.05)
        assert engine.scheduler.num_running == 0
        assert engine.kv.active_blocks == 0
        await engine.close()

    asyncio.run(main())


def test_engine_rejects_oversize_prompt():
    async def main():
        engine = TpuEngine(EngineConfig(**CFG))
        with pytest.raises(ValueError):
            await engine.generate(Context(_req(list(range(300)))))
        await engine.close()

    asyncio.run(main())


def test_engine_stop_token():
    async def main():
        engine = TpuEngine(EngineConfig(**CFG))
        # Find what the model generates, then stop on its 3rd token.
        toks, _ = await _generate(engine, [1, 2, 3], max_tokens=6)
        stop_tok = toks[2]
        pre = PreprocessedRequest(
            token_ids=[1, 2, 3],
            stop_conditions=StopConditions(
                max_tokens=6, ignore_eos=True, stop_token_ids=[stop_tok]
            ),
        )
        stream = await engine.generate(Context(pre.to_dict()))
        out = await collect(stream)
        got = [t for item in out for t in item["token_ids"]]
        # Generation halts at the stop token's FIRST occurrence (the tiny
        # greedy model may repeat tokens), and the stop token is not emitted.
        assert got == toks[: toks.index(stop_tok)]
        assert out[-1]["finish_reason"] == "stop"
        await engine.close()

    asyncio.run(main())


def test_kv_manager_reuse_and_eviction_order():
    events = []
    kv = KvBlockManager(4, 2, event_callback=events.append)
    blocks = hash_token_blocks([1, 2, 3, 4], 2)
    alloc = kv.allocate_sequence(blocks, 2)
    assert alloc is not None
    ids, cached = alloc
    assert cached == 0
    for bid, tb in zip(ids, blocks):
        kv.seal_block(bid, tb)
    kv.free_sequence(ids)
    assert kv.free_blocks == 4

    # Same prompt: full prefix hit, revived from the reuse pool.
    alloc2 = kv.allocate_sequence(blocks, 2)
    ids2, cached2 = alloc2
    assert ids2 == ids and cached2 == 4
    kv.free_sequence(ids2)

    # Exhaust the pool → reusable blocks evicted → Removed events.
    big = hash_token_blocks(list(range(10, 18)), 2)
    alloc3 = kv.allocate_sequence(big, 4)
    assert alloc3 is not None
    removed = [e for e in events if isinstance(e.data, KvCacheRemoveData)]
    assert removed


def test_kv_manager_shared_refcount():
    kv = KvBlockManager(8, 2)
    blocks = hash_token_blocks([1, 2, 3, 4], 2)
    ids1, _ = kv.allocate_sequence(blocks, 2)
    for bid, tb in zip(ids1, blocks):
        kv.seal_block(bid, tb)
    ids2, cached = kv.allocate_sequence(blocks, 3)
    assert ids2[:2] == ids1 and cached == 4
    kv.free_sequence(ids1)
    assert kv.active_blocks == 3  # still referenced by seq 2
    kv.free_sequence(ids2)
    assert kv.active_blocks == 0


def test_engine_generate_after_close_raises():
    async def main():
        engine = TpuEngine(EngineConfig(**CFG))
        await _generate(engine, [1, 2, 3], max_tokens=2)
        await engine.close()
        with pytest.raises(RuntimeError, match="closed"):
            await engine.generate(Context(_req([1, 2, 3])))

    asyncio.run(main())


def test_engine_preemption_respects_max_tokens():
    """A preempted sequence must not restart its output budget: usage and
    stop checks count generated tokens across preemptions (ADVICE r1)."""

    async def main():
        cfg = dict(CFG)
        cfg.update(num_blocks=6, max_batch=2, max_model_len=64)
        engine = TpuEngine(EngineConfig(**cfg))
        prompts = [[i + 1, i + 2, i + 3] for i in (0, 10, 20)]
        results = await asyncio.gather(
            *[_generate(engine, p, max_tokens=12) for p in prompts]
        )
        assert engine.scheduler.preempted > 0, "test needs pool pressure"
        for toks, final in results:
            assert len(toks) <= 12
            assert final["usage"]["completion_tokens"] == len(toks)
            assert final["usage"]["prompt_tokens"] == 3
        await engine.close()

    asyncio.run(main())


def test_scheduler_never_preempts_already_scheduled_rows():
    """ADVICE r2 (high): block-exhaustion preemption must not victimize a
    sequence already planned into this step — its freed blocks (block_ids=[])
    would leave a stale item that crashes _build_ragged and fails every
    in-flight request.  With running=[A(slot ok), B(needs a block)] and the
    pool dry, B must self-preempt, never preempt A."""
    from dynamo_tpu.engine.scheduler import Scheduler, SequenceState
    from dynamo_tpu.tokens import TokenBlockSequence

    cfg = EngineConfig(
        model="debug-tiny",
        block_size=4,
        num_blocks=3,
        max_batch=4,
        max_model_len=64,
        prefill_chunk=32,
        dtype="float32",
    )
    kv = KvBlockManager(3, 4)
    sched = Scheduler(cfg, kv)

    def mk(rid, n_blocks, num_computed):
        seq = SequenceState(
            request_id=rid,
            prompt=[1, 2, 3, 4],
            block_seq=TokenBlockSequence(block_size=4),
            num_computed=num_computed,
        )
        seq.output = [42]  # decoding: one sampled token pending
        seq.block_ids = [kv.allocate_block() for _ in range(n_blocks)]
        assert all(b is not None for b in seq.block_ids)
        return seq

    a = mk("a", 2, 4)  # slot for position 4 already allocated
    b = mk("b", 1, 4)  # position 4 needs a 2nd block; pool is dry
    sched.running = [a, b]
    assert kv.free_blocks == 0

    plan = sched.schedule()
    assert plan is not None
    for seq, start, n in plan.items:
        assert seq in sched.running
        assert seq.block_ids, f"{seq.request_id} scheduled with freed blocks"
        assert len(seq.block_ids) * cfg.block_size >= start + n
    assert [s.request_id for s, _, _ in plan.items] == ["a"]
    assert b in sched.waiting and sched.preempted == 1


def test_scheduler_session_plan_whatever_waits():
    """A plan with a decode row runs as a fused session whether the
    waiting queue is empty, blocked (slots full: at oversubscription it is
    never empty, and gating the fused path on it collapsed throughput,
    conc 32 below conc 16) or admissible (the session hosts the newcomer's
    prompt itself)."""
    from dynamo_tpu.engine.scheduler import Scheduler, SequenceState
    from dynamo_tpu.tokens import TokenBlockSequence

    cfg = EngineConfig(
        model="debug-tiny",
        block_size=4,
        num_blocks=64,
        max_batch=2,
        max_model_len=64,
        prefill_chunk=32,
        dtype="float32",
    )
    kv = KvBlockManager(64, 4)
    sched = Scheduler(cfg, kv)

    def mk(rid):
        seq = SequenceState(
            request_id=rid,
            prompt=[1, 2, 3, 4],
            block_seq=TokenBlockSequence(block_size=4),
            num_computed=4,
        )
        seq.output = [42]
        seq.block_ids = [kv.allocate_block(), kv.allocate_block()]
        return seq

    sched.running = [mk("a"), mk("b")]  # both slots taken, both decoding
    waiter = SequenceState(
        request_id="w",
        prompt=[9, 9, 9],
        block_seq=TokenBlockSequence(block_size=4),
    )
    sched.add(waiter)

    plan = sched.schedule()
    assert plan is not None
    assert plan.session, "blocked waiting must not break the fused path"
    assert not sched.admission_ready()

    # A slot frees up → the newcomer's prompt chunk is in the plan, beside
    # the decode row: still a session's plan.
    sched.remove(sched.running[0])
    assert sched.admission_ready()
    plan2 = sched.schedule()
    assert sorted(n for _, _, n in plan2.items) == [1, 3]
    assert plan2.session
    assert waiter in sched.running

    # Prompts only (the last decode row gone): one unified step.
    sched.remove(sched.running[0])
    plan3 = sched.schedule()
    assert [n for _, _, n in plan3.items] == [3] and not plan3.session


def test_engine_fused_decode_engages_at_oversubscription():
    """End-to-end: with 2 slots and 4 concurrent requests the fused decode
    pipeline must still dispatch (round 3 fell back to one unified step per
    token whenever anything waited), and outputs must match serial."""

    async def main():
        cfg = dict(CFG)
        cfg.update(max_batch=2, decode_steps=4, pipeline_depth=2)
        prompts = [[1, 2, 3], [9, 8, 7, 6], [5, 5, 5, 5, 5], [11, 12]]
        engine = TpuEngine(EngineConfig(**cfg))
        serial = []
        for p in prompts:
            toks, _ = await _generate(engine, p, max_tokens=24)
            serial.append(toks)
        await engine.close()

        engine2 = TpuEngine(EngineConfig(**cfg))
        results = await asyncio.gather(
            *[_generate(engine2, p, max_tokens=24) for p in prompts]
        )
        assert [r[0] for r in results] == serial
        fused = [k for k, *_ in engine2.step_trace if k == "decode_dispatch"]
        assert fused, "fused decode never engaged under oversubscription"
        await engine2.close()

    asyncio.run(main())


def test_scheduler_decode_rows_do_not_consume_prefill_budget():
    """Review r4: with max_batch > prefill_chunk, a full decode batch must
    neither disable the fused path nor starve admission — decode rows ride the
    unified step's own capacity (max_step_tokens = prefill_chunk +
    max_batch), they don't spend the prompt-chunk budget."""
    from dynamo_tpu.engine.scheduler import Scheduler, SequenceState
    from dynamo_tpu.tokens import TokenBlockSequence

    cfg = EngineConfig(
        model="debug-tiny",
        block_size=4,
        num_blocks=256,
        max_batch=8,
        max_model_len=64,
        prefill_chunk=4,  # smaller than max_batch
        dtype="float32",
    )
    kv = KvBlockManager(256, 4)
    sched = Scheduler(cfg, kv)

    def mk(rid):
        seq = SequenceState(
            request_id=rid,
            prompt=[1, 2, 3, 4],
            block_seq=TokenBlockSequence(block_size=4),
            num_computed=4,
        )
        seq.output = [42]
        seq.block_ids = [kv.allocate_block(), kv.allocate_block()]
        return seq

    # 6 decoding rows (> prefill_chunk), 2 slots free, 1 waiting.
    sched.running = [mk(f"r{i}") for i in range(6)]
    waiter = SequenceState(
        request_id="w",
        prompt=[9, 9, 9],
        block_seq=TokenBlockSequence(block_size=4),
    )
    sched.add(waiter)

    plan = sched.schedule()
    # The newcomer must be admitted (slot + blocks free) with a prompt
    # chunk in the plan, alongside all 6 decode rows.
    assert waiter in sched.running
    kinds = sorted(n for _, _, n in plan.items)
    assert kinds == [1, 1, 1, 1, 1, 1, 3]
    assert plan.session

    # With all slots decoding and one waiting, the batch must stay fused.
    sched.waiting.clear()
    sched.running = [mk(f"s{i}") for i in range(8)]
    sched.add(waiter2 := SequenceState(
        request_id="w2",
        prompt=[7, 7, 7],
        block_seq=TokenBlockSequence(block_size=4),
    ))
    plan2 = sched.schedule()
    assert plan2.session
    assert waiter2 in sched.waiting


def test_engine_mixed_phase_burst_matches_serial():
    """A long prompt arriving beside a decoding row is hosted by the fused
    session — admitted in-loop or, when the scheduler admitted it first,
    picked up still prefilling — and both streams match serial execution
    exactly (where a prompt's chunks run is a scheduling change, never a
    numerics change)."""

    async def main():
        from dynamo_tpu.runtime.engine import Context

        cfg = dict(CFG)
        cfg.update(
            max_batch=4,
            prefill_chunk=8,
            decode_steps=4,
            pipeline_depth=2,
            max_model_len=256,
            num_blocks=256,
        )
        long_prompt = list(range(1, 97))  # 96 tokens → 12 chunks of 8
        short = [7, 8, 9]

        engine = TpuEngine(EngineConfig(**cfg))
        serial_a, _ = await _generate(engine, short, max_tokens=40)
        serial_b, _ = await _generate(engine, long_prompt, max_tokens=6)
        await engine.close()

        engine2 = TpuEngine(EngineConfig(**cfg))
        # Let A reach steady decode before B's prefill starts.
        stream_a = await engine2.generate(Context(_req(short, 40)))
        it = stream_a.__aiter__()
        first = await it.__anext__()
        toks_a = list(first["token_ids"])
        toks_b, _ = await _generate(engine2, long_prompt, max_tokens=6)
        async for item in it:
            toks_a.extend(item.get("token_ids", ()))
        assert toks_a == serial_a
        assert toks_b == serial_b
        pipe = engine2.dispatch_summary()["pipeline"]
        hosted = pipe["continuous_admissions"] + sum(pipe["prompt_step"].values())
        assert hosted >= 1, f"the session did not host the prompt: {pipe}"
        kinds = {k for k, *_ in engine2.step_trace}
        assert "decode_dispatch" in kinds, kinds
        assert not any("burst" in k for k in kinds), kinds
        await engine2.close()

    asyncio.run(main())


def test_engine_burst_headroom_fallback():
    """When KV headroom for a fused window is missing, a session dispatches
    nothing and the engine falls through to the unified step (every row
    still advances one token a step) instead of stalling decode rows."""

    async def main():
        cfg = dict(CFG)
        cfg.update(
            max_batch=2,
            prefill_chunk=8,
            decode_steps=64,  # a fused chunk wants 64 lookahead slots
            num_blocks=18,  # tiny pool: lookahead can't allocate
            max_model_len=64,
        )
        engine = TpuEngine(EngineConfig(**cfg))
        results = await asyncio.gather(
            _generate(engine, [1, 2, 3], max_tokens=10),
            _generate(engine, list(range(5, 37)), max_tokens=6),
        )
        assert [len(r[0]) for r in results] == [10, 6]
        # While both rows are resident no fused chunk fits the pool: those
        # sessions drain at once for want of KV (``rebuilds``) and each is
        # followed by a unified step that fetches its rows' tokens.
        pipe = engine.dispatch_summary()["pipeline"]
        assert pipe["rebuilds"] >= 1, pipe
        kinds = [k for k, *_ in engine.step_trace]
        assert kinds.count("unified_fetch") > pipe["rebuilds"], (kinds, pipe)
        assert not any("burst" in k for k in kinds), kinds
        await engine.close()

    asyncio.run(main())


def test_cancel_while_token_fetch_in_flight():
    """A request cancelled while its sampled token is still in flight
    device→host (parked on awaiting_fetch) must terminate cleanly: the
    harvest skips the finished row, the flag clears, blocks free, and the
    engine keeps serving others."""

    async def main():
        from dynamo_tpu.runtime.engine import Context, collect

        cfg = dict(CFG)
        cfg.update(max_batch=2, decode_steps=4, pipeline_depth=2)
        engine = TpuEngine(EngineConfig(**cfg))

        ctx = Context(_req([1, 2, 3], max_tokens=10_000))
        stream = await engine.generate(ctx)
        it = stream.__aiter__()
        await it.__anext__()  # first tokens flowing
        # Cancel at an arbitrary moment relative to in-flight fetches.
        ctx.stop_generating()
        async for _ in it:
            pass

        # Engine fully releases the sequence despite the in-flight fetch.
        for _ in range(50):
            if (
                engine.scheduler.num_running == 0
                and engine.kv.active_blocks == 0
                and not engine._pending_fetches
            ):
                break
            await asyncio.sleep(0.05)
        assert engine.scheduler.num_running == 0
        assert engine.kv.active_blocks == 0

        # And a fresh request still serves normally afterwards.
        toks, final = await _generate(engine, [5, 6, 7], max_tokens=5)
        assert len(toks) == 5 and final["finish_reason"] == "length"
        assert all(
            not getattr(s, "awaiting_fetch", False)
            for s in engine.scheduler.running + list(engine.scheduler.waiting)
        )
        await engine.close()

    asyncio.run(main())


def test_warmup_compiles_side_by_side_what_the_walk_calls(tmp_path):
    """Warm-up is ONE path for every family: with a compile cache directory
    (and no mesh) the programs are lowered from the operands the walk uses
    (``_warm_operands``) and compiled side by side first (the start's account
    says so: ``warm:lower``, ``warm:compile``); the walk then adds exactly the
    programs it adds without it, and a request runs on them."""

    async def main():
        plain = TpuEngine(EngineConfig(**CFG))
        engine = TpuEngine(EngineConfig(**CFG))
        try:
            counts = plain.warmup()
            steps, multi = engine._warm_operands()
            assert len(steps) == len(engine.reachable_token_buckets()) and multi is not None
            engine.compile_cache_dir = str(tmp_path)  # as on an accelerator backend
            assert engine.warmup() == counts
            for account, passes in ((engine.setup, 1), (plain.setup, 0)):
                rows = account.summary()["phases"]
                assert rows["warm:lower"]["count"] == rows["warm:compile"]["count"] == passes
                assert rows["warm:walk"]["count"] == 1 + passes
            assert await _generate(engine, [1, 2, 3, 4, 5]) == await _generate(plain, [1, 2, 3, 4, 5])
            assert engine.compile_counts() == counts
        finally:
            await plain.close()
            await engine.close()

    asyncio.run(main())
