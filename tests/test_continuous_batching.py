"""Continuous fused decode gates (ISSUE 11).

The load-bearing property is EXACT-STREAM EQUIVALENCE: in-loop
admission/retirement is a SCHEDULING change, never a token change — the
seeded sampler keys on (seed, output-index) over the committed prefix, so
a request under churn must get, byte for byte, the stream it gets when a
fresh engine serves the same seeded requests one at a time (the serial
reference: no session ever holds two rows, speculation off), at any
temperature, spec on or off.  Also covered: migration freeze
quiescence while the session keeps fusing for other rows (the
``_pipeline_members`` accounting under dynamic membership), the
zero-new-compiles gate (in-loop admission reaches no program warmup did
not), and the scheduler-side RowSlots/admit_continuous primitives.

Engine economics: every TpuEngine pays its XLA compiles (the CPU
persistent cache is deliberately off), so tests share one config and keep
engine counts minimal; seeded sampling makes control streams independent
of which engine computed them (same config/seed ⇒ same weights).
"""

import asyncio
import functools
import os

import pytest

from dynamo_tpu.engine import EngineConfig, KvBlockManager
from dynamo_tpu.engine.engine import TpuEngine
from dynamo_tpu.engine.scheduler import (
    RowSlots,
    Scheduler,
    SequenceState,
)
from dynamo_tpu.llm.protocols import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.runtime.engine import Context, collect
from dynamo_tpu.tokens import TokenBlockSequence

CFG = dict(
    model="debug-tiny",
    block_size=4,
    num_blocks=256,
    max_batch=4,
    max_model_len=256,
    prefill_chunk=16,
    dtype="float32",
    decode_steps=4,
    pipeline_depth=2,
)


def _req(tokens, max_tokens=8, seed=None, temperature=0.0):
    return PreprocessedRequest(
        token_ids=list(tokens),
        stop_conditions=StopConditions(max_tokens=max_tokens, ignore_eos=True),
        sampling_options=SamplingOptions(temperature=temperature, seed=seed),
    ).to_dict()


def _prompt(i, n=12):
    return [(i * 7919 + j * 104729) % 251 + 1 for j in range(n)]


async def _gen(engine, i, n, osl, temperature):
    """Request ``i`` with an ``n``-token prompt: its token stream."""
    req = _req(_prompt(i, n), max_tokens=osl, seed=i + 1, temperature=temperature)
    items = await collect(await engine.generate(Context(req)))
    return [t for it in items for t in it["token_ids"]]


async def _one(engine, i, osl, temperature, late=False):
    if late:
        # Land INSIDE a live fused session: the whole point of the churn
        # trace is admission while the pipeline is running.
        for _ in range(4000):
            if engine._pipeline_members:
                break
            await asyncio.sleep(0.002)
    return await _gen(engine, i, 12, osl, temperature)


def _late(i, n):
    return i >= (n + 1) // 2


def _osl(i, n):
    return (5 + 3 * (i % 3)) if _late(i, n) else (24 + 8 * (i % 2))


async def _churn(engine, temperature, n=8):
    """Staggered finishes + late arrivals: first wave keeps the session
    alive while short rows retire; back half arrives mid-session."""
    return await asyncio.gather(*[
        _one(engine, i, _osl(i, n), temperature, late=_late(i, n))
        for i in range(n)
    ])


@functools.lru_cache(maxsize=None)
def _serial(reqs, temperature, over=()):
    """The control: seeded requests ``(i, prompt tokens, max_tokens)``, one
    at a time, on a fresh engine without speculation (``over``: items of
    EngineConfig overrides).  Cached: the tests of this file run in one
    process and share a reference a temperature."""

    async def main():
        engine = TpuEngine(EngineConfig(**dict(CFG, **dict(over))))
        try:
            return [await _gen(engine, *r, temperature) for r in reqs]
        finally:
            await engine.close()

    return asyncio.run(main())


CHURN = tuple((i, 12, _osl(i, 8)) for i in range(8))


def _run_modes(temperature, spec=None):
    """The churn trace on one engine against the serial reference; returns
    (churn_streams, serial_streams, engine_stats)."""

    async def churn():
        cfg = dict(CFG)
        if spec is not None:
            cfg["spec_decode"] = spec
        engine = TpuEngine(EngineConfig(**cfg))
        try:
            streams = await _churn(engine, temperature)
            return streams, {
                "rebuilds": engine.pipeline_rebuilds,
                "admissions": engine.continuous_admissions,
                "retired": engine.continuous_retired,
                "prompt_steps": dict(engine.prompt_step_order),
            }
        finally:
            await engine.close()

    streams, stats = asyncio.run(churn())
    return streams, _serial(CHURN, temperature), stats


def test_churn_vs_serial_exact_streams_seeded_temp09():
    """Mid-pipeline retirement + admission at temperature 0.9 with seeds:
    byte-identical streams vs the serial reference, and the engine
    actually exercised the in-loop paths."""
    on, off, stats = _run_modes(temperature=0.9)
    assert on == off, "continuous batching changed seeded streams"
    assert stats["admissions"] >= 1, stats
    assert stats["retired"] >= 1, stats
    assert stats["rebuilds"] == 0, stats


@pytest.mark.parametrize("spec", [None, {"enable": True, "k": 4}],
                         ids=["spec-off", "spec-on"])
def test_exact_streams_seeded_rows_join_ahead_of_the_top_up(spec):
    """The byte-identity gate under the iteration's order of ISSUE 29:
    seeded temperature-0.8 rows join mid-session, their prompt steps are
    enqueued AHEAD of the iteration's top-up chunk (never behind one), and
    not a token differs from the serial reference, speculation on and
    off."""
    on, off, stats = _run_modes(temperature=0.8, spec=spec)
    assert on == off, "the prompt step's place in the iteration changed a stream"
    assert stats["admissions"] >= 1, stats
    assert stats["prompt_steps"]["ahead"] >= 1, stats  # rows share steps
    assert stats["prompt_steps"]["behind"] == 0, stats


def test_churn_vs_serial_exact_streams_greedy_spec_on():
    """Greedy + speculative decoding enabled: spec-session probes and
    in-loop membership changes compose without changing a single token."""
    on, off, stats = _run_modes(temperature=0.0, spec={"enable": True, "k": 4})
    assert on == off, "continuous batching changed greedy/spec streams"
    assert stats["retired"] >= 1, stats


# ------------------------------------------- a mixed plan runs as a session


def _lockstep(engine):
    """Hold every device op until the token fetches issued before it have
    landed: a round trip is then one step long whatever the machine (on
    the CPU the host enqueues a dozen steps in the time one fetch takes),
    so which step first sees a row's token is a count, not a race."""

    async def land_fetches():
        tasks = [entry[1] for entry in engine._pending_fetches]
        if tasks:
            await asyncio.wait(tasks)

    engine.pace_hook = land_fetches


async def _all_at_once(engine, reqs, temperature):
    """Prompts of unlike length into an idle engine: the short one is
    decoding while the long ones still have chunks to compute."""
    return await asyncio.gather(*[_gen(engine, *r, temperature) for r in reqs])


async def _after_kv_drain(engine, reqs, temperature):
    """Two rows decode in one session; then every free block is taken
    hostage, so the next fused chunk finds no KV headroom and the session
    drains.  Unified steps carry the rows until one cannot get a slot for
    a single token and ``schedule()`` preempts the younger; the hostages
    come back at that preemption (another tenant's blocks freed), so the
    same ``schedule()`` call re-admits the victim: the plan after the
    drain holds a decode row AND a prompt."""
    sched, kv = engine.scheduler, engine.kv
    hostages = []
    preempt = sched._preempt

    def preempt_then_release(seq):
        preempt(seq)
        kv.free_sequence(hostages)
        del hostages[:]

    sched._preempt = preempt_then_release
    tasks = [asyncio.create_task(_gen(engine, *r, temperature)) for r in reqs]
    for _ in range(4000):
        if len(engine._pipeline_members) == len(reqs) and all(
            s.num_output_tokens >= 2 for s in sched.running
        ):
            break
        await asyncio.sleep(0.002)
    assert len(engine._pipeline_members) == len(reqs), "no shared session"
    while (bid := kv.allocate_block()) is not None:
        hostages.append(bid)
    streams = [await t for t in tasks]
    assert sched.preempted >= 1 and not hostages, "the pool never ran dry"
    assert engine.pipeline_rebuilds >= 1
    return streams


async def _waiting_at_session_start(engine, reqs, temperature):
    """Four requests at once into an idle engine: one prompt chunk of
    budget a step leaves the later ones WAITING, and admissible, when the
    first decode row appears and the session starts."""
    streams = await _all_at_once(engine, reqs, temperature)
    assert engine.continuous_admissions >= 1, "nobody waited at session start"
    return streams


# name -> (driver, requests (i, prompt tokens, max_tokens), EngineConfig
# overrides, most sessions a run may take)
MIXED_PLANS = {
    "unlike-prompts-into-an-idle-engine": (
        _all_at_once, ((0, 5, 40), (1, 150, 6)), {}, 2,
    ),
    # Between the drain and the preemption each plan is tried as a session
    # that finds no headroom and gives up, once a token until a row needs a
    # block: a few, never one a token of the run.
    "after-a-kv-exhaustion-drain": (
        _after_kv_drain, ((0, 12, 56), (1, 12, 56)), {"num_blocks": 64}, 8,
    ),
    "waiting-queue-admissible-at-session-start": (
        _waiting_at_session_start,
        ((0, 5, 40), (1, 40, 8), (2, 40, 8), (3, 40, 8)), {}, 2,
    ),
}


@pytest.mark.parametrize(
    "temperature,spec",
    [(0.9, None), (0.0, {"enable": True, "k": 4})],
    ids=["seeded-temp09", "greedy-spec-on"],
)
@pytest.mark.parametrize("name", list(MIXED_PLANS))
def test_mixed_plan_runs_as_a_session(name, temperature, spec):
    """A plan that holds a decode row AND a prompt runs as ONE fused
    session that hosts the prompt (``rejoin_strays`` / ``admit``), however
    the plan came about; no other cadence exists.  Streams equal the serial
    reference, and nothing compiles after ``warmup()``."""
    driver, reqs, over, most_sessions = MIXED_PLANS[name]

    async def main():
        cfg = dict(CFG, **over)
        if spec is not None:
            cfg["spec_decode"] = spec
        engine = TpuEngine(EngineConfig(**cfg))
        try:
            compiled = await asyncio.to_thread(engine.warmup)
            _lockstep(engine)
            streams = await driver(engine, reqs, temperature)
            assert engine.compile_counts() == compiled
            return streams, engine.dispatch_summary()["pipeline"], {
                k for k, *_ in engine.step_trace
            }
        finally:
            await engine.close()

    streams, pipe, kinds = asyncio.run(main())
    assert list(streams) == _serial(reqs, temperature, tuple(over.items()))
    hosted = pipe["continuous_admissions"] + sum(pipe["prompt_step"].values())
    assert hosted >= 1, f"no session hosted a prompt: {pipe}"
    assert 1 <= pipe["sessions"] <= most_sessions, pipe
    assert "decode_dispatch" in kinds, kinds
    assert not any("burst" in k for k in kinds), kinds


def test_grammar_row_keeps_plans_on_unified_steps():
    """While a grammar-constrained row is resident no plan runs as a
    session (a fused chunk feeds sampled tokens forward on the device; the
    row's mask advances on the host): EVERY resident row then takes one
    token a round trip through unified steps, beside whatever prompt is
    prefilling.  In lockstep a row sits out the one step its token is in
    flight for, so the plain row ends its 4 tokens within a dozen steps,
    long before the long prompt's 25 chunks are through: by the count of
    steps, not by the clock."""
    from dynamo_tpu.llm.tenancy.grammar import GrammarCompiler
    from dynamo_tpu.llm.tokenizer import ByteTokenizer

    grammar = GrammarCompiler(ByteTokenizer()).compile(
        {"kind": "regex", "pattern": "[a-z]{48}"}
    ).to_dict()

    async def main():
        engine = TpuEngine(EngineConfig(**dict(CFG, prefill_chunk=8)))
        try:
            plain_alone = await _gen(engine, 1, 12, 4, 0.9)
            await _session(engine, live=False)
            engine.step_trace.clear()
            sessions0 = engine.pipeline_sessions
            _lockstep(engine)
            order = []

            async def constrained():
                req = PreprocessedRequest(
                    token_ids=_prompt(0),
                    stop_conditions=StopConditions(max_tokens=64),
                    sampling_options=SamplingOptions(temperature=0.9, seed=5),
                    grammar=grammar,
                ).to_dict()
                items = await collect(await engine.generate(Context(req)))
                order.append("grammar-done")
                return [t for it in items for t in it["token_ids"]]

            async def plain():
                toks = await _gen(engine, 1, 12, 4, 0.9)
                order.append("plain-done")
                return toks

            async def long_prompt():
                req = _req(_prompt(2, 200), max_tokens=4, seed=3)
                stream = await engine.generate(Context(req))
                async for _ in stream:
                    if "long-first-token" not in order:
                        order.append("long-first-token")

            g, p, _ = await asyncio.gather(constrained(), plain(), long_prompt())
            kinds = {k for k, *_ in engine.step_trace}
            return g, p, plain_alone, order, kinds, engine.pipeline_sessions - sessions0
        finally:
            await engine.close()

    g, p, plain_alone, order, kinds, sessions = asyncio.run(main())
    assert len(g) == 48 and all(ord("a") <= t <= ord("z") for t in g), g
    assert p == plain_alone
    assert order == ["plain-done", "long-first-token", "grammar-done"], order
    # The constrained row outlives both others: no plan of this run could
    # be a session.
    assert sessions == 0 and "decode_dispatch" not in kinds, (sessions, kinds)


def test_spec_engagement_bar_applies_whenever_a_session_is_the_alternative():
    """``_spec_propose`` holds drafts to the fused pipeline's bar exactly
    when the plan would otherwise run as a session — with a prompt in the
    plan too — and not where the alternative is a unified step (a grammar
    row resident).  The bar is out of reach here (margin 4: 16 tokens a
    round trip from one row with at most 4 drafts)."""
    from dynamo_tpu.llm.metrics import spec_metrics

    async def main():
        cfg = dict(CFG, spec_decode={"enable": True, "k": 4, "pipeline_margin": 4.0})
        engine = TpuEngine(EngineConfig(**cfg))
        try:
            sched, kv = engine.scheduler, engine.kv

            def mk(rid, prompt, output=()):
                seq = SequenceState(
                    request_id=rid,
                    prompt=list(prompt),
                    block_seq=TokenBlockSequence(block_size=cfg["block_size"]),
                )
                seq.output = list(output)
                return seq

            # A decoding row whose history repeats: the n-gram proposer
            # drafts its continuation.
            row = mk("row", [1, 2, 3] * 4, output=[1])
            row.num_computed = len(row.prompt)
            row.block_ids = [kv.allocate_block() for _ in range(5)]
            sched.running.append(row)
            sched.add(mk("prompt", range(10, 30)))

            plan = sched.schedule()
            assert sorted(n for _, _, n in plan.items) == [1, 16]
            assert plan.session  # a decode row and a prompt: a session's plan
            fallbacks = spec_metrics.fallback_total
            assert engine._spec_propose(plan) == {}
            assert spec_metrics.fallback_total == fallbacks + 1

            # The same rows beside a resident grammar row: a unified step
            # is all the plan can be, so any draft is worth its rows.
            constrained = mk("constrained", [4, 5, 6, 7], output=[8])
            constrained.num_computed = 4
            constrained.grammar = object()
            constrained.block_ids = [kv.allocate_block() for _ in range(2)]
            sched.running.append(constrained)
            plan = sched.schedule()
            assert not plan.session
            drafts = engine._spec_propose(plan)
            assert drafts.get("row"), drafts
            assert spec_metrics.fallback_total == fallbacks + 1
        finally:
            await engine.close()

    asyncio.run(main())


def test_freeze_quiesces_continuous_pipeline_and_resumes_exact():
    """Migration freeze during a continuous session: the frozen row is
    parked out at its write barrier (leaves ``_pipeline_members``, no
    pending fetch) while the session keeps fusing for the other member;
    unfreeze rejoins the live session and the stream completes
    token-identically to an unfrozen control."""

    async def control():
        engine = TpuEngine(EngineConfig(**CFG))
        try:
            a, b = await asyncio.gather(
                _one(engine, 1, 40, 0.9), _one(engine, 2, 48, 0.9)
            )
            return a, b
        finally:
            await engine.close()

    async def frozen_run():
        engine = TpuEngine(EngineConfig(**CFG))
        try:
            ctx_a = Context(_req(_prompt(1), max_tokens=40, seed=2,
                                 temperature=0.9))
            ctx_b = Context(_req(_prompt(2), max_tokens=48, seed=3,
                                 temperature=0.9))
            task_a = asyncio.create_task(
                collect(await engine.generate(ctx_a))
            )
            task_b = asyncio.create_task(
                collect(await engine.generate(ctx_b))
            )
            # Both decoding inside one fused session.
            for _ in range(4000):
                seq = engine.find_sequence(ctx_a.id)
                if (
                    len(engine._pipeline_members) == 2
                    and seq is not None
                    and seq.num_output_tokens >= 2
                ):
                    break
                await asyncio.sleep(0.002)
            seq = await engine.freeze_sequence(ctx_a.id)
            assert seq is not None, "freeze did not reach quiescence"
            assert seq.frozen
            # Quiescent: no in-flight fused chunk or fetch can advance it.
            assert ctx_a.id not in engine._pipeline_members
            assert not seq.awaiting_fetch
            # The session keeps fusing for B while A is frozen.
            d0 = sum(
                1 for k, *_ in engine.step_trace if k == "decode_dispatch"
            )
            for _ in range(2000):
                d1 = sum(
                    1
                    for k, *_ in engine.step_trace
                    if k == "decode_dispatch"
                )
                if d1 > d0:
                    break
                await asyncio.sleep(0.002)
            assert d1 > d0, "session stalled while one row was frozen"
            frozen_progress = seq.num_output_tokens
            engine.unfreeze_sequence(ctx_a.id)
            items_a, items_b = await asyncio.gather(task_a, task_b)
            toks_a = [t for it in items_a for t in it["token_ids"]]
            toks_b = [t for it in items_b for t in it["token_ids"]]
            assert len(toks_a) == 40 and frozen_progress < 40
            return toks_a, toks_b
        finally:
            await engine.close()

    ctrl_a, ctrl_b = asyncio.run(control())
    got_a, got_b = asyncio.run(frozen_run())
    assert got_a == ctrl_a
    assert got_b == ctrl_b


async def _session(engine, live: bool):
    """Wait until a fused session is live, or until the last one has torn
    down (a stream ends a few iterations before its session does, and a
    request sent in between is admitted into the OLD session)."""
    for _ in range(4000):
        if bool(engine._pipeline_members) == live:
            return
        await asyncio.sleep(0.002)
    raise AssertionError(f"no fused session {'began' if live else 'ended'}")


def _record_enqueues(engine):
    """Recording stand-ins for the two device programs and the chunk
    accept: ``log`` reads ``P`` (a unified step enqueued), ``C`` (a fused
    chunk enqueued) and ``|`` (a fused chunk accepted: the end of the
    iteration that awaited it), in the loop's own order."""
    log = []
    step, multi, accept = engine._step_fn, engine._multi_fn, engine._accept_chunk

    def step_fn(*a, **k):
        log.append("P")
        return step(*a, **k)

    def multi_fn(*a, **k):
        log.append("C")
        return multi(*a, **k)

    def accept_chunk(*a, **k):
        log.append("|")
        return accept(*a, **k)

    # compile_counts() (dispatch_summary's device block) reads the jit cache.
    step_fn._cache_size, multi_fn._cache_size = step._cache_size, multi._cache_size
    engine._step_fn, engine._multi_fn = step_fn, multi_fn
    engine._accept_chunk = accept_chunk
    return log


def _record_joins(engine, log, then=None):
    """``J`` in ``log`` for every call of the device-side join's program
    (made on first use: a ``cached_property`` of the mixin), ``then()`` before
    the call goes out."""
    join = engine._join_fn

    def joined(*a):
        log.append("J")
        if then is not None:
            then()
        return join(*a)

    joined._cache_size = join._cache_size
    engine.__dict__["_join_fn"] = joined


def test_prompt_step_is_enqueued_ahead_of_the_top_up_chunk():
    """ISSUE 29 (a).  An iteration with an admitted prompt AND room in the
    fused window enqueues the prompt step first (device queue ``C_k, P_k,
    C_k+1``, not ``C_k, P_k-1, C_k+1, P_k``); an iteration with nobody
    prefilling enqueues what it always did: one chunk, the window's top-up."""

    async def main():
        engine = TpuEngine(EngineConfig(**CFG))
        try:
            log = _record_enqueues(engine)
            # Alone: prefill outside the session, then pure decode.
            await _one(engine, 0, 33, 0.0)
            await _session(engine, live=False)
            alone = "".join(log)
            assert engine.prompt_step_order == {"ahead": 0, "behind": 0}
            del log[:]
            # A long row keeps the session alive; a 40-token prompt (three
            # chunks of 16) joins it mid-session.
            long_row = asyncio.create_task(_one(engine, 1, 64, 0.0))
            await _session(engine, live=True)
            req = _req(_prompt(2, n=40), max_tokens=6, seed=3)
            await collect(await engine.generate(Context(req)))
            await long_row
            return alone, "".join(log), dict(engine.prompt_step_order)
        finally:
            await engine.close()

    alone, joined, order = asyncio.run(main())
    # Pure decode, as before the change: the prompt's one step, the window
    # filled to pipeline_depth 2, then exactly one chunk an iteration until
    # no row can use another (8 chunks carry the 32 tokens after the first).
    assert alone == "P" + "CC|" + "C|" * 6 + "|", alone
    iterations = [it for it in joined.split("|") if "P" in it and "C" in it]
    assert iterations, joined
    for it in iterations:
        assert it.index("P") < it.index("C"), (it, joined)
    assert order["behind"] == 0 and order["ahead"] >= 3, order


def test_first_token_is_applied_when_it_lands_not_an_iteration_later():
    """ISSUE 29 (b).  While a fused chunk's fetch is held, the first-token
    fetch of a row admitted in that iteration completes: the loop applies
    it at once (first token out, ``first_harvest{at="landed"}``), BEFORE the
    held chunk is accepted, and the row then joins the chain and finishes
    with the stream it has when served alone."""
    import threading

    from dynamo_tpu.llm.metrics import engine_dispatch_metrics

    async def main():
        engine = TpuEngine(EngineConfig(**CFG))
        try:
            alone = await _one(engine, 2, 9, 0.7)
            await _session(engine, live=False)
            log = _record_enqueues(engine)
            release = threading.Event()
            fetch_outs, apply = engine._fetch_outs, engine._apply_harvest

            def apply_harvest(kind, *a):
                log.append(kind)
                return apply(kind, *a)

            engine._apply_harvest = apply_harvest
            long_row = asyncio.create_task(_one(engine, 1, 96, 0.7))
            await _session(engine, live=True)
            # Park the loop at its next device op, queue the newcomer, let
            # go: the iteration that admits its one-chunk prompt (or, when
            # the window was empty, the next) has its chunk's fetch held.
            gate = asyncio.Event()
            engine.pace_hook = gate.wait
            admitted_before = engine.continuous_admissions

            def held_fetch(out, need_lp):
                # A fused chunk's [steps, rows], popped in the iteration that
                # admitted the newcomer (admit() runs before the fetch thread
                # starts) or later: by count, not by the clock.
                if (
                    out.tokens.ndim == 2
                    and engine.continuous_admissions > admitted_before
                    and not release.is_set()
                ):
                    log.append("held")
                    release.wait(timeout=60)
                return fetch_outs(out, need_lp)

            engine._fetch_outs = held_fetch
            req = _req(_prompt(2), max_tokens=9, seed=3, temperature=0.7)
            stream = await engine.generate(Context(req))
            for _ in range(4000):
                if engine.scheduler.num_waiting:
                    break
                await asyncio.sleep(0.002)
            assert engine.scheduler.num_waiting == 1
            del log[:]
            engine.pace_hook = None
            gate.set()
            first = await asyncio.wait_for(stream.__anext__(), timeout=30)
            seen = list(log)
            landed = dict(engine.first_harvest)
            engine_dispatch_metrics.set_source(engine.dispatch_summary)
            text = engine_dispatch_metrics.render()
            release.set()
            rest = [it async for it in stream]
            await long_row
            toks = [t for it in [first] + rest for t in it["token_ids"]]
            return alone, toks, seen, landed, text
        finally:
            engine_dispatch_metrics.reset()
            release.set()
            await engine.close()

    alone, toks, seen, landed, text = asyncio.run(main())
    assert toks == alone
    held, first = seen.index("held"), seen.index("first")
    assert held < first, seen
    assert "|" not in seen[held:], seen  # the held chunk is not accepted yet
    assert landed["landed"] >= 1, landed
    assert 'dynamo_tpu_pipeline_first_harvest_total{at="landed"} ' in text
    assert 'dynamo_tpu_pipeline_first_harvest_total{at="iteration"} ' in text
    assert 'dynamo_tpu_pipeline_prompt_step_total{order="ahead"} ' in text
    assert 'dynamo_tpu_pipeline_prompt_step_total{order="behind"} 0' in text


# ------------------------------------------------ the device-side join (ISSUE 46)


def _join_req(i, how=None, n=12, osl=9, temperature=0.0, logprobs=None):
    """Request ``i`` as a newcomer to a live session.  ``how`` makes its FIRST
    token end it (``max1``; ``stop``: a tuple of stop token ids) or gives it
    a penalty (``penalty``: the break path's case)."""
    stops = how if isinstance(how, tuple) else ()
    return PreprocessedRequest(
        token_ids=_prompt(i, n),
        stop_conditions=StopConditions(
            max_tokens=1 if how == "max1" else osl, ignore_eos=True,
            stop_token_ids=list(stops),
        ),
        sampling_options=SamplingOptions(
            temperature=temperature, seed=i + 1, logprobs=logprobs,
            frequency_penalty=0.6 if how == "penalty" else None,
        ),
    ).to_dict()


def _stream_of(items):
    """What a client can tell apart: the tokens and each token's numbers."""
    return [
        (it["token_ids"], (it.get("logprobs") or {}).get("logprob")) for it in items
    ]


async def _alone_then_joined(engine, reqs, on_join=None, rows=1):
    """``reqs`` one at a time on the idle engine (each its own session's
    FIRST member: a host merge), then all at once beside a long row's live
    session in lockstep (``rows``: that many long rows decode in it).
    Returns (alone, joined, joins while alone, joins of the shared session,
    prompt steps of the shared session, long row's two streams)."""
    compiled = await asyncio.to_thread(engine.warmup)
    # The join's program, behind a chunk's carry and behind a merge's host
    # seed: both made in warm-up (the count is the process's: two a row
    # count that some engine of it has warmed).
    assert compiled["join"] >= 2 and compiled["join"] % 2 == 0, compiled
    _lockstep(engine)
    long_req = _req(_prompt(1), max_tokens=96, seed=2, temperature=0.7)
    long_alone = await collect(await engine.generate(Context(long_req)))
    alone = []
    for r in reqs:
        await _session(engine, live=False)
        alone.append(await collect(await engine.generate(Context(r))))
    await _session(engine, live=False)
    joins_alone = dict(engine.pipeline_joins)
    steps0 = sum(engine.prompt_step_order.values())
    long_row = asyncio.create_task(collect(await engine.generate(Context(long_req))))
    await _session(engine, live=True)
    more = [
        asyncio.create_task(collect(await engine.generate(Context(
            _req(_prompt(4 + k), max_tokens=96, seed=9 + k, temperature=0.7)))))
        for k in range(rows - 1)
    ]
    for _ in range(4000 if more else 0):
        running = engine.scheduler.running
        if len(running) == rows and all(q.num_output_tokens > 4 for q in running):
            break
        await asyncio.sleep(0.002)
    # Park the loop at its next device op until every newcomer is queued: one
    # admit() then takes them all, and their prompts share one step.
    gate = asyncio.Event()
    engine.pace_hook = gate.wait
    ctxs = [Context(r) for r in reqs]
    streams = [await engine.generate(c) for c in ctxs]
    for _ in range(4000):
        if engine.scheduler.num_waiting == len(reqs):
            break
        await asyncio.sleep(0.002)
    assert engine.scheduler.num_waiting == len(reqs)
    if on_join is not None:
        on_join(ctxs)
    _lockstep(engine)
    gate.set()
    joined = await asyncio.gather(*[collect(s) for s in streams])
    long_joined = await long_row
    await asyncio.gather(*more)
    joins = {k: v - joins_alone[k] for k, v in engine.pipeline_joins.items()}
    steps = sum(engine.prompt_step_order.values()) - steps0
    assert engine.compile_counts() == compiled, "a join compiled a program"
    return alone, list(joined), joins_alone, joins, steps, (long_alone, long_joined)


@pytest.mark.parametrize("logprobs", [None, 2], ids=["tokens", "logprobs"])
@pytest.mark.parametrize("temperature", [0.0, 0.8], ids=["greedy", "seeded-temp08"])
def test_device_join_gives_the_stream_the_row_gets_alone(temperature, logprobs):
    """A row whose last prompt chunk is enqueued beside a live chain joins it
    ON THE DEVICE (no chain break, no host merge) and gets, token for token
    and number for number, what it gets served alone; so does the row it
    joined.  Alone, its session starts with nothing in flight: the host
    merge."""

    async def main():
        engine = TpuEngine(EngineConfig(**CFG))
        try:
            req = _join_req(2, temperature=temperature, logprobs=logprobs)
            return await _alone_then_joined(engine, [req])
        finally:
            await engine.close()

    alone, joined, joins_alone, joins, _, long_row = asyncio.run(main())
    assert _stream_of(joined[0]) == _stream_of(alone[0])
    assert sum(len(it["token_ids"]) for it in joined[0]) == 9
    assert _stream_of(long_row[1]) == _stream_of(long_row[0])
    # Alone: every session's first member came through the merge.
    assert joins_alone == {"device": 0, "break": 2}, joins_alone
    # Beside the long row (its own session start is the one break).
    assert joins == {"device": 1, "break": 1}, joins


def test_two_rows_whose_prompts_end_in_one_step_both_join_on_the_device():
    async def main():
        engine = TpuEngine(EngineConfig(**CFG))
        try:
            reqs = [_join_req(i, n=5, temperature=0.8) for i in (2, 3)]
            return await _alone_then_joined(engine, reqs)
        finally:
            await engine.close()

    alone, joined, _, joins, steps, _ = asyncio.run(main())
    assert [_stream_of(j) for j in joined] == [_stream_of(a) for a in alone]
    assert steps == 1, steps  # ONE prompt step carried both prompts' ends
    assert joins == {"device": 2, "break": 1}, joins


def test_penalty_row_takes_the_break_path_and_still_matches():
    """What the device-side join cannot carry (a ``counts`` row built on the
    host) it leaves to the chain-break merge, by the row's own options."""

    async def main():
        engine = TpuEngine(EngineConfig(**CFG))
        try:
            req = _join_req(2, how="penalty", temperature=0.8)
            return await _alone_then_joined(engine, [req])
        finally:
            await engine.close()

    alone, joined, _, joins, _, long_row = asyncio.run(main())
    assert _stream_of(joined[0]) == _stream_of(alone[0])
    assert _stream_of(long_row[1]) == _stream_of(long_row[0])
    assert joins == {"device": 0, "break": 2}, joins


@pytest.mark.parametrize("how", ["max1", "stop", "cancelled"])
def test_row_ended_by_its_first_token_after_a_device_join(how):
    """The first token of a device-joined row ends it (``max_tokens`` 1, a
    stop token, a client gone) when the row already rides a chunk: it emits
    no token of that chunk, and its blocks go back only past the write
    barrier: every chunk enqueued while it was in the chain is accepted
    first."""

    async def main():
        engine = TpuEngine(EngineConfig(**CFG))
        try:
            log = _record_enqueues(engine)
            first = (await _gen(engine, 2, 12, 1, 0.0))[0]
            stop = (first,) if how == "stop" else None
            req = _join_req(2, how=stop or ("max1" if how == "max1" else None))
            remove = engine.scheduler.remove
            rid, ctxs = [], []

            def removed(seq):
                if rid and seq.request_id == rid[0]:
                    log.append("freed" if seq.block_ids else "gone")
                return remove(seq)

            def leave():
                if how == "cancelled" and ctxs:
                    ctxs[0].stop_generating()

            engine.scheduler.remove = removed
            _record_joins(engine, log, leave)

            def on_join(cs):
                ctxs.extend(cs)
                rid.append(cs[0].id)
                del log[:]

            out = await _alone_then_joined(engine, [req], on_join)
            return out, "".join(x if len(x) == 1 else f"<{x}>" for x in log)
        finally:
            await engine.close()

    (alone, joined, _, joins, _, long_row), log = asyncio.run(main())
    assert joins["device"] == 1, (joins, log)
    toks = [t for it in joined[0] for t in it["token_ids"]]
    want = [t for it in alone[0] for t in it["token_ids"]]
    # (A client that left may or may not have been sent its first token, by
    # whether the token landed before the sweep saw the client gone.)
    assert toks == want[: len(toks)] and len(toks) <= 1, (toks, want)
    assert how == "cancelled" or toks == want
    assert _stream_of(long_row[1]) == _stream_of(long_row[0])
    # P J: the join goes out right behind the row's prompt step, and this
    # iteration's top-up carries the row (where the window was full an accept
    # comes first, and the sweep behind it may find the row ended already:
    # then no chunk ever carries it)...
    j = log.index("J")
    assert log[j - 1] == "P" and "C" in log[j:], log
    # ...and its blocks were freed once, after as many accepts as chunks had
    # been enqueued up to that one: nothing that could write them in flight.
    before, _, _ = log.partition("<freed>")
    assert "<freed>" in log and "<gone>" not in log, log
    rode_at_once = "|" not in log[j : log.index("C", j)]
    ridden = log[:j].count("C") + rode_at_once
    assert before.count("|") >= ridden, log


def test_row_whose_prompt_ends_in_a_merges_own_iteration_joins_against_the_host_seed():
    """Behind a merge no chunk has gone out yet: the chain's state is the
    host's seed, and a row whose last prompt chunk rides that iteration's
    step joins it there (the join's host-seeded variant, warmed too) instead
    of breaking the chain the merge just seeded.  Two prompts into an idle
    engine in lockstep: the short one's first token starts the session (the
    merge), whose first iteration carries the long one's third and last
    chunk."""
    reqs = ((0, 5, 24), (1, 35, 8))

    async def main():
        engine = TpuEngine(EngineConfig(**CFG))
        try:
            compiled = await asyncio.to_thread(engine.warmup)
            _lockstep(engine)
            streams = await _all_at_once(engine, reqs, 0.9)
            assert engine.compile_counts() == compiled
            return streams, dict(engine.pipeline_joins), engine.pipeline_sessions
        finally:
            await engine.close()

    streams, joins, sessions = asyncio.run(main())
    assert list(streams) == _serial(reqs, 0.9)
    assert sessions == 1 and joins == {"device": 1, "break": 1}, (sessions, joins)


def test_row_with_prompt_work_queued_deep_behind_it_takes_the_break_path():
    """With prompt work queued more than a step deep the prompt steps are
    the bottleneck, and a break serves them better than a join (its drain
    gives them iterations without a chunk; a joiner keeps chunks of a row or
    two going): decided by what the loop sees behind the row's last chunk.
    A short prompt and a seven-chunk prompt arrive together: the short one
    finds 89 tokens queued behind its step and waits for its token and a
    break; the long one finds nothing behind its last chunk and joins on the
    device."""

    async def main():
        # (No prefix cache: the long prompt computes its chunks both times.)
        engine = TpuEngine(EngineConfig(**dict(CFG, enable_prefix_caching=False)))
        try:
            log = _record_enqueues(engine)
            _record_joins(engine, log)
            reqs = [_join_req(2, n=5), _join_req(3, n=100)]
            out = await _alone_then_joined(engine, reqs, lambda _: log.clear())
            return out, "".join(log)
        finally:
            await engine.close()

    (alone, joined, _, joins, _, long_row), log = asyncio.run(main())
    assert [_stream_of(j) for j in joined] == [_stream_of(a) for a in alone]
    assert _stream_of(long_row[1]) == _stream_of(long_row[0])
    assert joins == {"device": 1, "break": 2} and log.count("J") == 1, (joins, log)
    j = log.index("J")
    assert log[j - 1 : j + 2] == "PJC" and "P" not in log[j:], log


def test_device_join_yields_one_chunk_slot_to_the_prompt_steps_that_are_queued():
    """With more than a step of prompt work queued behind a join (and no
    more steps than rows in the chain: else the break path) the slot the
    join owes is the prompt steps': ONE iteration that enqueues a step and no
    chunk, as a break's drain did, and never the joined row's own first
    chunk.  Three rows decode; a five-token prompt and one of 55 arrive
    together, and the first step leaves 44 tokens of the long one queued."""

    async def main():
        cfg = dict(CFG, enable_prefix_caching=False, max_batch=8)
        engine = TpuEngine(EngineConfig(**cfg))
        try:
            log = _record_enqueues(engine)
            _record_joins(engine, log)
            reqs = [_join_req(2, n=5), _join_req(3, n=55)]
            out = await _alone_then_joined(engine, reqs, lambda _: log.clear(), rows=3)
            return out, "".join(log)
        finally:
            await engine.close()

    (alone, joined, _, joins, _, long_row), log = asyncio.run(main())
    assert [_stream_of(j) for j in joined] == [_stream_of(a) for a in alone]
    assert _stream_of(long_row[1]) == _stream_of(long_row[0])
    # (The session's first member at a break; the two rows beside it and the
    # two newcomers on the device.)
    assert joins == {"device": 4, "break": 1} and log.count("J") == 2, (joins, log)
    first, second = log.index("J"), log.rindex("J")
    assert log[first - 1] == log[second - 1] == "P", log
    between = log[first:second]
    assert between.count("|P|") == 1, log  # the slot, given once...
    # ...and not the chunk the first joiner was waiting for (the window was
    # full when it joined): that one goes out first.
    assert "C" in between[: between.index("|P|")], log


def test_device_join_gives_the_chain_its_turn_where_no_prompt_backlog_waits():
    """Without a prompt backlog the slot a join owes is the chain's: the
    prompt step of the NEXT iteration waits one chunk (a re-seeded chain's
    first two chunks went out back to back too), once.  A five-token prompt
    and one of 21 arrive together: the first step takes the short one whole
    and 11 tokens of the other, whose last ten then wait for a chunk."""

    async def main():
        engine = TpuEngine(EngineConfig(**dict(CFG, enable_prefix_caching=False)))
        try:
            log = _record_enqueues(engine)
            _record_joins(engine, log)
            reqs = [_join_req(2, n=5), _join_req(3, n=21)]
            out = await _alone_then_joined(engine, reqs, lambda _: log.clear())
            return out, "".join(log)
        finally:
            await engine.close()

    (alone, joined, _, joins, steps, _), log = asyncio.run(main())
    assert [_stream_of(j) for j in joined] == [_stream_of(a) for a in alone]
    assert joins["device"] == 2 and steps == 2, (joins, steps, log)
    first, second = log.index("J"), log.rindex("J")
    between = log[first + 1 : second]
    # One iteration with a chunk and no prompt step, then the step, its join
    # and its chunk at once; nothing is withheld a second time.
    assert "|C|P" in between and between.count("P") == 1, log
    assert log[second - 1 : second + 2] == "PJC", log


def test_chunk_that_carries_a_joined_row_is_never_accepted_before_its_first_token():
    """The prompt step is ahead of the chunk on the device, so its fetch is
    complete when the chunk's is; where the HOST sees them the other way
    round (the first-token fetch held on its thread), the loop waits on it
    and does not reorder."""
    import threading

    async def main():
        engine = TpuEngine(EngineConfig(**CFG))
        try:
            release = threading.Event()
            fetch_first, accept = engine._fetch_first, engine._accept_chunk
            seen = []

            def held_first(*a):
                if seen == ["armed"]:
                    seen.append("held")
                    release.wait(timeout=60)
                return fetch_first(*a)

            def accept_chunk(members, pos0, *a):
                riders = [
                    s.request_id for i, s in enumerate(members)
                    if s is not None and s.riding_chain and pos0[i] >= 0
                ]
                if "held" in seen:
                    seen.append(("accept", riders, release.is_set()))
                return accept(members, pos0, *a)

            engine._fetch_first, engine._accept_chunk = held_first, accept_chunk

            def on_join(_):
                seen.append("armed")
                # Long after the chunk behind the prompt step has come home.
                threading.Timer(1.0, release.set).start()

            out = await _alone_then_joined(engine, [_join_req(2)], on_join)
            return out, seen
        finally:
            release.set()
            await engine.close()

    (alone, joined, _, joins, _, _), seen = asyncio.run(main())
    assert _stream_of(joined[0]) == _stream_of(alone[0])
    assert joins["device"] == 1 and "held" in seen, (joins, seen)
    accepts = [e for e in seen if isinstance(e, tuple)]
    assert accepts and not any(riders for _, riders, _ in accepts), seen


def test_zero_new_compiles_in_loop_admission():
    """Warmup covers every program the continuous pipeline can reach: a
    churn trace with in-loop admission/retirement (chain-break merges,
    interleaved prefill steps) must not add a single jit cache entry."""

    async def main():
        engine = TpuEngine(EngineConfig(**CFG))
        try:
            baseline = await asyncio.to_thread(engine.warmup)
            streams = await _churn(engine, temperature=0.9)
            assert engine.continuous_admissions >= 1
            after = engine.compile_counts()
            assert after == baseline, (
                f"in-loop admission compiled new programs: "
                f"{baseline} -> {after}"
            )
            assert all(streams)
        finally:
            await engine.close()

    asyncio.run(main())


def test_dispatch_metrics_exported():
    """engine.dispatch_summary → engine_dispatch_metrics: the pipeline
    health the planner and the benchmark read off /metrics — per-kind
    counts/percentiles plus the continuous-batching session counters and
    host-gap fraction."""
    from dynamo_tpu.llm.metrics import engine_dispatch_metrics

    async def main():
        engine = TpuEngine(EngineConfig(**CFG))
        try:
            engine_dispatch_metrics.set_source(engine.dispatch_summary)
            await _churn(engine, temperature=0.0, n=4)
            s = engine.dispatch_summary()
            assert s["pipeline"]["sessions"] >= 1
            assert 0.0 <= s["pipeline"]["host_gap_frac"] <= 1.0
            # tools/ci.sh runs this file under DYN_DECODE_KERNEL=pallas_fused:
            # the kernel that was asked for is the one that served the churn.
            asked = os.environ.get("DYN_DECODE_KERNEL", "").strip()
            if asked and asked != "auto":
                assert s["decode_kernel"] == asked, s["decode_kernel"]
            assert "decode_dispatch" in s["kinds"]
            text = engine_dispatch_metrics.render()
            assert (
                'dynamo_tpu_engine_dispatch_window_dispatches'
                '{kind="decode_dispatch"}' in text
            )
            assert "dynamo_tpu_engine_dispatch_host_gap_frac" in text
            assert (
                "dynamo_tpu_engine_dispatch_pipeline_sessions_total" in text
            )
            # What the fused kernel's dots take rides beside its name, and
            # only where that kernel served.
            operands = s["decode_kernel_operands"]
            if s["decode_kernel"] == "pallas_fused":
                assert operands in ("bf16", "float32"), operands
                assert (
                    f'decode_kernel_operands_info{{operands="{operands}"}} 1'
                    in text
                )
            else:
                assert operands is None
                assert "decode_kernel_operands_info" not in text
        finally:
            engine_dispatch_metrics.reset()
            await engine.close()

    asyncio.run(main())


def test_rowslots_free_list():
    """RowSlots: lowest-index-first assignment, pending (barrier) state
    between retire and free, capacity accounting."""
    slots = RowSlots(3)

    def mk(rid):
        return SequenceState(
            request_id=rid,
            prompt=[1, 2, 3],
            block_seq=TokenBlockSequence(block_size=4),
        )

    a, b = mk("a"), mk("b")
    assert slots.assign(a) == 0
    assert slots.assign(b) == 1
    assert slots.num_active == 2
    assert slots.capacity_left == 1
    slots.retire(0)
    assert slots.rows[0] is None
    assert slots.num_active == 1
    # Pending counts as capacity (reuse only happens after the barrier,
    # at a chain-break merge) but is NOT assignable yet.
    assert slots.capacity_left == 2
    c = mk("c")
    assert slots.assign(c) == 2  # the free slot, not the pending one
    slots.free(0)
    d = mk("d")
    assert slots.assign(d) == 0  # barrier passed: slot 0 reusable
    assert slots.num_active == 3
    assert slots.capacity_left == 0
    assert [i for i, _ in slots.active()] == [0, 1, 2]


def test_admit_continuous_compatibility_and_order():
    """Scheduler.admit_continuous: admits compatible waiting heads in WFQ
    order with full block accounting, stops at an incompatible (grammar)
    or frozen head — the pipeline drains for those."""
    cfg = EngineConfig(**{k: v for k, v in CFG.items()})
    kv = KvBlockManager(cfg.num_blocks, cfg.block_size)
    sched = Scheduler(cfg, kv)

    def mk(rid, grammar=None, frozen=False):
        seq = SequenceState(
            request_id=rid,
            prompt=[1, 2, 3, 4],
            block_seq=TokenBlockSequence(block_size=cfg.block_size),
        )
        seq.grammar = grammar
        seq.frozen = frozen
        return seq

    s1, s2 = mk("s1"), mk("s2")
    sched.add(s1)
    sched.add(s2)
    assert sched.waiting_head_compatible()
    admitted = sched.admit_continuous(8)
    assert admitted == [s1, s2]
    assert all(s in sched.running for s in admitted)
    assert all(s.block_ids for s in admitted)
    assert len(sched.admission_waits) == 2

    # A grammar-constrained head stops in-loop admission cold (it cannot
    # ride fused chunks), even with compatible requests behind it.
    g = mk("g", grammar=object())
    tail = mk("tail")
    sched.add(g)
    sched.add(tail)
    assert not sched.waiting_head_compatible()
    assert sched.admit_continuous(8) == []
    assert g in sched.waiting and tail in sched.waiting

    # Frozen head: blocked, not admitted (mid-migration).
    sched.waiting.clear()
    f = mk("f", frozen=True)
    sched.add(f)
    assert not sched.waiting_head_compatible()
    assert sched.admit_continuous(8) == []
