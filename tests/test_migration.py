"""Live sequence migration tests (llm/migration; ISSUE 5).

The load-bearing property is EXACT-STREAM EQUIVALENCE: a seeded request
migrated mid-decode (once, or twice) produces a byte-identical token stream
vs the unmigrated control run, at temperature > 0 — the seeded sampler keys
on (seed, output-index) and both survive the handoff, so migration is
unobservable to the client except as latency.  Also covered: two-phase
rollback (source stays authoritative), drain-via-migrate in O(transfer)
rather than O(sequence) driven over the remote migrate_out endpoint,
client-side crash resume under drop_mid_stream, the KV-transfer rollback
bugfix, the hub-native supervisor, and the prefill→decode cli role flip.

Engine economics: every TpuEngine pays its XLA compiles (the CPU persistent
cache is deliberately off — engine/xla_cache.py), so the wire tests share
one worker fleet per test and compute control streams on an engine that is
already warm; seeded sampling makes controls independent of which engine
(same config/seed ⇒ same weights) and of prefix-cache state.
"""

import asyncio
from types import SimpleNamespace

import pytest

from dynamo_tpu.engine import EngineConfig
from dynamo_tpu.engine.engine import TpuEngine
from dynamo_tpu.engine.scheduler import SequenceState
from dynamo_tpu.llm.metrics import migration_metrics
from dynamo_tpu.llm.migration import (
    MigratableWorker,
    SequenceSnapshot,
    pick_migration_target,
)
from dynamo_tpu.llm.protocols import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.runtime import DistributedRuntime, HubServer
from dynamo_tpu.runtime.engine import Context, collect

pytestmark = pytest.mark.migration

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


def _req(tokens, max_tokens=16, seed=1234, temperature=0.9):
    return PreprocessedRequest(
        token_ids=list(tokens),
        stop_conditions=StopConditions(max_tokens=max_tokens, ignore_eos=True),
        sampling_options=SamplingOptions(temperature=temperature, seed=seed),
    ).to_dict()


def _tokens(items):
    return [t for i in items for t in i.get("token_ids", [])]


async def _control_tokens_on(engine, req):
    """The unmigrated reference stream for ``req``, computed on an engine
    that is already warm.  Seeded sampling makes this independent of the
    engine instance and of any prefix-cache state it holds."""
    return _tokens(await collect(await engine.generate(Context(dict(req)))))


async def _prewarm(engine):
    """Compile the decode programs plus the KV gather/inject path up front
    so the migration tests' timing measures transfer, not first-call XLA
    compiles (a finished sequence correctly aborts its migration, and cold
    compiles on this throttled CPU would otherwise land inside the
    stream/copy race and serialize against live decode — measured slower
    AND flakier than paying them sequentially here)."""
    toks = list(range(200, 216))  # 4 full blocks, disjoint from test prompts
    await collect(
        await engine.generate(Context(_req(toks, max_tokens=4, seed=1)))
    )
    payload = await engine.export_prompt_blocks(toks)
    assert payload is not None
    await engine.inject_blocks(toks, payload)


async def _spawn_worker(hub, ns, comp, cfg=None):
    """One migration-capable worker over the service plane: its own
    runtime/service server, gen + migrate_in + migrate_out endpoints (the
    same wiring cli worker mode does)."""
    rt = await DistributedRuntime.connect(hub.address)
    engine = TpuEngine(EngineConfig(**(cfg or CFG)))
    await _prewarm(engine)
    mig = MigratableWorker(engine, chunk_blocks=4)
    component = rt.namespace(ns).component(comp)
    gen_ep = component.endpoint("gen")
    in_ep = component.endpoint("migrate_in")
    out_ep = component.endpoint("migrate_out")
    server = await rt.service_server()
    await in_ep.serve_endpoint(mig.migrate_in_handler)
    await out_ep.serve_endpoint(mig.migrate_out_handler)
    metadata = {
        "migrate": {
            "import_path": in_ep.path,
            "out_path": out_ep.path,
            "generate_path": gen_ep.path,
        }
    }
    await gen_ep.serve_endpoint(mig, metadata=metadata)
    return SimpleNamespace(
        rt=rt,
        engine=engine,
        mig=mig,
        gen_ep=gen_ep,
        info={
            "address": server.address,
            "path": gen_ep.path,
            "worker_id": rt.worker_id,
            "metadata": metadata,
        },
        target={
            "worker_id": rt.worker_id,
            "address": server.address,
            "import_path": in_ep.path,
            "generate_path": gen_ep.path,
        },
    )


async def _close_worker(w):
    await w.engine.close()
    await w.rt.close()


async def _wait_for(cond, timeout=30.0, interval=0.01):
    deadline = asyncio.get_running_loop().time() + timeout
    while not cond():
        assert asyncio.get_running_loop().time() < deadline, "condition timeout"
        await asyncio.sleep(interval)


def _consume(stream, items):
    async def run():
        async for it in stream:
            items.append(it)

    return asyncio.create_task(run())


class _Pace:
    """Deterministic decode throttle via the engine's injectable pace hook
    (engine.pace_hook — awaited before every device-op await).  The two
    fleet acceptance tests below race wall clocks (drain vs sequence
    completion; fault-arm vs stream end) and used to lose on slow
    containers when decode outran the copy loop / the arm.  Engaging the
    pace makes decode provably slower than the raced path — the KV
    copy/export plane runs under the device lock, NOT through
    ``_await_device``, so it is unthrottled — and ``release()`` restores
    full speed once the race is decided.  Token streams are seed-keyed, so
    pacing never changes bytes."""

    def __init__(self, *engines, delay_s=0.05):
        self._delay = delay_s
        self._engines = engines
        self._on = True
        for e in engines:
            e.pace_hook = self._hook

    async def _hook(self):
        if self._on:
            await asyncio.sleep(self._delay)

    def release(self):
        self._on = False
        for e in self._engines:
            e.pace_hook = None


class _CopyGate:
    """Count-bounded decode-vs-copy interlock: the deflaked successor to
    ``_Pace`` for the two acceptance races below (a wall-clock delay only
    SHRINKS the losing window; a budget closes it).

    Phase 1 (copy rounds): decode consumes one budget unit per paced
    device op (engine.pace_hook — awaited OUTSIDE the device lock, see
    pipeline._pace) and PARKS when the budget is dry; every completed
    copy round (worker.copy_round_hook) refills ``steps_per_round`` more.
    Decode therefore advances a bounded number of ops per shipped round
    no matter how slow the container is — the historical flake (decode
    finishing the sequence before the copy loop landed, aborting the
    migration) is structurally impossible — and the parked loop holds no
    lock, so the copy plane is never starved.

    Final phase (the worker fires ``final=True`` right before the
    freeze): the gate stops parking and degrades to a small per-op delay.
    Freeze quiescence NEEDS the decode loop running (in-flight harvests +
    fused-session retirement), while the delay keeps any co-resident
    control sequence, which needs hundreds of paced ops, provably slower
    than the O(transfer) cutover, which needs a handful.

    ``release()`` uninstalls both hooks and restores full speed."""

    def __init__(self, worker, steps_per_round=2, final_delay_s=0.02):
        self._worker = worker
        self._engine = worker.engine
        self._per_round = steps_per_round
        self._final_delay = final_delay_s
        self._budget = steps_per_round
        self._refill = asyncio.Event()
        self._final = False
        self._released = False
        self.rounds = 0  # phase-1 copy rounds observed
        worker.engine.pace_hook = self._pace
        worker.copy_round_hook = self._round

    async def _pace(self):
        if self._released:
            return
        if self._final:
            await asyncio.sleep(self._final_delay)
            return
        while self._budget <= 0 and not self._final and not self._released:
            self._refill.clear()
            await self._refill.wait()
        self._budget -= 1

    async def _round(self, cursor, final):
        if final:
            self._final = True
        else:
            self.rounds += 1
            self._budget += self._per_round
        self._refill.set()

    def release(self):
        self._released = True
        self._refill.set()
        self._engine.pace_hook = None
        self._worker.copy_round_hook = None


# ---------------------------------------------------------------- snapshot


def test_snapshot_roundtrip_and_resume_request():
    snap = SequenceSnapshot(
        request_id="r1",
        token_ids=[1, 2, 3, 4, 5, 6],
        orig_prompt_len=4,
        sampling={"seed": 99, "temperature": 0.7, "top_k": 0, "top_p": 1.0},
        stop={"max_tokens": 32, "stop_token_ids": [7], "ignore_eos": True},
        spec={"k": 3, "ewma": 0.5, "bench_until": -1, "next_try": 0, "miss": 1},
        deadline_s=2.5,
    )
    assert snap.emitted == 2
    back = SequenceSnapshot.from_dict(snap.to_dict())
    assert back == snap

    req = snap.to_resume_request()
    pre = PreprocessedRequest.from_dict(req)
    seq = SequenceState.from_request("r1", pre, EngineConfig(**CFG))
    # The resumed state continues EXACTLY: rng-stream position, budget
    # accounting, and the speculation controller all count from the
    # original prompt, not the folded one.
    assert seq.orig_prompt_len == 4
    assert seq.num_output_tokens == 2
    assert seq.sampling_seed == 99
    assert seq.max_new_tokens == 32
    assert seq.stop_token_ids == frozenset({7})
    assert seq.spec_k == 3 and seq.spec_ewma == 0.5 and seq.spec_miss == 1


def test_resume_annotation_ignores_garbage():
    pre = PreprocessedRequest.from_dict(
        {
            "token_ids": [1, 2, 3],
            "annotations": {"resume": {"orig_prompt_len": 99}},  # > len
        }
    )
    seq = SequenceState.from_request("r", pre, EngineConfig(**CFG))
    assert seq.orig_prompt_len == 3  # falls back to the fresh-request rule


# ------------------------------------------------- exact-stream equivalence


async def test_migrate_once_and_twice_exact_stream():
    """The acceptance gate, both depths on one three-worker fleet:

    - a seeded temperature>0 request migrated mid-decode (A→B) produces a
      byte-identical stream vs the unmigrated control, with the tail
      generated by the target;
    - a second request migrated TWICE (A→B→C) is also byte-identical —
      the resume request is self-describing, so a migrated sequence is
      itself migratable."""
    migration_metrics.reset()
    hub = await HubServer().start()
    a = await _spawn_worker(hub, "m", "w")
    b = await _spawn_worker(hub, "m", "w")
    c = await _spawn_worker(hub, "m", "w")
    client_rt = await DistributedRuntime.connect(hub.address)
    try:
        client = await client_rt.namespace("m").component("w").endpoint(
            "gen"
        ).client()
        await client.wait_for_instances(5)

        # --- migrate once: A → B ------------------------------------------
        req = _req(list(range(1, 18)), max_tokens=64)
        control = await _control_tokens_on(a.engine, req)
        assert len(control) == 64
        ctx = Context(dict(req))
        rid = ctx.id
        # Pin the start to A (direct routing — the splice must work there
        # too); the cutover re-dispatches via the instance set.
        stream = await client.generate(ctx, worker_id=a.rt.worker_id)
        items = []
        task = _consume(stream, items)
        await _wait_for(lambda: len(_tokens(items)) >= 5)
        before = len(_tokens(items))
        # Deterministic race: gate the source's decode on the copy-round
        # budget so the copy loop provably completes before the sequence
        # can finish (decode outran the copy loop on slow containers under
        # the old time-based throttle — the migration then aborted on a
        # finished sequence).
        gate = _CopyGate(a.mig)
        assert await a.mig.migrate_out(rid, b.target)
        assert gate.rounds >= 1  # the budget interlock actually engaged
        gate.release()
        await task
        assert _tokens(items) == control
        assert items[-1]["finish_reason"] is not None
        assert a.engine.find_sequence(rid) is None  # source released it
        assert before < len(control)  # tail came after the cutover
        assert migration_metrics.completed_total == 1
        assert migration_metrics.blocks_total > 0
        assert b.engine.kv.matched_blocks > 0  # resumed via prefix hit

        # --- migrate twice: A → B → C -------------------------------------
        # Longer budget: the B→C hop exports from a BUSY source (device
        # lock shared with its own fused decode), so the sequence needs
        # enough runway not to finish before the second freeze.
        req2 = _req(list(range(21, 41)), max_tokens=128, seed=777)
        control2 = await _control_tokens_on(a.engine, req2)
        ctx2 = Context(dict(req2))
        stream2 = await client.generate(ctx2, worker_id=a.rt.worker_id)
        items2 = []
        task2 = _consume(stream2, items2)
        await _wait_for(lambda: len(_tokens(items2)) >= 4)
        gate = _CopyGate(a.mig)
        assert await a.mig.migrate_out(ctx2.id, b.target)
        gate.release()
        # Wait until B owns the resumed sequence and has advanced it past what
        # the client held when the wait began.  (Compared with the client's
        # GROWING count the condition held only in the instants between B
        # applying a fused chunk and the client receiving it; under six
        # workers the polls missed them all until the sequence had finished.)
        seen = len(_tokens(items2))
        await _wait_for(
            lambda: (s := b.engine.find_sequence(ctx2.id)) is not None
            and s.num_output_tokens >= seen + 2
        )
        gate = _CopyGate(b.mig)
        assert await b.mig.migrate_out(ctx2.id, c.target)
        gate.release()
        await task2
        assert _tokens(items2) == control2
        assert b.engine.find_sequence(ctx2.id) is None
        assert c.engine.kv.matched_blocks > 0
        assert migration_metrics.completed_total == 3
        await client.close()
    finally:
        await _close_worker(a)
        await _close_worker(b)
        await _close_worker(c)
        await client_rt.close()
        await hub.close()


# -------------------------------------------------------- rollback paths


async def test_commit_failure_rolls_back_source_authoritative():
    """A target that fails the commit (here: folded prompt would exceed its
    max_model_len) must leave the source authoritative: the sequence
    unfreezes, keeps decoding, and the client stream is untouched.  A
    config mismatch (block_size) is caught even earlier, at the FIRST
    blocks push: the copy phase aborts without ever freezing."""
    migration_metrics.reset()
    src = TpuEngine(EngineConfig(**CFG))
    # Commit-refusing target: every phase-1 push lands (plenty of blocks),
    # ONLY the commit's max_model_len capacity gate can say no.
    tiny = TpuEngine(EngineConfig(**dict(CFG, max_model_len=16)))
    # Push-refusing target: mismatched block geometry.
    odd = TpuEngine(EngineConfig(**dict(CFG, block_size=8)))
    src_mig = MigratableWorker(src, chunk_blocks=4)
    src_mig.direct["tiny"] = MigratableWorker(tiny)
    src_mig.direct["odd"] = MigratableWorker(odd)
    try:
        req = _req(list(range(1, 18)), max_tokens=64, seed=42)
        control = await _control_tokens_on(src, req)
        ctx = Context(dict(req))
        task = asyncio.create_task(collect(await src.generate(ctx)))
        await _wait_for(
            lambda: (s := src.find_sequence(ctx.id)) is not None
            and s.num_output_tokens >= 3
        )
        # Deterministic race: both migrate attempts must land on a LIVE
        # sequence (a 64-token budget can otherwise finish before the
        # second attempt on a slow container, turning the asserted
        # rollback/abort codes into plain finished-sequence aborts).
        pace = _Pace(src)
        ok = await src_mig.migrate_out(
            ctx.id,
            {"worker_id": 9, "address": "tiny", "import_path": "-",
             "generate_path": "-"},
        )
        assert not ok
        assert migration_metrics.rolled_back_total == 1
        seq = src.find_sequence(ctx.id)
        assert seq is not None and not seq.frozen  # unfrozen, still live

        ok = await src_mig.migrate_out(
            ctx.id,
            {"worker_id": 9, "address": "odd", "import_path": "-",
             "generate_path": "-"},
        )
        assert not ok
        assert migration_metrics.aborted_total == 1  # never froze for this
        assert migration_metrics.rolled_back_total == 1
        pace.release()

        items = await task
        assert _tokens(items) == control  # stream never noticed either try
    finally:
        await src.close()
        await tiny.close()
        await odd.close()


# ----------------------- drain in O(transfer), driven remotely


@pytest.mark.slow  # heavy 2-worker fleet: ci.sh's migration step runs it
# (no `slow` filter there); tier-1 keeps the cheap gates.  The drain-vs-
# control race itself is DETERMINISTIC via the copy-round budget gate.
async def test_remote_drain_via_migrate_is_transfer_bound():
    """Planner scale-down/flip acceptance: draining a worker via its
    REMOTE migrate_out control endpoint (llm.migration.request_migrate_out
    — what a supervisor/preStop hook calls) completes while a 10x-longer
    control run of the SAME sequence is still decoding — actuation cost is
    KV-transfer time, not sequence time — with zero dropped or duplicated
    tokens."""
    from dynamo_tpu.llm.migration import request_migrate_out

    # A genuinely LONG-RUNNING sequence (the Llumnix motivation): it must
    # still be mid-decode when the drain finishes.  The SOURCE engine hosts
    # both it and the control run, so it needs headroom for two
    # allocations.
    cfg = dict(CFG, num_blocks=256)
    req = _req(list(range(1, 22)), max_tokens=320, seed=31)
    hub = await HubServer().start()
    a = await _spawn_worker(hub, "d", "w", cfg=cfg)
    b = await _spawn_worker(hub, "d", "w", cfg=cfg)
    client_rt = await DistributedRuntime.connect(hub.address)
    try:
        client = await client_rt.namespace("d").component("w").endpoint(
            "gen"
        ).client()
        await client.wait_for_instances(5)
        ctx = Context(dict(req))
        stream = await client.generate(ctx, worker_id=a.rt.worker_id)
        items = []
        task = _consume(stream, items)
        await _wait_for(lambda: len(_tokens(items)) >= 5)

        # Deterministic race: gate the SOURCE engine's decode on the
        # copy-round budget (the copy loop itself is unthrottled — it
        # runs under the device lock, not through the paced device-op
        # path) so it provably outpaces both the migrating sequence and
        # the control.  Under the old time-based throttle a slow
        # container could still decode 320 tokens before 16 copy rounds
        # landed and the drain aborted on a finished sequence; the budget
        # bounds decode by OP COUNT per shipped round instead.
        gate = _CopyGate(a.mig)
        # Control clock starts at the drain decision: the same seeded
        # sequence, decoded from scratch to completion on the SOURCE engine
        # (seeded streams are engine-agnostic; running it there keeps the
        # target's device lock free, so the copy phase measures transfer).
        # Waiting the control out is what drain() used to cost; the
        # migrate-out drain races it.
        control_task = asyncio.create_task(
            collect(await a.engine.generate(Context(dict(req))))
        )
        resp = await request_migrate_out(a.info, b.target, request_id=ctx.id)
        assert resp["ok"] and resp["migrated"] == [ctx.id]
        # The drain finished while the control run — which must wait out
        # the full sequence — is still decoding: O(transfer), not
        # O(sequence).
        assert not control_task.done(), (
            "drain-via-migrate was not faster than sequence completion"
        )
        assert ctx.id not in a.engine.live_request_ids()
        assert gate.rounds >= 1  # the budget interlock actually engaged
        # Race decided: restore full speed so the control (and the spliced
        # stream's tail on the target) finish promptly.
        gate.release()

        await task
        control = _tokens(await control_task)
        assert len(control) == 320
        # Zero dropped, zero duplicated: byte-identical to the control.
        assert _tokens(items) == control
        await client.close()
    finally:
        await _close_worker(a)
        await _close_worker(b)
        await client_rt.close()
        await hub.close()


# ------------------------------------------------ target discovery helpers


async def test_pick_migration_target_filters_and_orders():
    hub = await HubServer().start()
    try:
        client = await DistributedRuntime.connect(hub.address)
        try:
            await client.hub.kv_put(
                "instances/x/w/gen/5",
                {"address": "h:1", "path": "x.w.gen", "worker_id": 5,
                 "metadata": {"migrate": {"import_path": "x.w.migrate_in"}}},
            )
            await client.hub.kv_put(
                "instances/x/w/gen/3",
                {"address": "h:2", "path": "x.w.gen", "worker_id": 3,
                 "metadata": {"migrate": {"import_path": "x.w.migrate_in"}}},
            )
            await client.hub.kv_put(  # not migration-capable: skipped
                "instances/x/w/gen/1",
                {"address": "h:3", "path": "x.w.gen", "worker_id": 1,
                 "metadata": {}},
            )
            t = await pick_migration_target(client.hub, "instances/x/w/gen/", 3)
            assert t is not None and t["worker_id"] == 5  # self excluded
            t = await pick_migration_target(client.hub, "instances/x/w/gen/", 99)
            assert t["worker_id"] == 3  # deterministic lowest-id pick
            assert (
                await pick_migration_target(client.hub, "instances/none/", 1)
            ) is None
        finally:
            await client.close()
    finally:
        await hub.close()


# --------------------------------------------------- chaos: crash recovery


@pytest.mark.chaos
@pytest.mark.slow  # two full crash/resume rounds: ci.sh's migration step
# runs it (no `slow` filter there); tier-1 keeps the cheap gates.  The
# arm-vs-stream-end race is DETERMINISTIC via the injectable pace hook.
async def test_drop_mid_stream_crash_recovery():
    """Chaos acceptance on one two-worker fleet: a decode worker killed
    mid-stream (the ``drop_mid_stream`` fault point — same mechanism
    DYN_FAULTS arms in a subprocess) loses its connection after tokens have
    streamed.

    - A SEEDED request resumes on the surviving worker token-identically
      to the uncrashed control (the routed client rebuilds a resume
      request from the delivered tokens; explicit seed ⇒ deterministic).
    - An UNSEEDED request must NOT resume (engine-default seeds
      incorporate the worker's own engine seed, so the continuation is not
      guaranteed identical): the failure surfaces, exactly as before."""
    from dynamo_tpu.runtime.faultinject import faults
    from dynamo_tpu.runtime.resilience import metrics as res_metrics

    hub = await HubServer().start()
    a = await _spawn_worker(hub, "c", "w")
    b = await _spawn_worker(hub, "c", "w")
    client_rt = await DistributedRuntime.connect(hub.address)
    try:
        client = await client_rt.namespace("c").component("w").endpoint(
            "gen"
        ).client()
        await client.wait_for_instances(5)

        # --- seeded: resumes elsewhere, token-identical -------------------
        req = _req(list(range(61, 78)), max_tokens=64, seed=909)
        control = await _control_tokens_on(b.engine, req)
        before_resumes = res_metrics.stream_resumes_total
        # Deterministic fault window: throttle BOTH engines' decode so the
        # arm below provably lands while the 64-token stream is still
        # running (unpaced, a fast container could finish the whole stream
        # between the >= 5 check and the arm — the fault then never fired
        # and the resume count assertion raced).  Pacing is byte-invisible:
        # streams key on (seed, output index).
        pace = _Pace(a.engine, b.engine)
        stream = await client.generate(Context(dict(req)))
        items = []
        task = _consume(stream, items)
        await _wait_for(lambda: len(_tokens(items)) >= 5)
        # Kill the serving worker mid-stream: its next item send hard-aborts
        # the transport, exactly like DYN_FAULTS=drop_mid_stream#1.
        faults.arm("drop_mid_stream", match="gen", count=1)
        pace.release()  # fault armed: the race is decided
        await task
        assert _tokens(items) == control
        assert items[-1]["finish_reason"] is not None
        assert res_metrics.stream_resumes_total == before_resumes + 1

        # --- unseeded: refuses to resume, surfaces the crash --------------
        req = _req(list(range(61, 78)), max_tokens=64, seed=None)
        pace = _Pace(a.engine, b.engine)
        stream = await client.generate(Context(dict(req)))
        items = []
        with pytest.raises(Exception):
            got = 0
            async for it in stream:
                items.append(it)
                got += len(it.get("token_ids", []))
                if got >= 3:
                    faults.arm("drop_mid_stream", match="gen", count=1)
                    pace.release()
        assert items  # tokens streamed before the crash surfaced
        pace.release()  # crash may surface before the arm branch ran
        await client.close()
    finally:
        faults.reset()
        await _close_worker(a)
        await _close_worker(b)
        await client_rt.close()
        await hub.close()


# --------------------------------------- KV transfer rollback (satellite)


async def test_inject_paths_validate_and_roll_back():
    """Satellite bugfix, both import paths:

    - a malformed host payload (truncated bytes) is rejected BEFORE any
      allocation/eviction;
    - a device-scatter failure mid-import frees the just-allocated blocks
      (no allocated-forever leak) and leaves sealed prefixes intact;
    - the device-path import refuses mismatched page layouts itself,
      without touching the pool."""
    import numpy as np

    eng = TpuEngine(EngineConfig(**CFG))
    donor = TpuEngine(EngineConfig(**CFG))
    try:
        resident = list(range(1, 17))
        await collect(await eng.generate(Context(_req(resident, max_tokens=2))))
        other = list(range(100, 124))
        await collect(await donor.generate(Context(_req(other, max_tokens=2))))
        payload = await donor.export_prompt_blocks(other)
        assert payload is not None

        active_before = eng.kv.active_blocks
        hit_before = eng.estimate_prefix_hit(resident)

        # Malformed payload (truncated bytes): rejected pre-allocation.
        bad = dict(payload, k=payload["k"][:-8])
        assert await eng.inject_blocks(other, bad) == 0
        assert eng.kv.active_blocks == active_before

        # Mid-transfer failure: the scatter raises after allocation.
        real_inject = eng._inject_fn

        def boom(*a, **k):
            raise RuntimeError("injected scatter failure")

        eng._inject_fn = boom
        with pytest.raises(RuntimeError, match="injected scatter"):
            await eng.inject_blocks(other, payload)
        # Rolled back: nothing leaked, resident prefix untouched.
        assert eng.kv.active_blocks == active_before
        assert eng.estimate_prefix_hit(resident) == hit_before

        # And the import still works once the device behaves again.
        eng._inject_fn = real_inject
        assert await eng.inject_blocks(other, payload) == 24

        # Device path: layout validation happens before allocation.
        tokens = list(range(50, 66))
        shape = eng.cache.pages.shape  # [L, n, ps, 2KV, hd]
        active_before = eng.kv.active_blocks
        wrong_dtype = np.zeros((shape[0], 4) + shape[2:], np.float16)
        assert await eng.inject_blocks_from_device(tokens, wrong_dtype, 4) == 0
        wrong_layers = np.zeros(
            (shape[0] + 1, 4) + shape[2:], eng.cache.pages.dtype
        )
        assert await eng.inject_blocks_from_device(tokens, wrong_layers, 4) == 0
        assert eng.kv.active_blocks == active_before
    finally:
        await eng.close()
        await donor.close()


# -------------------------------------------------- resume-exactness units


async def test_penalty_counts_survive_prompt_folding():
    """Frequency/presence penalty counts must cover generated tokens that
    preemption or migration folded into the prompt (counting ``output``
    alone dropped them exactly when a request resumed)."""
    import numpy as np

    from dynamo_tpu.tokens import TokenBlockSequence

    eng = TpuEngine(EngineConfig(**CFG))
    try:
        seq = SequenceState(
            request_id="x",
            prompt=[1, 2, 3, 9, 9],  # 3 original + 2 folded generated
            block_seq=TokenBlockSequence(block_size=4),
            freq_penalty=0.5,
            orig_prompt_len=3,
        )
        seq.output = [7]
        samp = eng._sampling_arrays([seq])
        counts = np.asarray(samp.counts)
        assert counts[0, 9] == 2  # folded tokens still counted
        assert counts[0, 7] == 1
        assert counts[0, 1] == 0  # original prompt tokens are not penalized
    finally:
        await eng.close()


def test_decoder_state_roundtrip():
    """Stop-jail + detok state snapshot/restore (SequenceSnapshot.detok):
    a restored Decoder behaves identically to the uninterrupted one."""
    from dynamo_tpu.llm.backend import Decoder
    from dynamo_tpu.llm.tokenizer import ByteTokenizer

    stop = StopConditions(stop=["XY"], max_tokens=100)
    fed = [ord(c) for c in "abX"]
    d1 = Decoder(ByteTokenizer(), stop)
    emitted = "".join(d1.step(t)[0] for t in fed)
    assert emitted == "ab" and d1.state_dict()["jail"] == "X"

    state = d1.state_dict()
    d2 = Decoder(ByteTokenizer(), stop)
    d2.load_state(state, fed)
    assert d1.step(ord("Z")) == d2.step(ord("Z")) == ("XZ", None)

    d3 = Decoder(ByteTokenizer(), stop)
    d3.load_state(state, fed)
    text, fin = d3.step(ord("Y"))  # jail "X" + "Y" completes the stop string
    assert text == "" and str(fin) == "stop"


# ------------------------------------------------ hub-native supervisor


async def test_supervisor_enacts_planner_targets():
    """ROADMAP leftover: planner/targets/* now has a hub-native enactor —
    scale-up spawns, scale-down stops (LIFO) with the actuator's
    drain=migrate hint passed through to the stop hook."""
    from dynamo_tpu.planner.actuate import LocalActuator
    from dynamo_tpu.planner.policy import Decision, scale_decode, scale_prefill
    from dynamo_tpu.planner.supervisor import Supervisor
    from dynamo_tpu.runtime.transports.hub import InprocHub

    hub = await InprocHub().start()
    spawned, stopped = [], []

    async def spawn(pool):
        handle = f"{pool}-{len(spawned)}"
        spawned.append(handle)
        return handle

    async def stop(pool, handle, drain):
        stopped.append((pool, handle, drain))

    sup = await Supervisor(
        hub, spawn, stop, pools=["decode"], resync_s=0.2
    ).start()
    try:
        actuator = LocalActuator(hub)
        await actuator.apply(
            Decision(tick=1, actions=[scale_decode(2, 2, "up")], pressures={})
        )
        await _wait_for(lambda: sup.owned("decode") == 2)
        assert spawned == ["decode-0", "decode-1"]

        await actuator.apply(
            Decision(tick=2, actions=[scale_decode(-1, 1, "dn")], pressures={})
        )
        await _wait_for(lambda: sup.owned("decode") == 1)
        # Newest worker stopped first, with the migrate drain hint.
        assert stopped == [("decode", "decode-1", "migrate")]

        # Pools outside this supervisor's remit are ignored.
        await actuator.apply(
            Decision(tick=3, actions=[scale_prefill(1, 3, "x")], pressures={})
        )
        await asyncio.sleep(0.3)
        assert sup.owned("prefill") == 0 and len(spawned) == 2
    finally:
        await sup.stop()
        await hub.close()


# ----------------------------------------- cli role flips (both directions)


async def test_prefill_to_decode_flip_brings_up_full_decode_surface():
    """ROADMAP leftover: a prefill cli worker flipped to decode stops its
    PrefillWorkerLoop and brings up the FULL decode surface on the same
    engine — kv_import endpoint registration included — then can flip back,
    migrating out first (no peer here, so the drain degrades cleanly)."""
    from dynamo_tpu.cli import WorkerRoles
    from dynamo_tpu.planner.actuate import ROLE_PREFIX, RoleFlipWatcher

    hub = await HubServer().start()
    rt = await DistributedRuntime.connect(hub.address)
    engine = TpuEngine(EngineConfig(**CFG))
    endpoint = rt.namespace("f").component("w").endpoint("gen")
    args = SimpleNamespace(model="tiny", max_local_prefill=64)
    roles = WorkerRoles(args, rt, endpoint, engine, {"kind": "byte"})
    try:
        await roles.start_prefill()
        info = await rt.hub.kv_get(endpoint.instance_key(rt.worker_id))
        assert info["metadata"]["role"] == "prefill" and info["address"] == ""

        async def _switch_decode():
            await roles.start_decode(disagg=True)

        flipper = await RoleFlipWatcher(
            rt.hub,
            rt.worker_id,
            "prefill",
            drain={"decode": roles.stop_decode, "prefill": roles.stop_prefill},
            switch={"prefill": roles.start_prefill, "decode": _switch_decode},
        ).start()
        await rt.hub.kv_put(
            f"{ROLE_PREFIX}{rt.worker_id}", {"role": "decode", "tick": 1}
        )
        await _wait_for(lambda: flipper.flips == 1)

        info = await rt.hub.kv_get(endpoint.instance_key(rt.worker_id))
        assert info["metadata"]["role"] == "decode"
        assert info["address"]  # a real serving address now
        assert info["metadata"]["migrate"]["import_path"]
        # Import-endpoint registration happened on the flip.
        imports = await rt.hub.kv_get_prefix("instances/f/w/kv_import/")
        assert any(
            v.get("worker_id") == rt.worker_id for v in imports.values()
        )
        models = await rt.hub.kv_get_prefix("models/tiny/")
        assert models  # model registered for discovery

        # Flip back decode→prefill: drain (migrate path degrades — no
        # peer), stop the decode surface, return to queue-draining.
        await rt.hub.kv_put(
            f"{ROLE_PREFIX}{rt.worker_id}", {"role": "prefill", "tick": 2}
        )
        await _wait_for(lambda: flipper.flips == 2)
        info = await rt.hub.kv_get(endpoint.instance_key(rt.worker_id))
        assert info["metadata"]["role"] == "prefill" and info["address"] == ""
        assert not await rt.hub.kv_get_prefix("models/tiny/")
        await flipper.stop()
    finally:
        await roles.shutdown()
        await engine.close()
        await rt.close()
        await hub.close()
