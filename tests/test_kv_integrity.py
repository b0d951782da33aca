"""End-to-end KV integrity plane (docs/kv_tiering.md §integrity).

Checksummed blocks across every tier and wire plane: the corruption plane
matrix bit-flips each boundary (disk get, host restore, wire inject,
migration push, peer pull) and asserts detection BEFORE any scatter,
chained-descendant drop, Removed-event emission, negative-cache behavior,
and a byte-identical recompute fallback — plus checksum-less-peer wire
compat and the repeat-offender quarantine path.
"""

import asyncio
import os
from types import SimpleNamespace

import numpy as np
import pytest

from dynamo_tpu.engine import EngineConfig
from dynamo_tpu.engine.disk_cache import DiskKvStore
from dynamo_tpu.engine.engine import TpuEngine
from dynamo_tpu.engine.host_cache import HostKvStore
from dynamo_tpu.engine.integrity import (
    CorruptionCache,
    block_checksum,
    flip_array_byte,
    payload_block_checksums,
)
from dynamo_tpu.engine.kv_manager import KvBlockManager
from dynamo_tpu.llm.metrics import kv_integrity_metrics
from dynamo_tpu.llm.protocols import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.runtime.engine import Context, collect
from dynamo_tpu.tokens import hash_token_blocks

pytestmark = pytest.mark.integrity

BS = 4


def _cfg(tmp_path=None, **over):
    cfg = dict(
        model="debug-tiny",
        block_size=BS,
        num_blocks=16,
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
    out = await collect(await engine.generate(Context(req)))
    return [t for item in out for t in item["token_ids"]]


async def _settle_offload(engine, want_blocks):
    for _ in range(100):
        await engine.drain_offload()
        if len(engine.host_kv) >= want_blocks:
            return
        await asyncio.sleep(0.01)


async def _flood(engine, bases, length=12):
    for base in bases:
        await _generate(engine, [base + i for i in range(length)])
        await engine.drain_offload()


# ------------------------------------------------------------- primitives


def test_checksum_primitives_and_corruption_cache():
    blk = np.arange(2 * 4 * 4 * 8, dtype=np.float32).reshape(2, 4, 4, 8)
    assert block_checksum(blk) == block_checksum(blk.copy())
    assert block_checksum(blk) != block_checksum(flip_array_byte(blk))
    # per-block wire checksums localize a single flipped byte to ONE block
    k = np.random.default_rng(0).random((2, 3, 4, 4, 8)).astype(np.float32)
    v = np.random.default_rng(1).random((2, 3, 4, 4, 8)).astype(np.float32)
    sums = payload_block_checksums(k, v)
    diff = [
        i for i in range(3)
        if sums[i] != payload_block_checksums(flip_array_byte(k), v)[i]
    ]
    assert len(diff) == 1
    # TTL negative cache: bans expire, table is bounded
    clock = SimpleNamespace(t=0.0)
    cache = CorruptionCache(ttl_s=10.0, max_entries=3, clock=lambda: clock.t)
    cache.ban(1)
    assert cache.banned(1) and not cache.banned(2)
    assert cache.any_banned([5, 6, 1]) == 1
    clock.t = 10.0
    assert not cache.banned(1)  # expired: a healthy copy is reachable again
    for h in (10, 11, 12, 13):
        cache.ban(h)
    assert len(cache) <= 3


def test_disk_envelope_checksum_and_legacy_compat(tmp_path):
    blk = np.arange(2 * 4 * 4 * 8, dtype=np.float32).reshape(2, 4, 4, 8)
    store = DiskKvStore(1 << 20, str(tmp_path))
    stamp = block_checksum(blk)
    assert store.put(7, blk, checksum=stamp)
    arr, carried, corrupt = store.read(
        7, expected_shape=blk.shape, expected_dtype=blk.dtype
    )
    assert np.array_equal(arr, blk) and carried == stamp and not corrupt
    # flip one payload byte on disk: detected, deleted, loss RECORDED
    path = store._path(7)
    raw = bytearray(open(path, "rb").read())
    raw[-3] ^= 0xFF
    open(path, "wb").write(bytes(raw))
    arr, _, corrupt = store.read(7)
    assert arr is None and corrupt
    assert store.corrupt_blocks == 1
    assert ("drop", 7) in store.drain_transitions()
    assert not os.path.exists(path)
    # a STALE stamp is refused at the write (host-RAM rot is not laundered
    # into a structurally-valid file)
    assert store.put(8, blk, checksum=stamp ^ 1) is False
    assert store.corrupt_blocks == 2 and not store.contains(8)
    # legacy envelope without a checksum field stays readable (wire compat)
    import json as _json
    import struct as _struct

    header = _json.dumps(
        {"dtype": str(blk.dtype), "shape": list(blk.shape)}
    ).encode()
    legacy = (
        b"DKVB1\n" + _struct.pack("<I", len(header)) + header
        + np.ascontiguousarray(blk).tobytes()
    )
    lpath = os.path.join(str(tmp_path), f"{9:016x}.kvblk")
    open(lpath, "wb").write(legacy)
    store2 = DiskKvStore(1 << 20, str(tmp_path))
    arr, carried, corrupt = store2.read(9)
    assert np.array_equal(arr, blk) and carried is None and not corrupt


def test_disk_reindex_deletes_orphaned_tmp_files(tmp_path):
    blk = np.zeros((2, 4, 4, 8), np.float32)
    store = DiskKvStore(1 << 20, str(tmp_path))
    assert store.put(3, blk)
    # a crash mid-write leaves a .kvblk.tmp that lives OUTSIDE the byte
    # budget — the re-index must delete it, not carry it forever
    orphan = os.path.join(str(tmp_path), "00000000deadbeef.kvblk.tmp")
    open(orphan, "wb").write(b"torn write")
    again = DiskKvStore(1 << 20, str(tmp_path))
    assert not os.path.exists(orphan)
    assert again.contains(3)  # real blocks survive the cleanup


def test_disk_fsync_knob(tmp_path, monkeypatch):
    calls = []
    real_fsync = os.fsync
    monkeypatch.setattr(os, "fsync", lambda fd: calls.append(fd) or real_fsync(fd))
    blk = np.zeros((2, 4, 4, 8), np.float32)
    off = DiskKvStore(1 << 20, str(tmp_path / "off"))
    off.put(1, blk)
    assert calls == []  # default: rename-atomic only (docs/kv_tiering.md)
    on = DiskKvStore(1 << 20, str(tmp_path / "on"), fsync=True)
    on.put(1, blk)
    assert len(calls) == 1
    assert on.get(1) is not None


def test_host_store_stamps_and_drops():
    blk = np.arange(2 * 4 * 4 * 8, dtype=np.float32).reshape(2, 4, 4, 8)
    host = HostKvStore(1 << 20)
    host.put(5, blk.copy())
    assert host.checksum(5) == block_checksum(blk)
    # multi-host shard dicts stay unstamped (documented restriction)
    host.put(6, {0: blk.copy()})
    assert host.checksum(6) is None
    # quarantine drop: no demotion, loss recorded
    assert host.drop(5) and not host.contains(5)
    assert ("drop", 5) in host.drain_transitions()
    assert host.drop(5) is False


def test_evict_hashes_runs_real_eviction_path():
    events = []
    kv = KvBlockManager(8, BS, event_callback=events.append)
    blocks = hash_token_blocks(list(range(1, 13)), BS)
    ids, _ = kv.allocate_sequence(blocks, 3)
    for bid, tb in zip(ids, blocks):
        kv.seal_block(bid, tb)
    kv.free_sequence(ids)
    free_before = kv.free_blocks
    assert kv.evict_hashes([blocks[1].sequence_hash]) == 1
    assert blocks[1].sequence_hash not in kv._by_hash
    assert kv.free_blocks == free_before  # recycled, not leaked
    removed = {
        h
        for e in events
        if e.data.__class__.__name__ == "KvCacheRemoveData"
        for h in e.data.block_hashes
    }
    assert blocks[1].sequence_hash in removed
    # active (referenced) blocks are never touched
    ids2, _ = kv.allocate_sequence(blocks[:1], 1)
    assert kv.evict_hashes([blocks[0].sequence_hash]) == 0
    kv.free_sequence(ids2)


# ------------------------------------------------- plane matrix: disk, host


def test_corruption_plane_matrix_disk_and_host(tmp_path):
    """Bit-flip the disk and host boundaries under a live engine: each
    must detect before scatter, drop the chained descendants, emit
    Removed, negative-cache the hash, and recompute byte-identically."""

    async def main():
        events = []
        engine = TpuEngine(_cfg(tmp_path), event_callback=events.append)
        reported = []
        # the serving layer (cli start_decode) wires this to feed the
        # watchdog ledger with the worker's own id; capture the planes
        engine.set_integrity_reporter(reported.append)

        # --- disk plane ------------------------------------------------
        prompt = list(range(1, 13))  # 3 full blocks
        control = await _generate(engine, prompt, seed=3, temperature=0.9)
        await _settle_offload(engine, 3)
        engine.host_kv.capacity_bytes = 2 * engine.block_nbytes()
        await _flood(engine, (20, 40, 60, 80, 100, 120))
        blocks = hash_token_blocks(prompt, BS)
        on_disk = [
            tb.sequence_hash
            for tb in blocks
            if engine.disk_kv.contains(tb.sequence_hash)
        ]
        assert len(engine.kv.match_prefix(blocks)) < 3 and on_disk

        h = on_disk[0]
        path = engine.disk_kv._path(h)
        raw = bytearray(open(path, "rb").read())
        raw[-5] ^= 0xFF
        open(path, "wb").write(bytes(raw))
        chain = [tb.sequence_hash for tb in blocks]
        descendants = [
            d for d in chain[chain.index(h) + 1:]
            if engine.disk_kv.contains(d) or engine.host_kv.contains(d)
        ]
        c0 = kv_integrity_metrics.corrupt_total["disk"]
        events.clear()
        # Hold the write-behind pump over the recompute and the check: the
        # recompute seals the blocks again, and a pump cycle that lands before
        # the check stores their CLEAN copies (it did in a quarter of the
        # runs), which would hide whether the corrupt ones left the tiers.
        real_drain = engine.drain_offload

        async def held(max_blocks: int = 64) -> int:
            return 0

        engine.drain_offload = held
        try:
            again = await _generate(engine, prompt, seed=3, temperature=0.9)
            assert again == control  # recompute fallback is byte-identical
            assert kv_integrity_metrics.corrupt_total["disk"] == c0 + 1
            assert engine.integrity.banned(h)  # negative-cached (TTL)
            # the corrupt block AND its chained descendants left every tier
            for d in [h, *descendants]:
                assert not engine.disk_kv.contains(d)
                assert not engine.host_kv.contains(d)
        finally:
            engine.drain_offload = real_drain
        removed = {
            hh
            for e in events
            if e.data.__class__.__name__ == "KvCacheRemoveData"
            for hh in e.data.block_hashes
        }
        assert h in removed  # the router stops advertising the prefix

        # --- host plane -------------------------------------------------
        prompt2 = list(range(200, 212))
        control2 = await _generate(engine, prompt2, seed=5, temperature=0.9)
        engine.host_kv.capacity_bytes = 64 << 20
        blocks2 = hash_token_blocks(prompt2, BS)
        # Until one of THIS prompt's blocks is on the host tier: the tier
        # already holds others, and a pump cycle may be mid-commit.
        for _ in range(200):
            await engine.drain_offload()
            if any(engine.host_kv.contains(tb.sequence_hash) for tb in blocks2):
                break
            await asyncio.sleep(0.01)
        host_resident = [
            tb.sequence_hash
            for tb in blocks2
            if engine.host_kv.contains(tb.sequence_hash)
        ]
        assert host_resident, "test needs offloaded blocks"
        # force the repeats to RESTORE (deterministic HBM pressure)
        engine.kv.evict_hashes([tb.sequence_hash for tb in blocks2])
        # rot one byte of the host-tier entry in place
        entry = engine.host_kv.peek(host_resident[0])
        entry.reshape(-1).view(np.uint8)[7] ^= 0xFF
        c0 = kv_integrity_metrics.corrupt_total["host"]
        again2 = await _generate(engine, prompt2, seed=5, temperature=0.9)
        assert again2 == control2
        assert kv_integrity_metrics.corrupt_total["host"] == c0 + 1
        assert engine.integrity.banned(host_resident[0])
        assert not engine.host_kv.contains(host_resident[0])

        # negative cache: the banned hash skips restore attempts without
        # re-detecting (nothing left to detect), streams stay exact
        engine.kv.evict_hashes([tb.sequence_hash for tb in blocks2])
        third = await _generate(engine, prompt2, seed=5, temperature=0.9)
        assert third == control2
        assert kv_integrity_metrics.corrupt_total["host"] == c0 + 1

        # local-tier rot reported to the serving layer (ledger feed)
        assert reported == ["disk", "host"]

        await engine.close()

    asyncio.run(main())


# ----------------------------------------------------- plane matrix: wire


def test_wire_inject_verifies_truncates_and_accepts_legacy():
    """The wire boundary (inject_blocks — covers migration push and
    disagg import too): clean payloads verify, a corrupt block truncates
    the import at the verified prefix, and checksum-less payloads from
    older peers stay servable."""

    async def main():
        donor = TpuEngine(_cfg(host_cache_bytes=0))
        target = TpuEngine(_cfg(host_cache_bytes=0))
        prompt = list(range(1, 13))  # 3 full blocks
        await _generate(donor, prompt, max_tokens=1)
        payload = await donor.export_prompt_blocks(prompt)
        assert payload is not None and len(payload["checksums"]) == 3

        # clean inject: all blocks verify and seal
        v0 = kv_integrity_metrics.verified_total["wire"]
        covered = await target.inject_blocks(prompt, dict(payload))
        assert covered == 3 * BS
        assert kv_integrity_metrics.verified_total["wire"] == v0 + 3

        # corrupt the LAST block: the verified 2-block prefix still seals
        target2 = TpuEngine(_cfg(host_cache_bytes=0))
        shape = tuple(payload["shape"])
        arr = np.frombuffer(
            payload["k"], dtype=np.dtype(payload["dtype"])
        ).reshape(shape).copy()
        arr[:, 2] += 1.0
        bad = dict(payload, k=arr.tobytes())
        c0 = kv_integrity_metrics.corrupt_total["wire"]
        blocks = hash_token_blocks(prompt, BS)
        covered = await target2.inject_blocks(prompt, bad)
        assert covered == 2 * BS  # truncated at the corrupt block
        assert kv_integrity_metrics.corrupt_total["wire"] == c0 + 1
        assert blocks[0].sequence_hash in target2.kv._by_hash
        assert blocks[1].sequence_hash in target2.kv._by_hash
        assert blocks[2].sequence_hash not in target2.kv._by_hash
        assert target2.integrity.banned(blocks[2].sequence_hash)

        # corrupt block 0 → nothing seals, import rejected outright
        arr0 = np.frombuffer(
            payload["k"], dtype=np.dtype(payload["dtype"])
        ).reshape(shape).copy()
        arr0[:, 0] += 1.0
        target3 = TpuEngine(_cfg(host_cache_bytes=0))
        assert await target3.inject_blocks(prompt, dict(payload, k=arr0.tobytes())) == 0
        assert blocks[0].sequence_hash not in target3.kv._by_hash

        # checksum-less peer (pre-integrity wire format): still servable
        legacy = dict(payload)
        del legacy["checksums"]
        target4 = TpuEngine(_cfg(host_cache_bytes=0))
        assert await target4.inject_blocks(prompt, legacy) == 3 * BS

        # migration push rides the same boundary: a corrupted "blocks"
        # push reports the truncated coverage so the source's copy cursor
        # cannot advance past unsealed blocks
        from dynamo_tpu.llm.migration import MigratableWorker

        target5 = TpuEngine(_cfg(host_cache_bytes=0))
        mig = MigratableWorker(target5)
        resp = await mig._migrate_in({
            "kind": "blocks", "token_ids": prompt, "block_size": BS,
            "payload": dict(payload, k=arr.tobytes()),
        })
        assert resp["ok"] and resp["tokens_covered"] == 2 * BS

        for e in (donor, target, target2, target3, target4, target5):
            await e.close()

    asyncio.run(main())


def test_pull_corruption_degrades_attributes_and_negative_caches():
    """The peer-pull plane: a corrupt pulled payload is detected, the
    stream recomputes byte-identically, the donor is attributed in the
    corruption ledger, and the negative cache skips the next pull."""

    async def main():
        from dynamo_tpu.llm.kv_router.pull import PrefixPuller
        from dynamo_tpu.runtime.health import kv_corruption

        kv_corruption.reset()
        donor = TpuEngine(_cfg(host_cache_bytes=0))
        target = TpuEngine(_cfg(host_cache_bytes=0))
        control = TpuEngine(_cfg(host_cache_bytes=0))
        prompt = list(range(1, 13))
        await _generate(donor, prompt, max_tokens=1)
        calls = []

        async def corrupting_exporter(worker_id, data):
            calls.append(worker_id)
            payload = await donor.export_prompt_blocks(
                data["token_ids"],
                start_block=data.get("start_block", 0),
                max_blocks=data.get("max_blocks", 0),
                salt=data.get("salt"),
            )
            if payload is None:
                return None
            shape = tuple(payload["shape"])
            arr = np.frombuffer(
                payload["k"], dtype=np.dtype(payload["dtype"])
            ).reshape(shape).copy()
            arr[:, 0] += 1.0  # poison the first block in flight
            return dict(payload, k=arr.tobytes())

        target.set_prefix_puller(PrefixPuller(target, corrupting_exporter))
        DONOR_ID = 77
        hint = {"worker_id": DONOR_ID, "blocks": 3}
        c0 = kv_integrity_metrics.corrupt_total["wire"]
        pulled = await _generate(
            target, prompt, seed=11, temperature=0.9,
            annotations={"kv_pull": hint},
        )
        want = await _generate(control, prompt, seed=11, temperature=0.9)
        assert pulled == want  # degraded to local prefill, byte-identical
        assert kv_integrity_metrics.corrupt_total["wire"] == c0 + 1
        assert kv_corruption.count(DONOR_ID) == 1  # donor attributed
        # negative cache: the next pull of the same (banned) delta is
        # skipped WITHOUT dialing the donor.  Evict the recomputed local
        # copies first — with them resident the pull would bail at the
        # local-depth gate before the ban check.
        target.kv.evict_hashes(
            [tb.sequence_hash for tb in hash_token_blocks(prompt, BS)]
        )
        n_calls = len(calls)
        neg0 = kv_integrity_metrics.negative_cache_hits_total
        assert await target._prefix_puller.pull(prompt, None, hint) == 0
        assert len(calls) == n_calls
        assert kv_integrity_metrics.negative_cache_hits_total == neg0 + 1

        kv_corruption.reset()
        for e in (donor, target, control):
            await e.close()

    asyncio.run(main())


def test_kv_corrupt_fault_hooks_fire_per_plane(tmp_path):
    """The chaos hooks (runtime/faultinject.py kv_corrupt@plane) land at
    the same boundaries the checksums guard: armed wire/disk faults are
    detected and the streams stay byte-identical."""

    async def main():
        from dynamo_tpu.runtime.faultinject import faults

        donor = TpuEngine(_cfg(host_cache_bytes=0))
        target = TpuEngine(_cfg(host_cache_bytes=0))
        prompt = list(range(1, 13))
        await _generate(donor, prompt, max_tokens=1)
        payload = await donor.export_prompt_blocks(prompt)

        c0 = kv_integrity_metrics.corrupt_total["wire"]
        faults.arm("kv_corrupt", match="wire", count=1)
        try:
            covered = await target.inject_blocks(prompt, dict(payload))
            assert covered < 3 * BS  # the flip truncated the import
            assert kv_integrity_metrics.corrupt_total["wire"] == c0 + 1
        finally:
            faults.reset()

        # disk plane: armed flip on the file read is a recorded miss
        engine = TpuEngine(_cfg(tmp_path))
        control = await _generate(engine, prompt, seed=9, temperature=0.9)
        await _settle_offload(engine, 3)
        engine.host_kv.capacity_bytes = 2 * engine.block_nbytes()
        await _flood(engine, (20, 40, 60, 80, 100, 120))
        assert len(engine.disk_kv) > 0
        d0 = kv_integrity_metrics.corrupt_total["disk"]
        faults.arm("kv_corrupt", match="disk", count=1)
        try:
            again = await _generate(engine, prompt, seed=9, temperature=0.9)
            assert again == control
            assert kv_integrity_metrics.corrupt_total["disk"] >= d0 + 1
        finally:
            faults.reset()

        for e in (donor, target, engine):
            await e.close()

    asyncio.run(main())


# --------------------------------------------------------------- watchdog


async def test_watchdog_quarantines_repeat_corruption_offender():
    """Repeated checksum failures attributed to one donor quarantine it
    through the EXISTING watchdog path; ledger decay reinstates."""
    from dynamo_tpu.runtime import InprocHub
    from dynamo_tpu.runtime.health import (
        QUARANTINE_PREFIX,
        HealthConfig,
        HealthWatchdog,
        health_metrics,
        kv_corruption,
    )

    hub = await InprocHub().start()
    clock = SimpleNamespace(t=100.0)
    old_clock = kv_corruption._clock
    kv_corruption.reset()
    kv_corruption._clock = lambda: clock.t

    async def prober(address, timeout_s):
        return True

    drained = []

    async def drainer(info):
        drained.append(info["worker_id"])
        return 1

    for wid in (1, 2):
        await hub.kv_put(
            f"instances/i/c/gen/{wid}",
            {"address": f"a:{wid}", "path": "i.c.gen", "worker_id": wid,
             "metadata": {"role": "decode"}},
        )
    dog = HealthWatchdog(
        hub, "instances/i/", prober=prober, drainer=drainer,
        latency_source=lambda: {},
        config=HealthConfig(corrupt_after=3, eject_grace_s=1000.0),
        clock=lambda: clock.t,
    )
    q0 = health_metrics.corruption_quarantines_total
    k0 = kv_integrity_metrics.quarantined_total
    try:
        kv_corruption.record(1, n=2)
        await dog.tick()
        assert dog.workers[1].state == "healthy"  # below the bar
        kv_corruption.record(1)
        await dog.tick()
        assert dog.workers[1].state == "quarantined"
        assert dog.workers[1].reason == "kv_corruption=3"
        assert drained == [1]  # drain-via-migration kicked off
        assert health_metrics.corruption_quarantines_total == q0 + 1
        assert kv_integrity_metrics.quarantined_total == k0 + 1
        marker = await hub.kv_get(f"{QUARANTINE_PREFIX}1")
        assert marker and marker["state"] == "quarantined"
        assert dog.workers[2].state == "healthy"
        # ledger entries age out of the window → the donor reinstates
        clock.t += kv_corruption.window_s + 1.0
        await dog.tick()
        assert dog.workers[1].state == "healthy"
        assert await hub.kv_get(f"{QUARANTINE_PREFIX}1") is None
    finally:
        kv_corruption.reset()
        kv_corruption._clock = old_clock
        await dog.stop()
        await hub.close()


# ----------------------------------------------------------------- metrics


def test_integrity_metrics_render():
    text = kv_integrity_metrics.render()
    for plane in ("disk", "host", "wire"):
        assert f'dynamo_tpu_kv_integrity_verified_total{{plane="{plane}"}}' in text
        assert f'dynamo_tpu_kv_integrity_corrupt_total{{plane="{plane}"}}' in text
    assert "dynamo_tpu_kv_integrity_descendants_dropped_total" in text
    assert "dynamo_tpu_kv_integrity_negative_cache_hits_total" in text
    assert "dynamo_tpu_kv_integrity_recomputed_total" in text
    assert "dynamo_tpu_kv_integrity_quarantined_total" in text
    snap = kv_integrity_metrics.snapshot()
    assert "corrupt_wire_total" in snap and "verified_disk_total" in snap
