"""Cross-engine KV block transfer: host-staged export/import (the
cross-process wire format) and the same-process device-to-device path.

Split out of engine.py as a pure move (r5; VERDICT r4 weak #7).
"""

from __future__ import annotations

import asyncio
import time
import logging
from typing import TYPE_CHECKING, Any, Dict, List, Optional

if TYPE_CHECKING:  # annotation-only (transfer_blocks_device signature)
    from .engine import TpuEngine

import jax
import jax.numpy as jnp
import numpy as np

logger = logging.getLogger(__name__)


def _scales_close(a, b, rtol: float = 1e-3) -> bool:
    """Stored-representation scale compatibility for KV transfers.

    Exact equality would silently disable disagg transfers between two
    workers that each ran kv_scale='auto' (independent calibration drifts
    at the ULP level across device generations / compiler versions).  The
    tolerance covers exactly that ULP/compiler drift and NO more: beyond it
    the quantized rows genuinely encode different values, and importing
    them raw would carry a systematic dequantization error — such imports
    are rejected and the caller prefills locally (r4 review: the earlier 5%
    tolerance silently accepted up to ~5% of real scale error)."""
    if a is None or b is None:
        return a is None and b is None
    av = np.asarray(a, np.float32).reshape(-1)
    bv = np.asarray(b, np.float32).reshape(-1)
    if av.shape != bv.shape and av.size != 1 and bv.size != 1:
        return False
    return bool(np.allclose(av, bv, rtol=rtol))


class KvTransferMixin:
    async def export_prompt_blocks(
        self,
        token_ids: List[int],
        start_block: int = 0,
        max_blocks: int = 0,
        salt: Optional[str] = None,
        blocks: Optional[List[Any]] = None,
    ) -> Optional[Dict[str, Any]]:
        """Gather cached KV for ``token_ids``'s complete blocks to host.

        Exports the longest RESIDENT run starting at ``start_block`` (not
        all-or-nothing — a prompt that lost tail blocks to eviction still
        transfers its resident prefix; round-2 returned None in that case
        and recomputed everything).  ``max_blocks`` bounds the run (chunked
        transfer).  Returns None when nothing is resident at start_block.
        ``salt`` is the owning tenant's KV salt (llm/tenancy): tenant
        blocks seal under salted chained hashes, so an unsalted lookup
        cannot see them — and can never LEAK them to another tenant.

        ``blocks`` lets a caller that already holds the sealed chained-hash
        list (migration sends the same tokens chunk after chunk; the
        indexer sealed the chain once) pass it through instead of paying
        the O(len(tokens)) rehash per chunk.  Under ``__debug__`` the
        passed chain is asserted equal to a fresh recompute — a stale or
        wrongly-salted chain must fail loudly, not seal wrong bytes.
        """
        self._require_block_moves("KV export (disaggregated prefill, prefix pulls, migration)")
        from ..tokens import hash_token_blocks

        if jax.process_count() > 1:
            # Sharded global pages can't be gathered from one host (same
            # restriction as host_cache_bytes); refuse cleanly at request
            # time so the caller falls back to local prefill instead of
            # hanging on a non-addressable array (ADVICE r3).
            return None
        if blocks is None:
            blocks = hash_token_blocks(token_ids, self.cfg.block_size, salt)
        elif __debug__:
            fresh = hash_token_blocks(token_ids, self.cfg.block_size, salt)
            assert [tb.sequence_hash for tb in blocks] == [
                tb.sequence_hash for tb in fresh
            ], "export_prompt_blocks: passed block chain != sealed recompute"
        ids: List[int] = []
        for tb in blocks[start_block:]:
            bid = self.kv._by_hash.get(tb.sequence_hash)
            if bid is None:
                break
            ids.append(bid)
            if max_blocks and len(ids) >= max_blocks:
                break
        if not ids:
            return None
        async with self._device_lock:
            pages = np.asarray(self.cache.pages[:, np.asarray(ids, np.int32)])
        k = pages[:, :, :, 0::2]  # [L, n, page_size, KV, hd]
        v = pages[:, :, :, 1::2]
        from .integrity import payload_block_checksums

        return {
            "n_blocks": len(ids),
            "start_block": start_block,
            "block_size": self.cfg.block_size,
            "dtype": str(k.dtype),
            # Stored representation metadata: the importer must match (a
            # different quantization scale/dtype would seal wrongly-scaled
            # KV under valid hashes).
            "kv_scale": self._kv_scale_repr(),
            "shape": list(k.shape),
            # Per-block content checksums stamped from the HBM gather (the
            # source of truth) — the importer verifies before sealing, so
            # a wire/staging bit-flip costs one block's recompute instead
            # of fleet-wide poison.  Omit-when-absent on the importer side
            # keeps checksum-less peers servable.
            "checksums": payload_block_checksums(k, v),
            "k": np.ascontiguousarray(k).tobytes(),
            "v": np.ascontiguousarray(v).tobytes(),
        }

    async def inject_blocks(
        self,
        token_ids: List[int],
        payload: Dict[str, Any],
        salt: Optional[str] = None,
        donor: Optional[int] = None,
    ) -> int:
        """Write transferred KV into this engine's cache as sealed blocks.

        ``payload["start_block"]`` supports chunked transfers: chunk k's
        blocks seal under their chained hashes as they arrive, so decode can
        overlap with the remaining chunks' transfer (match_prefix walks from
        block 0, so chunks are useful as soon as their predecessors landed —
        the sender streams them in order).

        When the payload carries per-block ``checksums`` they are VERIFIED
        against the parsed arrays before anything is allocated or sealed
        (the wire integrity boundary — covers cross-worker pull, migration
        push and disagg import alike): the verified prefix seals, the first
        corrupt block and everything after it is dropped and the hash
        negative-cached.  Payloads without checksums (older peers) inject
        unverified — omit-when-absent wire compat.  ``donor`` attributes a
        corrupt payload to its sender for the health watchdog's ledger.

        Returns the number of tokens covered by this injection.  The blocks
        are immediately released to the reuse pool (contents intact), so the
        very next generate() for these tokens admits with a prefix hit — no
        special remote-prefill state in the scheduler.
        """
        self._require_block_moves("KV import")
        from ..tokens import hash_token_blocks

        start = int(payload.get("start_block", 0))
        # Tenant imports (llm/tenancy) seal under the tenant's salted hash
        # chain — the same identity the exporter read them under, so a
        # cross-tenant inject structurally cannot produce a matching hash.
        blocks = hash_token_blocks(token_ids, self.cfg.block_size, salt)[start:]
        n = min(int(payload["n_blocks"]), len(blocks))
        if n == 0:
            return 0
        blocks = blocks[:n]
        # Validate the payload BEFORE allocating: allocation can LRU-evict
        # sealed prefix-cache blocks, and an import that is about to be
        # rejected must never pay that eviction for blocks it frees right
        # back (the freed blocks return anonymous — the evicted contents
        # are gone for nothing).
        if int(payload.get("block_size", self.cfg.block_size)) != self.cfg.block_size:
            # Mismatched layouts would seal misaligned KV under valid hashes
            # — refuse and let the caller prefill locally.
            logger.warning(
                "rejecting KV import: block_size %s != local %s",
                payload.get("block_size"),
                self.cfg.block_size,
            )
            return 0
        local_scale = self._kv_scale_repr()
        if (
            payload.get("dtype", str(jnp.dtype(self.cfg.cache_dtype)))
            != str(jnp.dtype(self.cfg.cache_dtype))
            or not _scales_close(
                payload.get("kv_scale", local_scale), local_scale
            )
        ):
            # Stored-representation mismatch (quantization dtype/scale):
            # importing raw rows would mis-scale the prefix silently.
            logger.warning(
                "rejecting KV import: stored repr %s/scale %s != local %s/%s",
                payload.get("dtype"), payload.get("kv_scale"),
                jnp.dtype(self.cfg.cache_dtype), local_scale,
            )
            return 0
        # Parse/validate the payload ARRAYS before allocating too: a
        # malformed payload (truncated bytes, inconsistent shape) raising
        # after allocate_sequence would leak the freshly-taken blocks AND
        # may already have LRU-evicted sealed contents to take them.
        shape = tuple(payload["shape"])
        name = payload["dtype"]
        dt = jnp.dtype(name)  # ml_dtypes registers bf16/fp8 names
        expected = int(np.prod(shape)) * dt.itemsize
        if len(payload["k"]) != expected or len(payload["v"]) != expected:
            # Byte-length mismatch against the claimed shape: reject before
            # any array is even viewed, let alone copied.
            logger.warning("rejecting KV import: payload bytes != shape")
            return 0
        if shape[1] < n:
            logger.warning(
                "rejecting KV import: payload carries %d pages for n_blocks "
                "%d", shape[1], n,
            )
            return 0
        if not self.kv.would_fit(blocks, n):
            # Destination-budget reject-early: an import the block pool
            # cannot take must fail BEFORE the interleave below stages a
            # payload-sized copy in host RAM (and before allocation could
            # evict sealed contents it frees right back).
            logger.warning(
                "rejecting KV import: %d blocks exceed free KV capacity", n
            )
            return 0
        try:
            k = np.frombuffer(payload["k"], dtype=dt).reshape(shape)[:, :n]
            v = np.frombuffer(payload["v"], dtype=dt).reshape(shape)[:, :n]
        except ValueError:
            logger.warning("rejecting KV import: malformed payload arrays")
            return 0
        from ..runtime.faultinject import faults

        if faults.enabled and faults.should("kv_corrupt", "wire"):
            # Chaos hook: flip one byte of the staged K payload — models a
            # wire/staging bit-flip the structural checks cannot see.
            from .integrity import flip_array_byte

            k = flip_array_byte(k)
        sums = payload.get("checksums")
        if sums is not None:
            # The wire integrity boundary: verify every block BEFORE the
            # interleave copy (and long before allocation/sealing).  The
            # verified prefix stays usable; the first corrupt block
            # truncates the import — its chained descendants are
            # unreachable without it, so nothing poisoned can ever seal.
            from ..llm.metrics import kv_integrity_metrics
            from .integrity import payload_block_checksums

            got = payload_block_checksums(k, v)
            valid = n
            for i in range(n):
                if i >= len(sums) or int(sums[i]) != got[i]:
                    valid = i
                    break
            kv_integrity_metrics.verified_total["wire"] += valid
            if valid < n:
                self._record_corruption(
                    "wire", blocks[valid].sequence_hash, donor=donor
                )
                self._flush_tier_events()
                logger.warning(
                    "KV import failed checksum at block %d/%d; sealing the "
                    "verified prefix only", valid, n,
                )
                n = valid
                if n == 0:
                    return 0
                blocks = blocks[:n]
                k = k[:, :n]
                v = v[:, :n]
        # Interleave back to combined pages [L, n, ps, 2KV, hd] (K even).
        comb = np.stack([k, v], axis=4).reshape(
            k.shape[0], n, k.shape[2], 2 * k.shape[3], k.shape[4]
        )
        alloc = self.kv.allocate_sequence(blocks, n, count_hits=False)
        if alloc is None:
            return 0  # no capacity; caller falls back to local prefill
        ids, cached = alloc
        # Pad the page count to a power-of-two bucket so _inject_fn compiles
        # once per bucket, not once per distinct imported prompt length.
        pad = 1 << max(0, (n - 1).bit_length())
        page_ids = np.full((pad,), self.cfg.num_blocks, np.int32)  # OOB pad
        page_ids[:n] = ids
        comb_p = np.zeros(comb.shape[:1] + (pad,) + comb.shape[2:], comb.dtype)
        comb_p[:, :n] = comb

        try:
            async with self._device_lock:
                # Lock-HOLD wall only (t0 inside the lock — queueing behind a
                # decode chunk is the scheduler working as intended, not import
                # cost): the decode/transfer-overlap contract is that an import
                # never blocks decode longer than ONE chunk's scatter
                # (tests/test_disagg.py overlap test reads this).
                t0 = time.perf_counter()
                # Publish under the device lock (broadcast order == enqueue
                # order; see _run_unified).
                if self._publisher is not None:
                    await self._publisher.publish("inject", (page_ids, comb_p))
                # to_thread: compile/execute must not stall the engine loop.
                self.cache = await asyncio.to_thread(
                    self._inject_fn, self.cache, *self._prep((page_ids, comb_p))
                )
                hold = time.perf_counter() - t0
        except BaseException:
            # Mid-transfer failure: the blocks were never sealed — return
            # them to the pool instead of leaking them as allocated-forever
            # scratch, then surface the error (the sender retries/drops and
            # the decode side's timeout falls back to local prefill).
            self.kv.free_sequence(ids)
            raise
        self.step_trace.append(("inject", hold, n, 0))
        for bid, tb in zip(ids, blocks):
            self.kv.seal_block(bid, tb)
        self.kv.free_sequence(ids)
        return n * self.cfg.block_size

    async def inject_blocks_from_device(
        self,
        token_ids: List[int],
        pages_dev,
        n: int,
        start_block: int = 0,
        salt: Optional[str] = None,
    ) -> int:
        """Seal ``n`` transferred blocks whose pages are ALREADY on device
        (the ICI/device_put fast path — no host staging).  ``pages_dev`` is
        [L, pad, ps, 2KV, hd] with the first n slots valid."""
        self._require_block_moves("device-to-device KV import")
        from ..tokens import hash_token_blocks

        if jax.process_count() > 1:
            # Device handles can't cross the leader/follower broadcast; the
            # host-staged inject_blocks path handles multi-host transfers.
            return 0
        blocks = hash_token_blocks(token_ids, self.cfg.block_size, salt)[
            start_block:
        ]
        n = min(n, len(blocks))
        if n == 0:
            return 0
        # Validate config/capacity BEFORE allocating (mirror of the host
        # path's fix): a mismatched layout would seal wrong KV under valid
        # hashes, and a doomed allocation must never LRU-evict sealed
        # contents it immediately frees back.  transfer_blocks_device checks
        # these on the source side too, but this entry point is public
        # (disagg transfer_direct) and must be safe on its own.
        if (
            pages_dev.ndim != 5
            or pages_dev.shape[0] != self.cache.pages.shape[0]
            or pages_dev.shape[1] < n
            or pages_dev.shape[2:] != self.cache.pages.shape[2:]
            or pages_dev.dtype != self.cache.pages.dtype
        ):
            logger.warning(
                "rejecting device KV import: pages %s/%s vs local cache %s/%s",
                getattr(pages_dev, "shape", None), pages_dev.dtype,
                self.cache.pages.shape, self.cache.pages.dtype,
            )
            return 0
        alloc = self.kv.allocate_sequence(blocks[:n], n, count_hits=False)
        if alloc is None:
            return 0
        ids, _ = alloc
        pad = pages_dev.shape[1]
        page_ids = np.full((pad,), self.cfg.num_blocks, np.int32)  # OOB pad
        page_ids[:n] = ids
        try:
            async with self._device_lock:
                t0 = time.perf_counter()  # lock HOLD, not wait (see inject_blocks)
                self.cache = await asyncio.to_thread(
                    self._inject_fn, self.cache, page_ids, pages_dev
                )
                hold = time.perf_counter() - t0
        except BaseException:
            self.kv.free_sequence(ids)  # roll back: blocks never sealed
            raise
        self.step_trace.append(("inject", hold, n, 0))
        for bid, tb in zip(ids, blocks[:n]):
            self.kv.seal_block(bid, tb)
        self.kv.free_sequence(ids)
        return n * self.cfg.block_size

    def _pin_prefix(self, token_ids: List[int], salt: Optional[str] = None):
        """Take references on the resident prefix blocks of ``token_ids``
        (see generate(): keeps pre-admission sp/restore work alive)."""
        from ..tokens import hash_token_blocks

        return self.kv.acquire_prefix(
            hash_token_blocks(token_ids, self.cfg.block_size, salt)
        )

async def transfer_blocks_device(
    src: TpuEngine, dst: TpuEngine, token_ids, salt: Optional[str] = None
) -> int:
    """Co-located prefill→decode KV transfer that never stages in host RAM:
    device gather from the source cache → ``jax.device_put`` onto the
    destination's sharding → in-place scatter.  On one chip this is an HBM
    copy; across chips of a shared slice the put rides ICI — the reference's
    NIXL/GPUDirect block path (SURVEY §2.6) for same-slice deployments.
    Returns tokens covered (the longest resident prefix run)."""
    src._require_block_moves("device-to-device KV transfer")
    dst._require_block_moves("device-to-device KV transfer")
    from ..tokens import hash_token_blocks

    if jax.process_count() > 1:
        return 0  # same single-process restriction as export_prompt_blocks
    if src.cfg.block_size != dst.cfg.block_size:
        return 0
    if src.cache.pages.shape[0] != dst.cache.pages.shape[0]:
        return 0  # different layer counts: not the same model
    if src.cache.pages.dtype != dst.cache.pages.dtype or not _scales_close(
        src._kv_scale_repr(), dst._kv_scale_repr()
    ):
        return 0  # stored representation differs: host path will also refuse
    blocks = hash_token_blocks(token_ids, src.cfg.block_size, salt)
    src_ids: List[int] = []
    for tb in blocks:
        bid = src.kv._by_hash.get(tb.sequence_hash)
        if bid is None:
            break
        src_ids.append(bid)
    if not src_ids:
        return 0
    n = len(src_ids)
    pad = 1 << max(0, (n - 1).bit_length())
    gather_ids = np.zeros((pad,), np.int32)
    gather_ids[:n] = src_ids
    async with src._device_lock:
        pages = await asyncio.to_thread(src._gather_fn, src.cache, gather_ids)
    if dst.mesh is not None:
        pages = jax.device_put(
            pages, jax.tree_util.tree_leaves(dst.cache)[0].sharding
        )
    elif pages.devices() != dst.cache.pages.devices():
        pages = jax.device_put(pages, next(iter(dst.cache.pages.devices())))
    return await dst.inject_blocks_from_device(token_ids, pages, n, salt=salt)
