"""Paged KV block allocator with hash-based prefix reuse + event emission.

Reference semantics (not code): lib/llm/src/kv/{reuse,reserved,manager}.rs —
freed blocks *retain their contents* and sit in a reuse pool keyed by chained
sequence hash; a new request first matches its prompt's block hashes against
live ("inflight") blocks, then the reuse pool, and only then takes fresh
blocks (evicting the coldest reusable ones).  Every store/evict emits a
``KvCacheEvent`` so the router's index mirrors this pool exactly.

Host-side bookkeeping only — the device never sees hashes, just block ids.
Physical block order is irrelevant to the device (attention gathers via block
tables), so allocation never copies anything in HBM.

A family whose recurrent state lives in SLOTS beside the pages
(models/mamba2.py; docs/granite_hybrid.md) keeps them under this manager too:
``live_slots`` slots of which a running row owns one from admission to
retirement or preemption, and ``snapshot_slots`` slots each attached to the
sealed block at whose end its copy of the state was taken.  A prefix is
resumable where such a block is; a snapshot is freed with its block and
dropped first, least recently used, when the pool is full: a block without
its snapshot is not resumable, never wrong.

A family with layers that keep a WINDOW of the last positions only
(models/lfm2.py ``sliding_attention``; docs/k_exaone.md) gets a SECOND page
pool under this manager: ``window_pages`` pages of which a running row holds
the few its next query's window reaches (``take_window_page`` as it grows,
``release_window`` as pages fall behind), whatever its length.  The pages
before a resume point are RETAINED with that block's hash exactly as a
snapshot is (``retain_window``: evictable least recently used, dropped with
the block), and ``resumable`` cuts a hit back to the last block whose window
pages are still whole.  Pages are shared by count: a retained page a row
resumed from is both.  Admission counts both pools (``would_fit``): a running
row may hold ``window_row_pages`` pages, everything else can be evicted, so a
row that was admitted never finds the pool empty.
"""

from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Set, Tuple

from ..llm.kv_router.protocols import (
    KvCacheEvent,
    KvCacheStoredBlockData,
)
from ..llm.metrics import ssm_metrics, swa_metrics
from ..tokens import TokenBlock


@dataclass
class _Block:
    id: int
    ref_count: int = 0
    sequence_hash: Optional[int] = None  # contents identity (None = scratch)
    parent_hash: Optional[int] = None
    tokens_hash: Optional[int] = None


EventCallback = Callable[[KvCacheEvent], None]


class KvBlockManager:
    """Fixed pool of ``num_blocks`` physical blocks of ``block_size`` tokens.

    States a block moves through:
      free+anonymous → active (ref>0) → [sealed w/ hash] → free+reusable
      (contents intact, matchable) → evicted (hash dropped, Removed emitted)
      → active again.
    """

    def __init__(
        self,
        num_blocks: int,
        block_size: int,
        event_callback: Optional[EventCallback] = None,
        enable_prefix_caching: bool = True,
        live_slots: int = 0,
        snapshot_slots: int = 0,
        window_pages: int = 0,
        window_tokens: int = 0,
        window_row_pages: int = 0,
    ):
        # State slots (module docstring): live slots are ids [0, live_slots),
        # snapshot slots [live_slots, live_slots + snapshot_slots).
        self.live_slots = live_slots
        self.snapshot_slots = snapshot_slots
        self._live_free: List[int] = list(range(live_slots - 1, -1, -1))
        self._snap_free: List[int] = list(range(live_slots + snapshot_slots - 1,
                                                live_slots - 1, -1))
        # block id -> its snapshot's slot, least recently used first.
        self._snap_of: "OrderedDict[int, int]" = OrderedDict()
        self._snap_pins: Dict[int, int] = {}  # slot -> rows about to read it
        # The window pool (module docstring): ``window_tokens`` positions a
        # window layer keeps, so ``window_blocks`` pages before a resume point.
        self.window_pages = window_pages
        self.window_tokens = window_tokens
        self.window_row_pages = window_row_pages
        self.window_blocks = -(-(window_tokens - 1) // block_size) if window_pages else 0
        self.window_rows = 0  # running rows: each may hold window_row_pages pages
        self._win_free: List[int] = list(range(window_pages - 1, -1, -1))
        self._win_rows = [0] * window_pages  # references held by rows
        self._win_kept = [0] * window_pages  # references held by retained entries
        self._win_live = self._win_retained = 0  # pages a row holds; pages only retained
        # block id -> the window pages before its end, least recently used first.
        self._win_of: "OrderedDict[int, Tuple[int, ...]]" = OrderedDict()
        self.num_blocks = num_blocks
        self.block_size = block_size
        self._blocks = [_Block(i) for i in range(num_blocks)]
        # Free anonymous blocks (no reusable contents), FIFO.
        self._free_anon: List[int] = list(range(num_blocks))
        # Free blocks with reusable contents, LRU-ordered (oldest first).
        self._free_reusable: "OrderedDict[int, None]" = OrderedDict()
        # seq_hash → block id, for any block (active or free) holding it.
        self._by_hash: Dict[int, int] = {}
        self._event_callback = event_callback
        self._event_id = 0
        self._enable_prefix_caching = enable_prefix_caching
        # Tiered KV cache (engine/{host_cache,disk_cache}.py): maps a
        # sequence hash to the lower tier still holding its contents
        # ("host"/"disk") or None.  When set, HBM eviction of a block a
        # lower tier retains emits a TIER-TAGGED event instead of Removed —
        # the router keeps scoring the worker for that prefix, discounted
        # by restore cost, instead of forgetting it.
        self.tier_lookup: Optional[Callable[[int], Optional[str]]] = None
        # cumulative counters for metrics
        self.lookup_blocks = 0
        self.matched_blocks = 0

    # ------------------------------------------------------------------ stats
    @property
    def free_blocks(self) -> int:
        return len(self._free_anon) + len(self._free_reusable)

    @property
    def active_blocks(self) -> int:
        return self.num_blocks - self.free_blocks

    @property
    def usage(self) -> float:
        return self.active_blocks / self.num_blocks if self.num_blocks else 0.0

    @property
    def hit_rate(self) -> float:
        return self.matched_blocks / self.lookup_blocks if self.lookup_blocks else 0.0

    # ------------------------------------------------------------ state slots
    def take_live_slot(self) -> Optional[int]:
        slot = self._live_free.pop() if self._live_free else None
        self._slot_gauges()
        return slot

    def free_live_slot(self, slot: int) -> None:
        self._live_free.append(slot)
        self._slot_gauges()

    def _slot_gauges(self) -> None:
        ssm_metrics.slots_in_use = {
            "live": self.live_slots - len(self._live_free), "snapshot": len(self._snap_of)}

    def resumable(self, block_ids: Sequence[int], below: int):
        """(n, start): the longest run ``block_ids[:n]`` of a matched prefix
        that ends at a block holding what a row needs to go on from there and
        covers fewer than ``below`` tokens (a prompt's last token is always
        computed).  ``start`` is a snapshot's slot, (0, -1) where there is
        none; under a window pool the window pages before the block's end,
        (0, ()) where none are whole.  Either is PINNED until
        ``unpin_snapshot``: the slot is handed to nobody by
        ``reserve_snapshot`` until the row's first step is enqueued, and the
        pages are referenced for the row, which keeps them as its own."""
        for n in range(min(len(block_ids), (below - 1) // self.block_size), 0, -1):
            bid = block_ids[n - 1]
            if self.window_pages:
                pages = self._win_of.get(bid)
                if pages is not None:
                    self._win_of.move_to_end(bid)
                    for p in pages:
                        self._win_ref(p, rows=1)
                    return n, pages
                continue
            slot = self._snap_of.get(bid)
            if slot is not None:
                self._snap_of.move_to_end(bid)
                self._snap_pins[slot] = self._snap_pins.get(slot, 0) + 1
                return n, slot
        return (0, ()) if self.window_pages else (0, -1)

    def unpin_snapshot(self, slot) -> None:
        if isinstance(slot, tuple):  # window pages referenced by ``resumable``
            self.release_window(slot)
            return
        left = self._snap_pins.get(slot, 0) - 1
        if left > 0:
            self._snap_pins[slot] = left
        else:
            self._snap_pins.pop(slot, None)

    def has_snapshot(self, seq_hash: int) -> bool:
        return self._by_hash.get(seq_hash) in self._snap_of

    def reserve_snapshot(self) -> int:
        """A slot for a snapshot about to be taken: a free one, else the least
        recently used snapshot's that no admitted row is about to read, else
        -1.  The caller attaches it (``attach_snapshot``) once its block is
        sealed."""
        if self._snap_free:
            return self._snap_free.pop()
        for bid, slot in self._snap_of.items():
            if slot not in self._snap_pins:
                del self._snap_of[bid]
                ssm_metrics.snapshots["evicted"] += 1
                return slot
        ssm_metrics.snapshots["no_slot"] += 1
        return -1

    def free_snapshot(self, slot: int) -> None:
        """A reserved slot that no step wrote goes back to the pool."""
        self._snap_free.append(slot)

    def attach_snapshot(self, seq_hash: int, slot: int) -> None:
        """``slot`` holds the state at the end of the sealed block of
        ``seq_hash``.  Where that block is gone already or has a snapshot
        (two rows computed the same prefix side by side) the slot goes back."""
        bid = self._by_hash.get(seq_hash)
        if bid is None or bid in self._snap_of:
            self.free_snapshot(slot)
        else:
            self._snap_of[bid] = slot
            ssm_metrics.snapshots["taken"] += 1
        self._slot_gauges()

    # ------------------------------------------------------------ window pool
    def _win_ref(self, page: int, rows: int = 0, kept: int = 0) -> None:
        """Add references to ``page``; one that loses its last goes back to
        the pool.  Keeps the gauge's counts as it goes."""
        r0, k0 = self._win_rows[page], self._win_kept[page]
        r1, k1 = r0 + rows, k0 + kept
        self._win_rows[page], self._win_kept[page] = r1, k1
        self._win_live += (r1 > 0) - (r0 > 0)
        self._win_retained += (r1 == 0 and k1 > 0) - (r0 == 0 and k0 > 0)
        if r1 == 0 and k1 == 0:
            self._win_free.append(page)
        swa_metrics.pool_pages.update(
            live=self._win_live, retained=self._win_retained, free=len(self._win_free))

    def window_fits(self) -> bool:
        """Room for one more running row in the window pool."""
        return (self.window_rows + 1) * self.window_row_pages <= self.window_pages

    def take_window_page(self) -> int:
        """A page for a running row, dropping retained pages least recently
        used while none is free.  An admitted row always finds one."""
        while not self._win_free and self._win_of:
            self._drop_window(next(iter(self._win_of)))
        page = self._win_free.pop()
        self._win_ref(page, rows=1)
        return page

    def release_window(self, pages: Sequence[int]) -> None:
        for p in pages:
            self._win_ref(p, rows=-1)

    def retain_window(self, seq_hash: int, pages: Sequence[int]) -> None:
        """``pages`` hold the window layers' K/V of the positions before the
        end of the sealed block of ``seq_hash``: kept with that block, as a
        snapshot is.  Nothing where the block is gone or has them already."""
        bid = self._by_hash.get(seq_hash)
        if bid is None or bid in self._win_of:
            return
        self._win_of[bid] = tuple(pages)
        for p in pages:
            self._win_ref(p, kept=1)

    def has_window(self, seq_hash: int) -> bool:
        return self._by_hash.get(seq_hash) in self._win_of

    def _drop_window(self, block_id: int) -> None:
        for p in self._win_of.pop(block_id, ()):
            self._win_ref(p, kept=-1)

    def _drop_snapshot(self, block_id: int) -> None:
        self._drop_window(block_id)
        slot = self._snap_of.pop(block_id, None)
        if slot is not None:
            self._snap_free.append(slot)
            ssm_metrics.snapshots["evicted"] += 1
            self._slot_gauges()

    # ----------------------------------------------------------------- events
    def _emit(self, event: KvCacheEvent) -> None:
        if self._event_callback is not None:
            self._event_callback(event)

    def _next_event_id(self) -> int:
        self._event_id += 1
        return self._event_id

    def emit_tiered(self, tier: str, block_hashes: Sequence[int]) -> None:
        """Publish a tier change for blocks this manager does not hold in
        HBM (host→disk demotion, disk→host promotion) — the engine's tier
        stores have no event plane of their own."""
        if block_hashes and self._enable_prefix_caching:
            self._emit(
                KvCacheEvent.tiered(
                    self._next_event_id(), tier, list(block_hashes)
                )
            )

    def emit_removed(self, block_hashes: Sequence[int]) -> None:
        """Publish the loss of blocks evicted from the LAST tier holding
        them (see emit_tiered)."""
        if block_hashes and self._enable_prefix_caching:
            self._emit(
                KvCacheEvent.removed(self._next_event_id(), list(block_hashes))
            )

    # ------------------------------------------------------------- allocation
    def match_prefix(self, token_blocks: Sequence[TokenBlock]) -> List[int]:
        """Longest run of leading blocks already resident; returns block ids
        (does NOT take references — pair with allocate_sequence)."""
        matched: List[int] = []
        if not self._enable_prefix_caching:
            return matched
        for tb in token_blocks:
            bid = self._by_hash.get(tb.sequence_hash)
            if bid is None:
                break
            matched.append(bid)
        return matched

    def would_fit(
        self,
        token_blocks: Sequence[TokenBlock],
        num_blocks_needed: int,
        matched: Optional[List[int]] = None,
    ) -> bool:
        """Dry-run of allocate_sequence's capacity check (no side effects,
        no counter updates).  The fused-decode admission gate polls this —
        keeping the math here means it can never drift from real admission.
        ``matched`` lets a caller that already ran match_prefix skip the
        second walk."""
        if matched is None:
            matched = self.match_prefix(token_blocks)
        fresh_needed = num_blocks_needed - len(matched)
        # Matched blocks sitting in the reuse pool get revived and stop
        # counting as free, so subtract them from available capacity.
        revived = sum(1 for b in matched if self._blocks[b].ref_count == 0)
        if self.window_pages and not self.window_fits():
            return False  # both pools: a row that fits one and not the other waits
        return fresh_needed <= self.free_blocks - revived

    def allocate_sequence(
        self,
        token_blocks: Sequence[TokenBlock],
        num_blocks_needed: int,
        count_hits: bool = True,
        share: Optional[int] = None,
    ) -> Optional[Tuple[List[int], int]]:
        """Allocate ``num_blocks_needed`` blocks for a prompt whose complete
        blocks are ``token_blocks`` (hashed).  Leading blocks already resident
        are shared (ref++) instead of recomputed.

        ``count_hits=False`` skips the hit-rate counters — transfer-plane
        injections (inject_blocks) are bookkeeping, not request admissions,
        and counting them would skew gpu_prefix_cache_hit_rate the same way
        acquire_prefix's docstring warns about pinning.

        ``share``: share at most that many of the resident leading blocks (a
        hit cut back to where the sequence can be resumed); the others are
        computed again into fresh blocks.

        Returns (block_ids, num_cached_tokens) or None if out of capacity.
        """
        matched = self.match_prefix(token_blocks)[:share]
        if count_hits:
            self.lookup_blocks += len(token_blocks)
            self.matched_blocks += len(matched)
        if not self.would_fit(token_blocks, num_blocks_needed, matched):
            return None
        fresh_needed = num_blocks_needed - len(matched)
        ids: List[int] = []
        for bid in matched:
            blk = self._blocks[bid]
            if blk.ref_count == 0:
                self._free_reusable.pop(bid, None)  # revive from reuse pool
            blk.ref_count += 1
            ids.append(bid)
        for _ in range(fresh_needed):
            bid = self._take_free_block()
            if bid is None:  # rollback
                self.free_sequence(ids)
                return None
            self._blocks[bid].ref_count = 1
            ids.append(bid)
        return ids, len(matched) * self.block_size

    def acquire_prefix(self, token_blocks: Sequence[TokenBlock]) -> Optional[List[int]]:
        """Take references on the resident leading blocks WITHOUT touching
        the hit-rate counters (pre-admission pinning is bookkeeping, not a
        cache lookup — counting it would double-count every pinned prefix
        and inflate gpu_prefix_cache_hit_rate)."""
        matched = self.match_prefix(token_blocks)
        if not matched:
            return None
        ids: List[int] = []
        for bid in matched:
            blk = self._blocks[bid]
            if blk.ref_count == 0:
                self._free_reusable.pop(bid, None)
            blk.ref_count += 1
            ids.append(bid)
        return ids

    def allocate_block(self) -> Optional[int]:
        """One fresh anonymous block (decode growth)."""
        bid = self._take_free_block()
        if bid is not None:
            self._blocks[bid].ref_count = 1
        return bid

    def evict_hashes(self, seq_hashes: Sequence[int]) -> int:
        """Force-evict specific REUSABLE (ref==0, sealed-hash) blocks as if
        allocation pressure had recycled them: contents forgotten, the
        tier-aware Removed/tiered event emitted, the block returned to the
        anonymous pool.  Deterministic HBM-pressure simulation for chaos /
        bench harnesses (benchmarks/goodput.py L7 storm) — the real LRU
        path runs end to end, so event semantics cannot drift from organic
        eviction.  Active (referenced) blocks are never touched."""
        n = 0
        for h in list(seq_hashes):
            bid = self._by_hash.get(h)
            if bid is None:
                continue
            blk = self._blocks[bid]
            if blk.ref_count > 0 or bid not in self._free_reusable:
                continue
            # Rotate the victim to the LRU head and mask the anonymous
            # pool (the allocator prefers it); _take_free_block then
            # evicts exactly this block through the ordinary path.
            self._free_reusable.move_to_end(bid, last=False)
            anon, self._free_anon = self._free_anon, []
            try:
                got = self._take_free_block()
            finally:
                self._free_anon = anon
            if got is not None:
                self._free_anon.append(got)
                n += 1
        return n

    def _take_free_block(self) -> Optional[int]:
        if self._free_anon:
            return self._free_anon.pop()
        if self._free_reusable:
            bid, _ = self._free_reusable.popitem(last=False)  # LRU evict
            blk = self._blocks[bid]
            self._drop_snapshot(bid)
            if blk.sequence_hash is not None:
                self._by_hash.pop(blk.sequence_hash, None)
                # Tiered cache: a lower tier still holding the contents
                # demotes the router's view instead of erasing it.
                tier = (
                    self.tier_lookup(blk.sequence_hash)
                    if self.tier_lookup is not None
                    else None
                )
                if tier is not None:
                    self._emit(
                        KvCacheEvent.tiered(
                            self._next_event_id(), tier, [blk.sequence_hash]
                        )
                    )
                else:
                    self._emit(
                        KvCacheEvent.removed(
                            self._next_event_id(), [blk.sequence_hash]
                        )
                    )
            blk.sequence_hash = blk.parent_hash = blk.tokens_hash = None
            return bid
        return None

    # ---------------------------------------------------------------- sealing
    def seal_block(self, block_id: int, token_block: TokenBlock) -> None:
        """Mark a block's contents complete + reusable; emits Stored.

        Called when prefill writes a full block or decode fills one up.  If
        another block already holds this hash (a race between two identical
        prompts), the newer block stays anonymous (no double-publish).
        """
        if not self._enable_prefix_caching:
            return
        blk = self._blocks[block_id]
        if token_block.sequence_hash in self._by_hash:
            return
        blk.sequence_hash = token_block.sequence_hash
        blk.parent_hash = token_block.parent_hash
        blk.tokens_hash = token_block.block_hash
        self._by_hash[token_block.sequence_hash] = block_id
        self._emit(
            KvCacheEvent.stored(
                self._next_event_id(),
                token_block.parent_hash,
                [
                    KvCacheStoredBlockData(
                        block_hash=token_block.sequence_hash,
                        tokens_hash=token_block.block_hash,
                    )
                ],
            )
        )

    # ---------------------------------------------------------------- freeing
    def free_sequence(self, block_ids: Sequence[int]) -> None:
        """Release references; blocks with hashes park in the reuse pool
        (contents intact), anonymous ones return to the free list."""
        # Tail blocks are appended to the reuse pool first so eviction
        # (oldest-first popitem) consumes a sequence tail-before-head: heads
        # are the shareable prefixes and must outlive their tails, otherwise
        # match_prefix (which stops at the first missing block) can never
        # reach the surviving tail blocks.
        for bid in reversed(list(block_ids)):
            blk = self._blocks[bid]
            blk.ref_count -= 1
            if blk.ref_count > 0:
                continue
            if blk.sequence_hash is not None:
                self._free_reusable[bid] = None
            else:
                self._free_anon.append(bid)

    def clear(self) -> None:
        """Drop everything (emits Cleared)."""
        for blk in self._blocks:
            blk.ref_count = 0
            blk.sequence_hash = blk.parent_hash = blk.tokens_hash = None
        self._free_anon = list(range(self.num_blocks))
        self._free_reusable.clear()
        self._by_hash.clear()
        for bid in list(self._snap_of) + list(self._win_of):
            self._drop_snapshot(bid)
        self._emit(KvCacheEvent(self._next_event_id(), None))
