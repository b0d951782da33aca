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

What a family keeps BESIDE the pages (recurrent state in slots, the pages of
window layers: engine/resume.py; docs/granite_hybrid.md, "State beside the
pages") lives under this manager too, in ``UnitPool``s: a fixed range of units
that running rows hold and that sealed blocks keep as the place a later hit may
resume from, least recently used first.  A block's kept units go with the
block (``_take_free_block``); ``would_fit`` asks the family's object whether
one more row has room there too.
"""

from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Set, Tuple

from ..llm.kv_router.protocols import (
    KvCacheEvent,
    KvCacheStoredBlockData,
)
from ..tokens import TokenBlock
from .resume import Beside


class UnitPool:
    """Units ``first .. first + size - 1`` (state slots, pages of a second
    pool).  A unit is free, HELD by running rows (a count), KEPT with a sealed
    block as the place a later hit may resume from (a count), or both.
    ``publish(dropped)`` is called after every change with the kept entries it
    dropped: the ONE place the gauges are written from."""

    def __init__(self, first: int, size: int, publish: Optional[Callable[[int], None]] = None):
        self.first, self.size = first, size
        self._free: List[int] = list(range(first + size - 1, first - 1, -1))
        self._rows = [0] * size  # references held by rows
        self._kept = [0] * size  # references held by kept entries
        # block id -> the units kept with it, least recently used first.
        self._of: "OrderedDict[int, Tuple[int, ...]]" = OrderedDict()
        self.held = self.kept_only = 0  # units a row holds; units only kept
        self._publish = publish or (lambda dropped: None)

    free = property(lambda self: len(self._free))
    entries = property(lambda self: len(self._of))

    def __contains__(self, block_id: Optional[int]) -> bool:
        return block_id in self._of

    def _ref(self, units: Sequence[int], rows: int = 0, kept: int = 0) -> None:
        """Add references; a unit that loses its last goes back to the pool."""
        for u in units:
            i = u - self.first
            r0, k0 = self._rows[i], self._kept[i]
            r1, k1 = r0 + rows, k0 + kept
            self._rows[i], self._kept[i] = r1, k1
            self.held += (r1 > 0) - (r0 > 0)
            self.kept_only += (r1 == 0 and k1 > 0) - (r0 == 0 and k0 > 0)
            if r1 == 0 and k1 == 0:
                self._free.append(u)

    def take(self) -> Optional[int]:
        """A unit for a row, which holds it: a free one, else one of the least
        recently used kept entry that gives any back (an entry whose every
        unit a row holds is skipped: dropping it frees nothing and loses a
        resume point), else None."""
        dropped = 0
        while not self._free:
            gives = (b for b, units in self._of.items() if any(
                self._rows[u - self.first] == 0 and self._kept[u - self.first] == 1 for u in units))
            victim = next(gives, None)
            if victim is None:
                break
            self._ref(self._of.pop(victim), kept=-1)
            dropped += 1
        unit = self._free.pop() if self._free else None
        if unit is not None:
            self._ref((unit,), rows=1)
        self._publish(dropped)
        return unit

    def release(self, units: Sequence[int]) -> None:
        """Rows' references to ``units`` go."""
        self._ref(units, rows=-1)
        self._publish(0)

    def keep(self, block_id: int, units: Sequence[int]) -> bool:
        """``units`` hold what a row resumed at the end of sealed block
        ``block_id`` needs; False (and nothing kept) where it has an entry."""
        if block_id in self._of:
            return False
        self._of[block_id] = tuple(units)
        self._ref(units, kept=1)
        self._publish(0)
        return True

    def resume(self, block_ids: Sequence[int]) -> Tuple[int, Tuple[int, ...]]:
        """(n, units): the longest run ``block_ids[:n]`` that ends at a block
        with an entry, now the most recently used, whose units are HELD for
        the row that resumes there until it ``release``s them; (0, ()) where
        no block has one."""
        for n in range(len(block_ids), 0, -1):
            units = self._of.get(block_ids[n - 1])
            if units is not None:
                self._of.move_to_end(block_ids[n - 1])
                self._ref(units, rows=1)
                self._publish(0)
                return n, units
        return 0, ()

    def drop(self, block_id: Optional[int] = None) -> None:
        """The entry of ``block_id`` goes, if it has one (no id: every entry)."""
        gone = list(self._of) if block_id is None else [block_id] * (block_id in self._of)
        for bid in gone:
            self._ref(self._of.pop(bid), kept=-1)
        if gone:
            self._publish(len(gone))


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
        beside: Optional[Beside] = None,
    ):
        # What the family keeps beside the pages (engine/resume.py), and the
        # pools it made for it here.
        self.pools: List[UnitPool] = []
        self.beside = beside if beside is not None else Beside()
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
        self.enable_prefix_caching = enable_prefix_caching
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
        self.beside.bind(self)

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

    # ------------------------------------------------------ beside the pages
    def add_pool(self, first: int, size: int, publish=None) -> UnitPool:
        """A pool of units whose kept entries go with this manager's blocks."""
        self.pools.append(UnitPool(first, size, publish))
        return self.pools[-1]

    def block_of(self, seq_hash: int) -> Optional[int]:
        """The block that holds the sealed contents ``seq_hash``, or None."""
        return self._by_hash.get(seq_hash)

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
        if block_hashes and self.enable_prefix_caching:
            self._emit(
                KvCacheEvent.tiered(
                    self._next_event_id(), tier, list(block_hashes)
                )
            )

    def emit_removed(self, block_hashes: Sequence[int]) -> None:
        """Publish the loss of blocks evicted from the LAST tier holding
        them (see emit_tiered)."""
        if block_hashes and self.enable_prefix_caching:
            self._emit(
                KvCacheEvent.removed(self._next_event_id(), list(block_hashes))
            )

    # ------------------------------------------------------------- allocation
    def match_prefix(self, token_blocks: Sequence[TokenBlock]) -> List[int]:
        """Longest run of leading blocks already resident; returns block ids
        (does NOT take references — pair with allocate_sequence)."""
        matched: List[int] = []
        if not self.enable_prefix_caching:
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
        if not self.beside.fits():
            return False  # a row that fits the pages and not what lies beside them waits
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
            for pool in self.pools:
                pool.drop(bid)
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
        if not self.enable_prefix_caching:
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
        for pool in self.pools:
            pool.drop()
        self._emit(KvCacheEvent(self._next_event_id(), None))
