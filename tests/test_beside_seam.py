"""The seam for state beside the K/V pages (engine/resume.py): the ONE pool
class under ``KvBlockManager`` held to one contract over its three
instantiations, and a TOY third kind, defined here, that the scheduler and the
step builder serve without an edit to either.  Host bookkeeping only: no model,
no device.
"""

import types

import numpy as np
import pytest

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.kv_manager import KvBlockManager
from dynamo_tpu.engine.pipeline import DecodePipelineMixin
from dynamo_tpu.engine.resume import Beside, SlotState, WindowPages
from dynamo_tpu.engine.scheduler import RowSlots, Scheduler, SequenceState
from dynamo_tpu.tokens import TokenBlockSequence, hash_token_blocks

BS = 4


# ------------------------------------------------------------ the pool class
def sealed_manager(beside):
    """A manager whose 8 blocks are sealed (a 32-token document) and free."""
    kv = KvBlockManager(8, BS, beside=beside)
    blocks = hash_token_blocks(list(range(32)), BS, None)
    ids, _ = kv.allocate_sequence(blocks, 8)
    for bid, tb in zip(ids, blocks):
        kv.seal_block(bid, tb)
    kv.free_sequence(ids)
    return kv, ids, blocks


POOLS = {  # name -> (its kind, how to find it there): six units each
    "live": (lambda: SlotState(6, 3, 16), lambda kind: kind.live),
    "snapshot": (lambda: SlotState(2, 6, 16), lambda kind: kind.snapshots),
    "window": (lambda: WindowPages(6, 8, 3, 16), lambda kind: kind.pool),
}


def counts(pool):
    assert pool.held + pool.kept_only + pool.free == pool.size
    return pool.held, pool.kept_only, pool.free


def take_until_empty(pool, kv, ids, blocks):
    got = [pool.take() for _ in range(6)]
    assert sorted(got) == list(range(pool.first, pool.first + 6)) and got[0] == pool.first
    assert pool.take() is None and counts(pool) == (6, 0, 0)
    pool.release(got[:2])
    assert counts(pool) == (4, 0, 2) and pool.take() == got[1]  # the last given back


def kept_entries_go_least_recently_used_first(pool, kv, ids, blocks):
    units = [pool.take() for _ in range(6)]
    for k in range(3):
        assert pool.keep(ids[k], units[2 * k:2 * k + 2])
    assert not pool.keep(ids[0], units[4:])  # a block has ONE entry
    pool.release(units)
    assert counts(pool) == (0, 6, 0) and pool.entries == 3
    assert pool.resume(ids[:1]) == (1, tuple(units[0:2]))  # touched: now the newest
    pool.release(units[0:2])
    assert pool.take() in units[2:4] and ids[1] not in pool  # the oldest untouched entry went
    assert ids[0] in pool and ids[2] in pool
    assert pool.take() in units[2:4] and pool.entries == 2  # its other unit: nothing more dropped
    assert pool.take() in units[4:6] and ids[2] not in pool and ids[0] in pool


def an_entry_whose_units_rows_hold_is_skipped(pool, kv, ids, blocks):
    units = [pool.take() for _ in range(6)]
    assert pool.keep(ids[0], units[0:3]) and pool.keep(ids[1], units[3:6])
    pool.release(units[3:6])  # rows still hold every unit of the OLDER entry
    assert counts(pool) == (3, 3, 0)
    assert pool.take() in units[3:6] and ids[0] in pool and ids[1] not in pool
    for _ in range(2):
        assert pool.take() in units[3:6]
    # nothing comes free by dropping what is left: no unit, and the resume point stays
    assert pool.take() is None and ids[0] in pool and counts(pool) == (6, 0, 0)
    assert pool.resume(ids[:2]) == (1, tuple(units[0:3]))


def a_blocks_eviction_frees_its_units(pool, kv, ids, blocks):
    units = [pool.take() for _ in range(4)]
    assert pool.keep(ids[6], units[0:2]) and pool.keep(ids[7], units[2:4])
    pool.release(units[1:])  # a row still holds one unit of the first entry
    assert counts(pool) == (1, 3, 2)
    assert kv.evict_hashes([blocks[6].sequence_hash, blocks[7].sequence_hash]) == 2
    assert pool.entries == 0 and counts(pool) == (1, 0, 5)  # the held unit stays the row's
    pool.release(units[:1])
    assert counts(pool) == (0, 0, 6)
    assert pool.keep(ids[0], [pool.take()]) and pool.entries == 1
    kv.clear()  # every entry goes; a row's reference does not
    assert pool.entries == 0 and counts(pool) == (1, 0, 5)


def counts_add_up_through_shared_units(pool, kv, ids, blocks):
    a, b = pool.take(), pool.take()
    assert pool.keep(ids[0], [a]) and pool.keep(ids[1], [a, b])  # kept twice, held once
    assert counts(pool) == (2, 0, 4)
    pool.release([a, b])
    assert counts(pool) == (0, 2, 4)
    n, held = pool.resume(ids[:2])
    assert (n, held) == (2, (a, b)) and counts(pool) == (2, 0, 4)
    pool.drop(ids[1])
    assert counts(pool) == (2, 0, 4) and ids[0] in pool
    pool.release(held)
    assert counts(pool) == (0, 1, 5)  # ``a`` is still kept with the first block
    pool.drop(ids[0])
    assert counts(pool) == (0, 0, 6) and pool.resume(ids) == (0, ())


CASES = [take_until_empty, kept_entries_go_least_recently_used_first,
         an_entry_whose_units_rows_hold_is_skipped, a_blocks_eviction_frees_its_units,
         counts_add_up_through_shared_units]


@pytest.mark.parametrize("case", CASES, ids=lambda f: f.__name__)
@pytest.mark.parametrize("which", sorted(POOLS))
def test_the_pool_class_keeps_one_contract_in_each_of_its_three_places(which, case):
    make, find = POOLS[which]
    kv, ids, blocks = sealed_manager(make())
    pool = find(kv.beside)
    assert pool in kv.pools and pool.size == 6 and counts(pool) == (0, 0, 6)
    case(pool, kv, ids, blocks)


# ------------------------------------------------------------- a third kind
class Bookmarks(Beside):
    """A TOY kind: a family whose rows carry one BOOKMARK unit each (what its
    layers would keep of the text so far), all from one pool.  A row's bookmark
    at a multiple of the stride is kept with the block that ends there and the
    row goes on with a new one; a hit is cut back to the last bookmarked
    block.  The step's operand: (the bookmark read, the row's own, -1) a row."""

    def __init__(self, units: int, stride: int):
        super().__init__()
        self.stride, self.cache_kw = stride, {"bookmarks": units}

    def bind(self, kv):
        super().bind(kv)
        self.marks = kv.add_pool(100, self.cache_kw["bookmarks"])

    def fits(self):
        return self.marks.held < self.marks.size

    def cut(self, seq):
        n, units, _ = self._resume_point(self.marks, seq)
        seq.beside = types.SimpleNamespace(start=units[0] if units else -1, own=None)
        return n

    def _unpin(self, hold):
        if hold.start is not None and hold.start >= 0:
            self.marks.release([hold.start])
        hold.start = None

    def uncut(self, seq):
        self._unpin(seq.beside)
        seq.beside = None

    def admit(self, seq, cached_tokens):
        seq.beside.own = self.marks.take()
        return cached_tokens

    def operands(self, items, S, T):
        out = np.full((S, 3), -1, np.int32)
        for i, (seq, _, _) in enumerate(items):
            hold = seq.beside
            out[i, :2] = hold.own if hold.start is None else hold.start, hold.own
        return {"state_slots": out}

    def enqueued(self, seq, end):
        hold = seq.beside
        if hold is None:
            return
        self._unpin(hold)
        if self._on_stride(seq, end):
            bid = self.kv.block_of(seq.block_seq.blocks[end // self.kv.block_size - 1].sequence_hash)
            fresh = self.marks.take()
            if fresh is not None and bid is not None and self.marks.keep(bid, [hold.own]):
                fresh, hold.own = hold.own, fresh  # the old one stays with the block
            if fresh is not None:
                self.marks.release([fresh])

    def release(self, seq):
        hold, seq.beside = seq.beside, None
        if hold is not None:
            self._unpin(hold)
            self.marks.release([hold.own])


def serving(units=6, stride=8):
    cfg = EngineConfig(model="debug-tiny", block_size=BS, num_blocks=64, max_batch=4,
                       max_model_len=128, prefill_chunk=16, dtype="float32")
    kv = KvBlockManager(64, BS, beside=Bookmarks(units, stride))
    sched = Scheduler(cfg, kv)
    # The step builder, as the engine has it, over a stand-in for the engine.
    builder = types.SimpleNamespace(cfg=cfg, kv=kv, scheduler=sched, _lora_registry=None,
                                    _count_dispatch=None, host_kv=None)
    for name in ("_build_ragged", "_tables_row", "_seal_completed_blocks"):
        setattr(builder, name, types.MethodType(getattr(DecodePipelineMixin, name), builder))
    return sched, kv, builder


def request(tokens, rid):
    return SequenceState(request_id=rid, prompt=list(tokens),
                         block_seq=TokenBlockSequence(block_size=BS), max_new_tokens=8)


def step(sched, builder, only=None):
    """One pass of the engine's loop without a device: plan, build, 'enqueue'."""
    plan = sched.schedule()
    items = [it for it in plan.items if only is None or it[0] is only]
    rb = builder._build_ragged(items)
    for seq, start, n in items:
        seq.num_computed = start + n
        builder._seal_completed_blocks(seq)
        sched.beside.enqueued(seq, start + n)
    return items, rb


def test_a_third_kind_defined_here_is_served_by_the_scheduler_and_the_step_builder_unchanged():
    sched, kv, builder = serving()
    marks = kv.beside.marks
    doc = list(range(500, 519))  # 19 tokens: bookmarks at 8 and 16
    first = request(doc + [1, 2], "first")
    sched.add(first)
    # the stride bounds a prompt row's share of a step (the budget is 16)
    items, rb = step(sched, builder)
    assert [(st, n) for _, st, n in items] == [(0, 8)]
    assert rb.state_slots[0].tolist() == [-1, 100, -1] and (rb.state_slots[1:] == -1).all()
    assert first.beside.own == 101 and kv.block_of(first.block_seq.blocks[1].sequence_hash) in marks
    items, rb = step(sched, builder)
    assert [(st, n) for _, st, n in items] == [(8, 8)] and rb.state_slots[0].tolist() == [101, 101, -1]
    items, _ = step(sched, builder)
    assert [(st, n) for _, st, n in items] == [(16, 5)] and not first.in_prefill
    assert (marks.held, marks.kept_only, marks.entries) == (1, 2, 2)
    # a hit of 16 resident tokens... a sibling that arrives while the stretch is computed waits
    again = request(doc + [3], "again")
    sched.add(again)
    items, rb = step(sched, builder, only=again)  # (``first`` decodes beside it: no device here)
    assert [(s.request_id, st, n) for s, st, n in items] == [("again", 16, 4)]
    assert rb.state_slots[0].tolist() == [101, again.beside.own, -1]
    assert again.beside.start is None and marks._rows[101 - 100] == 0  # the pin went at the enqueue
    # a row leaves by any road: what it held goes back, what is kept stays
    sched._preempt(again)
    sched.remove(first)
    assert first.beside is None and again.beside is None
    assert (marks.held, marks.kept_only, marks.free) == (0, 2, 4)
    assert RowSlots(4, kv.beside.row).assign(first) == 0  # no row of its own: the lowest free


def test_a_third_kinds_room_and_its_wait_for_a_resume_point_hold_admission():
    # room: two bookmarks, two rows; the third request waits for one
    sched, kv, builder = serving(units=2)
    a, b, c = (request(range(i * 40, i * 40 + 6), f"r{i}") for i in range(3))
    for seq in (a, b, c):
        sched.add(seq)
    items, _ = step(sched, builder)
    assert [s.request_id for s, _, _ in items] == ["r0", "r1"] and c in sched.waiting
    assert not kv.beside.fits() and not sched.admission_ready()
    assert not kv.would_fit(hash_token_blocks(c.prompt, BS, None), 2)
    sched.remove(a)
    assert sched.admission_ready() and any(s is c for s, _, _ in sched.schedule().items)
    # the wait: a sibling is not admitted beside a row still computing their shared stretch
    sched, kv, builder = serving()
    doc = list(range(700, 720))
    first, sibling = request(doc + [1], "first"), request(doc + [2], "sibling")
    sched.add(first)
    step(sched, builder)  # first computed 0..8
    sched.add(sibling)
    items, _ = step(sched, builder)
    assert [s.request_id for s, _, _ in items] == ["first"] and sibling in sched.waiting
    # what the cut held for it went back: the one unit a row holds is ``first``'s own
    assert sibling.beside is None and kv.beside.marks.held == 1
    items, _ = step(sched, builder)  # first is past 16: the sibling starts from its bookmark there
    assert ("sibling", 16) in [(s.request_id, st) for s, st, _ in items]
