"""The SECOND page pool under ``KvBlockManager`` (engine/kv_manager.py
``UnitPool``): the pages of layers that keep a window of the last positions
only, under ``WindowPages`` (engine/resume.py: ``grow`` / ``enqueued`` /
``cut``) and the pool's ONE resume rule ``resume``.  Host bookkeeping only: no
model, no device.
"""

import pytest

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.kv_manager import KvBlockManager
from dynamo_tpu.engine.resume import WindowPages
from dynamo_tpu.engine.scheduler import Scheduler, SequenceState
from dynamo_tpu.llm.metrics import swa_metrics
from dynamo_tpu.tokens import TokenBlockSequence, hash_token_blocks

BS, W, WB, ROW = 4, 8, 2, 7  # page size, window, pages before a resume point, a row's most pages
STRIDE = 16


def manager(pages=64, window_pages=28, **kw):
    return KvBlockManager(pages, BS, beside=WindowPages(window_pages, W, ROW, STRIDE), **kw)


def scheduler(**kw):
    cfg = EngineConfig(model="debug-tiny", block_size=BS, num_blocks=64, max_batch=4,
                       max_model_len=128, prefill_chunk=STRIDE, dtype="float32")
    kv = manager(**kw)
    return Scheduler(cfg, kv), kv


def request(tokens, rid="r"):
    return SequenceState(request_id=rid, prompt=list(tokens),
                         block_seq=TokenBlockSequence(block_size=BS), max_new_tokens=64)


def pool(kv):
    return swa_metrics.pool_pages


def admit(sched, seq):
    sched.add(seq)
    plan = sched.schedule()
    assert plan is not None and any(s is seq for s, _, _ in plan.items), "not admitted"
    return next((st, n) for s, st, n in plan.items if s is seq)


def run_prompt(sched, seq):
    """Walk ``seq``'s prompt as the engine does: span, (the step), seal, retain."""
    while seq.in_prefill:
        start, n = seq.num_computed, sched.prompt_chunk(seq, STRIDE)
        sched.beside.grow(seq, start + n)
        assert seq.beside.base * BS <= max(0, start + 1 - W)
        assert (seq.beside.base + len(seq.beside.ids)) * BS >= start + n
        seq.num_computed = start + n
        for i in range(seq.num_sealed_blocks, seq.num_computed // BS):
            sched.kv.seal_block(seq.block_ids[i], seq.block_seq.blocks[i])
        seq.num_sealed_blocks = seq.num_computed // BS
        sched.beside.enqueued(seq, start + n)


def test_a_rows_window_pages_stay_bounded_over_a_long_decode_and_go_back_behind_the_window():
    sched, kv = scheduler()
    seq = request(range(100, 121))  # 21 tokens
    assert admit(sched, seq) == (0, 16)
    run_prompt(sched, seq)
    held = []
    for _ in range(60):  # three windows and more of decode
        assert sched._ensure_slot(seq, lookahead=2)
        held.append(len(seq.beside.ids))
        first = max(0, seq.num_computed + 1 - W) // BS
        assert seq.beside.base == first  # nothing wholly behind the window is kept
        assert (seq.beside.base + len(seq.beside.ids)) * BS >= seq.num_computed + 2
        seq.output.append(7)
        seq.num_computed += 1
    assert max(held) <= 4 and max(held) <= kv.beside.row_pages
    assert pool(kv)["live"] == len(seq.beside.ids)
    assert pool(kv)["live"] + pool(kv)["retained"] + pool(kv)["free"] == kv.beside.pool.size


@pytest.mark.parametrize("how", ["free", "preempt", "failure"])
def test_free_preempt_and_failure_return_every_window_page(how):
    sched, kv = scheduler()
    seq = request(range(40))
    admit(sched, seq)
    run_prompt(sched, seq)
    assert pool(kv)["live"] > 0 and kv.beside.rows == 1
    if how == "preempt":
        sched._preempt(seq)
        assert seq in sched.waiting and seq.beside is None
    else:  # a finished row and a failed one leave the scheduler the same way
        sched.remove(seq)
    assert kv.beside.rows == 0 and pool(kv)["live"] == 0
    # what stays is retained with the blocks before 16 and 32 (2 pages each), evictable
    assert pool(kv)["retained"] == 4 and pool(kv)["free"] == kv.beside.pool.size - 4
    assert kv.free_blocks == kv.num_blocks


def test_admission_counts_both_pools():
    """A request that fits the K/V pool and not the window pool waits: two
    running rows may hold 7 pages each of a pool of 14."""
    sched, kv = scheduler(window_pages=14)
    a, b, c = (request(range(i * 50, i * 50 + 10), f"r{i}") for i in range(3))
    admit(sched, a)
    admit(sched, b)
    sched.add(c)
    assert kv.free_blocks > 40 and not kv.beside.fits()
    assert not sched.admission_ready()
    blocks = hash_token_blocks(c.prompt, BS, None)
    assert not kv.would_fit(blocks, 3)
    plan = sched.schedule()
    assert c in sched.waiting and all(s is not c for s, _, _ in plan.items)
    sched.remove(a)
    assert kv.beside.fits() and sched.admission_ready()
    assert any(s is c for s, _, _ in sched.schedule().items)


def test_a_hit_is_kept_where_the_pages_are_held_and_cut_back_where_they_were_evicted():
    sched, kv = scheduler()
    swa_metrics.reset()
    doc = list(range(200, 238))  # 38 tokens: resume points at 16 and 32, 9 whole blocks
    first = request(doc + [1, 2, 3], "first")
    admit(sched, first)
    run_prompt(sched, first)
    sched.remove(first)
    h = hash_token_blocks(doc, BS, None)
    wpool = kv.beside.pool
    assert kv.block_of(h[3].sequence_hash) in wpool and kv.block_of(h[7].sequence_hash) in wpool
    assert kv.block_of(h[8].sequence_hash) not in wpool
    # a hit of 36 tokens is cut back to 32, and the row owns the two pages before it
    second = request(doc + [4, 5, 6, 7, 8], "second")
    assert admit(sched, second) == (32, 11)
    assert (second.beside.base, len(second.beside.ids)) == (6, 2)
    assert second.beside.ids == list(wpool._of[kv.block_of(h[7].sequence_hash)])
    assert swa_metrics.hit_tokens == {"resumed": 32, "cut": 4}
    assert pool(kv) == {"live": 2, "retained": 2, "free": wpool.size - 4}
    run_prompt(sched, second)
    sched.remove(second)
    assert pool(kv)["live"] == 0 and pool(kv)["retained"] == 4
    # the entry at 32 evicted: the same hit is cut back to 16
    wpool.drop(kv.block_of(h[7].sequence_hash))
    third = request(doc + [9], "third")
    assert admit(sched, third) == (16, 16)
    assert (third.beside.base, len(third.beside.ids)) == (2, 2)
    assert swa_metrics.hit_tokens == {"resumed": 48, "cut": 24}
    sched.remove(third)
    # the K/V block at 16 evicted: its window pages go with it, and nothing is resumable
    for p in kv.pools:  # as ``_take_free_block`` does
        p.drop(kv.block_of(h[3].sequence_hash))
    fourth = request(doc + [9], "fourth")
    assert admit(sched, fourth) == (0, 16) and fourth.beside.ids == []


def test_a_full_pool_drops_retained_pages_least_recently_used_and_a_shared_page_survives():
    kv = manager(window_pages=8)
    blocks = hash_token_blocks(list(range(32)), BS, None)
    ids, _ = kv.allocate_sequence(blocks, 8)
    for bid, tb in zip(ids, blocks):
        kv.seal_block(bid, tb)
    wpool = kv.beside.pool
    pages = [wpool.take() for _ in range(6)]
    assert wpool.keep(ids[1], pages[0:2])
    assert wpool.keep(ids[3], pages[2:4])
    assert wpool.keep(ids[5], pages[4:6])
    wpool.release(pages)
    assert pool(kv) == {"live": 0, "retained": 6, "free": 2}
    # a row resumes behind the OLDEST entry: its pages are shared, and it is now the newest
    n, start = wpool.resume(ids[:2])
    assert (n, start) == (2, tuple(pages[0:2]))
    taken = [wpool.take() for _ in range(4)]  # 2 free, then the entry at block 3 goes
    assert set(taken[2:]) == set(pages[2:4])
    assert ids[1] in wpool and ids[5] in wpool and ids[3] not in wpool
    assert wpool.resume(ids[:4])[0] == 2  # cut back past the dropped entry
    wpool.release(tuple(pages[0:2]))
    wpool.release(start)
    assert pool(kv) == {"live": 4, "retained": 4, "free": 0}
    kv.clear()
    wpool.release(taken)
    assert pool(kv) == {"live": 0, "retained": 0, "free": 8}


def test_a_prompt_that_ends_on_a_resume_point_resumes_from_the_one_before():
    sched, kv = scheduler()
    doc = list(range(300, 332))  # 32 tokens
    first = request(doc + [1], "first")
    admit(sched, first)
    run_prompt(sched, first)
    sched.remove(first)
    again = request(doc, "again")  # its last token is always computed
    assert admit(sched, again) == (16, 16)
