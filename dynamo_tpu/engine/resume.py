"""State that lives beside the K/V pages: ONE notion, one object.

A sequence can be resumed where everything a later position needs is still
held.  For K/V alone that is every token; a family that keeps more (recurrent
state in slots, window layers' K/V in a second page pool: docs/
granite_hybrid.md, "State beside the pages") names a ``Beside`` through
``ModelFamily.beside``, and that object answers every question the engine has
about it.  The scheduler and the step builder call its hooks and compare no
kind; what a row holds is ONE field, ``SequenceState.beside``, which only the
kind reads and writes.  The units (slots, pages) live in ``UnitPool``s under
``KvBlockManager``, which drops a block's kept units with the block.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..llm.metrics import ssm_metrics, swa_metrics


class Beside:
    """Nothing beside the pages: a hit may end at any token, and a fully
    cached prompt gives its last token back (always computed, for its logits).
    With ``whole_blocks`` the state is held BY PAGE (models/lfm2.py,
    convolution layers): a sealed entry ends AT a block's last token, so such
    a prompt gives its whole last block back.  Also the base of every kind:
    each hook's default is "nothing".  ``items``: a step's rows (sequence,
    first position, tokens); ``S``, ``T``: its row and token counts."""

    # A prompt row's share of a step never crosses a multiple of ``stride``
    # (0: no boundary), so that the state there is a row's LAST in some step.
    stride = 0
    cache_kw: Dict[str, int] = {}  # what ``create_cache`` takes beside the pages

    def __init__(self, whole_blocks: bool = False):
        self.whole_blocks = whole_blocks

    def bind(self, kv) -> None:
        """Called once, by the block manager that holds this object."""
        self.kv = kv

    def fits(self) -> bool:
        """Room for one more running row."""
        return True

    def cut(self, seq) -> Optional[int]:
        """How many of ``seq``'s resident leading blocks a hit may share: the
        prefix cut back to the last point the row can be resumed from, whose
        units are HELD for it from here (``admit`` makes them its own,
        ``uncut`` gives them back).  None: every resident block."""
        return None

    def ahead(self, seq, share: Optional[int], running) -> bool:
        """A running row is still computing, inside its own prompt, the
        stretch of ``seq``'s prompt from where it could be resumed now
        (``share`` blocks) to the next multiple of the stride, and will leave
        its state there.  ``seq`` then WAITS for it: admitted now it would
        compute the same tokens beside that row, from a start fixed at
        admission (PERF.md section 6, PR 44).  The wait ends with that row's
        prompt at the latest."""
        if not self.stride or not self.kv.enable_prefix_caching:
            return False
        ahead = share * self.kv.block_size + self.stride
        if ahead >= len(seq.prompt):  # a resume point lies before the prompt's last token
            return False
        last = ahead // self.kv.block_size - 1
        want = seq.block_seq.blocks[last].sequence_hash
        return any(
            r.num_computed < ahead <= len(r.prompt)
            and not r.finished
            and not r.frozen
            and len(r.block_seq.blocks) > last
            and r.block_seq.blocks[last].sequence_hash == want
            for r in running
        )

    def uncut(self, seq) -> None:
        """``seq`` is not admitted after all: what ``cut`` held goes back."""

    def admit(self, seq, cached_tokens: int) -> int:
        """``seq`` is admitted behind ``cached_tokens`` shared tokens: it takes
        what a running row holds; returns the tokens it starts behind."""
        if cached_tokens >= len(seq.prompt):
            cached_tokens = len(seq.prompt) - (self.kv.block_size if self.whole_blocks else 1)
        return cached_tokens

    def grow(self, seq, upto: int) -> None:
        """``seq``'s next steps write the positions up to ``upto``."""

    def row(self, seq) -> Optional[int]:
        """The row of the fused decode program ``seq`` must take (None: any)."""
        return None

    def operands(self, items, S: int, T: int) -> Dict[str, np.ndarray]:
        """The ``RaggedBatch`` fields a unified step of ``items`` takes beside
        its page tables (no item: a warm-up step's, nothing read or written)."""
        return {}

    def chunk_operand(self, rows, S: int) -> Optional[np.ndarray]:
        """What a fused decode chunk takes beside its K/V tables, for ``rows``
        of (row, sequence, the chunk's first position); None: nothing."""
        return None

    def probe_kw(self, pages: int) -> Dict[str, int]:
        """``cache_kw`` of the scale calibration's probe cache of ``pages`` pages."""
        return {}

    def enqueued(self, seq, end: Optional[int]) -> None:
        """A step that carried ``seq`` is enqueued and the blocks it completed
        are sealed (``end``: the row's length now; None: it had finished,
        nothing sealed): what a step that ended ON a stride left there is kept
        with that block."""

    def release(self, seq) -> None:
        """``seq`` leaves the running rows: everything it holds goes back."""

    # ---------------------------------------------------- for the kinds below
    def _resume_point(self, pool, seq) -> Tuple[int, Tuple[int, ...], int]:
        """(n, units, matched): the longest run of ``seq``'s ``matched``
        resident leading blocks that covers fewer tokens than its prompt and
        ends at a block with units kept in ``pool``, now held for the row."""
        matched = self.kv.match_prefix(seq.block_seq.blocks)
        n, units = pool.resume(matched[: (len(seq.prompt) - 1) // self.kv.block_size])
        return n, units, len(matched)

    def _on_stride(self, seq, end: Optional[int]) -> bool:
        """A row whose step ended at ``end`` leaves a resume point there."""
        return bool(self.kv.enable_prefix_caching and end is not None
                    and end <= len(seq.prompt) and end % self.stride == 0)


@dataclass
class _Slots:
    """A row of ``SlotState``.  ``start``: where its NEXT step reads its state
    from, set at admission and cleared once that step is enqueued: -1 zeros, a
    snapshot's slot (held until then), or None once the row goes on from its
    own live slot.  ``due``: (block hash, slot) of the snapshot its step in
    flight leaves at its end."""

    matched: int
    start: Optional[int]
    slot: int = -1  # its live slot, while it runs
    due: Optional[Tuple[int, int]] = None


class SlotState(Beside):
    """Recurrent state in SLOTS (models/mamba2.py): ``live`` slots of which a
    running row owns one while it runs (ids from 0: the row of the fused
    program IS the slot), and ``snapshots`` slots each kept with the sealed
    block at whose end its copy of the state was taken.  A hit is cut back to
    the last block that holds one; a block without is not resumable, never wrong."""

    def __init__(self, live: int, snapshots: int, stride: int):
        super().__init__()
        self.stride, self._sizes = stride, (live, snapshots)
        self.cache_kw = {"state_slots": live + snapshots}

    def bind(self, kv) -> None:
        super().bind(kv)
        live, snapshots = self._sizes
        self.live = kv.add_pool(0, live, self._account)
        self.snapshots = kv.add_pool(live, snapshots, self._account)

    def _account(self, dropped: int) -> None:
        ssm_metrics.snapshots["evicted"] += dropped
        ssm_metrics.slots_in_use = {"live": self.live.held, "snapshot": self.snapshots.entries}

    def fits(self) -> bool:
        return self.live.free > 0

    def cut(self, seq) -> int:
        n, units, matched = self._resume_point(self.snapshots, seq)
        seq.beside = _Slots(matched, units[0] if units else -1)
        return n

    def uncut(self, seq) -> None:
        self._started(seq.beside)
        seq.beside = None

    def admit(self, seq, cached_tokens: int) -> int:
        seq.beside.slot = self.live.take()
        ssm_metrics.add_start(seq.beside.matched * self.kv.block_size, cached_tokens)
        return cached_tokens

    def row(self, seq) -> Optional[int]:
        return seq.beside.slot

    def operands(self, items, S: int, T: int) -> Dict[str, np.ndarray]:
        """(read, write, snapshot) slots a row: ``RaggedBatch.state_slots``.
        A prompt row that ends ON a stride leaves a snapshot there, unless
        its block has one or the pool has no slot to give.  The snapshot a row
        starts from stays HELD until the step is enqueued: no row of the step
        being built is handed it as the slot to write."""
        out = np.full((S, 3), -1, np.int32)
        for i, (seq, start, n) in enumerate(items):
            hold, snap = seq.beside, -1
            if self._on_stride(seq, start + n):
                h = seq.block_seq.blocks[(start + n) // self.kv.block_size - 1].sequence_hash
                if self.kv.block_of(h) not in self.snapshots:
                    got = self.snapshots.take()
                    if got is None:
                        ssm_metrics.snapshots["no_slot"] += 1
                    else:
                        snap, hold.due = got, (h, got)
            out[i] = hold.slot if hold.start is None else hold.start, hold.slot, snap
        return {"state_slots": out}

    def _started(self, hold: _Slots) -> None:
        """The row reads its state from its own live slot from now on."""
        if hold.start is not None and hold.start >= 0:
            self.snapshots.release((hold.start,))
        hold.start = None

    def enqueued(self, seq, end: Optional[int]) -> None:
        hold = seq.beside
        if hold is None:
            return
        self._started(hold)  # (what writes that snapshot next runs behind the step that read it)
        if hold.due is not None:
            # Where that block is gone already or has a snapshot (two rows
            # computed the same prefix side by side) the slot goes back.
            (h, slot), hold.due = hold.due, None
            bid = self.kv.block_of(h)
            if bid is not None and self.snapshots.keep(bid, (slot,)):
                ssm_metrics.snapshots["taken"] += 1
            self.snapshots.release((slot,))

    def release(self, seq) -> None:
        hold, seq.beside = seq.beside, None
        if hold is None:
            return
        if hold.slot >= 0:
            self.live.release((hold.slot,))
        self._started(hold)
        if hold.due is not None:  # reserved for a step that was built and did not run
            self.snapshots.release((hold.due[1],))


@dataclass
class _Window:
    """A row of ``WindowPages``: its pages, for its logical blocks ``base``
    onward: what its next query's window reaches and its steps in flight write."""

    matched: int
    ids: List[int] = field(default_factory=list)
    base: int = 0


class WindowPages(Beside):
    """Layers that keep a WINDOW of the last ``tokens`` positions only
    (models/lfm2.py ``sliding_attention``) in a second pool of ``pages``
    pages: a running row holds the few its next query's window reaches (at
    most ``row_pages``: its table's width), and the pages before a multiple
    of the stride are KEPT with the block that ends there: a hit is cut back
    to the last block whose window pages are still whole.  Pages are shared
    by count.  Everything but a running row's ``row_pages`` pages can be
    dropped, so an admitted row never finds the pool empty."""

    def __init__(self, pages: int, tokens: int, row_pages: int, stride: int):
        super().__init__()
        self.stride, self.tokens, self.row_pages = stride, tokens, row_pages
        self.cache_kw = {"window_pages": pages}
        self.rows = 0  # running rows

    def bind(self, kv) -> None:
        super().bind(kv)
        self.pool = kv.add_pool(0, self.cache_kw["window_pages"], self._account)
        # Pages that hold the ``tokens - 1`` positions before a resume point.
        self.blocks = -(-(self.tokens - 1) // kv.block_size)

    def _account(self, dropped: int) -> None:
        swa_metrics.pool_pages.update(
            live=self.pool.held, retained=self.pool.kept_only, free=self.pool.free)

    def fits(self) -> bool:
        return (self.rows + 1) * self.row_pages <= self.pool.size

    def cut(self, seq) -> int:
        n, units, matched = self._resume_point(self.pool, seq)
        seq.beside = _Window(matched, list(units), n - len(units))
        return n

    def uncut(self, seq) -> None:
        self.pool.release(seq.beside.ids)
        seq.beside = None

    def admit(self, seq, cached_tokens: int) -> int:
        self.rows += 1
        swa_metrics.add_hit(seq.beside.matched * self.kv.block_size, cached_tokens)
        return cached_tokens

    def grow(self, seq, upto: int) -> None:
        """``seq`` holds pages for exactly what is ahead of it: pages wholly
        behind the window of its NEXT query (position ``num_computed``) go
        back, and pages are taken for the positions up to ``upto``.  (A step
        in flight may still read a page given back here: whoever takes it
        writes it in a step enqueued later, and the device runs them in order.)"""
        hold = seq.beside
        if hold is None:
            return
        bs, ids = self.kv.block_size, hold.ids
        first = max(0, seq.num_computed + 1 - self.tokens) // bs
        drop = min(max(first - hold.base, 0), len(ids))
        self.pool.release(ids[:drop])
        del ids[:drop]
        # (Nothing held: the row's pages begin where its window does.)
        hold.base = hold.base + drop if ids else max(hold.base + drop, first)
        for _ in range((upto - 1) // bs + 1 - hold.base - len(ids)):
            page = self.pool.take()
            if page is None:  # ``fits`` admits no row that could bring this about
                raise RuntimeError("the window pool is empty under an admitted row")
            ids.append(page)

    def table_row(self, out: np.ndarray, i: int, seq, start: int) -> int:
        """Row ``i`` of a window table for a step (or fused chunk) whose first
        query is at ``start``: the row's pages from the block that query's
        window reaches (returned: the step's window lengths count from it)."""
        base = max(0, start + 1 - self.tokens) // self.kv.block_size
        ids = seq.beside.ids[max(0, base - seq.beside.base):][: out.shape[1]]
        out[i, : len(ids)] = ids
        return base

    def operands(self, items, S: int, T: int) -> Dict[str, np.ndarray]:
        """A second, short table a row, its context counted from the table's
        first page, and the tokens' slots in the window pool."""
        bs = self.kv.block_size
        tab = np.zeros((S, self.row_pages), np.int32)
        lens, slots = np.zeros((S,), np.int32), np.full((T,), -1, np.int32)
        if not items:  # warm-up: one row owns every token, as in the K/V fields
            lens[0] = T
        at = 0
        for i, (seq, start, n) in enumerate(items):
            self.grow(seq, start + n)
            base = self.table_row(tab, i, seq, start)
            p = np.arange(start, start + n, dtype=np.int32)
            ids = np.asarray(seq.beside.ids, np.int32)
            slots[at : at + n] = ids[p // bs - seq.beside.base] * bs + p % bs
            lens[i] = start + n - base * bs
            at += n
        swa_metrics.add_rows([len(seq.beside.ids) for seq, _, _ in items])
        return dict(window_indices=tab, window_lens=lens, window_slots=slots)

    def chunk_operand(self, rows, S: int) -> np.ndarray:
        """The chunk's second table, begun where ``table_row`` says (the
        program finds that block from ``pos0``).  A NEW array a chunk: its
        rows shift from chunk to chunk, and a host array handed to a dispatch
        still in flight must not change under it."""
        tab = np.zeros((S, self.row_pages), np.int32)
        for i, seq, start in rows:
            self.table_row(tab, i, seq, start)
        swa_metrics.add_rows([len(seq.beside.ids) for _, seq, _ in rows])
        return tab

    def probe_kw(self, pages: int) -> Dict[str, int]:
        return {"window_pages": pages}

    def enqueued(self, seq, end: Optional[int]) -> None:
        hold = seq.beside
        if hold is None or not self._on_stride(seq, end):
            return
        b1 = end // self.kv.block_size
        b0 = max(0, b1 - self.blocks)
        bid = self.kv.block_of(seq.block_seq.blocks[b1 - 1].sequence_hash)
        if b0 >= hold.base and bid is not None:
            self.pool.keep(bid, hold.ids[b0 - hold.base : b1 - hold.base])

    def release(self, seq) -> None:
        hold, seq.beside = seq.beside, None
        if hold is not None:  # freed, preempted or failed: every page goes back
            self.pool.release(hold.ids)
            self.rows -= 1
