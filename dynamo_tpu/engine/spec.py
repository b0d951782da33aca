"""Draft-free speculative decoding: n-gram proposer + in-step verification.

Decode is memory-bound (r5: decode MFU 54.89% with the fused pipeline —
every decode step streams the full weights for ONE token per row), so the
remaining hot-path lever is verifying several tokens per weight stream.
Classic speculative decoding (Leviathan et al., ICML 2023) needs a draft
model; the prompt-lookup variant (Saxena 2023) replaces it with an n-gram
match against the sequence's OWN prompt+output history — free drafts that
win hardest on the prefix-heavy templated traffic the KV-router already
optimizes for.

The engine needs no new device code.  The unified ragged program already
mixes rows of arbitrary q_len/kv_len with per-row sampling, so a draft of
``k`` tokens verifies as ``k+1`` SINGLE-TOKEN ROWS of one unified step:
row ``j`` feeds draft position ``num_computed + j`` with
``kv_len = num_computed + j + 1`` over the sequence's own block table,
producing that position's logits AND its seeded sample in the same
dispatch (ops/sampling.py draws from ``fold_in(PRNGKey(seed), step)``
where ``step`` is the row's output-token index, so the sample at a
position depends only on the committed prefix — not on how it was
batched).

Acceptance is therefore EXACT-STREAM: accept the longest draft prefix
that matches the sampled tokens row by row.  Under greedy this is the
argmax match of Leviathan's Theorem 1; under temperature>0 the sampled
token at each position IS the token non-speculative decoding would have
drawn (same seed, same step, same logits), so speculation on/off produces
identical token streams at ANY temperature — a strictly stronger property
than distribution-level rejection sampling, and the one the tier-1
equivalence gate asserts.

Rollback is bookkeeping-only: rejected rows wrote KV into slots past
``num_computed``, but blocks only seal (hash-publish) once accepted
tokens cover them, so a rejected tail is plain scratch that the next real
token overwrites.  ``num_computed`` simply does not advance past the
accepted prefix.

The per-sequence adaptive controller moves each sequence's draft length
``k`` inside [k_min, k] on acceptance results and benches collapsed
proposers (EWMA below ``accept_floor``) for ``cooldown_tokens`` committed
tokens; when no sequence drafts — or the expected tokens-per-round-trip
falls below the fused pipeline's ``decode_steps`` per row — the engine
falls back to the fused multi-step pipeline unchanged.
"""

from __future__ import annotations

import asyncio
import logging
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from ..llm.metrics import spec_metrics
from ..models.llama import RaggedBatch
from .config import SpecDecodeConfig
from .scheduler import SequenceState, StepPlan

logger = logging.getLogger(__name__)


def propose_ngram(
    hist: np.ndarray, ngram_min: int, ngram_max: int, k: int
) -> np.ndarray:
    """Prompt-lookup proposal: match the last ``n`` tokens (longest ``n``
    first) against the rest of ``hist`` and return up to ``k`` tokens that
    followed an earlier occurrence — the most recent one whose
    continuation covers ``k`` (recency beats the canonical first-match on
    drifting templated traffic, but a truncated continuation must not cap
    drafts at period-1 on short loops).  Vectorized numpy: one
    sliding-window comparison per tried ``n``.  Empty when nothing
    matches."""
    empty = np.empty((0,), dtype=hist.dtype)
    size = int(hist.size)
    if k < 1 or size < ngram_min + 1:
        return empty
    # Windows over hist[:-1]: a match always has >= 1 continuation token,
    # and the suffix can never match itself.
    for n in range(min(ngram_max, size - 1), ngram_min - 1, -1):
        pattern = hist[size - n :]
        windows = np.lib.stride_tricks.sliding_window_view(
            hist[: size - 1], n
        )
        hits = np.nonzero((windows == pattern).all(axis=1))[0]
        if hits.size:
            # Latest hit whose continuation still covers k tokens; when
            # none does (short periodic loops — every late hit runs into
            # the end of history), the hit with the longest continuation.
            # Pure recency would cap drafts at period-1 tokens exactly on
            # the loops speculation wins hardest on.
            cont = size - (hits + n)
            full = hits[cont >= k]
            start = int(full[-1] if full.size else hits[np.argmax(cont)]) + n
            return hist[start : start + k].copy()
    return empty


class AcceptanceController:
    """Per-sequence adaptive draft length, EWMA-driven.

    State lives on the SequenceState (spec_k / spec_ewma /
    spec_bench_until) so it follows the request through preemption; the
    controller itself is pure policy."""

    def __init__(self, sd: SpecDecodeConfig):
        self.sd = sd

    def current_k(self, seq: SequenceState) -> int:
        sd = self.sd
        if seq.spec_k < 0:
            seq.spec_k = sd.k
        if seq.spec_bench_until >= 0:
            if seq.num_output_tokens < seq.spec_bench_until:
                return 0
            # Cooldown served: re-probe gently (k_min) with the EWMA reset
            # above the floor so one miss doesn't instantly re-bench.
            seq.spec_bench_until = -1
            seq.spec_k = sd.k_min
            seq.spec_ewma = min(1.0, 2.0 * sd.accept_floor)
        return seq.spec_k

    def record(self, seq: SequenceState, drafted: int, accepted: int) -> None:
        sd = self.sd
        if drafted <= 0:
            return
        ratio = accepted / drafted
        seq.spec_ewma += sd.ewma_alpha * (ratio - seq.spec_ewma)
        if accepted >= drafted:
            # Fully accepted: the match run is longer than we dared — grow.
            seq.spec_k = min(sd.k, max(seq.spec_k + 1, seq.spec_k * 2))
        else:
            # Partial/none: next draft needs only cover the observed run.
            seq.spec_k = max(sd.k_min, min(seq.spec_k, accepted + 1))
        if seq.spec_ewma < sd.accept_floor:
            seq.spec_bench_until = seq.num_output_tokens + sd.cooldown_tokens


class SpecDecodeMixin:
    """TpuEngine methods for the speculative decode path (engine.py mixes
    this in next to the fused-pipeline mixin; ``self._spec_ctl`` is the
    AcceptanceController, or None when spec_decode.enable is false)."""

    # Session-probe backoff: accept rounds to skip after a probe whose
    # drafts failed the engagement bar (otherwise a batch that drafts but
    # never engages re-scans every member's history every chunk).
    _spec_probe_skip = 0
    _spec_probe_miss = 0

    # ------------------------------------------------------------- proposal
    def _spec_draft_for(
        self, seq: SequenceState, start: int, rows_free: int
    ) -> Optional[np.ndarray]:
        """One sequence's draft candidate at position ``start`` — budgeted
        against free batch rows and the sequence's remaining output /
        context / table headroom, but NOT against KV block allocation
        (allocation-free so the fused pipeline can probe mid-session)."""
        cfg = self.cfg
        sd = cfg.spec_decode
        if not seq.spec_enabled:
            return None
        if seq.freq_penalty != 0 or seq.pres_penalty != 0:
            # Penalty counts are built per dispatch; mid-draft accepts
            # would need in-window count updates — not worth the HLO.
            return None
        k = self._spec_ctl.current_k(seq)
        if k < 1:
            return None
        if seq.total_tokens < seq.spec_next_try:
            return None  # backing off after misses: skip the scan entirely
        out_budget = (
            seq.max_new_tokens - seq.num_output_tokens
            if seq.max_new_tokens is not None
            else cfg.max_model_len
        )
        len_budget = cfg.max_model_len - seq.total_tokens
        cap = min(
            k,
            rows_free,
            out_budget - 1,
            len_budget - 1,
            cfg.max_blocks_per_seq * cfg.block_size - start - 1,
        )
        if cap < 1:
            return None
        # Slice the tails BEFORE concatenating: building the full
        # prompt+output list first would make every proposal O(context),
        # defeating the lookback bound at long contexts.
        lb = sd.lookback
        if lb and len(seq.prompt) + len(seq.output) > lb:
            out_tail = seq.output[-lb:]
            need = lb - len(out_tail)
            hist_list = (seq.prompt[-need:] if need > 0 else []) + out_tail
        else:
            hist_list = seq.prompt + seq.output
        hist = np.asarray(hist_list, np.int64)
        d = propose_ngram(hist, sd.ngram_min, sd.ngram_max, cap)
        if d.size == 0:
            # Exponential miss backoff (2..64 tokens): random traffic must
            # not pay a history scan per scheduling round forever.
            seq.spec_miss = min(seq.spec_miss + 1, 6)
            seq.spec_next_try = seq.total_tokens + (1 << seq.spec_miss)
            return None
        seq.spec_miss = 0
        seq.spec_next_try = 0
        return d

    def _spec_collect(
        self, pairs: List[Tuple[SequenceState, int]], rows_free: int
    ) -> List[Tuple[SequenceState, List[int]]]:
        """Draft candidates for (seq, start) pairs, trimmed to the free-row
        budget.  Trimming pops from the LONGEST draft first, so the row
        budget spreads across drafting sequences instead of the plan-order
        head draining it."""
        cands: List[Tuple[SequenceState, List[int]]] = []
        for seq, start in pairs:
            d = self._spec_draft_for(seq, start, rows_free)
            if d is not None:
                cands.append((seq, [int(x) for x in d]))
        total = sum(len(d) for _, d in cands)
        while total > rows_free:
            _, longest = max(cands, key=lambda c: len(c[1]))
            longest.pop()
            total -= 1
        return [(s, d) for s, d in cands if d]

    def _spec_engaged(self, expected: int, n_decode: int) -> bool:
        """Engagement bar vs the fused pipeline: a verification step
        streams the weights once where a fused chunk streams them
        ``decode_steps`` times, so speculation wins well below raw
        tokens-per-round-trip parity (pipeline_margin).  Both callers
        ask only where a session is the alternative (decode_steps > 1)."""
        cfg = self.cfg
        bar = cfg.spec_decode.pipeline_margin * n_decode * cfg.decode_steps
        return expected >= bar

    def _spec_propose(self, plan: StepPlan) -> Dict[str, List[int]]:
        """Drafts for this plan's decode rows: {request_id: tokens}.

        Each draft token is one extra row of the unified step; where the
        plan would otherwise run as a fused session (``plan.session``)
        speculation must also beat the fused pipeline (_spec_engaged), else
        stand down — the adaptive controller keeps dead proposers from
        dragging live batches."""
        cfg = self.cfg
        decode_items = [
            (seq, start)
            for seq, start, n in plan.items
            if n == 1 and start >= len(seq.prompt)
        ]
        if not decode_items:
            return {}
        rows_free = cfg.max_batch - len(plan.items)
        if rows_free <= 0:
            return {}
        cands = self._spec_collect(decode_items, rows_free)
        if not cands:
            return {}
        if plan.session:
            # Engagement BEFORE allocation: standing down must not have
            # paid _ensure_slot evictions (which can LRU-evict sealed
            # prefix-cache blocks) for drafts that never run.
            expected = sum(len(d) + 1 for _, d in cands) + (
                len(decode_items) - len(cands)
            )
            if not self._spec_engaged(expected, len(decode_items)):
                spec_metrics.fallback_total += 1
                return {}
        drafts: Dict[str, List[int]] = {}
        bs = cfg.block_size
        for seq, d in cands:
            start = seq.num_computed
            # KV slots for the fed tail token + every draft position; on a
            # tight pool, trim the draft to the blocks we actually got.
            if not self.scheduler._ensure_slot(seq, lookahead=len(d) + 1):
                limit = len(seq.block_ids) * bs
                d = d[: max(0, limit - start - 1)]
                if not d:
                    continue
            drafts[seq.request_id] = d
        return drafts

    def _spec_session_probe(self, members: List[SequenceState]) -> bool:
        """Would speculation beat the fused pipeline for ``members`` RIGHT
        NOW?  Called by the pipeline after each accept round (drafts only
        appear as output accrues — a session started draft-less must not
        lock repetitive traffic out of speculation).  Pure numpy over the
        committed history, no allocation; a True verdict drains the
        session and lets the next schedule() re-propose for real."""
        if self._spec_ctl is None:
            return False
        rows_free = self.cfg.max_batch - len(members)
        if rows_free <= 0:
            return False  # saturated batch: no rows for draft expansion
        if any(seq.finished for seq in members):
            return False  # session is about to rebuild anyway
        if self._spec_probe_skip > 0:
            self._spec_probe_skip -= 1
            return False
        cands = self._spec_collect(
            [(seq, seq.num_computed) for seq in members], rows_free
        )
        if not cands:
            return False
        expected = sum(len(d) + 1 for _, d in cands) + (
            len(members) - len(cands)
        )
        if not self._spec_engaged(expected, len(members)):
            # Drafts exist but are not worth leaving the pipeline for;
            # exponential probe backoff (the per-seq miss backoff never
            # fires here because the scans HIT) caps the re-scan rate.
            self._spec_probe_miss = min(self._spec_probe_miss + 1, 3)
            self._spec_probe_skip = 1 << self._spec_probe_miss
            return False
        self._spec_probe_miss = 0
        return True

    # ------------------------------------------------------------- dispatch
    async def _run_spec_unified(
        self, plan: StepPlan, drafts: Dict[str, List[int]]
    ) -> None:
        """One unified ragged step verifying every drafted row in-step.

        Drafted decode rows expand to ``1 + len(draft)`` single-token rows
        (per-position logits + seeded samples); prefill chunks and
        undrafted decode rows ride along exactly as in _run_unified.  The
        token fetch is deferred (kind "spec"): acceptance, rollback and
        metrics land at the harvest point."""
        cfg = self.cfg
        bs, S, PP = cfg.block_size, cfg.max_batch, cfg.max_blocks_per_seq
        tok_l: List[int] = []
        pos_l: List[int] = []
        slot_l: List[int] = []
        aslot_l: List[int] = []  # per-token LoRA slot (llm/tenancy)
        kv_lens = np.zeros((S,), np.int32)
        tables = np.zeros((S, PP), np.int32)
        cu = np.zeros((S + 1,), np.int32)
        row_seqs: List[SequenceState] = []
        offsets: List[int] = []
        gstates: List[Optional[int]] = []
        spec_groups: List[Tuple[SequenceState, int, List[int]]] = []
        plain_rows: List[Tuple[SequenceState, int, int, int]] = []
        at = 0
        row = 0
        for seq, start, n in plan.items:
            d = (
                drafts.get(seq.request_id)
                if n == 1 and start >= len(seq.prompt)
                else None
            )
            all_toks = seq.prompt + seq.output
            blk = np.asarray(seq.block_ids, np.int32)
            if d:
                feed = [all_toks[start]] + list(d)
                # Grammar × spec (llm/tenancy): the logit mask must hold at
                # EVERY draft-verify position — row j samples output
                # position j, whose automaton state is the current state
                # advanced through draft[0..j-1] (acceptance implies the
                # committed tokens ARE the draft tokens, so these states
                # are exact for every committable position).  A draft token
                # the automaton rejects makes all later states -1 =
                # unconstrained: their samples can never commit (the
                # admissible sample at j must differ from the inadmissible
                # draft[j], so acceptance breaks there), but they must not
                # draw from an all-masked distribution.
                st: Optional[int] = (
                    seq.grammar_state if seq.grammar is not None else None
                )
                row_states: List[Optional[int]] = []
                for dt in d:
                    row_states.append(st if st is not None else None)
                    if st is not None and st != -1:
                        nxt = seq.grammar.advance(st, int(dt))
                        st = -1 if nxt is None else nxt
                    # st stays -1 (or None for unconstrained seqs)
                row_states.append(st)
                row0 = row
                for j, t in enumerate(feed):
                    p = start + j
                    tok_l.append(int(t))
                    pos_l.append(p)
                    slot_l.append(int(blk[p // bs]) * bs + p % bs)
                    aslot_l.append(seq.adapter_slot)
                    self._tables_row(tables, row, seq)
                    kv_lens[row] = p + 1
                    at += 1
                    cu[row + 1] = at
                    row_seqs.append(seq)
                    offsets.append(j)
                    gstates.append(row_states[j])
                    row += 1
                seq.awaiting_fetch = True
                spec_groups.append((seq, row0, list(d)))
            else:
                tok_l.extend(all_toks[start : start + n])
                p = np.arange(start, start + n, dtype=np.int32)
                pos_l.extend(p.tolist())
                slot_l.extend((blk[p // bs] * bs + p % bs).tolist())
                aslot_l.extend([seq.adapter_slot] * n)
                self._tables_row(tables, row, seq)
                kv_lens[row] = start + n
                at += n
                cu[row + 1] = at
                row_seqs.append(seq)
                offsets.append(0)
                gstates.append(None)  # plain row: current automaton state
                plain_rows.append((seq, start, n, row))
                if start + n >= len(seq.prompt):
                    # Parked BEFORE the dispatch, like drafted rows above:
                    # quiescence pollers (freeze_sequence) must see the
                    # in-flight token from commit time (engine/migrate.py).
                    seq.awaiting_fetch = True
                row += 1
        cu[row + 1 :] = at
        T = cfg.bucket_tokens(at)
        tok = np.zeros((T,), np.int32)
        tok[:at] = tok_l
        pos = np.zeros((T,), np.int32)
        pos[:at] = pos_l
        slots = np.full((T,), -1, np.int32)
        slots[:at] = slot_l
        # LoRA rows in a spec step (llm/tenancy): the verify forward must
        # apply each row's OWN adapter — and LoRA-less engines must keep
        # the None leaf so their compiled programs are unchanged.
        if self._lora_registry is not None:
            aslots: Any = np.full((T,), -1, np.int32)
            aslots[:at] = aslot_l
        else:
            aslots = None
        rb = RaggedBatch(
            token_ids=tok,
            positions=pos,
            slot_mapping=slots,
            kv_lens=kv_lens,
            page_indices=tables,
            cu_q_lens=cu,
            num_seqs=np.asarray([row], np.int32),
            adapter_slots=aslots,
        )
        samp = self._sampling_arrays(
            row_seqs, step_offsets=offsets, grammar_states=gstates
        )
        need_lp = bool(samp.need_logprobs)
        if self._rep_sharding is not None:
            rb_d, samp_d = self._prep((rb, samp))
        else:
            rb_d, samp_d = rb, samp
        step = self._step_fn
        while self._pending_fetches and self._pending_fetches[0][1].done():
            await self._harvest_pending()  # free: task already complete

        def run():
            out, self.cache = step(self.params, self.cache, rb_d, samp_d)
            # Capability probed once at engine init (pipeline._start_d2h) —
            # no per-dispatch AttributeError swallowing.
            self._start_d2h(out, need_lp)
            return out

        t0 = time.perf_counter()
        async with self._device_lock:
            # Broadcast order must equal enqueue order (see _run_unified).
            if self._publisher is not None:
                await self._publisher.publish(
                    "unified",
                    (rb, jax.tree_util.tree_map(np.asarray, samp)),
                )
            # Same decode-stall watchdog as every other device-op await
            # (engine/pipeline.py _await_device): a wedge inside a spec
            # verify step is the identical hang class.
            out = await self._await_device(
                self._device_task(run), "spec_dispatch", len(plan.items)
            )
        self.step_trace.append(
            ("spec_verify", time.perf_counter() - t0, len(plan.items), at)
        )
        spec_metrics.dispatches_total += 1

        first_rows: List[Tuple[SequenceState, int]] = []
        for seq, start, n, r in plain_rows:
            if seq.finished:
                seq.awaiting_fetch = False  # pre-marked; never parked
                continue
            if start >= len(seq.prompt):
                # Decode row: the fed token joins the hash stream.
                seq.block_seq.append((seq.prompt + seq.output)[start])
            seq.num_computed = start + n
            self._seal_completed_blocks(seq)
            if not seq.in_prefill:
                seq.awaiting_fetch = True
                first_rows.append((seq, r))
        self._stash_fetch("spec", out, need_lp, first_rows, spec_groups)

    # -------------------------------------------------------------- harvest
    def _harvest_spec(self, entry, sampled, logp, top_ids, top_lp) -> None:
        """Apply a spec step's tokens: plain rows accept like "first"
        entries; each drafted group commits its longest sampled-matching
        prefix plus the correcting sample, rolls the rest back (num_computed
        simply stops at the accepted frontier — rejected KV is unsealed
        scratch), and feeds the acceptance controller."""
        first_rows, groups = entry[2], entry[3]
        for seq, i in first_rows:
            seq.awaiting_fetch = False
            if seq.finished:
                continue  # cancelled while the token was in flight
            self._accept_token(
                seq,
                int(sampled[i]),
                logprobs=self._lp_info(seq, i, logp, top_ids, top_lp),
            )
        bs = self.cfg.block_size
        ctl = self._spec_ctl
        finished: List[SequenceState] = []
        for seq, row0, draft in groups:
            seq.awaiting_fetch = False
            if seq.finished:
                continue
            accepted = committed = 0
            limit = len(seq.block_ids) * bs
            for j in range(len(draft) + 1):
                if seq.num_computed >= limit:
                    break  # beyond allocation: never KV-backed
                fed = (seq.prompt + seq.output)[seq.num_computed]
                if seq.num_computed >= len(seq.prompt):
                    seq.block_seq.append(fed)
                seq.num_computed += 1
                self._seal_completed_blocks(seq)
                tok = int(sampled[row0 + j])
                self._accept_token(
                    seq,
                    tok,
                    defer_removal=True,
                    logprobs=self._lp_info(
                        seq, row0 + j, logp, top_ids, top_lp
                    ),
                )
                committed += 1
                if seq.finished:
                    finished.append(seq)
                    break
                if j < len(draft):
                    if int(draft[j]) != tok:
                        break  # rejection: rows past here are rolled back
                    accepted += 1
            ctl.record(seq, drafted=len(draft), accepted=accepted)
            spec_metrics.drafted_total += len(draft)
            spec_metrics.accepted_total += accepted
            spec_metrics.emitted_total += committed
        for seq in finished:
            self.scheduler.remove(seq)
