"""Fused decode pipeline: unified ragged steps, multi-step decode chains,
deferred token fetches/harvest, and token acceptance.

Split out of engine.py as a pure move (r5; VERDICT r4 weak #7) — these are
TpuEngine methods, combined via mixin inheritance.  See engine.py for the
engine-wide invariants (device lock, dispatch ordering, trace format).
"""

from __future__ import annotations

import asyncio
import functools
import time
import logging
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

logger = logging.getLogger(__name__)

from collections import deque

from ..llm.metrics import request_hop_metrics, tenancy_metrics
from ..llm.protocols import FinishReason, LLMEngineOutput
from ..ops.sampling import SamplingParams
from .join import join_rows
from .scheduler import RowSlots, SequenceState, StepPlan
from ..models.llama import RaggedBatch

_FINISHED = object()  # queue sentinel (engine.py imports this)


class DecodePipelineMixin:
    # Numpy fast path for per-chunk token acceptance (_accept_chunk); tests
    # flip this off to prove equivalence against the scalar loop.
    _vectorized_accept = True

    # Made on first use, not in ``TpuEngine.__init__``: lines of engine.py at
    # or above ``warmup`` are in the call stacks that key every Mosaic
    # kernel's compile-cache entry (the note beside ``make_cache`` there).
    @functools.cached_property
    def _join_fn(self):
        """The device-side join's program (engine/join.py), compiled in
        ``warmup`` (``_warm_join``) and counted by ``compile_counts``."""
        return jax.jit(join_rows)

    @functools.cached_property
    def pipeline_joins(self) -> Dict[str, int]:
        """Rows that joined a fused chain, by how: ``device`` (behind their
        prompt step, the chain unbroken) or ``break`` (the host merge)."""
        return {"device": 0, "break": 0}

    def _warm_join(self, out, last, steps_f, counts_f, rows):
        """Warm-up of the device-side join, in the order the loop runs it: the
        join behind a step's tokens and a chunk's carry, then a chunk fed by
        the join's carry (committed arrays of another program).  No row joins
        (``src`` -1) and ``rows`` are inactive: nothing is written.  Returns
        the last chunk's token carry for ``warmup`` to fetch."""
        S = self.cfg.max_batch
        nobody = self._prep((np.full((S,), -1, np.int32), np.zeros((S,), np.int32)))
        # Behind a merge the chain's state is the HOST's seed (no chunk went
        # out yet): host arrays in the carry's place, another cache entry.
        seed = self._prep((np.zeros((S,), np.int32), np.zeros((S,), np.int32)))
        for tok, steps in (seed, (last, steps_f)):
            tok, steps = self._join_fn(tok, steps, out.tokens, *nobody)
        _, last, _, _, self.cache = self._multi_fn(
            self.params, self.cache, tok, steps, counts_f, *rows
        )
        return last

    def _start_d2h(self, out, need_lp: bool) -> None:
        """Start the sampled-output device→host copies for a dispatched
        step (no ``except AttributeError``: a renamed SampleOut field must
        fail loudly, not turn every fetch into a synchronous round trip)."""
        out.tokens.copy_to_host_async()
        if out.aux is not None:
            out.aux.copy_to_host_async()
        if need_lp:
            out.logprob.copy_to_host_async()
            out.top_ids.copy_to_host_async()
            out.top_logprobs.copy_to_host_async()

    def _sampling_arrays(
        self,
        seqs: List[Optional[SequenceState]],
        step_offsets: Optional[List[int]] = None,
        grammar_states: Optional[List[Optional[int]]] = None,
    ) -> SamplingParams:
        """Build the per-row device sampling state for this step.

        ``seqs`` is one entry per batch ROW (a sequence may own several
        rows in a speculative verification step; ``step_offsets[i]`` then
        shifts row i's rng-stream position to the output index it scores —
        engine/spec.py).  The counts matrix ([S, V], penalties) is the
        engine's cached all-zeros DEVICE buffer unless some row actually
        uses a penalty — the common path never pays the [S, V]
        host→device transfer.  Same economy for the grammar mask
        ([S, ceil(V/32)] packed bits, llm/tenancy): the cached all-zero
        device buffer rides along (cond-skipped) unless a constrained row
        is present.  ``grammar_states[i]`` overrides row i's automaton
        state (spec verification scores draft positions, whose states are
        the current state advanced through the draft prefix); -1 forces
        the row unconstrained (positions past an inadmissible draft token
        — their samples can never commit, but they must not sample from an
        all-masked distribution)."""
        S = self.cfg.max_batch
        V = self.model_config.vocab_size
        seeds = np.zeros((S,), np.uint32)
        steps = np.zeros((S,), np.int32)
        temp = np.zeros((S,), np.float32)
        topk = np.zeros((S,), np.int32)
        topp = np.ones((S,), np.float32)
        fpen = np.zeros((S,), np.float32)
        ppen = np.zeros((S,), np.float32)
        need_lp = False
        any_pen = False
        # ``seqs[i] is None`` marks a free/retired row slot (the continuous
        # decode pipeline passes its RowSlots.rows directly): the row keeps
        # the same greedy defaults as padding rows past len(seqs).
        for i, seq in enumerate(seqs):
            if seq is None:
                continue
            seeds[i] = seq.sampling_seed
            steps[i] = seq.num_output_tokens + (
                step_offsets[i] if step_offsets is not None else 0
            )
            temp[i] = seq.sampling_temperature
            topk[i] = seq.sampling_top_k
            topp[i] = seq.sampling_top_p
            fpen[i] = seq.freq_penalty
            ppen[i] = seq.pres_penalty
            need_lp = need_lp or seq.logprobs is not None
            any_pen = any_pen or seq.freq_penalty != 0 or seq.pres_penalty != 0
        if any_pen:
            counts_np = np.zeros((S, V), np.int16)
            for i, seq in enumerate(seqs):
                if seq is None:
                    continue
                # Generated tokens since the ORIGINAL prompt: preemption and
                # migration-resume fold output into ``prompt``, and counting
                # ``output`` alone would silently drop the folded tokens'
                # penalty contributions exactly when a request resumes.
                gen = np.asarray(
                    (seq.prompt + seq.output)[seq.orig_prompt_len :], np.int64
                )
                if gen.size:
                    np.add.at(counts_np[i], gen % V, 1)
            if self._rep_sharding is not None:
                counts = self._prep(counts_np)
            else:
                counts = jnp.asarray(counts_np)  # committed, key matches cache
        else:
            counts = self._zero_counts

        # Grammar masks (llm/tenancy/grammar.py): packed admissible-token
        # bits for constrained rows; unconstrained rows get all-ones.
        masked_rows = [
            i
            for i, seq in enumerate(seqs)
            if seq is not None
            and seq.grammar is not None
            and (grammar_states is None or grammar_states[i] != -1)
        ]
        if masked_rows:
            mw = np.full((S, self._mask_w), 0xFFFFFFFF, np.uint32)
            for i in masked_rows:
                seq = seqs[i]
                state = seq.grammar_state
                if grammar_states is not None and grammar_states[i] is not None:
                    state = grammar_states[i]
                mw[i] = seq.grammar.packed_mask(state)
            # jnp, not np: device arrays and numpy arrays key DIFFERENT
            # jit-cache entries, and the warmup/common path dispatches the
            # cached device zero-mask — same trick as the counts buffer.
            mask_words: Any = jnp.asarray(mw)
            any_mask = np.asarray(True)
            tenancy_metrics.grammar_masked_rows_total += len(masked_rows)
        else:
            mask_words = self._zero_mask
            any_mask = np.asarray(False)
        # LoRA slots (llm/tenancy/lora.py): per-row resident adapter slot,
        # -1 = base.  None (absent from the jit treedef) on LoRA-less
        # engines so their compiled programs are unchanged.
        if self._lora_registry is not None:
            aslots: Any = np.full((S,), -1, np.int32)
            for i, seq in enumerate(seqs):
                if seq is not None:
                    aslots[i] = seq.adapter_slot
        else:
            aslots = None
        return SamplingParams(
            seeds=seeds,
            steps=steps,
            temperature=temp,
            top_k=topk,
            top_p=topp,
            freq_penalty=fpen,
            pres_penalty=ppen,
            counts=counts,
            need_logprobs=np.asarray(need_lp),
            mask_words=mask_words,
            any_mask=any_mask,
            adapter_slots=aslots,
        )

    def _tables_row(self, out: np.ndarray, i: int, seq: SequenceState) -> None:
        ids = seq.block_ids[: out.shape[1]]
        out[i, : len(ids)] = ids

    def _build_ragged(self, items) -> RaggedBatch:
        bs = self.cfg.block_size
        S = self.cfg.max_batch
        PP = self.cfg.max_blocks_per_seq
        total = sum(n for _, _, n in items)
        T = self.cfg.bucket_tokens(total)

        tok = np.zeros((T,), np.int32)
        pos = np.zeros((T,), np.int32)
        slots = np.full((T,), -1, np.int32)
        kv_lens = np.zeros((S,), np.int32)
        tables = np.zeros((S, PP), np.int32)
        cu = np.zeros((S + 1,), np.int32)
        aslots = (
            np.full((T,), -1, np.int32)
            if self._lora_registry is not None
            else None
        )
        at = 0
        for i, (seq, start, n) in enumerate(items):
            all_toks = seq.prompt + seq.output
            tok[at : at + n] = all_toks[start : start + n]
            p = np.arange(start, start + n, dtype=np.int32)
            pos[at : at + n] = p
            blk = np.asarray(seq.block_ids, np.int32)
            slots[at : at + n] = blk[p // bs] * bs + p % bs
            if aslots is not None:
                aslots[at : at + n] = seq.adapter_slot
            self._tables_row(tables, i, seq)
            kv_lens[i] = start + n
            at += n
            cu[i + 1] = at
        cu[len(items) + 1 :] = at
        if self._count_dispatch:
            self._count_dispatch(
                "unified", [st for _, st, _ in items], [n for _, _, n in items], T
            )
        return RaggedBatch(
            token_ids=tok,
            positions=pos,
            slot_mapping=slots,
            kv_lens=kv_lens,
            page_indices=tables,
            cu_q_lens=cu,
            num_seqs=np.asarray([len(items)], np.int32),
            adapter_slots=aslots,
            # What the family keeps beside the pages (engine/resume.py): where
            # each row's state is read and written, its second page table.
            **self.kv.beside.operands(items, S, T),
        )

    async def _run_unified(self, plan: StepPlan):
        """One unified step of ``plan``'s rows; returns its sampled output,
        still on the device (row ``i`` is ``plan.items[i]``'s)."""
        with self._phase("prompt_build"):
            rb = self._build_ragged(plan.items)
            samp = self._sampling_arrays([s for s, _, _ in plan.items])
            need_lp = bool(samp.need_logprobs)
            # A step whose every row stays mid-prefill produces sampled tokens
            # nobody consumes — skip the device→host fetch entirely and let the
            # next chunk's dispatch queue behind this one: it saves a sync
            # per chunk (how much: not measured on this machine).
            need_tokens = any(
                start + n >= len(seq.prompt) for seq, start, n in plan.items
            )
            if self._rep_sharding is not None:
                rb_d, samp_d = self._prep((rb, samp))
            else:
                rb_d, samp_d = rb, samp
            step = self._step_fn
            # Park rows BEFORE the first suspension point, not after the
            # dispatch: from here to the harvest this coroutine yields, and
            # anything polling quiescence (freeze_sequence, engine/migrate.py)
            # must see these rows as having a token en route — marking after
            # the await left a window where a migration snapshot missed the
            # in-flight token and the client received it twice.  (Rows of OLD
            # pending fetches are disjoint from this plan's rows — the
            # scheduler never plans a parked row — so the harvests below can't
            # clear these marks early.)
            for seq, start, n in plan.items:
                if not seq.finished and start + n >= len(seq.prompt):
                    seq.awaiting_fetch = True

            def run():
                with self._phase("dispatch:unified"):
                    out, self.cache = step(self.params, self.cache, rb_d, samp_d)
                    if need_tokens:
                        # Start the D2H now; the accept is deferred to a harvest
                        # point so the round trip overlaps later dispatches.
                        self._start_d2h(out, need_lp)
                return out

        while self._pending_fetches and self._pending_fetches[0][1].done():
            await self._harvest_pending()  # free: task already complete
        with self._phase("enqueue:unified"):
            await self._pace()
            t0 = time.perf_counter()
            # Hop account, one comparison per row per dispatch: a row still in
            # its prompt is stamped before its first chunk's dispatch, and the
            # rows whose FINAL prompt token this step carries are kept — their
            # first token is what the fetch below brings to the host.
            first_rows: Optional[List[SequenceState]] = None
            for seq, start, n in plan.items:
                if seq.t_last_chunk == 0.0:
                    if seq.t_first_chunk == 0.0:
                        seq.t_first_chunk = t0
                    if start + n >= len(seq.prompt):
                        first_rows = (first_rows or []) + [seq]
            async with self._device_lock:
                # Publish INSIDE the device lock: broadcast order must equal
                # device enqueue order or followers replay a different program
                # sequence than the leader ran (SPMD divergence).
                if self._publisher is not None:
                    await self._publisher.publish(
                        "unified",
                        (rb, jax.tree_util.tree_map(np.asarray, samp)),
                    )
                out = await self._await_device(
                    self._device_task(run), "unified_dispatch", len(plan.items)
                )
            wall = time.perf_counter() - t0
            self.step_trace.append(
                (
                    "unified_fetch" if need_tokens else "unified",
                    wall,
                    len(plan.items),
                    len(rb.token_ids),
                )
            )
            # Prefill-chunk accounting: any step that advanced prompt tokens
            # counts as one chunk (mixed plans attribute the whole dispatch
            # wall — the prefill rows dominate it by construction of the
            # chunked scheduler).  Feeds the per-chunk latency quantiles on
            # /metrics.
            prefill_tokens = sum(
                min(n, len(seq.prompt) - start)
                for seq, start, n in plan.items
                if start < len(seq.prompt)
            )
            if prefill_tokens > 0:
                self._note_prefill_chunk(wall, prefill_tokens)

            if first_rows:
                for seq in first_rows:
                    seq.t_last_chunk = t0 + wall
            pending_rows: List[Tuple[SequenceState, int]] = []
            for i, (seq, start, n) in enumerate(plan.items):
                if seq.finished:
                    seq.awaiting_fetch = False  # pre-marked above; never parked
                    self.kv.beside.enqueued(seq, None)  # no block of its was sealed
                    continue
                if start >= len(seq.prompt):
                    # Decode row: the fed token joins the hash stream.
                    seq.block_seq.append((seq.prompt + seq.output)[start])
                seq.num_computed = start + n
                self._seal_completed_blocks(seq)
                # What the step left at this row's end is kept with that block.
                self.kv.beside.enqueued(seq, start + n)
                if not seq.in_prefill:
                    # This row's sampled token is in flight (pre-marked before
                    # the dispatch); park the row until a harvest point applies
                    # it.
                    seq.awaiting_fetch = True
                    pending_rows.append((seq, i))
            if pending_rows:
                self._stash_fetch(
                    "first", out, need_lp, pending_rows, first_rows=first_rows
                )
        return out

    async def _pace(self) -> None:
        """Await the injectable test pace hook (engine.py pace_hook)
        before a device op.  Always called OUTSIDE ``_device_lock``: the
        hook is allowed to BLOCK (tests/test_migration.py gates decode on
        a per-copy-round budget), and the KV copy/export plane needs the
        device lock to make the progress that un-blocks it — pacing under
        the lock would deadlock that interlock."""
        if self.pace_hook is not None:
            await self.pace_hook()

    async def _await_device(self, task, kind: str, rows: int):
        """Await a device-op task (token fetch OR dispatch) under the
        decode-stall watchdog.

        r5 diagnosed a ~3-minute ``decode_wait`` hang (a wedged device
        fetch) that no engine-side detector caught — the worker kept
        answering health probes while every stream it owned sat frozen.
        With the threshold set (EngineConfig.decode_stall_s /
        ``DYN_DECODE_STALL_S``; default off), a device op that exceeds it
        LOUDLY logs the recent dispatch trace, bumps ``decode_stalls``
        (``dynamo_tpu_engine_stall_total`` on /metrics) and records
        ``last_stall`` for ``dispatch_summary()`` — then KEEPS WAITING:
        the watchdog attributes the hang, it does not guess at recovery
        (killing an op whose DMA later lands would corrupt the
        dispatch-order invariants).  Dispatch awaits are covered too: a
        wedge can just as well surface one await earlier, blocking the
        ``to_thread(run)`` handoff with no fetch outstanding."""
        if self._stall_threshold_s <= 0:
            return await task
        await self._wait_first({task}, kind, rows)
        return task.result()

    async def _wait_first(self, tasks, kind: str, rows: int) -> None:
        """Return once ANY of ``tasks`` is done, under the decode-stall
        watchdog of ``_await_device``.  The fused loop waits for a chunk's
        fetch and the oldest first-token fetch at once, so a new row's token
        is applied when it lands and not an iteration later."""
        thr = self._stall_threshold_s
        waited = 0.0
        while True:
            done, _ = await asyncio.wait(
                tasks,
                timeout=thr if thr > 0 else None,
                return_when=asyncio.FIRST_COMPLETED,
            )
            if done:
                return
            first = waited == 0.0
            waited += thr
            if first:
                self.decode_stalls += 1
            trace = [
                [k, round(t, 4), r, n]
                for k, t, r, n in list(self.step_trace)[-8:]
            ]
            self.last_stall = {
                "kind": kind,
                "rows": rows,
                "waited_s": round(waited, 3),
                "trace": trace,
            }
            logger.error(
                "decode stall: %s (%d rows) exceeded %.1fs (waited %.1fs, "
                "threshold decode_stall_s/DYN_DECODE_STALL_S); recent "
                "dispatch trace: %s",
                kind, rows, thr, waited, trace,
            )

    def _device_task(self, fn):
        """Wrap a device-op thread in a Task so _await_device can watch it."""
        return asyncio.get_running_loop().create_task(asyncio.to_thread(fn))

    def _fetch_outs(self, out, need_lp: bool):
        """Materialize a step's sampled outputs on host (ONE definition of
        the SampleOut fetch shape — the stash path and the fused pipeline
        both use it, so a payload change cannot silently diverge them)."""
        if out.aux is not None:
            # The family's small int32 account, [n] or [steps, n]: it rides
            # the fetch that brings the tokens home.
            self.family.count_aux(np.asarray(out.aux))
        if need_lp:
            return (
                np.asarray(out.tokens),
                np.asarray(out.logprob),
                np.asarray(out.top_ids),
                np.asarray(out.top_logprobs),
            )
        return np.asarray(out.tokens), None, None, None

    def _fetch_first(self, out, need_lp: bool, first_rows: List[SequenceState]):
        """``_fetch_outs`` for a step that completed prompts: stamps the
        moment the sampled tokens are on the host (hop account
        ``t_fetch_done``) — here on the fetch thread, where the copy ends,
        so the event loop's latency stays out of the device half.  One
        clock read per fetch; only rows awaiting their FIRST token."""
        res = self._fetch_outs(out, need_lp)
        now = request_hop_metrics.now()
        for seq in first_rows:
            seq.t_fetch_done = now
        return res

    def _timed_fetch(self, call: str, fetch, *args):
        """Run a token fetch on its worker thread inside its device-call
        phase (``fetch:*``, engine/phases.py): the copy to the host, apart
        from the event loop getting round to the finished task."""
        with self._phase(call):
            return fetch(*args)

    def _stash_fetch(self, kind: str, out, need_lp: bool, *meta,
                     first_rows=None) -> None:
        """Park a dispatched step's token fetch: the np.asarray runs on a
        worker thread STARTING NOW (the D2H was already initiated with
        copy_to_host_async), and the loop applies the result at a harvest
        point once the task completes — the device round trip never blocks
        dispatching."""
        fetch = (
            (self._fetch_first, out, need_lp, first_rows)
            if first_rows
            else (self._fetch_outs, out, need_lp)
        )
        task = asyncio.get_running_loop().create_task(
            asyncio.to_thread(self._timed_fetch, f"fetch:{kind}", *fetch)
        )
        self._pending_fetches.append((kind, task, *meta))

    async def _harvest_pending(
        self, all_pending: bool = False, at: str = "iteration"
    ) -> None:
        """Apply deferred fetches in dispatch order.  Harvests the oldest
        entry (awaiting its background task), or everything outstanding.
        ``at`` says for the ``first_harvest`` counter whether the caller
        waited on the fetch itself ("landed") or looked at a harvest
        point of its iteration."""
        while self._pending_fetches:
            entry = self._pending_fetches.pop(0)
            kind, task = entry[0], entry[1]
            with self._phase(f"harvest:{kind}"):
                if kind == "first":
                    self.first_harvest[at] += 1
                await self._pace()
                t0 = time.perf_counter()
                sampled, logp, top_ids, top_lp = await self._await_device(
                    task, f"{kind}_fetch", len(entry[2])
                )
            with self._phase("emit"):
                t1 = time.perf_counter()
                self.step_trace.append(
                    (f"{kind}_harvest", t1 - t0, len(entry[2]), 0)
                )
                self._apply_harvest(
                    kind, entry, sampled, logp, top_ids, top_lp, t1
                )
            if not all_pending:
                break

    def _apply_harvest(
        self, kind: str, entry, sampled, logp, top_ids, top_lp, t1: float
    ) -> None:
        """Accept one harvested fetch's tokens (``t1``: when the loop had
        them) and hand them to the streams."""
        if kind == "first":
            for seq, i in entry[2]:
                seq.awaiting_fetch = False
                # A row that joined the chain on the device rides chunks in
                # flight: if this token ends it, its blocks go back past the
                # session's write barrier (sweep_retire), not here.
                riding, seq.riding_chain = seq.riding_chain, False
                if seq.finished:
                    continue  # cancelled while the token was in flight
                if seq.t_first_token == 0.0:
                    # Hop account: the first token's accept — also
                    # handed to a colocated edge on the in-process
                    # context object (never on the stream item).
                    seq.t_first_token = t1
                    c = self._contexts.get(seq.request_id)
                    if c is not None:
                        c.t_first_token = t1
                self._accept_token(
                    seq,
                    int(sampled[i]),
                    defer_removal=riding,
                    logprobs=self._lp_info(seq, i, logp, top_ids, top_lp),
                )
        else:  # "spec": speculative verification (engine/spec.py)
            self._harvest_spec(entry, sampled, logp, top_ids, top_lp)

    async def _decode_pipeline(self, members: List[SequenceState]) -> bool:
        """Continuous fused decode: multi-step dispatches with the token
        carry on device, up to cfg.pipeline_depth dispatches in flight,
        host readback overlapped — and CONTINUOUS membership:

        - **In-loop retirement**: a row that stops (or whose client
          cancels) is excluded from further dispatches immediately
          (``pos_disp = -1``) and its slot + KV blocks are released once
          the write barrier passes — every chunk dispatched while it was
          active has been harvested — while the session keeps fusing for
          everyone else.
        - **In-loop admission**: compatible waiting sequences are admitted
          into free row slots mid-session; their prompts prefill through
          ordinary unified steps INTERLEAVED between fused chunks (the
          fused cadence never stops), and they join the chain ON THE
          DEVICE, right behind the step that carries their last prompt
          chunk (``join_on_device``: the step's sampled token goes into the
          carry of the chunk enqueued next; no drain, no host sync).  What
          that cannot carry joins once its first token has landed, at the
          next chain-break merge — a drain of
          in-flight chunks only, never an exit to the scheduler.  Rows the
          scheduler admitted before the session began, still in their
          prompts, are hosted the same way (``rejoin_strays``).
        - **Double-buffered dispatch**: the oldest chunk's token fetch runs
          in a worker thread while the admission prefill dispatch, the
          next chunk's host-side planning (slot ensure, table rows) and
          its dispatch proceed, in that order — the host never plans on
          the critical path (``decode_wait`` measures device compute, not
          host work).
        - **The first token's path**: a prompt step is enqueued AHEAD of
          the iteration's top-up chunk, and a first-token fetch is applied
          when it lands (the wait for the chunk's fetch watches both), so
          work for a new row is neither ordered behind nor noticed after
          work for old rows that was issued later
          (docs/decode_pipeline.md has the iteration's order).

        ``want_rebuild`` fires only for genuinely incompatible changes:
        engine close, a frozen (mid-migration) row, a waiting head the
        fused loop cannot host (grammar-constrained), KV exhaustion, or a
        speculation-session flip.  Everything else is absorbed in-loop.

        Exactness: samples depend only on (seed, rng-step, committed
        prefix), a chain-break merge re-seeds the device carry with
        exactly the values it already holds, and a device-side join hands
        it the token and the rng-step a merge would — so a request under churn
        gets the stream it gets when served alone, at any temperature
        (tests/test_continuous_batching.py gates it, spec on/off).

        Invariant: no member's KV blocks are freed while any dispatch that
        writes them is in flight — retirement defers the release to the
        per-row write barrier.
        """
        cfg = self.cfg
        bs = cfg.block_size
        S, T = cfg.max_batch, cfg.decode_steps
        # Visible to freeze_sequence (engine/migrate.py) BEFORE the first
        # suspension point; maintained as membership changes below.
        self._pipeline_members = {s.request_id for s in members}
        self.pipeline_sessions += 1
        session_t0 = time.perf_counter()
        waited0 = self.phases.waited_s()
        multi = self._multi_fn

        tok0 = np.zeros((S,), np.int32)
        pos_disp = np.full((S,), -1, np.int32)  # dispatch frontier (-1 = free)
        tables = np.zeros((S, cfg.max_blocks_per_seq), np.int32)
        # What a chunk takes beside its K/V tables (``Beside.chunk_operand``:
        # a family's second table, made anew by every plan), or None.
        beside = self.kv.beside
        tables_beside: Optional[np.ndarray] = None
        limits = np.zeros((S,), np.int32)
        slots = RowSlots(S, beside.row)
        samp: Optional[SamplingParams] = None
        samp_np: Any = None
        need_lp = False
        # (token, rng-step, penalty-counts) carry: host seeds at each chain
        # break, then the previous dispatch's on-device outputs.
        carry: Optional[Tuple[Any, Any, Any]] = None

        inflight: deque = deque()  # (outs, pos0, chunk_id, need_lp)
        chunk_id = 0   # monotone dispatch counter — the write-barrier clock
        harvested = 0  # highest chunk id applied so far
        iter_chunk0 = 0  # chunk_id when the current iteration began
        # (seq, slot, barrier, remove): remove=False parks a FROZEN row out
        # of the session (migration quiescence) without releasing it from
        # the scheduler — the row stays resident, just unplanned.
        retired: List[Tuple[SequenceState, int, int, bool]] = []
        prefilling: List[SequenceState] = []  # admitted in-loop, prompt computing
        # Sequences joining the fused chain at the next chain-break merge.
        # The INITIAL members seed through the same merge: one code path
        # for session start and mid-session joins.
        ready: List[SequenceState] = list(members)
        rebuild = False
        dispatched_any = False
        # What a device-side join owes, and to whom.  A chain break did
        # more than make the joiner wait: its drain gave the prompt steps an
        # iteration without a chunk, and the re-seeded chain's first two
        # chunks went out back to back, the next prompt step behind them.  A
        # join that breaks nothing does neither, and on a busy device a
        # closed loop pays either way (a client's cycle is TTFT + decode
        # time and the device's work a request is fixed: PERF.md section 6,
        # PR 46).  So a join owes ONE slot of the device (``owed``, at most
        # two), to the side that is short of it, by the prompt work queued
        # (``prompt_steps_queued``): more than a step of it and the slot is
        # the prompt steps' (``yield_slot``: an iteration that enqueues a
        # step and no chunk); else the chain's (``chain_turn``: the next
        # prompt step waits one chunk; a turn not taken at once lapses).
        # With more steps queued than rows ride the chain the prompt steps
        # bound the whole load, and the row takes the break path: a joiner
        # keeps chunks of two or three rows going that a break would not
        # have run.  ``joined_now``: this iteration joined.
        owed = 0
        joined_now = False

        def prompt_steps_queued(beyond: int = 0) -> float:
            """Prompt work queued, in steps of the prefill budget, ``beyond``
            tokens from now: what is left of the rows in prefill, and a
            step for each request still waiting."""
            left = sum(
                len(seq.prompt) - seq.num_computed
                for seq in prefilling
                if seq.in_prefill and not (seq.finished or seq.frozen)
            )
            return self.scheduler.num_waiting + (left - beyond) / cfg.prefill_chunk

        def merge_ready() -> None:
            """Chain-break merge: assign slots to joining sequences and
            re-seed the whole chain from host state.  Only legal with
            nothing in flight — exactly then the continuing rows' frontier
            tokens are host-known (accepted == dispatched), and the host
            (steps, counts) equal the device carry they replace."""
            nonlocal samp, samp_np, need_lp, carry
            for seq in ready:
                slots.assign(seq)
            self.pipeline_joins["break"] += len(ready)
            ready.clear()
            for i, seq in slots.active():
                all_toks = seq.prompt + seq.output
                tok0[i] = all_toks[seq.num_computed]
                # Rows whose frontier overshot a wall earlier re-dispatch
                # those positions; the recomputed (seeded) samples are
                # identical — same as a full rebuild.
                pos_disp[i] = seq.num_computed
            samp = self._sampling_arrays(slots.rows)
            # Host copy only needed for the follower broadcast — np.asarray
            # on samp.counts would otherwise drag the [S, V] device buffer
            # to host on every merge.
            samp_np = (
                jax.tree_util.tree_map(np.asarray, samp)
                if self._publisher is not None
                else None
            )
            need_lp = bool(samp.need_logprobs)
            carry = None  # next dispatch re-seeds (tok, steps, counts)

        def join_on_device(out, items) -> None:
            """Device-side join, behind the prompt step just enqueued (``out``
            its sampled output, ``items`` its rows): the rows whose LAST
            prompt chunk rode it take their slots NOW, and the step's tokens
            move into the chain's carry on the device (engine/join.py), ahead
            of the next chunk.  No drain, no ``carry = None``, no host sync.
            The first token still goes home through the step's own fetch,
            and ``riding_chain`` holds every accept of a chunk behind it.

            Who joins here is decided by what the loop observes: a chain to
            join (seeded at least: before a session's first merge there is
            none), no break already on its way (``ready`` rows take the
            joiners with them), a truly free slot (a pending one may still be
            written by a chunk in flight), no penalty (the ``[S, V]`` counts
            row would have to be built on the device), no publisher
            (followers replay ``unified`` and ``multi`` alone), and no more
            prompt steps queued than rows ride the chain (a load that the
            prompt steps bound does better by the break).  Everyone else
            joins at a chain break."""
            nonlocal samp, need_lp, carry, owed, joined_now
            if (
                samp is None or ready or rebuild
                or self._publisher is not None
                or prompt_steps_queued() > slots.num_active
            ):
                return
            step_row: Dict[int, int] = {}
            for row, (seq, _, _) in enumerate(items):
                if (
                    seq.awaiting_fetch  # its LAST prompt chunk rode the step
                    and not seq.in_prefill
                    and not (seq.finished or seq.frozen)
                    and seq.freq_penalty == 0
                    and seq.pres_penalty == 0
                    # (A row the family's state binds to ITS row: that one is free.)
                    and (beside.row(seq) is not None or slots.num_free > len(step_row))
                ):
                    step_row[id(seq)] = row
            if not step_row:
                return
            with self._phase("merge"):
                for seq, _, _ in items:
                    if id(seq) in step_row:
                        prefilling.remove(seq)
                        slots.assign(seq)  # released by sweep_retire, like any member's
                        seq.riding_chain = True
                src = np.full((S,), -1, np.int32)
                first_steps = np.zeros((S,), np.int32)
                for i, seq in slots.active():
                    if id(seq) in step_row:
                        pos_disp[i] = seq.num_computed  # its prompt: the token feeds there
                        src[i] = step_row[id(seq)]
                        first_steps[i] = seq.num_output_tokens + 1
                # The joiners' sampling scalars, by the one mapping there is
                # (no penalty among them: the cached zero counts, no upload),
                # laid over the chain's into FRESH host arrays: the ones in
                # ``samp`` were handed to the dispatches in flight.
                # ``need_logprobs`` is an operand of the chunk, carried a
                # dispatch (``inflight`` keeps each chunk's own): a join may
                # flip it.
                theirs = self._sampling_arrays(
                    [seq if src[i] >= 0 else None for i, seq in enumerate(slots.rows)]
                )
                need_lp = need_lp or bool(theirs.need_logprobs)
                samp = samp._replace(
                    need_logprobs=np.asarray(need_lp),
                    **{
                        f: np.where(src >= 0, getattr(theirs, f), getattr(samp, f))
                        for f in ("seeds", "temperature", "top_k", "top_p",
                                  "freq_penalty", "pres_penalty")
                        + (() if samp.adapter_slots is None else ("adapter_slots",))
                    },
                )
                # No carry: a merge just seeded the chain and no chunk went out
                # since (this step is the merge's own iteration's), so the
                # host's seed IS the chain's state, and the join writes into
                # that: a break does not beget a break.
                tok, steps, counts = carry or (tok0.copy(), samp.steps, samp.counts)
                tok, steps = self._join_fn(tok, steps, out.tokens, src, first_steps)
                carry = (tok, steps, counts)
                self.pipeline_joins["device"] += len(step_row)
                owed, joined_now = min(owed + 1, 2), True

        def chain_turn(due: bool) -> bool:
            """Does this iteration's prompt step (``due``: there is one)
            wait one chunk?  In the iteration after a device-side join, where
            no more than a step of prompt work is queued behind it and a
            chunk can go out in its place (a seeded chain with a row in it,
            room in the window, no break on its way); a turn not taken then
            lapses."""
            nonlocal owed
            if not owed or prompt_steps_queued(cfg.prefill_chunk) > 1:
                return False  # (the slot is the prompts': yield_slot)
            take = (
                due and samp is not None
                and not (ready or rebuild or inflight)
                and slots.num_active > 0
            )
            owed = owed - 1 if take else 0
            return take

        def yield_slot(prompt_step: bool, in_flight_now: int) -> bool:
            """Does this iteration's top-up go to the prompt steps?  After a
            device-side join with more than a step of prompt work queued, in
            an iteration that enqueued a prompt step (the device has that to
            run) behind a chunk still in flight, and never the chunk a joined
            row has been waiting for since its join (the window was full
            then)."""
            nonlocal owed
            if not (owed and prompt_step and in_flight_now) or joined_now:
                return False
            if any(pos_disp[i] == seq.num_computed for i, seq in slots.active()):
                return False
            if prompt_steps_queued() <= 1:
                return False
            owed -= 1
            return True

        def riding(pos0: Optional[np.ndarray] = None) -> bool:
            """Is a row in the chain (of the chunk dispatched at ``pos0``)
            whose first token is still on its way home?"""
            return any(
                seq.riding_chain and (pos0 is None or pos0[i] >= 0)
                for i, seq in slots.active()
            )

        def sweep_retire() -> None:
            """Retire finished, client-cancelled and migration-frozen
            rows: excluded from future dispatches NOW; slot (+ blocks,
            unless frozen) released once the write barrier passes."""
            for i, seq in slots.active():
                if not seq.finished:
                    c = self._contexts.get(seq.request_id)
                    if c is not None and c.is_stopped:
                        # In-loop cancellation IS retirement — the stream
                        # is dead; nobody needs a whole-pipeline drain.
                        seq.finished = True
                        self._finish(seq, FinishReason.CANCELLED)
                if seq.finished:
                    slots.retire(i)
                    pos_disp[i] = -1
                    retired.append((seq, i, chunk_id, True))
                    self.continuous_retired += 1
                elif seq.frozen:
                    # Migration freeze: park the row OUT of the session.
                    # Its slot goes None, so any not-yet-harvested chunk
                    # tokens for the row are DROPPED at accept (recomputed
                    # identically on resume — seeded sampler), keeping the
                    # snapshot frontier equal to the emitted stream; the
                    # barrier hands quiescence to freeze_sequence via the
                    # _pipeline_members discard — the session keeps fusing
                    # for everyone else.
                    slots.retire(i)
                    pos_disp[i] = -1
                    retired.append((seq, i, chunk_id, False))

        def flush_retired() -> None:
            """Release retirements whose write barrier has passed: every
            chunk dispatched while the row was active has been harvested,
            so nothing in flight can still write its blocks (or, for a
            frozen row, still advance it — quiescence)."""
            while retired and retired[0][2] <= harvested:
                seq, i, _, remove = retired.pop(0)
                if remove:
                    self.scheduler.remove(seq)
                self._pipeline_members.discard(seq.request_id)
                slots.free(i)

        def rejoin_strays() -> None:
            """Running rows OUTSIDE the session come in: a row the
            scheduler admitted before the session began and whose prompt
            is still computing goes on prefilling here, and a decode row
            joins at the next chain break (a migration rollback's
            unfreeze is how a member falls out of membership; it would
            otherwise starve until the session ends)."""
            nonlocal rebuild
            known = (
                slots.num_active
                + len(prefilling)
                + len(ready)
                + len(retired)
            )
            if len(self.scheduler.running) == known:
                return
            in_session = (
                {id(s) for _, s in slots.active()}
                | {id(s) for s in prefilling}
                | {id(s) for s in ready}
                | {id(s) for s, _, _, _ in retired}
            )
            for seq in self.scheduler.running:
                if (
                    id(seq) in in_session
                    or seq.frozen
                    or seq.finished
                    or seq.awaiting_fetch  # parked: its fetch lands first
                ):
                    continue
                if seq.grammar is not None:
                    # Constrained rows can't ride fused chunks: drain for
                    # the scheduler's unified-step routing.
                    rebuild = True
                    continue
                if seq.in_prefill:
                    prefilling.append(seq)
                else:
                    ready.append(seq)
                self._pipeline_members.add(seq.request_id)

        def want_rebuild() -> bool:
            if self._closed:
                return True
            if any(s.frozen for s in prefilling) or any(
                s.frozen for s in ready
            ):
                # A freeze landing in the join window (rare): drain — the
                # joining row has no slot to park out of.
                return True
            # Only a head the fused loop cannot host (grammar-constrained —
            # its mask advances host-side per token) still needs the full
            # scheduler rebuild.
            return (
                self.scheduler.admission_ready()
                and not self.scheduler.waiting_head_compatible()
            )

        def admit() -> None:
            room = slots.capacity_left - len(prefilling) - len(ready)
            if room <= 0 or not self.scheduler.admission_ready():
                return
            if not self.scheduler.waiting_head_compatible():
                return
            for seq in self.scheduler.admit_continuous(room):
                self._pipeline_members.add(seq.request_id)
                self.continuous_admissions += 1
                prefilling.append(seq)

        def prompt_rows() -> List[Tuple[SequenceState, int, int]]:
            """The rows of one unified step advancing every in-loop-admitted
            prompt by a chunk (ordinary _run_unified: chunked prefill,
            deferred first-token fetch, block sealing), or none.  Fused
            chunks around it touch disjoint rows and blocks."""
            budget = cfg.prefill_chunk
            items: List[Tuple[SequenceState, int, int]] = []
            for seq in prefilling:
                if budget <= 0:
                    break
                if (
                    seq.finished
                    or seq.frozen
                    or seq.awaiting_fetch
                    or not seq.in_prefill
                ):
                    continue
                chunk = self.scheduler.prompt_chunk(seq, budget)
                items.append((seq, seq.num_computed, chunk))
                budget -= chunk
            return items

        def promote_ready() -> None:
            for seq in list(prefilling):
                if seq.finished:
                    # First token hit a stop / the client cancelled:
                    # _accept_token already removed it — it never joins.
                    prefilling.remove(seq)
                    self._pipeline_members.discard(seq.request_id)
                elif not seq.in_prefill and not seq.awaiting_fetch:
                    # Prompt computed AND first token harvested: joins the
                    # fused chain at the next chain break.
                    prefilling.remove(seq)
                    ready.append(seq)

        def plan_chunk() -> Optional[np.ndarray]:
            """Host-side planning for one fused chunk: KV slot ensure,
            table refresh, per-row write limits.  None = nothing worth
            dispatching (or KV exhausted → rebuild)."""
            nonlocal rebuild, tables_beside
            # Don't dispatch chunks no row can still use — checked BEFORE
            # allocating lookahead blocks: a never-dispatched chunk must
            # not take KV capacity from other sequences.
            if not self._any_useful_rows(slots.rows, pos_disp):
                return None
            ok = True
            for i, seq in slots.active():
                need = int(pos_disp[i]) + T - seq.num_computed
                if not self.scheduler._ensure_slot(seq, lookahead=need):
                    ok = False
                self._tables_row(tables, i, seq)
                limits[i] = min(
                    len(seq.block_ids) * bs, cfg.max_blocks_per_seq * bs
                )
            if not ok:
                # Out of KV headroom: drain any in-flight work, then return
                # so schedule() can preempt with nothing pending.
                rebuild = True
                return None
            tables_beside = beside.chunk_operand(
                [(i, seq, int(pos_disp[i])) for i, seq in slots.active()], S)
            return pos_disp.copy()

        def plan_top_up(in_flight_now: int, depth: int) -> Optional[np.ndarray]:
            """The dispatch window's next chunk, or None: the window is
            full, the chain must break first (a pending merge, a rebuild),
            or no row can use one."""
            if rebuild or ready or samp is None or in_flight_now >= depth:
                return None
            return plan_chunk()

        async def dispatch_chunk(pos0: np.ndarray) -> None:
            nonlocal carry, chunk_id, dispatched_any, progressed, rebuild
            with self._phase("enqueue:decode"):
                first = carry is None
                if self._count_dispatch:
                    self._count_dispatch(
                        "decode", pos0, np.where(pos0 >= 0, cfg.decode_steps, 0)
                    )
                n_active = slots.num_active
                pub_payload = (
                    tok0 if first else None,  # None → follower's own carry
                    pos0,
                    tables.copy(),
                    limits.copy(),
                    samp_np,
                )
                if first:
                    c_tok, c_steps, c_counts = tok0, samp.steps, samp.counts
                    if self._rep_sharding is not None:
                        c_tok, c_steps = self._prep((c_tok, c_steps))
                else:
                    c_tok, c_steps, c_counts = carry
                if self._rep_sharding is not None:
                    d_args = self._prep((pos0, tables.copy(), limits.copy(), samp))
                elif tables_beside is not None:
                    d_args = (pos0, (tables, tables_beside), limits, samp)
                else:
                    d_args = (pos0, tables, limits, samp)

                def run(args=d_args, tok_in=c_tok, st=c_steps, ct=c_counts):
                    with self._phase("dispatch:decode"):
                        outs, last, steps_f, counts_f, self.cache = multi(
                            self.params, self.cache, tok_in, st, ct, *args
                        )
                    return outs, (last, steps_f, counts_f)

                await self._pace()
                t0 = time.perf_counter()
                async with self._device_lock:
                    # Broadcast order must equal device enqueue order (see
                    # _run_unified) — publish under the device lock.
                    if self._publisher is not None:
                        await self._publisher.publish("multi", pub_payload)
                    outs, new_carry = await self._await_device(
                        self._device_task(run), "decode_dispatch", n_active
                    )
                carry = new_carry
                t1 = time.perf_counter()
                wall = t1 - t0
                self.step_trace.append(
                    ("decode_dispatch", wall, n_active, n_active * T)
                )
                self._trace_decode_chunk(slots.active(), t0, t1, T)
                # Start the D2H copy NOW: it proceeds in the background while
                # later chunks compute, so the wait below pays ~zero round trip
                # instead of compute + full link latency.
                self._start_d2h(outs, need_lp)
                chunk_id += 1
                inflight.append((outs, pos0, chunk_id, need_lp))
                dispatched_any = True
                pos_disp[:] = np.where(pos_disp >= 0, pos_disp + T, pos_disp)
                progressed = True
                if want_rebuild():
                    rebuild = True

        progressed = False
        while True:
            with self._phase("retire"):
                iter_chunk0 = chunk_id
                joined_now = False
                sweep_retire()
                flush_retired()
                if not rebuild:
                    rejoin_strays()
                if want_rebuild():
                    rebuild = True
            if ready and not inflight and not rebuild and not riding():
                # (A row riding the chain has no host token to re-seed from
                # yet: its fetch lands first, the merge an iteration later.)
                with self._phase("merge"):
                    merge_ready()

            with self._phase("admit"):
                # Pop the oldest chunk and start its fetch FIRST: everything
                # below — admission, the interleaved prefill, next-chunk
                # planning + dispatch, completed first-token harvests —
                # overlaps the D2H running in the fetch thread.
                # landed: None until the wait for the chunk begins, then
                # whether a first-token fetch landed during it.
                fetch_task = landed = None
                if inflight:
                    outs, pos0_c, cid, lp = inflight.popleft()
                    wait_t0 = time.perf_counter()
                    fetch_task = asyncio.get_running_loop().create_task(
                        asyncio.to_thread(
                            self._timed_fetch, "fetch:decode",
                            self._fetch_outs, outs, lp,
                        )
                    )

                # Prompt steps go to the device BEFORE this iteration's
                # top-up chunk: the device queue reads C_k, P_k, C_k+1 and a
                # prompt's last chunk (its first token) does not wait behind
                # a fused chunk dispatched microseconds before it.  The
                # chunk already in flight keeps the device fed while the
                # host builds the step; the device order between the two is
                # free (disjoint rows and blocks).  A pure-decode iteration
                # does nothing here.
                progressed = False
                items: List[Tuple[SequenceState, int, int]] = []
                if not rebuild:
                    admit()
                    items = prompt_rows()
                    if chain_turn(bool(items)):
                        items = []
                if items:
                    self.prompt_step_order[
                        "behind" if chunk_id > iter_chunk0 else "ahead"
                    ] += 1
                    dispatched_any = progressed = True
            if items:
                join_on_device(await self._run_unified(StepPlan(items)), items)
            # First tokens that landed while the loop was busy apply here,
            # still ahead of the top-up: a row that is ``ready`` holds it.
            while self._pending_fetches and self._pending_fetches[0][1].done():
                await self._harvest_pending()
                progressed = True

            # Top up the dispatch window.  With anyone waiting to join
            # (queued, prefilling, or merge-pending), cap the in-flight
            # depth at 2 — enough to overlap fetch with compute — so the
            # drain a join must wait for stays bounded.  A pending merge
            # holds fused dispatch entirely: the chain must break first.
            with self._phase("schedule"):
                promote_ready()
                depth = (
                    min(cfg.pipeline_depth, 2)
                    if (self.scheduler.num_waiting or prefilling or ready)
                    else cfg.pipeline_depth
                )
                in_flight_now = len(inflight) + (
                    1 if fetch_task is not None else 0
                )
                pos0 = (
                    None if yield_slot(bool(items), in_flight_now)
                    else plan_top_up(in_flight_now, depth)
                )
            while pos0 is not None:
                await dispatch_chunk(pos0)
                with self._phase("schedule"):
                    in_flight_now += 1
                    pos0 = plan_top_up(in_flight_now, depth)

            if fetch_task is not None:
                while True:
                    with self._phase("harvest:decode"):
                        if landed is None:
                            await self._pace()
                        else:
                            promote_ready()
                        # A first token that lands while the chunk computes
                        # is applied NOW, not at the next iteration's
                        # harvest point: its stream gets it at once, and its
                        # row is in ``ready`` before the next top-up.  The
                        # wait is left for that fetch's own phases
                        # (harvest:first, emit) and taken up again.
                        landed = False
                        while (
                            self._pending_fetches
                            and not fetch_task.done()
                            and not landed
                        ):
                            first_task = self._pending_fetches[0][1]
                            await self._wait_first(
                                {fetch_task, first_task},
                                "decode_wait",
                                slots.num_active,
                            )
                            landed = first_task.done()
                        if not landed:
                            sampled, logp, top_ids, top_lp = (
                                await self._await_device(
                                    fetch_task, "decode_wait", slots.num_active
                                )
                            )
                            # A row that joined on the device rides this chunk
                            # BEHIND its prompt step: that step's fetch is
                            # complete too, and its token is applied before
                            # the chunk's (never the other way round).
                            if not (self._pending_fetches and riding(pos0_c)):
                                break
                    await self._harvest_pending(at="landed")
                with self._phase("emit"):
                    wait_wall = time.perf_counter() - wait_t0
                    self.step_trace.append(
                        # "wait" not "fetch": the D2H copy started at
                        # dispatch, so this wall (from the chunk's pop at
                        # the iteration's top) is dominated by the chunk's
                        # device compute.
                        (
                            "decode_wait",
                            wait_wall,
                            slots.num_active,
                            slots.num_active * T,
                        )
                    )
                    self._accept_chunk(
                        slots.rows, pos0_c, sampled, logp, top_ids, top_lp
                    )
                    harvested = cid
                    if not rebuild and self._spec_session_probe(
                        [s for _, s in slots.active() if not s.riding_chain]
                    ):
                        # Output grew repetitive enough that in-step
                        # speculation now beats the fused chunks: drain and
                        # let schedule() re-propose for real (engine/spec.py).
                        rebuild = True
            elif not progressed:
                if self._pending_fetches:
                    # Nothing dispatchable until a first-token fetch lands:
                    # block on the oldest instead of spinning.
                    await self._harvest_pending(at="landed")
                else:
                    with self._phase("retire"):
                        promote_ready()
                        if ready and not rebuild:
                            continue  # late joiners: merge next iteration
                        # Nothing in flight, nothing to dispatch, nothing
                        # pending: drained for a rebuild, or every member
                        # finished — the session is over.
                        break
            with self._phase("yield"):
                promote_ready()
                if rebuild and not inflight:
                    break
                # Let ingress/egress run between chunks: whatever the HTTP
                # side and the other coroutines do with the thread.
                await asyncio.sleep(0)

        # A row that joined on the device and never rode an accepted chunk
        # (a drain came first) takes its first token before the session ends:
        # from here the scheduler owns it, as a decode row.
        while self._pending_fetches and riding():
            await self._harvest_pending(at="landed")
        with self._phase("retire"):
            # Drained: every dispatched chunk was harvested, so every write
            # barrier has passed — release whatever retirement is pending.
            sweep_retire()
            flush_retired()
            self._pipeline_members = set()
            if rebuild:
                self.pipeline_rebuilds += 1
        self.pipeline_wall_s += time.perf_counter() - session_t0
        self.pipeline_waited_s += self.phases.waited_s() - waited0
        return dispatched_any

    def _any_useful_rows(
        self, members: List[Optional[SequenceState]], pos_disp: np.ndarray
    ) -> bool:
        """True if any active member could still accept a token from one more
        fused chunk, given how far its dispatch frontier already overshoots
        its accepted position (in-flight tokens count against the budget).
        ``None`` entries are free/retired row slots."""
        for i, seq in enumerate(members):
            if seq is None or seq.finished or pos_disp[i] < 0:
                continue
            overshoot = int(pos_disp[i]) - seq.num_computed
            budget = self.cfg.max_model_len - seq.total_tokens
            if seq.max_new_tokens is not None:
                budget = min(budget, seq.max_new_tokens - seq.num_output_tokens)
            if budget - overshoot > 0:
                return True
        return False

    def _seal_completed_blocks(self, seq: SequenceState) -> None:
        complete = seq.num_computed // self.cfg.block_size
        hashed = len(seq.block_seq.blocks)
        while seq.num_sealed_blocks < min(complete, hashed):
            idx = seq.num_sealed_blocks
            tb = seq.block_seq.blocks[idx]
            self.kv.seal_block(seq.block_ids[idx], tb)
            seq.num_sealed_blocks += 1
            if self.host_kv is not None and not self.host_kv.contains(
                tb.sequence_hash
            ):
                self._offload_queue.append((seq.block_ids[idx], tb))

    def _accept_chunk(
        self,
        members: List[SequenceState],
        pos0: np.ndarray,
        sampled: np.ndarray,  # [T, S]
        logp,
        top_ids,
        top_lp,
    ) -> None:
        """Apply one fused chunk's sampled tokens to ``members``.

        Fast path: a row without logprobs computes its whole accept run
        with numpy mask math (allocation wall, LENGTH cutoffs, stop
        tokens under min_new_tokens) and emits ONE multi-token queue item
        — the scalar ``for t: for seq`` loop was the dominant term of the
        r5 16% host gap at batch 256.  Rows needing per-token logprob
        payloads (and engines with ``_vectorized_accept=False``, the
        test toggle) take the scalar row loop; both paths produce
        identical streams (tests/test_spec_decode.py asserts it)."""
        T = int(sampled.shape[0])
        bs = self.cfg.block_size
        for i, seq in enumerate(members):
            if seq is None:
                continue  # free/retired row slot (continuous pipeline)
            if seq.finished or pos0[i] < 0:
                continue  # (pos0 < 0: the row joined behind this chunk)
            p0 = int(pos0[i])
            if seq.num_computed != p0:
                continue  # stopped/hit the allocation wall in a prior chunk
            if not self._vectorized_accept or seq.logprobs is not None:
                self._accept_chunk_row_scalar(
                    seq, i, p0, sampled, logp, top_ids, top_lp
                )
                continue
            n_cap = min(T, len(seq.block_ids) * bs - p0)
            if n_cap <= 0:
                continue  # beyond allocation: tokens were never KV-backed
            if seq.trace is not None:
                # Normally latched by the "first" harvest path; belt for a
                # traced row whose first token rides a fused chunk.  AFTER
                # the n_cap guard: a row that accepts zero tokens from this
                # chunk has not produced its first token yet.
                self._trace_first_token(seq)
            col = np.asarray(sampled[:, i])
            # LENGTH cutoff: the token that reaches the budget is accepted
            # (and emitted) with finish_reason length, exactly as
            # _check_stop does after each append.
            m_len = self.cfg.max_model_len - seq.total_tokens
            if seq.max_new_tokens is not None:
                m_len = min(
                    m_len, seq.max_new_tokens - seq.num_output_tokens
                )
            m_len = max(1, m_len)
            if m_len <= n_cap:
                n_acc, reason = m_len, FinishReason.LENGTH
            else:
                n_acc, reason = n_cap, None
            stops = set(seq.stop_token_ids)
            if not seq.ignore_eos:
                stops |= set(self.model_config.eos_token_ids)
            if stops:
                hit = np.isin(col, np.fromiter(stops, np.int64))
                if seq.min_new_tokens is not None:
                    # Token m (1-based) lands at output index n_out + m.
                    hit &= (
                        seq.num_output_tokens + 1 + np.arange(T)
                    ) >= seq.min_new_tokens
                idx = np.nonzero(hit)[0]
                if idx.size and int(idx[0]) + 1 <= n_acc:
                    # STOP wins ties with LENGTH (stop checks run first).
                    n_acc, reason = int(idx[0]) + 1, FinishReason.STOP
            # Fed tokens: the committed tail + each previously sampled
            # token — members are decoding, so all join the hash stream.
            fed = [(seq.prompt + seq.output)[p0]] + [
                int(x) for x in col[: n_acc - 1]
            ]
            seq.block_seq.extend(fed)
            seq.num_computed += n_acc
            self._seal_completed_blocks(seq)
            toks = [int(x) for x in col[:n_acc]]
            seq.output.extend(toks)
            emit = toks[:-1] if reason is FinishReason.STOP else toks
            queue = self._queues.get(seq.request_id)
            if queue is not None and emit:
                queue.put_nowait(LLMEngineOutput.tokens(emit))
            if reason is not None:
                seq.finished = True
                self._finish(seq, reason)

    def _accept_chunk_row_scalar(
        self,
        seq: SequenceState,
        i: int,
        p0: int,
        sampled: np.ndarray,
        logp,
        top_ids,
        top_lp,
    ) -> None:
        """Reference per-token accept loop for one row (logprob payloads
        are per token; also the oracle the vectorized path is tested
        against)."""
        bs = self.cfg.block_size
        for t in range(sampled.shape[0]):
            if seq.num_computed != p0 + t:
                continue  # stopped earlier in this chunk
            if seq.num_computed >= len(seq.block_ids) * bs:
                continue  # beyond allocation: token was never KV-backed
            fed = (seq.prompt + seq.output)[seq.num_computed]
            if seq.num_computed >= len(seq.prompt):
                seq.block_seq.append(fed)
            seq.num_computed += 1
            self._seal_completed_blocks(seq)
            self._accept_token(
                seq,
                int(sampled[t, i]),
                defer_removal=True,
                logprobs=self._lp_info(
                    seq,
                    i,
                    None if logp is None else logp[t],
                    None if top_ids is None else top_ids[t],
                    None if top_lp is None else top_lp[t],
                ),
            )
            if seq.finished:
                break

    def _lp_info(
        self, seq: SequenceState, i: int, logp, top_ids, top_lp
    ) -> Optional[Dict[str, Any]]:
        """Per-token logprob payload for row ``i`` (None unless requested)."""
        if seq.logprobs is None or logp is None:
            return None
        k = min(int(seq.logprobs), top_ids.shape[-1])
        return {
            "logprob": float(logp[i]),
            "top": [
                (int(top_ids[i, j]), float(top_lp[i, j])) for j in range(k)
            ],
        }

    def _trace_first_token(self, seq: SequenceState) -> None:
        """First output token of a traced sequence: record the
        ``engine.prefill`` span (admission → first token — chunked prompt
        compute plus the first sampled fetch) with a ``first_token`` event,
        the TTFT decomposition's engine-side anchor.  One latch per
        sequence; untraced rows cost a single attr check."""
        st = seq.trace
        if st is None or st.first_done:
            return
        st.first_done = True
        from ..runtime.tracing import _wall_ms
        from ..runtime.tracing import collector as trace_collector

        now = time.perf_counter()
        trace_collector.record(
            st.ctx, "engine.prefill", "engine",
            seq.t_admit or seq.enqueue_t, now,
            attrs={
                "prompt_tokens": len(seq.prompt),
                "cached_tokens": seq.num_cached_prompt,
            },
            events=[{"name": "first_token", "t_ms": round(_wall_ms(now), 3)}],
        )

    def _trace_decode_chunk(self, rows, t0: float, t1: float, steps: int) -> None:
        """One ``engine.decode_chunk`` span per TRACED row per fused
        dispatch — the ISSUE 15 granularity contract: decode records at
        chunk (dispatch) granularity only, never per token.  Untraced rows
        cost one attr check per chunk; rows whose first token hasn't
        landed yet are skipped (their wall belongs to engine.prefill).
        Every row's first fused dispatch also latches its ``t_join`` stamp
        (hop account): one more comparison per row per dispatch."""
        for _i, seq in rows:
            if seq is None:
                continue
            if seq.t_join == 0.0:
                seq.t_join = t0  # hop account: first fused dispatch of the row
            st = seq.trace
            if st is None or not st.first_done:
                continue
            from ..runtime.tracing import collector as trace_collector

            trace_collector.record(
                st.ctx, "engine.decode_chunk", "engine", t0, t1,
                attrs={"steps": steps},
            )

    def _accept_token(
        self,
        seq: SequenceState,
        token: int,
        defer_removal: bool = False,
        logprobs: Optional[Dict[str, Any]] = None,
    ) -> None:
        if seq.trace is not None:
            self._trace_first_token(seq)
        seq.output.append(token)
        reason = self._check_stop(seq, token)
        # Grammar advance (llm/tenancy): the automaton state moves per
        # ACCEPTED token — constrained rows only flow through this accept
        # path (never the fused-chunk ones), so this is the single place
        # tenant state advances.
        emit_with_stop = False
        violation = False
        if seq.grammar is not None and reason is not FinishReason.STOP:
            nxt = seq.grammar.advance(seq.grammar_state, token)
            if nxt is None:
                # Defensive — the logit mask makes this unreachable; if it
                # ever fires, fail the stream rather than emit output that
                # cannot parse under the schema.
                tenancy_metrics.grammar_violations_total += 1
                violation = True
                reason = reason or FinishReason.ERROR
            else:
                seq.grammar_state = nxt
                if reason is None and seq.grammar.is_terminal(nxt):
                    # The value is complete and only EOS could follow: this
                    # token is real content (unlike eos/stop tokens), so it
                    # is emitted AND the stream finishes.
                    reason = FinishReason.STOP
                    emit_with_stop = True
        queue = self._queues.get(seq.request_id)
        # Stop-triggering tokens (eos / stop_token_ids) are not emitted,
        # matching the reference Backend's stop handling (backend.rs:234-423).
        if queue is not None and not violation and (
            reason is not FinishReason.STOP or emit_with_stop
        ):
            item = LLMEngineOutput.token(token)
            if logprobs is not None:
                item["logprobs"] = logprobs
            queue.put_nowait(item)
        if reason is not None:
            seq.finished = True
            if not defer_removal:
                self.scheduler.remove(seq)
            self._finish(seq, reason)

    def _check_stop(self, seq: SequenceState, token: int) -> Optional[FinishReason]:
        n_out = seq.num_output_tokens  # survives preemption's prompt-folding
        if (
            seq.grammar is not None
            and token in self.model_config.eos_token_ids
        ):
            # Grammar completion ends the stream regardless of ignore_eos /
            # min_tokens: the mask admits EOS only in accepting states, and
            # an un-advanceable eos "content" token would wedge the
            # automaton (eos has no edge).
            return FinishReason.STOP
        min_ok = seq.min_new_tokens is None or n_out >= seq.min_new_tokens
        if min_ok and token in seq.stop_token_ids:
            return FinishReason.STOP
        if (
            min_ok
            and not seq.ignore_eos
            and token in self.model_config.eos_token_ids
        ):
            return FinishReason.STOP
        if seq.max_new_tokens is not None and n_out >= seq.max_new_tokens:
            return FinishReason.LENGTH
        if seq.total_tokens >= self.cfg.max_model_len:
            return FinishReason.LENGTH
        return None

    def _fold_hops(self, seq: SequenceState) -> None:
        """The engine's fold of the hop account, once per sequence at its
        end: neighbouring stamps become sums on ``/metrics`` and, for a
        sampled request, the spans of the same intervals.  A sequence that
        was resumed, migrated in or prefilled again after a preemption
        (its prompt no longer the original) is incomplete by definition."""
        seq.hops_folded = True
        fresh = len(seq.prompt) == seq.orig_prompt_len
        ok = request_hop_metrics.fold_engine(
            seq.enqueue_t if fresh else 0.0, seq.t_admit, seq.t_first_chunk,
            seq.t_last_chunk, seq.t_fetch_done, seq.t_first_token, seq.t_join,
        )
        st = seq.trace
        if st is None or not ok:
            return
        from ..runtime.tracing import _wall_ms
        from ..runtime.tracing import collector as trace_collector

        trace_collector.record(
            st.ctx, "engine.prefill_wait", "engine",
            seq.t_admit, seq.t_first_chunk,
        )
        trace_collector.record(
            st.ctx, "engine.prefill_run", "engine",
            seq.t_first_chunk, seq.t_last_chunk,
        )
        trace_collector.record(
            st.ctx, "engine.first_fetch", "engine",
            seq.t_last_chunk, seq.t_first_token,
            events=[{"name": "fetch_done",
                     "t_ms": round(_wall_ms(seq.t_fetch_done), 3)}],
        )

    def _finish(self, seq: SequenceState, reason: FinishReason) -> None:
        # Drop the adapter-slot pin BEFORE the queue check: every finish
        # path funnels here (including cancelled/error streams whose queue
        # is already gone), and a leaked ref would pin the slot forever.
        if (
            self._lora_registry is not None
            and seq.adapter is not None
            and not seq.adapter_released
        ):
            seq.adapter_released = True
            self._lora_registry.release(seq.adapter)
        if not seq.hops_folded:
            self._fold_hops(seq)
        queue = self._queues.get(seq.request_id)
        if queue is None:
            return
        queue.put_nowait(
            LLMEngineOutput.finished(
                reason,
                usage={
                    "prompt_tokens": seq.orig_prompt_len,
                    "completion_tokens": seq.num_output_tokens,
                    "total_tokens": seq.total_tokens,
                },
            )
        )
        queue.put_nowait(_FINISHED)
