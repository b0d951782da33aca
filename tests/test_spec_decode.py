"""Draft-free speculative decoding (engine/spec.py).

The defining property is the greedy-equivalence gate: with
``spec_decode.enable=true`` vs ``false`` the engine produces IDENTICAL
token streams — across mixed batches (prefill + decode, chunk
boundaries, preemption), when every draft is rejected (rollback
correctness), with mid-draft stop tokens, and at temperature>0 (the
seeded sampler makes acceptance exact-stream, not just
distribution-preserving).  Plus: proposer/controller units, KV
accounting invariants after rollback, the vectorized accept-loop
equivalence (pipeline.py satellite), per-request opt-out, and metrics.
"""

import asyncio

import numpy as np
import pytest

from dynamo_tpu.engine import EngineConfig, SpecDecodeConfig
from dynamo_tpu.engine.engine import TpuEngine
from dynamo_tpu.engine.scheduler import SequenceState
from dynamo_tpu.engine.spec import AcceptanceController, propose_ngram
from dynamo_tpu.llm.metrics import spec_metrics
from dynamo_tpu.llm.protocols import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.runtime.engine import Context, collect

pytestmark = pytest.mark.spec

CFG = dict(
    model="debug-tiny",
    block_size=4,
    num_blocks=256,
    max_batch=4,
    max_model_len=256,
    prefill_chunk=32,
    dtype="float32",
)

REPETITIVE = [1, 2, 3, 4, 5, 6, 7, 8] * 4  # period-8 templated prompt
RANDOM = [(j * 104729 + 13) % 251 for j in range(24)]


def _req(tokens, max_tokens=24, stop_token_ids=(), ignore_eos=True, **kw):
    return PreprocessedRequest(
        token_ids=list(tokens),
        stop_conditions=StopConditions(
            max_tokens=max_tokens,
            stop_token_ids=list(stop_token_ids),
            ignore_eos=ignore_eos,
        ),
        sampling_options=SamplingOptions(**kw),
    ).to_dict()


async def _generate(engine, tokens, **kw):
    stream = await engine.generate(Context(_req(tokens, **kw)))
    out = await collect(stream)
    toks = [t for item in out for t in item["token_ids"]]
    return toks, out[-1]


def _assert_kv_consistent(engine, idle=True):
    """KV accounting invariant: no leaked or double-freed blocks."""
    kv = engine.kv
    for blk in kv._blocks:
        assert blk.ref_count >= 0, f"block {blk.id} over-freed"
    anon, reusable = set(kv._free_anon), set(kv._free_reusable)
    assert not anon & reusable, "block on both free lists"
    assert len(anon) == len(kv._free_anon), "duplicate in free list"
    active = sum(1 for b in kv._blocks if b.ref_count > 0)
    assert active + kv.free_blocks == kv.num_blocks
    if idle:
        assert kv.active_blocks == 0, "blocks leaked after all finished"


def _spec_cfg(decode_steps=1, **spec):
    spec = {"enable": True, "k": 6, **spec}
    return EngineConfig(**CFG, decode_steps=decode_steps, spec_decode=spec)


# ----------------------------------------------------------------- proposer
def test_propose_ngram_matches_continuation():
    hist = np.asarray([9, 1, 2, 3, 7, 7, 1, 2, 3], np.int64)
    d = propose_ngram(hist, 2, 4, 2)
    # suffix [1,2,3] (n=3) matches at index 1; continuation [7, 7].
    assert d.tolist() == [7, 7]


def test_propose_ngram_prefers_full_continuation():
    # Period-3 loop: the latest hit truncates at history end; the proposer
    # must back off to a hit that still covers k tokens.
    hist = np.asarray([4, 5, 6] * 5, np.int64)
    d = propose_ngram(hist, 2, 4, 6)
    assert len(d) == 6
    # Continuation must continue the cycle after suffix ...[4,5,6].
    assert d.tolist() == [4, 5, 6, 4, 5, 6]


def test_propose_ngram_no_match_and_short_history():
    assert propose_ngram(np.asarray([1, 2, 3, 4], np.int64), 2, 4, 4).size == 0
    assert propose_ngram(np.asarray([1, 1], np.int64), 2, 4, 4).size == 0
    assert propose_ngram(np.asarray([5, 5, 5], np.int64), 2, 2, 2).size > 0


def test_propose_ngram_longest_ngram_wins():
    # [8,9] occurs early with continuation 50; [7,8,9] later with 60 —
    # the longer (more specific) n-gram must win.
    hist = np.asarray([8, 9, 50, 0, 7, 8, 9, 60, 0, 7, 8, 9], np.int64)
    d = propose_ngram(hist, 2, 4, 1)
    assert d.tolist() == [60]


# --------------------------------------------------------------- controller
def test_acceptance_controller_adapts_and_benches():
    sd = SpecDecodeConfig(
        enable=True, k=8, k_min=1, accept_floor=0.2, cooldown_tokens=16,
        ewma_alpha=0.5,
    )
    ctl = AcceptanceController(sd)
    seq = SequenceState(request_id="r", prompt=[1], block_seq=None)
    assert ctl.current_k(seq) == 8  # seeded from config
    ctl.record(seq, drafted=8, accepted=8)
    assert seq.spec_k == 8  # already at max
    ctl.record(seq, drafted=8, accepted=2)
    assert seq.spec_k == 3  # shrink toward observed run (+1)
    # Collapse: repeated total rejections bench the proposer.
    for _ in range(8):
        ctl.record(seq, drafted=seq.spec_k, accepted=0)
    assert seq.spec_bench_until >= 0
    assert ctl.current_k(seq) == 0  # benched
    # Cooldown served (num_output_tokens >= bench_until): re-probe at k_min.
    seq.prompt = [1] * (seq.spec_bench_until + 1)  # n_out grows past bench
    seq.output = [2]
    seq.orig_prompt_len = 0
    assert ctl.current_k(seq) == sd.k_min
    assert seq.spec_ewma >= sd.accept_floor


def test_spec_config_normalize_and_validation():
    assert not SpecDecodeConfig.normalize(None).enable
    assert SpecDecodeConfig.normalize(True).enable
    assert SpecDecodeConfig.normalize({"enable": True, "k": 3}).k == 3
    sd = SpecDecodeConfig.normalize(SpecDecodeConfig(enable=True))
    assert sd.enable
    with pytest.raises(ValueError):
        SpecDecodeConfig.normalize({"bogus": 1})
    with pytest.raises(ValueError):
        SpecDecodeConfig(ngram_min=3, ngram_max=2)
    with pytest.raises(ValueError):
        SpecDecodeConfig(k=2, k_min=4)


# ------------------------------------------------------- equivalence gates
def test_greedy_equivalence_mixed_batch():
    """Spec on == spec off, token for token, across a concurrent mixed
    batch: repetitive + random prompts, a long prompt spanning chunked
    prefill, different max_tokens.  Speculation must actually engage."""

    async def main():
        prompts = [
            (REPETITIVE, 48),
            (RANDOM, 24),
            ([3] * 80, 32),  # long prompt: chunked prefill + loop-heavy
            ([9, 9, 5, 9, 9, 5], 40),
        ]

        async def run(spec_on):
            # max_batch 8 > concurrency 4: speculation needs free batch
            # rows for its draft expansion (at saturation it correctly
            # stands down for the fused pipeline).
            cfg_d = dict(CFG, max_batch=8)
            cfg = EngineConfig(
                **cfg_d,
                decode_steps=4,
                spec_decode={"enable": spec_on, "k": 6},
            )
            engine = TpuEngine(cfg)
            results = await asyncio.gather(
                *[
                    _generate(engine, p, max_tokens=mt)
                    for p, mt in prompts
                ]
            )
            _assert_kv_consistent(engine)
            await engine.close()
            return [r[0] for r in results], [
                r[1]["finish_reason"] for r in results
            ], engine

        spec_metrics.reset()
        toks_off, fin_off, _ = await run(False)
        toks_on, fin_on, eng = await run(True)
        assert toks_on == toks_off
        assert fin_on == fin_off
        assert spec_metrics.dispatches_total > 0, "speculation never engaged"
        assert spec_metrics.accepted_total > 0
        assert any(k == "spec_verify" for k, *_ in eng.step_trace)

    asyncio.run(main())


def test_equivalence_under_preemption():
    """Tiny block pool forces recompute-style preemption mid-stream; spec
    on/off streams must still match and no block may leak."""

    async def main():
        cfg_common = dict(CFG)
        # Tight: the three requests need 26 blocks at their ends.  24 was
        # "tight enough" only while all three finished together; on the
        # installed JAX's random weights the streams repeat, drafts accept
        # and rows finish at different times, so 24 no longer preempts.
        cfg_common["num_blocks"] = 20
        prompts = [REPETITIVE[:16], [7] * 20, [11, 12, 13, 11, 12, 13]]

        async def run(spec_on):
            cfg = EngineConfig(
                **cfg_common,
                decode_steps=1,
                spec_decode={"enable": spec_on, "k": 4},
            )
            engine = TpuEngine(cfg)
            results = await asyncio.gather(
                *[_generate(engine, p, max_tokens=20) for p in prompts]
            )
            preempted = engine.scheduler.preempted
            _assert_kv_consistent(engine)
            await engine.close()
            return [r[0] for r in results], preempted

        toks_off, _ = await run(False)
        toks_on, preempted = await run(True)
        assert toks_on == toks_off
        assert preempted > 0, "pool was not tight enough to preempt"

    asyncio.run(main())


def test_all_drafts_rejected_rollback(monkeypatch):
    """An adversarial proposer whose drafts NEVER match: every draft row
    is rejected and rolled back, the stream must equal spec-off exactly,
    and the KV accounting must balance (rejected rows wrote only unsealed
    scratch)."""
    import dynamo_tpu.engine.spec as spec_mod

    async def main():
        engine_off = TpuEngine(EngineConfig(**CFG, decode_steps=1))
        toks_off, fin_off = await _generate(
            engine_off, REPETITIVE, max_tokens=24
        )
        _assert_kv_consistent(engine_off)
        await engine_off.close()

        vocab = engine_off.model_config.vocab_size

        def bad_proposer(hist, ngram_min, ngram_max, k):
            # Always draft; continuation is a token run greedy decode of
            # debug-tiny never emits twice in a row at these prompts.
            return np.full((k,), vocab - 1, np.int64)

        monkeypatch.setattr(spec_mod, "propose_ngram", bad_proposer)
        spec_metrics.reset()
        engine_on = TpuEngine(_spec_cfg(decode_steps=1, accept_floor=0.0))
        toks_on, fin_on = await _generate(
            engine_on, REPETITIVE, max_tokens=24
        )
        _assert_kv_consistent(engine_on)
        await engine_on.close()
        assert toks_on == toks_off
        assert fin_on["finish_reason"] == fin_off["finish_reason"]
        assert spec_metrics.drafted_total > 0
        # The adversarial drafts must be (essentially) all rejected; every
        # dispatch still commits its one real sampled token.
        assert spec_metrics.accepted_total <= spec_metrics.drafted_total // 8
        assert spec_metrics.emitted_total >= spec_metrics.dispatches_total

    asyncio.run(main())


def test_mid_draft_stop_token(monkeypatch):
    """A stop token landing inside an ACCEPTED draft run must finish the
    stream at exactly the same point as non-speculative decoding (tokens
    after the stop are rolled back, the stop token is not emitted)."""
    import dynamo_tpu.engine.spec as spec_mod

    async def main():
        engine = TpuEngine(EngineConfig(**CFG, decode_steps=1))
        ref, _ = await _generate(engine, REPETITIVE, max_tokens=24)
        await engine.close()
        # A mid-stream token becomes the stop condition: the first token
        # at position >= 4 that has not occurred before it (ref[6] itself
        # can repeat ref[0] — it does on the installed JAX's random
        # weights — and the stream would then stop before any draft ran).
        stop_tok = next(
            t for i, t in enumerate(ref) if i >= 4 and t not in ref[:i]
        )

        engine_off = TpuEngine(EngineConfig(**CFG, decode_steps=1))
        toks_off, fin_off = await _generate(
            engine_off, REPETITIVE, max_tokens=24, stop_token_ids=[stop_tok]
        )
        await engine_off.close()

        # Oracle proposer: drafts the true continuation, so the stop token
        # is always inside an accepted draft run.
        def oracle(hist, ngram_min, ngram_max, k):
            pos = len(hist) - len(REPETITIVE)  # tokens generated so far
            return np.asarray(ref[pos : pos + k], np.int64)

        monkeypatch.setattr(spec_mod, "propose_ngram", oracle)
        spec_metrics.reset()
        engine_on = TpuEngine(_spec_cfg(decode_steps=1))
        toks_on, fin_on = await _generate(
            engine_on, REPETITIVE, max_tokens=24, stop_token_ids=[stop_tok]
        )
        _assert_kv_consistent(engine_on)
        await engine_on.close()
        assert fin_off["finish_reason"] == "stop"
        assert toks_on == toks_off
        assert fin_on["finish_reason"] == "stop"
        assert stop_tok not in toks_on[len(REPETITIVE) :]
        assert spec_metrics.accepted_total > 0, "oracle drafts must accept"

    asyncio.run(main())


def test_seeded_sampling_equivalence():
    """temperature>0: acceptance is exact-stream (the per-(seed, step)
    sampler draws the same token the non-spec path would), so streams
    match even under sampling."""

    async def main():
        async def run(spec_on):
            cfg = EngineConfig(
                **CFG,
                decode_steps=1,
                spec_decode={"enable": spec_on, "k": 4},
            )
            engine = TpuEngine(cfg)
            results = await asyncio.gather(
                _generate(
                    engine, REPETITIVE, max_tokens=32,
                    temperature=0.8, seed=7,
                ),
                _generate(
                    engine, [5, 5, 5, 5, 5, 5, 5, 5], max_tokens=24,
                    temperature=1.1, top_k=8, seed=123,
                ),
            )
            await engine.close()
            return [r[0] for r in results]

        assert await run(True) == await run(False)

    asyncio.run(main())


def test_per_request_opt_out(monkeypatch):
    """sampling_options.spec_decode=false must keep a request off the
    speculative path even when its drafts would hit (nvext plumbing is
    covered below)."""
    import dynamo_tpu.engine.spec as spec_mod

    async def main():
        def oracle(hist, ngram_min, ngram_max, k):
            return np.asarray(hist[-k:], np.int64)  # always drafts

        monkeypatch.setattr(spec_mod, "propose_ngram", oracle)
        spec_metrics.reset()
        engine = TpuEngine(_spec_cfg(decode_steps=1))
        toks, _ = await _generate(
            engine, REPETITIVE, max_tokens=16, spec_decode=False
        )
        await engine.close()
        assert len(toks) == 16
        assert spec_metrics.dispatches_total == 0

    asyncio.run(main())


def test_nvext_spec_decode_plumbs_to_sampling_options():
    from dynamo_tpu.llm.openai import ChatCompletionRequest

    req = ChatCompletionRequest.model_validate(
        {
            "model": "m",
            "messages": [{"role": "user", "content": "hi"}],
            "nvext": {"spec_decode": False},
        }
    )
    assert req.sampling_options().spec_decode is False
    d = req.sampling_options().to_dict()
    assert SamplingOptions.from_dict(d).spec_decode is False


# --------------------------------------------------- vectorized accept loop
def test_vectorized_accept_matches_scalar():
    """The numpy fast path in _accept_chunk must reproduce the scalar
    per-token loop exactly: stop tokens, min/max_tokens, eos, and plain
    length finishes, across fused chunks."""

    async def main():
        prompts = [
            (dict(max_tokens=40), REPETITIVE),
            (dict(max_tokens=40, stop_token_ids=[83, 126]), REPETITIVE),
            (dict(max_tokens=8), RANDOM),
            (dict(max_tokens=30, temperature=0.9, seed=3), [7] * 12),
        ]

        async def run(vectorized):
            engine = TpuEngine(
                EngineConfig(**CFG, decode_steps=4, pipeline_depth=2)
            )
            engine._vectorized_accept = vectorized
            results = await asyncio.gather(
                *[_generate(engine, p, **kw) for kw, p in prompts]
            )
            _assert_kv_consistent(engine)
            await engine.close()
            return [
                (r[0], r[1]["finish_reason"], r[1]["usage"]) for r in results
            ]

        assert await run(True) == await run(False)

    asyncio.run(main())


def test_logprobs_requests_keep_per_token_payloads():
    """Logprob rows take the scalar path and still deliver one payload per
    token under fused decode AND under speculation."""

    async def main():
        engine = TpuEngine(_spec_cfg(decode_steps=4))
        stream = await engine.generate(
            Context(_req(REPETITIVE, max_tokens=12, logprobs=2))
        )
        out = await collect(stream)
        await engine.close()
        tok_items = [it for it in out if it.get("token_ids")]
        assert all(len(it["token_ids"]) == 1 for it in tok_items)
        assert all("logprobs" in it for it in tok_items)
        assert all(len(it["logprobs"]["top"]) == 2 for it in tok_items)

    asyncio.run(main())


# ------------------------------------------------------------------ metrics
def test_spec_metrics_render():
    spec_metrics.reset()
    spec_metrics.drafted_total = 10
    spec_metrics.accepted_total = 7
    spec_metrics.emitted_total = 9
    spec_metrics.dispatches_total = 2
    text = spec_metrics.render("dynamo_tpu")
    assert "dynamo_tpu_spec_decode_acceptance_rate 0.7" in text
    assert "dynamo_tpu_spec_decode_tokens_per_dispatch 4.5" in text
    assert "dynamo_tpu_spec_decode_drafted_tokens_total 10" in text
    assert "dynamo_tpu_spec_decode_fallback_total 0" in text
    spec_metrics.reset()


def test_engine_metrics_endpoint_includes_spec_gauges():
    """The HTTP edge /metrics exposition carries the spec gauges."""
    from dynamo_tpu.llm.http_service import HttpService

    async def main():
        svc = HttpService()
        from aiohttp.test_utils import TestClient, TestServer

        client = TestClient(TestServer(svc.app))
        await client.start_server()
        resp = await client.get("/metrics")
        body = await resp.text()
        await client.close()
        assert "spec_decode_acceptance_rate" in body
        assert "spec_decode_tokens_per_dispatch" in body

    asyncio.run(main())
