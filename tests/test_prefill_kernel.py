"""Chunked paged Pallas prefill kernel gates (ISSUE 19).

The prefill sibling of test_decode_kernel.py, all CPU-runnable:

1. **Interpret-mode parity vs the XLA oracle** — the kernel body (per-row
   q-block DMA at ragged offsets, double-buffered paged-prefix stream,
   in-kernel dequant, KV splits + LSE combine) runs under the Pallas
   interpreter against ``ragged_attention``'s XLA fallback across ragged
   multi-row geometries, int8 pages, traced scales, every block knob.
2. **Chunk-boundary causality suite** — the engine prefills the SAME
   prompt split at every page-boundary offset (chunk ends mid-page,
   on-page, one-past) under int8 and fp8 KV: the sealed KV bytes and the
   token stream must be byte-identical across chunkings, across
   DYN_PREFILL_KERNEL modes, and vs single-shot prefill — with zero new
   compiles after warmup.
3. **Prompts inside a session** — with the kernel enabled (interpret
   mode) a long prompt's chunks interleave with fused decode chunks inside
   one session, by counted events.
4. **Selector / tuner / metrics plumbing** — resolve_prefill_kernel
   semantics, tuned-table prefill keys, the prefill-chunk summary on
   ``/metrics``.
"""

import asyncio
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.ops.decode_attention import (
    clear_tuned_hints,
    hint_key,
    install_tuned_hints,
    resolve_hint,
)
from dynamo_tpu.ops.prefill_attention import fused_prefill_attention
from dynamo_tpu.ops.ragged_attention import (
    ragged_attention,
    resolve_prefill_kernel,
)

pytestmark = pytest.mark.prefill_kernel


# --------------------------------------------------------------- parity


def _case(seed, S, PP, ps, KV, G, D, kv_lens_list, q_lens_list,
          dtype=jnp.float32, kv_scale=None, pad_tokens=2):
    """Ragged chunked-prefill batch: row i's queries are the LAST
    ``q_lens_list[i]`` tokens of its ``kv_lens_list[i]``-token context —
    shuffled page tables, optional quantized pages, trailing padding
    tokens past cu_q_lens[num_seqs]."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    H = KV * G
    P = S * PP + 3  # spare pages: tables must be a strict subset
    T = sum(q_lens_list) + pad_tokens
    q = jax.random.normal(keys[0], (T, H, D), jnp.float32)
    vals = jax.random.normal(keys[1], (P, ps, 2 * KV, D), jnp.float32) * 3.0
    if dtype == jnp.int8:
        pages = jnp.clip(jnp.round(vals / kv_scale), -127, 127).astype(jnp.int8)
    else:
        pages = vals
    kv_lens = np.zeros(S, np.int32)
    kv_lens[: len(kv_lens_list)] = kv_lens_list
    cu = np.zeros(S + 1, np.int32)
    for i, n in enumerate(q_lens_list):
        cu[i + 1] = cu[i] + n
    for i in range(len(q_lens_list), S):
        cu[i + 1] = cu[i]
    tables = np.asarray(
        np.random.default_rng(seed).permutation(S * PP), np.int32
    ).reshape(S, PP)
    num = np.asarray([len(q_lens_list)], np.int32)
    return (q, pages, jnp.asarray(kv_lens), jnp.asarray(tables),
            jnp.asarray(cu), jnp.asarray(num))


GEOMETRIES = [
    # (S, PP, ps, KV, G, D, kv lens, q lens, dtype, scale)
    # mixed chunk tails + a full self-attending prompt
    (3, 4, 4, 2, 2, 16, [16, 7, 12], [16, 3, 12], jnp.float32, None),
    # rows past num_seqs (padding rows must stay exactly zero)
    (4, 4, 4, 2, 2, 16, [13, 9], [5, 9], jnp.float32, None),
    # int8 pages + a 1-token chunk + a zero-query row mid-batch
    (4, 4, 8, 2, 1, 16, [32, 1, 17, 5], [4, 1, 17, 2], jnp.int8, 0.05),
    # single long row: KV splits cover an uneven page count
    (1, 16, 4, 1, 2, 16, [61], [13], jnp.int8, 0.1),
    # fp32 with a non-trivial scale (the scale path without quantization)
    (2, 5, 2, 2, 1, 8, [9, 10], [3, 10], jnp.float32, 2.5),
]


@pytest.mark.parametrize("geom", GEOMETRIES, ids=lambda g: f"S{g[0]}PP{g[1]}")
@pytest.mark.parametrize("qb,splits,ppcb", [(128, 1, 1), (4, 2, 2), (1, 3, 1)])
def test_prefill_kernel_parity_vs_xla_oracle(geom, qb, splits, ppcb):
    S, PP, ps, KV, G, D, kls, qls, dt, scale = geom
    q, pages, kv_lens, tables, cu, num = _case(
        0, S, PP, ps, KV, G, D, kls, qls, dt, scale
    )
    sm = D**-0.5
    want = ragged_attention(
        q, pages, kv_lens, tables, cu, num, sm_scale=sm, kv_scale=scale,
        prefill_kernel="xla",
    )
    got = fused_prefill_attention(
        q, pages, kv_lens, tables, cu, num, sm_scale=sm, kv_scale=scale,
        q_block=qb, num_kv_splits=splits, pages_per_block=ppcb,
        interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )
    # Padding tokens (at/past cu_q_lens[num_seqs]) are exactly zero.
    np.testing.assert_array_equal(np.asarray(got)[int(cu[int(num[0])]):], 0.0)


def test_prefill_kernel_traced_scale_under_jit():
    """kv_scale is an SMEM operand: a TRACED per-layer calibration scale
    works without the algebraic q/out fold the stock path needs."""
    S, PP, ps, KV, G, D = 4, 4, 8, 2, 1, 16
    q, pages, kv_lens, tables, cu, num = _case(
        0, S, PP, ps, KV, G, D, [32, 1, 17, 5], [4, 1, 17, 2], jnp.int8, 0.05
    )
    sm = D**-0.5

    @jax.jit
    def f(q, pages, s):
        return fused_prefill_attention(
            q, pages, kv_lens, tables, cu, num, sm_scale=sm, kv_scale=s,
            q_block=4, num_kv_splits=2, pages_per_block=1, interpret=True,
        )

    got = f(q, pages, jnp.float32(0.05))
    want = ragged_attention(
        q, pages, kv_lens, tables, cu, num, sm_scale=sm, kv_scale=0.05,
        prefill_kernel="xla",
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


def test_routed_through_ragged_attention():
    """prefill_kernel="pallas" routes the entry the model forward calls."""
    S, PP, ps, KV, G, D = 3, 4, 4, 2, 2, 16
    q, pages, kv_lens, tables, cu, num = _case(
        1, S, PP, ps, KV, G, D, [16, 7, 12], [16, 3, 12]
    )
    sm = D**-0.5
    want = ragged_attention(
        q, pages, kv_lens, tables, cu, num, sm_scale=sm, prefill_kernel="xla"
    )
    got = ragged_attention(
        q, pages, kv_lens, tables, cu, num, sm_scale=sm,
        prefill_kernel="pallas",
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


# ------------------------------------------------------------- selector


def test_resolve_prefill_kernel(monkeypatch):
    monkeypatch.delenv("DYN_PREFILL_KERNEL", raising=False)
    assert resolve_prefill_kernel("stock") == "stock"
    assert resolve_prefill_kernel("xla") == "xla"
    assert resolve_prefill_kernel("pallas") == "pallas"
    # auto on CPU resolves to stock (pre-kernel behaviour unchanged)
    assert resolve_prefill_kernel("auto") == "stock"
    # attn_impl="xla" pins auto to stock; an EXPLICIT pallas still wins.
    assert resolve_prefill_kernel("auto", attn_impl="xla") == "stock"
    assert resolve_prefill_kernel("pallas", attn_impl="xla") == "pallas"
    # ''/whitespace env means unset.
    monkeypatch.setenv("DYN_PREFILL_KERNEL", "")
    assert resolve_prefill_kernel("auto") == "stock"
    assert resolve_prefill_kernel("") == "stock"
    # env fills the auto slot; explicit config still wins over env.
    monkeypatch.setenv("DYN_PREFILL_KERNEL", "pallas")
    assert resolve_prefill_kernel("auto") == "pallas"
    assert resolve_prefill_kernel("xla") == "xla"
    with pytest.raises(ValueError):
        resolve_prefill_kernel("fused")  # typo'd names fail loudly


def test_engine_config_validates_prefill_kernel():
    from dynamo_tpu.engine import EngineConfig

    with pytest.raises(ValueError):
        EngineConfig(model="debug-tiny", prefill_kernel="bogus")


# --------------------------------------- engine chunk-boundary suite

CFG = dict(
    model="debug-tiny",
    block_size=4,
    num_blocks=64,
    max_batch=2,
    max_model_len=64,
    dtype="float32",
    decode_steps=2,
    pipeline_depth=2,
)


def _req(tokens, max_tokens=3, seed=None, temperature=0.0):
    from dynamo_tpu.llm.protocols import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )

    return PreprocessedRequest(
        token_ids=list(tokens),
        stop_conditions=StopConditions(max_tokens=max_tokens, ignore_eos=True),
        sampling_options=SamplingOptions(temperature=temperature, seed=seed),
    ).to_dict()


def _prompt(i, n):
    return [(i * 7919 + j * 104729) % 251 + 1 for j in range(n)]


def _run_chunk_case(prefill_kernel, cache_dtype, chunk, prompt_len=10,
                    max_tokens=3):
    """One request through a fresh engine: returns the token stream AND the
    request's sealed KV bytes (its blocks gathered across all layers in
    logical order, so the comparison is independent of physical block
    ids), plus compile stability."""
    from dynamo_tpu.engine import EngineConfig
    from dynamo_tpu.engine.engine import TpuEngine
    from dynamo_tpu.runtime.engine import Context, collect

    out = {}

    async def go():
        cfg = EngineConfig(
            **CFG,
            prefill_chunk=chunk,
            prefill_kernel=prefill_kernel,
            cache_dtype=cache_dtype,
            kv_scale=0.05 if cache_dtype == "int8" else 1.0,
        )
        engine = TpuEngine(cfg)
        compiles0 = engine.warmup()
        # Capture the request's block ids at removal time (remove() frees
        # AND clears them; freed blocks keep their contents in the reuse
        # pool, so the pages stay readable until close).
        captured = {}
        orig_remove = engine.scheduler.remove

        def remove(seq):
            captured[seq.request_id] = list(seq.block_ids)
            return orig_remove(seq)

        engine.scheduler.remove = remove
        try:
            items = await collect(
                await engine.generate(
                    Context(_req(_prompt(3, prompt_len),
                                 max_tokens=max_tokens))
                )
            )
            out["stream"] = [t for it in items for t in it["token_ids"]]
            out["compiles_stable"] = engine.compile_counts() == compiles0
            out["resolved"] = engine.prefill_kernel
            # The removal runs on the engine loop's NEXT pass after the
            # stream's last item — give it a few ticks.
            for _ in range(500):
                if captured:
                    break
                await asyncio.sleep(0.01)
            (ids,) = captured.values()
            # [num_layers, num_pages, page_size, 2*kv_heads, head_dim]
            pages = np.asarray(engine.cache.pages)
            out["kv_bytes"] = b"".join(
                pages[l, b].tobytes()
                for l in range(pages.shape[0])
                for b in ids
            )
            out["prefill_chunks"] = engine.prefill_chunks
        finally:
            await engine.close()

    asyncio.run(go())
    return out


@pytest.mark.parametrize("cache_dtype", ["int8", "float8_e4m3fn"])
def test_chunk_boundary_byte_identity(cache_dtype):
    """Prefill split at every page-boundary offset (block_size=4: chunk 3
    ends mid-page, 4 on-page, 5 one-past) must leave the sealed KV bytes
    and the full token stream byte-identical — across chunkings, across
    DYN_PREFILL_KERNEL modes, and vs single-shot prefill."""
    baseline = _run_chunk_case("xla", cache_dtype, chunk=64)  # single-shot
    assert baseline["compiles_stable"]
    for chunk in (3, 4, 5):
        runs = {
            k: _run_chunk_case(k, cache_dtype, chunk)
            for k in ("pallas", "xla")
        }
        for k, r in runs.items():
            assert r["resolved"] == k
            assert r["compiles_stable"], (
                f"{cache_dtype}/chunk{chunk}/{k}: compiles grew after warmup"
            )
            assert r["prefill_chunks"] > 0
            assert r["stream"][0] == baseline["stream"][0], (
                f"{cache_dtype}/chunk{chunk}/{k}: first token diverged "
                "from single-shot prefill"
            )
            assert r["stream"] == baseline["stream"], (
                f"{cache_dtype}/chunk{chunk}/{k}: stream diverged"
            )
            assert r["kv_bytes"] == baseline["kv_bytes"], (
                f"{cache_dtype}/chunk{chunk}/{k}: sealed KV bytes diverged "
                "from single-shot prefill"
            )


def test_stock_kernel_matches_across_chunkings():
    """The pre-existing stock path holds the same chunk-boundary bar (the
    suite must catch a write-path regression, not just a kernel one)."""
    a = _run_chunk_case("stock", "int8", chunk=3)
    b = _run_chunk_case("stock", "int8", chunk=64)
    assert a["stream"] == b["stream"]
    assert a["kv_bytes"] == b["kv_bytes"]


# --------------------------------------------- prompts inside a session


def test_mixed_phase_cadence_with_kernel_enabled():
    """With the Pallas prefill kernel in interpret mode, a long prompt that
    arrives beside a decoding row prefills INSIDE the fused session: its
    chunks are enqueued ahead of the iterations' fused chunks
    (``prompt_step_order``), fused chunks were dispatched, and the
    prefill-chunk summary surfaces on dispatch_summary.  Counted events
    only: no cadence, no wall time."""
    from dynamo_tpu.engine import EngineConfig
    from dynamo_tpu.engine.engine import TpuEngine
    from dynamo_tpu.runtime.engine import Context

    async def go():
        cfg = EngineConfig(
            **dict(CFG, prefill_chunk=4, prefill_kernel="pallas", decode_steps=4)
        )
        engine = TpuEngine(cfg)
        engine.warmup()
        try:

            async def one(i, n, max_tokens, started=None):
                tokens = []
                async for it in await engine.generate(
                    Context(_req(_prompt(i, n), max_tokens=max_tokens))
                ):
                    tokens += it["token_ids"]
                    if started is not None:
                        started.set()
                return tokens

            # The long prompt is sent when the short row's FIRST token has
            # reached this loop, so the row is decoding (eleven fused chunks
            # to go) when the prompt's ten chunks arrive: an order of events,
            # where two requests sent together raced the first token's way to
            # the host against ten prompt steps (it lost once under six
            # workers: no prompt step ran inside a session).
            started = asyncio.Event()
            short = asyncio.create_task(one(1, 6, 48, started))
            await started.wait()
            streams = [await one(2, 40, 8), await short][::-1]
            assert [len(s) for s in streams] == [48, 8]
            kinds = {k for k, *_ in engine.step_trace}
            assert "decode_dispatch" in kinds, kinds
            assert not any("burst" in k for k in kinds), kinds
            summary = engine.dispatch_summary()
            assert summary["pipeline"]["prompt_step"]["ahead"] >= 1, summary
            assert summary["pipeline"]["prompt_step"]["behind"] == 0, summary
            assert summary["prefill_kernel"] == "pallas"
            assert summary["prefill"]["chunks"] == engine.prefill_chunks >= 11
            assert summary["prefill"]["prompt_tokens"] >= 46
            assert summary["prefill"]["wall_s"] > 0
        finally:
            await engine.close()

    asyncio.run(go())


# ------------------------------------------- tuner table + metrics


@pytest.fixture
def clean_hints():
    clear_tuned_hints()
    yield
    clear_tuned_hints()


def test_tuned_table_serves_prefill_keys(tmp_path, monkeypatch, clean_hints):
    """The prefill knobs ride the SAME tuned table as the decode families
    (tools/tune_decode.py writes one entry per geometry)."""
    table = {
        hint_key("debug-tiny", 4, 4): {
            "splits": 3, "prefill_qb": 7, "prefill_splits": 2,
            "prefill_ppcb": 3,
        }
    }
    path = tmp_path / "tune.json"
    path.write_text(json.dumps(table))
    monkeypatch.setenv("DYN_DECODE_TUNE_TABLE", str(path))
    for v in ("DYN_PREFILL_QB", "DYN_PREFILL_SPLITS", "DYN_PREFILL_PPCB"):
        monkeypatch.delenv(v, raising=False)

    install_tuned_hints("debug-tiny", 4, 4)
    assert resolve_hint("DYN_PREFILL_QB", "prefill_qb", 128) == 7
    assert resolve_hint("DYN_PREFILL_SPLITS", "prefill_splits", 0) == 2
    assert resolve_hint("DYN_PREFILL_PPCB", "prefill_ppcb", 99) == 3
    # Explicit env var still wins over the tuned entry.
    monkeypatch.setenv("DYN_PREFILL_QB", "64")
    assert resolve_hint("DYN_PREFILL_QB", "prefill_qb", 128) == 64


def test_tune_sweep_prefill_smoke(clean_hints):
    """One combo through the sweep harness end-to-end (interpret mode on
    CPU — a smoke of the case builder + kernel-call plumbing, not a
    timing)."""
    from tools.tune_decode import _build_prefill_case, sweep_prefill

    case = _build_prefill_case("debug-tiny", 2, 4, 4, "int8", 8, 0)
    best, allr = sweep_prefill(case, [8], [1], [1], iters=1)
    assert best is not None
    assert best["qb"] == 8 and best["splits"] == 1 and best["ppcb"] == 1
    assert allr == [best]


def test_prefill_chunk_metric_on_metrics():
    """dynamo_tpu_prefill_chunk_seconds rides /metrics off the dispatch
    summary source, plus the prefill kernel info gauge."""
    from dynamo_tpu.llm.metrics import EngineDispatchMetrics

    m = EngineDispatchMetrics()
    m.set_source(
        lambda: {
            "kinds": {},
            "decode_kernel": "pallas_fused",
            "prefill_kernel": "pallas",
            "prefill": {
                "chunks": 12, "wall_s": 0.5, "prompt_tokens": 4096,
                "p50_ms": 40.0, "p99_ms": 55.0,
            },
            "pipeline": {"stalls": 0, "host_gap_frac": 0.1},
        }
    )
    text = m.render()
    assert 'prefill_kernel_info{kernel="pallas"} 1' in text
    assert 'dynamo_tpu_prefill_chunk_seconds{quantile="0.5"} 0.04' in text
    assert 'dynamo_tpu_prefill_chunk_seconds{quantile="0.99"} 0.055' in text
    assert "dynamo_tpu_prefill_chunk_seconds_sum 0.5" in text
    assert "dynamo_tpu_prefill_chunk_seconds_count 12" in text
    assert "dynamo_tpu_prefill_tokens_total 4096" in text
