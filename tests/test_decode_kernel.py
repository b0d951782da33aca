"""Fused-dequant Pallas decode kernel gates (ISSUE 13).

Four layers of defense, all CPU-runnable:

1. **Interpret-mode parity vs the XLA oracle** — the pallas kernel body
   (split-KV grid, double-buffered page DMA, in-kernel dequant) runs
   under the Pallas interpreter against ``ragged_decode_attention``'s XLA
   fallback on ragged page tables: varying chain lengths, int8 and fp32
   KV, static and traced scales, empty rows, every split/block combo.
2. **Exact-stream equivalence across DYN_DECODE_KERNEL modes** — the
   engine must emit byte-identical token streams under
   pallas_fused/stock/xla at temperature 0 AND seeded temperature 0.9,
   spec decode on or off, with ZERO new compiles after warmup.
3. **Decode-stall watchdog** — an injected fetch hang trips the counter +
   loud log; a clean run stays silent.
4. **Autotuner table** — install/fallback resolution order (env > tuned >
   default) and the merge-on-write behaviour of tools/tune_decode.py.
"""

import asyncio
import json
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.ops.decode_attention import (
    active_hints,
    clear_tuned_hints,
    fused_decode_attention,
    hint_key,
    install_tuned_hints,
    resolve_hint,
)
from dynamo_tpu.ops.ragged_attention import (
    ragged_decode_attention,
    resolve_decode_kernel,
)

pytestmark = pytest.mark.decode_kernel


# --------------------------------------------------------------- parity


FP8 = getattr(jnp, "float8_e4m3fn", None)  # where the backend has it


def _case(seed, S, PP, ps, KV, G, D, kv_lens_list, nvalid,
          dtype=jnp.float32, kv_scale=None, q_dtype=jnp.float32):
    """Ragged decode batch: shuffled page tables, per-row chain lengths,
    optionally quantized pages (int8, fp8) stored as value/scale, or bf16
    pages; ``q`` in float32 or bf16."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    H = KV * G
    P = S * PP + 3  # spare pages: tables must be a strict subset
    q = jax.random.normal(keys[0], (S, H, D), jnp.float32).astype(q_dtype)
    vals = jax.random.normal(keys[1], (P, ps, 2 * KV, D), jnp.float32) * 3.0
    if dtype == jnp.int8:
        pages = jnp.clip(jnp.round(vals / kv_scale), -127, 127).astype(jnp.int8)
    elif dtype == jnp.float32:
        pages = vals
    else:  # fp8 (value/scale, rounded by the cast) or bf16
        pages = (vals / (kv_scale or 1.0)).astype(dtype)
    kv_lens = np.zeros(S, np.int32)
    kv_lens[: len(kv_lens_list)] = kv_lens_list
    tables = np.asarray(
        np.random.default_rng(seed).permutation(S * PP), np.int32
    ).reshape(S, PP)
    num = np.asarray([nvalid], np.int32)
    return q, pages, jnp.asarray(kv_lens), jnp.asarray(tables), jnp.asarray(num)


def _fused_f32(q, *args, **kw):
    """The fused kernel's result BEFORE it takes ``q``'s type (float32), so
    that a bf16 ``q`` is held to the file's tolerance and not to bf16's
    spacing; the public wrapper is that, cast
    (test_operands_follow_the_dtypes_the_kernel_sees)."""
    from dynamo_tpu.ops import decode_attention as da

    return da._attend(q, *args, **{"interpret": True, **kw})


def _oracle(q, *args, **kw):
    """The XLA fallback on the same VALUES in float32 (it casts its result
    to ``q``'s type too)."""
    return ragged_decode_attention(
        q.astype(jnp.float32), *args, impl="xla", **kw
    )


GEOMETRIES = [
    # (S, PP, ps, KV, G, D, chain lengths, valid rows, dtype, scale)
    (4, 6, 4, 2, 2, 16, [24, 1, 13, 7], 4, jnp.float32, None),
    (4, 6, 4, 2, 2, 16, [24, 1, 13, 7], 2, jnp.float32, None),  # empty rows
    (5, 8, 4, 1, 4, 16, [32, 0, 5, 17, 2], 5, jnp.int8, 0.05),  # int8 + 0-len
    (2, 5, 2, 2, 1, 8, [9, 10], 2, jnp.float32, 2.5),  # fp32 with scale
    (3, 4, 4, 2, 2, 8, [16, 16, 16], 3, jnp.int8, 0.1),  # full chains
    # Work follows the live pages (ISSUE 26).  Slots are sparse, not
    # compacted: empty rows sit BETWEEN live ones.  At ppcb 2 a block is 8
    # positions: 13 is no multiple of it, 8 is exactly one block, 1 is one
    # token, 32 fills PP; at ppcb 3 the block does not divide PP either.
    (6, 8, 4, 2, 2, 16, [13, 0, 8, 1, 0, 32], 6, jnp.float32, None),
    (6, 8, 4, 1, 4, 16, [13, 0, 8, 1, 0, 32], 6, jnp.int8, 0.05),
]
# The other page types whose values bf16 holds exactly (ISSUE 48).
MORE_PAGE_TYPES = [
    (6, 8, 4, 2, 2, 16, [13, 0, 8, 1, 0, 32], 6, jnp.bfloat16, None),
] + (
    [(6, 8, 4, 1, 4, 16, [13, 0, 8, 1, 0, 32], 6, FP8, 0.05)] if FP8 else []
)
SPLITS_PPCB = [(1, 1), (2, 2), (3, 1), (4, 2), (1, 2), (1, 3), (2, 3)]
F32, BF16 = jnp.float32, jnp.bfloat16


def _parity_cases():
    """(geom, splits, ppcb, q dtype, window).  The operand axis (ISSUE
    48): the kernel's dots take bf16 where the pages are one byte wide or
    bf16 AND ``q`` is bf16, float32 otherwise; every page type meets both
    ``q`` types, with and without a window, at this file's one tolerance."""
    cases = [(g, s, b, F32, None) for s, b in SPLITS_PPCB for g in GEOMETRIES]
    cases += [(g, s, b, F32, None) for s, b in SPLITS_PPCB[-3:]
              for g in MORE_PAGE_TYPES]
    for q_dtype, window in ((BF16, None), (BF16, 6), (F32, 6)):
        cases += [(g, s, b, q_dtype, window) for s, b in ((1, 2), (2, 3))
                  for g in GEOMETRIES + MORE_PAGE_TYPES]
    return cases


def _parity_id(case):
    g, splits, ppcb, q_dtype, window = case
    return (f"{splits}-{ppcb}-S{g[0]}PP{g[1]}n{g[7]}-{jnp.dtype(g[8]).name}"
            f"-q{jnp.dtype(q_dtype).name}" + (f"-w{window}" if window else ""))


@pytest.mark.parametrize("case", _parity_cases(), ids=_parity_id)
def test_fused_kernel_parity_vs_xla_oracle(case):
    geom, splits, ppcb, q_dtype, window = case
    S, PP, ps, KV, G, D, lens, nv, dt, scale = geom
    q, pages, kv_lens, tables, num = _case(0, S, PP, ps, KV, G, D, lens, nv,
                                           dt, scale, q_dtype)
    sm = D**-0.5
    want = _oracle(
        q, pages, kv_lens, tables, num, sm_scale=sm, kv_scale=scale,
        window=window,
    )
    got = _fused_f32(
        q, pages, kv_lens, tables, num, sm_scale=sm, kv_scale=scale,
        num_kv_splits=splits, pages_per_block=ppcb, interpret=True,
        window=window,
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )
    # Rows past num_seqs and zero-length rows are exactly zero (the
    # oracle's padding contract).
    for i in range(S):
        if i >= nv or int(kv_lens[i]) == 0:
            np.testing.assert_array_equal(np.asarray(got)[i], 0.0)


def test_operands_follow_the_dtypes_the_kernel_sees():
    """bf16 exactly where it holds every value of both dots' operands."""
    from dynamo_tpu.ops.decode_attention import built_operands, operand_dtype

    exact = [jnp.int8, BF16] + ([FP8] if FP8 else [])
    for pages_dtype in exact:
        assert operand_dtype(BF16, pages_dtype) == BF16
        assert operand_dtype(F32, pages_dtype) == F32
    assert operand_dtype(BF16, F32) == F32
    for q_dtype, name in ((BF16, "bf16"), (F32, "float32")):
        q, pages, kv_lens, tables, num = _case(
            0, 3, 4, 4, 2, 2, 8, [16, 5, 0], 3, jnp.int8, 0.1, q_dtype
        )
        args = (q, pages, kv_lens, tables, num)
        got = fused_decode_attention(
            *args, sm_scale=1.0, kv_scale=0.1, interpret=True
        )
        assert built_operands() == name
        assert got.dtype == q_dtype
        np.testing.assert_array_equal(
            np.asarray(got.astype(F32)),
            np.asarray(
                _fused_f32(*args, sm_scale=1.0, kv_scale=0.1)
                .astype(q_dtype).astype(F32)
            ),
        )


class _CountingTpu:
    """``pltpu`` with ``make_async_copy`` wrapped so each start and wait
    bumps a host counter — interpret mode runs the callbacks, the chip's
    build never sees this."""

    def __init__(self, real, counts):
        self._real, self._counts = real, counts

    def __getattr__(self, name):
        return getattr(self._real, name)

    def make_async_copy(self, src, dst, sem):
        dma, counts = self._real.make_async_copy(src, dst, sem), self._counts

        def bump(key):
            jax.debug.callback(lambda: counts.__setitem__(key, counts[key] + 1))

        class Counted:
            def start(self):
                bump("started")
                dma.start()

            def wait(self):
                bump("waited")
                dma.wait()

        return Counted()


@pytest.mark.parametrize("dtype,scale", [(jnp.float32, None), (jnp.int8, 0.05)],
                         ids=["f32", "int8"])
@pytest.mark.parametrize("splits,ppcb", [(1, 1), (1, 2), (1, 3), (2, 2), (1, 8)])
def test_fused_kernel_copies_only_live_pages(monkeypatch, splits, ppcb,
                                             dtype, scale):
    """The property ISSUE 26 exists for, counted not timed: the kernel
    starts one page copy for each page a row HAS — none for a padding row
    (kv_lens 0, or past num_seqs), none past a row's last page in its last
    block — and waits for exactly those."""
    from dynamo_tpu.ops import decode_attention as da

    S, PP, ps, KV, G, D = 7, 8, 4, 2, 2, 16
    lens = [13, 0, 8, 1, 0, 32, 20]
    nvalid = 6  # row 6 holds 20 tokens but lies past num_seqs
    q, pages, kv_lens, tables, num = _case(
        3, S, PP, ps, KV, G, D, lens, nvalid, dtype, scale
    )
    counts = {"started": 0, "waited": 0}
    monkeypatch.setattr(da, "pltpu", _CountingTpu(da.pltpu, counts))
    got = fused_decode_attention(
        q, pages, kv_lens, tables, num, sm_scale=D**-0.5, kv_scale=scale,
        num_kv_splits=splits, pages_per_block=ppcb, interpret=True,
    )
    jax.block_until_ready(got)
    jax.effects_barrier()
    live_pages = sum(-(-n // ps) for n in lens[:nvalid])
    assert counts == {"started": live_pages, "waited": live_pages}


def test_fused_kernel_default_blocks_follow_page_shape(monkeypatch):
    """The built-in defaults: a compute block of MAX_BLOCK_CTX positions
    whatever the page size (fewer where the VMEM budget holds fewer), and
    one split."""
    from dynamo_tpu.ops import decode_attention as da

    for name in ("DYN_DECODE_NKV_MB", "DYN_DECODE_FUSED_PPCB", "DYN_DECODE_SPLITS"):
        monkeypatch.delenv(name, raising=False)
    clear_tuned_hints()
    for ps in (16, 32, 128):
        assert da._default_ppcb(ps, 8, 128, 1) * ps == da.MAX_BLOCK_CTX
    # bf16 pages of 8 KV heads (llama-3.1-8b): the 4MB double-buffered
    # budget holds exactly a full block; at 16 KV heads, or under a 1MB
    # budget, it holds fewer pages and wins.
    assert da._default_ppcb(16, 16, 128, 2) * 16 == da.MAX_BLOCK_CTX
    assert da._default_ppcb(16, 32, 128, 2) == 16
    monkeypatch.setenv("DYN_DECODE_NKV_MB", "1")
    assert da._default_ppcb(16, 16, 128, 2) == 8


def test_stock_branch_floors_context_and_zeroes_padding_rows(monkeypatch):
    """The floor of one token lives where it is needed — the stock
    kernel's call — and a row without context comes back zero from it as
    from the other two implementations."""
    import sys
    import types

    seen = {}

    def fake_kernel(q, pages, kv_lens, page_indices, cu, num, **kw):
        seen["kv_lens"] = np.asarray(kv_lens)
        return jnp.ones_like(q)

    name = "jax.experimental.pallas.ops.tpu.ragged_paged_attention"
    fake = types.ModuleType(name)
    fake.ragged_paged_attention = fake_kernel
    monkeypatch.setitem(sys.modules, name, fake)
    S, PP, ps, KV, G, D = 4, 6, 4, 2, 2, 16
    q, pages, kv_lens, tables, num = _case(
        2, S, PP, ps, KV, G, D, [9, 0, 24, 0], 4
    )
    out = ragged_decode_attention(
        q, pages, kv_lens, tables, num, sm_scale=D**-0.5, impl="tpu",
        kernel="stock",
    )
    assert seen["kv_lens"].tolist() == [9, 1, 24, 1]
    assert np.asarray(out)[[0, 2]].min() == 1.0
    np.testing.assert_array_equal(np.asarray(out)[[1, 3]], 0.0)


@pytest.mark.parametrize("q_dtype", [F32, BF16], ids=["qf32", "qbf16"])
@pytest.mark.parametrize(
    "dtype", [jnp.int8] + ([FP8] if FP8 else []), ids=lambda d: jnp.dtype(d).name
)
def test_fused_kernel_traced_scale_under_jit(dtype, q_dtype):
    """The fused kernel's dequant contract: kv_scale is an SMEM operand,
    so a TRACED per-layer calibration scale works without the algebraic
    q/out fold the stock path needs — with either operand type."""
    S, PP, ps, KV, G, D = 5, 8, 4, 1, 4, 16
    q, pages, kv_lens, tables, num = _case(
        0, S, PP, ps, KV, G, D, [32, 0, 5, 17, 2], 5, dtype, 0.05, q_dtype
    )
    sm = D**-0.5

    @jax.jit
    def f(q, pages, s):
        return _fused_f32(
            q, pages, kv_lens, tables, num, sm_scale=sm, kv_scale=s,
            num_kv_splits=2, pages_per_block=2, interpret=True,
        )

    got = f(q, pages, jnp.float32(0.05))
    want = _oracle(q, pages, kv_lens, tables, num, sm_scale=sm, kv_scale=0.05)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


def _float64_reference(q, pages, kv_lens, tables, sm_scale, kv_scale):
    """Plain attention a row in numpy float64 over the same stored values."""
    q, pages = np.asarray(q.astype(F32), np.float64), np.asarray(pages.astype(F32), np.float64)
    S, H, D = q.shape
    KV = pages.shape[2] // 2
    G = H // KV
    out = np.zeros((S, H, D))
    for i, n in enumerate(np.asarray(kv_lens)):
        if n == 0:
            continue
        kv = pages[np.asarray(tables)[i]].reshape(-1, 2 * KV, D)[:n] * kv_scale
        for h in range(KV):
            logits = (q[i, h * G:(h + 1) * G] * sm_scale) @ kv[:, 2 * h].T
            p = np.exp(logits - logits.max(axis=1, keepdims=True))
            out[i, h * G:(h + 1) * G] = (p / p.sum(axis=1, keepdims=True)) @ kv[:, 2 * h + 1]
    return out


def test_bf16_operands_are_no_less_precise_than_float32_ones():
    """"No lower precision" as a test: on the same int8 pages and the same
    bf16 ``q`` values, the bf16-operand form (exact K, V and q in the dots,
    the scales on the logits and the output, p as bf16 pieces) errs against
    float64 no more than the float32-operand form does (the values as
    float32 into float32 dots): over six draws together, and in no draw by
    more than a tenth."""
    S, PP, ps, KV, G, D = 4, 16, 8, 2, 4, 64
    lens = [128, 77, 1, 40]
    sm = D**-0.5
    kw = dict(sm_scale=sm, kv_scale=0.05, pages_per_block=4)
    sq_bf16 = sq_f32 = 0.0
    for seed in range(6):
        q, pages, kv_lens, tables, num = _case(
            seed, S, PP, ps, KV, G, D, lens, S, jnp.int8, 0.05, BF16
        )
        args = (pages, kv_lens, tables, num)
        ref = _float64_reference(q, pages, kv_lens, tables, sm, 0.05)

        def mean_sq(got):
            return float(np.mean((np.asarray(got, np.float64) - ref) ** 2))

        err_bf16 = mean_sq(_fused_f32(q, *args, **kw))
        err_f32 = mean_sq(_fused_f32(q.astype(F32), *args, **kw))
        # Both are float32-grade (bf16's spacing would read 1e-5).
        assert err_f32 < 1e-12 * float(np.mean(ref**2))
        assert err_bf16 <= 1.1**2 * err_f32, (seed, err_bf16, err_f32)
        sq_bf16, sq_f32 = sq_bf16 + err_bf16, sq_f32 + err_f32
    assert sq_bf16 <= sq_f32, (sq_bf16, sq_f32)


@pytest.mark.parametrize("dtype", [jnp.int8, BF16], ids=["int8", "bf16"])
def test_scale_is_folded_onto_logits_and_output(dtype):
    """The cached block is converted, never multiplied: (a) with the logits
    held still (sm_scale / 4 against kv_scale * 4) the outputs stand in
    exactly the scales' ratio; (b) the kernel's program holds no multiply
    the size of ONE dot's cached operand or larger.  int8 pages take the
    word view, bf16 pages the transposition."""
    S, PP, ps, KV, G, D = 3, 16, 4, 2, 2, 32
    q, pages, kv_lens, tables, num = _case(
        4, S, PP, ps, KV, G, D, [64, 9, 20], S, dtype, 0.05, BF16
    )
    args, sm = (q, pages, kv_lens, tables, num), D**-0.5
    one = _fused_f32(*args, sm_scale=sm, kv_scale=0.05, pages_per_block=16)
    four = _fused_f32(*args, sm_scale=sm / 4, kv_scale=0.2, pages_per_block=16)
    assert float(jnp.max(jnp.abs(one))) > 0.1
    np.testing.assert_array_equal(np.asarray(four), 4 * np.asarray(one))

    def muls(jaxpr):
        """Sizes of every product in a jaxpr and the jaxprs inside it."""
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "mul":
                yield int(np.prod(eqn.outvars[0].aval.shape))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from muls(sub)

    closed = jax.make_jaxpr(
        lambda *a: fused_decode_attention(
            *a, sm_scale=sm, kv_scale=0.05, pages_per_block=16, interpret=False
        )
    )(*args)
    (call,) = [e for e in closed.jaxpr.eqns if e.primitive.name == "pallas_call"]
    sizes = list(muls(call.params["jaxpr"]))
    C = 16 * ps  # positions a compute block: K of one head is [C, D]
    assert sizes and max(sizes) < C * D, sizes


def test_routed_through_ragged_decode_attention():
    """kernel="pallas_fused" routes the entry the engine dispatches."""
    S, PP, ps, KV, G, D = 4, 6, 4, 2, 2, 16
    q, pages, kv_lens, tables, num = _case(
        1, S, PP, ps, KV, G, D, [20, 3, 11, 6], 4
    )
    sm = D**-0.5
    want = ragged_decode_attention(
        q, pages, kv_lens, tables, num, sm_scale=sm, impl="xla"
    )
    got = ragged_decode_attention(
        q, pages, kv_lens, tables, num, sm_scale=sm, impl="xla",
        kernel="pallas_fused",
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


# ------------------------------------------------------------- selector


def test_resolve_decode_kernel(monkeypatch):
    monkeypatch.delenv("DYN_DECODE_KERNEL", raising=False)
    assert resolve_decode_kernel("stock") == "stock"
    assert resolve_decode_kernel("xla") == "xla"
    assert resolve_decode_kernel("pallas_fused") == "pallas_fused"
    # auto on CPU resolves to stock (pre-kernel behaviour unchanged)
    assert resolve_decode_kernel("auto") == "stock"
    # attn_impl="xla" (the oracle-numerics debugging contract) pins auto
    # to stock — which honours impl=xla — even where auto would otherwise
    # pick the fused kernel; an EXPLICIT pallas_fused still wins.
    assert resolve_decode_kernel("auto", attn_impl="xla") == "stock"
    assert (
        resolve_decode_kernel("pallas_fused", attn_impl="xla")
        == "pallas_fused"
    )
    # ''/whitespace env means unset (a template rendering an empty value
    # must not fail worker boot), and the config layer tolerates it too.
    monkeypatch.setenv("DYN_DECODE_KERNEL", "")
    assert resolve_decode_kernel("auto") == "stock"
    assert resolve_decode_kernel("") == "stock"
    # env fills the auto slot; explicit config still wins over env
    monkeypatch.setenv("DYN_DECODE_KERNEL", "pallas_fused")
    assert resolve_decode_kernel("auto") == "pallas_fused"
    assert resolve_decode_kernel("xla") == "xla"
    with pytest.raises(ValueError):
        resolve_decode_kernel("fused")  # typo'd names fail loudly


def test_engine_config_validates_decode_kernel():
    from dynamo_tpu.engine import EngineConfig

    with pytest.raises(ValueError):
        EngineConfig(model="debug-tiny", decode_kernel="bogus")


# ------------------------------------------- engine stream equivalence

CFG = dict(
    model="debug-tiny",
    block_size=4,
    num_blocks=256,
    max_batch=4,
    max_model_len=256,
    prefill_chunk=16,
    dtype="float32",
    decode_steps=4,
    pipeline_depth=2,
)


def _req(tokens, max_tokens=10, seed=None, temperature=0.0):
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


def _prompt(i, n=12):
    return [(i * 7919 + j * 104729) % 251 + 1 for j in range(n)]


async def _generate_streams(engine):
    """One engine serves both temperature regimes: temp-0 rows and seeded
    temp-0.9 rows in the same concurrent batch (mixed-temperature
    dispatches are the serving shape, not a per-test luxury)."""
    from dynamo_tpu.runtime.engine import Context, collect

    async def one(i, temperature):
        items = await collect(
            await engine.generate(
                Context(_req(_prompt(i), seed=i + 1, temperature=temperature))
            )
        )
        return [t for it in items for t in it["token_ids"]]

    jobs = [one(i, 0.0) for i in range(3)]
    jobs += [one(i + 10, 0.9) for i in range(3)]
    return await asyncio.gather(*jobs)


def _run_kernel_mode(kernel, spec=None):
    from dynamo_tpu.engine import EngineConfig
    from dynamo_tpu.engine.engine import TpuEngine

    out = {}

    async def go():
        cfg = dict(CFG, decode_kernel=kernel)
        if spec is not None:
            cfg["spec_decode"] = spec
        engine = TpuEngine(EngineConfig(**cfg))
        compiles0 = engine.warmup()
        try:
            out["streams"] = await _generate_streams(engine)
            out["compiles_stable"] = engine.compile_counts() == compiles0
            out["resolved"] = engine.decode_kernel
            out["stalls"] = engine.decode_stalls
        finally:
            await engine.close()

    asyncio.run(go())
    return out


def test_exact_streams_across_kernel_modes():
    """Byte-identical streams pallas_fused vs stock vs xla, temp 0 and
    seeded temp 0.9 in one batch, zero new compiles after warmup — the
    repo's standing kernel gate.  Also the clean-run half of the stall
    watchdog bar: no stall fires without an injected hang."""
    runs = {k: _run_kernel_mode(k) for k in ("stock", "xla", "pallas_fused")}
    for k, r in runs.items():
        assert r["resolved"] == k
        assert r["compiles_stable"], f"{k}: compiles grew after warmup"
        assert r["stalls"] == 0, f"{k}: stall watchdog fired on a clean run"
    assert runs["stock"]["streams"] == runs["xla"]["streams"]
    assert runs["stock"]["streams"] == runs["pallas_fused"]["streams"], (
        "fused kernel changed the token streams"
    )


@pytest.mark.spec
def test_exact_streams_with_spec_decode():
    """Spec decode rides the UNIFIED program (not the fused decode
    kernel), but session flips between the two regimes must still leave
    streams byte-identical across kernel modes."""
    spec = dict(enable=True, k=4, ngram_min=2, ngram_max=3)
    a = _run_kernel_mode("pallas_fused", spec=spec)
    b = _run_kernel_mode("stock", spec=spec)
    assert a["compiles_stable"] and b["compiles_stable"]
    assert a["streams"] == b["streams"], (
        "fused kernel + spec decode diverged from stock"
    )


# ------------------------------------------------------ stall watchdog


def test_stall_watchdog_trips_on_injected_hang(caplog):
    """A wedged token fetch (r5's ~3-minute decode_wait hang class) must
    trip the watchdog: counter bumped, last_stall recorded with the
    dispatch trace, loud log — while the stream still completes once the
    fetch lands."""
    import time as _time

    from dynamo_tpu.engine import EngineConfig
    from dynamo_tpu.engine.engine import TpuEngine

    async def go():
        engine = TpuEngine(
            EngineConfig(**CFG, decode_kernel="stock", decode_stall_s=0.05)
        )
        orig = engine._fetch_outs
        injected = {"n": 0}

        def slow_fetch(out, need_lp):
            if injected["n"] == 0:
                injected["n"] = 1
                _time.sleep(0.4)  # > threshold: a hung device fetch
            return orig(out, need_lp)

        engine._fetch_outs = slow_fetch
        try:
            with caplog.at_level(logging.ERROR, "dynamo_tpu.engine.pipeline"):
                streams = await _generate_streams(engine)
            assert all(len(s) == 10 for s in streams)  # streams completed
            assert engine.decode_stalls >= 1
            stall = engine.dispatch_summary()["pipeline"]
            assert stall["stalls"] == engine.decode_stalls
            assert stall["last_stall"] is not None
            assert stall["last_stall"]["kind"]
            assert isinstance(stall["last_stall"]["trace"], list)
            assert any("decode stall" in r.message for r in caplog.records)
        finally:
            await engine.close()

    asyncio.run(go())


def test_stall_counter_on_metrics():
    """dynamo_tpu_engine_stall_total rides /metrics off the dispatch
    summary source, and the kernel info gauge names the active kernel."""
    from dynamo_tpu.llm.metrics import EngineDispatchMetrics

    m = EngineDispatchMetrics()
    m.set_source(
        lambda: {
            "kinds": {},
            "decode_kernel": "pallas_fused",
            "decode_kernel_operands": "bf16",
            "pipeline": {"stalls": 3, "host_gap_frac": 0.1},
        }
    )
    text = m.render()
    assert "dynamo_tpu_engine_stall_total 3" in text
    assert 'decode_kernel_info{kernel="pallas_fused"} 1' in text
    assert 'decode_kernel_operands_info{operands="bf16"} 1' in text


# ------------------------------------------------------ autotuner table


@pytest.fixture
def clean_hints():
    clear_tuned_hints()
    yield
    clear_tuned_hints()


def test_tuned_hints_install_and_fallback(tmp_path, monkeypatch, clean_hints):
    table = {
        hint_key("debug-tiny", 4, 4): {
            "splits": 3, "ppcb": 2, "nq": 7, "nkv_mb": 1
        }
    }
    path = tmp_path / "tune.json"
    path.write_text(json.dumps(table))
    monkeypatch.setenv("DYN_DECODE_TUNE_TABLE", str(path))
    monkeypatch.delenv("DYN_DECODE_SPLITS", raising=False)
    monkeypatch.delenv("DYN_DECODE_FUSED_PPCB", raising=False)

    # Matching geometry: entry installed, hints resolve from it.
    entry = install_tuned_hints("debug-tiny", 4, 4)
    assert entry == table[hint_key("debug-tiny", 4, 4)]
    assert active_hints() == entry
    assert resolve_hint("DYN_DECODE_SPLITS", "splits", 0) == 3
    assert resolve_hint("DYN_DECODE_FUSED_PPCB", "ppcb", 99) == 2
    # Explicit env var still wins over the tuned entry.
    monkeypatch.setenv("DYN_DECODE_SPLITS", "5")
    assert resolve_hint("DYN_DECODE_SPLITS", "splits", 0) == 5

    # Non-matching geometry: fallback to built-in defaults.
    assert install_tuned_hints("debug-tiny", 8, 16) is None
    assert active_hints() is None
    assert resolve_hint("DYN_DECODE_FUSED_PPCB", "ppcb", 99) == 99

    # Corrupt table: never raises, falls back.
    path.write_text("{not json")
    assert install_tuned_hints("debug-tiny", 4, 4) is None


def test_tuned_hints_feed_stock_block_hints(tmp_path, monkeypatch, clean_hints):
    from dynamo_tpu.ops.ragged_attention import _decode_block_hints

    pages = jnp.zeros((8, 4, 4, 16), jnp.float32)
    tables = jnp.zeros((2, 6), jnp.int32)
    monkeypatch.delenv("DYN_DECODE_NQ", raising=False)
    monkeypatch.delenv("DYN_DECODE_NKV_MB", raising=False)
    nq0, nkv0 = _decode_block_hints(pages, tables)
    assert nq0 == 16  # built-in default

    path = tmp_path / "tune.json"
    path.write_text(json.dumps({hint_key("m", 2, 4): {"nq": 7, "nkv_mb": 4}}))
    monkeypatch.setenv("DYN_DECODE_TUNE_TABLE", str(path))
    install_tuned_hints("m", 2, 4)
    nq, nkv = _decode_block_hints(pages, tables)
    assert nq == 7
    assert nkv == nkv0  # same 4MB budget -> same page count
    # Env pin beats the table.
    monkeypatch.setenv("DYN_DECODE_NQ", "11")
    assert _decode_block_hints(pages, tables)[0] == 11


def test_tune_table_write_merges(tmp_path):
    from tools.tune_decode import write_entry

    path = str(tmp_path / "t.json")
    write_entry(path, "a|b1|ps4", {"splits": 1})
    write_entry(path, "c|b2|ps8", {"splits": 2})
    write_entry(path, "a|b1|ps4", {"splits": 4})  # overwrite in place
    table = json.loads(open(path).read())
    assert table == {"a|b1|ps4": {"splits": 4}, "c|b2|ps8": {"splits": 2}}
