"""Compile the serving path's attention kernels for a DESCRIBED TPU v5e.

No chip is attached here: the chip's compiler (libtpu) is installed and
compiles for ``topologies.get_topology_desc("tpu", "v5e:2x2")``.  What it
refuses here it refuses on the chip — a slice not aligned to the tiling, more
VMEM than a kernel may use — and interpret mode can show neither.  A compile
that passes is not a chip run (``chip_smoke.py`` is).

The topology is described inside a module-scoped fixture and nowhere else:
one process at a time may load libtpu, so the call must not run at import, in
a ``skipif``/``parametrize`` argument or in conftest.py, and these tests stay
in this ONE file (another file could land on another xdist worker, whose
fixture would skip in silence).  Compiles run in the test's own process with
the persistent compilation cache off around them.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from dynamo_tpu.ops.decode_attention import (
    MAX_BLOCK_CTX,
    P_PIECES,
    fused_decode_attention,
)
from dynamo_tpu.ops.prefill_attention import fused_prefill_attention
from dynamo_tpu.ops.ragged_attention import ragged_decode_attention

# (q heads, kv heads) per shard; head_dim 128 and page size 16 throughout.
GEOMETRY = {
    "qwen2.5-7b": (28, 4),
    "llama-3.1-8b": (32, 8),
    "llama-3.1-8b-tp4": (8, 2),
    # 32 query and 8 K/V heads of 64, two K/V heads a 128-lane row
    # (models/lfm2.py ``head_pack``): what the kernels see of lfm2-8b-a1b.
    "lfm2-8b-a1b-packed": (32, 4),
}
D, PS = 128, 16
LAYERS_X_PAGES = 28 * 2048  # the engine's layer-merged page slab
CTX = 4096  # --max-model-len of the smoke's deployment
ROWS = 64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no libtpu / lock held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def sds(topo):
    """ShapeDtypeStruct factory placed on one described chip."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip
    )


@pytest.fixture()
def no_persistent_cache():
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without a chip (JAX warns and recompiles).
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, *shapes) -> str:
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    # Compiled by Mosaic for the chip, not interpreted.
    assert "tpu_custom_call" in text
    return text


def _decode_shapes(sds, model, page_dtype, rows=ROWS, pages=LAYERS_X_PAGES):
    H, KV = GEOMETRY[model]
    PP = CTX // PS
    return (
        sds((rows, H, D), jnp.bfloat16),
        sds((pages, PS, 2 * KV, D), jnp.dtype(page_dtype)),
        sds((rows,), jnp.int32),
        sds((rows, PP), jnp.int32),
        sds((1,), jnp.int32),
    )


def _fused_decode(q, pages, kv_lens, tables, num, scale):
    """The kernel ``auto`` picks for decode on a TPU, built-in defaults,
    with a TRACED kv_scale (the per-layer calibration vector's element)."""
    return fused_decode_attention(
        q, pages, kv_lens, tables, num, sm_scale=D**-0.5,
        kv_scale=scale, interpret=False,
    )


@pytest.mark.parametrize("page_dtype", ["int8", "bfloat16"])
@pytest.mark.parametrize("model", list(GEOMETRY))
def test_fused_decode_kernel_compiles(
    sds, no_persistent_cache, model, page_dtype
):
    _compile(
        _fused_decode, *_decode_shapes(sds, model, page_dtype),
        sds((), jnp.float32),
    )


def _mosaic_bodies(text):
    """The kernels of a compiled program as Mosaic wrote them: each
    ``tpu_custom_call`` carries its module as base64 MLIR bytecode."""
    import base64
    import re

    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib.mlir import ir

    ctx = jax_mlir.make_ir_context()
    ctx.allow_unregistered_dialects = True  # the versioned ``stable_mosaic``
    with ctx:
        return [
            str(ir.Module.parse(base64.b64decode(body)))
            for body in re.findall(r'"body":"([A-Za-z0-9+/=]+)"', text)
        ]


@pytest.mark.parametrize(
    "model,rows_,ctx,heads,pages",
    [
        # chipbench/configs/qwen2.5-7b.json: 32 rows, 256 pages a row,
        # 12288 int8 pages a layer.
        ("qwen2.5-7b", 32, 4096, (28, 4), 28 * 12288),
        # chipbench/configs/k-exaone-236b-a23b-8l-ep8.json, its two full
        # layers: 16 rows, 832 pages a row, 2KV 16, 32768 int8 pages a layer.
        ("k-exaone-236b-a23b-8l-ep8", 16, 13312, (64, 8), 2 * 32768),
    ],
)
def test_fused_decode_kernel_compiles_at_the_cells_shape(
    sds, no_persistent_cache, model, rows_, ctx, heads, pages
):
    """The benchmark cells' decode shapes.  With the built-in defaults the
    call has ONE split and keeps the name the trace readers find it by; the
    LARGE operand of both dots, the cached block, is bf16 and unscaled
    (ISSUE 48), and p meets V as P_PIECES stacked bf16 pieces in ONE dot."""
    import re

    H, KV = heads
    text = _compile(
        _fused_decode,
        sds((rows_, H, D), jnp.bfloat16),
        sds((pages, PS, 2 * KV, D), jnp.int8),
        sds((rows_,), jnp.int32),
        sds((rows_, ctx // PS), jnp.int32),
        sds((1,), jnp.int32),
        sds((), jnp.float32),
    )
    assert "fused_decode_attention" in text
    # The word view: two groups of dots over J = 2KV / 4 heads each, their
    # query rows padded to whole tiles (here 8 a head).
    J = 2 * KV // 4
    rows = J * 8
    assert f"f32[{rows_},1,{2 * rows},{D}]" in text
    (body,) = _mosaic_bodies(text)
    cached = f"vector<{MAX_BLOCK_CTX * J}x{D}xbf16>"  # a dot's K or V
    dots = re.findall(r"tpu\.matmul.*?: \((vector<[^>]+>), (vector<[^>]+>)", body)
    assert dots == 2 * [
        (f"vector<{rows}x{D}xbf16>", cached),  # q K^T
        (f"vector<{P_PIECES * rows}x{MAX_BLOCK_CTX * J}xbf16>", cached),  # p V
    ], dots
    # The cached side is converted (int32 -> float32 -> bf16, a vreg at a
    # time) and never multiplied: no product the size of a dot's operand.
    assert not re.search(
        rf"arith\.mulf.*vector<{MAX_BLOCK_CTX * J}x{D}xf32>", body
    )
    # No transposition and no byte shuffle of the block: the int8 rows are
    # taken out of the stored words by shifts.
    assert "vector.transpose" not in body and "tpu.transpose" not in body
    assert "arith.shrsi" in body


@pytest.mark.parametrize("page_dtype", ["int8", "bfloat16"])
@pytest.mark.parametrize("model", list(GEOMETRY))
def test_prefill_kernel_compiles(sds, no_persistent_cache, model, page_dtype):
    """The kernel ``auto`` picks for prefill on a TPU: a 2048-token chunk
    against a 4096-token paged context."""
    H, KV = GEOMETRY[model]
    T, PP = 2048, CTX // PS

    def fn(q, pages, kv_lens, tables, cu, num, scale):
        return fused_prefill_attention(
            q, pages, kv_lens, tables, cu, num, sm_scale=D**-0.5,
            kv_scale=scale, interpret=False,
        )

    _compile(
        fn,
        sds((T, H, D), jnp.bfloat16),
        sds((LAYERS_X_PAGES, PS, 2 * KV, D), jnp.dtype(page_dtype)),
        sds((ROWS,), jnp.int32),
        sds((ROWS, PP), jnp.int32),
        sds((ROWS + 1,), jnp.int32),
        sds((1,), jnp.int32),
        sds((), jnp.float32),
    )


@pytest.mark.parametrize("page_dtype", ["int8", "bfloat16"])
@pytest.mark.parametrize("model", list(GEOMETRY))
def test_stock_decode_with_repo_hints_compiles(
    sds, no_persistent_cache, model, page_dtype
):
    """``decode_kernel=stock``: the jax ragged_paged_attention kernel under
    the repo's decode block hints (ops/ragged_attention.py
    _decode_block_hints)."""

    def fn(q, pages, kv_lens, tables, num):
        return ragged_decode_attention(
            q, pages, kv_lens, tables, num, sm_scale=D**-0.5, impl="tpu",
            kernel="stock",
        )

    _compile(fn, *_decode_shapes(sds, model, page_dtype))


def test_one_kv_head_per_shard_is_refused_with_a_sentence():
    """qwen2.5-7b at tp=4 with int8 pages leaves one KV head per shard,
    which no kernel here compiles for: the engine says so at validation
    (before allocating anything), not inside Mosaic."""
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import TpuEngine

    cfg = EngineConfig(
        model="qwen2.5-7b", tp=4, cache_dtype="int8", kv_scale=0.05,
        weight_quant="int8", attn_impl="tpu",
    )
    with pytest.raises(ValueError, match="KV head.* per shard"):
        TpuEngine(cfg)


_DSV32_PAGES = 32768 * 16 * (640 + 128) * 2 * 6  # latent and indexer pages, six layers


def _family_step(topo, config_file: str, pages_bytes: int, traced=None, prompt_tokens=None,
                 state_slots: int = 0, window=(), **static_kw):
    """``compiled(decode)``: the whole step of a configuration file of the
    latent or the hybrid family for a described v5e, each program compiled
    once for the tests that share it (which turn the persistent cache off
    around it: ``no_persistent_cache``).  The kernels go through Mosaic as on
    the chip: conftest's interpreter switch is off, and nothing is patched.
    ``static_kw`` and ``traced`` (name -> (shape, dtype)): the engine's options
    on the chip, where the family's defaults are not those.  ``prompt_tokens``:
    the prompt program's token bucket (the configuration's ``prefill_chunk``
    unless given).  ``state_slots``: the slots of a family whose state lives
    in slots beside the pages (the prompt program then names a row's).
    ``window``: (pages of the window pool, a row's window table) of a family
    with layers that keep a window (the step then carries the window side)."""
    import functools
    import json
    import os

    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.models.family import RaggedBatch, family_of

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, config_file)) as f:
        body = json.load(f)
    serve = body["serve"]
    mc = ModelConfig.from_hf_config(body, name=body["name"] + "-compile")
    one_chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)

    fam = family_of(mc)

    @functools.lru_cache(maxsize=None)
    def compiled(decode: bool):
        # int8 leaves where the configuration serves them, the release's dtype elsewhere
        init = fam.init_params_quantized if serve.get("weight_quant") else fam.init_params
        params = on_chip(jax.eval_shape(lambda k: init(mc, k), jax.random.PRNGKey(0)))
        cache = on_chip(jax.eval_shape(lambda: fam.create_cache(
            mc, serve["num_blocks"], serve["block_size"],
            dtype=jnp.dtype(serve["kv_cache_dtype"]),
            **({"state_slots": state_slots} if state_slots else {}),
            **({"window_pages": window[0]} if window else {}))))
        assert sum(a.size * a.dtype.itemsize
                   for a in jax.tree_util.tree_leaves(cache)) == pages_bytes
        S, PP = serve["max_batch"], serve["max_model_len"] // serve["block_size"]
        T = S if decode else prompt_tokens or serve["prefill_chunk"]
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)  # noqa: E731
        rb = RaggedBatch(i32(T), i32(T), i32(T), i32(S), i32(S, PP), i32(S + 1), i32(1),
                         state_slots=i32(S, 3) if state_slots and not decode else None,
                         **(dict(window_indices=i32(S, window[1]), window_lens=i32(S),
                                 window_slots=i32(T)) if window else {}))
        traced_args = {k: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
                       for k, (shape, dtype) in (traced or {}).items()}
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("DYN_PALLAS_INTERPRET", "0")
            return jax.jit(
                lambda p, c, rb, tr: fam.forward(p, mc, rb, c, decode=decode, **static_kw, **tr),
                donate_argnums=1,
            ).lower(params, cache, rb, traced_args).compile()

    return compiled


@pytest.fixture(scope="module")
def dsv32_step(topo):
    return _family_step(topo, "chipbench/configs/deepseek-v3.2-exp-6l-ep16.json", _DSV32_PAGES)


_KIMI_PAGES = 32768 * 16 * 640 * 2 * 6  # latent pages alone, six layers


@pytest.fixture(scope="module")
def kimi_step(topo):
    return _family_step(topo, "chipbench/configs/kimi-k2-6l-ep32.json", _KIMI_PAGES)


_LFM2_KV_PAGES = 16384 * 16 * 2 * 8 * 64 * 6  # int8 K/V of the six attention layers
_LFM2_STATE_PAGES = 16384 * 2 * 2048 * 2 * 18  # one bfloat16 entry a page, eighteen layers


@pytest.fixture(scope="module")
def lfm2_step(topo):
    """chipbench/configs/lfm2-8b-a1b.json whole, with the options the engine
    resolves on a TPU: both Pallas attention kernels and the calibrated
    per-layer K/V scale (traced)."""
    return _family_step(
        topo, "chipbench/configs/lfm2-8b-a1b.json", _LFM2_KV_PAGES + _LFM2_STATE_PAGES,
        traced={"kv_scale": ((6,), jnp.float32)}, attn_impl="tpu",
        decode_kernel="pallas_fused", prefill_kernel="pallas")


@pytest.mark.parametrize("decode", [False, True], ids=["unified-512", "decode-32"])
def test_lfm2_step_compiles_at_the_cells_shapes_without_copying_either_page_array(
    lfm2_step, no_persistent_cache, decode
):
    """chipbench/configs/lfm2-8b-a1b.json whole: 8.36 GB of weights (all 24
    layers, all 32 experts of 22 layers, the whole vocabulary), 16384 pages of
    K/V (head size 64, two heads a lane tile) and of convolution state, a
    512-token chunk (or 32 decode rows) against 256 pages a row.  Both page
    arrays are updated in place and the step's temporaries stay far under the
    smaller of them (a copy into or out of a step would be at least that).
    Attention goes through the two Pallas kernels of the dense family, once a
    layer, and the experts through the grouped matmul: no other kernel."""
    compiled = lfm2_step(decode)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= _LFM2_KV_PAGES + _LFM2_STATE_PAGES
    assert mem.temp_size_in_bytes < 0.4e9 < _LFM2_KV_PAGES, mem
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 13.5e9, mem
    calls = _custom_calls(compiled.as_text())
    attn = "fused_decode_attention" if decode else "fused_prefill_attention"
    assert len([ln for ln in calls if attn in ln]) == 6, calls
    assert len([ln for ln in calls if "moe_grouped_matmul" in ln]) == 44, calls
    assert all(attn in ln or "moe_grouped_matmul" in ln for ln in calls), calls
    # the K/V pages as the kernels see them: 8 rows of 128 lanes a token
    assert "s8[98304,16,8,128]" in compiled.as_text()


def test_lfm2_decode_program_reads_the_experts_through_the_grouped_matmul_alone(
    lfm2_step, no_persistent_cache
):
    """All 32 experts of a layer are held: nothing but the kernel may touch
    an expert leaf, whole or a layer of it (see the latent family's test)."""
    text = lfm2_step(True).as_text()
    leaf = ("s8[22,32,2048,1792]", "s8[22,32,1792,2048]")
    layer = ("s8[32,2048,1792]", "s8[32,1792,2048]", "s8[1,32,2048,1792]", "s8[1,32,1792,2048]")
    calls = [ln for ln in _custom_calls(text) if "moe_grouped_matmul" in ln]
    assert all(any(shape in ln for shape in leaf) for ln in calls), calls
    entry = text[text.index("ENTRY "):]
    for ln in entry.splitlines():
        if " = " not in ln or " parameter(" in ln or "moe_grouped_matmul" in ln:
            continue
        assert not any(shape in ln for shape in leaf + layer), ln
    assert not any(shape in text for shape in layer)


@pytest.mark.parametrize("decode", [False, True], ids=["unified-512", "decode-32"])
def test_lfm2_short_conv_metric_matches_the_scopes_ops_and_no_others(
    lfm2_step, no_persistent_cache, decode
):
    """``short_conv_time_share`` matches XLA's op names (the harness keeps an
    op's name and shape, not its scope).  In both programs compiled for a
    described v5e every op the pattern matches lies under the scope
    ``short_conv``, and the ops that move the page entries are matched:
    another compiler or shape fails HERE, not as a metric that reads 0."""
    import json
    import os
    import re

    from chipbench.trace_reduce import short_name

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "chipbench/layer_metrics/short_conv_time_share.json")) as f:
        pattern = re.compile(json.load(f)["args"]["pattern"])
    matched, fused = set(), False
    for ln in lfm2_step(decode).as_text().splitlines():
        if ln.endswith("{") and " -> " in ln:  # a computation's head
            fused = "fused" in ln.split(" ", 1)[0]
        if fused or " = " not in ln:
            continue
        name = short_name(ln.strip().removeprefix("ROOT "))
        if pattern.search(name):
            matched.add(name)
            assert "short_conv" in ln, ln
    want = {"fusion bf16[18,16384,2,2048]"} | (
        {"copy bf16[32,18,2,2048]", "slice bf16[32,1,2048]"} if decode else
        {"copy bf16[64,18,2,2048]", "fusion bf16[512,1,2048]", "fusion bf16[64,2,2048]",
         "reduce-precision_convert_fusion bf16[512,2048]"})
    assert want <= matched, matched


_GRANITE_KV_PAGES = 16384 * 16 * 2 * 8 * 128  # int8 K/V of the one attention layer
_GRANITE_SLOTS = 134  # 32 live + 102 snapshots (lfm2.snapshot_slots(16384, 16, 512))
_GRANITE_STATE = _GRANITE_SLOTS * 9 * (128 * 64 * 128 * 4 + 3 * 8448 * 2)
# What XLA made of the one-step form's state arithmetic until the call
# ``mamba2_step`` took its place (PR 55): the update in place, the read-out.
_GRANITE_STEP_OPS_GONE = ("select_dynamic-update-slice_fusion f32[9,134,8192,128]",
                          "multiply_reduce_fusion f32[32,8192]")


@pytest.fixture(scope="module")
def granite_step(topo):
    """chipbench/configs/granite-4.0-h-small-10l-ep2.json with the options the
    engine resolves on a TPU and its 134 state slots."""
    return _family_step(
        topo, "chipbench/configs/granite-4.0-h-small-10l-ep2.json",
        _GRANITE_KV_PAGES + _GRANITE_STATE, traced={"kv_scale": ((1,), jnp.float32)},
        state_slots=_GRANITE_SLOTS, attn_impl="tpu", decode_kernel="pallas_fused",
        prefill_kernel="pallas")


@pytest.mark.parametrize("decode", [False, True], ids=["unified-512", "decode-32"])
def test_granite_step_compiles_at_the_cells_shapes_without_copying_either_pool(
    granite_step, no_persistent_cache, decode
):
    """chipbench/configs/granite-4.0-h-small-10l-ep2.json: 4.8 GB of weights
    (10 layers, 36 of 72 experts, half the vocabulary), 16384 K/V pages of one
    attention layer and 134 slots of 38.2 MB of scan state and tail, a
    512-token chunk (or 32 decode rows).  The pages and both slot pools are
    updated in place: the step's temporaries stay far under the 5.1 GB of the
    state pool (a copy of it, or of one layer's slots, 0.57 GB, into or out of
    a step would show).  Attention goes through the dense family's Pallas
    kernels, once, and the experts through the grouped matmul.  The decode
    program's nine Mamba-2 layers each move their rows' state in ONE call,
    ``mamba2_step`` under the scope ``mamba2_step`` (ops/mamba2_step.py: the
    pool aliased to its output and left in ``pl.ANY``, so a pool copied into
    or out of the call would show in the temporaries), and neither of the two
    ops XLA made of the state's arithmetic is left; the prompt program holds
    no such call."""
    compiled = granite_step(decode)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= _GRANITE_KV_PAGES + _GRANITE_STATE
    assert mem.temp_size_in_bytes < 0.5e9, mem
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 13.5e9, mem
    text = compiled.as_text()
    calls = _custom_calls(text)
    attn = "fused_decode_attention" if decode else "fused_prefill_attention"
    assert len([ln for ln in calls if attn in ln]) == 1, calls
    # decode: unrolled, two calls a layer; a prompt program: two runs of Mamba-2
    # layers each ONE loop body, and the attention layer's own feed-forward
    assert len([ln for ln in calls if "moe_grouped_matmul" in ln]) == (20 if decode else 6), calls
    step_calls = [ln for ln in calls if "%mamba2_step" in ln]
    assert len(step_calls) == (9 if decode else 0), calls
    assert all("/mamba2_step/" in ln.split("op_name=", 1)[1] for ln in step_calls), step_calls
    assert all(attn in ln or "moe_grouped_matmul" in ln or "%mamba2_step" in ln
               for ln in calls), calls
    if decode:
        assert not _op_names(text) & set(_GRANITE_STEP_OPS_GONE)


def _metric_matches_its_scope_alone(step, decode: bool, metric: str, scope: str,
                                    holds: bool = True, gone=None) -> None:
    """A ``trace_time_share`` metric of a plain-XLA mixer matches XLA's op names
    (the harness keeps an op's name and shape, not its scope).  In the program
    compiled for a described v5e every op the pattern matches lies under
    ``scope``, and the ops its file ``holds`` are matched: another compiler or
    shape fails HERE, not as a metric that reads 0.  A trace does not keep an
    op's program either, so the pattern matches NOTHING in the other program:
    the two shares of a mixer count no op twice.  ``holds`` False: a kernel took
    the names ``gone`` (every name the file holds unless given) off the path,
    and the pattern still matches the rest of the scope."""
    import json
    import os
    import re

    from chipbench.trace_reduce import short_name

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, f"chipbench/layer_metrics/{metric}.json")) as f:
        spec = json.load(f)
    pattern = re.compile(spec["args"]["pattern"])

    def ops(program):
        """(name as a trace has it, HLO line) of every op outside a fusion's body."""
        fused = False
        for ln in program.as_text().splitlines():
            if ln.endswith("{") and " -> " in ln:  # a computation's head
                fused = "fused" in ln.split(" ", 1)[0]
            if not fused and " = " in ln:
                yield short_name(ln.strip().removeprefix("ROOT ")), ln

    matched = set()
    for name, ln in ops(step(decode)):
        if pattern.search(name):
            matched.add(name)
            assert scope in ln, ln
    held = set(spec["holds"])
    lost = set() if holds else held if gone is None else set(gone)
    assert matched and held - lost <= matched and not lost & matched, matched
    assert not [ln for name, ln in ops(step(not decode)) if pattern.search(name)]


@pytest.mark.parametrize("decode,metric", [(False, "ssm_scan_time_share"),
                                           (True, "ssm_step_time_share")],
                         ids=["unified-512", "decode-32"])
def test_granite_ssm_metrics_match_the_scopes_ops_and_no_others(
    granite_step, no_persistent_cache, decode, metric
):
    """``ssm_scan_time_share`` / ``ssm_step_time_share`` (standing by) against
    the scopes ``mamba2_scan`` / ``mamba2_step``.  Since PR 55 the step's state
    arithmetic is the call ``mamba2_step``: the two XLA names the step's file
    holds are gone from the decode program, and its pattern matches the taps'
    and the tail's ops alone there (PERF.md section 7: the next ``benchmark``
    issue re-points it at the call's name)."""
    _metric_matches_its_scope_alone(
        granite_step, decode, metric, "mamba2_step" if decode else "mamba2_scan",
        holds=not decode, gone=_GRANITE_STEP_OPS_GONE)


_KIMI_LINEAR_SLOTS = 32 + 32768 * 16 // (5 * 512)  # max_batch live + lfm2.snapshot_slots
_KIMI_LINEAR_PAGES = 32768 * 16 * 640 * 2 * 2  # latent pages of the two MLA layers
_KIMI_LINEAR_STATE = _KIMI_LINEAR_SLOTS * 6 * (32 * 128 * 128 * 4 + 3 * 12288 * 2)


@pytest.fixture(scope="module")
def kimi_linear_step(topo):
    """chipbench/configs/kimi-linear-48b-a3b-8l-ep8.json with its 236 state slots."""
    return _family_step(
        topo, "chipbench/configs/kimi-linear-48b-a3b-8l-ep8.json",
        _KIMI_LINEAR_PAGES + _KIMI_LINEAR_STATE, state_slots=_KIMI_LINEAR_SLOTS)


@pytest.mark.parametrize("decode", [False, True], ids=["unified-512", "decode-32"])
def test_kimi_linear_step_compiles_at_the_cells_shapes_without_copying_a_pool(
    kimi_linear_step, no_persistent_cache, decode
):
    """chipbench/configs/kimi-linear-48b-a3b-8l-ep8.json: 2.8 GB of weights (8
    layers, 32 of 256 experts, the whole vocabulary), 32768 latent pages of two
    MLA layers (1.34 GB) and 236 slots of 13 MB of KDA state and tails (3.07
    GB), a 512-token chunk (or 32 decode rows): 7.2 GB of arguments, the 7.3 GB
    ISSUE 53 reckons.  The pages and both slot pools are updated in place: the
    step's temporaries stay far under the 0.51 GB of ONE layer's slots (a copy
    of a pool, or of a layer of it, into or out of a step would show).  The MLA
    layers attend in the latent family's two Pallas calls (32 heads) and the
    experts go through the grouped matmul.  The decode program's six KDA
    layers each move their rows' state in ONE call, ``kda_step`` under the scope
    ``kda_step`` (ops/kda_step.py: the pool aliased to its output, so a pool
    copied into or out of the call would show in the temporaries), and none of
    the four ops XLA made of the state's arithmetic is left; the prompt
    programs hold no such call."""
    compiled = kimi_linear_step(decode)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= _KIMI_LINEAR_PAGES + _KIMI_LINEAR_STATE
    assert mem.temp_size_in_bytes < 0.2e9, mem
    assert 7.1e9 < mem.argument_size_in_bytes < 7.4e9, mem
    text = compiled.as_text()
    calls = _custom_calls(text)
    count = lambda name: len([ln for ln in calls if name in ln])  # noqa: E731
    # decode: unrolled, two calls an expert layer; a prompt program: the two runs
    # of KDA layers with experts each ONE loop body, and the MLA layers' own
    assert count("moe_grouped_matmul") == (14 if decode else 8), calls
    assert count("mla_dense_decode_attention") == 2  # riding rows too, in a prompt program
    assert count("mla_dense_prefill_attention") == (0 if decode else 2)
    kda_calls = [ln for ln in calls if "%kda_step" in ln]
    assert len(kda_calls) == (6 if decode else 0), calls
    assert all("/kda_step/" in ln.split("op_name=", 1)[1] for ln in kda_calls), kda_calls
    assert all(any(n in ln for n in ("moe_grouped_matmul", "mla_dense_", "%kda_step"))
               for ln in calls), calls
    if decode:
        assert not _op_names(text) & {
            "select_dynamic-update-slice_fusion f32[6,236,4096,128]", "slice f32[1,32,4096,128]",
            "multiply_reduce_fusion f32[32,32,128]", "broadcast f32[32,32,128,128]"}


@pytest.mark.parametrize("decode,metric", [(False, "kda_scan_time_share"),
                                           (True, "kda_step_time_share")],
                         ids=["unified-512", "decode-32"])
def test_kimi_linear_kda_metrics_match_the_scopes_ops_and_no_others(
    kimi_linear_step, no_persistent_cache, decode, metric
):
    """``kda_scan_time_share`` / ``kda_step_time_share`` (standing by) against
    the scopes ``kda_scan`` / ``kda_step``.  Since PR 54 the step's state
    arithmetic is the call ``kda_step``: the four XLA names the step's file
    holds are gone from the decode program, and its pattern matches the tail's
    two ``slice_bitcast_fusion`` alone there (PERF.md section 7 (bd): the next
    ``benchmark`` issue re-points it at the call's name)."""
    _metric_matches_its_scope_alone(
        kimi_linear_step, decode, metric, "kda_step" if decode else "kda_scan", holds=not decode)


_JAMBA_SLOTS = 32 + 32768 * 16 // (5 * 512)  # max_batch live + lfm2.snapshot_slots
_JAMBA_PAGES = 32768 * 16 * 2 * 128 * 2 * 2  # bfloat16 K/V of ONE head in the two attention layers
_JAMBA_STATE = _JAMBA_SLOTS * 26 * (16 * 5120 * 4 + 3 * 5120 * 2)


# What XLA made of the prompt-side token loop (the names
# ``mamba1_scan_time_share``'s file holds), off the path since the call
# ``mamba1_scan`` took their place (PR 57): a block's decays, its inputs, a
# token's update with its read-out.
_JAMBA_SCAN_OPS_GONE = {"multiply_exponential_fusion f32[8,8,16,5120]",
                        "broadcast_multiply_fusion f32[8,8,16,5120]",
                        "multiply_reduce_fusion f32[5120]"}
# The float ops left under the scope ``mamba1_scan`` beside the call: B and C
# side by side and along the lanes, A, a row's state out of its slot and into
# its live slot and its snapshot's.
_JAMBA_SCAN_SCOPE = {"mamba1_scan f32[16,5120]", "pad_maximum_fusion f32[512,32]",
                     "broadcast_in_dim f32[512,32,128]", "negate_bitcast_fusion f32[16,5120]",
                     "bitcast_select_fusion f32[16,5120]", "select_bitcast_fusion f32[16,5120]",
                     "bitcast_dynamic-update-slice_fusion f32[26,236,16,5120]"}


@pytest.fixture(scope="module")
def jamba_step(topo):
    """chipbench/configs/jamba2-3b.json whole (bfloat16 weights: the
    configuration serves no ``weight_quant``) with the options the engine
    resolves on a TPU and its 236 state slots."""
    return _family_step(
        topo, "chipbench/configs/jamba2-3b.json", _JAMBA_PAGES + _JAMBA_STATE,
        state_slots=_JAMBA_SLOTS, attn_impl="tpu", decode_kernel="pallas_fused",
        prefill_kernel="pallas")


@pytest.mark.parametrize("decode", [False, True], ids=["unified-512", "decode-32"])
def test_jamba_step_compiles_at_the_cells_shapes_without_copying_a_pool(
    jamba_step, no_persistent_cache, decode
):
    """chipbench/configs/jamba2-3b.json: 6.06 GB of bfloat16 weights (28 of 28
    layers, the whole vocabulary), 32768 K/V pages of ONE head in two attention
    layers (0.54 GB) and 236 slots of 9.3 MB of Mamba-1 state and tails (2.2
    GB), a 512-token chunk (or 32 decode rows): 8.8 GB of arguments, the 8.79
    GB ISSUE 56 reckons.  The pages and both slot pools are updated in place:
    the step's temporaries stay far under the 77 MB of ONE layer's state slots
    (a copy of a pool, or of a layer of it, into or out of a step would show).
    Attention goes through the dense family's two Pallas kernels at 20 query
    heads over one K/V head, once a layer.  The prompt program's recurrence is
    the call ``mamba1_scan`` under the scope ``mamba1_scan`` (ops/
    mamba1_scan.py), once a run of Mamba layers (7, 13 and 6 of them, each run
    ONE loop body), and none of the three ops XLA made of the token loop is
    left; the decode program holds no such call, and neither holds another
    custom call: no experts, the one-step form plain XLA."""
    compiled = jamba_step(decode)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= _JAMBA_PAGES + _JAMBA_STATE
    assert mem.temp_size_in_bytes < 0.06e9, mem
    assert 8.7e9 < mem.argument_size_in_bytes < 8.9e9, mem
    text = compiled.as_text()
    calls = _custom_calls(text)
    attn = "fused_decode_attention" if decode else "fused_prefill_attention"
    assert len([ln for ln in calls if attn in ln]) == 2, calls
    scans = [ln for ln in calls if "%mamba1_scan" in ln]
    assert len(scans) == (0 if decode else 3) and len(calls) == 2 + len(scans), calls
    assert all("/mamba1_scan/" in ln.split("op_name=", 1)[1] for ln in scans), scans
    scope = "/mamba1_step/" if decode else "/mamba1_scan/"
    assert scope in text and ("/mamba1_scan/" if decode else "/mamba1_step/") not in text
    assert "/mamba1_taps/" in text
    assert not _op_names(text) & _JAMBA_SCAN_OPS_GONE


def test_jamba_mamba1_scan_scope_is_the_call_and_the_rows_bookkeeping(jamba_step, no_persistent_cache):
    """Under the scope ``mamba1_scan`` the 512-token program computes in
    float32 the call, its operands' layout and a row's slot reads and writes,
    and nothing else: no decay and no input of a block of tokens is written
    out ([tokens, 16, 5120] appears nowhere)."""
    import re

    text = jamba_step(False).as_text()
    moved = {n for n in _op_names(text, "/mamba1_scan/")
             if " f32[" in n and n.split(" ")[0] not in ("get-tuple-element", "while")}
    assert moved == _JAMBA_SCAN_SCOPE, moved ^ _JAMBA_SCAN_SCOPE
    assert not [n for n in _op_names(text)  # but the 26 layers' pool and ``A_log``
                if re.search(r"f32\[\d+,(\d+,)?16,5120\]", n) and " f32[26," not in n]


@pytest.mark.parametrize("tokens,block", [(512, 256), (16, 16)], ids=["of-512-tokens", "of-16-tokens"])
def test_mamba1_scan_call_compiles_at_both_prompt_programs_shapes(sds, no_persistent_cache, tokens, block):
    """ops/mamba1_scan.py alone at the cell's state [16, 5120] for a described
    v5e: a block of the 512-token program's and the 16-token program's one
    (what Mosaic refuses, an unaligned window or too much VMEM, raises here)."""
    from dynamo_tpu.models import mamba1
    from dynamo_tpu.ops import mamba1_scan as ks

    assert block == min(mamba1.SCAN_CHUNK, tokens)
    f32 = lambda *shape: sds(shape, jnp.float32)  # noqa: E731
    compiled = jax.jit(functools.partial(ks._call, block=block, tile=ks.TILE, interpret=False)).lower(
        f32(16, 5120), f32(16, 5120), f32(tokens, 5120), f32(tokens, 5120), f32(tokens, 32, 128),
        sds((), jnp.int32), sds((), jnp.int32)).compile()
    assert len([ln for ln in _custom_calls(compiled.as_text()) if "%mamba1_scan" in ln]) == 1


@pytest.mark.parametrize("decode,metric", [(False, "mamba1_scan_time_share"),
                                           (True, "mamba1_step_time_share")],
                         ids=["unified-512", "decode-32"])
def test_jamba_mamba1_metrics_match_the_scopes_ops_and_no_others(
    jamba_step, no_persistent_cache, decode, metric
):
    """``mamba1_scan_time_share`` / ``mamba1_step_time_share`` (standing by)
    against the scopes ``mamba1_scan`` / ``mamba1_step``: the recurrence's ops
    alone (the taps and the tail lie under ``mamba1_taps``, which neither
    pattern may match).  Since PR 57 the prompt side's arithmetic is the call
    ``mamba1_scan``: the three XLA names its file holds are gone from the
    512-token program, and its pattern matches a row's slot reads and writes
    alone there (PERF.md section 7: the next ``benchmark`` issue re-points it
    at the call's name)."""
    _metric_matches_its_scope_alone(
        jamba_step, decode, metric, "/mamba1_step/" if decode else "/mamba1_scan/",
        holds=decode, gone=_JAMBA_SCAN_OPS_GONE)


@pytest.mark.parametrize("decode", [False, True], ids=["unified-512", "decode-16"])
def test_deepseek_v32_step_compiles_at_the_cells_shapes_without_copying_pages(
    dsv32_step, no_persistent_cache, decode
):
    """chipbench/configs/deepseek-v3.2-exp-6l-ep16.json whole: 5.7 GB of int8
    weights, 32768 latent and indexer pages, a 512-token chunk (or 16 decode
    rows) against 576 pages a row.  The step's temporaries must stay far
    under the page arrays' 4.8 GB: with a latent width of 576 the compiler
    chose a page-minor layout and copied the whole array into and out of
    every step (4.1 GB of temporaries; PR 28's rehearsal); 640 lanes
    (``latent_width``) cured it."""
    mem = dsv32_step(decode).memory_analysis()
    assert mem.alias_size_in_bytes >= _DSV32_PAGES  # the step updates the pages in place
    assert mem.temp_size_in_bytes < 2.5e9, mem
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 14e9, mem


def test_deepseek_v32_decode_program_attends_in_the_kernel_and_gathers_nothing(
    dsv32_step, no_persistent_cache
):
    """The decode program's one-query attention is the Pallas kernel, once a
    layer, reading the latent pages where they lie: no sort (``lax.top_k``),
    no gathered copy of the kept entries (``bf16[32768,640]``: 16 rows x
    2048) nor their slots (``s32[32768]``), and no temporary the size of a
    layer's latent pages (the operand of a custom call in another layout
    would be copied whole: the 576-lane lesson of PR 28)."""
    compiled = dsv32_step(True)
    text = compiled.as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln and " custom-call(" in ln]
    assert len([ln for ln in calls if "mla_sparse_decode_attention" in ln]) == 6, calls
    # (The router's top_k still sorts [16, 256] and smaller.)
    assert not [ln for ln in text.splitlines() if " sort(" in ln and "9216" in ln.split(" sort(")[0]]
    assert "[32768,640]" not in text and "s32[32768]" not in text
    layer_latent = 32768 * 16 * 640 * 2
    assert compiled.memory_analysis().temp_size_in_bytes < layer_latent, compiled.memory_analysis()


_EXAONE_KV = 2 * 32768 * 16 * 2 * 8 * 128  # int8 K/V of the two full-attention layers
_EXAONE_WINDOW = 6 * 8192 * 16 * 2 * 8 * 128  # ... and the six window layers' pool


@pytest.fixture(scope="module")
def exaone_step(topo):
    """chipbench/configs/k-exaone-236b-a23b-8l-ep8.json with the options the
    engine resolves on a TPU, its window pool of 8192 pages and a window
    table of 41 pages a row (models/family.py ``window_pool``)."""
    return _family_step(
        topo, "chipbench/configs/k-exaone-236b-a23b-8l-ep8.json", _EXAONE_KV + _EXAONE_WINDOW,
        traced={"kv_scale": ((8,), jnp.float32)}, window=(8192, 41), attn_impl="tpu",
        decode_kernel="pallas_fused", prefill_kernel="pallas")


@pytest.mark.parametrize("decode", [False, True], ids=["unified-512", "decode-16"])
def test_k_exaone_step_compiles_at_the_cells_shapes_and_names_its_window_calls(
    exaone_step, no_persistent_cache, decode
):
    """chipbench/configs/k-exaone-236b-a23b-8l-ep8.json: 6 GB of weights (8
    layers, 16 of 128 experts, an eighth of the vocabulary), 32768 K/V pages
    of two full layers and 8192 window pages of six, a 512-token chunk (or 16
    decode rows).  Both pools are updated in place (no copy of either into or
    out of the step).  The full layers attend in ``fused_*_attention``, the
    window layers in ``window_*_attention``: names of their own in the device
    trace, so that ``attn_decode_time_share`` / ``attn_prefill_time_share``
    (``^fused_...``) read the full layers' calls and count no window call, and
    the ``swa_window_*`` patterns read the window calls alone."""
    import json
    import os
    import re

    compiled = exaone_step(decode)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= _EXAONE_KV + _EXAONE_WINDOW
    assert mem.temp_size_in_bytes < 1.0e9, mem
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 13.5e9, mem
    calls = _custom_calls(compiled.as_text())
    phase = "decode" if decode else "prefill"
    full = [ln for ln in calls if f"fused_{phase}_attention" in ln]
    win = [ln for ln in calls if f"window_{phase}_attention" in ln]
    assert (len(full), len(win)) == (2, 6), calls
    assert len([ln for ln in calls if "moe_grouped_matmul" in ln]) == 14, calls
    assert len(full) + len(win) + 14 == len(calls), calls
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def pattern(metric):
        with open(os.path.join(root, f"chipbench/layer_metrics/{metric}.json")) as f:
            return re.compile(json.load(f)["args"]["pattern"])

    names = _op_names(compiled.as_text())
    old, new = pattern(f"attn_{phase}_time_share"), pattern(f"swa_window_{phase}_time_share")
    matched_old = {n for n in names if old.search(n)}
    matched_new = {n for n in names if new.search(n)}
    assert matched_old and matched_new and not matched_old & matched_new
    assert all(n.startswith(f"fused_{phase}_attention") for n in matched_old), matched_old
    assert all(n.startswith(f"window_{phase}_attention") for n in matched_new), matched_new
    other = "prefill" if decode else "decode"
    for metric in (f"attn_{other}_time_share", f"swa_window_{other}_time_share"):
        assert not {n for n in names if pattern(metric).search(n)}


def _custom_calls(text: str) -> list:
    return [ln for ln in text.splitlines() if "tpu_custom_call" in ln and " custom-call(" in ln]


def _op_names(text: str, scope: str = "") -> set:
    """The short names (``chipbench.trace_reduce.short_name``: what the
    harness prints in ``breakdown.device_ops``) of a compiled program's ops
    outside its fused computations; under ``scope`` alone where given."""
    from chipbench.trace_reduce import short_name

    names, fused = set(), False
    for ln in text.splitlines():
        if ln.endswith("{") and " -> " in ln:  # a computation's head
            fused = "fused" in ln.split(" ", 1)[0]
        if not fused and " = " in ln and scope in ln:
            names.add(short_name(ln.strip().removeprefix("ROOT ")))
    return names


@pytest.fixture(scope="module")
def dsv32_small_step(topo):
    """The same configuration's 64-token prompt program: under
    ``ops/sparse_mla.py::PREFILL_KERNEL_TOKENS``."""
    return _family_step(topo, "chipbench/configs/deepseek-v3.2-exp-6l-ep16.json", _DSV32_PAGES,
                        prompt_tokens=64)


def test_deepseek_v32_prompt_programs_attend_in_the_form_their_token_count_pays_for(
    dsv32_step, dsv32_small_step, no_persistent_cache
):
    """The cell's 512-token program holds the Pallas call
    ``mla_sparse_prefill_attention`` exactly twice (the unrolled dense layer
    and the scan's body), under the name the harness prints in
    ``breakdown.device_ops``, and nothing of the absorbed loop's attention
    stage: no float32 state ``f32[64,128,512]`` / ``f32[64,128]`` carried
    through HBM, no score block ``f32[64,128,1024]``, and no temporary the
    size of a layer's latent pages (an operand of the call in another layout
    would be copied whole).  The selector's stages are still XLA's, under the
    names ``dsa_select_time_share`` lists.  The 64-token program holds no
    such call and the loop's stages under the names
    ``mla_sparse_attn_time_share`` lists: the metric still reads the small
    programs."""
    from dynamo_tpu.ops import sparse_mla

    assert 64 < sparse_mla.PREFILL_KERNEL_TOKENS <= 512
    big = dsv32_step(False)
    text = big.as_text()
    calls = [ln for ln in _custom_calls(text) if "mla_sparse_prefill_attention" in ln]
    assert len(calls) == 2, calls
    names = _op_names(text)
    assert {n for n in names if n.startswith("mla_sparse_prefill_attention")} == {
        "mla_sparse_prefill_attention bf16[512,16384]"}
    for gone in ("fusion f32[64,128]", "fusion f32[64,128,512]", "broadcast f32[64,128,512]",
                 "constant_dynamic-slice_fusion bf16[64,128,640]"):
        assert gone not in names, gone
    assert "f32[64,128,512]" not in text and "f32[64,128,1024]" not in text
    assert {"fusion f32[64,1024]", "reduce-window s32[64,72,128]"} <= names
    layer_latent = 32768 * 16 * 640 * 2
    assert big.memory_analysis().temp_size_in_bytes < layer_latent, big.memory_analysis()

    small = dsv32_small_step(False).as_text()
    assert not [ln for ln in _custom_calls(small) if "mla_sparse_prefill_attention" in ln]
    assert {"fusion f32[64,128]", "fusion f32[64,128,512]"} <= _op_names(small)


@pytest.mark.parametrize("decode", [False, True], ids=["unified-512", "decode-16"])
def test_kimi_k2_step_compiles_at_the_cells_shapes_without_copying_pages(
    kimi_step, no_persistent_cache, decode
):
    """chipbench/configs/kimi-k2-6l-ep32.json whole: 4.24 GB of weights, 32768
    latent pages and no others, a 512-token chunk (or 16 decode rows) against
    832 pages a row.  The step updates the pages in place and its temporaries
    stay under ONE layer's pages (a copy of the page array into or out of a
    step, or a layout change before the kernel, would be at least that)."""
    compiled = kimi_step(decode)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= _KIMI_PAGES
    assert mem.temp_size_in_bytes < _KIMI_PAGES // 6, mem
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 10e9, mem
    # The one-query kernel is in both programs (decode rows ride a prompt
    # step), once for the unrolled dense layer and once in the scanned layers;
    # the prompt program has the prompt-chunk kernel beside it; the only
    # other kernel is the grouped expert matmul.
    calls = _custom_calls(compiled.as_text())
    assert [ln for ln in calls if "mla_dense_decode_attention" in ln], calls
    allowed = ("mla_dense_decode_attention", "moe_grouped_matmul") + (
        () if decode else ("mla_dense_prefill_attention",))
    assert all(any(name in ln for name in allowed) for ln in calls), calls


@pytest.mark.parametrize("decode", [False, True], ids=["unified-512", "decode-16"])
def test_kimi_k2_programs_sort_and_gather_nothing_of_the_context(
    kimi_step, no_persistent_cache, decode
):
    """Without a selector nothing is scored, sorted or selected: no sort over
    a row's 13312 positions (the router's top_k sorts [T, 384] and the
    dispatch tables a few thousand pairs), no gather of ``num_blocks x 16``
    slots nor of a row's whole context (16 rows x 832 pages), and no mask
    operand of a row's positions."""
    text = kimi_step(decode).as_text()
    for ln in text.splitlines():
        if " sort(" in ln:
            assert "13312" not in ln.split(" sort(")[0] and "524288" not in ln, ln
    for shape in ("[524288,640]", "s32[524288]", "[16,832,16,640]", "[13312,16,640]",
                  "[16,13312]", "[512,13312]"):
        assert shape not in text, shape



@pytest.mark.parametrize("model,E", [("kimi", 12), ("dsv32", 16)])
def test_latent_decode_programs_read_the_held_experts_through_the_grouped_matmul_alone(
    kimi_step, dsv32_step, no_persistent_cache, model, E
):
    """The decode program at the cell's shapes: the routed experts go through
    the Pallas call ``moe_grouped_matmul`` (gate with up, and down: two calls
    an expert layer, five layers), whose operand is the STACKED leaf as the
    program was given it; nothing else touches an expert leaf, whole or a
    layer of it (a dot or a fusion over ``s8[E,7168,2048]`` would read every
    held expert; a slice of a layer before the call would copy them), and no
    ``conditional`` is left of the two table sizes."""
    text = (kimi_step if model == "kimi" else dsv32_step)(True).as_text()
    calls = [ln for ln in _custom_calls(text) if "moe_grouped_matmul" in ln]
    assert len(calls) == 10, calls
    leaf = (f"s8[5,{E},7168,2048]", f"s8[5,{E},2048,7168]")
    layer = (f"s8[{E},7168,2048]", f"s8[{E},2048,7168]", f"s8[1,{E},7168,2048]",
             f"s8[1,{E},2048,7168]")
    assert all(any(shape in ln for shape in leaf) for ln in calls), calls
    entry = text[text.index("ENTRY "):]
    for ln in entry.splitlines():
        if " = " not in ln or " parameter(" in ln or "moe_grouped_matmul" in ln:
            continue
        assert not any(shape in ln for shape in leaf + layer), ln
    assert not any(shape in text for shape in layer)
    assert " conditional(" not in text


@pytest.mark.parametrize("model", ["kimi", "dsv32"])
def test_latent_prompt_programs_group_the_experts_rows_in_the_kernel_too(
    kimi_step, dsv32_step, no_persistent_cache, model
):
    """The 512-token program: the same two calls, in the loop over chunks of
    row tiles inside the scan over layers, on the stacked leaves (passed
    through the loops, never sliced to a layer)."""
    text = (kimi_step if model == "kimi" else dsv32_step)(False).as_text()
    calls = [ln for ln in _custom_calls(text) if "moe_grouped_matmul" in ln]
    assert len(calls) == 2 and all("s8[5," in ln for ln in calls), calls
    E = 12 if model == "kimi" else 16
    assert not any(shape in text for shape in (f"s8[{E},7168,2048]", f"s8[{E},2048,7168]"))


@pytest.mark.parametrize("quant", [True, False], ids=["int8", "bfloat16"])
def test_sharded_experts_stay_where_they_are_under_the_grouped_matmul(
    topo, no_persistent_cache, monkeypatch, quant
):
    """The llama family's ``moe_mlp`` on the described 2x2 v5e, its leaves
    placed as parallel/mesh.py places them (E over ``ep``, the intermediate
    width over ``tp``): each chip's ``moe_grouped_matmul`` gets ITS shard of
    the leaves, no collective moves an expert leaf (a Pallas call left to
    GSPMD would have had them all gathered to every chip), and what crosses
    chips is the tokens' partial sums (and, int8, a row scale)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from dynamo_tpu.models import moe
    from dynamo_tpu.models.config import get_config
    from dynamo_tpu.parallel import param_pspecs

    monkeypatch.setenv("DYN_PALLAS_INTERPRET", "0")
    E, K, D, F, T = 8, 2, 1024, 2048, 64
    cfg = get_config("debug-tiny-moe").with_overrides(
        hidden_size=D, intermediate_size=F, num_experts=E, num_experts_per_token=K)
    mesh = Mesh(np.array(topo.devices).reshape(1, 2, 1, 2), ("dp", "ep", "sp", "tp"))
    specs = param_pspecs(cfg)["layers"]
    wdt = jnp.int8 if quant else jnp.bfloat16

    def leaf(name, shape, dtype):  # one layer's: the specs' leading L stripped
        return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(mesh, P(*specs[name][1:])))

    lp = {"router": leaf("router", (D, E), jnp.bfloat16),
          "moe_gate": leaf("moe_gate", (E, D, F), wdt), "moe_up": leaf("moe_up", (E, D, F), wdt),
          "moe_down": leaf("moe_down", (E, F, D), wdt)}
    if quant:
        lp.update({"moe_gate_scale": leaf("moe_gate_scale", (E, F), jnp.float32),
                   "moe_up_scale": leaf("moe_up_scale", (E, F), jnp.float32),
                   "moe_down_scale": leaf("moe_down_scale", (E, D), jnp.float32)})
    x = jax.ShapeDtypeStruct((1, T, D), jnp.bfloat16, sharding=NamedSharding(mesh, P()))
    text = jax.jit(lambda x, lp: moe.moe_mlp(x, lp, cfg, mesh)).lower(x, lp).compile().as_text()
    calls = [ln for ln in _custom_calls(text) if "moe_grouped_matmul" in ln]
    t = "s8" if quant else "bf16"
    assert calls and all(f"{t}[1,{E // 2},{D},{F // 2}]" in ln or f"{t}[1,{E // 2},{F // 2},{D}]" in ln
                         for ln in calls), calls
    moved = [ln for ln in text.splitlines()
             if any(op in ln for op in (" all-gather(", " all-to-all(", " collective-permute("))
             and f"{t}[" in ln and (f",{D}," in ln or f",{F // 2}," in ln or f",{F}," in ln)]
    assert not moved, moved
    assert f"{t}[{E},{D},{F}]" not in text and f"{t}[1,{E},{D},{F}]" not in text
    assert " all-reduce(" in text


def test_kimi_k2_prompt_program_attends_in_the_prefill_kernel_and_keeps_no_state_in_hbm(
    kimi_step, no_persistent_cache
):
    """The 512-token program compiled for a described v5e holds the Pallas
    call ``mla_dense_prefill_attention`` twice (the unrolled dense layer and
    the scanned layers), under the name the harness prints in
    ``breakdown.device_ops``; the stages of the XLA loop it replaced are in no
    program (the float32 state written back after every block, a key block
    decompressed by XLA and its layout copy); and the pattern of
    ``mla_dense_prefill_kernel_time_share`` (the benchmark's file, PR 40)
    matches that call's name and no other op of the program, so the metric
    times the kernel and nothing else.  (``mla_dense_prefill_attn_time_share``
    lists XLA's names for the deleted loop and waits for a ``benchmark`` issue
    to retire it: PERF.md section 7.)"""
    import json
    import os
    import re

    from chipbench.trace_reduce import short_name

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "chipbench/layer_metrics/mla_dense_prefill_kernel_time_share.json")) as f:
        pattern = re.compile(json.load(f)["args"]["pattern"])
    text = kimi_step(False).as_text()
    calls = [ln for ln in _custom_calls(text) if "mla_dense_prefill_attention" in ln]
    assert len(calls) == 2, calls
    names = _op_names(text)
    kernel = {n for n in names if n.startswith("mla_dense_prefill_attention")}
    assert kernel == {"mla_dense_prefill_attention bf16[512,8192]"}, kernel
    for gone in ("dynamic_update_slice f32[64,640,128]", "dynamic_update_slice f32[64,640]",
                 "convolution_bitcast_fusion bf16[64,1024,128]", "copy bf16[64,1024,128]",
                 "fusion f32[64,128,1024]", "fusion f32[64,128,128]", "fusion f32[64,128]"):
        assert gone not in names, gone
    assert not any("[64,1024,128]" in n or "[64,640,128]" in n for n in names), names
    assert {n for n in names if pattern.search(n)} == kernel
    assert all(pattern.search(short_name(ln.strip().removeprefix("ROOT "))) for ln in calls), calls


def test_the_dense_prefill_kernel_compiles_for_a_step_of_two_token_blocks(
    sds, no_persistent_cache, monkeypatch
):
    """A step of more than ``PREFILL_STEP_TOKENS`` tokens walks the kernel's
    second grid axis.  The engine builds one whenever ``--prefill-chunk`` plus
    ``--max-batch`` passes 1024 (a step's bucket holds the chunk and the
    riding decode rows); every cell runs chunks of 512, so the axis is held
    here: 2048 tokens at Kimi-K2's widths compile for the described v5e."""
    from dynamo_tpu.ops import dense_mla

    monkeypatch.setenv("DYN_PALLAS_INTERPRET", "0")
    T, S, H, PP = 2 * dense_mla.PREFILL_STEP_TOKENS, 16, 64, 16384 // 16
    bf16, i32 = jnp.bfloat16, jnp.int32
    text = _compile(
        functools.partial(dense_mla.dense_prefill_attention, sm_scale=192 ** -0.5),
        sds((T, H, 192), bf16), sds((32768, 16, 640), bf16),
        sds((H, 512, 128), bf16), sds((H, 512, 128), bf16),
        sds((S,), i32), sds((S, PP), i32), sds((S + 1,), i32), sds((1,), i32))
    assert any("mla_dense_prefill_attention" in ln for ln in _custom_calls(text))


def test_the_device_side_join_compiles_and_neither_copies_counts_nor_aliases(
    sds, no_persistent_cache
):
    """``engine/join.py`` at ``max_batch`` 32: five ``[S]`` operands and two
    ``[S]`` results.  The ``[S, V]`` penalty counts are no operand of it (a row
    with a penalty takes the chain-break merge), so a join moves 4 bytes a
    row; and nothing is donated: the prompt step's tokens are on their way to
    the host, the carry may feed a chunk in flight."""
    from dynamo_tpu.engine.join import join_rows

    S = 32
    ops = [sds((S,), jnp.int32)] * 5
    compiled = jax.jit(join_rows).lower(*ops).compile()
    text = compiled.as_text()
    assert "input_output_alias" not in text, "the join donates an operand"
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes <= 5 * S * 4 * 8, mem  # tiles, not [S, V]
    assert mem.alias_size_in_bytes == 0
    shapes = jax.eval_shape(join_rows, *ops)
    assert [(x.shape, x.dtype) for x in shapes] == [((S,), jnp.int32)] * 2
