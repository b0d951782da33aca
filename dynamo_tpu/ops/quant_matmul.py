"""W8A8-dynamic int8 matmul: the MXU path for quantized weights.

``qdot`` is the single hot op behind weight quantization
(models/quant.py): dynamic symmetric per-row int8 activations x static
per-output-channel int8 weights, int32 accumulation on the MXU, f32
rescale.  XLA fuses the quantize (max/abs/round) into the surrounding
elementwise work and runs the dot on the native int8 systolic path —
measured 1.73x bf16 on decode-geometry chains and 1.87x on prefill
(tools/quant_microbench.py on v5e; near both the int8 HBM roofline and the
int8 MXU peak).

Reference counterpart: vLLM's fp8-dynamic execution of the baseline
checkpoint (per-token dynamic activation scales, per-channel weight
scales) — /root/reference/examples/llm/benchmarks/README.md's
``...-FP8-dynamic`` workload.  v5e's native low-precision MXU format is
int8, so that is the TPU-first mapping.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def quantize_rows(x: jnp.ndarray, across=()) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Dynamic symmetric per-row int8: returns (x_q int8, row_scale f32
    [..., 1]).  Rows of zeros get scale 1e-9 and quantize to zeros.
    ``across``: the mesh axes a row is split over where each shard holds a
    slice of it (models/moe.py under ``tp``); the scale is the whole row's."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    if across:
        amax = jax.lax.pmax(amax, across)
    ax = jnp.maximum(amax / 127.0, 1e-9)
    xq = jnp.clip(jnp.round(xf / ax), -127, 127).astype(jnp.int8)
    return xq, ax


def qdot(x: jnp.ndarray, w_q: jnp.ndarray, scale: jnp.ndarray, out_dtype=None):
    """``x @ dequant(w_q)`` via native int8: x [..., K] float, w_q [K, N]
    int8, scale [N] f32 (per-output-channel).  int32 accumulation is exact
    for K <= ~130k (|acc| <= K * 127^2 < 2^31)."""
    xq, ax = quantize_rows(x)
    acc = jax.lax.dot_general(
        xq, w_q, (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    out = acc.astype(jnp.float32) * ax * scale
    return out.astype(out_dtype or x.dtype)


def qdot_batched(x: jnp.ndarray, w_q: jnp.ndarray, scale: jnp.ndarray, out_dtype=None):
    """Batched variant over experts' tables: x [E, C, K] float, w_q [E, K, N]
    int8, scale [E, N] f32 → [E, C, N] (einsum "eck,ekn->ecn").  The serving
    path groups rows by expert instead (ops/grouped_matmul.py, the same
    contract a row); this is what the tests hold that to."""
    xq, ax = quantize_rows(x)
    acc = jax.lax.dot_general(
        xq, w_q, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.int32,
    )
    out = acc.astype(jnp.float32) * ax * scale[:, None, :]
    return out.astype(out_dtype or x.dtype)
