"""Decide the int8 weight-quant matmul strategy on real hardware.

Compares, at decode geometry (B rows x [D, F] weights, chained like an FFN
stack so HBM prefetch behavior shows up):

  bf16      x(bf16) @ w(bf16)                      — today's baseline
  w8a16     (x @ w_q.astype(bf16)) * s             — weight-only; fast ONLY
            if XLA fuses the int8->bf16 convert into the dot's operand read
            instead of materializing a bf16 copy of the weights
  w8a8dyn   per-row dynamic act quant; int8 x int8 dot -> int32; scale out
            — native MXU int8 path (v5e int8 peak ~2x bf16), the closest
            analog of the reference baseline's FP8-dynamic checkpoint

Prints per-variant ms/iter and device memory. Run on the TPU:
    python tools/quant_microbench.py
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

B = 256  # decode batch rows
D = 4096
F = 14336
LAYERS = 8  # chain length: enough for prefetch behavior to matter


def _run(fn, args, iters):
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    float(jnp.sum(out))  # a device->host fetch ends the timed region
    return time.perf_counter() - t0


def timeit(fn, *args, iters=20, repeats=3):
    out = fn(*args)
    float(jnp.sum(out))  # compile + warm
    best = float("inf")
    for _ in range(repeats):
        # Difference two iteration counts so the constant fetch RTT cancels.
        lo = _run(fn, args, 2)
        hi = _run(fn, args, 2 + iters)
        best = min(best, (hi - lo) / iters * 1e3)
    return best  # ms


def mem_mb():
    try:
        s = jax.devices()[0].memory_stats()
        return s.get("bytes_in_use", 0) / 1e6
    except Exception:
        return 0.0


def main():
    key = jax.random.PRNGKey(0)
    print(f"backend={jax.default_backend()} B={B} D={D} F={F} layers={LAYERS}")
    x = jax.random.normal(key, (B, D), jnp.bfloat16)

    # --- bf16 baseline ----------------------------------------------------
    w_bf = jax.random.normal(key, (LAYERS, D, F), jnp.bfloat16) * 0.02
    w2_bf = jax.random.normal(key, (LAYERS, F, D), jnp.bfloat16) * 0.02

    @jax.jit
    def chain_bf16(x, w, w2):
        for l in range(LAYERS):
            h = x @ w[l]
            x = (h @ w2[l]).astype(jnp.bfloat16)
        return x

    ms = timeit(chain_bf16, x, w_bf, w2_bf)
    # bytes: weights dominate (2 * L * D * F * 2B)
    gb = 2 * LAYERS * D * F * 2 / 1e9
    print(f"bf16   : {ms:8.3f} ms/iter  ({gb/ (ms/1e3):.0f} GB/s wts)  mem={mem_mb():.0f}MB")

    # --- int8 weights -----------------------------------------------------
    s1 = (jnp.max(jnp.abs(w_bf), axis=1) / 127.0).astype(jnp.float32)  # [L, F]
    w_q = jnp.round(w_bf / s1[:, None, :]).astype(jnp.int8)
    s2 = (jnp.max(jnp.abs(w2_bf), axis=1) / 127.0).astype(jnp.float32)  # [L, D]
    w2_q = jnp.round(w2_bf / s2[:, None, :]).astype(jnp.int8)

    @jax.jit
    def chain_w8a16(x, w, s1, w2, s2):
        for l in range(LAYERS):
            h = ((x @ w[l].astype(jnp.bfloat16)).astype(jnp.float32) * s1[l]).astype(
                jnp.bfloat16
            )
            x = ((h @ w2[l].astype(jnp.bfloat16)).astype(jnp.float32) * s2[l]).astype(
                jnp.bfloat16
            )
        return x

    ms = timeit(chain_w8a16, x, w_q, s1, w2_q, s2)
    gb = 2 * LAYERS * D * F * 1 / 1e9
    print(f"w8a16  : {ms:8.3f} ms/iter  ({gb/ (ms/1e3):.0f} GB/s wts)  mem={mem_mb():.0f}MB")

    # --- w8a8 dynamic ------------------------------------------------------
    @jax.jit
    def chain_w8a8(x, w, s1, w2, s2):
        for l in range(LAYERS):
            ax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=1, keepdims=True) / 127.0
            xq = jnp.round(x.astype(jnp.float32) / jnp.maximum(ax, 1e-9)).astype(jnp.int8)
            h32 = jax.lax.dot_general(
                xq, w[l], (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32
            )
            h = (h32.astype(jnp.float32) * ax * s1[l]).astype(jnp.bfloat16)
            ah = jnp.max(jnp.abs(h.astype(jnp.float32)), axis=1, keepdims=True) / 127.0
            hq = jnp.round(h.astype(jnp.float32) / jnp.maximum(ah, 1e-9)).astype(jnp.int8)
            x32 = jax.lax.dot_general(
                hq, w2[l], (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32
            )
            x = (x32.astype(jnp.float32) * ah * s2[l]).astype(jnp.bfloat16)
        return x

    ms = timeit(chain_w8a8, x, w_q, s1, w2_q, s2)
    print(f"w8a8dyn: {ms:8.3f} ms/iter  ({gb/ (ms/1e3):.0f} GB/s wts)  mem={mem_mb():.0f}MB")

    # --- w8a8 static act scale (no serialized max-abs reduction) -----------
    @jax.jit
    def chain_w8a8s(x, w, s1, w2, s2):
        for l in range(LAYERS):
            xq = jnp.round(x.astype(jnp.float32) * 32.0).astype(jnp.int8)
            h32 = jax.lax.dot_general(
                xq, w[l], (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32
            )
            h = (h32.astype(jnp.float32) * (s1[l] / 32.0)).astype(jnp.bfloat16)
            hq = jnp.round(h.astype(jnp.float32) * 32.0).astype(jnp.int8)
            x32 = jax.lax.dot_general(
                hq, w2[l], (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32
            )
            x = (x32.astype(jnp.float32) * (s2[l] / 32.0)).astype(jnp.bfloat16)
        return x

    ms = timeit(chain_w8a8s, x, w_q, s1, w2_q, s2)
    print(f"w8a8sta: {ms:8.3f} ms/iter  ({gb/ (ms/1e3):.0f} GB/s wts)  mem={mem_mb():.0f}MB")

    # --- mixed dot: bf16 activations x int8 weights directly ---------------
    @jax.jit
    def chain_mixed(x, w, s1, w2, s2):
        for l in range(LAYERS):
            h32 = jax.lax.dot_general(
                x, w[l], (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
            )
            h = (h32 * s1[l]).astype(jnp.bfloat16)
            x32 = jax.lax.dot_general(
                h, w2[l], (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
            )
            x = (x32 * s2[l]).astype(jnp.bfloat16)
        return x

    try:
        ms = timeit(chain_mixed, x, w_q, s1, w2_q, s2)
        print(f"mixed  : {ms:8.3f} ms/iter  ({gb/ (ms/1e3):.0f} GB/s wts)  mem={mem_mb():.0f}MB")
    except Exception as e:
        print(f"mixed  : unsupported ({type(e).__name__})")

    # --- prefill geometry (compute-bound): chained big matmuls --------------
    xp = jax.random.normal(key, (2048, D), jnp.bfloat16)

    @jax.jit
    def pchain_bf16(x, w, w2):
        for l in range(LAYERS):
            h = x @ w[l]
            x = (h @ w2[l]).astype(jnp.bfloat16)
        return x

    @jax.jit
    def pchain_w8a8(x, w, s1, w2, s2):
        for l in range(LAYERS):
            ax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=1, keepdims=True) / 127.0
            xq = jnp.round(x.astype(jnp.float32) / jnp.maximum(ax, 1e-9)).astype(jnp.int8)
            h32 = jax.lax.dot_general(
                xq, w[l], (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32
            )
            h = (h32.astype(jnp.float32) * ax * s1[l]).astype(jnp.bfloat16)
            ah = jnp.max(jnp.abs(h.astype(jnp.float32)), axis=1, keepdims=True) / 127.0
            hq = jnp.round(h.astype(jnp.float32) / jnp.maximum(ah, 1e-9)).astype(jnp.int8)
            x32 = jax.lax.dot_general(
                hq, w2[l], (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32
            )
            x = (x32.astype(jnp.float32) * ah * s2[l]).astype(jnp.bfloat16)
        return x

    flops = 2 * 2048 * D * F * 2 * LAYERS
    ms = timeit(pchain_bf16, xp, w_bf, w2_bf, iters=40)
    print(f"prefill bf16   : {ms:7.3f} ms  ({flops/(ms/1e3)/1e12:.0f} TFLOP/s)")
    ms = timeit(pchain_w8a8, xp, w_q, s1, w2_q, s2, iters=40)
    print(f"prefill w8a8dyn: {ms:7.3f} ms  ({flops/(ms/1e3)/1e12:.0f} TFLOP/s)")


if __name__ == "__main__":
    main()
