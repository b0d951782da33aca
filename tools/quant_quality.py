"""Quantization quality evidence on the real-checkpoint stack
(VERDICT r4 next #1 "accuracy tables" + weak #5 "KV drift on
non-degenerate logits").

Builds the golden HF-format checkpoint (the same builder the golden-token
serving tests use — tests/test_real_checkpoint.py), then measures, prompt
by prompt, last-token distributions against the bf16 forward of the SAME
weights:

  weight-int8      W8A8-dynamic execution of per-channel int8 weights
                   (models/quant.py) vs the f32 dequantized reference
  kv-int8 / kv-fp8 bf16 weights with quantized KV pages (per-layer
                   auto-calibrated scales) vs the bf16-KV forward

Reported per config: mean KL divergence, top-1 agreement overall, and
top-1 agreement on DECISIVE positions (reference top-2 margin > 3x the
observed max logit error — random-init logits are near-ties, so raw
agreement under-reports; decisive agreement is the honest gate).

Writes benchmarks/results/r5_quant_quality.json (a CPU product: quality
numbers, no timings).  Run on CPU:
    JAX_PLATFORMS=cpu python tools/quant_quality.py
"""

from __future__ import annotations

import json
import os
import sys
import tempfile

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tests"))

N_PROMPTS = 16
PROMPT_LEN = 24


def _forward(params, cfg, prompt, cache_dtype, kv_scale):
    import jax.numpy as jnp

    from dynamo_tpu.models.llama import PagedKVCache, RaggedBatch, forward_ragged

    T = len(prompt)
    bs = 4
    nb = (T + bs - 1) // bs + 1
    cache = PagedKVCache.create(cfg, nb, bs, dtype=jnp.dtype(cache_dtype))
    rb = RaggedBatch(
        token_ids=jnp.asarray(prompt, jnp.int32),
        positions=jnp.arange(T, dtype=jnp.int32),
        slot_mapping=jnp.arange(T, dtype=jnp.int32),
        kv_lens=jnp.asarray([T], jnp.int32),
        page_indices=jnp.arange(nb, dtype=jnp.int32)[None],
        cu_q_lens=jnp.asarray([0, T], jnp.int32),
        num_seqs=jnp.asarray([1], jnp.int32),
    )
    logits, _ = forward_ragged(
        params, cfg, rb, cache, attn_impl="xla", kv_scale=kv_scale
    )
    return np.asarray(logits[0], np.float32)


def _calibrate(params, cfg, probe_prompt, dtype_name):
    """Per-layer KV scales from a bf16 probe (engine._calibrate_kv_scales
    logic at module level)."""
    import jax.numpy as jnp

    from dynamo_tpu.models.llama import PagedKVCache, RaggedBatch, forward_ragged

    T = len(probe_prompt)
    bs = 4
    nb = (T + bs - 1) // bs + 1
    cache = PagedKVCache.create(cfg, nb, bs, dtype=jnp.float32)
    rb = RaggedBatch(
        token_ids=jnp.asarray(probe_prompt, jnp.int32),
        positions=jnp.arange(T, dtype=jnp.int32),
        slot_mapping=jnp.arange(T, dtype=jnp.int32),
        kv_lens=jnp.asarray([T], jnp.int32),
        page_indices=jnp.arange(nb, dtype=jnp.int32)[None],
        cu_q_lens=jnp.asarray([0, T], jnp.int32),
        num_seqs=jnp.asarray([1], jnp.int32),
    )
    _, probe = forward_ragged(params, cfg, rb, cache, attn_impl="xla")
    maxabs = np.asarray(
        jnp.max(jnp.abs(probe.pages.astype(jnp.float32)), axis=(1, 2, 3, 4))
    )
    if dtype_name == "int8":
        qmax = 127.0
    else:
        import jax.numpy as jnp

        qmax = float(jnp.finfo(jnp.float8_e4m3fn).max)  # 448
    return np.maximum(maxabs / qmax, 1e-6).astype(np.float32)


def _stats(ref_logits, got_logits):
    kls, agree, decisive, agree_all = [], 0, 0, 0
    for lr, lq in zip(ref_logits, got_logits):
        pr = np.exp(lr - lr.max()); pr /= pr.sum()
        pq = np.exp(lq - lq.max()); pq /= pq.sum()
        kls.append(float(np.sum(pr * (np.log(pr + 1e-12) - np.log(pq + 1e-12)))))
        agree_all += int(np.argmax(lq) == np.argmax(lr))
        err = np.max(np.abs(lq - lr))
        top2 = np.partition(lr, -2)[-2:]
        if top2[1] - top2[0] > 3 * err:
            decisive += 1
            agree += int(np.argmax(lq) == np.argmax(lr))
    n = len(ref_logits)
    return {
        "mean_kl": round(float(np.mean(kls)), 6),
        "top1_agree": f"{agree_all}/{n}",
        "decisive": decisive,
        "decisive_agree": f"{agree}/{decisive}" if decisive else "0/0",
    }


def main() -> None:
    from test_real_checkpoint import build_checkpoint

    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.models.loader import load_params
    from dynamo_tpu.models.quant import dequantize_params, quantize_params

    out_path = os.path.join(REPO, "benchmarks", "results", "r5_quant_quality.json")
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "model")
        build_checkpoint(path)
        cfg = ModelConfig.from_local_path(path).with_overrides(
            name="golden-tiny", dtype="float32"
        )
        params = load_params(cfg, path)
        qp = quantize_params(load_params(cfg, path))
        deq = dequantize_params(qp)

        rng = np.random.default_rng(17)
        prompts = [
            rng.integers(3, cfg.vocab_size, size=PROMPT_LEN).tolist()
            for _ in range(N_PROMPTS)
        ]
        kv_scales = {
            name: _calibrate(params, cfg, prompts[0], name)
            for name in ("int8", "float8_e4m3fn")
        }

        ref_deq = [_forward(deq, cfg, p, "float32", None) for p in prompts]
        ref_bf16kv = [_forward(params, cfg, p, "float32", None) for p in prompts]

        rows = []
        got = [_forward(qp, cfg, p, "float32", None) for p in prompts]
        rows.append({"config": "weights int8 (W8A8-dynamic) vs dequantized ref",
                     **_stats(ref_deq, got)})
        for name, label in (("int8", "kv int8 + per-layer auto scales"),
                            ("float8_e4m3fn", "kv fp8-e4m3 + per-layer auto scales")):
            got = [
                _forward(params, cfg, p, name, kv_scales[name]) for p in prompts
            ]
            rows.append({"config": f"{label} vs bf16-KV ref", **_stats(ref_bf16kv, got)})
        got = [_forward(qp, cfg, p, "int8", kv_scales["int8"]) for p in prompts]
        rows.append({"config": "weights int8 + kv int8 (full serving config)",
                     **_stats(ref_deq, got)})

    doc = {
        "n_prompts": N_PROMPTS,
        "prompt_len": PROMPT_LEN,
        "checkpoint": "golden-tiny (tests/test_real_checkpoint.py builder)",
        "rows": rows,
    }
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=1)
    print(json.dumps(doc, indent=1))
    print(f"wrote {out_path}", file=sys.stderr)


if __name__ == "__main__":
    main()
