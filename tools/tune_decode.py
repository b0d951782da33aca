"""Decode-kernel block-hint autotuner (``python -m tools.tune_decode``).

Sweeps the decode attention grid/block knobs at a given (model, batch,
page-size) geometry and writes the winner into a small JSON table that
engine init loads (``ops/decode_attention.install_tuned_hints``) instead
of the hardcoded ``_decode_block_hints`` defaults — falling back to them
when no entry matches.  Two knob families:

- **fused** (``DYN_DECODE_KERNEL=pallas_fused``,
  ops/decode_attention.py): ``splits`` (KV-split grid width) and ``ppcb``
  (pages per compute block) — swept by calling the kernel with explicit
  overrides, one jit trace per combo.
- **stock** (the jax pallas ragged kernel, TPU only): ``nq`` query block
  and ``nkv_mb`` KV VMEM budget — swept through the env vars the hint
  function reads at trace time.
- **prefill** (``DYN_PREFILL_KERNEL=pallas``, ops/prefill_attention.py):
  ``prefill_qb`` (query tokens per block), ``prefill_splits`` (KV-split
  grid width) and ``prefill_ppcb`` (pages per compute block) — swept by
  calling the kernel with explicit overrides at a chunked-prefill
  geometry (every row one ``--prefill-chunk`` tail against a full-chain
  paged prefix).

On a CPU the kernels run only under the Pallas interpreter, and only when
asked (``DYN_PALLAS_INTERPRET=1``): timings are then meaningless — the sweep
is a smoke of every combo and the table write path; run on the v5e for
numbers of record.  Resolution
order stays: explicit env var > tuned table > default, so a sweep never
overrides an operator's pin.

Example:
    python -m tools.tune_decode --model llama-3.1-8b --batch 256 \
        --page-size 32 --pages-per-seq 64 --cache-dtype int8 \
        --out decode_tune.json
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time
from typing import Any, Dict, List, Optional, Tuple

import jax


def _build_case(model: str, batch: int, page_size: int, pages_per_seq: int,
                cache_dtype: str, seed: int):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.models.config import get_config

    c = get_config(model)
    H, KV, D = c.num_heads, c.num_kv_heads, c.head_dim
    P = batch * pages_per_seq + 1
    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    q = jax.random.normal(keys[0], (batch, H, D), jnp.bfloat16)
    dt = jnp.dtype(cache_dtype)
    vals = jax.random.normal(keys[1], (P, page_size, 2 * KV, D), jnp.float32)
    if dt.itemsize == 1 and jnp.issubdtype(dt, jnp.integer):
        pages = jnp.clip(jnp.round(vals * 40.0), -127, 127).astype(dt)
        kv_scale = 1.0 / 40.0
    else:
        pages = vals.astype(dt)
        kv_scale = None
    rng = np.random.default_rng(seed)
    # Full chains: the sweep times the worst (longest-context) geometry.
    kv_lens = jnp.full((batch,), pages_per_seq * page_size, jnp.int32)
    tables = jnp.asarray(
        rng.permutation(batch * pages_per_seq).reshape(batch, pages_per_seq),
        jnp.int32,
    )
    num = jnp.asarray([batch], jnp.int32)
    return q, pages, kv_lens, tables, num, D**-0.5, kv_scale


def _build_prefill_case(model: str, batch: int, page_size: int,
                        pages_per_seq: int, cache_dtype: str, chunk: int,
                        seed: int):
    """Chunked-prefill geometry: every row is computing its LAST ``chunk``
    prompt tokens against a full paged chain (prefix + own chunk already
    in cache) — the worst-case prefix read the kernel exists to speed."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.models.config import get_config

    c = get_config(model)
    H, KV, D = c.num_heads, c.num_kv_heads, c.head_dim
    P = batch * pages_per_seq + 1
    chunk = min(chunk, pages_per_seq * page_size)
    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    q = jax.random.normal(keys[0], (batch * chunk, H, D), jnp.bfloat16)
    dt = jnp.dtype(cache_dtype)
    vals = jax.random.normal(keys[1], (P, page_size, 2 * KV, D), jnp.float32)
    if dt.itemsize == 1 and jnp.issubdtype(dt, jnp.integer):
        pages = jnp.clip(jnp.round(vals * 40.0), -127, 127).astype(dt)
        kv_scale = 1.0 / 40.0
    else:
        pages = vals.astype(dt)
        kv_scale = None
    rng = np.random.default_rng(seed)
    kv_lens = jnp.full((batch,), pages_per_seq * page_size, jnp.int32)
    tables = jnp.asarray(
        rng.permutation(batch * pages_per_seq).reshape(batch, pages_per_seq),
        jnp.int32,
    )
    cu = jnp.arange(batch + 1, dtype=jnp.int32) * chunk
    num = jnp.asarray([batch], jnp.int32)
    return q, pages, kv_lens, tables, cu, num, D**-0.5, kv_scale


def sweep_prefill(case, qb_list: List[int], splits_list: List[int],
                  ppcb_list: List[int],
                  iters: int) -> Tuple[Optional[Dict[str, Any]], List[Dict]]:
    from dynamo_tpu.ops.prefill_attention import fused_prefill_attention

    q, pages, kv_lens, tables, cu, num, sm, kv_scale = case
    results = []
    for qb in qb_list:
        for s in splits_list:
            for p in ppcb_list:
                if p > tables.shape[1]:
                    continue
                fn = jax.jit(
                    lambda q, pages, kv_lens, tables, cu, num,
                           _qb=qb, _s=s, _p=p:
                    fused_prefill_attention(
                        q, pages, kv_lens, tables, cu, num, sm_scale=sm,
                        kv_scale=kv_scale, q_block=_qb, num_kv_splits=_s,
                        pages_per_block=_p,
                    )
                )
                try:
                    us = _time_fn(
                        fn, (q, pages, kv_lens, tables, cu, num), iters
                    )
                except Exception as e:
                    print(f"tune: prefill qb={qb} splits={s} ppcb={p} "
                          f"rejected: {e}", file=sys.stderr)
                    continue
                results.append(
                    {"qb": qb, "splits": s, "ppcb": p, "us": round(us, 1)}
                )
                print(f"tune: prefill qb={qb} splits={s} ppcb={p}: "
                      f"{us:.1f}us", file=sys.stderr)
    best = min(results, key=lambda r: r["us"]) if results else None
    return best, results


def _time_fn(fn, args, iters: int) -> float:
    """Median wall microseconds per call (after one warmup/compile)."""
    out = fn(*args)
    jax.block_until_ready(out)
    samples = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        samples.append((time.perf_counter() - t0) * 1e6)
    return statistics.median(samples)


def sweep_fused(case, splits_list: List[int], ppcb_list: List[int],
                iters: int) -> Tuple[Optional[Dict[str, Any]], List[Dict]]:
    from dynamo_tpu.ops.decode_attention import fused_decode_attention

    q, pages, kv_lens, tables, num, sm, kv_scale = case
    results = []
    for s in splits_list:
        for p in ppcb_list:
            if p > tables.shape[1]:
                continue
            fn = jax.jit(
                lambda q, pages, kv_lens, tables, num, _s=s, _p=p:
                fused_decode_attention(
                    q, pages, kv_lens, tables, num, sm_scale=sm,
                    kv_scale=kv_scale, num_kv_splits=_s, pages_per_block=_p,
                )
            )
            try:
                us = _time_fn(fn, (q, pages, kv_lens, tables, num), iters)
            except Exception as e:
                print(f"tune: fused splits={s} ppcb={p} rejected: {e}",
                      file=sys.stderr)
                continue
            results.append({"splits": s, "ppcb": p, "us": round(us, 1)})
            print(f"tune: fused splits={s} ppcb={p}: {us:.1f}us",
                  file=sys.stderr)
    best = min(results, key=lambda r: r["us"]) if results else None
    return best, results


def sweep_stock(case, nq_list: List[int], nkv_mb_list: List[int],
                iters: int) -> Tuple[Optional[Dict[str, Any]], List[Dict]]:
    """TPU only: the stock kernel's hints are env-read at trace time, so
    each combo re-jits under its own env.  Skipped on CPU (the stock path
    there is the XLA fallback, which ignores the hints entirely)."""
    from dynamo_tpu.ops.ragged_attention import on_tpu, ragged_decode_attention

    if not on_tpu():
        print("tune: stock sweep skipped (not on TPU — XLA fallback has "
              "no block hints)", file=sys.stderr)
        return None, []
    q, pages, kv_lens, tables, num, sm, kv_scale = case
    results = []
    for nq in nq_list:
        for mb in nkv_mb_list:
            os.environ["DYN_DECODE_NQ"] = str(nq)
            os.environ["DYN_DECODE_NKV_MB"] = str(mb)
            fn = jax.jit(
                lambda q, pages, kv_lens, tables, num:
                ragged_decode_attention(
                    q, pages, kv_lens, tables, num, sm_scale=sm,
                    impl="tpu", kv_scale=kv_scale, kernel="stock",
                )
            )
            try:
                us = _time_fn(fn, (q, pages, kv_lens, tables, num), iters)
            except Exception as e:
                print(f"tune: stock nq={nq} nkv_mb={mb} rejected: {e}",
                      file=sys.stderr)
                continue
            finally:
                os.environ.pop("DYN_DECODE_NQ", None)
                os.environ.pop("DYN_DECODE_NKV_MB", None)
            results.append({"nq": nq, "nkv_mb": mb, "us": round(us, 1)})
            print(f"tune: stock nq={nq} nkv_mb={mb}: {us:.1f}us",
                  file=sys.stderr)
    best = min(results, key=lambda r: r["us"]) if results else None
    return best, results


def write_entry(path: str, key: str, entry: Dict[str, Any]) -> None:
    """Merge one geometry's entry into the table (other keys preserved)."""
    table: Dict[str, Any] = {}
    try:
        with open(path) as f:
            table = json.load(f)
    except (OSError, ValueError):
        pass
    if not isinstance(table, dict):
        table = {}
    table[key] = entry
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(table, f, indent=2, sort_keys=True)
    os.replace(tmp, path)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--model", default="debug-tiny")
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--page-size", type=int, default=32)
    ap.add_argument("--pages-per-seq", type=int, default=64)
    ap.add_argument("--cache-dtype", default="int8")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--splits", default="1,2,4,8",
                    help="fused KV-split candidates (comma list)")
    ap.add_argument("--ppcb", default="1,2,4,8",
                    help="fused pages-per-compute-block candidates")
    ap.add_argument("--nq", default="8,16,32",
                    help="stock query-block candidates (TPU only)")
    ap.add_argument("--nkv-mb", default="2,4,8",
                    help="stock KV VMEM budget candidates in MB (TPU only)")
    ap.add_argument("--prefill-chunk", type=int, default=512,
                    help="prompt tokens per row in the prefill sweep case")
    ap.add_argument("--prefill-qb", default="64,128,256",
                    help="prefill query-block candidates (comma list)")
    ap.add_argument("--prefill-splits", default="1,2,4",
                    help="prefill KV-split candidates")
    ap.add_argument("--prefill-ppcb", default="1,2,4,8",
                    help="prefill pages-per-compute-block candidates")
    ap.add_argument("--out", default=None,
                    help="table path (default: DYN_DECODE_TUNE_TABLE or "
                         "decode_tune.json at the repo root)")
    args = ap.parse_args(argv)

    from dynamo_tpu.ops.decode_attention import default_table_path, hint_key

    ints = lambda s: [int(x) for x in str(s).split(",") if x.strip()]
    case = _build_case(args.model, args.batch, args.page_size,
                       args.pages_per_seq, args.cache_dtype, args.seed)
    fused_best, fused_all = sweep_fused(
        case, ints(args.splits), ints(args.ppcb), args.iters
    )
    stock_best, stock_all = sweep_stock(
        case, ints(args.nq), ints(args.nkv_mb), args.iters
    )
    prefill_case = _build_prefill_case(
        args.model, args.batch, args.page_size, args.pages_per_seq,
        args.cache_dtype, args.prefill_chunk, args.seed,
    )
    prefill_best, prefill_all = sweep_prefill(
        prefill_case, ints(args.prefill_qb), ints(args.prefill_splits),
        ints(args.prefill_ppcb), args.iters,
    )
    if fused_best is None and stock_best is None and prefill_best is None:
        print("tune: no combo survived — nothing written", file=sys.stderr)
        return 1

    entry: Dict[str, Any] = {
        "geometry": {
            "model": args.model, "batch": args.batch,
            "page_size": args.page_size, "pages_per_seq": args.pages_per_seq,
            "cache_dtype": args.cache_dtype,
        },
        "backend": jax.default_backend(),
        "iters": args.iters,
    }
    if fused_best:
        entry.update(splits=fused_best["splits"], ppcb=fused_best["ppcb"],
                     fused_us=fused_best["us"])
    if stock_best:
        entry.update(nq=stock_best["nq"], nkv_mb=stock_best["nkv_mb"],
                     stock_us=stock_best["us"])
    if prefill_best:
        # Keys match resolve_hint's tuned_key names in
        # ops/prefill_attention.py, so install_tuned_hints serves them
        # with zero extra plumbing.
        entry.update(prefill_qb=prefill_best["qb"],
                     prefill_splits=prefill_best["splits"],
                     prefill_ppcb=prefill_best["ppcb"],
                     prefill_us=prefill_best["us"])
    path = args.out or default_table_path()
    key = hint_key(args.model, args.batch, args.page_size)
    write_entry(path, key, entry)
    print(json.dumps({"key": key, "path": path, "entry": entry,
                      "fused_sweep": fused_all, "stock_sweep": stock_all,
                      "prefill_sweep": prefill_all}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
