"""The fused decode program of a latent-attention model with a sparse
selector against its roofline.

Measured: device time of the module events matching ``module_pattern``, per
decode step, as ``decode_roofline`` takes it.  Needed: the larger of the bytes
of chipbench.shapes_mla_dsa.decode_step_bytes over the published HBM
bandwidth and the context-dependent operations over the bf16 peak, for the
rows that were decoding while the trace ran and the positions they held (from
the generator's request records): a row of context n has n positions scored
and min(``index_topk``, n) kept.  A configuration without the selector's keys
has nothing to read here.
"""

from chipbench import shapes_mla_dsa, trace_reduce


def in_flight(requests: list, a: float, b: float, topk: int):
    """Time-averaged (rows decoding, positions they hold, positions they keep)
    over [a, b] seconds, a request decoding from its first to its last token
    and its context growing evenly from ``prompt_len`` by ``n_tokens``."""
    rows = held = kept = 0.0
    for r in requests:
        if not r["ok"] or r["t_first"] is None or r["t_last"] <= r["t_first"]:
            continue
        lo, hi = max(a, r["t_first"]), min(b, r["t_last"])
        if hi <= lo:
            continue
        rate = r["n_tokens"] / (r["t_last"] - r["t_first"])
        ctx = r["prompt_len"] + rate * ((lo + hi) / 2 - r["t_first"])
        rows += hi - lo
        held += (hi - lo) * ctx
        kept += (hi - lo) * min(topk, ctx)
    return rows / (b - a), held / (b - a), kept / (b - a)


def read(ctx, module_pattern: str, steps_flag: str = "decode_steps"):
    trace, model = ctx["trace"], ctx["model"]
    if trace is None or trace.t_start_s is None or "index_topk" not in model:
        return None
    mods = trace.all_modules()
    calls = trace_reduce.count_matching(mods, module_pattern)
    if not calls:
        return None
    step_s = (trace_reduce.sum_matching_ns(mods, module_pattern) / 1e9
              / (calls * ctx["serve"].get(steps_flag, 1)))
    rows, held, kept = in_flight(ctx["window"]["requests"], trace.t_start_s, trace.t_stop_s,
                                 model["index_topk"])
    need_bytes = shapes_mla_dsa.decode_step_bytes(model, ctx["serve"], rows, held, kept)
    need_flops = shapes_mla_dsa.decode_attention_flops(model, held, kept)
    least_s = max(need_bytes / ctx["peaks"]["hbm_bytes_per_s"],
                  need_flops / ctx["peaks"]["bf16_flops"])
    return 100.0 * least_s / step_s
