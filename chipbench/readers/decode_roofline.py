"""The fused decode program against the memory roofline.

Measured: device time of the module events matching ``module_pattern``, per
decode step (a program runs ``serve[steps_flag]`` steps).  Needed: the bytes
of chipbench.shapes.decode_step_bytes for the cached tokens that were
attended to while the trace ran (from the generator's request records),
over the device's published HBM bandwidth.  The step is bandwidth-bound at
these batch sizes (32 rows x 2 FLOP per weight byte is far under the ridge),
so bytes bound it.
"""

from chipbench import shapes, trace_reduce


def in_flight(requests: list, a: float, b: float):
    """Time-averaged (rows decoding, cached tokens they attend to) over [a, b]
    seconds, a request decoding from its first to its last token and its
    context growing evenly from ``prompt_len`` by ``n_tokens``."""
    rows = tokens = 0.0
    for r in requests:
        if not r["ok"] or r["t_first"] is None or r["t_last"] <= r["t_first"]:
            continue
        lo, hi = max(a, r["t_first"]), min(b, r["t_last"])
        if hi <= lo:
            continue
        rows += hi - lo
        rate = r["n_tokens"] / (r["t_last"] - r["t_first"])
        mid = (lo + hi) / 2 - r["t_first"]
        tokens += (hi - lo) * (r["prompt_len"] + rate * mid)
    return rows / (b - a), tokens / (b - a)


def read(ctx, module_pattern: str, steps_flag: str = "decode_steps"):
    trace = ctx["trace"]
    if trace is None or trace.t_start_s is None:
        return None
    mods = trace.all_modules()
    calls = trace_reduce.count_matching(mods, module_pattern)
    if not calls:
        return None
    step_s = (trace_reduce.sum_matching_ns(mods, module_pattern) / 1e9
              / (calls * ctx["serve"].get(steps_flag, 1)))
    _, kv_tokens = in_flight(ctx["window"]["requests"], trace.t_start_s, trace.t_stop_s)
    need = shapes.decode_step_bytes(ctx["model"], ctx["serve"], kv_tokens)
    return 100.0 * (need / ctx["peaks"]["hbm_bytes_per_s"]) / step_s
