"""The fused decode program of a hybrid model with Kimi Delta Attention layers
(state in slots, latent attention in two layers, a share of every expert
layer's experts held) against its roofline.

Measured: device time of the module events matching ``module_pattern``, per
decode step, as ``decode_roofline`` takes it.  Needed: the larger of the bytes
of chipbench.shapes_kda_hybrid.decode_step_bytes over the published HBM
bandwidth and of its operations over the int8 peak, for the rows that were
decoding while the trace ran and the positions they held (from the generator's
request records).  A configuration without ``linear_attn_config`` has nothing
to read here.
"""

from chipbench import shapes_kda_hybrid, trace_reduce
from chipbench.readers.decode_roofline_mla_dsa import in_flight


def read(ctx, module_pattern: str, steps_flag: str = "decode_steps"):
    trace, model = ctx["trace"], ctx["model"]
    if trace is None or trace.t_start_s is None or "linear_attn_config" not in model:
        return None
    mods = trace.all_modules()
    calls = trace_reduce.count_matching(mods, module_pattern)
    if not calls:
        return None
    step_s = (trace_reduce.sum_matching_ns(mods, module_pattern) / 1e9
              / (calls * ctx["serve"].get(steps_flag, 1)))
    rows, held, _ = in_flight(ctx["window"]["requests"], trace.t_start_s, trace.t_stop_s,
                              float("inf"))
    least_s = max(
        shapes_kda_hybrid.decode_step_bytes(model, ctx["serve"], rows, held)
        / ctx["peaks"]["hbm_bytes_per_s"],
        shapes_kda_hybrid.decode_step_ops(model, rows, held) / ctx["peaks"]["int8_ops"])
    return 100.0 * least_s / step_s
