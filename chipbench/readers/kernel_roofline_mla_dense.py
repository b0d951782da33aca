"""The one-query kernel of latent attention over the whole context against
its roofline.

Measured: device time of the op events matching ``pattern`` (the Pallas
call's name), summed.  Needed: for EVERY such call the least time of
chipbench.shapes_mla_dense.kernel_call_need_s for the positions the decoding
rows held while the trace ran (from the generator's request records: a call
serves every decoding row, in one layer).  Nothing to read where the trace
has no such op or the configuration is not of the latent family.
"""

from chipbench import shapes_mla_dense, trace_reduce
from chipbench.readers.decode_roofline_mla_dsa import in_flight


def read(ctx, pattern: str):
    trace, model = ctx["trace"], ctx["model"]
    if trace is None or trace.t_start_s is None or "kv_lora_rank" not in model:
        return None
    ops = trace.all_ops()
    calls = trace_reduce.count_matching(ops, pattern)
    kernel_s = trace_reduce.sum_matching_ns(ops, pattern) / 1e9
    if not calls or kernel_s <= 0:
        return None
    _, held, _ = in_flight(ctx["window"]["requests"], trace.t_start_s, trace.t_stop_s,
                           float("inf"))
    need_s = calls * shapes_mla_dense.kernel_call_need_s(model, ctx["serve"], held, ctx["peaks"])
    return 100.0 * need_s / kernel_s
