"""The window layers' attention calls against their roofline, the NEED COUNTED
FROM THE WINDOW whatever implements it.

Measured: device time of the op events matching ``pattern`` (the call's name
in the device trace), summed.  Needed, by ``phase``:

- ``decode``: for EVERY such call chipbench.shapes_swa_moe.
  window_decode_call_need_s: the K and V of min(t, window) positions of every
  decoding row, over the HBM bandwidth (a call serves every decoding row in
  one window layer), for the rows that were decoding while the trace ran
  (from the generator's request records).
- ``prefill``: chipbench.shapes_swa_moe.window_prefill_flops over the bf16
  peak, in every window layer, of the requests whose prompts were computed
  while the trace ran (as ``kernel_roofline_mla_dense_prefill`` takes them:
  a request adds its need times the share of its wait for the first token
  that lies in the traced interval; its prefix hit is the mix's shared prefix
  cut back to a whole resume stride, ``prefill_chunk``).

Nothing to read where the trace has no such op or the configuration has no
``sliding_window``.
"""

from chipbench import shapes_swa_moe, trace_reduce
from chipbench.readers.decode_roofline_mla_dsa import in_flight
from chipbench.readers.kernel_roofline_mla_dense_prefill import prompts_in


def read(ctx, pattern: str, phase: str):
    trace, model, serve = ctx["trace"], ctx["model"], ctx["serve"]
    if trace is None or trace.t_start_s is None or "sliding_window" not in model:
        return None
    ops = trace.all_ops()
    calls = trace_reduce.count_matching(ops, pattern)
    kernel_s = trace_reduce.sum_matching_ns(ops, pattern) / 1e9
    if not calls or kernel_s <= 0:
        return None
    requests = ctx["window"]["requests"]
    if phase == "decode":
        rows, held, _ = in_flight(requests, trace.t_start_s, trace.t_stop_s, float("inf"))
        need_s = calls * shapes_swa_moe.window_decode_call_need_s(
            model, serve, rows, held, ctx["peaks"])
    else:
        sharing, stride = ctx["cell"]["mix"].get("sharing", {}), int(serve["prefill_chunk"])
        shared = sharing.get("prefix_len", 0) if sharing.get("kind") == "shared_prefix" else 0
        flops = sum(share * shapes_swa_moe.window_prefill_flops(
            model, n, min(shared, n - 1) // stride * stride)
            for n, share in prompts_in(requests, trace.t_start_s, trace.t_stop_s))
        need_s = (shapes_swa_moe.layer_counts(model)["window"] * flops
                  / ctx["peaks"]["bf16_flops"])
    if not need_s:
        return None
    return 100.0 * need_s / kernel_s
