"""The prompt chunks' kernel of latent attention over the whole context
against the bf16 peak.

Measured: device time of the op events matching ``pattern`` (the Pallas
call's name), summed over layers and token buckets.  Needed:
chipbench.shapes_mla_dense.prefill_request_flops, in every layer, of the
requests whose prompts were computed while the trace ran, from the
generator's request records: a request's prompt steps run somewhere between
its send and its first token, so it adds its need times the share of that
wait which lies in the traced interval (whole for a request inside it; what
the two edges cut off evens out between them).  Its prefix hit is the mix's
shared prefix in whole pages: computed from below, since a request that
missed did more work than is counted.  Nothing to read where the trace has no
such op or the configuration is not of the latent family.
"""

from chipbench import shapes_mla_dense, trace_reduce


def prompts_in(requests: list, a: float, b: float) -> list:
    """``(prompt_len, share)`` of the requests that waited for their first
    token inside [a, b] seconds: the share of that wait which lies in it."""
    out = []
    for r in requests:
        if not r["ok"] or r["t_first"] is None or r["t_first"] <= r["t_ref"]:
            continue
        lo, hi = max(a, r["t_ref"]), min(b, r["t_first"])
        if hi > lo:
            out.append((r["prompt_len"], (hi - lo) / (r["t_first"] - r["t_ref"])))
    return out


def read(ctx, pattern: str):
    trace, model, serve = ctx["trace"], ctx["model"], ctx["serve"]
    if trace is None or trace.t_start_s is None or "index_topk" in model \
            or "kv_lora_rank" not in model:
        return None
    kernel_s = trace_reduce.sum_matching_ns(trace.all_ops(), pattern) / 1e9
    if kernel_s <= 0:
        return None
    sharing, page = ctx["cell"]["mix"].get("sharing", {}), int(serve.get("block_size", 16))
    shared = sharing.get("prefix_len", 0) if sharing.get("kind") == "shared_prefix" else 0
    flops = sum(share * shapes_mla_dense.prefill_request_flops(
        model, serve, n, min(shared, n - 1) // page * page)
        for n, share in prompts_in(ctx["window"]["requests"], trace.t_start_s, trace.t_stop_s))
    if not flops:
        return None
    need_s = model["num_hidden_layers"] * flops / ctx["peaks"]["bf16_flops"]
    return 100.0 * need_s / kernel_s
