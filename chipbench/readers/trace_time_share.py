"""Device time of the ops whose name matches ``pattern``, as a share of the
time the device was busy."""

from chipbench import trace_reduce


def read(ctx, pattern: str):
    trace = ctx["trace"]
    if trace is None or trace.busy_s <= 0:
        return None
    ns = trace_reduce.sum_matching_ns(trace.all_ops(), pattern)
    return 100.0 * ns / 1e9 / max(1, trace.n_devices) / trace.busy_s
