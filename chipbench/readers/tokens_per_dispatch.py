"""Output tokens of the window over the dispatches of one kind, each counted
``serve[steps_flag]`` times: the decode rows an average dispatch carried.  The
first token of a request comes from its last prefill chunk, not from a decode
dispatch, so it is left out of the count."""

from chipbench import promtext


def read(ctx, series: str, labels: dict | None = None, steps_flag: str | None = None):
    n = promtext.delta(ctx["before"], ctx["after"], series, labels)
    if not n:
        return None
    steps = ctx["serve"].get(steps_flag, 1) if steps_flag else 1
    w = ctx["window"]
    decoded = w["output_tokens_total"] - sum(1 for r in w["requests"] if r["n_tokens"])
    return decoded / (n * steps)
