"""Percentile and latency arithmetic of the benchmark (stdlib only).

Copied in spirit from benchmarks/loadgen.py and corrected: a tail states its
sample count and is refused when fewer than ten samples lie beyond it; TPOT
is (last event - first event) / (tokens - 1) per request, which does not
care how a fused burst of tokens was chunked on the wire.
"""

from __future__ import annotations

import math

MIN_BEYOND = 10
TPOT_MIN_TOKENS = 8


class TooFewSamples(ValueError):
    """A percentile was asked of a sample that cannot support it."""


def percentile(values, q: float, min_beyond: int = MIN_BEYOND) -> float:
    """Nearest-rank percentile, ``q`` in (0, 100).

    Refused (``TooFewSamples``) on an empty sample, and on a tail (q > 50)
    with fewer than ``min_beyond`` samples beyond the rank it picks."""
    if not 0.0 < q < 100.0:
        raise ValueError(f"percentile {q} outside (0, 100)")
    xs = sorted(values)
    n = len(xs)
    if n == 0:
        raise TooFewSamples("no samples")
    rank = max(1, math.ceil(q / 100.0 * n))  # 1-based
    if q > 50.0 and n - rank < min_beyond:
        raise TooFewSamples(
            f"p{q:g} of {n} samples has {n - rank} beyond it; {min_beyond} are needed"
        )
    return xs[rank - 1]


def request_tpot_s(event_times, n_tokens: int) -> float | None:
    """Seconds per output token of one request, or None when it has fewer
    than ``TPOT_MIN_TOKENS`` tokens.  ``event_times`` are the arrival times
    of its stream events; an event carries one token or a fused burst of
    them, and the wire does not say how many, so the rate is taken over the
    whole stream: (last event - first event) / (tokens - 1)."""
    if n_tokens < TPOT_MIN_TOKENS or len(event_times) < 2:
        return None
    return (event_times[-1] - event_times[0]) / (n_tokens - 1)


def tokens_in_window(event_times, n_tokens: int, window_s: float) -> float:
    """A request's tokens that arrived in [0, window_s]: all of them when all
    its events did, else its tokens in proportion to its events inside."""
    if not event_times:
        return 0.0
    inside = sum(1 for t in event_times if 0.0 <= t <= window_s)
    return n_tokens * inside / len(event_times)


def summarize(requests, window_s: float) -> dict:
    """The window's end-to-end quantities from per-request records.

    A record has ``ok``, ``t_ref`` (due time in an open loop, send time in a
    closed one), ``t_first``, ``t_last``, ``n_tokens`` and ``event_times``,
    all in seconds since the window began.  Latencies are over requests that
    completed inside the window; a failed request is in no latency."""
    done = [r for r in requests if r["ok"] and r["t_last"] <= window_s]
    ttft = [r["t_first"] - r["t_ref"] for r in done]
    tpot = [t for t in (request_tpot_s(r["event_times"], r["n_tokens"]) for r in done)
            if t is not None]
    n_in = sum(tokens_in_window(r["event_times"], r["n_tokens"], window_s)
               for r in requests if r["ok"])
    return {
        "ttft_s": ttft,
        "tpot_s": tpot,
        "n_completed": len(done),
        "n_tpot": len(tpot),
        "output_tokens_in_window": n_in,
        "output_tokens_per_s": n_in / window_s,
    }
