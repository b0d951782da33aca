"""The one general traffic generator (stdlib only; never imports JAX).

A mix is a data file ``traffic/<name>.json``:

    {"loop": "open" | "closed",
     "arrivals": "poisson" | "burst",          (open loop only)
     "burst": {"on_s": 2, "off_s": 4},         (arrivals == "burst")
     "prompt": {"dist": "lognormal", "median": 512, "sigma": 0.8, "min": 64, "max": 2048},
     "output": {"dist": "uniform", "min": 16, "max": 64},
     "sharing": {"kind": "none"} | {"kind": "shared_prefix", "groups": 16, "prefix_len": 2048}}

The rate (``rate_rps``) or client count (``clients``) and the size of the
request pool sit in the cell's own file, ``cells/<cell>.json``, so one mix
serves several configurations.

The SCHEDULE of a phase — how many requests, when each is due, and the
order of the lengths — is drawn from the mix's own ``schedule_seed`` and is the
same in every run: arrival gaps are independent exponential draws (a Poisson
process, with the clusters and lulls that make an open loop's tail), the
lengths are each distribution's quantile grid in a free shuffle.  ``--seed``
decides what the prompts SAY: every token of every prompt.  On the chip a
schedule redrawn per seed moved the median TTFT of a 45 s window by 20% from
seed to seed, reproducibly per seed (PERF.md, PR 23): where the long requests
and the short gaps fall is part of the work, so it is part of the cell, as one
documented sample, and runs with different seeds do the same work.
"""

from __future__ import annotations

import math
import random
from statistics import NormalDist

TOKEN_LO = 16  # below it sit the byte tokenizer's specials
_MIX = 1_000_003  # keeps (seed, salt) pairs apart


def schedule_rng(mix: dict, salt: int) -> random.Random:
    """The generator of a phase's schedule: the mix's ``schedule_seed`` and
    the phase's ``salt``, never ``--seed``."""
    return random.Random(int(mix.get("schedule_seed", 0)) * _MIX + salt)


def shuffled(values: list, rng: random.Random) -> list:
    out = list(values)
    rng.shuffle(out)
    return out


def lengths(dist: dict, n: int) -> list:
    """The n-point quantile grid of a length distribution, as integers."""
    kind = dist["dist"]
    qs = [(i + 0.5) / n for i in range(n)]
    if kind == "fixed":
        xs = [float(dist["value"])] * n
    elif kind == "uniform":
        lo, hi = dist["min"], dist["max"]
        xs = [lo + q * (hi - lo) for q in qs]
    elif kind == "lognormal":
        z = NormalDist()
        xs = [dist["median"] * math.exp(dist["sigma"] * z.inv_cdf(q)) for q in qs]
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    lo, hi = dist.get("min", 1), dist.get("max", math.inf)
    return [int(min(max(round(x), lo), hi)) for x in xs]


def scale_dist(dist: dict, factor: float) -> dict:
    """A distribution with every length multiplied (the CPU rehearsal)."""
    out = dict(dist)
    for key in ("value", "median", "min", "max"):
        if key in out:
            out[key] = max(1, int(round(out[key] * factor)))
    return out


def arrival_times(mix: dict, rate_rps: float, seconds: float, rng: random.Random) -> list:
    """The due times (seconds from the phase's start) of an open loop that
    fall inside ``seconds``: independent exponential gaps at ``rate_rps``.
    The unit-rate gaps are the same at every rate (a sweep's steps offer one
    process at rising speed).  With ``arrivals: burst`` the same arrivals are
    squeezed into the ``on_s`` part of every ``on_s + off_s`` cycle, which
    keeps the mean rate."""
    kind = mix.get("arrivals", "poisson")
    if kind not in ("poisson", "burst"):
        raise ValueError(f"unknown arrivals {kind!r}")
    times, t = [], 0.0
    while t < seconds:
        times.append(t)
        t += rng.expovariate(1.0) / rate_rps
    if kind == "burst":
        on, off = float(mix["burst"]["on_s"]), float(mix["burst"]["off_s"])
        share = on / (on + off)
        out = []
        for t in times:
            tau = t * share  # time spent inside "on" periods so far
            k = math.floor(tau / on)
            out.append(k * (on + off) + (tau - k * on))
        times = out
    return times


def _tokens(rng: random.Random, n: int, vocab: int) -> list:
    return rng.choices(range(TOKEN_LO, vocab), k=n)


def build_requests(mix: dict, n: int, seed: int, vocab: int, salt: int = 0) -> list:
    """n requests ``{"prompt": [ids], "max_tokens": k, "prompt_len": m,
    "shared_len": h}``: sizes and their order from the schedule, tokens from
    ``seed``; the first ``shared_len`` tokens are the request's group's shared
    prefix (0 when the mix shares nothing).  ``salt`` separates the phases of
    one run (warm traffic, window) so that they share neither order nor
    prompt."""
    order = schedule_rng(mix, salt)
    p_lens = shuffled(lengths(mix["prompt"], n), order)
    o_lens = shuffled(lengths(mix["output"], n), order)
    rng = random.Random(seed * _MIX + salt)
    sharing = mix.get("sharing", {"kind": "none"})
    prefixes = []
    if sharing["kind"] == "shared_prefix":
        prefixes = [
            _tokens(random.Random(rng.getrandbits(48)), sharing["prefix_len"], vocab)
            for _ in range(sharing["groups"])
        ]
    elif sharing["kind"] != "none":
        raise ValueError(f"unknown sharing {sharing['kind']!r}")
    out = []
    for i, (pl, ol) in enumerate(zip(p_lens, o_lens)):
        body = random.Random(rng.getrandbits(48))
        head = prefixes[i % len(prefixes)][:pl] if prefixes else []
        prompt = head + _tokens(body, pl - len(head), vocab)
        out.append({"prompt": prompt, "max_tokens": ol, "prompt_len": pl,
                    "shared_len": len(head)})
    return out


def renewed(req: dict, lap: int = 1) -> dict:
    """The request a closed loop sends on its ``lap``-th pass beyond its pool:
    the same sizes, the shared prefix kept, every token of its own part changed,
    and changed otherwise on every lap (``t ^ lap``: lap 1 is ``t ^ 1``), so
    that a later lap does not send an earlier one's prompts again as
    whole-prompt hits.  A new turn on a context the replica holds; where
    nothing is shared, a new prompt.  The flips 1 to 15 stay among the 16 ids
    of a token's aligned group, above ``TOKEN_LO``; the 16th lap, which no
    cell comes near, begins them again."""
    flip = 1 + (lap - 1) % (TOKEN_LO - 1)
    k, prompt = req.get("shared_len", 0), req["prompt"]
    return dict(req, prompt=prompt[:k] + [t ^ flip for t in prompt[k:]])


def build_phase(mix: dict, params: dict, seed: int, seconds: float, vocab: int,
                salt: int = 0, max_output: int | None = None) -> dict:
    """One phase of traffic: the requests, and for an open loop their due
    times.  A closed loop gets ``params['pool_per_s'] * seconds`` requests to
    draw from in order (wrapping, should the system outrun the pool: see
    ``renewed``)."""
    loop = mix["loop"]
    if loop == "open":
        due = arrival_times(mix, float(params["rate_rps"]), seconds,
                            schedule_rng(mix, salt + 1))
        phase = {"loop": "open", "due": due,
                 "requests": build_requests(mix, len(due), seed, vocab, salt)}
    elif loop == "closed":
        n = max(int(params["clients"]), round(float(params["pool_per_s"]) * seconds))
        phase = {"loop": "closed", "clients": int(params["clients"]),
                 "requests": build_requests(mix, n, seed, vocab, salt)}
    else:
        raise ValueError(f"unknown loop {loop!r}")
    if max_output is not None:
        for r in phase["requests"]:
            r["max_tokens"] = min(r["max_tokens"], max_output)
    return phase
