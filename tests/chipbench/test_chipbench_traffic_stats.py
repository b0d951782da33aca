"""The yardstick's arithmetic: seeded traffic, percentiles, TPOT (no JAX)."""

import os
import random
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import loader, stats, traffic  # noqa: E402

MIXES = sorted(f[:-5] for f in os.listdir(os.path.join(ROOT, "chipbench", "traffic")))


def _mix(name):
    return loader.read_json(loader.data_file("traffic", name))


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_traffic(name):
    mix = _mix(name)
    params = {"rate_rps": 6.0, "clients": 4, "pool_per_s": 5.0}
    a = traffic.build_phase(mix, params, 3000000001, 20.0, 50000, salt=202)
    b = traffic.build_phase(mix, params, 3000000001, 20.0, 50000, salt=202)
    assert a == b


@pytest.mark.parametrize("name", MIXES)
def test_other_seed_same_schedule_other_tokens(name):
    """--seed changes what the prompts say and nothing of the work: the same
    sizes in the same order, due at the same times."""
    mix = _mix(name)
    params = {"rate_rps": 6.0, "clients": 4, "pool_per_s": 5.0}
    a = traffic.build_phase(mix, params, 1, 20.0, 50000, salt=202)
    b = traffic.build_phase(mix, params, 3000000001, 20.0, 50000, salt=202)
    for key in ("prompt_len", "max_tokens"):
        assert [r[key] for r in a["requests"]] == [r[key] for r in b["requests"]]
    assert a.get("due") == b.get("due")
    assert all(x["prompt"] != y["prompt"] for x, y in zip(a["requests"], b["requests"]))
    # another schedule_seed is another order of the same sizes
    c = traffic.build_phase(dict(mix, schedule_seed=99), params, 1, 20.0, 50000, salt=202)
    if "due" not in a:
        assert sorted(r["prompt_len"] for r in c["requests"]) == \
            sorted(r["prompt_len"] for r in a["requests"])
    assert [r["prompt_len"] for r in c["requests"]] != [r["prompt_len"] for r in a["requests"]]


@pytest.mark.parametrize("name", MIXES)
def test_lengths_respect_their_clips(name):
    mix = _mix(name)
    reqs = traffic.build_requests(mix, 500, 7, 50000)
    for r in reqs:
        assert mix["prompt"]["min"] <= r["prompt_len"] <= mix["prompt"]["max"]
        assert mix["output"]["min"] <= r["max_tokens"] <= mix["output"]["max"]
        assert len(r["prompt"]) == r["prompt_len"]
        assert all(traffic.TOKEN_LO <= t < 50000 for t in r["prompt"])
    # the clips bite on neither side for the median
    mids = sorted(r["prompt_len"] for r in reqs)
    if mix["prompt"]["dist"] == "lognormal":
        assert abs(mids[len(mids) // 2] - mix["prompt"]["median"]) <= 0.02 * mix["prompt"]["median"]


def test_phases_of_one_run_share_no_prompt():
    mix = _mix(MIXES[0])
    warm = traffic.build_requests(mix, 50, 9, 50000, salt=101)
    window = traffic.build_requests(mix, 50, 9, 50000, salt=202)
    heads = {tuple(r["prompt"][:16]) for r in warm}
    assert not heads & {tuple(r["prompt"][:16]) for r in window}


def test_poisson_arrivals_are_independent_exponential_gaps():
    """What an evened-out order cannot pass: gaps with the exponential's spread
    (coefficient of variation 1) and counts per second as dispersed as their
    mean (index of dispersion 1), so arrivals cluster as independent users do."""
    mix = {"arrivals": "poisson"}
    t = traffic.arrival_times(mix, 5.0, 2000.0, random.Random(4))
    assert t == sorted(t) and t[0] == 0.0 and t[-1] < 2000.0
    assert abs(len(t) / 2000.0 - 5.0) < 0.15
    gaps = [b - a for a, b in zip(t, t[1:])]
    mean = sum(gaps) / len(gaps)
    cv = (sum((g - mean) ** 2 for g in gaps) / len(gaps)) ** 0.5 / mean
    assert 0.95 < cv < 1.05
    counts = [0] * 2000
    for x in t:
        counts[int(x)] += 1
    m = sum(counts) / len(counts)
    dispersion = sum((c - m) ** 2 for c in counts) / len(counts) / m
    assert 0.85 < dispersion < 1.15
    assert max(counts) >= 12  # a second with over twice the mean rate does happen


def test_a_sweep_offers_one_process_at_rising_speed():
    mix = {"arrivals": "poisson"}
    slow = traffic.arrival_times(mix, 3.0, 50.0, random.Random(1))
    fast = traffic.arrival_times(mix, 6.0, 25.0, random.Random(1))
    n = min(len(slow), len(fast))
    assert n > 100 and all(abs(a - 2 * b) < 1e-9 for a, b in zip(slow[:n], fast[:n]))


def test_burst_arrivals_keep_the_mean_rate_and_leave_gaps():
    mix = {"arrivals": "burst", "burst": {"on_s": 2.0, "off_s": 4.0}}
    t = traffic.arrival_times(mix, 6.0, 60.0, random.Random(5))
    assert 300 < len(t) < 420 and 54.0 < t[-1] <= 60.0
    assert all((x % 6.0) <= 2.0 + 1e-9 for x in t)  # nothing is due in an off period
    with pytest.raises(ValueError):
        traffic.arrival_times({"arrivals": "tidal"}, 6.0, 60.0, random.Random(5))


def test_open_phase_holds_what_is_due_inside_the_window():
    mix = _mix("chat-open")
    phase = traffic.build_phase(mix, {"rate_rps": 5.0}, 11, 10.0, 50000)
    assert phase["loop"] == "open" and len(phase["due"]) == len(phase["requests"])
    assert all(0.0 <= d < 10.0 for d in phase["due"])
    assert 30 <= len(phase["requests"]) <= 75  # Poisson(50)


def test_shared_prefix_is_shared_and_the_rest_is_not():
    mix = dict(_mix("chat-open"), sharing={"kind": "shared_prefix", "groups": 2, "prefix_len": 64})
    reqs = traffic.build_requests(mix, 8, 3, 50000)
    assert reqs[0]["prompt"][:64] == reqs[2]["prompt"][:64]
    assert reqs[0]["prompt"][:64] != reqs[1]["prompt"][:64]
    assert reqs[0]["prompt"][64:80] != reqs[2]["prompt"][64:80]


def test_unknown_distribution_loop_and_sharing_are_errors():
    with pytest.raises(ValueError):
        traffic.lengths({"dist": "zipf"}, 4)
    with pytest.raises(ValueError):
        traffic.build_phase({"loop": "half-open"}, {}, 1, 1.0, 300)
    with pytest.raises(ValueError):
        traffic.build_requests(dict(_mix("chat-open"), sharing={"kind": "sessions"}), 2, 1, 300)


# ------------------------------------------------------------- percentiles
def test_percentile_is_nearest_rank():
    xs = list(range(1, 201))
    assert stats.percentile(xs, 50) == 100
    assert stats.percentile(xs, 90) == 180
    assert stats.percentile([3.0], 50) == 3.0


@pytest.mark.parametrize("n,q,ok", [(130, 90, True), (100, 90, True), (99, 90, False),
                                    (130, 95, False), (200, 95, True), (9, 50, True)])
def test_a_tail_needs_ten_samples_beyond_it(n, q, ok):
    xs = [float(i) for i in range(n)]
    if ok:
        stats.percentile(xs, q)
    else:
        with pytest.raises(stats.TooFewSamples) as e:
            stats.percentile(xs, q)
        assert str(n) in str(e.value)  # the refusal states the sample count


def test_percentile_of_nothing_is_refused():
    with pytest.raises(stats.TooFewSamples):
        stats.percentile([], 50)
    with pytest.raises(ValueError):
        stats.percentile([1.0], 100)


# -------------------------------------------------------------------- TPOT
def test_tpot_does_not_care_how_a_burst_is_chunked():
    # 33 tokens over 3.2 s: token by token, in bursts of 4, or in two lumps
    one_by_one = [0.1 * i for i in range(33)]
    bursts = [0.0] + [0.4 * i for i in range(1, 9)]
    lumps = [0.0, 3.2]
    want = 3.2 / 32
    for times in (one_by_one, bursts, lumps):
        assert stats.request_tpot_s(times, 33) == pytest.approx(want)
    assert stats.request_tpot_s([0.0, 0.3], 7) is None  # under 8 tokens: no rate
    assert stats.request_tpot_s([0.5], 12) is None  # one event: no span


def _rec(t_ref, times, n, ok=True):
    return {"ok": ok, "t_ref": t_ref, "t_first": times[0] if times else None,
            "t_last": times[-1] if times else None, "n_tokens": n, "event_times": times}


def test_summarize_counts_only_what_completed_in_the_window():
    reqs = [
        _rec(0.0, [1.0, 2.0, 3.0], 9),           # done inside
        _rec(8.0, [9.0, 10.5, 11.0, 12.0], 16),  # straddles the end at 10 s
        _rec(1.0, [], 0, ok=False),              # failed: in no latency
    ]
    s = stats.summarize(reqs, 10.0)
    assert s["n_completed"] == 1 and s["ttft_s"] == [1.0]
    assert s["tpot_s"] == [pytest.approx(2.0 / 8)]
    # 9 tokens of the first + a quarter of the second's events (4 of 16 tokens)
    assert s["output_tokens_in_window"] == pytest.approx(9 + 4)
    assert s["output_tokens_per_s"] == pytest.approx(1.3)


# ------------------------------------------------------------- no JAX here
def test_the_generator_side_imports_neither_jax_nor_the_program():
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "import chipbench.loader, chipbench.traffic, chipbench.stats, chipbench.promtext\n"
        "import chipbench.shapes, chipbench.trace_reduce, chipbench.loadgen\n"
        "import pkgutil, importlib, chipbench.readers as r\n"
        "[importlib.import_module('chipbench.readers.' + m.name) for m in pkgutil.iter_modules(r.__path__)]\n"
        "bad = [m for m in sys.modules if m.split('.')[0] in ('jax', 'jaxlib', 'dynamo_tpu')]\n"
        "assert not bad, bad\n" % ROOT
    )
    p = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr


# ------------------------------------------------- what a wrapped request keeps
@pytest.mark.parametrize("prefix_len,lo,hi", [(64, 80, 120), (64, 40, 120), (200, 80, 120)])
def test_shared_len_is_the_shorter_of_prefix_and_prompt(prefix_len, lo, hi):
    mix = dict(_mix("prefill-closed"), prompt={"dist": "uniform", "min": lo, "max": hi},
               sharing={"kind": "shared_prefix", "groups": 2, "prefix_len": prefix_len})
    reqs = traffic.build_requests(mix, 16, 3, 50000)
    assert all(r["shared_len"] == min(prefix_len, r["prompt_len"]) for r in reqs)
    assert len({r["shared_len"] == r["prompt_len"] for r in reqs}) == (2 if lo < prefix_len < hi else 1)
    by_group = {i % 2: r for i, r in enumerate(reqs)}
    for i, r in enumerate(reqs):
        k = min(r["shared_len"], by_group[i % 2]["shared_len"])
        assert r["prompt"][:k] == by_group[i % 2]["prompt"][:k]


@pytest.mark.parametrize("name", MIXES)
def test_a_renewed_request_keeps_its_sizes_and_what_its_mix_shares(name):
    mix = _mix(name)
    shares = mix.get("sharing", {"kind": "none"})["kind"] == "shared_prefix"
    for r in traffic.build_requests(mix, 6, 3000000001, 50000, salt=202):
        k = r["shared_len"]
        assert k == (min(mix["sharing"]["prefix_len"], r["prompt_len"]) if shares else 0)
        new = traffic.renewed(r)
        assert {key: new[key] for key in new if key != "prompt"} == \
            {key: r[key] for key in r if key != "prompt"}
        assert new["prompt"][:k] == r["prompt"][:k] and len(new["prompt"]) == r["prompt_len"]
        assert all(a != b and a >= traffic.TOKEN_LO for a, b in zip(new["prompt"][k:], r["prompt"][k:]))
        assert k < r["prompt_len"], "every committed mix leaves a request a part of its own"
        # A second lap is new traffic too: it repeats neither the pool nor the first lap.
        again = traffic.renewed(r, 2)
        assert again["prompt"][:k] == r["prompt"][:k]
        assert all(len({a, b, c}) == 3 and c >= traffic.TOKEN_LO
                   for a, b, c in zip(r["prompt"][k:], new["prompt"][k:], again["prompt"][k:]))
    assert traffic.renewed(r, 15) != traffic.renewed(r, 1) == traffic.renewed(r, 16)
