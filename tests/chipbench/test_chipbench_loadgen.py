"""The load generator against a fake SSE server: an open loop times a request
from when it was DUE and says how late it ran; a burst-delivered stream gives
the right token count and rate; failures count in no latency (no JAX)."""

import asyncio
import json
import os
import sys
import time

import aiohttp
import pytest
from aiohttp import web

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import loadgen, stats, traffic  # noqa: E402


class FakeServer:
    """Streams ``max_tokens`` tokens as bursts of ``burst`` per SSE event,
    ``gap_s`` apart, as the real edge does (text, then a finish chunk with
    usage).  ``stall_first_s`` blocks the whole event loop once, the way a
    stalled process would.  Asked for ``logprobs`` it gives each position 20
    alternatives under glyphs of their own: ``top_values(n, body, position)``
    says which for the n-th request that asked (by default a function of the prompt's last token alone, as a
    model's would be), and ``shuffle_keys`` hands them out in another order."""

    def __init__(self, burst=4, gap_s=0.01, stall_first_s=0.0, short_by=0, status=200,
                 top_values=None, shuffle_keys=(), programs_step=0):
        self.burst, self.gap_s, self.stall_first_s = burst, gap_s, stall_first_s
        self.short_by, self.status, self.seen, self.asked = short_by, status, 0, 0
        self.top_values, self.shuffle_keys = top_values or default_top_values, shuffle_keys
        # /metrics says 9 compiled programs, and programs_step more each scrape.
        self.prompts, self.programs, self.programs_step = [], 9, programs_step

    def logprobs(self, body, first, k):
        tops = []
        for pos in range(first, first + k):
            vals = self.top_values(self.asked, body, pos)
            keys = [chr(0x4e00 + i) for i in range(len(vals))]
            if self.asked in self.shuffle_keys:
                keys.reverse()
            tops.append(dict(zip(keys, vals)))
        return {"tokens": ["x"] * k, "token_logprobs": [max(t.values()) for t in tops],
                "top_logprobs": tops}

    async def completions(self, request):
        body = await request.json()
        self.seen += 1
        self.prompts.append(body["prompt"])
        self.asked += body.get("logprobs") is not None
        if self.status != 200:
            return web.Response(status=self.status, text="refused")
        if self.stall_first_s and self.seen == 1:
            time.sleep(self.stall_first_s)  # blocks generator and server alike
        n = body["max_tokens"] - self.short_by
        resp = web.StreamResponse(headers={"content-type": "text/event-stream"})
        await resp.prepare(request)
        sent = 0
        while sent < n:
            k = min(self.burst if sent else 1, n - sent)  # first token alone
            sent += k
            chunk = {"choices": [{"index": 0, "text": "x" * k, "finish_reason": None}]}
            if body.get("logprobs") is not None:
                chunk["choices"][0]["logprobs"] = self.logprobs(body, sent - k, k)
            await resp.write(f"data: {json.dumps(chunk)}\n\n".encode())
            await asyncio.sleep(self.gap_s)
        last = {"choices": [{"index": 0, "text": "", "finish_reason": "length"}],
                "usage": {"completion_tokens": n}}
        await resp.write(f"data: {json.dumps(last)}\n\ndata: [DONE]\n\n".encode())
        return resp

    async def metrics(self, request):
        programs, self.programs = self.programs, self.programs + self.programs_step
        return web.Response(text=f"dynamo_tpu_engine_compiled_programs {programs}\n"
                                 f"fake_requests_total {self.seen}\n")

    async def __aenter__(self):
        app = web.Application()
        app.router.add_post("/v1/completions", self.completions)
        app.router.add_get("/metrics", self.metrics)
        self.runner = web.AppRunner(app)
        await self.runner.setup()
        site = web.TCPSite(self.runner, "127.0.0.1", 0)
        await site.start()
        self.url = "http://127.0.0.1:%d" % site._server.sockets[0].getsockname()[1]
        return self

    async def __aexit__(self, *exc):
        await self.runner.cleanup()


def default_top_values(call, body, pos):
    """20 descending log-probabilities that depend on the last prompt token
    and the position, not on which call it is."""
    base = -1.0 - 0.37 * (body["prompt"][-1] % 7) - 0.01 * pos
    return [base - 0.25 * i for i in range(20)]


def _req(n_out, n_in=8):
    return {"prompt": list(range(16, 16 + n_in)), "max_tokens": n_out, "prompt_len": n_in}


async def test_open_loop_latency_counts_from_the_due_time():
    # The first request stalls the process for 0.4 s; the second was due at
    # 0.05 s and can only be sent once the stall is over.  Its latency must
    # include the wait, and the generator must own up to being late.
    async with FakeServer(stall_first_s=0.4) as srv, aiohttp.ClientSession() as s:
        phase = {"loop": "open", "due": [0.0, 0.05], "requests": [_req(9), _req(9)]}
        out = await loadgen.run_phase(s, srv.url, "m", phase, 1.0)
    first, second = out["records"]
    assert second["ok"] and second["t_ref"] == 0.05
    assert second["t_sent"] >= 0.35, "the send was held up by the stall"
    ttft_from_due = second["t_first"] - second["t_ref"]
    ttft_from_send = second["t_first"] - second["t_sent"]
    # The latency from the due time holds the whole wait behind the stall, and
    # from the send it is small: a quarter of a second is what a loaded machine
    # keeps (100 ms failed once in 27 runs under six workers).
    assert ttft_from_due >= 0.3 and ttft_from_due - ttft_from_send >= 0.3
    assert ttft_from_send < 0.25
    assert max(out["late_s"]) >= 0.3
    rep = loadgen.phase_report(out, 1.0)
    assert rep["generator_late_ms"]["max"] >= 300 and rep["generator_late_ms"]["n"] == 2
    assert max(rep["ttft_s"]) >= 0.3


async def test_closed_loop_times_from_the_send_and_keeps_its_client_count():
    async with FakeServer(gap_s=0.005) as srv, aiohttp.ClientSession() as s:
        phase = {"loop": "closed", "clients": 3, "requests": [_req(9) for _ in range(40)]}
        out = await loadgen.run_phase(s, srv.url, "m", phase, 0.5)
    recs = out["records"]
    assert len(recs) >= 9 and all(r["ok"] for r in recs)
    # From the send: the reference is read just before it, and a loaded
    # machine keeps the two within a quarter of a second.
    assert all(r["t_ref"] <= r["t_sent"] < r["t_ref"] + 0.25 for r in recs)
    assert out["in_flight_at_end"] <= 3
    assert out["t_drained"] >= 0.5 and not out["late_s"]


async def test_a_burst_delivered_stream_counts_tokens_from_usage():
    async with FakeServer(burst=4, gap_s=0.02) as srv, aiohttp.ClientSession() as s:
        rec = await loadgen.stream_request(s, srv.url, "m", _req(33), time.perf_counter(), 0.0)
    assert rec["ok"] and rec["n_tokens"] == 33
    assert len(rec["event_times"]) == 1 + 8 + 1  # first token, 8 bursts, the finish chunk
    tpot = stats.request_tpot_s(rec["event_times"], rec["n_tokens"])
    assert 0.02 * 9 / 32 * 0.8 < tpot < 0.02 * 9 / 32 * 2.5
    assert rec["text"] == "x" * 33


async def test_short_and_refused_requests_fail_and_are_in_no_latency():
    async with FakeServer(short_by=2) as srv, aiohttp.ClientSession() as s:
        phase = {"loop": "open", "due": [0.0, 0.01], "requests": [_req(9), _req(9)]}
        short = loadgen.phase_report(await loadgen.run_phase(s, srv.url, "m", phase, 0.3), 0.3)
    assert short["attempted"] == 2 and short["failed"] == 2 and short["short"] == 2
    assert short["ttft_s"] == [] and short["n_completed"] == 0
    async with FakeServer(status=429) as srv, aiohttp.ClientSession() as s:
        phase = {"loop": "open", "due": [0.0], "requests": [_req(9)]}
        refused = loadgen.phase_report(await loadgen.run_phase(s, srv.url, "m", phase, 0.2), 0.2)
    assert refused["failed"] == 1 and refused["short"] == 0
    assert refused["errors"][0].startswith("HTTP 429")


async def test_a_whole_cell_against_the_fake_server(tmp_path):
    mix = {"loop": "closed", "prompt": {"dist": "uniform", "min": 8, "max": 24},
           "output": {"dist": "uniform", "min": 8, "max": 20}, "sharing": {"kind": "none"}}
    async with FakeServer(gap_s=0.002) as srv, aiohttp.ClientSession() as s:
        job = {"url": srv.url, "model": "m", "mix": mix,
               "params": {"clients": 4, "pool_per_s": 400.0}, "seed": 3000000001,
               "seconds": 0.6, "warm_seconds": 0.2, "warm_max_output": 8, "vocab": 300,
               "probe": _probe_job(srv.url)["probe"]}
        res = await loadgen.run_cell(job, s)
    w = res["window"]
    assert w["failed"] == 0 and w["n_completed"] >= 10 and res["probe"]["identical"]
    assert res["probe"]["hit_gap"] == 0.0 and res["probe"]["positions"] == [8] * 4
    assert "control" not in res["probe"]
    assert w["output_tokens_total"] == sum(r["n_tokens"] for r in w["requests"])
    assert 0 < w["output_tokens_per_s"] <= w["output_tokens_total"] / 0.6 + 1e-9
    assert "fake_requests_total" in res["metrics_before"]
    before = float(res["metrics_before"].split()[-1])
    after = float(res["metrics_after"].split()[-1])
    assert after - before == w["attempted"]  # both scrapes with nothing in flight
    assert w["pool"] >= w["attempted"], "the pool covers the window, so no prompt repeats"


SHARED_MIX = {"loop": "closed", "prompt": {"dist": "uniform", "min": 20, "max": 28},
              "output": {"dist": "fixed", "value": 5},
              "sharing": {"kind": "shared_prefix", "groups": 2, "prefix_len": 12}}


@pytest.mark.parametrize("sharing", ["shared_prefix", "none"])
async def test_a_closed_loop_past_its_pool_keeps_what_the_mix_shares(sharing):
    mix = dict(SHARED_MIX, sharing=SHARED_MIX["sharing"] if sharing == "shared_prefix"
               else {"kind": "none"})
    phase = traffic.build_phase(mix, {"clients": 3, "pool_per_s": 8.0}, 3000000001, 0.5, 300,
                                salt=202)
    pool = phase["requests"]
    assert len(pool) == 4
    async with FakeServer(gap_s=0.002) as srv, aiohttp.ClientSession() as s:
        out = await loadgen.run_phase(s, srv.url, "m", phase, 0.5)
    sent = srv.prompts
    assert len(sent) > 4 and out["wrapped"] == len(sent) - 4 and out["pool"] == 4
    assert loadgen.phase_report(out, 0.5)["wrapped"] == len(sent) - 4
    assert sorted(sent[:4]) == sorted(r["prompt"] for r in pool)  # three clients start at once
    by_len = {}
    for r in pool:
        by_len.setdefault(len(r["prompt"]), []).append(r)
    for prompt in sent[4:]:
        # Some pool request of this length was renewed into it: its shared
        # prefix kept token for token, every token of its own part changed.
        assert any(prompt[:r["shared_len"]] == r["prompt"][:r["shared_len"]]
                   and all(a != b for a, b in zip(prompt[r["shared_len"]:],
                                                  r["prompt"][r["shared_len"]:]))
                   for r in by_len[len(prompt)])
    assert {r["shared_len"] for r in pool} == ({12} if sharing == "shared_prefix" else {0})
    # The pool, the first lap past it and the second: no prompt is sent twice.
    assert len(sent) >= 12 and len({tuple(p) for p in sent[:12]}) == 12
    if sharing == "none":
        assert all(all(a != b for a, b in zip(prompt, r["prompt"]))
                   for prompt in sent[4:] for r in by_len[len(prompt)])


EXACT = {"hit_gap": 0.0, "cold_gap": 0.0}  # run.py's PROBE["limits"]


def _probe_job(url, limits=None):
    return {"url": url, "model": "m", "vocab": 300,
            "probe": {"prompt_len": 20, "max_tokens": 8, "seed": 1, "limits": limits or EXACT}}


def _nudged(by_call, from_pos=0):
    """The default values, moved by ``by_call[n]`` nats in the n-th call at
    the positions from ``from_pos`` on."""
    def top_values(call, body, pos):
        by = by_call.get(call, 0.0) if pos >= from_pos else 0.0
        return [v + by for v in default_top_values(call, body, pos)]
    return top_values


def _one_value_fewer(call, body, pos):
    """The second call's last position reports 19 values where the others report 20."""
    vals = default_top_values(call, body, pos)
    return vals[:-1] if (call, pos) == (2, 7) else vals


# The four probe runs are the requests 1 (cold), 2 (hit), 3 (either), 4 (hit)
# that ask for log-probabilities.
@pytest.mark.parametrize("case,server,limits,identical", [
    ("equal", {}, EXACT, True),
    ("near: cold within a limit of the hit", {"top_values": _nudged({1: 0.01})},
     {"hit_gap": 0.0, "cold_gap": 0.05}, True),
    ("near, under the exact limit", {"top_values": _nudged({1: 1e-5})}, EXACT, False),
    ("far: cold beyond the limit", {"top_values": _nudged({1: 0.5})},
     {"hit_gap": 0.0, "cold_gap": 0.05}, False),
    ("cold parts from the hit at the last positions only",
     {"top_values": _nudged({1: 0.5}, from_pos=6)}, EXACT, False),
    ("far: the two hits apart", {"top_values": _nudged({4: 1e-3})},
     {"hit_gap": 1e-6, "cold_gap": 0.05}, False),
    ("the first run after the window may be anything", {"top_values": _nudged({3: 0.5})},
     EXACT, True),
    ("another count of values at one position", {"top_values": _one_value_fewer}, EXACT, False),
    ("permuted but equal", {"shuffle_keys": (2, 3)}, EXACT, True),
])
async def test_the_probe_judges_sorted_values(case, server, limits, identical):
    async with FakeServer(gap_s=0.001, **server) as srv, aiohttp.ClientSession() as s:
        job = _probe_job(srv.url, limits)
        runs = [await loadgen.probe(s, srv.url, "m", job) for _ in range(4)]
        control = await loadgen.probe(s, srv.url, "m", job, flip_last=True)
    assert all(r["ok"] and len(r["values"]) == 8 and len(r["values"][0]) == 20 for r in runs[2:])
    v = loadgen.probe_verdict(runs[:2], runs[2:], job["probe"]["limits"], control)
    assert v["identical"] is identical, case
    assert v["text_identical"]  # the text is equal in every case: it judges nothing
    assert v["control"]["identical"] is False
    assert v["control"]["cold_gap"] > 0.3 and v["control"]["hit_gap"] > 0.3
    if "after the window" in case:
        assert v["after_first_gap"] == pytest.approx(0.5)
    assert srv.prompts[4][:-1] == srv.prompts[0][:-1] and srv.prompts[4][-1] != srv.prompts[0][-1]


def test_values_gap_compares_equal_ranks_and_nothing_else():
    assert loadgen.values_gap([-1.0, -2.0, -3.0], [-1.0, -2.5, -3.0]) == pytest.approx(0.5)
    # Another count of values (an id with a glyph of its own on one side):
    # not the same alternatives, so there is no gap to state.
    assert loadgen.values_gap([-1.0, -2.0, -3.0], [-1.1, -3.0]) is None
    assert loadgen.values_gap([], [-1.0]) is None and loadgen.values_gap([], []) is None
    # Of ids that share a glyph the wire keeps one: the chosen token's value
    # comes from token_logprobs, and equal values count once.
    lp = {"tokens": ["?", "?"], "token_logprobs": [-0.5, -0.7],
          "top_logprobs": [{"?": -4.0, "a": -0.5}, {"?": -3.5}]}
    assert loadgen.position_values(lp) == [[-0.5, -4.0], [-0.7, -3.5]]
    assert loadgen.position_values({"tokens": ["a"], "token_logprobs": [-0.1]}) == [[-0.1]]


@pytest.mark.parametrize("failure", ["no logprobs on the wire", "a probe run failed",
                                     "fewer positions on one side"])
def test_a_probe_with_nothing_to_compare_is_not_identical(failure):
    good = {"ok": True, "text": "x", "error": None, "seconds": 0.1,
            "values": [[-1.0, -2.0]] * 3}
    bad = {"no logprobs on the wire": dict(good, values=[]),
           "a probe run failed": dict(good, ok=False, error="HTTP 500"),
           "fewer positions on one side": dict(good, values=[[-1.0, -2.0]] * 2)}[failure]
    assert loadgen.probe_verdict([good, good], [good, good], EXACT)["identical"]
    assert not loadgen.probe_verdict([good, good], [good, bad], EXACT)["identical"]
    assert not loadgen.probe_verdict([bad, good], [good, good], EXACT)["identical"]
    # What the window left of the probe is reported, whatever it is.
    assert loadgen.probe_verdict([good, good], [bad, good], EXACT)["identical"] is (
        failure != "a probe run failed")
