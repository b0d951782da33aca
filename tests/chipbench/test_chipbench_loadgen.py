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

from chipbench import loadgen, stats  # noqa: E402


class FakeServer:
    """Streams ``max_tokens`` tokens as bursts of ``burst`` per SSE event,
    ``gap_s`` apart, as the real edge does (text, then a finish chunk with
    usage).  ``stall_first_s`` blocks the whole event loop once, the way a
    stalled process would."""

    def __init__(self, burst=4, gap_s=0.01, stall_first_s=0.0, short_by=0, status=200):
        self.burst, self.gap_s, self.stall_first_s = burst, gap_s, stall_first_s
        self.short_by, self.status, self.seen = short_by, status, 0

    async def completions(self, request):
        body = await request.json()
        self.seen += 1
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
            await resp.write(f"data: {json.dumps(chunk)}\n\n".encode())
            await asyncio.sleep(self.gap_s)
        last = {"choices": [{"index": 0, "text": "", "finish_reason": "length"}],
                "usage": {"completion_tokens": n}}
        await resp.write(f"data: {json.dumps(last)}\n\ndata: [DONE]\n\n".encode())
        return resp

    async def metrics(self, request):
        return web.Response(text=f"fake_requests_total {self.seen}\n")

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
    assert ttft_from_due >= 0.3 > ttft_from_send + 0.2
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
    assert all(r["t_ref"] == pytest.approx(r["t_sent"], abs=0.01) for r in recs)
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
               "probe": {"prompt_len": 20, "max_tokens": 8, "seed": 1}}
        res = await loadgen.run_cell(job, s)
    w = res["window"]
    assert w["failed"] == 0 and w["n_completed"] >= 10 and res["probe"]["identical"]
    assert w["output_tokens_total"] == sum(r["n_tokens"] for r in w["requests"])
    assert 0 < w["output_tokens_per_s"] <= w["output_tokens_total"] / 0.6 + 1e-9
    assert "fake_requests_total" in res["metrics_before"]
    before = float(res["metrics_before"].split()[-1])
    after = float(res["metrics_after"].split()[-1])
    assert after - before == w["attempted"]  # both scrapes with nothing in flight
    assert w["pool"] >= w["attempted"], "the pool covers the window, so no prompt repeats"
