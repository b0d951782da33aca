#!/usr/bin/env python3
"""The load generator: a child process off the chip (stdlib + aiohttp).

It never imports JAX or ``dynamo_tpu``, so its event loop shares neither the
chip nor the server's interpreter lock.  Usage: ``loadgen.py JOB.json``.  It
writes one JSON line per phase boundary to stdout for the harness
(``{"event": ..., "t_epoch": ...}``) and its results to ``job["results_path"]``.

What it corrects against benchmarks/loadgen.py: an open loop times a request
from when it was DUE, not from when a late generator got round to sending it,
and says how late it ran; every request has its own lengths; a token's time is
its network chunk's arrival, and the per-request rate is taken over the whole
stream (stats.request_tpot_s), not over gaps between events: the server sends a
fused burst of decode_steps tokens as ONE event, and only the final usage chunk
says how many tokens there were.
"""

from __future__ import annotations

import asyncio
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import aiohttp  # noqa: E402

from chipbench import stats, traffic  # noqa: E402

DRAIN_CAP_S = 120.0
PROBE_TOP = 20  # the most alternatives the server gives (llm/openai.py)


def say(event: str, **fields) -> None:
    print(json.dumps({"event": event, "t_epoch": time.time(), **fields}), flush=True)


def blank_record(req: dict, t_ref: float, t_sent: float, error=None) -> dict:
    return {"ok": False, "t_ref": t_ref, "t_sent": t_sent, "t_first": None, "t_last": None,
            "n_tokens": 0, "event_times": [], "short": False, "prompt_len": req["prompt_len"],
            "max_tokens": req["max_tokens"], "text": "", "error": error, "logprobs": []}


async def stream_request(session, url: str, model: str, req: dict, clock0: float,
                         t_ref: float, logprobs: int | None = None) -> dict:
    """One streamed /v1/completions call.  Times are seconds since ``clock0``
    (perf_counter); ``t_ref`` is the time its latency counts from.  With
    ``logprobs`` the record keeps, per generated position the wire reports,
    the log-probabilities it gave (``position_values``)."""
    payload = {
        "model": model, "prompt": req["prompt"], "stream": True,
        "max_tokens": req["max_tokens"], "temperature": 0.0,
        "nvext": {"ignore_eos": True},
    }
    if logprobs is not None:
        payload["logprobs"] = logprobs
    rec = blank_record(req, t_ref, time.perf_counter() - clock0)
    times, parts, usage_n, done = rec["event_times"], [], None, False
    try:
        async with session.post(f"{url}/v1/completions", json=payload) as resp:
            if resp.status != 200:
                rec["error"] = f"HTTP {resp.status}: {(await resp.text())[:200]}"
                return rec
            buf = b""
            async for raw in resp.content.iter_any():
                now = time.perf_counter() - clock0
                buf += raw
                while b"\n" in buf:
                    head, buf = buf.split(b"\n", 1)
                    line = head.strip()
                    if not line.startswith(b"data:"):
                        continue
                    data = line[5:].strip()
                    if data == b"[DONE]":
                        done = True
                        break
                    chunk = json.loads(data)
                    choice = (chunk.get("choices") or [{}])[0]
                    usage = chunk.get("usage")
                    if usage:
                        usage_n = usage.get("completion_tokens", usage_n)
                    # The finish chunk counts as an event: the last tokens
                    # ride in it when their text is empty or the answer is
                    # one burst long, and it arrives with them.
                    if "text" in choice or choice.get("finish_reason"):
                        times.append(now)
                        parts.append(choice.get("text") or "")
                    if choice.get("logprobs"):
                        rec["logprobs"] += position_values(choice["logprobs"])
                if done:
                    break
    except asyncio.CancelledError:
        rec["error"] = "cancelled at the drain cap"
        raise
    except (aiohttp.ClientError, asyncio.TimeoutError, ValueError, OSError) as e:
        rec["error"] = f"{type(e).__name__}: {e}"
        return rec
    finally:
        if times:
            rec["t_first"], rec["t_last"] = times[0], times[-1]
        rec["text"] = "".join(parts)
    # An event carries one token or a fused burst of them; the final usage
    # chunk is the only count of tokens the wire gives.
    rec["n_tokens"] = usage_n or 0
    if done and times and usage_n == req["max_tokens"]:
        rec["ok"] = True
    else:
        rec["short"] = True
        rec["error"] = (f"short: done={done} usage={usage_n} events={len(times)} "
                        f"of {req['max_tokens']}")
    return rec


def position_values(lp: dict) -> list:
    """Per position of one chunk's ``logprobs``, the values the wire gave,
    largest first: the chosen token's and the alternatives'.  VALUES, because
    the wire names a token by its glyph and glyphs collide: ``top_logprobs``
    is a dict keyed by glyph, so of the ids that share a glyph (with the byte
    tokenizer every id from 128 up) it keeps the last, the lowest of them."""
    chosen = lp.get("token_logprobs") or []
    tops = lp.get("top_logprobs") or [{}] * len(chosen)
    return [sorted({c, *(top or {}).values()}, reverse=True) for c, top in zip(chosen, tops)]


def values_gap(a: list, b: list):
    """The widest gap between two positions' values of equal rank.  None:
    nothing to compare, or the two sides gave another COUNT of values (an id
    with a glyph of its own among one side's alternatives only): they are not
    the same distribution, whatever their ends say."""
    if not a or len(a) != len(b):
        return None
    return max(abs(x - y) for x, y in zip(a, b))


async def run_phase(session, url: str, model: str, phase: dict, seconds: float) -> dict:
    """Offer one phase of traffic for ``seconds``, then let what is in flight
    finish (capped).  Returns the per-request records, times relative to the
    phase's start, and how late an open loop's generator ran."""
    clock0 = time.perf_counter()
    tasks, late = [], []
    reqs = phase["requests"]

    if phase["loop"] == "open":
        for due, req in zip(phase["due"], reqs):
            delay = clock0 + due - time.perf_counter()
            if delay > 0:
                await asyncio.sleep(delay)
            late.append(time.perf_counter() - clock0 - due)
            tasks.append(asyncio.ensure_future(
                stream_request(session, url, model, req, clock0, due)))
        rest = clock0 + seconds - time.perf_counter()
        if rest > 0:
            await asyncio.sleep(rest)
        runners = []
    else:
        cursor = iter(range(10**9))

        async def client() -> None:
            while True:
                now = time.perf_counter() - clock0
                if now >= seconds:
                    return
                i = next(cursor)
                req = reqs[i % len(reqs)]
                if i >= len(reqs):  # the system outran the pool: lap 1, 2, ...
                    req = traffic.renewed(req, i // len(reqs))
                t = asyncio.ensure_future(
                    stream_request(session, url, model, req, clock0, now))
                tasks.append(t)
                await asyncio.wait([t])

        runners = [asyncio.ensure_future(client()) for _ in range(phase["clients"])]
        await asyncio.sleep(seconds)
    in_flight_at_end = sum(1 for t in tasks if not t.done())
    t_end = time.perf_counter() - clock0
    pending = [t for t in tasks + runners if not t.done()]
    if pending:
        _, still = await asyncio.wait(pending, timeout=DRAIN_CAP_S)
        for t in still:
            t.cancel()
        if still:
            await asyncio.wait(still)
    # A request cut at the drain cap was sent and got no full answer: it fails.
    records = [
        blank_record({"prompt_len": 0, "max_tokens": 0}, 0.0, 0.0, "cancelled at the drain cap")
        if t.cancelled() else t.result()
        for t in tasks
    ]
    return {
        "records": records,
        "t_end": t_end,
        "t_drained": time.perf_counter() - clock0,
        "in_flight_at_end": in_flight_at_end,
        "late_s": late,
        "pool": len(reqs),
        "wrapped": max(0, len(tasks) - len(reqs)),
    }


def phase_report(out: dict, seconds: float) -> dict:
    """Counts and latencies of one phase, without the per-token times."""
    recs = out["records"]
    s = stats.summarize(recs, seconds)
    late = sorted(out["late_s"])
    report = {
        "attempted": len(recs),
        "failed": sum(1 for r in recs if not r["ok"]),
        "short": sum(1 for r in recs if r["short"]),
        "errors": sorted({r["error"] for r in recs if r.get("error")})[:5],
        "n_completed": s["n_completed"], "n_tpot": s["n_tpot"],
        "ttft_s": s["ttft_s"], "tpot_s": s["tpot_s"],
        "output_tokens_in_window": s["output_tokens_in_window"],
        "output_tokens_per_s": s["output_tokens_per_s"],
        "output_tokens_total": sum(r["n_tokens"] for r in recs),
        "prompt_tokens_total": sum(r["prompt_len"] for r in recs),
        "in_flight_at_end": out["in_flight_at_end"],
        "t_drained": out["t_drained"],
        "pool": out["pool"], "wrapped": out["wrapped"],
        "generator_late_ms": {
            "n": len(late),
            "p50": late[len(late) // 2] * 1e3 if late else 0.0,
            "max": late[-1] * 1e3 if late else 0.0,
        },
        # For readers that need who was decoding when (no prompts, no tokens).
        "requests": [
            {k: r[k] for k in ("ok", "t_ref", "t_first", "t_last", "n_tokens", "prompt_len")}
            for r in recs
        ],
    }
    return report


async def scrape(session, url: str) -> str:
    async with session.get(f"{url}/metrics") as resp:
        return await resp.text()


async def probe(session, url: str, model: str, job: dict, flip_last: bool = False) -> dict:
    """The probe prompt alone on the server, greedy, with the wire's
    log-probabilities.  ``flip_last`` changes its last token (the control)."""
    p = job["probe"]
    req = traffic.build_requests(
        {"prompt": {"dist": "fixed", "value": p["prompt_len"]},
         "output": {"dist": "fixed", "value": p["max_tokens"]}},
        1, p["seed"], job["vocab"], salt=7)[0]
    if flip_last:
        req["prompt"][-1] ^= 1
    rec = await stream_request(session, url, model, req, time.perf_counter(), 0.0,
                               logprobs=PROBE_TOP)
    return {"ok": rec["ok"], "text": rec["text"], "error": rec["error"],
            "seconds": rec["t_last"], "values": rec["logprobs"]}


def runs_gap(a: dict, b: dict):
    """The widest gap between two probe runs over every generated position;
    None where a run failed or reported no values, or where the two differ in
    their count of positions or of values at a position."""
    va, vb = a["values"], b["values"]
    if not (a["ok"] and b["ok"] and va) or len(va) != len(vb):
        return None
    gaps = [values_gap(x, y) for x, y in zip(va, vb)]
    return None if None in gaps else max(gaps)


def probe_verdict(before: list, after: list, limits: dict, control: dict | None = None) -> dict:
    """``before`` and ``after`` are two runs each of the probe, on either side
    of the window: (cold, hit) and (either, hit).  The probe's hit begins on a
    prefill-chunk boundary, so the hit's one prompt step IS the cold prefill's
    last: one program over the same values, and every run decodes from the
    same pages.  Identical means, at EVERY generated position, (i) hit against
    hit, one from each side of the window, within ``hit_gap`` and (ii) cold
    against hit, before the window, within ``cold_gap``.  The first run after
    the window may find any part of the prompt still cached (a part that ends
    inside a chunk is another chunking, so another rounding): its gap is
    reported and judges nothing, as the served text does, in which one glyph
    stands for most ids."""
    runs = before + after

    def judge(cold_run: dict, hit_before: dict, hit_after: dict) -> dict:
        hit, cold = runs_gap(hit_before, hit_after), runs_gap(cold_run, hit_before)
        ok = (all(r["ok"] for r in runs) and hit is not None and cold is not None
              and hit <= limits["hit_gap"] and cold <= limits["cold_gap"])
        return {"identical": ok, "hit_gap": hit, "cold_gap": cold}

    out = judge(before[0], before[1], after[1])
    out.update({
        "limits": limits,
        "after_first_gap": runs_gap(after[0], after[1]),
        "positions": [len(r["values"]) for r in runs],
        "values_per_position": sorted({len(v) for v in before[1]["values"]}),
        "text_identical": all(r["ok"] for r in runs) and len({r["text"] for r in runs}) == 1,
        "seconds": [r["seconds"] for r in runs],
        "errors": [r["error"] for r in runs if r["error"]],
    })
    if control is not None:
        # The hit with its last prompt token changed, in the place of the hit
        # before the window: both comparisons read it.
        out["control"] = dict(judge(before[0], control, after[1]), ok=control["ok"])
    return out


async def run_cell(job: dict, session) -> dict:
    url, model, mix, params = job["url"], job["model"], job["mix"], job["params"]
    seed, seconds, vocab = job["seed"], job["seconds"], job["vocab"]
    warm_s = job["warm_seconds"]
    warm = traffic.build_phase(mix, params, seed, warm_s, vocab, salt=101,
                               max_output=job["warm_max_output"])
    window = traffic.build_phase(mix, params, seed, seconds, vocab, salt=202)
    say("warm_start")
    warm_out = phase_report(await run_phase(session, url, model, warm, warm_s), warm_s)
    before = [await probe(session, url, model, job) for _ in range(2)]  # cold, then a hit
    metrics_before = await scrape(session, url)
    say("window_start")
    out = await run_phase(session, url, model, window, seconds)
    say("window_end", t_end=out["t_end"])
    metrics_after = await scrape(session, url)
    after = [await probe(session, url, model, job) for _ in range(2)]  # either, then a hit
    control = (await probe(session, url, model, job, flip_last=True)
               if job["probe"].get("control") else None)
    report = phase_report(out, seconds)
    for k in ("ttft_s", "tpot_s", "requests"):
        warm_out.pop(k)
    return {
        "window": report, "warm": warm_out,
        "probe": probe_verdict(before, after, job["probe"]["limits"], control),
        "metrics_before": metrics_before, "metrics_after": metrics_after,
    }


async def run_sweep(job: dict, session) -> dict:
    """Rising fixed rates on one server, ``seconds`` each; stops at the first
    rate whose backlog grows (see sweep.py for the rule)."""
    url, model, mix = job["url"], job["model"], job["mix"]
    sw, seconds, steps = job["sweep"], job["seconds"], []
    warm = traffic.build_phase(mix, {"rate_rps": sw["rates"][0]}, job["seed"], 5.0,
                               job["vocab"], salt=101, max_output=job["warm_max_output"])
    await run_phase(session, url, model, warm, 5.0)
    for rate in sw["rates"]:
        # The window's own schedule (salt 202), so every step offers the
        # arrival process the cell runs, at its rate.
        phase = traffic.build_phase(mix, {"rate_rps": rate}, job["seed"], seconds,
                                    job["vocab"], salt=202)
        say("sweep_step", rate_rps=rate)
        rep = phase_report(await run_phase(session, url, model, phase, seconds), seconds)
        half = [r for r in rep["requests"] if r["ok"] and r["t_ref"] >= seconds / 2]
        first = [r for r in rep["requests"] if r["ok"] and r["t_ref"] < seconds / 2]
        step = {
            "rate_rps": rate, "attempted": rep["attempted"], "failed": rep["failed"],
            "n_completed": rep["n_completed"],
            "in_flight_at_end": rep["in_flight_at_end"],
            "drain_s": rep["t_drained"] - seconds,
            "output_tokens_per_s": rep["output_tokens_in_window"] / seconds,
            "generator_late_ms": rep["generator_late_ms"],
            "ttft_ms_mean_first_half": _mean_ttft_ms(first),
            "ttft_ms_mean_second_half": _mean_ttft_ms(half),
        }
        for name, q, key in (("ttft_ms_p50", 50, "ttft_s"), ("ttft_ms_p90", 90, "ttft_s"),
                             ("tpot_ms_p90", 90, "tpot_s")):
            try:
                step[name] = stats.percentile(rep[key], q, min_beyond=0) * 1e3
            except stats.TooFewSamples:
                step[name] = None
        steps.append(step)
        grows = (step["in_flight_at_end"] > sw["max_in_flight"]
                 or (step["ttft_ms_mean_first_half"] or 0) * sw["ttft_growth"]
                 < (step["ttft_ms_mean_second_half"] or 0)
                 and (step["ttft_ms_mean_second_half"] or 0) > sw["ttft_floor_ms"])
        step["backlog_grows"] = bool(grows)
        if grows or step["failed"]:
            break
    return {"steps": steps}


def _mean_ttft_ms(reqs: list):
    vals = [(r["t_first"] - r["t_ref"]) * 1e3 for r in reqs if r["t_first"] is not None]
    return sum(vals) / len(vals) if vals else None


async def amain(job: dict) -> dict:
    timeout = aiohttp.ClientTimeout(total=None, sock_connect=30, sock_read=DRAIN_CAP_S)
    conn = aiohttp.TCPConnector(limit=0)
    async with aiohttp.ClientSession(timeout=timeout, connector=conn) as session:
        if job.get("mode") == "sweep":
            return await run_sweep(job, session)
        return await run_cell(job, session)


def main() -> int:
    with open(sys.argv[1]) as f:
        job = json.load(f)
    result = asyncio.run(amain(job))
    tmp = job["results_path"] + ".tmp"
    with open(tmp, "w") as f:
        json.dump(result, f)
    os.replace(tmp, job["results_path"])
    say("done")
    return 0


if __name__ == "__main__":
    sys.exit(main())
