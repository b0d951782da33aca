#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that dynamo-tpu still starts on the chip.

Default run (one TPU v5e chip; what the driver runs):

1. kernel parity ON THE CHIP, in a child process that exits before the server
   starts: every attention kernel ``auto`` resolves to on a TPU (fused decode,
   chunked prefill; int8 and bf16 pages; qwen2.5-7b head geometry; a traced
   ``kv_scale``) against the ``xla`` oracle of ops/ragged_attention.py;
2. serve: ``python -m dynamo_tpu.cli run in=http out=tpu --arch qwen2.5-7b`` at
   full published width and depth (28 layers), int8 weights, int8 KV pages,
   seeded random weights, and a handful of requests over HTTP;
3. what the server says about itself (``/metrics`` + its start-up line);
4. stop the server, check it exited cleanly, print the contract's last line.

``--chips 4`` (the builder runs it; the driver has one chip) runs ONLY the
sharded path and what it is compared with: llama-3.1-8b int8/int8 full depth
at tp=1 on one chip (a child that exits), then tp=4 over four chips in one
process, compared on logprobs; then README's own bf16 ``--tp 4`` CLI line
answers one request.

``--rehearse-cpu`` walks the same control flow at debug-tiny size on the CPU
backend (Pallas interpreter) — a rehearsal, never a result: its last line
says ``"ok": false``.

The parent process never imports JAX: a chip belongs to one process at a
time, and every phase that needs it runs in a child that exits.  Every phase
prints one JSON line with its seconds; any failure ends the run non-zero.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(HERE, "chiprun_out", "chip_smoke")  # git-ignored

# --------------------------------------------------------------- tolerances
# Kernel parity: |kernel - oracle| / max|oracle| per case.  Both sides
# accumulate in f32 and round the result to bf16 (2^-8 relative = 0.4%); the
# kernel and the oracle order the online-softmax sums differently, and the
# chip's f32 dots may run as bf16 passes.  The oracle runs under
# default_matmul_precision("highest") so the bound measures the KERNEL.
PARITY_TOL = 2e-2
# tp=4 vs tp=1 logprobs (absolute, nats), on the top-5 tokens both runs name
# at a position both runs reached with the same tokens (always the prompt's
# last position).  The sharding rules themselves are exact: on the CPU
# backend tp=4 equals tp=1 BIT FOR BIT at 8 layers of llama-3.1-8b width, bf16
# activations, int8/int8 (PR 21).  On the chip the two programs are compiled
# differently (tp=1 fuses wqkv/w_gateup; XLA:TPU picks other fusions and
# summation orders), which seeds 1-ulp differences — and a W8A8 pipeline
# amplifies a seed: one flipped bf16 rounding moves int8 activation roundings
# (1 LSB = 0.8% of the row max) in the next matmul, until the difference sits
# at the quantisation-noise floor, which then grows like sqrt(depth).
# Measured on four v5e chips (PR 21): max 0.123 / median 0.035 nats at one
# layer (all 32 greedy tokens agreeing), max 0.637 / median 0.213 at 32
# layers — a ratio of 5.2 against sqrt(32) = 5.7 — where the top logprobs sit
# 0.1 apart, yet every top-5 pair still overlapped.  So:
# - SHALLOW (one layer, full width: every sharded op kind once, little depth
#   to amplify in): a tight bound, twice the measured maximum;
# - FULL DEPTH (32 layers): the top-5 sets must overlap at every position (a
#   wrong shard — heads permuted, a scale misplaced — decorrelates the logits
#   and two top-5 sets out of 128k tokens are then disjoint), and the
#   difference on the common tokens stays within a few noise floors.
TP_SHALLOW_TOL = 0.25
TP_FULL_TOL = 1.5
# Per-device peak over the per-device share of weights + KV pages: what
# "activations" may add under tp=4 (step temporaries, sampler buffers).  The
# sharded initialiser itself needs no temporaries: its memory_analysis()
# for a described v5e:2x2 says 0.00 GB per device (PR 21).
TP_PEAK_ALLOWANCE = 1 << 30

SERVE = {  # the one-chip deployment
    "arch": "qwen2.5-7b", "layers": 28, "dtype": "bfloat16",
    "block_size": 16, "num_blocks": 12288, "max_model_len": 4096,
    "max_batch": 8, "prefill_chunk": 1024, "decode_steps": 4,
    "short_prompt": 511, "long_prompt": 3000, "max_tokens": 64,
}
SERVE_REHEARSAL = {
    "arch": "debug-tiny", "layers": 2, "dtype": "float32",
    "block_size": 16, "num_blocks": 256, "max_model_len": 512,
    "max_batch": 8, "prefill_chunk": 128, "decode_steps": 4,
    "short_prompt": 63, "long_prompt": 300, "max_tokens": 16,
}
TP = {  # the four-chip comparison
    "model": "llama-3.1-8b", "block_size": 16, "num_blocks": 4096,
    "max_batch": 8, "max_model_len": 1024, "prefill_chunk": 256,
    "dtype": "bfloat16", "prompt_len": 200, "max_tokens": 4, "prompts": 8,
    "shallow_layers": 1,
}
TP_REHEARSAL = dict(  # debug-tiny widened to 4 KV heads (see _tp_engine)
    TP, model="debug-tiny-kv4", num_blocks=128, max_model_len=256,
    prefill_chunk=64, dtype="float32", prompt_len=40, shallow_layers=1,
)


def emit(phase: str, t0: float, **fields) -> None:
    print(
        json.dumps({"phase": phase, "seconds": round(time.time() - t0, 2), **fields}),
        flush=True,
    )


def fail(msg: str) -> "NoReturn":  # noqa: F821
    print(f"chip_smoke.py: FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


# ====================================================================== parent
def child_env(rehearse: bool, devices: int = 1) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = HERE + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("TPU_LOG_DIR", "disabled")
    if rehearse:
        # The CPU rehearsal is ASKED for, here and nowhere else.
        env["JAX_PLATFORMS"] = "cpu"
        env["DYN_PALLAS_INTERPRET"] = "1"
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    return env


def run_child(name: str, rehearse: bool, *extra: str, devices: int = 1,
              must: bool = True):
    """Run one JAX-touching phase in a child; its LAST stdout line is its
    JSON result.  A non-zero exit ends the run (``must=False``: returns
    None instead, for a caller that has more to show before it fails)."""
    cmd = [sys.executable, os.path.abspath(__file__), "--child", name, *extra]
    if rehearse:
        cmd.append("--rehearse-cpu")
    p = subprocess.run(
        cmd, env=child_env(rehearse, devices), cwd=HERE,
        stdout=subprocess.PIPE, text=True,
    )
    lines = [l for l in p.stdout.splitlines() if l.strip()]
    for l in lines[:-1]:
        print(l, flush=True)
    if p.returncode != 0:
        for l in lines[-1:]:
            print(l, flush=True)
        if not must:
            return None
        fail(f"phase {name!r} exited {p.returncode}")
    return json.loads(lines[-1])


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http(port: int, path: str, body=None, timeout: float = 600.0):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=None if body is None else json.dumps(body).encode(),
        headers={"content-type": "application/json"},
    )
    return urllib.request.urlopen(req, timeout=timeout)


def complete(port: int, model: str, prompt: str, max_tokens: int, *,
             stream: bool = False, temperature: float = 0.0, seed=None) -> dict:
    """One /v1/completions call → {status, text, completion_tokens}."""
    body = {
        "model": model, "prompt": prompt, "max_tokens": max_tokens,
        "temperature": temperature, "stream": stream,
        "nvext": {"ignore_eos": True},
    }
    if seed is not None:
        body["seed"] = seed
    with http(port, "/v1/completions", body) as r:
        status = r.status
        if not stream:
            d = json.loads(r.read())
            return {
                "status": status,
                "text": d["choices"][0]["text"],
                "completion_tokens": d["usage"]["completion_tokens"],
            }
        text, usage = [], None
        for raw in r:
            line = raw.decode().strip()
            if not line.startswith("data:"):
                continue
            data = line[5:].strip()
            if data == "[DONE]":
                break
            chunk = json.loads(data)
            for c in chunk.get("choices", []):
                text.append(c.get("text") or "")
            usage = chunk.get("usage") or usage
        return {
            "status": status,
            "text": "".join(text),
            "completion_tokens": (usage or {}).get("completion_tokens"),
        }


class Server:
    """The CLI server as a child process (its log under chiprun_out/)."""

    def __init__(self, argv, rehearse: bool, log_name: str, devices: int = 1):
        os.makedirs(OUT, exist_ok=True)
        self.port = free_port()
        self.log_path = os.path.join(OUT, log_name)
        self.log = open(self.log_path, "w")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "dynamo_tpu.cli", "run", "in=http", "out=tpu",
             *argv, "--host", "127.0.0.1", "--port", str(self.port)],
            env=child_env(rehearse, devices), cwd=HERE,
            stdout=self.log, stderr=subprocess.STDOUT,
        )

    def wait_ready(self, timeout: float) -> None:
        t_end = time.time() + timeout
        while time.time() < t_end:
            if self.proc.poll() is not None:
                self.dump()
                fail(f"server exited {self.proc.returncode} before readiness")
            try:
                with http(self.port, "/health", timeout=2.0) as r:
                    if r.status == 200:
                        return
            except (urllib.error.URLError, OSError):
                time.sleep(1.0)
        self.kill()
        self.dump()
        fail(f"server not ready after {timeout:.0f}s")

    def engine_line(self) -> dict:
        with open(self.log_path) as f:
            for line in f:
                if line.startswith("engine {"):
                    return json.loads(line[len("engine "):])
        fail("server log has no 'engine {...}' line")

    def metrics(self) -> str:
        with http(self.port, "/metrics", timeout=60.0) as r:
            return r.read().decode()

    def stop(self) -> int:
        self.proc.send_signal(signal.SIGTERM)
        try:
            rc = self.proc.wait(timeout=120)
        except subprocess.TimeoutExpired:
            self.kill()
            fail("server did not exit within 120s of SIGTERM")
        self.log.close()
        return rc

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()

    def dump(self, n: int = 60) -> None:
        self.log.flush()
        with open(self.log_path) as f:
            tail = f.readlines()[-n:]
        sys.stderr.write("".join(tail))


def gauge(text: str, name: str, labels: str = "") -> float:
    m = re.search(
        rf"^{re.escape(name)}(?:\{{[^}}]*{re.escape(labels)}[^}}]*\}})? (\S+)$",
        text, re.M,
    )
    if m is None:
        fail(f"/metrics has no {name} {labels}")
    return float(m.group(1))


def info_labels(text: str, name: str) -> dict:
    m = re.search(rf"^{re.escape(name)}\{{(.*)\}} 1$", text, re.M)
    if m is None:
        fail(f"/metrics has no {name}")
    return dict(re.findall(r'(\w+)="((?:[^"\\]|\\.)*)"', m.group(1)))


def compiled_programs(text: str) -> dict:
    return {
        k: int(float(v))
        for k, v in re.findall(
            r'^dynamo_tpu_engine_compiled_programs\{fn="(\w+)"\} (\S+)$', text, re.M
        )
    }


def build_native() -> None:
    """native/build/ is git-ignored: build the hasher here, from source
    (make rebuilds when a source is newer), never use one as found."""
    t0 = time.time()
    p = subprocess.run(
        ["make", "-C", os.path.join(HERE, "native")],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    if p.returncode != 0:
        sys.stderr.write(p.stdout)
        fail("make -C native failed")
    emit("native_build", t0, lib="native/build/libdyn_native.so")


def prompt_of(n: int, tag: str) -> str:
    """n ASCII chars (the byte tokenizer: n tokens + BOS), distinct per tag
    from the first character on so requests share no cached prefix."""
    words = f"{tag} the quick brown fox jumps over the lazy dog; "
    return (words * (n // len(words) + 1))[:n]


def phase_serve(rehearse: bool) -> dict:
    cfg = SERVE_REHEARSAL if rehearse else SERVE
    model = cfg["arch"]
    argv = [
        "--arch", cfg["arch"], "--model", model, "--dtype", cfg["dtype"],
        "--weight-quant", "int8", "--kv-cache-dtype", "int8", "--kv-scale", "auto",
        "--max-model-len", str(cfg["max_model_len"]),
        "--num-blocks", str(cfg["num_blocks"]), "--block-size", str(cfg["block_size"]),
        "--max-batch", str(cfg["max_batch"]), "--prefill-chunk", str(cfg["prefill_chunk"]),
        "--decode-steps", str(cfg["decode_steps"]),
    ]
    t0 = time.time()
    srv = Server(argv, rehearse, "server.log")
    try:
        # Cold, ready came after 651-662 s on the chip (PR 21); the whole
        # smoke has 1200 s, of which parity takes about 55 and the
        # requests about 10.
        srv.wait_ready(1050.0)
        emit("serve_ready", t0, argv=" ".join(argv))
        before = srv.metrics()

        # --- four concurrent ~512-token prompts, 64 tokens out, 2 streamed
        t1 = time.time()
        n_out = cfg["max_tokens"]
        results = [None] * 4

        def one(i: int) -> None:
            results[i] = complete(
                srv.port, model, prompt_of(cfg["short_prompt"], f"req{i}"),
                n_out, stream=(i % 2 == 0),
            )

        threads = [threading.Thread(target=one, args=(i,)) for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for i, r in enumerate(results):
            if r is None or r["status"] != 200 or r["completion_tokens"] != n_out:
                fail(f"concurrent request {i}: {r}")
        emit("serve_concurrent", t1, requests=4, streamed=2,
             prompt_tokens=cfg["short_prompt"] + 1, tokens_each=n_out)

        # --- one ~3000-token prompt twice at temperature 0: several prefill
        # chunks against a growing paged prefix; the repeat must be
        # byte-identical and served from the prefix cache.
        t1 = time.time()
        long_p = prompt_of(cfg["long_prompt"], "long")
        hit0 = gauge(srv.metrics(), "dynamo_tpu_kv_tier_prefix_hit_rate")
        a = complete(srv.port, model, long_p, n_out)
        b = complete(srv.port, model, long_p, n_out)
        hit1 = gauge(srv.metrics(), "dynamo_tpu_kv_tier_prefix_hit_rate")
        for r in (a, b):
            if r["status"] != 200 or r["completion_tokens"] != n_out:
                fail(f"long request: {r}")
        if a["text"] != b["text"]:
            fail("long prompt repeated at temperature 0 is not byte-identical")
        if not hit1 > hit0:
            fail(f"no prefix-cache hit on the repeat: hit rate {hit0} -> {hit1}")
        emit("serve_long_prompt", t1, prompt_tokens=cfg["long_prompt"] + 1,
             prefill_chunks=-(-(cfg["long_prompt"] + 1) // cfg["prefill_chunk"]),
             byte_identical=True, prefix_hit_rate=[hit0, hit1])

        # --- one seeded sampled request, repeated with the same seed
        t1 = time.time()
        sp = prompt_of(cfg["short_prompt"], "seeded")
        c = complete(srv.port, model, sp, n_out, temperature=0.8, seed=1234)
        d = complete(srv.port, model, sp, n_out, temperature=0.8, seed=1234)
        for r in (c, d):
            if r["status"] != 200 or r["completion_tokens"] != n_out:
                fail(f"seeded request: {r}")
        if c["text"] != d["text"]:
            fail("seeded sampled request is not reproducible")
        emit("serve_seeded", t1, reproducible=True)
        requests_wall = time.time() - t0

        # --- phase 3: what the server says about itself
        t1 = time.time()
        after = srv.metrics()
        info = info_labels(after, "dynamo_tpu_engine_info")
        line = srv.engine_line()
        dk = info_labels(after, "dynamo_tpu_engine_dispatch_decode_kernel_info")["kernel"]
        pk = info_labels(after, "dynamo_tpu_engine_dispatch_prefill_kernel_info")["kernel"]
        cc0, cc1 = compiled_programs(before), compiled_programs(after)
        report = {
            "jax": info["jax"], "libtpu": info["libtpu"],
            "platform": info["platform"], "device_kind": info["device_kind"],
            "device_count": int(info["device_count"]),
            "model": info["model"], "num_layers": int(info["num_layers"]),
            "weight_quant": info["weight_quant"], "cache_dtype": info["cache_dtype"],
            "attn_impl": info["attn_impl"], "decode_kernel": dk, "prefill_kernel": pk,
            "hasher": info["hasher"],
            "warmup_seconds": gauge(after, "dynamo_tpu_engine_warmup_seconds"),
            "compiled_programs_before": cc0, "compiled_programs_after": cc1,
            "compile_cache_dir": info["compile_cache_dir"],
            "compile_cache_entries": int(gauge(after, "dynamo_tpu_engine_compile_cache_entries")),
            "compile_cache_hits": int(gauge(after, "dynamo_tpu_engine_compile_cache_hits")),
            "compile_cache_misses": int(gauge(after, "dynamo_tpu_engine_compile_cache_misses")),
            "hbm_bytes_in_use": int(gauge(after, "dynamo_tpu_engine_hbm_bytes_in_use", 'device="0"')),
            "hbm_bytes_limit": int(gauge(after, "dynamo_tpu_engine_hbm_bytes_limit", 'device="0"')),
            "tokens_generated": 8 * n_out,
            "prompt_tokens_computed": int(gauge(after, "dynamo_tpu_prefill_tokens_total")),
            "wall_seconds_since_start": round(requests_wall, 2),
        }
        emit("server_report", t1, **report)
        want = (
            {"platform": "cpu", "attn_impl": "xla", "decode_kernel": "stock",
             "prefill_kernel": "stock"}
            if rehearse else
            # What `auto` resolves to on a TPU (ops/ragged_attention.py).
            {"platform": "tpu", "attn_impl": "tpu", "decode_kernel": "pallas_fused",
             "prefill_kernel": "pallas"}
        )
        for k, v in want.items():
            if report[k] != v:
                fail(f"server reports {k}={report[k]!r}, expected {v!r}")
        if (report["num_layers"], report["weight_quant"], report["cache_dtype"]) != (
            cfg["layers"], "int8", "int8"
        ):
            fail(f"server is not the full-depth int8/int8 model: {report}")
        if line["device_kind"] != report["device_kind"]:
            fail("start-up line and /metrics disagree on device_kind")
        if cc0 != cc1 or not cc0 or min(cc0.values()) < 0:
            fail(f"programs compiled after warmup: {cc0} -> {cc1}")
        if report["warmup_seconds"] <= 0:
            fail("server did not warm up before serving")
        if report["hasher"] != "native":
            fail("the Python hasher served although native/ was built")
    except BaseException:
        srv.kill()
        raise
    # --- phase 4: stop, exited cleanly
    t1 = time.time()
    rc = srv.stop()
    if rc != 0:
        srv.dump()
        fail(f"server exited {rc} on SIGTERM")
    emit("serve_stopped", t1, exit_code=rc)
    return report


def run_one_chip(rehearse: bool) -> dict:
    build_native()
    dev = run_child("parity", rehearse)
    run_child("parity-dsv32", rehearse)
    report = phase_serve(rehearse)
    for k_dev, k_rep in (("platform", "platform"), ("kind", "device_kind"),
                         ("count", "device_count")):
        if dev[k_dev] != report[k_rep]:
            fail(f"parity child and server saw different devices: {dev} / {report}")
    return dev


def run_four_chips(rehearse: bool) -> dict:
    build_native()
    os.makedirs(OUT, exist_ok=True)
    ref_path = os.path.join(OUT, "tp1_reference.json")
    run_child("tp1", rehearse, "--ref", ref_path, devices=4)
    # The README phase runs even when the comparison failed (its lines are
    # worth the chips already held); the run still ends non-zero.
    dev = run_child("tp4", rehearse, "--ref", ref_path, devices=4, must=False)
    # README's own quick-start line: bf16, --tp 4, through the CLI.  With
    # --no-warmup: four chips cost four times a second, and the request
    # compiles the programs it needs (the one-chip run proves warmup).
    t0 = time.time()
    argv = ["--arch", TP["model"], "--model", "m", "--tp", "4", "--no-warmup"]
    if rehearse:  # debug-tiny has two KV heads
        argv = ["--arch", "debug-tiny", "--model", "m", "--tp", "2", "--no-warmup",
                "--dtype", "float32", "--max-model-len", "256", "--num-blocks", "64"]
    srv = Server(argv, rehearse, "server_tp4.log", devices=4)
    try:
        srv.wait_ready(900.0)
        r = complete(srv.port, "m", prompt_of(100, "readme"), 8)
        if r["status"] != 200 or r["completion_tokens"] != 8:
            fail(f"README --tp 4 line: {r}")
        line = srv.engine_line()
    except BaseException:
        srv.kill()
        raise
    rc = srv.stop()
    if rc != 0:
        srv.dump()
        fail(f"--tp 4 server exited {rc} on SIGTERM")
    emit("readme_tp4_cli", t0, argv=" ".join(argv), completion_tokens=8,
         device_count=line["device_count"], weight_quant=line["weight_quant"],
         hbm_bytes_in_use=line["hbm_bytes_in_use"], exit_code=rc)
    if dev is None:
        fail("phase 'tp4' failed (see above)")
    return dev


# ==================================================================== children
def child_device(rehearse: bool) -> dict:
    """First thing every child does: name the device, refuse a CPU."""
    import jax

    d = jax.devices()
    dev = {"platform": d[0].platform, "kind": d[0].device_kind, "count": len(d)}
    if rehearse:
        if dev["platform"] != "cpu":
            fail("--rehearse-cpu is for the CPU backend")
    elif dev["platform"] != "tpu":
        fail(
            f"JAX found no TPU (platform={dev['platform']!r}, "
            f"kind={dev['kind']!r}): this smoke measures the chip and does "
            "not fall back to a CPU.  `--rehearse-cpu` walks the control "
            "flow at debug-tiny size without claiming a result."
        )
    return dev


def child_parity(rehearse: bool) -> None:
    t0 = time.time()
    dev = child_device(rehearse)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.ops.decode_attention import fused_decode_attention
    from dynamo_tpu.ops.prefill_attention import fused_prefill_attention
    from dynamo_tpu.ops.ragged_attention import (
        ragged_attention, ragged_decode_attention,
        resolve_decode_kernel, resolve_prefill_kernel,
    )

    if not rehearse:
        got = (resolve_decode_kernel("auto"), resolve_prefill_kernel("auto"))
        if got != ("pallas_fused", "pallas"):
            fail(f"auto resolves to {got} on this TPU backend")
    emit("device", t0, **dev, jax=jax.__version__)

    H, KV, D, ps = 28, 4, 128, 16  # qwen2.5-7b heads; the engine's page size
    rng = np.random.default_rng(0)
    SCALE = 0.02
    if rehearse:  # the interpreter is slow: same geometry, short contexts
        dec_lens, PPd, P = [1, 17, 100, 256, 33, 0, 0, 0], 16, 160
        pre_rows, PPp = [(20, 60), (7, 7), (1, 40), (30, 100)], 8
    else:
        dec_lens = [1, 17, 500, 4096, 3000, 16, 2049, 777, 4095, 64, 1000, 31, 2, 0, 0, 0]
        PPd, P = 256, 4096
        # (q_len, kv_len): a chunk against a paged prefix, a whole short
        # prompt, a decode row riding a mixed step, a long chunk.
        pre_rows, PPp = [(100, 700), (37, 37), (1, 300), (118, 1000)], 64

    def pages_of(dtype):
        if jnp.dtype(dtype).itemsize == 1:
            return jnp.asarray(rng.integers(-127, 128, (P, ps, 2 * KV, D)), dtype)
        return jnp.asarray(rng.normal(0, 1.0, (P, ps, 2 * KV, D)), dtype)

    def tables(S, PP):
        return jnp.asarray(
            rng.permutation(P)[: S * PP].reshape(S, PP) if S * PP <= P
            else rng.integers(0, P, (S, PP)), jnp.int32,
        )

    def check(name, fn_kernel, fn_oracle, args, scale):
        t1 = time.time()
        traced = jnp.asarray(1.0 if scale is None else scale, jnp.float32)
        compiled = jax.jit(fn_kernel).lower(*args, traced).compile()
        if not rehearse and "tpu_custom_call" not in compiled.as_text():
            fail(f"{name}: no tpu_custom_call in the compiled program "
                 "(the kernel was not compiled for the chip)")
        out = np.asarray(compiled(*args, traced), np.float32)
        with jax.default_matmul_precision("highest"):
            ref = np.asarray(jax.jit(fn_oracle)(*args), np.float32)
        if out.shape != ref.shape or not np.isfinite(out).all():
            fail(f"{name}: shape {out.shape} vs {ref.shape} / non-finite values")
        ref_max = float(np.max(np.abs(ref)))
        err = float(np.max(np.abs(out - ref)) / (ref_max + 1e-30))
        emit("parity_" + name, t1, rel_err=err, tol=PARITY_TOL, ref_max=ref_max,
             shape=list(out.shape), compiled=not rehearse)
        if ref_max == 0.0 or not err <= PARITY_TOL:
            fail(f"{name}: rel_err {err} > {PARITY_TOL}")

    for dtype in ("int8", "bfloat16"):
        scale = SCALE if dtype == "int8" else None
        pages = pages_of(dtype)
        # ---- decode: one token per row
        S = len(dec_lens)
        q = jnp.asarray(rng.normal(0, 1, (S, H, D)), jnp.bfloat16)
        dargs = (q, pages, jnp.asarray(dec_lens, jnp.int32), tables(S, PPd),
                 jnp.asarray([sum(1 for n in dec_lens if n)], jnp.int32))
        check(
            f"decode_{dtype}",
            lambda q, pg, kl, pi, ns, sc: fused_decode_attention(
                q, pg, kl, pi, ns, sm_scale=D ** -0.5,
                kv_scale=None if scale is None else sc),
            lambda q, pg, kl, pi, ns: ragged_decode_attention(
                q, pg, kl, pi, ns, sm_scale=D ** -0.5, kv_scale=scale,
                kernel="xla"),
            dargs, scale,
        )
        # ---- prefill: ragged chunks against paged prefixes (+ padding)
        S = len(pre_rows) + 2
        qlens = [a for a, _ in pre_rows] + [0, 0]
        T = 1 << (sum(qlens) - 1).bit_length()  # a token bucket, like the engine
        q = jnp.asarray(rng.normal(0, 1, (T, H, D)), jnp.bfloat16)
        pargs = (q, pages,
                 jnp.asarray([b for _, b in pre_rows] + [0, 0], jnp.int32),
                 tables(S, PPp),
                 jnp.asarray(np.concatenate([[0], np.cumsum(qlens)]), jnp.int32),
                 jnp.asarray([len(pre_rows)], jnp.int32))
        check(
            f"prefill_{dtype}",
            lambda q, pg, kl, pi, cu, ns, sc: fused_prefill_attention(
                q, pg, kl, pi, cu, ns, sm_scale=D ** -0.5,
                kv_scale=None if scale is None else sc),
            lambda q, pg, kl, pi, cu, ns: ragged_attention(
                q, pg, kl, pi, cu, ns, sm_scale=D ** -0.5, kv_scale=scale,
                prefill_kernel="xla"),
            pargs, scale,
        )
    print(json.dumps(dev), flush=True)


# --------------------------------------------------- DeepSeek-V3.2-Exp parity
# `--child parity-dsv32`: chipbench/configs/deepseek-v3.2-exp-6l-ep16.json at
# its published widths under longdoc-shared's shapes, against the float32
# reference (dynamo_tpu/models/reference/deepseek_v32.py) on the same
# dequantised weights.
#
# What runs.  Row A is the compared request: a 7680-token document that another
# request left in sealed pages, a 512-token question, 32 decoded tokens.  Rows
# B (the same document's pages, another question) and C (a short prompt: fewer
# positions than index_topk) are live beside it, so that every program runs
# with several rows.  Every step goes through TWO programs on the same batch:
# the ENGINE'S OWN jitted program (`engine._step_fn`: the lax.scan prefill with
# decode rows riding; `engine._multi_fn`: four fused decode steps, the sampled
# token fed back on the device) which decides every token and leaves its
# entries in the pages, and the check's own jit of the same forward with
# `return_selection`, teacher-forced on the engine's tokens, which gives what
# the engine's programs do not return: whole logits and S_t.  Three comparisons:
#   (1) the engine's top-20 log-probabilities against the check's logits
#       (`engine_link`): the two programs are the same model;
#   (2) the check's logits at A's 33 positions against one float32 pass over
#       the 8224 tokens, with the reference's own S_t and with the system's
#       S_t forced, as the maximum and as the root-mean-square over all logits;
#   (3) the share of the reference's S_t that the system also chose.
# Controls, all teacher-forced on the SAME tokens: every page rounded to int8
# (the nearest precision below the stated bfloat16; must fail), and the
# weights dequantised to bfloat16 with no activation rounding (above the
# stated W8A8: shows how much of the error is W8A8's).  Limits and the
# readings they come from: PERF.md section 6.
DSV32 = {"config": "chipbench/configs/deepseek-v3.2-exp-6l-ep16.json", "reference": "deepseek_v32",
         "control": "int8_pages",  # the control that must fail a limit
         "doc": 7680, "prompt": 8192, "decode": 32, "short": 96, "num_blocks": 2048,
         "q_block": 256}
_PARITY_REHEARSAL = dict(doc=448, prompt=512, decode=8, short=12, num_blocks=256, q_block=128)
DSV32_REHEARSAL = dict(DSV32, **_PARITY_REHEARSAL)
# Readings on the chip (PR 28, seeds 29 / 30; 33 positions, context 8224, largest reference logit 8.2 / 7.9), the
# system first, then the same tokens over int8 pages:
#   rms_err_forced_selection   0.080 / 0.097   |  0.164 / 0.180   limit 0.13: between, a third of room on each side
#   engine_link                0.066 / 0.073   |  0.192 / 0.172   limit 0.11: between, half of room on each side
#   rel_err_forced_selection   0.127 / 0.183   |  0.245 / 0.326   limit 0.30: a maximum over 533k logits moves by a
#       third from seed to seed (0.101-0.192 over seven readings), so it only bounds; the two above separate
#   rel_err_own_selection      0.460 / 0.279   |  0.393 / 0.326   limit 0.65: does NOT separate: with its own S_t the
#       reference differs in 13% of the kept positions (bfloat16 selector scores against float32, the 2048th score
#       in a dense crowd under random weights) and that difference is larger than a page's rounding
#   selection_overlap_mean     0.869 / 0.879                      least 0.75 (int8 pages: read by this run)
# Where the error comes from: W8A8 rounds each activation row to 1/127 of its largest element in each of ~7
# quantised matmuls a layer, six layers along the residual; the bfloat16-weights control (no activation rounding,
# same tokens) reads what is left without it.  The two programs of comparison (1) differ by 0.07 because a scan
# and an unrolled loop fuse differently and a bfloat16 difference flips int8 roundings and near-tie selections.
DSV32_LIMITS = {
    # name of the reading: (limit, "max" = may not exceed | "min" = may not fall below)
    "rms_err_forced_selection": (0.13, "max"),
    "engine_link": (0.11, "max"),
    "rel_err_forced_selection": (0.30, "max"),
    "rel_err_own_selection": (0.65, "max"),
    "selection_overlap_mean": (0.75, "min"),
}


# `--child parity-kimi-k2`: chipbench/configs/kimi-k2-6l-ep32.json at its
# published widths under agent-shared's shapes, the same rows and programs as
# above (row A: a 12288-token context in sealed pages, a 512-token turn, 32
# decoded tokens: context 12832; B on the same pages, C short), against ONE
# float32 pass of dynamo_tpu/models/reference/kimi_k2.py (decompressed
# per-head attention with a whole causal softmax, where the system's decode is
# absorbed and its chunks blocked) on the same dequantised weights.  Without a
# selector nothing is selected: comparison (3) and the forced pass do not
# exist.  Limits and their readings: PERF.md section 6 (PR 32).
KIMI_K2 = {"config": "chipbench/configs/kimi-k2-6l-ep32.json", "reference": "kimi_k2",
           "control": "coarse_activations",
           "doc": 12288, "prompt": 12800, "decode": 32, "short": 96, "num_blocks": 1024,
           "q_block": 256}
KIMI_K2_REHEARSAL = dict(KIMI_K2, **_PARITY_REHEARSAL)
# Readings on the chip (PR 32, seeds 28 / 29 / 30; 33 positions, context 12832, largest reference logit 8.6 / 8.2 /
# 10.0), the system first, then the same tokens with W8A8's activations one step coarser (6 bits and a sign):
#   rms_err_whole_context   0.064 / 0.060 / 0.065   |  0.141 / 0.146 / 0.148   limit 0.10: between, half of room above the
#       system and a third under the control: THE limit that separates
#   engine_link             0.057 / 0.016 / 0.033   |  0.110 / 0.140 / 0.086   limit 0.08: two programs of one model (a
#       scan against an unrolled loop); it moves 3.5 times from seed to seed, so it bounds more than it separates
#   rel_err_whole_context   0.110 / 0.115 / 0.095   |  0.203 / 0.188 / 0.139   limit 0.16: a maximum over 676k logits; bounds
# Every latent page int8 (the other family's control) reads 0.074 / 0.070 / 0.064 here, inside the system's own spread:
# a query attends to 12.8k entries and their roundings average out, where the other family's selector amplifies them
# into another S_t.  The bfloat16-weights control reads 0.024 / 0.039 / 0.024: most of the system's error is W8A8's.
KIMI_K2_LIMITS = {
    "rms_err_whole_context": (0.10, "max"),
    "engine_link": (0.08, "max"),
    "rel_err_whole_context": (0.16, "max"),
}


def child_parity_dsv32(rehearse: bool) -> None:
    _parity_latent(rehearse, "dsv32", DSV32_REHEARSAL if rehearse else DSV32, DSV32_LIMITS)


def child_parity_kimi_k2(rehearse: bool) -> None:
    _parity_latent(rehearse, "kimi_k2", KIMI_K2_REHEARSAL if rehearse else KIMI_K2, KIMI_K2_LIMITS)


def _parity_latent(rehearse: bool, tag: str, par: dict, limits: dict) -> None:
    """The latent family on the chip against its float32 reference, with the
    selector (``parity-dsv32``) or without one (``parity-kimi-k2``: S_t is the
    whole context, so nothing is selected, forced or overlapped, and ONE
    reference pass serves)."""
    t0 = time.time()
    dev = child_device(rehearse)
    import importlib
    import types

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import TpuEngine
    from dynamo_tpu.models import deepseek_v32 as ds
    from dynamo_tpu.models.config import ModelConfig, register_config
    from dynamo_tpu.models.family import RaggedBatch

    ref = importlib.import_module("dynamo_tpu.models.reference." + par["reference"])
    # Another seed draws other weights and another sequence (both children).
    seed = int(os.environ.get("DSV32_PARITY_SEED", "28"))
    with open(os.path.join(HERE, par["config"])) as f:
        body = json.load(f)
    serve = dict(body["serve"])
    if rehearse:
        hf = dict(body["rehearsal"]["model"])
        serve.update(body["rehearsal"]["serve"])
    else:
        hf = {k: v for k, v in body.items() if k not in (
            "name", "source", "serve", "chips", "reduced", "assumed", "stands_for",
            "rehearsal", "notes")}
    mc = register_config(ModelConfig.from_hf_config(hf, name="parity-" + tag))
    cfg = EngineConfig(
        model=mc.name, block_size=serve["block_size"], num_blocks=par["num_blocks"],
        max_batch=serve["max_batch"], max_model_len=serve["max_model_len"],
        prefill_chunk=serve["prefill_chunk"], decode_steps=serve["decode_steps"],
        dtype=serve["dtype"], cache_dtype=serve["kv_cache_dtype"],
        weight_quant=serve["weight_quant"], seed=20260900 + seed)
    engine = TpuEngine(cfg)
    mc, fam = engine.model_config, engine.family
    selector = mc.index_topk > 0
    emit(f"{tag}_engine", t0, **dev, hbm=(jax.local_devices()[0].memory_stats() or {}).get(
        "bytes_in_use"))

    bs, S, PP, chunk = cfg.block_size, cfg.max_batch, cfg.max_blocks_per_seq, cfg.prefill_chunk
    steps = cfg.decode_steps
    n_doc, n_prompt, n_dec, n_short = par["doc"], par["prompt"], par["decode"], par["short"]
    assert n_prompt - n_doc == chunk and n_dec % steps == 0 and n_doc % chunk == 0
    T = n_prompt + n_dec
    L = mc.num_layers
    rng = np.random.default_rng(seed)
    draw = lambda n: rng.integers(16, mc.vocab_size, n).astype(np.int32)
    # The question's chunk is split so that two steps carry A with B (and C):
    # A 5/8 of a chunk then the rest, B the room A leaves in each.
    a1 = chunk * 5 // 8
    a2 = b1 = b2 = chunk - a1
    assert a2 + b2 + n_short <= chunk
    doc_pages, own_pages = n_doc // bs, PP - n_doc // bs

    def row(tokens, n_prompt_row, first_own, n_own, shared):
        table = np.zeros((PP,), np.int32)
        table[:shared] = np.arange(shared)
        table[shared:shared + n_own] = first_own + np.arange(n_own)
        toks = np.zeros((n_prompt_row + n_dec + 1,), np.int32)
        toks[:n_prompt_row] = tokens
        return types.SimpleNamespace(tokens=toks, n_prompt=n_prompt_row, table=table,
                                     limit=(shared + n_own) * bs)

    doc = draw(n_doc)
    short_pages = -(-(n_short + n_dec) // bs) + 1
    A = row(np.concatenate([doc, draw(chunk)]), n_prompt, doc_pages + 7, own_pages, doc_pages)
    B = row(np.concatenate([doc, draw(b1 + b2)]), n_doc + b1 + b2, doc_pages + 7 + own_pages,
            own_pages, doc_pages)
    C = row(draw(n_short), n_short, doc_pages + 7 + 2 * own_pages, short_pages, 0)
    rows = [A, B, C]

    fwd = jax.jit(
        lambda p, c, rb, dec: fam.forward(p, mc, rb, c, decode=dec, return_selection=True),
        static_argnums=3, donate_argnums=1)
    # Greedy rows that ask for log-probabilities: the engine's own sampling
    # state, as `_run_unified` builds it for such requests.
    samp = engine._sampling_arrays([])._replace(need_logprobs=np.asarray(True))

    def seq_of(r, upto):
        return types.SimpleNamespace(prompt=[int(t) for t in r.tokens[:upto]], output=[],
                                     block_ids=[int(x) for x in r.table], adapter_slot=-1)

    def prefill_batch(items):
        return engine._build_ragged([(seq_of(r, a + n), a, n) for r, a, n in items])

    def decode_batch(live, j):
        """One token a row at position n_prompt_row + j, as `_multi` builds it."""
        t, p, kv = (np.zeros((S,), np.int32) for _ in range(3))
        sl = np.full((S,), -1, np.int32)
        tables = np.zeros((S, PP), np.int32)
        for i, r in enumerate(live):
            pos = r.n_prompt + j
            t[i], p[i], kv[i] = r.tokens[pos], pos, pos + 1
            sl[i] = int(r.table[pos // bs]) * bs + pos % bs
            tables[i] = r.table
        return RaggedBatch(t, p, sl, kv, tables, np.arange(S + 1, dtype=np.int32),
                           np.asarray([S], np.int32))

    def mask_rows(sels, n):
        """S_t of the step's first n tokens (row A's) as masks over A's positions."""
        return [np.asarray(m[:n, :T]) for m in sels] if selector else []

    def hot_rows(sels):
        out = []
        for sel in sels if selector else []:  # decode: positions [S, k], -1 where fewer exist
            s0 = np.asarray(sel[0])
            m = np.zeros((1, T), bool)
            m[0, s0[s0 >= 0]] = True
            out.append(m)
        return out

    def log_softmax(x):
        x = np.asarray(x, np.float64)
        return x - x.max(-1, keepdims=True) - np.log(np.exp(x - x.max(-1, keepdims=True)).sum(
            -1, keepdims=True))

    link = {"abs": 0.0, "tokens": 0, "agree": 0}
    a_top = []  # the engine's (top ids, their log-probabilities) at A's compared positions

    def link_rows(tokens, top_ids, top_lps, logits, n_rows):
        """The engine's sampled outputs [S, ...] against the check's logits [S, V]."""
        lp = log_softmax(logits[:n_rows])
        for i in range(n_rows):
            d = np.abs(np.asarray(top_lps[i], np.float64) - lp[i][np.asarray(top_ids[i])]).max()
            link["abs"] = max(link["abs"], float(d))
            link["tokens"] += 1
            link["agree"] += int(int(tokens[i]) == int(np.argmax(logits[i])))

    def system(params, cache, masks, with_engine, fwd=fwd):
        """Row A's logits at the compared positions through the check's jit,
        teacher-forced on `rows[*].tokens`; with `with_engine` every step also
        goes through the engine's own program, which decides the tokens."""
        live = rows if with_engine else [A]
        plan = [[(A, a, chunk)] for a in range(0, n_doc, chunk)] if masks["doc"] else []
        plan += [[(A, n_doc, a1)] + ([(B, n_doc, b1)] if with_engine else []),
                 [(A, n_doc + a1, a2)] + ([(B, n_doc + b1, b2), (C, 0, n_short)]
                                          if with_engine else [])]
        out_logits = []
        for items in plan:
            rb = prefill_batch(items)
            logits, cache, sels = fwd(params, cache, rb, False)
            for l, m in enumerate(mask_rows(sels, items[0][2])):
                masks["A"][l].append(m)
            if with_engine:
                out, cache = engine._step_fn(params, cache, rb, samp)
                link_rows(np.asarray(out.tokens), np.asarray(out.top_ids),
                          np.asarray(out.top_logprobs), np.asarray(logits, np.float32),
                          len(items))
        lg = np.asarray(logits, np.float32)
        out_logits.append(lg[0])
        if with_engine:
            first = np.asarray(out.tokens)
            a_top.append((np.asarray(out.top_ids)[0], np.asarray(out.top_logprobs)[0]))
            for i, r in enumerate(live):
                r.tokens[r.n_prompt] = first[i]
            pos0 = np.full((S,), -1, np.int32)
            tables, limits = np.zeros((S, PP), np.int32), np.zeros((S,), np.int32)
            tok0 = np.zeros((S,), np.int32)
            for i, r in enumerate(live):
                pos0[i], tables[i], limits[i], tok0[i] = r.n_prompt, r.table, r.limit, first[i]
            carry = (tok0, samp.steps, samp.counts)
        for d in range(n_dec // steps):
            if with_engine:
                # The fused program: `steps` tokens a row, chained on the device.
                outs, last, steps_f, counts_f, cache = engine._multi_fn(
                    params, cache, *carry, pos0 + np.where(pos0 >= 0, d * steps, 0),
                    tables, limits, samp)
                carry = (last, steps_f, counts_f)
                toks = np.asarray(outs.tokens)  # [steps, S]
                for i, r in enumerate(live):
                    r.tokens[r.n_prompt + d * steps + 1: r.n_prompt + (d + 1) * steps + 1] = \
                        toks[:, i]
            for j in range(d * steps, (d + 1) * steps):
                logits, cache, sels = fwd(params, cache, decode_batch(live, j), True)
                lg = np.asarray(logits, np.float32)
                out_logits.append(lg[0])
                for l, m in enumerate(hot_rows(sels)):
                    masks["A"][l].append(m)
                if with_engine:
                    k = j - d * steps
                    ids, lps = np.asarray(outs.top_ids)[k], np.asarray(outs.top_logprobs)[k]
                    link_rows(toks[k], ids, lps, lg, len(live))
                    a_top.append((ids[0], lps[0]))
        return np.stack(out_logits), cache

    def new_masks(with_doc):
        return {"doc": with_doc, "A": [[] for _ in range(L)]}

    def whole(masks):
        return [np.concatenate(m) for m in masks["A"]] if selector else None  # [T, T] per layer

    # ---- the system as configured
    t1 = time.time()
    masks = new_masks(True)
    sys_logits, cache = system(engine.params, engine.cache, masks, True)
    sys_masks = whole(masks)
    emit(f"{tag}_system", t1, positions=len(sys_logits), rows=len(rows),
         engine_tokens=link["tokens"], engine_argmax_agree=link["agree"])

    # ---- control 1: every page rounded to int8 (one scale a token and part),
    # the nearest precision below the stated bfloat16 pages; same tokens.
    def int8_pages(a):
        def q(x):
            s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0, 1e-12)
            return jnp.round(x / s) * s

        af = a.astype(jnp.float32)
        r = mc.kv_lora_rank
        if a.shape[-1] > r:  # latent pages: [c | k_rope | zero lanes]
            af = jnp.concatenate([q(af[..., :r]), q(af[..., r:])], axis=-1)
        else:
            af = q(af)
        return af.astype(a.dtype)

    round_pages = jax.jit(lambda c: jax.tree_util.tree_map(int8_pages, c), donate_argnums=0)

    def fwd_rounded(p, c, rb, dec):
        """The check's jit with the pages rounded again after every step, so
        that the question's and the answer's entries are int8's too."""
        logits, c, sels = fwd(p, c, rb, dec)
        return logits, round_pages(c), sels

    # ---- control 1b (where ``par`` names it as the control that must fail):
    # W8A8's activations one step coarser, 6 bits and a sign where the
    # configuration states 7 (every second int8 level), in the check's jit
    # over the question and the answer; the document's pages stay the engine's.
    controls = {}
    if par["control"] == "coarse_activations":
        from dynamo_tpu.ops import quant_matmul

        t1 = time.time()
        rows_int8 = quant_matmul.quantize_rows

        def rows_int7(x):
            xq, scale = rows_int8(x)
            return (jnp.round(xq.astype(jnp.float32) / 2.0) * 2.0).astype(xq.dtype), scale

        fwd_coarse = jax.jit(
            lambda p, c, rb, dec: fam.forward(p, mc, rb, c, decode=dec, return_selection=True),
            static_argnums=3, donate_argnums=1)
        quant_matmul.quantize_rows = rows_int7  # read when ``fwd_coarse`` traces
        try:
            controls["coarse_activations"], cache = system(
                engine.params, cache, new_masks(False), False, fwd_coarse)
        finally:
            quant_matmul.quantize_rows = rows_int8
        emit(f"{tag}_coarse_activations", t1)

    t1 = time.time()
    # The pages as the engine's programs left them, A's question and answer
    # included: the control recomputes those entries from the rounded document.
    low_masks = new_masks(False)
    low_logits, low_cache = system(engine.params, round_pages(cache), low_masks, False,
                                   fwd_rounded)
    del low_cache
    controls["int8_pages"] = low_logits
    emit(f"{tag}_int8_pages", t1)

    # ---- the engine leaves the chip; its weights stay on the host
    host_params = jax.tree_util.tree_map(np.asarray, engine.params)
    cache_shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), engine.cache)
    del cache
    engine.params = engine.cache = None

    def leaf_groups(tree):
        yield "top", tree
        for g in ("layers", "dense", "moe"):
            yield g, tree[g]

    # ---- control 2: the same weights as bfloat16, no activation rounding
    t1 = time.time()
    dt = jnp.dtype(serve["dtype"])
    deq = jax.jit(lambda w, s, axis: (w.astype(jnp.float32) * jnp.expand_dims(s, axis)
                                      ).astype(dt), static_argnums=2)

    def float_tree():
        tree = {}
        for g, leaves in leaf_groups(host_params):
            dst = tree if g == "top" else tree.setdefault(g, {})
            for name, leaf in leaves.items():
                if isinstance(leaf, dict) or name.endswith("_scale"):
                    continue
                if name + "_scale" in leaves:
                    dst[name] = deq(leaf, leaves[name + "_scale"], ds.QUANT_AXES[g][name])
                else:
                    dst[name] = jnp.asarray(leaf)
        return tree

    fparams = float_tree()
    fcache = jax.tree_util.tree_map(lambda sd: jnp.zeros(sd.shape, sd.dtype), cache_shapes)
    fmasks = new_masks(True)
    float_logits, fcache = system(fparams, fcache, fmasks, False)
    float_masks = whole(fmasks)
    del fparams, fcache
    emit(f"{tag}_float_weights", t1)
    engine = None

    # ---- the reference, layer by layer, on the dequantised weights
    t1 = time.time()
    compare = np.arange(n_prompt - 1, T)
    pos = jnp.arange(T, dtype=jnp.int32)
    held = list(ds.held_experts(mc))
    tokens = A.tokens[:T]

    def layer_f32(l):
        Ld = mc.first_k_dense_replace
        group, i = ("dense", l) if l < Ld else ("moe", l - Ld)
        lp = {}
        for g, j in (("layers", l), (group, i)):
            leaves = host_params[g]
            for name, leaf in leaves.items():
                if name.endswith("_scale"):
                    continue
                w = jnp.asarray(leaf[j], jnp.float32)
                if name + "_scale" in leaves:
                    axis = ds.QUANT_AXES[g][name] - 1  # the layer axis is gone
                    w = w * jnp.expand_dims(jnp.asarray(leaves[name + "_scale"][j]), axis)
                lp[name] = w
        return lp

    def reference(forced):
        with jax.default_matmul_precision("highest"):
            emb = jnp.asarray(host_params["embed"][tokens], jnp.float32)
            h = emb * jnp.asarray(host_params["embed_scale"][tokens])[:, None]
            sels = []
            for l in range(L):
                lp = layer_f32(l)
                if selector:
                    sel = None if forced is None else jnp.asarray(forced[l])
                    h, m = ref.layer(lp, hf, h, pos, held, sel, par["q_block"])
                    sels.append(np.asarray(m)[compare])
                else:
                    h = ref.layer(lp, hf, h, pos, held, par["q_block"])
                del lp
            h = ref.rms_norm(h[compare], jnp.asarray(host_params["final_norm"], jnp.float32),
                             hf["rms_norm_eps"])
            head = (jnp.asarray(host_params["lm_head"], jnp.float32)
                    * jnp.asarray(host_params["lm_head_scale"])[None, :])
            return np.asarray(h @ head), sels

    def rel_err(a, b):
        return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))

    def rms_err(a, b):
        return float(np.sqrt(np.mean((a - b) ** 2) / np.mean(b ** 2)))

    def overlap_of(ref_sels, masks_):
        """``masks_``: per layer, S_t of at least the compared positions (the last rows)."""
        o = []
        for l in range(L):
            both = (ref_sels[l] & masks_[l][-len(compare):]).sum(axis=1)
            o.append(both / np.maximum(ref_sels[l].sum(axis=1), 1))
        return np.stack(o)

    # The reference passes the system is read against: with a selector one
    # under the reference's own S_t and one with the system's S_t forced;
    # without one, S_t is the whole context and ONE pass serves.
    ref_own, ref_sels = reference(None)
    emit(f"{tag}_reference_own", t1)
    if selector:
        t1 = time.time()
        ref_forced, _ = reference(sys_masks)
        emit(f"{tag}_reference_forced", t1)
        refs = {"own_selection": ref_own, "forced_selection": ref_forced}
    else:
        ref_forced = ref_own
        refs = {"whole_context": ref_own}
    ref_max = float(np.max(np.abs(ref_forced)))
    out = {
        "engine_link": link["abs"] / ref_max,
        "engine_link_nats": link["abs"],
        "engine_tokens": link["tokens"], "engine_argmax_agree": link["agree"],
        "argmax_agree": int((sys_logits.argmax(-1) == ref_own.argmax(-1)).sum()),
        "ref_max_abs_logit": ref_max,
        "positions": int(len(compare)), "context": int(T), "seed": seed,
        "limits": {k: v[0] for k, v in limits.items()},
    }
    for name, r in refs.items():
        out.update({f"rel_err_{name}": rel_err(sys_logits, r),
                    f"rms_err_{name}": rms_err(sys_logits, r)})
    for control, logits in controls.items():
        # The engine's top-20 log-probabilities against the control's logits:
        # the link's reading one precision down.
        lp = log_softmax(logits)
        out.update({
            f"{control}_engine_link": max(
                float(np.abs(np.asarray(lps, np.float64) - lp[j][ids]).max())
                for j, (ids, lps) in enumerate(a_top)) / ref_max,
            f"{control}_vs_system_rel": rel_err(logits, sys_logits),
            f"{control}_vs_system_rms": rms_err(logits, sys_logits)})
        for name, r in refs.items():
            out.update({f"{control}_rel_err_{name}": rel_err(logits, r),
                        f"{control}_rms_err_{name}": rms_err(logits, r)})
    if selector:
        overlap = overlap_of(ref_sels, sys_masks)
        out.update({
            "selection_overlap_mean": float(overlap.mean()),
            "selection_overlap_min": float(overlap.min()),
            "int8_pages_selection_overlap_mean": float(
                overlap_of(ref_sels, whole(low_masks)).mean()),
            "float_weights_selection_overlap_mean": float(
                overlap_of(ref_sels, float_masks).mean()),
        })
        t1 = time.time()
        refs["forced_selection"], _ = reference(float_masks)  # the float control's own S_t
        emit(f"{tag}_reference_forced_float", t1)
    for name, r in refs.items():
        out.update({f"float_weights_rel_err_{name}": rel_err(float_logits, r),
                    f"float_weights_rms_err_{name}": rms_err(float_logits, r)})
    emit(f"{tag}_parity", t0, **out)
    if not rehearse:
        within = lambda v, limit, kind: v <= limit if kind == "max" else v >= limit
        for name, (limit, kind) in limits.items():
            if not within(out[name], limit, kind):
                fail(f"{tag}: {name} {out[name]} against its limit {limit} ({kind})")
        control = par["control"]
        low = {n: lk for n, lk in limits.items() if f"{control}_{n}" in out}
        if all(within(out[f"{control}_{n}"], *lk) for n, lk in low.items()):
            fail(f"{tag}: the control {control} passes every limit it is read against "
                 f"({sorted(low)}): the limits are too loose")
    print(json.dumps(dev), flush=True)


# ------------------------------------------------------------ LFM2 parity
# `--child parity-lfm2`: chipbench/configs/lfm2-8b-a1b.json at its published
# widths and full depth under assist-shared's shapes, against the float32
# reference (dynamo_tpu/models/reference/lfm2_moe.py) on the same dequantised
# weights, computed layer by layer in query blocks.
#
# What runs.  (1) The ENGINE'S OWN programs decide every token: row A
# prefills a 3000-token prompt COLD in 512-token chunks (`engine._step_fn`),
# row B the same prompt behind a 2048-token PREFIX HIT (its table names A's
# first 128 pages: its convolutions start from the 128th page's entry), then
# both decode 64 tokens side by side in 16 fused chunks (`engine._multi_fn`,
# the sampled token fed back on the device).  B's tokens and top-20
# log-probabilities must EQUAL A's (`hit_vs_cold`: the same programs over the
# same values; the benchmark's probe compares exactly those two paths).
# (2) The check's own jit of the same forward with the engine's options,
# teacher-forced on A's tokens into fresh pages, gives what the engine's
# programs do not return: whole logits, at every chunk's end and every decode
# step; the engine's top-20 log-probabilities are read against them
# (`engine_link`).  (3) Those logits against the reference at the same 70
# positions, as the root-mean-square and the maximum over all logits.
# Control, teacher-forced on the SAME tokens, must fail: the page entry
# dropped (zeros read) wherever a run starts on a page boundary: every
# chunk's first token and every sixteenth decode step lose u_{t-1}, u_{t-2}
# in 18 layers.
# (4) The same comparison over ONE PERIOD of the layer pattern (the first
# ``shallow_layers`` 4 layers: convolution, convolution, attention,
# convolution; both dense feed-forwards and two expert layers of all 32
# experts) at the published widths, the same weights, tokens, pages and
# programs' options: ``shallow_*``.  Seeded random blocks are each as large
# as the stream they add to, so 24 of them amplify every rounding (and every
# expert choice a rounding flips) until the whole model's logits read 0.4
# against float32 where a wrong position reads 1.2; one period is where an
# arithmetic fault in a kind of layer or in a kernel shows against W8A8's own
# noise, so its limits are the tight ones.
# Limits and the readings they come from: PERF.md section 6.
LFM2 = {"config": "chipbench/configs/lfm2-8b-a1b.json", "prefix": 2048, "prompt": 3000,
        "decode": 64, "num_blocks": 1024, "q_block": 512, "shallow_layers": 4}
LFM2_REHEARSAL = dict(LFM2, prefix=128, prompt=200, decode=8, num_blocks=128, q_block=64)
# The readings of each comparison; the control (page entries dropped) reads the
# same names behind "dropped_state_" and must pass one limit of each at least
# (it passes every one of them on every seed).
LFM2_WHOLE = ("rms_err", "rel_err", "rms_err_worst_position", "engine_link")
LFM2_ONE_PERIOD = ("shallow_rms_err", "shallow_rel_err", "shallow_rms_err_worst_position")
# Each limit lies between the largest the system read and the least the control
# read on the chip over seeds 28 / 29 / 30 (PR 36; the table is in PERF.md
# section 6), near their geometric mean: system | control.
LFM2_LIMITS = {  # the most each may read
    "rms_err": 0.47,  # 0.393-0.415 | 0.522-0.544: four or five positions of 70 lose their state
    "rel_err": 0.72,  # 0.456-0.567 | 0.926-1.057: the largest single logit error
    "rms_err_worst_position": 0.85,  # 0.584-0.619 | 1.188-1.198: a position whose state was dropped
    "engine_link": 0.52,  # 0.238-0.372 | 0.738-0.841: the engine's own top-20 against these logits
    "shallow_rms_err": 0.17,  # 0.088-0.094 | 0.319-0.327: W8A8 and bfloat16 over four layers
    "shallow_rel_err": 0.35,  # 0.151-0.153 | 0.816-0.951
    "shallow_rms_err_worst_position": 0.42,  # 0.169-0.183 | 1.031-1.098
    "hit_vs_cold": 0.0,  # the same programs over the same values: equal to the bit
}


def child_parity_lfm2(rehearse: bool) -> None:
    t0 = time.time()
    dev = child_device(rehearse)
    import types

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import TpuEngine
    from dynamo_tpu.models import lfm2
    from dynamo_tpu.models.config import ModelConfig, register_config
    from dynamo_tpu.models.family import RaggedBatch
    from dynamo_tpu.models.reference import lfm2_moe as ref

    par = LFM2_REHEARSAL if rehearse else LFM2
    seed = int(os.environ.get("DSV32_PARITY_SEED", "28"))
    with open(os.path.join(HERE, par["config"])) as f:
        body = json.load(f)
    serve = dict(body["serve"])
    if rehearse:
        hf = dict(body["rehearsal"]["model"])
        serve.update(body["rehearsal"]["serve"])
    else:
        hf = {k: v for k, v in body.items() if k not in (
            "name", "source", "serve", "chips", "reduced", "assumed", "stands_for",
            "rehearsal", "notes")}
    mc = register_config(ModelConfig.from_hf_config(hf, name="parity-lfm2"))
    kv_scale = serve.get("kv_scale", 1.0)
    cfg = EngineConfig(
        model=mc.name, block_size=serve["block_size"], num_blocks=par["num_blocks"],
        max_batch=serve["max_batch"], max_model_len=serve["max_model_len"],
        prefill_chunk=serve["prefill_chunk"], decode_steps=serve["decode_steps"],
        dtype=serve["dtype"], cache_dtype=serve["kv_cache_dtype"],
        kv_scale=kv_scale if kv_scale == "auto" else float(kv_scale),
        weight_quant=serve["weight_quant"], seed=20260900 + seed)
    engine = TpuEngine(cfg)
    mc, fam = engine.model_config, engine.family
    emit("lfm2_engine", t0, **dev, attn_impl=engine.attn_impl, decode_kernel=engine.decode_kernel,
         prefill_kernel=engine.prefill_kernel,
         hbm=(jax.local_devices()[0].memory_stats() or {}).get("bytes_in_use"))

    bs, S, PP, chunk = cfg.block_size, cfg.max_batch, cfg.max_blocks_per_seq, cfg.prefill_chunk
    steps = cfg.decode_steps
    n_prefix, n_prompt, n_dec = par["prefix"], par["prompt"], par["decode"]
    assert n_prefix % chunk == 0 and n_dec % steps == 0 and n_prefix % bs == 0
    T = n_prompt + n_dec
    own = -(-T // bs) + 1  # pages a row needs
    assert 4 * own <= cfg.num_blocks and own <= PP
    rng = np.random.default_rng(seed)
    tokens = np.zeros((T + 1,), np.int32)
    tokens[:n_prompt] = rng.integers(16, mc.vocab_size, n_prompt)

    def table_of(first, shared=0):
        t = np.zeros((PP,), np.int32)
        t[:shared] = np.arange(shared)  # row A's pages
        t[shared:own] = first + np.arange(own - shared)
        return t

    tab_a, tab_b = table_of(0), table_of(own, n_prefix // bs)
    tab_c, tab_d = table_of(2 * own), table_of(3 * own)
    samp = engine._sampling_arrays([])._replace(need_logprobs=np.asarray(True))
    chunks = lambda a: [(s, min(chunk, n_prompt - s)) for s in range(a, n_prompt, chunk)]

    def prefill_batch(table, start, n):
        seq = types.SimpleNamespace(prompt=[int(t) for t in tokens[:start + n]], output=[],
                                    block_ids=[int(x) for x in table], adapter_slot=-1)
        return engine._build_ragged([(seq, start, n)])

    # ---- (1) the engine's own programs: A cold, B behind the hit, both decode
    t1 = time.time()
    params, cache = engine.params, engine.cache
    top = {"A": {}, "B": {}}  # position -> (token, top ids, their log-probabilities)
    for name, table, start in (("A", tab_a, 0), ("B", tab_b, n_prefix)):
        for a, n in chunks(start):
            out, cache = engine._step_fn(params, cache, prefill_batch(table, a, n), samp)
            top[name][a + n - 1] = (int(np.asarray(out.tokens)[0]), np.asarray(out.top_ids)[0],
                                    np.asarray(out.top_logprobs)[0])
    tokens[n_prompt] = top["A"][n_prompt - 1][0]
    pos0 = np.full((S,), -1, np.int32)
    tables, limits = np.zeros((S, PP), np.int32), np.zeros((S,), np.int32)
    tok0 = np.zeros((S,), np.int32)
    for i, (name, table) in enumerate((("A", tab_a), ("B", tab_b))):
        pos0[i], tables[i], limits[i] = n_prompt, table, own * bs
        tok0[i] = top[name][n_prompt - 1][0]
    carry = (tok0, samp.steps, samp.counts)
    for d in range(n_dec // steps):
        outs, last, steps_f, counts_f, cache = engine._multi_fn(
            params, cache, *carry, pos0 + np.where(pos0 >= 0, d * steps, 0), tables, limits, samp)
        carry = (last, steps_f, counts_f)
        toks, ids, lps = (np.asarray(x) for x in (outs.tokens, outs.top_ids, outs.top_logprobs))
        for k in range(steps):
            p = n_prompt + d * steps + k
            tokens[p + 1] = toks[k, 0]
            top["A"][p] = (int(toks[k, 0]), ids[k, 0], lps[k, 0])
            top["B"][p] = (int(toks[k, 1]), ids[k, 1], lps[k, 1])
    shared = sorted(set(top["A"]) & set(top["B"]))
    hit_vs_cold = max(float(np.abs(top["A"][p][2] - top["B"][p][2]).max()) for p in shared)
    hit_same = sum(int(top["A"][p][0] == top["B"][p][0]
                       and np.array_equal(top["A"][p][1], top["B"][p][1])) for p in shared)
    emit("lfm2_engine_programs", t1, positions=len(shared), hit_same_tokens_and_top20=hit_same,
         hit_vs_cold_nats=hit_vs_cold)

    # ---- (2) whole logits by the check's jit, teacher-forced on A's tokens
    def forward_of(config):
        return jax.jit(
            lambda p, c, rb, dec, drop: fam.forward(
                p, config, rb, c, decode=dec, attn_impl=engine.attn_impl,
                kv_scale=engine.kv_scale, decode_kernel=engine.decode_kernel,
                prefill_kernel=engine.prefill_kernel, drop_state_at_page_boundary=drop)[:2],
            static_argnums=(3, 4), donate_argnums=1)

    def decode_batch(table, p):
        t, ps_, kv = (np.zeros((S,), np.int32) for _ in range(3))
        sl = np.full((S,), -1, np.int32)
        tb = np.zeros((S, PP), np.int32)
        t[0], ps_[0], kv[0], tb[0] = tokens[p], p, p + 1, table
        sl[0] = int(table[p // bs]) * bs + p % bs
        return RaggedBatch(t, ps_, sl, kv, tb, np.arange(S + 1, dtype=np.int32),
                           np.asarray([S], np.int32))

    def system(fwd, params, cache, table, drop):
        """Logits [compared positions, V] of one cold pass into ``table``."""
        out = []
        for a, n in chunks(0):
            logits, cache = fwd(params, cache, prefill_batch(table, a, n), False, drop)
            out.append(np.asarray(logits, np.float32)[0])
        for p in range(n_prompt, T):
            logits, cache = fwd(params, cache, decode_batch(table, p), True, drop)
            out.append(np.asarray(logits, np.float32)[0])
        return np.stack(out), cache

    compare = np.asarray([a + n - 1 for a, n in chunks(0)] + list(range(n_prompt, T)))
    fwd = forward_of(mc)
    t1 = time.time()
    sys_logits, cache = system(fwd, params, cache, tab_c, False)
    emit("lfm2_system", t1, positions=len(compare))
    t1 = time.time()
    ctl_logits, cache = system(fwd, params, cache, tab_d, True)
    emit("lfm2_dropped_state", t1)

    # ---- (4) one period of the pattern: the leading layers of the same weights
    t1 = time.time()
    n_sh = par["shallow_layers"]
    mc_sh = mc.with_overrides(num_layers=n_sh, layer_types=mc.layer_types[:n_sh])
    kept = dict(zip(("conv", "attn", "dense", "moe"), lfm2.layer_counts(mc_sh)), layers=n_sh)
    params_sh = {g: {k: a[:kept[g]] for k, a in v.items()} if g in kept else v
                 for g, v in params.items()}
    cache_sh = fam.create_cache(mc_sh, cfg.num_blocks, bs, dtype=cache.pages.dtype)
    fwd_sh = forward_of(mc_sh)
    sys_sh, cache_sh = system(fwd_sh, params_sh, cache_sh, tab_c, False)
    ctl_sh, cache_sh = system(fwd_sh, params_sh, cache_sh, tab_d, True)
    emit("lfm2_shallow", t1, layers=n_sh, kinds=list(mc_sh.layer_types))

    # ---- the engine leaves the chip; its weights stay on the host
    host_params = jax.tree_util.tree_map(np.asarray, engine.params)
    del cache, params, cache_sh, params_sh
    engine.params = engine.cache = None
    engine = None

    # ---- (3) the reference, layer by layer, on the dequantised weights
    t1 = time.time()

    def f32_leaf(group, name, i=None):
        leaves = host_params if group == "top" else host_params[group]
        w = leaves[name] if i is None else leaves[name][i]
        w = jnp.asarray(w, jnp.float32)
        if name + "_scale" in leaves:
            sc = leaves[name + "_scale"] if i is None else leaves[name + "_scale"][i]
            axis = lfm2.QUANT_AXES[group][name] - (0 if i is None else 1)
            w = w * jnp.expand_dims(jnp.asarray(sc), axis)
        return w

    def layer_f32(l):
        kinds = mc.layer_types
        mixer = "conv" if kinds[l] == "conv" else "attn"
        i = sum(k == kinds[l] for k in kinds[:l])
        Ld = mc.first_k_dense_replace
        group, j = ("dense", l) if l < Ld else ("moe", l - Ld)
        lp = {}
        for g, at in (("layers", l), (mixer, i), (group, j)):
            for name in host_params[g]:
                if not name.endswith("_scale"):
                    lp[name] = f32_leaf(g, name, at)
        return lp

    with jax.default_matmul_precision("highest"):
        embed = f32_leaf("top", "embed")
        final_norm = jnp.asarray(host_params["final_norm"], jnp.float32)
        logits_of = lambda h: np.asarray(
            ref.rms_norm(h[compare], final_norm, hf.get("norm_eps", 1e-5)) @ embed.T)
        pos = jnp.arange(T, dtype=jnp.int32)
        h = embed[jnp.asarray(tokens[:T])]
        held = list(range(mc.num_experts))
        for l, kind in enumerate(mc.layer_types):
            h = ref.layer(layer_f32(l), hf, h, pos, kind, held, par["q_block"])
            if l == n_sh - 1:
                ref_sh = logits_of(h)
        ref_logits = logits_of(h)
    emit("lfm2_reference", t1)

    def rel_err(a, b):
        return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))

    def rms_err(a, b):
        return float(np.sqrt(np.mean((a - b) ** 2) / np.mean(b ** 2)))

    def worst_position(a, b):
        """The largest root-mean-square error of ONE position's logits."""
        return float(np.max(np.sqrt(np.mean((a - b) ** 2, axis=-1) / np.mean(b ** 2, axis=-1))))

    def log_softmax(x):
        x = np.asarray(x, np.float64)
        return x - x.max(-1, keepdims=True) - np.log(np.exp(x - x.max(-1, keepdims=True)).sum(
            -1, keepdims=True))

    ref_max = float(np.max(np.abs(ref_logits)))

    def link(logits):
        """The engine's top-20 log-probabilities of row A against ``logits``."""
        lp = log_softmax(logits)
        return max(float(np.abs(np.asarray(top["A"][int(p)][2], np.float64)
                                - lp[j][top["A"][int(p)][1]]).max())
                   for j, p in enumerate(compare)) / ref_max

    def readings(tag, got, want):
        return {tag + "rms_err": rms_err(got, want), tag + "rel_err": rel_err(got, want),
                tag + "rms_err_worst_position": worst_position(got, want)}

    n_pre = len(chunks(0))
    out = {
        **readings("", sys_logits, ref_logits), "engine_link": link(sys_logits),
        **readings("shallow_", sys_sh, ref_sh),
        "hit_vs_cold": hit_vs_cold / ref_max, "hit_same_tokens_and_top20": hit_same,
        "hit_positions": len(shared),
        "rms_err_prefill": rms_err(sys_logits[:n_pre], ref_logits[:n_pre]),
        "rms_err_decode": rms_err(sys_logits[n_pre:], ref_logits[n_pre:]),
        "argmax_agree": int((sys_logits.argmax(-1) == ref_logits.argmax(-1)).sum()),
        "shallow_argmax_agree": int((sys_sh.argmax(-1) == ref_sh.argmax(-1)).sum()),
        **readings("dropped_state_", ctl_logits, ref_logits),
        "dropped_state_engine_link": link(ctl_logits),
        # Held to nothing (PERF.md section 6): the state as a bfloat16 pool would
        # hold it, against the reference and against the system itself; the
        # system's error by depth, the whole model last.
        "bf16_state_rms_err_past_boundary": rms_err(bf16_logits[past], ref_logits[past]),
        **readings("bf16_state_", bf16_logits, ref_logits),
        "bf16_state_rms_err_decode": rms_err(bf16_logits[n_pre:], ref_logits[n_pre:]),
        "bf16_state_vs_system": readings("", bf16_logits, sys_logits),
        "depth_rms_err": {**{str(d): rms_err(sys_at[d], ref_at[d]) for d in sys_at},
                          str(mc.num_layers): rms_err(sys_logits, ref_logits)},
        "depth_rms_err_decode": {
            **{str(d): rms_err(sys_at[d][n_pre:], ref_at[d][n_pre:]) for d in sys_at},
            str(mc.num_layers): rms_err(sys_logits[n_pre:], ref_logits[n_pre:])},
        **readings("dropped_state_shallow_", ctl_sh, ref_sh),
        "ref_max_abs_logit": ref_max, "shallow_ref_max_abs_logit": float(np.max(np.abs(ref_sh))),
        "positions": int(len(compare)), "context": int(T), "seed": seed,
        "limits": LFM2_LIMITS,
    }
    emit("lfm2_parity", t0, **out)
    if not rehearse:
        over = [f"{n} {out[n]} against its limit {limit}"
                for n, limit in LFM2_LIMITS.items() if out[n] > limit]
        if over:
            fail("lfm2: " + "; ".join(over))
        if hit_same != len(shared):
            fail(f"lfm2: the hit's tokens or top-20 differ from the cold prefill's at "
                 f"{len(shared) - hit_same} of {len(shared)} positions")
        for what, names in (("whole model's", LFM2_WHOLE), ("one period's", LFM2_ONE_PERIOD)):
            if not any(out["dropped_state_" + n] > LFM2_LIMITS[n] for n in names):
                fail(f"lfm2: the control (page entries dropped) passes every one of the "
                     f"{what} limits: too loose")
    print(json.dumps(dev), flush=True)


# ---------------------------------------------------------- granite parity
# `--child parity-granite`: chipbench/configs/granite-4.0-h-small-10l-ep2.json
# at its published widths (one whole period: 9 Mamba-2 layers and the
# attention layer) under assist-shared's shapes, against the float32 reference
# (dynamo_tpu/models/reference/granitemoehybrid.py: the recurrence one token at
# a time) on the same dequantised weights, computed layer by layer with the
# attention in query blocks.
#
# What runs.  (1) The ENGINE'S OWN programs decide every token: row A prefills
# a 3000-token prompt COLD in 512-token chunks (`engine._step_fn`), each chunk
# that ends on a multiple of 512 leaving a SNAPSHOT of its state; row B the
# same prompt behind a 2048-token PREFIX HIT (its table names A's first 128
# pages, its state starts from the snapshot at 2048 in another live slot),
# then both decode 64 tokens side by side in 16 fused chunks
# (`engine._multi_fn`: row i's state in slot i).  B's tokens and top-20
# log-probabilities must EQUAL A's (`hit_vs_cold`: a snapshot is a copy, and
# a row's sums do not depend on what shares its step).  (2) The check's own
# jit of the same forward with the engine's options, teacher-forced on A's
# tokens into fresh pages and a fresh slot, gives whole logits at every
# chunk's end and every decode step; the engine's top-20 log-probabilities
# are read against them (`engine_link`).  (3) Those logits against the
# reference at the same positions.  Control, teacher-forced on the SAME
# tokens, must fail: the state and tail dropped (zeros read) wherever a
# prompt chunk starts on a multiple of the stride.  Reported beside it and
# held to nothing: the same pass with the scan state ROUNDED TO BFLOAT16
# after every step (what a bfloat16 pool would hold; `bf16_state_*`), and the
# leading 1 and 5 layers of the same weights against the reference at that
# depth (`depth_rms_err`: where the whole model's error comes from).
# Limits and the readings they come from: PERF.md section 6.
GRANITE = {"config": "chipbench/configs/granite-4.0-h-small-10l-ep2.json", "prefix": 2048,
           "prompt": 3000, "decode": 64, "num_blocks": 1024, "q_block": 512, "depths": (1, 5)}
GRANITE_REHEARSAL = dict(GRANITE, prefix=128, prompt=200, decode=8, num_blocks=128, q_block=64,
                         depths=(1,))
GRANITE_READINGS = ("rms_err", "rel_err", "rms_err_worst_position", "rms_err_past_boundary")
# Each limit lies between the largest the system read and the least the control
# read on the chip over seeds 28 / 29 / 30 (my chip run, PR 44; PERF.md section
# 6), near their geometric mean: system | control.
GRANITE_LIMITS = {  # the most each may read
    # 0.158-0.243 | 0.324-0.366.  By depth (`depth_rms_err`, seeds 28 / 29 / 30): 0.087 / 0.059 / 0.039
    # after one layer, 0.190 / 0.137 / 0.109 after five, the root of the depth: every layer adds W8A8
    # noise and router near-ties of its own size.  A state ROUNDED TO BFLOAT16 after every step reads
    # 0.160-0.245 and passes every limit here (`bf16_state_*`: it moves the logits by 0.11 of their
    # size, as far as any perturbation that re-rolls the near-ties, and no farther from the
    # reference): the state's precision is held by tests/test_granite_hybrid.py on the CPU, not here.
    "rms_err": 0.28,
    "rel_err": 0.18,  # 0.072-0.097 | 0.328-0.392: the largest single logit error
    "rms_err_worst_position": 0.55,  # 0.234-0.274 | 1.094-1.128: a position 8 past a boundary
    "rms_err_past_boundary": 0.48,  # 0.198-0.225 | 1.060-1.090: the five positions 8 past a boundary
    # The engine's own top-20 are at chunk ENDS, 511 tokens past a drop, where
    # most heads have forgotten it: the control reads 0.029-0.052 against the
    # system's 0.024-0.027 and is not held to this one (three times the system's).
    "engine_link": 0.08,
    "hit_vs_cold": 0.0,  # the same programs over the same values: equal to the bit
}


def child_parity_granite(rehearse: bool) -> None:
    parity_slotted(rehearse, "granite", "granitemoehybrid",
                   GRANITE_REHEARSAL if rehearse else GRANITE, GRANITE_LIMITS)


# `--child parity-kimi-linear`: chipbench/configs/kimi-linear-48b-a3b-8l-ep8.json
# at its published widths (two whole periods: six KDA layers and two latent
# attention layers) under reason-shared's shapes, against the float32 reference
# (dynamo_tpu/models/reference/kimi_linear.py: the delta rule one token at a
# time, MLA decompressed) on the same dequantised weights.  A sibling of
# `parity-granite` and the SAME walk (``parity_slotted``): row A prefills a
# 1500-token prompt cold in 512-token chunks, leaving a snapshot at 1024; row B
# the same prompt behind a 1024-token hit (A's 64 latent pages, the state from
# the snapshot); both decode 64 tokens in 16 fused chunks; B must EQUAL A; the
# check's jit teacher-forced on A's tokens against the reference.  Controls as
# granite's: the state dropped at every stride boundary must fail; the state
# rounded to bfloat16 after every step and the error at depths 1 (a KDA layer
# with the dense MLP) and 4 (one period) are reported and held to nothing.
KIMI_LINEAR = {"config": "chipbench/configs/kimi-linear-48b-a3b-8l-ep8.json", "prefix": 1024,
               "prompt": 1500, "decode": 64, "num_blocks": 1024, "q_block": 512, "depths": (1, 4)}
KIMI_LINEAR_REHEARSAL = dict(KIMI_LINEAR, prefix=128, prompt=200, decode=8, num_blocks=128,
                             q_block=64, depths=(1,))
# As GRANITE_LIMITS: each between the largest the system read and the least the
# control read on the chip over seeds 28 / 29 / 30 (my chip run, PR 53; PERF.md
# section 6): system | control.
KIMI_LINEAR_LIMITS = {
    # 0.088-0.091 | 0.205-0.212.  By depth (`depth_rms_err`, seeds 28 / 29 / 30): 0.0335 / 0.0333 /
    # 0.0333 after one layer (KDA and the dense MLP), 0.059 / 0.061 / 0.057 after one period: the
    # root of the depth, as granite's: every layer adds W8A8 noise and router near-ties of its own
    # size.  A state ROUNDED TO BFLOAT16 after every step reads 0.084-0.092 and passes every limit
    # here (`bf16_state_*`): the state's precision is held by tests/test_kimi_linear.py on the CPU.
    "rms_err": 0.14,
    "rel_err": 0.35,  # 0.112-0.135 | 0.920-1.136: the largest single logit error
    "rms_err_worst_position": 0.40,  # 0.135-0.152 | 1.056-1.075: a position 8 past a boundary
    "rms_err_past_boundary": 0.33,  # 0.078-0.103 | 1.049-1.058: the two positions 8 past a boundary
    # The engine's own top-20 are at chunk ENDS and decode steps, hundreds of tokens past a
    # drop: the control reads 0.078-0.094 against the system's 0.043-0.067 and is not held to
    # this one (half again the system's largest).
    "engine_link": 0.10,
    "hit_vs_cold": 0.0,  # the same programs over the same values: equal to the bit
}


def child_parity_kimi_linear(rehearse: bool) -> None:
    parity_slotted(rehearse, "kimi_linear", "kimi_linear",
                   KIMI_LINEAR_REHEARSAL if rehearse else KIMI_LINEAR, KIMI_LINEAR_LIMITS)


# `--child parity-jamba2`: chipbench/configs/jamba2-3b.json WHOLE (28 layers: 26
# Mamba-1 layers and the attention layers at 7 and 21, every width, bfloat16
# weights as the cell serves them) under prefill-closed's shapes, against the
# float32 reference (dynamo_tpu/models/reference/jamba.py: the selective scan
# one token at a time).  A sibling of `parity-granite` and the SAME walk
# (``parity_slotted``): row A prefills a 2200-token prompt cold in 512-token
# chunks, leaving a snapshot at 1024; row B the same prompt behind a 1024-token
# hit (A's 64 K/V pages, the state from the snapshot); both decode 64 tokens in
# 16 fused chunks; B must EQUAL A; the check's jit teacher-forced on A's tokens
# against the reference.  Three controls: the state dropped at every stride
# boundary and the reference computed WITHOUT the mixer's three inner norms
# (what the system would give had it left them out) must each fail a limit;
# the state rounded to bfloat16 after every step is reported and held to
# nothing (JAMBA2_LIMITS says why).  The error at depths 1 (a Mamba-1 layer
# with its SwiGLU) and 8 (the first run and the first attention layer) is
# reported.
JAMBA2 = {"config": "chipbench/configs/jamba2-3b.json", "prefix": 1024, "prompt": 2200,
          "decode": 64, "num_blocks": 1024, "q_block": 512, "depths": (1, 8),
          "reference_controls": {"no_inner_norms": {"inner_norms": False}},
          "must_fail": ("dropped_state", "no_inner_norms")}
JAMBA2_REHEARSAL = dict(JAMBA2, prefix=128, prompt=200, decode=8, num_blocks=128, q_block=64,
                        depths=(1,))
# Each limit lies between the largest the system read and the least a control
# that must fail it read on the chip (my chip run, PR 56, seeds 28 / 29 / 30;
# PERF.md section 6), near their geometric mean: system | state dropped |
# reference without the inner norms.  The weights are bfloat16 as released (no
# W8A8), so the system stands at a third to a half of what the W8A8 slotted
# configurations read; what is left is bfloat16 activations through 28 layers
# of seeded random weights (`depth_rms_err`: 0.0075 after one layer, 0.022 after
# eight, 0.045 after all).  The THIRD control, the state rounded to bfloat16
# after every step, is reported and held to NOTHING here: it reads 0.0455-0.0458
# where the system reads 0.0450-0.0451 and lies as far from the system (0.0455-0.0461) as
# both lie from the reference: any perturbation is amplified to the same
# floor by the depth, so no reading of logits can tell it apart on the chip;
# tests/test_jamba.py holds the state's precision on the CPU in float32, where
# the same control fails the limit five times over.
JAMBA2_LIMITS = {  # the most each may read
    "rms_err": 0.11,  # 0.0450-0.0451 | 0.290-0.404 | 0.427-0.485
    "rel_err": 0.16,  # 0.043-0.046 | 0.760-0.788 | 0.639-0.675: the largest single logit error
    "rms_err_worst_position": 0.20,  # 0.052-0.055 | 0.863-1.013 | 0.776-0.821
    "rms_err_past_boundary": 0.13,  # 0.042-0.046 | 0.794-0.943 | 0.430-0.543: the positions 8 past a boundary
    # The engine's own top-20 log-probabilities at chunk ends and decode steps:
    # 0.020-0.021 | 0.198-0.440 (the reference has no engine to link).
    "engine_link": 0.065,
    "hit_vs_cold": 0.0,  # the same programs over the same values: equal to the bit
}


def child_parity_jamba2(rehearse: bool) -> None:
    parity_slotted(rehearse, "jamba2", "jamba", JAMBA2_REHEARSAL if rehearse else JAMBA2,
                   JAMBA2_LIMITS)


def parity_slotted(rehearse: bool, tag: str, reference: str, par: dict, held_to: dict) -> None:
    """The parity walk of a hybrid model whose mixers keep their state in
    SLOTS (Mamba-2, KDA, Mamba-1): see `parity-granite` above.  ``reference``:
    the module under dynamo_tpu/models/reference; ``tag`` names the emitted
    lines.  ``par["reference_controls"]`` (name -> keywords of the reference's
    ``layer``): the reference computed again with a part of the mathematics
    left out, read against the system as ``<name>_*``; ``par["must_fail"]``:
    the controls that must each exceed a limit (the dropped state alone unless
    given)."""
    t0 = time.time()
    dev = child_device(rehearse)
    import importlib
    import types

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import TpuEngine
    from dynamo_tpu.engine.resume import Beside
    from dynamo_tpu.models import lfm2
    from dynamo_tpu.models.config import ModelConfig, register_config
    from dynamo_tpu.models.family import RaggedBatch

    ref = importlib.import_module("dynamo_tpu.models.reference." + reference)
    seed = int(os.environ.get("DSV32_PARITY_SEED", "28"))
    with open(os.path.join(HERE, par["config"])) as f:
        body = json.load(f)
    serve = dict(body["serve"])
    if rehearse:
        hf = dict(body["rehearsal"]["model"])
        serve.update(body["rehearsal"]["serve"])
    else:
        hf = {k: v for k, v in body.items() if k not in (
            "name", "source", "serve", "chips", "reduced", "assumed", "stands_for",
            "rehearsal", "notes")}
    mc = register_config(ModelConfig.from_hf_config(hf, name="parity-" + tag.replace("_", "-")))
    kv_scale = serve.get("kv_scale", 1.0)
    cfg = EngineConfig(
        model=mc.name, block_size=serve["block_size"], num_blocks=par["num_blocks"],
        max_batch=serve["max_batch"], max_model_len=serve["max_model_len"],
        prefill_chunk=serve["prefill_chunk"], decode_steps=serve["decode_steps"],
        dtype=serve["dtype"], cache_dtype=serve["kv_cache_dtype"],
        kv_scale=kv_scale if kv_scale == "auto" else float(kv_scale),
        weight_quant=serve.get("weight_quant"), seed=20260900 + seed)
    engine = TpuEngine(cfg)
    mc, fam = engine.model_config, engine.family

    def mixer_group(kind: str) -> str:
        """The leaf group of a layer kind's mixer (models/lfm2.py)."""
        if kind in ("mamba", "kda", "mamba1"):
            return kind
        return "mla" if lfm2.latent_attention(mc) else "attn"

    emit(tag + "_engine", t0, **dev, attn_impl=engine.attn_impl,
         decode_kernel=engine.decode_kernel, prefill_kernel=engine.prefill_kernel,
         slots=[engine.kv.beside.live.size, engine.kv.beside.snapshots.size],
         hbm=(jax.local_devices()[0].memory_stats() or {}).get("bytes_in_use"))

    bs, S, PP, chunk = cfg.block_size, cfg.max_batch, cfg.max_blocks_per_seq, cfg.prefill_chunk
    steps = cfg.decode_steps
    n_prefix, n_prompt, n_dec = par["prefix"], par["prompt"], par["decode"]
    assert n_prefix % chunk == 0 and n_dec % steps == 0 and n_prefix % bs == 0
    assert engine.kv.beside.snapshots.size >= 1
    T = n_prompt + n_dec
    own = -(-T // bs) + 1  # pages a row needs
    assert 4 * own <= cfg.num_blocks and own <= PP
    rng = np.random.default_rng(seed)
    tokens = np.zeros((T + 1,), np.int32)
    tokens[:n_prompt] = rng.integers(16, mc.vocab_size, n_prompt)

    def table_of(first, shared=0):
        t = np.zeros((PP,), np.int32)
        t[:shared] = np.arange(shared)  # row A's pages
        t[shared:own] = first + np.arange(own - shared)
        return t

    tab_a, tab_b = table_of(0), table_of(own, n_prefix // bs)
    tab_c, tab_d = table_of(2 * own), table_of(3 * own)
    samp = engine._sampling_arrays([])._replace(need_logprobs=np.asarray(True))
    chunks = lambda a: [(s, min(chunk, n_prompt - s)) for s in range(a, n_prompt, chunk)]
    SNAP = S  # the one snapshot kept: the state at ``n_prefix``, in the pool's first slot

    def prefill_batch(table, start, n, slots=None):
        """The engine's own batch of one prompt row; ``slots``: the row's
        (read, write, snapshot) slots, or None: row 0 lives in slot 0."""
        seq = types.SimpleNamespace(prompt=[int(t) for t in tokens[:start + n]], output=[],
                                    block_ids=[int(x) for x in table], adapter_slot=-1)
        kind, engine.kv.beside = engine.kv.beside, Beside()  # the slots are named here
        try:
            rb = engine._build_ragged([(seq, start, n)])
        finally:
            engine.kv.beside = kind
        state = np.full((S, 3), -1, np.int32)
        state[0] = slots if slots is not None else (0 if start else -1, 0, -1)
        return rb._replace(state_slots=state)

    # ---- (1) the engine's own programs: A cold, B behind the hit, both decode
    t1 = time.time()
    params, cache = engine.params, engine.cache
    top = {"A": {}, "B": {}}  # position -> (token, top ids, their log-probabilities)
    for name, table, start, live in (("A", tab_a, 0, 0), ("B", tab_b, n_prefix, 1)):
        for a, n in chunks(start):
            if name == "A":
                slots = (0 if a else -1, 0, SNAP if a + n == n_prefix else -1)
            else:
                slots = (SNAP if a == n_prefix else 1, 1, -1)
            out, cache = engine._step_fn(params, cache, prefill_batch(table, a, n, slots), samp)
            top[name][a + n - 1] = (int(np.asarray(out.tokens)[0]), np.asarray(out.top_ids)[0],
                                    np.asarray(out.top_logprobs)[0])
    snapshot_is_a_copy = bool(float(jnp.abs(cache.ssm[:, SNAP]).max()) > 0)
    tokens[n_prompt] = top["A"][n_prompt - 1][0]
    pos0 = np.full((S,), -1, np.int32)
    tables, limits = np.zeros((S, PP), np.int32), np.zeros((S,), np.int32)
    tok0 = np.zeros((S,), np.int32)
    for i, (name, table) in enumerate((("A", tab_a), ("B", tab_b))):
        pos0[i], tables[i], limits[i] = n_prompt, table, own * bs
        tok0[i] = top[name][n_prompt - 1][0]
    carry = (tok0, samp.steps, samp.counts)
    for d in range(n_dec // steps):
        outs, last, steps_f, counts_f, cache = engine._multi_fn(
            params, cache, *carry, pos0 + np.where(pos0 >= 0, d * steps, 0), tables, limits, samp)
        carry = (last, steps_f, counts_f)
        toks, ids, lps = (np.asarray(x) for x in (outs.tokens, outs.top_ids, outs.top_logprobs))
        for k in range(steps):
            p = n_prompt + d * steps + k
            tokens[p + 1] = toks[k, 0]
            top["A"][p] = (int(toks[k, 0]), ids[k, 0], lps[k, 0])
            top["B"][p] = (int(toks[k, 1]), ids[k, 1], lps[k, 1])
    shared = sorted(set(top["A"]) & set(top["B"]))
    hit_vs_cold = max(float(np.abs(top["A"][p][2] - top["B"][p][2]).max()) for p in shared)
    hit_same = sum(int(top["A"][p][0] == top["B"][p][0]
                       and np.array_equal(top["A"][p][1], top["B"][p][1])) for p in shared)
    emit(tag + "_engine_programs", t1, positions=len(shared), hit_same_tokens_and_top20=hit_same,
         hit_vs_cold_nats=hit_vs_cold, snapshot_nonzero=snapshot_is_a_copy)

    # ---- (2) whole logits by the check's jit, teacher-forced on A's tokens
    def forward_of(config, kv_scale):
        return jax.jit(
            lambda p, c, rb, dec, drop: fam.forward(
                p, config, rb, c, decode=dec, attn_impl=engine.attn_impl, kv_scale=kv_scale,
                decode_kernel=engine.decode_kernel, prefill_kernel=engine.prefill_kernel,
                drop_state_at_stride=drop)[:2],
            static_argnums=(3, 4), donate_argnums=1)

    fwd = forward_of(mc, engine.kv_scale)
    # Row 0's scan state as a bfloat16 pool would hold it: rounded after every step.
    as_bf16 = jax.jit(lambda c: c._replace(ssm=c.ssm.at[:, 0].set(
        jax.lax.reduce_precision(c.ssm[:, 0], 8, 7))), donate_argnums=0)

    def decode_batch(table, p):
        t, ps_, kv = (np.zeros((S,), np.int32) for _ in range(3))
        sl = np.full((S,), -1, np.int32)
        tb = np.zeros((S, PP), np.int32)
        t[0], ps_[0], kv[0], tb[0] = tokens[p], p, p + 1, table
        sl[0] = int(table[p // bs]) * bs + p % bs
        return RaggedBatch(t, ps_, sl, kv, tb, np.arange(S + 1, dtype=np.int32),
                           np.asarray([S], np.int32))

    # The check's own chunking: every 512-token chunk as its first 8 tokens and
    # the rest, so that logits are read 8 tokens PAST each stride boundary,
    # where a dropped state shows (511 tokens on, most heads have forgotten it).
    check_chunks = [piece for a, n in chunks(0)
                    for piece in ([(a, 8), (a + 8, n - 8)] if n > 8 else [(a, n)])]

    def system(cache, table, drop, fwd=fwd, params=params, after=lambda c: c):
        """Logits [compared positions, V] of one cold pass into ``table``, slot 0."""
        out = []
        for a, n in check_chunks:
            logits, cache = fwd(params, cache, prefill_batch(table, a, n), False, drop)
            cache = after(cache)
            out.append(np.asarray(logits, np.float32)[0])
        for p in range(n_prompt, T):
            logits, cache = fwd(params, cache, decode_batch(table, p), True, drop)
            cache = after(cache)
            out.append(np.asarray(logits, np.float32)[0])
        return np.stack(out), cache

    compare = np.asarray([a + n - 1 for a, n in check_chunks] + list(range(n_prompt, T)))
    t1 = time.time()
    sys_logits, cache = system(cache, tab_c, 0)
    emit(tag + "_system", t1, positions=len(compare))
    t1 = time.time()
    ctl_logits, cache = system(cache, tab_d, chunk)
    emit(tag + "_dropped_state", t1)
    t1 = time.time()
    bf16_logits, cache = system(cache, tab_d, 0, after=as_bf16)
    emit(tag + "_bf16_state", t1)

    # ---- (4) the leading layers of the same weights, for the error's growth with depth
    t1 = time.time()
    sys_at = {}
    for depth in par["depths"]:
        mc_d = mc.with_overrides(num_layers=depth, layer_types=mc.layer_types[:depth])
        dense = min(depth, mc.first_k_dense_replace)
        kept = {"layers": depth, "dense": dense, "moe": depth - dense, "shared": depth - dense,
                **{g: sum(mixer_group(k) == g for k in mc_d.layer_types)
                   for g in ("mamba", "attn", "kda", "mla", "mamba1")}}
        params_d = {g: {k: a[:kept[g]] for k, a in v.items()} if g in kept else v
                    for g, v in params.items()}
        cache_d = fam.create_cache(mc_d, cfg.num_blocks, bs, dtype=cache.pages.dtype,
                                   state_slots=S)
        sys_at[depth], cache_d = system(cache_d, tab_c, 0, fwd=forward_of(mc_d, engine.kv_scale),
                                        params=params_d)
        del cache_d, params_d
    emit(tag + "_depths", t1, depths=list(par["depths"]))

    # ---- the engine leaves the chip; its weights stay on the host
    host_params = jax.tree_util.tree_map(np.asarray, engine.params)
    del cache, params
    engine.params = engine.cache = None
    engine = None

    # ---- (3) the reference, layer by layer, on the dequantised weights
    t1 = time.time()

    def f32_leaf(group, name, i=None):
        leaves = host_params if group == "top" else host_params[group]
        w = leaves[name] if i is None else leaves[name][i]
        w = jnp.asarray(w, jnp.float32)
        if name + "_scale" in leaves:
            sc = leaves[name + "_scale"] if i is None else leaves[name + "_scale"][i]
            axis = lfm2.QUANT_AXES[group][name] - (0 if i is None else 1)
            w = w * jnp.expand_dims(jnp.asarray(sc), axis)
        return w

    def layer_f32(l):
        kinds, dense = mc.layer_types, mc.first_k_dense_replace
        i = sum(mixer_group(k) == mixer_group(kinds[l]) for k in kinds[:l])
        ffn = [("dense", l)] if l < dense else [("moe", l - dense)] + (
            [("shared", l - dense)] if "shared" in host_params else [])
        lp = {}
        for g, at in [("layers", l), (mixer_group(kinds[l]), i)] + ffn:
            for name in host_params[g]:
                if not name.endswith("_scale"):
                    lp[name] = f32_leaf(g, name, at)
        return lp

    with jax.default_matmul_precision("highest"):
        embed = f32_leaf("top", "embed")
        final_norm = jnp.asarray(host_params["final_norm"], jnp.float32)
        pos = jnp.arange(T, dtype=jnp.int32)
        h0 = hf.get("embedding_multiplier", 1.0) * embed[jnp.asarray(tokens[:T])]
        # A reference whose model has experts is told which are held here.
        held = [ref.held_experts(hf)] if hasattr(ref, "held_experts") else []
        head = f32_leaf("top", "lm_head") if "lm_head" in host_params else embed.T
        logits_of = lambda h: np.asarray(
            ref.rms_norm(h[compare], final_norm, hf.get("rms_norm_eps", 1e-5)) @ head
        ) / hf.get("logits_scaling", 1.0)

        def reference(at=(), **controls):
            """(logits after the last layer, logits after each depth of ``at``)."""
            h, by_depth = h0, {}
            # The reference's own names of the kinds, where it has them.
            for l, kind in enumerate(getattr(ref, "layer_kinds", lambda _: mc.layer_types)(hf)):
                h = ref.layer(layer_f32(l), hf, h, pos, kind, *held, par["q_block"], **controls)
                if l + 1 in at:
                    by_depth[l + 1] = logits_of(h)
            return logits_of(h), by_depth

        ref_logits, ref_at = reference(at=sys_at)
        left_out = {name: reference(**kw)[0]
                    for name, kw in par.get("reference_controls", {}).items()}
    emit(tag + "_reference", t1, passes=1 + len(left_out))

    def rel_err(a, b):
        return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))

    def rms_err(a, b):
        return float(np.sqrt(np.mean((a - b) ** 2) / np.mean(b ** 2)))

    def worst_position(a, b):
        """The largest root-mean-square error of ONE position's logits."""
        return float(np.max(np.sqrt(np.mean((a - b) ** 2, axis=-1) / np.mean(b ** 2, axis=-1))))

    def log_softmax(x):
        x = np.asarray(x, np.float64)
        return x - x.max(-1, keepdims=True) - np.log(np.exp(x - x.max(-1, keepdims=True)).sum(
            -1, keepdims=True))

    ref_max = float(np.max(np.abs(ref_logits)))

    def link(logits):
        """The engine's top-20 log-probabilities of row A against ``logits``."""
        lp = log_softmax(logits)
        return max(float(np.abs(np.asarray(top["A"][int(p)][2], np.float64)
                                - lp[j][top["A"][int(p)][1]]).max())
                   for j, p in enumerate(compare) if int(p) in top["A"]) / ref_max

    def readings(tag, got, want):
        return {tag + "rms_err": rms_err(got, want), tag + "rel_err": rel_err(got, want),
                tag + "rms_err_worst_position": worst_position(got, want)}

    n_pre = len(check_chunks)
    past = [j for j, (a, n) in enumerate(check_chunks) if a % chunk == 0 and a]  # 8 past a boundary
    out = {
        "rms_err_past_boundary": rms_err(sys_logits[past], ref_logits[past]),
        "dropped_state_rms_err_past_boundary": rms_err(ctl_logits[past], ref_logits[past]),
        **readings("", sys_logits, ref_logits), "engine_link": link(sys_logits),
        "hit_vs_cold": hit_vs_cold / ref_max, "hit_same_tokens_and_top20": hit_same,
        "hit_positions": len(shared),
        "rms_err_prefill": rms_err(sys_logits[:n_pre], ref_logits[:n_pre]),
        "rms_err_decode": rms_err(sys_logits[n_pre:], ref_logits[n_pre:]),
        "argmax_agree": int((sys_logits.argmax(-1) == ref_logits.argmax(-1)).sum()),
        **readings("dropped_state_", ctl_logits, ref_logits),
        "dropped_state_engine_link": link(ctl_logits),
        # Held to nothing (PERF.md section 6): the state as a bfloat16 pool would
        # hold it, against the reference and against the system itself; the
        # system's error by depth, the whole model last.
        "bf16_state_rms_err_past_boundary": rms_err(bf16_logits[past], ref_logits[past]),
        **readings("bf16_state_", bf16_logits, ref_logits),
        "bf16_state_rms_err_decode": rms_err(bf16_logits[n_pre:], ref_logits[n_pre:]),
        "bf16_state_vs_system": readings("", bf16_logits, sys_logits),
        "depth_rms_err": {**{str(d): rms_err(sys_at[d], ref_at[d]) for d in sys_at},
                          str(mc.num_layers): rms_err(sys_logits, ref_logits)},
        "depth_rms_err_decode": {
            **{str(d): rms_err(sys_at[d][n_pre:], ref_at[d][n_pre:]) for d in sys_at},
            str(mc.num_layers): rms_err(sys_logits[n_pre:], ref_logits[n_pre:])},
        "ref_max_abs_logit": ref_max, "positions": int(len(compare)), "context": int(T),
        "seed": seed, "limits": held_to,
    }
    # The system against a reference that leaves a part of the mathematics out.
    for name, logits in left_out.items():
        out.update(readings(name + "_", sys_logits, logits))
        out[name + "_rms_err_past_boundary"] = rms_err(sys_logits[past], logits[past])
    out["bf16_state_over"] = [n for n in GRANITE_READINGS
                              if out["bf16_state_" + n] > held_to[n]]
    emit(tag + "_parity", t0, **out)
    if not rehearse:
        over = [f"{n} {out[n]} against its limit {limit}"
                for n, limit in held_to.items() if out[n] > limit]
        if over:
            fail(tag + ": " + "; ".join(over))
        if hit_same != len(shared):
            fail(f"{tag}: the hit's tokens or top-20 differ from the cold prefill's at "
                 f"{len(shared) - hit_same} of {len(shared)} positions")
        for control in par.get("must_fail", ("dropped_state",)):
            if not any(out[f"{control}_{n}"] > held_to[n] for n in GRANITE_READINGS):
                fail(f"{tag}: the control {control} passes every limit: too loose")
    print(json.dumps(dev), flush=True)


# `--child parity-k-exaone`: chipbench/configs/k-exaone-236b-a23b-8l-ep8.json at
# its published widths (two whole periods: six window layers and two full
# ones) against the float32 reference (dynamo_tpu/models/reference/
# exaone_moe.py: the window a mask over the full score matrix) on the same
# dequantised weights, computed layer by layer with the attention in query
# blocks.
#
# What runs.  (1) The ENGINE'S OWN programs decide every token: row A prefills
# a 3072-token prompt COLD in 512-token chunks (`engine._step_fn`) through
# both page pools, its window pages taken and given back by the engine's own
# block manager; row B the same prompt behind a 2048-token PREFIX HIT (its K/V
# table names A's first 128 pages, its window table the 8 window pages A held
# before position 2048, as a retained entry hands them to a resumed row);
# then both decode 64 tokens side by side in 16 fused chunks
# (`engine._multi_fn`: a chunk's window table begins at the page its first
# position's window reaches).  B's tokens and top-20 log-probabilities must
# EQUAL A's (`hit_vs_cold`).  (2) The check's own jit of the same forward with
# the engine's options, teacher-forced on A's tokens into fresh pages of both
# pools, gives whole logits 8 tokens into and at the end of every chunk and at
# every decode step; the engine's top-20 log-probabilities are read against
# them (`engine_link`).  (3) Those logits against the reference at the same
# positions, and the same for the LEADING LAYER alone (one window layer with
# the dense MLP: `depth1_*`, where one position of 128 shows above W8A8's
# noise).  Three controls, each of which must be over a limit: the window
# pages before the hit DROPPED (zeros read by a row resumed at 2048: the
# system's own programs, `dropped_*`); the window ONE POSITION SHORT (127: the
# system's leading layer with `sliding_window` 127 against the reference's
# 128, `short_depth1_*`, and the whole reference at 127 against itself at
# 128, `short_ref_*`); the window layers attending to the WHOLE CONTEXT (the
# reference without the mask against itself with it, `nowindow_ref_*`: the
# system's window tables hold the window's pages only and cannot express it).
# Limits and the readings they come from: PERF.md section 6.
EXAONE = {"config": "chipbench/configs/k-exaone-236b-a23b-8l-ep8.json", "prefix": 2048,
          "prompt": 3072, "decode": 64, "num_blocks": 1024, "q_block": 512}
EXAONE_REHEARSAL = dict(EXAONE, prefix=128, prompt=192, decode=8, num_blocks=128, q_block=64)
EXAONE_READINGS = ("rms_err", "rel_err", "rms_err_worst_position", "rms_err_past_hit")
# Each limit lies between what the system read and what a control read on the
# chip over seeds 28 / 29 / 30 (my chip run, PR 47, the final tree; PERF.md
# section 6), near their geometric mean: system | control.
EXAONE_LIMITS = {  # the most each may read
    # 0.087-0.100 | the window one position short 0.148-0.158 (the whole
    # reference at 127 against itself at 128), no window 1.29-1.32.  Without
    # the V gain (int8 pages under ONE scale a layer, K normed and V not) the
    # system read 0.540: a lower precision of the pages than the file states
    # fails this one.
    "rms_err": 0.12,
    "rel_err": 0.30,  # 0.181-0.189 | no window 1.31-1.39: the largest single logit error
    "rms_err_worst_position": 0.35,  # 0.193-0.222 | no window 1.34-1.36, dropped pages 1.31-1.33
    "rms_err_past_hit": 0.25,  # 0.042-0.090 | 1.31-1.33: 8 past the hit, the pages before it dropped
    "behind_hit_rms_err": 0.15,  # 0.085-0.099 | 0.233-0.257: every compared position behind the hit
    # The leading layer alone (a window layer and the dense MLP): 0.0343-0.0347 |
    # the SYSTEM with the window one position short 0.106-0.113.
    "depth1_rms_err": 0.06,
    "engine_link": 0.15,  # 0.054-0.078: the engine's own top-20 at chunk ends and decode steps
    "hit_vs_cold": 0.0,  # the same programs over the same values: equal to the bit
}


def child_parity_k_exaone(rehearse: bool) -> None:
    t0 = time.time()
    dev = child_device(rehearse)
    import types

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import TpuEngine
    from dynamo_tpu.models import lfm2
    from dynamo_tpu.models.config import ModelConfig, register_config
    from dynamo_tpu.models.family import RaggedBatch
    from dynamo_tpu.models.reference import exaone_moe as ref

    par = EXAONE_REHEARSAL if rehearse else EXAONE
    seed = int(os.environ.get("DSV32_PARITY_SEED", "28"))
    with open(os.path.join(HERE, par["config"])) as f:
        body = json.load(f)
    serve = dict(body["serve"])
    if rehearse:
        hf = dict(body["rehearsal"]["model"])
        serve.update(body["rehearsal"]["serve"])
    else:
        hf = {k: v for k, v in body.items() if k not in (
            "name", "source", "serve", "chips", "reduced", "assumed", "stands_for",
            "rehearsal", "notes")}
    mc = register_config(ModelConfig.from_hf_config(hf, name="parity-k-exaone"))
    kv_scale = serve.get("kv_scale", 1.0)
    cfg = EngineConfig(
        model=mc.name, block_size=serve["block_size"], num_blocks=par["num_blocks"],
        max_batch=serve["max_batch"], max_model_len=serve["max_model_len"],
        prefill_chunk=serve["prefill_chunk"], decode_steps=serve["decode_steps"],
        dtype=serve["dtype"], cache_dtype=serve["kv_cache_dtype"],
        kv_scale=kv_scale if kv_scale == "auto" else float(kv_scale),
        weight_quant=serve.get("weight_quant"), seed=20260900 + seed)
    engine = TpuEngine(cfg)
    mc, fam, kv = engine.model_config, engine.family, engine.kv
    kind = kv.beside  # engine/resume.py ``WindowPages``: the rows below hold pages as its rows do
    W, wb, wpool = mc.sliding_window, kind.blocks, kind.pool
    emit("k_exaone_engine", t0, **dev, attn_impl=engine.attn_impl,
         decode_kernel=engine.decode_kernel, prefill_kernel=engine.prefill_kernel,
         window_pool=[wpool.size, kind.tokens, kind.row_pages],
         hbm=(jax.local_devices()[0].memory_stats() or {}).get("bytes_in_use"))

    bs, S, PP, chunk = cfg.block_size, cfg.max_batch, cfg.max_blocks_per_seq, cfg.prefill_chunk
    steps = cfg.decode_steps
    n_prefix, n_prompt, n_dec = par["prefix"], par["prompt"], par["decode"]
    assert n_prefix % chunk == 0 and n_prompt % chunk == 0 and n_dec % steps == 0
    T = n_prompt + n_dec
    own = -(-T // bs) + 1  # K/V pages a row needs
    assert 4 * own <= cfg.num_blocks and own <= PP and T - n_prompt < W
    rng = np.random.default_rng(seed)
    tokens = np.zeros((T + 1,), np.int32)
    tokens[:n_prompt] = rng.integers(16, mc.vocab_size, n_prompt)

    def row(first, shared=0):
        """A row as the engine's batch builder and block manager see one: its
        K/V pages (the first ``shared`` of them row A's) and no window page yet."""
        table = list(range(shared)) + [first + i for i in range(own - shared)]
        return types.SimpleNamespace(prompt=[], output=[], block_ids=table, adapter_slot=-1,
                                     beside=types.SimpleNamespace(ids=[], base=0), num_computed=0)

    def prefill_batch(seq, start, n):
        """The engine's own batch of one prompt row (window pages taken and
        given back by ``WindowPages.grow`` as for a running row)."""
        seq.prompt, seq.num_computed = [int(t) for t in tokens[:start + n]], start
        return engine._build_ragged([(seq, start, n)])

    def resumed(first, zeros=False):
        """A row resumed at ``n_prefix`` behind row A's K/V pages, with the
        window pages A held before that point (``zeros``: with window pages
        that hold nothing instead: the control)."""
        seq = row(first, n_prefix // bs)
        at = n_prefix // bs
        seq.beside.base = max(0, at - wb)
        if zeros:
            seq.beside.ids = [wpool.take() for _ in range(at - seq.beside.base)]
        else:  # as a hit does: the pages kept with the block that ends at the point
            seq.beside.ids = list(wpool.resume([at - 1])[1])
        return seq

    samp = engine._sampling_arrays([])._replace(need_logprobs=np.asarray(True))
    chunks = lambda a: [(s, chunk) for s in range(a, n_prompt, chunk)]

    # ---- (1) the engine's own programs: A cold, B behind the hit, both decode
    t1 = time.time()
    params, cache = engine.params, engine.cache
    top = {"A": {}, "B": {}}  # position -> (token, top ids, their log-probabilities)
    row_a, pages_held = row(0), []
    for a, n in chunks(0):
        out, cache = engine._step_fn(params, cache, prefill_batch(row_a, a, n), samp)
        pages_held.append(len(row_a.beside.ids))
        if a + n == n_prefix:  # what a kept entry holds: the pages before the point
            at, held = n_prefix // bs, row_a.beside
            assert wpool.keep(at - 1, held.ids[max(0, at - wb) - held.base:at - held.base])
        top["A"][a + n - 1] = (int(np.asarray(out.tokens)[0]), np.asarray(out.top_ids)[0],
                               np.asarray(out.top_logprobs)[0])
    row_b = resumed(own)
    for a, n in chunks(n_prefix):
        out, cache = engine._step_fn(params, cache, prefill_batch(row_b, a, n), samp)
        top["B"][a + n - 1] = (int(np.asarray(out.tokens)[0]), np.asarray(out.top_ids)[0],
                               np.asarray(out.top_logprobs)[0])
    tokens[n_prompt] = top["A"][n_prompt - 1][0]
    pos0 = np.full((S,), -1, np.int32)
    tables, limits = np.zeros((S, PP), np.int32), np.zeros((S,), np.int32)
    wtables = np.zeros((S, kind.row_pages), np.int32)
    tok0 = np.zeros((S,), np.int32)
    for i, (name, seq) in enumerate((("A", row_a), ("B", row_b))):
        pos0[i], limits[i], tok0[i] = n_prompt, own * bs, top[name][n_prompt - 1][0]
        tables[i, :own] = seq.block_ids
    carry = (tok0, samp.steps, samp.counts)
    for d in range(n_dec // steps):
        at = pos0 + np.where(pos0 >= 0, d * steps, 0)
        for i, seq in enumerate((row_a, row_b)):
            seq.num_computed = int(at[i])
            kind.grow(seq, int(at[i]) + steps)
            kind.table_row(wtables, i, seq, int(at[i]))
        pages_held.append(len(row_a.beside.ids))
        outs, last, steps_f, counts_f, cache = engine._multi_fn(
            params, cache, *carry, at, (tables, wtables.copy()), limits, samp)
        carry = (last, steps_f, counts_f)
        toks, ids, lps = (np.asarray(x) for x in (outs.tokens, outs.top_ids, outs.top_logprobs))
        for k in range(steps):
            p = n_prompt + d * steps + k
            tokens[p + 1] = toks[k, 0]
            top["A"][p] = (int(toks[k, 0]), ids[k, 0], lps[k, 0])
            top["B"][p] = (int(toks[k, 1]), ids[k, 1], lps[k, 1])
    shared = sorted(set(top["A"]) & set(top["B"]))
    hit_vs_cold = max(float(np.abs(top["A"][p][2] - top["B"][p][2]).max()) for p in shared)
    hit_same = sum(int(top["A"][p][0] == top["B"][p][0]
                       and np.array_equal(top["A"][p][1], top["B"][p][1])) for p in shared)
    emit("k_exaone_engine_programs", t1, positions=len(shared), hit_same_tokens_and_top20=hit_same,
         hit_vs_cold_nats=hit_vs_cold, window_pages_held_by_a_row=[min(pages_held), max(pages_held)])

    # ---- (2) whole logits by the check's jit, teacher-forced on A's tokens
    def forward_of(config, scale):
        return jax.jit(
            lambda p, c, rb, dec: fam.forward(
                p, config, rb, c, decode=dec, attn_impl=engine.attn_impl, kv_scale=scale,
                decode_kernel=engine.decode_kernel, prefill_kernel=engine.prefill_kernel)[:2],
            static_argnums=3, donate_argnums=1)

    def decode_batch(seq, p):
        seq.num_computed = p
        kind.grow(seq, p + 1)
        t, ps_, kvl, wl = (np.zeros((S,), np.int32) for _ in range(4))
        sl, wsl = np.full((S,), -1, np.int32), np.full((S,), -1, np.int32)
        tb, wt = np.zeros((S, PP), np.int32), np.zeros((S, kind.row_pages), np.int32)
        base = kind.table_row(wt, 0, seq, p)
        t[0], ps_[0], kvl[0], wl[0] = tokens[p], p, p + 1, p + 1 - base * bs
        tb[0, :own] = seq.block_ids
        sl[0] = int(seq.block_ids[p // bs]) * bs + p % bs
        wsl[0] = int(seq.beside.ids[p // bs - seq.beside.base]) * bs + p % bs
        return RaggedBatch(t, ps_, sl, kvl, tb, np.arange(S + 1, dtype=np.int32),
                           np.asarray([S], np.int32), window_indices=wt, window_lens=wl,
                           window_slots=wsl)

    # The check's own chunking: every 512-token chunk as its first 8 tokens and
    # the rest, so that logits are read 8 tokens PAST each boundary, inside the
    # window of what lies before it.
    pieces = lambda a: [pc for s, n in chunks(a) for pc in ((s, 8), (s + 8, n - 8))]

    def system(cache, seq, start, fwd, params):
        """Logits [compared positions, V] of one pass of ``seq`` from ``start``."""
        out = []
        for a, n in pieces(start):
            logits, cache = fwd(params, cache, prefill_batch(seq, a, n), False)
            out.append(np.asarray(logits, np.float32)[0])
        for p in range(n_prompt, T):
            logits, cache = fwd(params, cache, decode_batch(seq, p), True)
            out.append(np.asarray(logits, np.float32)[0])
        wpool.release(seq.beside.ids)
        return np.stack(out), cache

    compare = np.asarray([a + n - 1 for a, n in pieces(0)] + list(range(n_prompt, T)))
    behind = compare >= n_prefix  # what a row resumed at the hit computes
    fwd = forward_of(mc, engine.kv_scale)
    t1 = time.time()
    sys_logits, cache = system(cache, row(2 * own), 0, fwd, params)
    emit("k_exaone_system", t1, positions=len(compare))
    t1 = time.time()
    ctl_logits, cache = system(cache, resumed(3 * own, zeros=True), n_prefix, fwd, params)
    emit("k_exaone_dropped_window_pages", t1)

    # ---- the leading layer alone (a window layer and the dense MLP), as stated and
    # with the window one position short
    t1 = time.time()
    depth1 = {}
    for tag, window in (("", W), ("short_", W - 1)):
        mc_1 = mc.with_overrides(num_layers=1, layer_types=mc.layer_types[:1],
                                 sliding_window=window)
        kept = {"layers": 1, "wattn": 1, "dense": 1}
        params_1 = {g: {k: a[:kept[g]] for k, a in v.items()} if g in kept else v
                    for g, v in params.items() if g not in ("attn", "moe", "shared")}
        params_1["attn"] = {k: a[:0] for k, a in params["attn"].items()}
        cache_1 = fam.create_cache(mc_1, cfg.num_blocks, bs, dtype=cache.pages.dtype,
                                   **kind.cache_kw)
        # The leading layer is the first WINDOW layer: its scale and its gain.
        La, n_attn = lfm2.layer_counts(mc)[1], len(mc.layer_types)
        scale_1 = None if engine.kv_scale is None else np.asarray(engine.kv_scale)[[La, n_attn + La]]
        depth1[tag], cache_1 = system(cache_1, row(2 * own), 0, forward_of(mc_1, scale_1), params_1)
        del cache_1, params_1
    emit("k_exaone_depth1", t1)

    # ---- the engine leaves the chip; its weights stay on the host
    host_params = jax.tree_util.tree_map(np.asarray, engine.params)
    del cache, params
    engine.params = engine.cache = None
    engine = None

    # ---- (3) the reference, layer by layer, on the dequantised weights
    t1 = time.time()

    def f32_leaf(group, name, i=None):
        leaves = host_params if group == "top" else host_params[group]
        w = leaves[name] if i is None else leaves[name][i]
        w = jnp.asarray(w, jnp.float32)
        if name + "_scale" in leaves:
            sc = leaves[name + "_scale"] if i is None else leaves[name + "_scale"][i]
            axis = lfm2.QUANT_AXES[group][name] - (0 if i is None else 1)
            w = w * jnp.expand_dims(jnp.asarray(sc), axis)
        return w

    Ld = mc.first_k_dense_replace

    def layer_f32(l):
        kinds = mc.layer_types
        group = "wattn" if kinds[l] == "sliding_attention" else "attn"
        i = sum(k == kinds[l] for k in kinds[:l])
        lp = {"dense_mlp": l < Ld}
        mlp_groups = (("dense", l),) if l < Ld else (("moe", l - Ld), ("shared", l - Ld))
        for g, at in (("layers", l), (group, i)) + mlp_groups:
            for name in host_params[g]:
                if not name.endswith("_scale"):
                    lp[name] = f32_leaf(g, name, at)
        return lp

    with jax.default_matmul_precision("highest"):
        embed, head = f32_leaf("top", "embed"), f32_leaf("top", "lm_head")
        final_norm = jnp.asarray(host_params["final_norm"], jnp.float32)
        pos = jnp.arange(T, dtype=jnp.int32)
        held = ref.held_experts(hf)
        logits_of = lambda h: np.asarray(
            ref.rms_norm(h[compare], final_norm, hf.get("rms_norm_eps", 1e-5)) @ head)
        ref_logits, ref_depth1 = {}, {}
        for tag, window in (("", None), ("short_", W - 1), ("nowindow_", 0)):
            h = embed[jnp.asarray(tokens[:T])]
            for l, kind in enumerate(mc.layer_types):
                h = ref.layer(layer_f32(l), hf, h, pos, kind, held, par["q_block"], window=window)
                if l == 0:
                    ref_depth1[tag] = logits_of(h)
            ref_logits[tag] = logits_of(h)
    emit("k_exaone_reference", t1)

    def rel_err(a, b):
        return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))

    def rms_err(a, b):
        return float(np.sqrt(np.mean((a - b) ** 2) / np.mean(b ** 2)))

    def worst_position(a, b):
        """The largest root-mean-square error of ONE position's logits."""
        return float(np.max(np.sqrt(np.mean((a - b) ** 2, axis=-1) / np.mean(b ** 2, axis=-1))))

    def log_softmax(x):
        x = np.asarray(x, np.float64)
        return x - x.max(-1, keepdims=True) - np.log(np.exp(x - x.max(-1, keepdims=True)).sum(
            -1, keepdims=True))

    want = ref_logits[""]
    ref_max = float(np.max(np.abs(want)))
    lp = log_softmax(sys_logits)
    link = max(float(np.abs(np.asarray(top["A"][int(p)][2], np.float64)
                            - lp[j][top["A"][int(p)][1]]).max())
               for j, p in enumerate(compare) if int(p) in top["A"]) / ref_max

    def readings(tag, got, want):
        return {tag + "rms_err": rms_err(got, want), tag + "rel_err": rel_err(got, want),
                tag + "rms_err_worst_position": worst_position(got, want)}

    past = [j for j, p in enumerate(compare) if p == n_prefix + 7]  # 8 past the hit
    n_pre = len(pieces(0))
    out = {
        **readings("", sys_logits, want), "engine_link": link,
        "rms_err_past_hit": rms_err(sys_logits[past], want[past]),
        "hit_vs_cold": hit_vs_cold / ref_max, "hit_same_tokens_and_top20": hit_same,
        "hit_positions": len(shared),
        "rms_err_prefill": rms_err(sys_logits[:n_pre], want[:n_pre]),
        "rms_err_decode": rms_err(sys_logits[n_pre:], want[n_pre:]),
        "argmax_agree": int((sys_logits.argmax(-1) == want.argmax(-1)).sum()),
        # control 1: the window pages before the hit dropped (the system's programs)
        **readings("dropped_", ctl_logits, want[behind]),
        "dropped_rms_err_past_hit": rms_err(ctl_logits[:1], want[past]),
        "behind_hit_rms_err": rms_err(sys_logits[behind], want[behind]),
        # the leading layer alone, and control 2: its window one position short
        **readings("depth1_", depth1[""], ref_depth1[""]),
        **readings("short_depth1_", depth1["short_"], ref_depth1[""]),
        **readings("short_ref_depth1_", ref_depth1["short_"], ref_depth1[""]),
        **readings("short_ref_", ref_logits["short_"], want),
        # control 3: the window layers attending to the whole context (reference side)
        **readings("nowindow_ref_", ref_logits["nowindow_"], want),
        **readings("nowindow_ref_depth1_", ref_depth1["nowindow_"], ref_depth1[""]),
        "ref_max_abs_logit": ref_max, "positions": int(len(compare)), "context": int(T),
        "seed": seed, "limits": EXAONE_LIMITS,
    }
    emit("k_exaone_parity", t0, **out)
    if not rehearse:
        over = [f"{n} {out[n]} against its limit {limit}"
                for n, limit in EXAONE_LIMITS.items() if out[n] > limit]
        if over:
            fail("k-exaone: " + "; ".join(over))
        if hit_same != len(shared):
            fail(f"k-exaone: the hit's tokens or top-20 differ from the cold prefill's at "
                 f"{len(shared) - hit_same} of {len(shared)} positions")
        for control, names in EXAONE_CONTROLS.items():
            if not any(out[got] > EXAONE_LIMITS[limit] for got, limit in names
                       if limit in EXAONE_LIMITS):
                fail(f"k-exaone: the control {control} passes every limit: too loose")
    print(json.dumps(dev), flush=True)


# Each control and the (its reading, the limit it must be over) pairs of which one suffices.
EXAONE_CONTROLS = {
    "window pages before the hit dropped": (
        ("dropped_rms_err", "behind_hit_rms_err"), ("dropped_rms_err_past_hit", "rms_err_past_hit")),
    "window one position short": (
        ("short_depth1_rms_err", "depth1_rms_err"), ("short_ref_rms_err", "rms_err")),
    "window layers attending to the whole context": (
        ("nowindow_ref_rms_err", "rms_err"), ("nowindow_ref_depth1_rms_err", "depth1_rms_err")),
}


def _tp_engine(cfg: dict, tp: int, kv_scale, layers: int = 0):
    """The comparison's engine; ``layers`` > 0 cuts DEPTH only (widths stay
    the published ones) for the shallow, tightly-toleranced comparison."""
    from dataclasses import replace

    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import TpuEngine
    from dynamo_tpu.models.config import get_config, register_config

    if cfg["model"] == "debug-tiny-kv4":  # the rehearsal's tp=4-able toy
        register_config(replace(
            get_config("debug-tiny"), name="debug-tiny-kv4",
            num_heads=8, num_kv_heads=4,
        ))
    model = cfg["model"]
    if layers:
        model = f"{model}-{layers}L"
        register_config(replace(
            get_config(cfg["model"]), name=model, num_layers=layers
        ))
    return TpuEngine(EngineConfig(
        model=model, block_size=cfg["block_size"],
        num_blocks=cfg["num_blocks"], max_batch=cfg["max_batch"],
        max_model_len=cfg["max_model_len"], prefill_chunk=cfg["prefill_chunk"],
        dtype=cfg["dtype"], cache_dtype="int8", kv_scale=kv_scale,
        weight_quant="int8", tp=tp, decode_steps=4,
    ))


def _tp_requests(engine, cfg: dict) -> list:
    """The same few greedy requests, with top-5 logprobs per token."""
    import asyncio

    import numpy as np

    from dynamo_tpu.llm.protocols import (
        PreprocessedRequest, SamplingOptions, StopConditions,
    )
    from dynamo_tpu.runtime.engine import Context, collect

    vocab = engine.model_config.vocab_size
    rng = np.random.default_rng(7)
    prompts = [
        [int(x) for x in rng.integers(0, vocab, cfg["prompt_len"] + 17 * i)]
        for i in range(cfg["prompts"])
    ]

    async def one(tokens):
        req = PreprocessedRequest(
            token_ids=tokens,
            stop_conditions=StopConditions(max_tokens=cfg["max_tokens"], ignore_eos=True),
            sampling_options=SamplingOptions(temperature=0.0, logprobs=5),
        ).to_dict()
        items = await collect(await engine.generate(Context(req)))
        toks = [t for it in items for t in it["token_ids"]]
        lps = [it["logprobs"] for it in items if it.get("logprobs")]
        return {"tokens": toks, "logprobs": lps}

    async def main():
        out = await asyncio.gather(*[one(p) for p in prompts])
        await engine.close()
        return out

    return asyncio.run(main())


def _flat_logprobs(entry) -> list:
    """Engine logprob payloads → [{chosen, top: {id: lp}} per token]."""
    out = []
    for lp in entry:
        for one in lp if isinstance(lp, list) else [lp]:
            out.append({"chosen": one["logprob"],
                        "top": {int(i): float(v) for i, v in one["top"]}})
    return out


def _compare(res: list, ref_results: list) -> dict:
    """Logprob agreement of two runs of the same greedy requests."""
    diffs, overlaps, compared, agree = [], [], 0, 0
    for got, want in zip(res, ref_results):
        g, w = _flat_logprobs(got["logprobs"]), _flat_logprobs(want["logprobs"])
        for i, (a, b) in enumerate(zip(g, w)):
            # Position i is comparable while both runs fed the same tokens:
            # always the prompt's last position (i == 0).
            if got["tokens"][:i] != want["tokens"][:i]:
                break
            common = set(a["top"]) & set(b["top"])
            overlaps.append(len(common))
            diffs += [abs(a["top"][t] - b["top"][t]) for t in common]
            compared += 1
            agree += got["tokens"][i] == want["tokens"][i]
    diffs.sort()
    return {
        "requests": len(res), "positions_compared": compared,
        "tokens_agreeing": agree, "top5_overlap_min": min(overlaps, default=0),
        "top5_overlap_mean": round(sum(overlaps) / max(1, len(overlaps)), 2),
        "max_abs_logprob_diff": diffs[-1] if diffs else None,
        "median_abs_logprob_diff": diffs[len(diffs) // 2] if diffs else None,
    }


def _drop(engine) -> None:
    """Free an engine's device arrays before the next one is built."""
    import gc

    engine.params = engine.cache = None
    gc.collect()


def child_tp1(rehearse: bool, ref_path: str) -> None:
    t0 = time.time()
    dev = child_device(rehearse)
    cfg = TP_REHEARSAL if rehearse else TP
    out = {}
    for name, layers in (("shallow", cfg["shallow_layers"]), ("full", 0)):
        t1 = time.time()
        engine = _tp_engine(cfg, 1, "auto", layers)
        out[name] = {
            "kv_scale": [float(x) for x in engine.kv_scale],
            "results": _tp_requests(engine, cfg),
        }
        emit(f"tp1_reference_{name}", t1, model=engine.cfg.model,
             layers=engine.model_config.num_layers,
             first_tokens=[r["tokens"][0] for r in out[name]["results"]])
        _drop(engine)
    with open(ref_path, "w") as f:
        json.dump(out, f)
    emit("tp1_reference", t0, **dev)
    print(json.dumps(dev), flush=True)


def child_tp4(rehearse: bool, ref_path: str) -> None:
    dev = child_device(rehearse)
    if dev["count"] < 4:
        fail(f"--chips 4 needs four devices, JAX reports {dev['count']}")
    import jax
    import numpy as np

    cfg = TP_REHEARSAL if rehearse else TP
    with open(ref_path) as f:
        ref = json.load(f)
    # Every check runs and prints before any of them ends the run: four
    # chips are too dear to learn one fact per call.
    failures = []

    # --- shallow: one layer at full width, tight tolerance
    t0 = time.time()
    engine = _tp_engine(cfg, 4, ref["shallow"]["kv_scale"], cfg["shallow_layers"])
    cmp_s = _compare(_tp_requests(engine, cfg), ref["shallow"]["results"])
    emit("tp4_vs_tp1_shallow", t0, layers=engine.model_config.num_layers,
         tol=TP_SHALLOW_TOL, **cmp_s)
    if cmp_s["positions_compared"] < cmp_s["requests"] or not (
        cmp_s["top5_overlap_min"] >= 1
        and cmp_s["max_abs_logprob_diff"] <= TP_SHALLOW_TOL
    ):
        failures.append(f"shallow tp=4 disagrees with tp=1: {cmp_s}")
    _drop(engine)

    # --- full depth
    t0 = time.time()
    engine = _tp_engine(cfg, 4, ref["full"]["kv_scale"])
    devices = jax.local_devices()[:4]

    # spread: every array has four addressable shards on four devices, and
    # each device's bytes are a quarter of the total.
    per_dev = {d.id: 0 for d in devices}
    total = 0
    n_sharded = 0
    for a in jax.tree_util.tree_leaves((engine.params, engine.cache)):
        total += a.nbytes
        shards = a.addressable_shards
        if {s.device.id for s in shards} != set(per_dev):
            failures.append(f"an array of shape {a.shape} is not on all four devices")
        if shards[0].data.nbytes * 4 == a.nbytes:
            n_sharded += 1
        for s in shards:
            per_dev[s.device.id] += s.data.nbytes
    share = max(per_dev.values())
    if share > total / 4 * 1.05 + (64 << 20):
        failures.append(f"a device holds {share} of {total} bytes: not spread four ways")
    pages = engine.cache.pages
    if len(pages.addressable_shards) != 4 or (
        pages.addressable_shards[0].data.shape[3] * 4 != pages.shape[3]
    ):
        failures.append("KV pages are not sharded four ways on the head axis")
    stats0 = [d.memory_stats() or {} for d in devices]
    emit("tp4_spread", t0, total_bytes=total, per_device_bytes=per_dev,
         sharded_leaves=n_sharded,
         bytes_in_use=[s.get("bytes_in_use") for s in stats0],
         peak_bytes_in_use=[s.get("peak_bytes_in_use") for s in stats0])

    # collectives in the compiled unified step
    t1 = time.time()
    from dynamo_tpu.models.llama import RaggedBatch

    c = engine.cfg
    S, PP, T = c.max_batch, c.max_blocks_per_seq, c.bucket_tokens(cfg["prompt_len"])
    cu = np.zeros((S + 1,), np.int32)
    cu[1:] = T
    rb = RaggedBatch(
        token_ids=np.zeros((T,), np.int32), positions=np.zeros((T,), np.int32),
        slot_mapping=np.full((T,), -1, np.int32),
        kv_lens=np.asarray([T] + [0] * (S - 1), np.int32),
        page_indices=np.zeros((S, PP), np.int32), cu_q_lens=cu,
        num_seqs=np.asarray([1], np.int32),
    )
    text = engine._step_fn.lower(
        engine.params, engine.cache, rb, engine._sampling_arrays([])
    ).compile().as_text()
    found = {
        k: len(re.findall(rf"= \S+ {k}(?:-start)?\(", text))
        for k in ("all-reduce", "all-gather", "reduce-scatter",
                  "collective-permute", "all-to-all")
    }
    emit("tp4_collectives", t1, step_tokens=T, **found,
         tpu_custom_call=text.count("tpu_custom_call"))
    if not rehearse and not (found["all-reduce"] or found["reduce-scatter"]):
        failures.append("the tp=4 step has no all-reduce: it is not sharded on tp")

    # the same requests; logprobs against the tp=1 reference
    t1 = time.time()
    cmp_f = _compare(_tp_requests(engine, cfg), ref["full"]["results"])
    stats1 = [d.memory_stats() or {} for d in devices]
    peaks = [s.get("peak_bytes_in_use") for s in stats1]
    emit("tp4_vs_tp1_full", t1, layers=engine.model_config.num_layers,
         tol=TP_FULL_TOL, **cmp_f, peak_bytes_in_use=peaks, share_bytes=share,
         peak_allowance=TP_PEAK_ALLOWANCE)
    if cmp_f["positions_compared"] < cmp_f["requests"] or not (
        cmp_f["top5_overlap_min"] >= 1
        and cmp_f["max_abs_logprob_diff"] <= TP_FULL_TOL
    ):
        failures.append(f"full-depth tp=4 disagrees with tp=1: {cmp_f}")
    if not rehearse:
        # Chip 0 is where everything used to land before sharding.
        for d, peak in zip(devices, peaks):
            if peak is None or peak > share + TP_PEAK_ALLOWANCE:
                failures.append(
                    f"device {d.id} peaked at {peak} bytes: more than its "
                    f"share {share} + {TP_PEAK_ALLOWANCE}")
    if failures:
        fail("; ".join(failures))
    print(json.dumps(dev), flush=True)


# ======================================================================== main
def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--child", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--ref", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(HERE, "dynamo_tpu")):
        fail("no dynamo_tpu package next to this script: nothing to smoke")
    if args.child:
        sys.path.insert(0, HERE)
        {"parity": child_parity,
         "parity-dsv32": child_parity_dsv32,
         "parity-kimi-k2": child_parity_kimi_k2,
         "parity-lfm2": child_parity_lfm2,
         "parity-granite": child_parity_granite,
         "parity-kimi-linear": child_parity_kimi_linear,
         "parity-jamba2": child_parity_jamba2,
         "parity-k-exaone": child_parity_k_exaone,
         "tp1": lambda r: child_tp1(r, args.ref),
         "tp4": lambda r: child_tp4(r, args.ref)}[args.child](args.rehearse_cpu)
        return
    t0 = time.time()
    dev = (run_four_chips if args.chips == 4 else run_one_chip)(args.rehearse_cpu)
    emit("total", t0, chips=args.chips)
    # The contract's last line, nothing added.  A rehearsal is not a result.
    print(json.dumps({"ok": not args.rehearse_cpu, "device": dev}), flush=True)


if __name__ == "__main__":
    main()
