#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python3 chipbench/run.py --workload W --seed N --seconds S --trace 0|1

This process IS the server: the main thread calls ``dynamo_tpu.cli.main``
with the argv an operator would type (``run in=http out=tpu ...``, flags from
the cell's configuration file), so the entry point, HTTP service,
preprocessor, scheduler, engine and kernels are the normal path; only the
process that holds the chip can record a ``jax.profiler`` trace of it.  A
helper thread drives the phases — wait for /health, start the load generator
(a child that never imports JAX: loadgen.py), trace the middle of the window
when asked, read what the generator measured, SIGTERM this process — and the
contract's one JSON line is printed after ``main`` has returned.

``--rehearse-cpu`` walks the same control flow on the CPU backend at the
configuration's ``rehearsal`` size.  A rehearsal is never a result: its line
says ``"correct": false`` and ``"rehearsal": true``.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import socket  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
import traceback  # noqa: E402
import urllib.error  # noqa: E402
import urllib.request  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import loader, promtext, stats, trace_reduce  # noqa: E402

READY_TIMEOUT_S = 1100.0
WARM_SECONDS = 5.0
WARM_MAX_OUTPUT = 32
# The probe (loadgen.probe_verdict): `chunks` whole prefill chunks and a tail
# shorter than a page, so that its prefix hit (the prompt's whole pages) begins
# ON a chunk boundary and the hit's one prompt step is the cold prefill's last
# step: one program over the same values.  Cold against hit and hit against
# hit are then exact comparisons, in every cell, whatever its pages' type: the
# limit is 0 nats (README; the readings: PERF.md section 2).  A hit that begins
# inside a chunk is another chunking of the same prompt and reads 0.02-0.16
# nats at the first position, as much as another prompt does.
PROBE = {"chunks": 2, "tail_tokens": 8, "max_tokens": 32, "seed": 20260927,
         "limits": {"hit_gap": 0.0, "cold_gap": 0.0}}
TRACE_SECONDS = 3.0
# Keys of a configuration file that are the harness's; every other top-level
# key is the model's HF-style config.json and goes to --model-config.
CONFIG_KEYS = {"name", "source", "serve", "chips", "reduced", "assumed",
               "stands_for", "rehearsal", "notes"}


def log(msg: str) -> None:
    print(f"chipbench[{time.time() - T_PROCESS_START:7.1f}s] {msg}", file=sys.stderr, flush=True)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def model_of(config: dict, rehearse: bool) -> dict:
    if rehearse:
        return dict(config["rehearsal"]["model"])
    return {k: v for k, v in config.items() if k not in CONFIG_KEYS}


def serve_of(config: dict, rehearse: bool) -> dict:
    serve = dict(config["serve"])
    if rehearse:
        serve.update(config["rehearsal"].get("serve", {}))
    return serve


def serve_argv(config: dict, model_path: str, port: int, rehearse: bool) -> list:
    argv = ["run", "in=http", "out=tpu", "--model-config", model_path,
            "--model", config["name"], "--host", "127.0.0.1", "--port", str(port)]
    for flag, value in serve_of(config, rehearse).items():
        argv += ["--" + flag.replace("_", "-"), str(value)]
    return argv


def probe_of(serve: dict, cell: str) -> dict:
    """The probe's sizes under a cell's serving flags (see PROBE)."""
    chunk, page = int(serve["prefill_chunk"]), int(serve.get("block_size", 16))
    prompt_len = PROBE["chunks"] * chunk + PROBE["tail_tokens"]
    if chunk % page or not 0 < PROBE["tail_tokens"] < page \
            or prompt_len + PROBE["max_tokens"] > int(serve["max_model_len"]):
        raise loader.BenchmarkError(
            f"{cell}: a probe of {PROBE['chunks']} chunks of {chunk} tokens and "
            f"{PROBE['tail_tokens']} more does not hit on a chunk boundary with pages of {page}, "
            f"or does not fit max_model_len {serve['max_model_len']}")
    return {"prompt_len": prompt_len, "max_tokens": PROBE["max_tokens"], "seed": PROBE["seed"],
            "limits": dict(PROBE["limits"])}


def build_native() -> None:
    """native/build/ is git-ignored; the C++ block hasher is what a deployment
    serves with, so build it here from source (make is a no-op when fresh)."""
    p = subprocess.run(["make", "-C", os.path.join(ROOT, "native")],
                       stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    if p.returncode != 0:
        sys.stderr.write(p.stdout)
        raise SystemExit("chipbench: make -C native failed")


def evaluate_end_to_end(spec: dict, window: dict, setup_s: float):
    """An end-to-end metric from its file ``end_to_end/<name>.json``."""
    kind = spec["kind"]
    if kind == "setup":
        return setup_s, None
    if kind == "window_value":
        return window[spec["key"]] * spec.get("scale", 1.0), None
    if kind == "percentile":
        xs = window[spec["of"]]
        try:
            return stats.percentile(xs, spec["q"]) * spec.get("scale", 1.0), None
        except stats.TooFewSamples as e:
            if not xs:
                return None, str(e)
            return stats.percentile(xs, spec["q"], min_beyond=0) * spec.get("scale", 1.0), str(e)
    raise loader.BenchmarkError(f"end_to_end/{spec['name']}.json: unknown kind {kind!r}")


class Run:
    def __init__(self, args, cell: dict):
        self.args, self.cell = args, cell
        self.rehearse = args.rehearse_cpu
        self.config = cell["config"]
        self.model = model_of(self.config, self.rehearse)
        self.serve_flags = serve_of(self.config, self.rehearse)
        self.port = free_port()
        self.tmp = tempfile.mkdtemp(prefix="chipbench-")
        self.server_done = threading.Event()
        self.error: str | None = None
        self.line: dict | None = None
        self.device: dict = {}
        self.peaks: dict = {}

    # ------------------------------------------------------------ main thread
    def check_device(self) -> None:
        import jax

        devs = jax.devices()
        self.device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
                       "count": len(devs)}
        if self.rehearse:
            if self.device["platform"] != "cpu":
                raise SystemExit("chipbench: --rehearse-cpu is for the CPU backend")
            self.peaks = {"hbm_bytes_per_s": 1.0}
            return
        if self.device["platform"] != "tpu":
            raise SystemExit(f"chipbench: JAX found no accelerator ({self.device}); "
                             "--rehearse-cpu walks the flow without one")
        if len(devs) < self.cell["cell"]["chips"]:
            raise SystemExit(f"chipbench: the cell needs {self.cell['cell']['chips']} chips, "
                             f"JAX found {len(devs)}")
        self.peaks = loader.load_peaks(self.device["kind"])

    def serve_until_stopped(self) -> None:
        from dynamo_tpu import cli

        model_path = os.path.join(self.tmp, "model_config.json")
        with open(model_path, "w") as f:
            json.dump(dict(self.model, _name=self.config["name"]), f)
        argv = serve_argv(self.config, model_path, self.port, self.rehearse)
        log("serving: python -m dynamo_tpu.cli " + " ".join(argv))
        try:
            cli.main(argv)
        finally:
            self.server_done.set()

    # ---------------------------------------------------------- helper thread
    def url(self, path: str = "") -> str:
        return f"http://127.0.0.1:{self.port}{path}"

    def wait_ready(self) -> None:
        t_end = time.time() + READY_TIMEOUT_S
        while time.time() < t_end:
            if self.server_done.is_set():
                raise RuntimeError("the server exited before it was ready")
            try:
                with urllib.request.urlopen(self.url("/health"), timeout=2.0) as r:
                    if r.status == 200:
                        return
            except (urllib.error.URLError, OSError):
                time.sleep(0.5)
        raise RuntimeError(f"the server was not ready after {READY_TIMEOUT_S:.0f}s")

    def job(self, results_path: str) -> dict:
        mix, params = dict(self.cell["mix"]), self.cell["params"]
        if self.rehearse:
            from chipbench import traffic

            scale = self.config["rehearsal"]["length_scale"]
            mix["prompt"] = traffic.scale_dist(mix["prompt"], scale)
            mix["output"] = traffic.scale_dist(mix["output"], scale)
        probe = probe_of(self.serve_flags, self.cell["name"])
        probe["control"] = bool(self.args.probe_control)
        return {
            "mode": "run", "url": self.url(), "model": self.config["name"],
            "mix": mix, "params": params, "seed": self.args.seed,
            "seconds": self.args.seconds, "warm_seconds": WARM_SECONDS,
            "warm_max_output": WARM_MAX_OUTPUT, "vocab": self.model["vocab_size"],
            "probe": probe, "results_path": results_path,
        }

    def run_generator(self, job: dict, on_event) -> dict:
        """Run loadgen.py to its end; ``on_event`` sees each of its lines."""
        job_path = os.path.join(self.tmp, "job.json")
        with open(job_path, "w") as f:
            json.dump(job, f)
        env = {k: v for k, v in os.environ.items()
               if not k.startswith(("JAX_", "XLA_", "TPU_", "DYN_"))}
        proc = subprocess.Popen(
            [sys.executable, os.path.join(ROOT, "chipbench", "loadgen.py"), job_path],
            stdout=subprocess.PIPE, text=True, cwd=ROOT, env=env)
        try:
            for line in proc.stdout:
                line = line.strip()
                if line.startswith("{"):
                    on_event(json.loads(line))
            rc = proc.wait()
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
        if rc != 0:
            raise RuntimeError(f"the load generator exited {rc}")
        with open(job["results_path"]) as f:
            return json.load(f)

    def trace_window(self, t_window_epoch: float, out: dict) -> None:
        """Trace ``TRACE_SECONDS`` around the middle of the window."""
        import jax

        dur = min(TRACE_SECONDS, self.args.seconds / 3.0)
        start = t_window_epoch + self.args.seconds / 2.0 - dur / 2.0
        time.sleep(max(0.0, start - time.time()))
        trace_dir = os.path.join(self.tmp, "trace")
        # Device and XLA host events only: the Python tracer would log every
        # call of the serving threads and slow the host it shares.
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        # The interval in which the trace surely records: starting and
        # stopping take their own time (stop_trace writes the file).
        out["t_start_s"] = time.time() - t_window_epoch
        time.sleep(dur)
        out["t_stop_s"] = time.time() - t_window_epoch
        jax.profiler.stop_trace()
        out["dir"] = trace_dir

    def drive(self) -> None:
        try:
            self.wait_ready()
            t_ready = time.time()
            log(f"ready after {t_ready - T_PROCESS_START:.1f}s")
            marks, tracing, tracer = {}, {}, []

            def on_event(ev: dict) -> None:
                marks[ev["event"]] = ev["t_epoch"]
                log(f"generator: {ev['event']}")
                if ev["event"] == "window_start" and self.args.trace:
                    t = threading.Thread(target=self.trace_window,
                                         args=(ev["t_epoch"], tracing), daemon=True)
                    t.start()
                    tracer.append(t)

            res = self.run_generator(self.job(os.path.join(self.tmp, "results.json")), on_event)
            for t in tracer:
                t.join()
            self.line = self.report(res, marks, tracing, t_ready)
        except BaseException as e:  # noqa: BLE001 — reported, then the run fails
            self.error = f"{type(e).__name__}: {e}"
            traceback.print_exc()
        finally:
            if not self.server_done.is_set():
                os.kill(os.getpid(), signal.SIGTERM)

    # ------------------------------------------------------------- the result
    def report(self, res: dict, marks: dict, tracing: dict, t_ready: float) -> dict:
        import jax

        window = res["window"]
        before, after = promtext.parse(res["metrics_before"]), promtext.parse(res["metrics_after"])
        setup_s = marks["window_start"] - T_PROCESS_START
        programs = {
            "before": promtext.value(before, "dynamo_tpu_engine_compiled_programs"),
            "after": promtext.value(after, "dynamo_tpu_engine_compiled_programs"),
        }
        probe = res["probe"]
        compared = {
            "short_answers": {"value": window["short"], "limit": 0},
            "probe_hit_gap": {"value": probe["hit_gap"], "limit": probe["limits"]["hit_gap"]},
            "probe_cold_gap": {"value": probe["cold_gap"], "limit": probe["limits"]["cold_gap"]},
            "programs_compiled_in_window": {
                "value": None if None in programs.values()
                else programs["after"] - programs["before"], "limit": 0},
        }
        checks = {
            "no_short_answers": window["short"] == 0,
            "probe_identical": probe["identical"],
            "no_compile_in_window": programs["before"] is not None
            and programs["before"] == programs["after"],
            "device_in_peaks": bool(self.peaks) and not self.rehearse,
            "something_completed": window["n_completed"] > 0,
        }
        mem = [d.memory_stats() or {} for d in jax.local_devices()]
        peak = max(m.get("peak_bytes_in_use", 0) for m in mem)
        device = dict(self.device, memory_peak_bytes=int(peak))
        notes, seen, breakdown, host_trace = {}, {}, None, None
        for m in self.cell["end_to_end"]:
            spec = loader.read_json(loader.data_file("end_to_end", m["name"]))
            value, note = evaluate_end_to_end(spec, window, setup_s)
            if value is not None:
                seen[m["name"]] = {"value": value, "unit": m["unit"]}
            if note:
                notes[m["name"]] = note
        if self.args.trace:
            metrics, trace = {}, None
            if tracing.get("dir"):
                path = trace_reduce.find_xplane(tracing["dir"])
                t_load = time.time()
                # The CPU has no device plane: a rehearsal walks the code on host lines.
                planes, extent, host = (
                    trace_reduce.load_xplane(path, re.compile(r"^/host:CPU$"), lines=None)
                    if self.rehearse else trace_reduce.load_xplane(path))
                t_reduce = time.time()
                trace = trace_reduce.DeviceTrace(planes, tracing["t_start_s"],
                                                 tracing["t_stop_s"], extent, host)
                device.update(busy_s=trace.busy_s, window_s=trace.window_s)
                breakdown = trace.breakdown()
                checks["device_ran"] = trace.busy_s > 0 or self.rehearse
            ctx = {"model": self.model, "serve": self.serve_flags, "peaks": self.peaks,
                   "before": before, "after": after, "window": window,
                   "seconds": self.args.seconds, "trace": trace, "cell": self.cell}
            for spec in self.cell["per_layer"]:
                value = loader.load_reader(spec["reader"]).read(ctx, **spec.get("args", {}))
                if value is not None:
                    metrics[spec["name"]] = {"value": value, "unit": spec["unit"]}
            if trace is not None:
                # For the reader: what the trace's file cost to load and to
                # reduce (after the window, in no timed metric), how much of
                # the host's side was kept, and what the clock check saw.
                host_trace = {"file_bytes": os.path.getsize(path), "load_s": t_reduce - t_load,
                              "reduce_s": time.time() - t_reduce,
                              "annotations": len(host["annotations"]),
                              "launches": len(host["launches"]), "clock": trace.clock()}
        else:
            metrics = seen
            checks["every_metric_read"] = len(metrics) == len(self.cell["end_to_end"])
            if notes and self.args.seconds >= self.cell["run_seconds"]:
                checks["tails_supported"] = False
        correct = all(checks.values())
        line = {
            "correct": bool(correct) and not self.rehearse,
            "attempted": window["attempted"], "failed": window["failed"],
            "metrics": metrics, "device": device,
        }
        if breakdown is not None:
            line["breakdown"] = breakdown
        line.update({
            "workload": self.cell["name"], "seed": self.args.seed,
            "seconds": self.args.seconds, "rehearsal": self.rehearse, "checks": checks,
            "samples": {"completed_in_window": window["n_completed"],
                        "with_tpot": window["n_tpot"],
                        "in_flight_at_end": window["in_flight_at_end"],
                        "pool": window["pool"], "wrapped": window["wrapped"]},
            "generator_late_ms": window["generator_late_ms"],
            "errors": window["errors"], "short_tails": notes,
            # What the traced run saw end to end (3 s of it under the profiler):
            # beside the per-layer metrics, never in place of an untraced run.
            "end_to_end_seen": {k: v["value"] for k, v in seen.items()},
            "host_trace": host_trace,
            "output_tokens_per_s": window["output_tokens_per_s"],
            "setup": {
                "ready_s": t_ready - T_PROCESS_START,
                "warmup_s": promtext.value(after, "dynamo_tpu_engine_warmup_seconds"),
                "warm_traffic_and_probe_s": marks["window_start"] - t_ready,
                "cache_hits": promtext.value(after, "dynamo_tpu_engine_compile_cache_hits"),
                "cache_misses": promtext.value(after, "dynamo_tpu_engine_compile_cache_misses"),
                "compiled_programs": programs,
            },
            "drain_s": window["t_drained"] - self.args.seconds,
            # Reported, never judged: the text (one glyph stands for most
            # ids), the first run after the window against its hit, the control.
            "probe_text_identical": probe["text_identical"],
            "probe": {k: probe.get(k) for k in ("after_first_gap", "positions", "seconds",
                                                "values_per_position", "errors", "control")},
            "warm": {k: res["warm"][k] for k in ("attempted", "failed")},
            "hbm_bytes_in_use": mem[0].get("bytes_in_use"),
            "engine": promtext.labels_of(after, "dynamo_tpu_engine_info"),
            # Last: every number `correct` compared, beside its limit.
            "compared": compared,
        })
        return line


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse-cpu", action="store_true")
    p.add_argument("--probe-control", type=int, choices=(0, 1), default=0,
                   help="also send the probe with its last prompt token changed and report "
                        "what the comparison says of it (never part of a check's run)")
    return p.parse_args(argv)


def execute(run: Run):
    """Serve on this thread while ``run.drive`` works on another; returns
    (exit code, the line ``run.report`` made or None)."""
    if not os.path.isdir(os.path.join(ROOT, "dynamo_tpu")):
        print("chipbench: the program (dynamo_tpu/) is not in this checkout", file=sys.stderr)
        shutil.rmtree(run.tmp, ignore_errors=True)
        return 1, None
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if run.rehearse:
        # The CPU rehearsal is ASKED for, here and nowhere else.
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["DYN_PALLAS_INTERPRET"] = "1"
    try:
        run.check_device()
        build_native()
        helper = threading.Thread(target=run.drive, name="chipbench-driver")
        helper.start()
        try:
            run.serve_until_stopped()
        except BaseException as e:  # noqa: BLE001
            run.error = run.error or f"server: {type(e).__name__}: {e}"
            traceback.print_exc()
        helper.join()
    finally:
        shutil.rmtree(run.tmp, ignore_errors=True)
    if run.error or run.line is None:
        print(f"chipbench: FAILED: {run.error}", file=sys.stderr, flush=True)
        return 1, None
    return 0, run.line


def main(argv=None) -> int:
    args = parse_args(argv)
    try:
        cell = loader.load_cell(args.workload)
    except loader.BenchmarkError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    rc, line = execute(Run(args, cell))
    if rc == 0:
        sys.stdout.flush()
        print(json.dumps(line), flush=True)
    log(f"exit {rc}")
    if rc == 0:
        for name, c in line["compared"].items():
            print(f"compared {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
        sys.stderr.flush()
    return rc


if __name__ == "__main__":
    sys.exit(main())
