"""run.py's own pieces, and the whole command rehearsed on the CPU backend at
the configurations' rehearsal size (a rehearsal is never a result)."""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import loader, run as harness, stats  # noqa: E402

CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def test_importing_the_harness_imports_no_jax():
    code = ("import sys; sys.path.insert(0, %r); import chipbench.run, chipbench.sweep\n"
            "assert not [m for m in sys.modules if m.split('.')[0] in ('jax', 'dynamo_tpu')]" % ROOT)
    p = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, timeout=60)
    assert p.returncode == 0, p.stderr


def test_the_server_gets_the_argv_an_operator_would_type():
    cfg = loader.read_json(loader.data_file("configs", "qwen2.5-7b"))
    argv = harness.serve_argv(cfg, "/t/m.json", 8123, rehearse=False)
    assert argv[:3] == ["run", "in=http", "out=tpu"]
    joined = " ".join(argv)
    for flag in ("--model-config /t/m.json", "--model qwen2.5-7b", "--port 8123",
                 "--weight-quant int8", "--kv-cache-dtype int8", "--kv-scale auto",
                 "--num-blocks 12288", "--max-batch 32", "--prefill-chunk 512",
                 "--max-model-len 4096", "--decode-steps 4", "--dtype bfloat16"):
        assert flag in joined
    assert "--no-warmup" not in joined
    model = harness.model_of(cfg, rehearse=False)
    assert model["hidden_size"] == 3584 and model["num_hidden_layers"] == 28
    assert not set(model) & harness.CONFIG_KEYS
    tiny = harness.serve_argv(cfg, "/t/m.json", 1, rehearse=True)
    assert "--dtype float32" in " ".join(tiny) and "--weight-quant int8" in " ".join(tiny)
    assert harness.model_of(cfg, rehearse=True)["hidden_size"] == 64


def test_end_to_end_metrics_are_evaluated_from_their_files():
    window = {"ttft_s": [i / 1000 for i in range(1, 201)], "tpot_s": [0.02] * 5,
              "output_tokens_per_s": 812.5}
    spec = loader.read_json(loader.data_file("end_to_end", "ttft_ms_p50"))
    assert harness.evaluate_end_to_end(spec, window, 1.0) == (pytest.approx(100.0), None)
    spec = loader.read_json(loader.data_file("end_to_end", "tpot_ms_p90"))
    value, note = harness.evaluate_end_to_end(spec, window, 1.0)
    assert value == pytest.approx(20.0) and "5 samples" in note  # reported, and flagged
    spec = loader.read_json(loader.data_file("end_to_end", "output_tokens_per_s"))
    assert harness.evaluate_end_to_end(spec, window, 1.0) == (812.5, None)
    spec = loader.read_json(loader.data_file("end_to_end", "setup_s"))
    assert harness.evaluate_end_to_end(spec, window, 251.5) == (251.5, None)
    assert harness.evaluate_end_to_end({"name": "x", "kind": "percentile", "of": "tpot_s", "q": 90},
                                       {"tpot_s": []}, 1.0)[0] is None
    with pytest.raises(loader.BenchmarkError):
        harness.evaluate_end_to_end({"name": "x", "kind": "mode"}, window, 1.0)
    assert stats.MIN_BEYOND == 10


def _run(args, cwd=ROOT, timeout=300):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_RUN", None)
    return subprocess.run([sys.executable, os.path.join(cwd, "chipbench", "run.py"), *args],
                          capture_output=True, text=True, timeout=timeout, cwd=cwd, env=env)


def test_without_a_chip_the_command_fails_and_prints_no_result():
    p = _run(["--workload", "qwen2.5-7b.chat-open", "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert p.returncode != 0 and "no accelerator" in p.stderr
    assert not [l for l in p.stdout.splitlines() if l.startswith("{")]


def test_an_unknown_cell_fails_before_anything_starts():
    p = _run(["--workload", "nope.nope", "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert p.returncode != 0 and "no workload" in p.stderr and not p.stdout.strip()


def test_alone_with_its_own_files_the_command_fails(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(["--workload", "qwen2.5-7b.chat-open", "--seed", "1", "--seconds", "1",
              "--trace", "0", "--rehearse-cpu"], cwd=str(tmp_path))
    assert p.returncode != 0 and not [l for l in p.stdout.splitlines() if l.startswith("{")]


# slow: each case is a whole server plus a generator for 20 s on all cores, and
# tier-1's timing-sensitive tests (migration races, burst cadence) flake under
# that load.  Run by hand: pytest tests/chipbench -m slow
@pytest.mark.slow
@pytest.mark.parametrize("cell,trace", [("qwen2.5-7b.prefill-closed", "1"),
                                        ("qwen2.5-7b.chat-open", "0")])
def test_rehearsal_walks_the_whole_flow_and_is_never_a_result(cell, trace):
    p = _run(["--workload", cell, "--seed", "3000000001", "--seconds", "4",
              "--trace", trace, "--rehearse-cpu"])
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert CONTRACT_KEYS <= set(line)
    assert line["rehearsal"] is True and line["correct"] is False
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] >= 1
    assert line["attempted"] > 0 and line["failed"] == 0
    checks = line["checks"]
    assert checks["probe_identical"] and checks["no_compile_in_window"]
    assert checks["no_short_answers"] and not checks["device_in_peaks"]
    loaded = loader.load_cell(cell)
    wanted = loaded["per_layer"] if trace == "1" else loaded["end_to_end"]
    names = {m["name"] for m in wanted}
    assert set(line["metrics"]) <= names and line["metrics"]
    for name, m in line["metrics"].items():
        assert isinstance(m["value"], (int, float)) and loader.check_unit(m["unit"])
    if trace == "1":
        assert {"busy_s", "window_s"} <= set(line["device"]) and "breakdown" in line
        assert "prefix_hit_rate" in line["metrics"]
    else:
        assert line["metrics"]["setup_s"]["value"] > 0 and "ttft_ms_p50" in line["metrics"]
        assert line["generator_late_ms"]["n"] == line["attempted"]


# ------------------------------------------------ the rest of a run, no chip
# The harness's look for a chip is skipped and the server is a fake one; the
# generator's run of a cell and Run.report are the real ones.  A sound server
# reads correct; each fault planted under the timed path reads not correct.
FAULTS = {
    "sound": ({}, None),
    "an answer altered where it is produced: the hit after the window is another":
        ({"nudge": {4: 1e-3}}, "probe_identical"),
    "cold prefill and the prefix hit part": ({"nudge": {1: 0.5}}, "probe_identical"),
    "cold prefill and the prefix hit part by a rounding":
        ({"nudge": {1: 1e-5}}, "probe_identical"),
    "answers a token short": ({"short_by": 1}, "no_short_answers"),
    "a program compiled inside the window": ({"programs_step": 1}, "no_compile_in_window"),
}


@pytest.mark.parametrize("fault", list(FAULTS))
async def test_report_reads_correct_only_on_a_sound_server(fault):
    import argparse
    import time

    import aiohttp

    from chipbench import loadgen
    from test_chipbench_loadgen import FakeServer, _nudged

    server, failing = FAULTS[fault]
    server = dict(server)
    if "nudge" in server:
        server["top_values"] = _nudged(server.pop("nudge"))
    cell = loader.load_cell("kimi-k2-6l-ep32.agent-shared")
    args = argparse.Namespace(workload=cell["name"], seed=3000000001, seconds=0.6, trace=0,
                              rehearse_cpu=False, probe_control=0)
    r = harness.Run(args, cell)
    try:
        r.peaks, r.device = {"hbm_bytes_per_s": 1.0}, {"platform": "tpu", "kind": "fake", "count": 1}
        mix = {"loop": "closed", "prompt": {"dist": "uniform", "min": 30, "max": 40},
               "output": {"dist": "uniform", "min": 8, "max": 12},
               "sharing": {"kind": "shared_prefix", "groups": 2, "prefix_len": 16}}
        async with FakeServer(gap_s=0.002, **server) as srv, aiohttp.ClientSession() as s:
            job = dict(r.job("unused"), url=srv.url, mix=mix, vocab=300, warm_seconds=0.2,
                       params={"clients": 3, "pool_per_s": 10.0})
            assert job["probe"]["prompt_len"] == 2 * 512 + 8  # two chunks and a tail
            job["probe"].update(prompt_len=20, max_tokens=8)
            res = await loadgen.run_cell(job, s)
        line = r.report(res, {"window_start": time.time()}, {}, time.time())
    finally:
        shutil.rmtree(r.tmp, ignore_errors=True)
    assert CONTRACT_KEYS <= set(line) and list(line)[-1] == "compared"
    assert line["correct"] is (failing is None), (fault, line["checks"], line["compared"])
    failed = [k for k, v in line["checks"].items() if not v]
    # An answer cut short fails more than its own check: nothing completes.
    assert failed == [failing] if failing in ("probe_identical", "no_compile_in_window") \
        else failing in failed if failing else not failed
    c = line["compared"]
    assert set(c) == {"short_answers", "probe_hit_gap", "probe_cold_gap",
                      "programs_compiled_in_window"}
    assert all(set(v) == {"value", "limit"} for v in c.values())
    assert c["probe_cold_gap"]["limit"] == harness.PROBE["limits"]["cold_gap"] == 0.0
    assert c["probe_hit_gap"]["limit"] == harness.PROBE["limits"]["hit_gap"] == 0.0
    assert line["samples"]["wrapped"] == line["attempted"] - line["samples"]["pool"] > 0
    assert line["probe_text_identical"] is (failing != "no_short_answers")


@pytest.mark.parametrize("cell", [w["name"] for w in loader.read_json(
    os.path.join(ROOT, "BENCHMARK.json"))["workloads"]])
@pytest.mark.parametrize("rehearse", [False, True])
def test_every_cells_probe_hits_on_a_chunk_boundary_under_exact_limits(cell, rehearse):
    """The relation that makes cold against hit an exact comparison, for every
    cell that is there or comes: whole chunks and a tail inside one page, so the
    hit (the prompt's whole pages) begins where the cold prefill's last step does."""
    import argparse

    loaded = loader.load_cell(cell)
    args = argparse.Namespace(workload=cell, seed=1, seconds=1.0, trace=0, rehearse_cpu=rehearse,
                              probe_control=1)
    r = harness.Run(args, loaded)
    try:
        probe = r.job("unused")["probe"]
    finally:
        shutil.rmtree(r.tmp, ignore_errors=True)
    serve = harness.serve_of(loaded["config"], rehearse)
    chunk, page = serve["prefill_chunk"], serve.get("block_size", 16)
    n = probe["prompt_len"]
    hit = (n - 1) // page * page  # whole pages; a whole-pages prompt gives its last one back
    assert hit > 0 and hit % chunk == 0 and 0 < n - hit < page
    assert n + probe["max_tokens"] <= serve["max_model_len"]
    assert probe["limits"] == {"hit_gap": 0.0, "cold_gap": 0.0} and probe["control"] is True
    assert "probe_cold_gap" not in loaded["params"], "the limit is the harness's, not a cell's"


@pytest.mark.parametrize("serve,why", [
    ({"prefill_chunk": 500, "max_model_len": 4096}, "a chunk that is no whole number of pages"),
    ({"prefill_chunk": 512, "block_size": 8, "max_model_len": 4096}, "a tail as long as a page"),
    ({"prefill_chunk": 512, "max_model_len": 1056}, "a context the probe does not fit"),
])
def test_a_probe_that_cannot_hit_on_a_chunk_boundary_is_refused(serve, why):
    with pytest.raises(loader.BenchmarkError, match="chunk boundary"):
        harness.probe_of(serve, "some.cell")
    assert harness.probe_of({"prefill_chunk": 512, "max_model_len": 1064}, "c")["prompt_len"] == 1032
