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
