"""The two driver-facing contracts must never regress silently:

- ``bench.py`` prints exactly ONE JSON line carrying metric/value/unit/
  vs_baseline (the driver records it as BENCH_r{N}.json) plus the
  machine-readable trajectory block (decode_mfu / host_gap_frac /
  dispatch percentiles / pipeline counters — ISSUE 11: the ROADMAP used
  to quote these by hand from stderr);
- ``__graft_entry__.entry()`` returns a jittable (fn, args) and
  ``dryrun_multichip(n)`` compiles+executes the full sharded step on an
  n-device mesh in a hermetic CPU subprocess;
- ``chip_smoke.py`` and ``bench.py`` never stand a CPU in for the chip, and
  the compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says or to one
  fixed path inside the checkout.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env() -> dict:
    from conftest import hermetic_child_env  # tests/ is on sys.path under pytest

    return hermetic_child_env(REPO)


def test_bench_prints_one_json_line():
    proc = subprocess.run(
        # The tiny CPU configuration is ASKED for (bench.py has no silent
        # fallback) and the line is labelled as such.
        [sys.executable, "bench.py", "--cpu-smoke"],
        cwd=REPO,
        env=_env(),
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [l for l in proc.stdout.splitlines() if l.strip()]
    assert len(lines) == 1, f"want exactly one stdout line, got {lines}"
    out = json.loads(lines[0])
    # The driver's four keys are load-bearing; the trajectory block rides
    # along so BENCH_r*.json carries what the ROADMAP quotes.
    assert set(out) == {
        "metric", "value", "unit", "vs_baseline",
        "decode_mfu", "decode_kernel", "attention", "host_gap_frac",
        "dispatch", "pipeline",
        "prefill_mfu", "prefill_kernel", "prefill",
        "device",
    }, sorted(out)
    assert out["metric"] == "engine_output_tokens_per_sec_cpu_smoke"
    assert out["device"] == {
        "platform": "cpu", "device_kind": "cpu", "count": out["device"]["count"],
    }
    assert out["value"] > 0
    assert 0.0 <= out["host_gap_frac"] <= 1.0
    # A CPU run has no device metric: utilisation is null, never a number.
    assert out["decode_mfu"] is None and out["prefill_mfu"] is None
    # ISSUE 13: which decode kernel served the run + the analytic
    # attention byte-share so BENCH_r06 can attribute MFU movement to the
    # kernel vs the matmuls.  ISSUE 19 rides the prefill half alongside:
    # which prefill kernel served, its MFU, and the per-chunk summary.
    assert out["decode_kernel"] in ("pallas_fused", "stock", "xla")
    assert out["prefill_kernel"] in ("pallas", "stock", "xla")
    assert {"chunks", "wall_s", "prompt_tokens",
            "p50_ms", "p99_ms"} <= set(out["prefill"])
    assert out["prefill"]["chunks"] >= 1
    assert {"share_est", "kv_bytes_per_step",
            "weight_bytes_per_step",
            "prefill_share_est",
            "prefill_kv_bytes_per_chunk"} <= set(out["attention"])
    assert 0.0 <= out["attention"]["share_est"] <= 1.0
    assert 0.0 <= out["attention"]["prefill_share_est"] <= 1.0
    for kind, v in out["dispatch"].items():
        assert {"dispatches", "p50_ms", "p99_ms"} <= set(v), (kind, v)
    assert {"sessions", "rebuilds", "continuous_admissions",
            "continuous_retired", "host_gap_frac", "stalls"} <= set(
                out["pipeline"])


def test_graft_entry_compiles():
    code = (
        "import __graft_entry__ as g, jax; "
        "fn, a = g.entry(); r = jax.jit(fn)(*a); "
        "assert r[0].shape == (4,), r[0].shape; print('entry-ok')"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code],
        cwd=REPO,
        env=_env(),
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "entry-ok" in proc.stdout


def test_dryrun_multichip_hermetic():
    # Hostile caller environment on purpose: the child must scrub it.
    env = _env()
    env.update(JAX_PLATFORMS="tpu", TPU_LIBRARY_PATH="/nonexistent")
    proc = subprocess.run(
        [sys.executable, "-c", "import __graft_entry__ as g; g.dryrun_multichip(8)"],
        cwd=REPO,
        env=env,
        capture_output=True,
        text=True,
        timeout=2000,  # > dryrun's internal 2 x 900s retry budget
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "dryrun_multichip ok" in proc.stdout


def test_bench_refuses_a_cpu_backend_unless_asked():
    """No silent debug-tiny: without --cpu-smoke a CPU backend is an error
    that says why, and no result line is printed."""
    proc = subprocess.run(
        [sys.executable, "bench.py"], cwd=REPO, env=_env(),
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no accelerator" in proc.stderr and "--cpu-smoke" in proc.stderr


def test_bench_unknown_device_kind_is_an_error():
    import bench

    assert set(bench.DEVICE_PEAKS["TPU v5 lite"]) == {
        "bf16_flops", "int8_ops", "hbm_bytes_per_s", "hbm_bytes",
    }
    with pytest.raises(SystemExit, match="no published peaks"):
        bench.device_peaks()  # device_kind "cpu" is not in the table


def test_chip_smoke_refuses_a_cpu_backend():
    """chip_smoke.py on a machine without a TPU: non-zero exit, the reason
    on stderr, and no result line (the driver checks exactly this)."""
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=REPO, env=_env(),
        capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode != 0
    assert "JAX found no TPU" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_chip_smoke_refuses_without_the_program(tmp_path):
    """Alone in a directory (no dynamo_tpu package) it fails too."""
    import shutil

    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path, env=_env(),
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert "no dynamo_tpu package" in proc.stderr
    assert proc.stdout.strip() == ""


def test_compile_cache_rule(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR set → the code sets no directory (JAX's own
    handling stands); unset → CPU: no cache, accelerator: ONE fixed path
    inside the checkout."""
    import jax

    from dynamo_tpu.engine import xla_cache

    set_dirs = []
    real_update = jax.config.update

    def spy(name, value):
        if name == "jax_compilation_cache_dir":
            set_dirs.append(value)
        else:
            real_update(name, value)

    monkeypatch.setattr(jax.config, "update", spy)
    # set: nothing assigned, the variable's value reported
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert xla_cache.setup_compilation_cache() == str(tmp_path)
    assert set_dirs == []
    # unset on the CPU backend: no persistent cache at all
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert xla_cache.setup_compilation_cache() is None
    assert set_dirs == []
    # unset on an accelerator backend: the fixed in-checkout path
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(os, "makedirs", lambda *a, **k: None)
    assert xla_cache.setup_compilation_cache() == os.path.join(REPO, ".xla_cache")
    assert set_dirs == [os.path.join(REPO, ".xla_cache")]
