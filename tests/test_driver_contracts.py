"""The driver-facing contracts must never regress silently:

- ``__graft_entry__.entry()`` returns a jittable (fn, args) and
  ``dryrun_multichip(n)`` compiles+executes the full sharded step on an
  n-device mesh in a hermetic CPU subprocess;
- ``chip_smoke.py`` never stands a CPU in for the chip, and the compile
  cache goes where ``JAX_COMPILATION_CACHE_DIR`` says or to one fixed path
  inside the checkout.

(The benchmark's own contract — ``chipbench/run.py``'s one JSON line, its
refusal of a CPU backend, its peak table — is held by ``tests/chipbench/``.)
"""

import os
import subprocess
import sys


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env() -> dict:
    from conftest import hermetic_child_env  # tests/ is on sys.path under pytest

    return hermetic_child_env(REPO)


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
