"""Persistent XLA compilation cache.

Warming every reachable program is minutes of XLA compiles on a cold engine
start (not measured on this machine before PR 21's chip_smoke.py run), so a
restarted worker should replay its warmup from disk.  JAX's persistent
compilation cache does that; the directory is part of the cache key, so it
must never move.  The rule:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX's own handling of it stands and
  this module sets no directory.
- unset, accelerator backend: ONE fixed path inside the checkout
  (``<repo>/.xla_cache``, git-ignored) — never under ``~``, a temp name, a
  pid or a time.
- unset, CPU backend: no persistent cache.  XLA:CPU AOT entries embed the
  compile machine's CPU feature set and can fail (or SIGILL) when loaded
  under a different feature detection — observed between a serving process
  and hermetic child processes on the SAME host — and CPU compiles are
  cheap.  Setting the variable opts the CPU in.
"""

from __future__ import annotations

import logging
import os
import threading
from typing import Optional

from .. import REPO_ROOT

logger = logging.getLogger(__name__)

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = os.path.join(REPO_ROOT, ".xla_cache")


# Persistent-cache hits and misses of this process, counted from JAX's own
# monitoring events (reported on /metrics; chip_smoke.py reads them to show
# that a second start really replayed from disk).  A hit is one executable
# read back from the directory; a miss is one executable compiled anew AND
# written there (JAX records it at the write, so a process without a cache
# directory counts neither).
cache_events = {"hits": 0, "misses": 0}
# JAX's own seconds for the WHOLE life of the process, by stage: sums of
# WORK, not of wall (``warm:compile`` runs the stages on several threads at
# once), lying inside the start's phases (engine/phases.py SETUP_PHASES) and,
# where a program compiles after ``ready``, inside the serving loop.
# ``backend_compile`` is JAX's ``compile_or_get_cached``: it holds the cache's
# key and ``cache_retrieval`` where the cache answers, the compiler where not.
STAGE_OF_EVENT = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval",
}
compile_stages = {
    stage: {"seconds": 0.0, "events": 0} for stage in STAGE_OF_EVENT.values()
}
_stages_lock = threading.Lock()  # the side-by-side pass compiles on threads
_listening = False


def _on_event(event: str, **_kw) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        cache_events["hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        cache_events["misses"] += 1


def _on_duration(event: str, duration_secs: float, **_kw) -> None:
    stage = STAGE_OF_EVENT.get(event)
    if stage is not None:
        row = compile_stages[stage]
        with _stages_lock:
            row["seconds"] += duration_secs
            row["events"] += 1


def setup_compilation_cache() -> Optional[str]:
    """Apply the rule above (idempotent); returns the active cache
    directory or None."""
    global _listening
    import jax

    if not _listening:
        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _listening = True

    path = os.environ.get(CACHE_ENV)
    if not path:
        if jax.default_backend() == "cpu":
            return None
        path = CHECKOUT_CACHE_DIR
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    # Cache everything: the whole point is restart-time warmup, and the
    # warmup set is dozens of programs of wildly varying compile cost.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    logger.info("persistent XLA compilation cache at %s", path)
    return path


def cache_entries(path: Optional[str]) -> int:
    """Files currently in the cache directory (0 when there is none)."""
    if not path or not os.path.isdir(path):
        return 0
    return sum(1 for _ in os.scandir(path))
