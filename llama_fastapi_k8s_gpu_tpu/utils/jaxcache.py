"""Persistent XLA compilation cache setup (SURVEY.md §5 "Checkpoint/resume").

Cuts the jit-warmup cost of a process restart from minutes to seconds — the
serving analogue of the reference's model-artifact reuse across pod restarts
(reference helm/templates/deployment.yaml:26-49 initContainer).

ONE function decides where the cache lives, and every entry point (Engine,
server, benches, ``chip_smoke.py`` children) goes through it:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads its own variable; this module
  sets no directory in code (the Helm chart places the cache on a volume
  that way).
- unset: the cache is on at the FIXED ``<checkout>/.lfkt_xla_cache``
  (git-ignored).  The directory is part of the cache key, so it is never
  built from a temporary name, a pid or the time.

Hits and misses of the persistent cache are counted from JAX's own
monitoring events and shown at ``GET /debug/compiles``.

The directory also holds the EXECUTABLE STORE, in ``executables/`` (PR 55;
utils/execstore.py): the same executables under a key that needs no trace,
so that a later start loads them instead of building every program's HLO
to look them up.  It rides the same volume and the same variable with no
setting of its own, and is on exactly where the persistent cache is
(``jax_enable_compilation_cache``), the backend is not the CPU and the
process drives ONE device (the tree has no program that spans devices:
utils/execstore.py).  JAX's cache lists only its own
``*-cache`` / ``*-atime`` files at the top of the directory for its LRU
(``jax/_src/lru_cache.py``), so it leaves the subdirectory alone.  To empty
either: delete the directory (or just ``executables/``); the next start
rebuilds and serves.
"""

from __future__ import annotations

import os
import threading

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".lfkt_xla_cache")

#: a compile under this many seconds is written to neither cache: JAX's
#: floor, which the executable store takes as its own (ExecStore.floor_s)
FLOOR_S = 0.5

_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "requests",
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "misses",
}
_counts = dict.fromkeys(_EVENTS.values(), 0)
_lock = threading.Lock()
_listening = False


def compile_cache_dir() -> str:
    """Where the persistent cache lives for this process."""
    return os.environ.get(CACHE_DIR_ENV) or DEFAULT_CACHE_DIR


def _on_event(event: str, **_kw) -> None:
    key = _EVENTS.get(event)
    if key is not None:
        with _lock:
            _counts[key] += 1


def setup_compile_cache() -> str:
    """Turn the persistent cache on (idempotent); returns its directory."""
    global _listening
    import jax

    if not os.environ.get(CACHE_DIR_ENV):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", FLOOR_S)
    with _lock:
        if not _listening:
            jax.monitoring.register_event_listener(_on_event)
            _listening = True
    from ..obs.devtime import DEVTIME

    # not on the CPU backend: XLA:CPU's AOT results are the ones the tests
    # keep out of (tests/conftest.py), and one of them (the lane engine's
    # chunk program, a while loop) loads and then fails when it RUNS
    # ("Function compare_reduce_fusion not found"), where no fallback can
    # catch it.  One device: every start that was measured, and whose
    # loaded replies were compared with built ones, is a one-chip start
    DEVTIME.use_store(os.path.join(compile_cache_dir(), "executables")
                      if jax.config.jax_enable_compilation_cache
                      and jax.default_backend() != "cpu"
                      and jax.device_count() == 1 else None)
    return compile_cache_dir()


def compile_cache_stats() -> dict:
    """{"dir", "requests", "hits", "misses"} since process start."""
    with _lock:
        return {"dir": compile_cache_dir(), **_counts}
