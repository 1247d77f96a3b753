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
"""

from __future__ import annotations

import os
import threading

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".lfkt_xla_cache")

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
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    with _lock:
        if not _listening:
            jax.monitoring.register_event_listener(_on_event)
            _listening = True
    return compile_cache_dir()


def compile_cache_stats() -> dict:
    """{"dir", "requests", "hits", "misses"} since process start."""
    with _lock:
        return {"dir": compile_cache_dir(), **_counts}
