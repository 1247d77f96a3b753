"""Profiling hook: the bounded on-demand XProf capture.

The reference's sole instrument is a request-timing log middleware (reference
api.py:179-194).  SURVEY.md §5 "Tracing / profiling" calls for per-phase
timers (queue wait, prefill/TTFT, decode tokens/sec — implemented in
engine/engine.py and server/app.py against utils/metrics.py) plus optional
``jax.profiler`` capture; this module provides the capture.
``LFKT_PROFILE_DIR`` has ONE meaning: it arms ``GET /debug/profile``
(:func:`capture_profile`), and with it the ``lfkt.*`` phase annotations that
put the program's own phases inside such a capture (obs/trace.py ``phase``).
No generation is ever profiled on its own: JAX allows one profile at a time,
so a per-generation trace would refuse or cut short the operator's capture.
Unset, nothing here runs.
"""

from __future__ import annotations

import logging
import os
import threading
import time

logger = logging.getLogger(__name__)

#: /debug/profile capture bounds: a runaway ``seconds=`` must not park the
#: profiler on a serving pod
MAX_CAPTURE_SECONDS = 30.0
MIN_CAPTURE_SECONDS = 0.05

#: one capture at a time (jax.profiler keeps process-global state; a
#: second start_trace while one runs raises deep inside the profiler)
_CAPTURE_LOCK = threading.Lock()


class ProfileDisabled(RuntimeError):
    """LFKT_PROFILE_DIR is unset — profiling is opt-in, off by default."""


class ProfileBusy(RuntimeError):
    """A capture is already running (the exclusive-capture guard)."""


def capture_profile(seconds: float) -> dict:
    """Bounded on-demand XProf capture (the ``GET /debug/profile`` body):
    start ``jax.profiler`` into ``LFKT_PROFILE_DIR``, hold it for a
    clamped window, stop, and report where the trace landed.  Blocking —
    callers run it in a worker thread.  Raises :class:`ProfileDisabled`
    when the knob is unset and :class:`ProfileBusy` when a capture is
    already in flight; profiler-internal failures are reported in the
    result rather than raised (capture is best-effort, serving is not)."""
    d = profile_dir()
    if not d:
        raise ProfileDisabled(
            "set LFKT_PROFILE_DIR to enable /debug/profile captures")
    seconds = max(MIN_CAPTURE_SECONDS, min(MAX_CAPTURE_SECONDS,
                                           float(seconds)))
    if not _CAPTURE_LOCK.acquire(blocking=False):
        raise ProfileBusy("a profiler capture is already running")
    try:
        import jax

        os.makedirs(d, exist_ok=True)
        t0 = time.time()
        try:
            jax.profiler.start_trace(d)
        except Exception as e:  # noqa: BLE001 — capture is best-effort
            logger.warning("profiler capture unavailable (%s)", e)
            return {"ok": False, "error": str(e), "dir": d}
        try:
            time.sleep(seconds)
        finally:
            try:
                jax.profiler.stop_trace()
            except Exception as e:  # noqa: BLE001
                logger.warning("profiler teardown failed (%s)", e)
                return {"ok": False, "error": str(e), "dir": d,
                        "seconds": seconds,
                        "wall_s": round(time.time() - t0, 3)}
        # "seconds" is the clamped capture window; "wall_s" additionally
        # counts start/stop_trace itself — the teardown serializes every
        # event the profiler retained and can dwarf a short window on a
        # long-lived process, so the two must not be conflated
        return {"ok": True, "dir": d, "seconds": seconds,
                "wall_s": round(time.time() - t0, 3)}
    finally:
        _CAPTURE_LOCK.release()


def profile_dir() -> str | None:
    from .config import knob

    return knob("LFKT_PROFILE_DIR") or None
