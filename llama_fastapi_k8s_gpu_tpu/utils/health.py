"""Health state machine + engine progress heartbeat (resilience layer).

The reference advertises k8s probes but never implements them and handles
every engine failure by crash-looping the pod (SURVEY.md §2C); before this
module our probes conflated "briefly degraded" with "kill me" — one
``/health`` endpoint served readiness AND liveness.  This module is the
shared vocabulary for the in-process resilience layer:

- :class:`HealthMonitor` — the pod-level state machine
  ``STARTING → READY ⇄ DEGRADED → DEAD`` (plus ``DRAINING`` on SIGTERM),
  with reason codes and a transition log.  Readiness (route traffic here?)
  is true only in READY; liveness (restart the pod?) is false only in
  DEAD.  A watchdog trip therefore sheds traffic without inviting a
  restart, and only exhausted recovery budgets escalate to the pod kill
  the reference used as its *first* resort.
- :class:`Heartbeat` — the progress pulse every engine publishes (one
  ``beat()`` per device step, busy counts, an error ring) and the
  watchdog samples (engine/watchdog.py).  Engines never import the
  watchdog; the heartbeat is the entire interface between them.
- :class:`EngineUnavailable` / :class:`DeadlineExceeded` — the error
  classification the server maps to 503 / 408 (server/app.py), distinct from
  the generic engine-bug 500.
"""

from __future__ import annotations

import threading
import time
from collections import deque

from ..obs import flightrec as _flightrec
from ..obs.trace import annotate_all_inflight

# -- states (string constants: JSON-friendly, no enum dependency) ----------
STARTING = "STARTING"    # model loading / warmup: not ready, alive
READY = "READY"          # serving: ready, alive
DEGRADED = "DEGRADED"    # watchdog tripped, recovery in flight: not ready, alive
DRAINING = "DRAINING"    # SIGTERM received, finishing in-flight: not ready, alive
DEAD = "DEAD"            # recovery budget exhausted: not ready, NOT alive

#: numeric codes for the /metrics gauge (dashboards alert on > 1)
STATE_CODES = {STARTING: 0, READY: 1, DEGRADED: 2, DRAINING: 3, DEAD: 4}

_TERMINAL = frozenset({DEAD})


class EngineUnavailable(RuntimeError):
    """The engine cannot serve right now (watchdog trip, recovery in
    progress, scheduler restart).  The server maps this to 503 — retryable
    against another replica — instead of the generic 500 that means
    "this request hit a bug"."""


class DeadlineExceeded(TimeoutError):
    """A request's propagated deadline expired inside the engine; its
    lane/slot has been reclaimed.  Maps to the reference-parity 408."""


class Heartbeat:
    """Engine progress pulse sampled by the watchdog (thread-safe).

    Writers (the engine's own threads) call :meth:`beat` once per device
    step/prefill slice, bracket work with :meth:`enter`/:meth:`leave` (or
    :meth:`set_busy` for schedulers that own an occupancy number), and
    :meth:`record_error` on engine-side exceptions.  The reader (watchdog)
    uses :meth:`idle_for`, :meth:`busy_count` and :meth:`error_burst`:
    "busy but no beat for N seconds" is the stall signal that catches both
    a wedged decode loop and a hung device call, with zero cost on the
    no-fault path beyond a lock-guarded float store."""

    # every field is written by engine threads and read by the watchdog
    # thread: all access goes through _lock (lfkt-lint LOCK001)
    _GUARDED_BY = {
        "_last_beat": "_lock", "_busy": "_lock", "_errors": "_lock",
        "beats_total": "_lock", "errors_total": "_lock",
        "last_error": "_lock",
    }

    def __init__(self, error_keep: int = 32):
        self._lock = threading.Lock()
        self._last_beat = time.monotonic()
        self._busy = 0
        self._errors: deque[float] = deque(maxlen=error_keep)
        self.beats_total = 0
        self.errors_total = 0
        self.last_error: str | None = None

    def beat(self) -> None:
        with self._lock:
            self._last_beat = time.monotonic()
            self.beats_total += 1

    def enter(self) -> None:
        with self._lock:
            self._busy += 1
            self._last_beat = time.monotonic()

    def leave(self) -> None:
        with self._lock:
            self._busy = max(0, self._busy - 1)
            self._last_beat = time.monotonic()

    def set_busy(self, n: int) -> None:
        with self._lock:
            self._busy = max(0, int(n))

    def record_error(self, exc: BaseException) -> None:
        msg = f"{type(exc).__name__}: {exc}"
        with self._lock:
            self._errors.append(time.monotonic())
            self.errors_total += 1
            self.last_error = msg
        # device OOM is THE incident the flight recorder exists for: by
        # the time the watchdog trips on the burst, the allocation state
        # that caused it is gone — bundle it at first sight.  Outside the
        # lock; disarmed this is one attribute read inside record(), and
        # the per-kind debounce keeps an OOM burst at one bundle.
        if _flightrec.OOM_SIGNATURE in msg:
            _flightrec.record_incident(
                "resource_exhausted", msg,
                extra={"errors_total": self.errors_total})

    def clear_errors(self) -> None:
        """Consume the burst evidence (watchdog trip handled): a re-trip
        must require NEW errors, or one transient burst re-trips every
        poll until the recovery budget is spent."""
        with self._lock:
            self._errors.clear()

    def reset(self) -> None:
        """Post-recovery: clear stall/burst evidence so the old incident
        cannot immediately re-trip the watchdog against the fresh engine."""
        with self._lock:
            self._last_beat = time.monotonic()
            self._busy = 0
            self._errors.clear()

    # -- watchdog-side reads ------------------------------------------------
    def busy_count(self) -> int:
        with self._lock:
            return self._busy

    def idle_for(self) -> float:
        with self._lock:
            return time.monotonic() - self._last_beat

    def error_burst(self, n: int, window_seconds: float) -> bool:
        """True when ≥ ``n`` errors were recorded in the last ``window``."""
        cutoff = time.monotonic() - window_seconds
        with self._lock:
            return sum(1 for t in self._errors if t >= cutoff) >= n


class HealthMonitor:
    """Thread-safe pod health state machine with reason codes.

    DEAD is terminal: once the recovery budget is spent the only exit is a
    pod restart (liveness probe fails), so nothing may transition out of
    it.  Every transition is recorded (bounded log) for /health."""

    # probe handlers, the watchdog and SIGTERM handling all race on the
    # state: every read/write goes through _lock (lfkt-lint LOCK001)
    _GUARDED_BY = {
        "_state": "_lock", "_reason": "_lock", "_since": "_lock",
        "_log": "_lock",
    }

    def __init__(self):
        self._lock = threading.Lock()
        self._state = STARTING
        self._reason = "initializing"
        self._since = time.time()
        self._log: deque[dict] = deque(maxlen=16)

    @property
    def state(self) -> str:
        with self._lock:
            return self._state

    def transition(self, state: str, reason: str = "") -> bool:
        """Move to ``state``; returns False when refused (DEAD is terminal,
        and DRAINING only yields to DEAD — a draining pod that degrades
        must not re-advertise readiness)."""
        if state not in STATE_CODES:
            raise ValueError(f"unknown health state {state!r}")
        with self._lock:
            if self._state in _TERMINAL and state != self._state:
                return False
            if self._state == DRAINING and state not in (DRAINING, DEAD):
                return False
            if state == self._state and reason == self._reason:
                return True
            self._log.append({
                "at": time.time(), "from": self._state, "to": state,
                "reason": reason,
            })
            prev = self._state
            self._state = state
            self._reason = reason
            self._since = time.time()
        # outside _lock: the tracer takes its own lock, and a state change
        # is a process-level fact every in-flight trace should carry
        # (lfkt-obs — a request slowed by a DEGRADED window says so)
        annotate_all_inflight("health_transition", from_state=prev,
                              to_state=state, reason=reason)
        return True

    # -- probe semantics ----------------------------------------------------
    def ready(self) -> bool:
        """Readiness: should traffic route here?  Only READY qualifies —
        DEGRADED/DRAINING shed load while staying alive."""
        with self._lock:
            return self._state == READY

    def alive(self) -> bool:
        """Liveness: should k8s restart the pod?  Only DEAD answers no —
        a briefly degraded pod recovering in-process must not be killed
        mid-recovery (that is the reference's crash-loop, reinstated)."""
        with self._lock:
            return self._state != DEAD

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "state": self._state,
                "reason": self._reason,
                "since": self._since,
                "transitions": list(self._log),
            }
