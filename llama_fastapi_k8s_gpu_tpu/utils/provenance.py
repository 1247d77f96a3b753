"""Bench-artifact provenance stamps (lfkt-perf regression sentinel).

Every JSON line ``bench.py``/``bench_server.py`` emits carries a
``provenance`` block: the git commit it measured, the device it ran on,
and the full ``LFKT_*`` environment fingerprint — so a banked artifact
can never again be ambiguous about *what* produced it, and
``tools/perf_gate.py`` can refuse to compare numbers measured under
different knob sets without anyone having to remember.  Schema validated
by ``tools/check_manifest.py`` over the whole banked corpus (tier-1).

Everything here is best-effort metadata: a missing git binary or a
jax-less process degrades fields to ``"unknown"`` rather than failing
the bench that asked for the stamp.
"""

from __future__ import annotations

import functools
import hashlib
import json
import os
import subprocess

#: provenance schema version (tools/check_manifest.py validates this shape)
SCHEMA = 1


# memoized: commit and device cannot change within one bench process, and
# a sweep emits one stamped line per grid point — no git subprocess per line
@functools.lru_cache(maxsize=None)
def _git_commit(cwd: str | None = None) -> str:
    if cwd is None:
        # the repo checkout this package lives in (best effort)
        cwd = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
    try:
        out = subprocess.run(["git", "rev-parse", "HEAD"], cwd=cwd,
                             capture_output=True, text=True, timeout=10)
        if out.returncode == 0 and out.stdout.strip():
            return out.stdout.strip()
    except Exception:  # noqa: BLE001 — metadata must never fail a bench
        pass
    return "unknown"


@functools.lru_cache(maxsize=None)
def _device_kind() -> str:
    try:
        import jax

        d = jax.devices()[0]
        return f"{d.platform}:{getattr(d, 'device_kind', '?')}"
    except Exception:  # noqa: BLE001
        return "unknown"


#: knobs that place a run but cannot move a measured number — bind
#: address/port, filesystem locations, log format.  Excluded from the
#: fingerprint so a bench run from a different checkout dir or port does
#: not cry "knob drift" on every perf_gate comparison (the warning must
#: stay rare enough that operators read it).
VOLATILE_KNOBS = frozenset({
    "LFKT_HOST", "LFKT_PORT", "LFKT_MODEL_DIR", "LFKT_PROFILE_DIR",
    "LFKT_JSON_LOGS",
})


def _lfkt_env(skip: frozenset) -> list[tuple[str, str]]:
    """The ``LFKT_*`` variables as set for this process, sorted, less
    ``skip``."""
    return sorted((k, v) for k, v in os.environ.items()
                  if k.startswith("LFKT_") and k not in skip)


def knob_fingerprint() -> dict:
    """The perf-relevant ``LFKT_*`` environment as set for this process,
    plus a short stable hash — two artifacts with equal ``knob_hash``
    were measured under byte-identical knob sets (modulo
    :data:`VOLATILE_KNOBS`)."""
    knobs = dict(_lfkt_env(VOLATILE_KNOBS))
    digest = hashlib.sha256(
        json.dumps(knobs, sort_keys=True).encode()).hexdigest()[:12]
    return {"knobs": knobs, "knob_hash": digest}


#: beside :data:`VOLATILE_KNOBS`, variables that cannot reach a TRACE
#: because of what they name: the file a model is read from (its weights
#: are arguments, its shapes are in the key) and what the tracer samples.
#: Everything else is in the executable store's key (utils/execstore.py): a
#: variable wrongly in the key costs a rebuild, one wrongly out of it a
#: wrong answer, so a new knob is in the key until someone shows it
#: belongs here.
NOT_TRACED = VOLATILE_KNOBS | {
    "LFKT_MODEL_NAME", "LFKT_TRACE_SAMPLE", "LFKT_TRACE_RING"}


def trace_env() -> list[tuple[str, str]]:
    """Every ``LFKT_*`` variable the environment holds, registered or not,
    sorted, but :data:`NOT_TRACED`: what a trace in this process may read
    through ``knob`` / ``_env_variant``."""
    return _lfkt_env(NOT_TRACED)


def mem_stats() -> dict:
    """The stamp's memory axis: process peak RSS and (where the backend
    reports memory_stats) device peak HBM bytes.  NOT memoized — peaks
    only grow, and each emitted line should carry the peak as of ITS
    measurement.  Best-effort like everything here: a field that cannot
    be read is omitted, never faked."""
    out: dict = {}
    try:
        import resource
        import sys

        ru = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        # ru_maxrss is KB on Linux, bytes on macOS
        out["rss_peak_bytes"] = int(ru) * (1 if sys.platform == "darwin"
                                           else 1024)
    except Exception:  # noqa: BLE001 — metadata must never fail a bench
        pass
    try:
        import jax

        stats = jax.devices()[0].memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        if peak:
            out["device_peak_bytes"] = int(peak)
    except Exception:  # noqa: BLE001
        pass
    return out


def stamp(cwd: str | None = None) -> dict:
    """The full provenance block for one bench JSON line."""
    return {"schema": SCHEMA,
            "git_commit": _git_commit(cwd),
            "device": _device_kind(),
            "mem": mem_stats(),
            **knob_fingerprint()}
