"""The program's start-up timeline as the run's READY ``/health`` holds it
(``engine.startup``: process start to the READY flip, phase by phase, on
``time.time()``; docs/OBSERVABILITY.md "Start-up timeline").  A program
that serves none (the parent of the PR that added it) gives None."""

from __future__ import annotations


def of(run: dict) -> dict | None:
    """``/health`` ``engine.startup`` of the run, or None."""
    return (run["health"].get("engine") or {}).get("startup") or None


def phase(run: dict, name: str) -> dict | None:
    """The top-level phase ``name``, or None."""
    return next((p for p in (of(run) or {}).get("phases") or []
                 if p.get("name") == name), None)


def seconds(run: dict, names: tuple) -> float | None:
    """The seconds of the top-level phases ``names`` together; None where
    none of them ran (or there is no timeline)."""
    mine = [p["seconds"] for p in (phase(run, n) for n in names) if p]
    return float(sum(mine)) if mine else None
