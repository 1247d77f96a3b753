"""The program's cumulative counters over the measured window: the
difference between the first and the last of the 5 Hz ``/metrics`` texts
(``run["samples"]``).  A counter the program does not export (the parent of
the PR that added it) gives None."""

from __future__ import annotations

from server import parse_gauge


def delta(run: dict, name: str) -> float | None:
    """Last sample's ``name`` less the first's; None where either lacks it
    or fewer than two samples were taken."""
    samples = run.get("samples") or []
    if len(samples) < 2:
        return None
    first = parse_gauge(samples[0][1], name)
    last = parse_gauge(samples[-1][1], name)
    if first is None or last is None:
        return None
    return last - first


def ratio(run: dict, over: str, under: str, scale: float = 1.0
          ) -> float | None:
    """``scale`` x delta(over) / delta(under); None where either is missing
    or nothing was counted under the line."""
    a, b = delta(run, over), delta(run, under)
    if a is None or not b:
        return None
    return scale * a / b
