"""The jit registry's device seconds by program over the measured window
(PR 54; ``obs/devtime.py``).

While the tracer is armed the program stamps the host clock when one leaf
of each dispatch's result is ready, and sums per program the intervals
``[max(previous done, the dispatch's return), done]``:
``jit_device_seconds_total{program=}`` in ``/metrics``.  An interval is an
upper bound on its program: device work that reaches the chip outside the
registry lies in the interval of the next stamped program.

The growth is the last 5 Hz ``/metrics`` sample less the first, over the
seconds between those two samples.  A program's series appears with its
first interval, so one that is missing from the first sample counts from 0.
A program without the counter (the parent of the PR that added it) gives
None everywhere here.
"""

from __future__ import annotations

import re

SERIES = re.compile(
    r'^jit_device_seconds_total\{program="([^"]+)"\} (\S+)$', re.M)
#: a prompt's programs: one slice, a prompt of one slice or less, a mesh
#: engine's batch
PREFILL = ("prefill_chunk", "prefill", "batched_prefill")
#: the lane engine's decode chunk
LANE_CHUNK = ("lane_decode_chunk",)


def by_program(text: str) -> dict[str, float]:
    return {name: float(value) for name, value in SERIES.findall(text)}


def growth(run: dict) -> dict[str, float] | None:
    """{program: device seconds grown between the first and the last
    sample}; None with fewer than two samples or without the counter."""
    samples = run.get("samples") or []
    if len(samples) < 2:
        return None
    first, last = by_program(samples[0][1]), by_program(samples[-1][1])
    if not last:
        return None
    return {name: secs - first.get(name, 0.0) for name, secs in last.items()}


def seconds_of(run: dict, programs) -> float | None:
    """Device seconds the named programs grew by; None without the
    counter, 0.0 where none of them ran."""
    grown = growth(run)
    if grown is None:
        return None
    return sum(grown.get(name, 0.0) for name in programs)


def sampled_seconds(run: dict) -> float:
    samples = run["samples"]
    return samples[-1][0] - samples[0][0]
