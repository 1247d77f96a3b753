"""The program's own phases inside a profiler capture, read beside the
device's idle gaps.

With ``LFKT_PROFILE_DIR`` set the program names its phases on the thread
that runs them (``obs/trace.py`` ``phase``: a scheduler wave, a prefill
slice, a tokenizer call) as ``jax.profiler.TraceAnnotation("lfkt.<name>")``.
They land in the ``/debug/profile`` capture as host events on the trace's
own clock, the one the device's operations are on.  ``xplane.py`` keeps
only the Python tracer's frames of the host plane; this reader keeps the
``lfkt.`` events, and puts each idle gap of the device
(``run["profile"]["gaps"]``, the 50 longest) down to the innermost phase
that was open at the gap's middle, on any thread.

The arithmetic works on plain ``(name, start_s, duration_s)`` lists; only
``load`` touches the file.  With a capture of the device there is always a
number: a capture without a phase has all its idle seconds unnamed, one
without an idle second has none unnamed.  Only a run without a capture (an
unsound one: no trace file, no operation on a device) gives None.
"""

from __future__ import annotations

import os

import xplane

PREFIX = "lfkt."
UNNAMED = "(no phase open)"


def events(path: str):
    """(thread's line name, event name with the prefix, start_s,
    duration_s) of every ``lfkt.`` host event of one trace file."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")   # this process holds no chip
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    yield (line.name, e.name, e.start_ns * 1e-9,
                           e.duration_ns * 1e-9)


def load(path: str) -> list[tuple[str, float, float]]:
    """(phase name without the prefix, start_s, duration_s) of every
    ``lfkt.`` host event of one trace file, in start order."""
    return sorted(((name[len(PREFIX):], start, dur)
                   for _, name, start, dur in events(path)),
                  key=lambda e: e[1])


def phase_at(phases, t: float) -> str | None:
    """The innermost phase open at trace time ``t``: of those that cover
    it, the one that started last."""
    best = None
    for name, start, dur in phases:
        if start > t:
            break
        if start + dur >= t and (best is None or start >= best[0]):
            best = (start, name)
    return best[1] if best else None


def idle_by_phase(gaps, phases) -> dict[str, float]:
    """Idle seconds of ``gaps`` ((start, seconds) each) by the phase open at
    each gap's middle, most idle first; ``UNNAMED`` holds the rest: host
    time the program does not name yet."""
    sums: dict[str, float] = {}
    for start, secs in gaps:
        name = phase_at(phases, start + secs / 2) or UNNAMED
        sums[name] = sums.get(name, 0.0) + secs
    return dict(sorted(sums.items(), key=lambda kv: -kv[1]))


def of_run(run: dict) -> dict[str, float] | None:
    """``idle_by_phase`` of a traced run's mid-window capture, computed once
    and kept in ``run["notes"]["idle_by_phase"]`` (the diagnostics line
    prints it).  The capture is the file ``xplane.capture_of`` finds, the
    one the run's reduction read.  None when there is no capture or no
    device operation in it; ``{}`` where its gaps sum to nothing."""
    notes = run["notes"]
    if "idle_by_phase" in notes:
        return notes["idle_by_phase"]
    path = xplane.capture_of(run)
    profile = run.get("profile")
    if not path or not profile:
        return None
    phases = load(path)
    counts: dict[str, int] = {}
    for name, _, _ in phases:
        counts[name] = counts.get(name, 0) + 1
    notes["phases_in_capture"] = counts
    notes["idle_by_phase"] = idle_by_phase(profile["gaps"], phases)
    return notes["idle_by_phase"]


def idle_share(run: dict, only: str | None = None) -> float | None:
    """Per cent of the idle seconds (of the capture's 50 longest gaps) that
    lie inside some phase, or inside the phase ``only``.  Where the capture
    holds no idle second nothing is unnamed and nothing is in ``only``: 100
    for the whole, 0 for one phase.  None only without a capture."""
    by = of_run(run)
    if by is None:
        return None
    total = sum(by.values())
    if not total:
        return 0.0 if only else 100.0
    named = by.get(only, 0.0) if only else total - by.get(UNNAMED, 0.0)
    return 100.0 * named / total
