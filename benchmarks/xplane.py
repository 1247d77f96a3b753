"""Reduction of a profiler trace (``.xplane.pb``) to numbers: device busy
and idle time, self time per operation and per kernel group, and the idle
gaps.  The arithmetic works on plain ``(name, start_s, duration_s)`` lists,
so that it is checked without a profiler (``tests/test_xplane.py``); only
``load`` touches the file, through ``jax.profiler.ProfileData``.

Times are seconds on the trace's own clock.
"""

from __future__ import annotations

import glob
import os
import re

OPS_LINE = "XLA Ops"          # one event per executed HLO operation
MODULES_LINE = "XLA Modules"  # one event per executed program


def newest_trace(profile_dir: str) -> str | None:
    paths = glob.glob(os.path.join(profile_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(paths, key=os.path.getmtime) if paths else None


def capture_of(run: dict) -> str | None:
    """The trace file of a traced run's capture: the newest under the
    directory the run itself set for it (``run["profile_dir"]``), looked up
    once and kept in ``run["capture_path"]``.  Every reader of the capture
    finds it here, never through the answer of ``/debug/profile``, which
    may come late or not at all while the file is on disk."""
    if "capture_path" not in run:
        where = run.get("profile_dir")
        run["capture_path"] = newest_trace(where) if where else None
    return run["capture_path"]


def load(path: str) -> dict:
    """{"devices": {plane name: {line name: [(name, start_s, dur_s)]}},
    "host": {thread name: [Python frames as (name, start_s, dur_s)]}} of one
    trace file.  Device planes are those
    JAX names ``/device:...``; a trace without one has ``devices == {}``."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")   # this process holds no chip
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = {"devices": {}, "host": {}}
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            if "TPU" not in plane.name and "GPU" not in plane.name:
                continue
            dest = out["devices"].setdefault(plane.name, {})
        elif plane.name.startswith("/host:CPU"):
            dest = out["host"]
        else:
            continue
        for line in plane.lines:
            events = [(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                      for e in line.events]
            if dest is out["host"]:
                # one line per host thread; keep the Python tracer's frames
                events = [e for e in events if e[0].startswith("$")]
            if events:
                dest.setdefault(line.name, []).extend(events)
    return out


def self_times(events) -> list[tuple[str, float, float, float]]:
    """(name, start, duration, self time) per event, in start order.  Events
    of one line nest (a ``while`` covers its body's operations); an event's
    self time is its duration less that of the events directly inside it."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out = []
    stack = []                     # indices into out of the open events
    for name, start, dur in evs:
        while stack and out[stack[-1]][1] + out[stack[-1]][2] <= start + 1e-12:
            stack.pop()
        if stack:
            p = out[stack[-1]]
            out[stack[-1]] = (p[0], p[1], p[2], p[3] - dur)
        out.append((name, start, dur, dur))
        stack.append(len(out) - 1)
    return [(n, s, d, max(x, 0.0)) for n, s, d, x in out]


def busy_intervals(events) -> list[tuple[float, float]]:
    """The union of the events' intervals, merged and sorted."""
    merged: list[list[float]] = []
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        end = start + dur
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def window_of(events) -> tuple[float, float]:
    return (min(e[1] for e in events), max(e[1] + e[2] for e in events))


def busy_seconds(events, window=None) -> tuple[float, float]:
    """(busy seconds, window seconds).  The window defaults to first start
    .. last end of the events themselves."""
    if not events:
        return 0.0, 0.0
    w0, w1 = window or window_of(events)
    busy = sum(max(0.0, min(b, w1) - max(a, w0))
               for a, b in busy_intervals(events))
    return busy, w1 - w0


def idle_gaps(events, window=None) -> list[tuple[float, float]]:
    """(start, seconds) of every gap between busy intervals in the window,
    longest first."""
    if not events:
        return []
    w0, w1 = window or window_of(events)
    gaps, at = [], w0
    for a, b in busy_intervals(events):
        if a > at and a <= w1:
            gaps.append((at, a - at))
        at = max(at, b)
    if w1 > at:
        gaps.append((at, w1 - at))
    return sorted(gaps, key=lambda g: -g[1])


def by_name(events) -> dict[str, float]:
    """Self seconds summed by operation name."""
    out: dict[str, float] = {}
    for name, _, _, self_s in self_times(events):
        out[name] = out.get(name, 0.0) + self_s
    return out


def group_seconds(per_name: dict[str, float], groups: dict[str, list[str]]
                  ) -> dict[str, float]:
    """Self seconds per kernel group.  ``groups`` maps a group's name to
    regular expressions (``kernels/*.json``); an operation counts for the
    first group, in name order, one of whose patterns it matches."""
    compiled = [(g, [re.compile(p) for p in pats])
                for g, pats in sorted(groups.items())]
    out = {g: 0.0 for g in groups}
    for name, secs in per_name.items():
        for g, pats in compiled:
            if any(p.search(name) for p in pats):
                out[g] += secs
                break
    return out


def capture_window(host: dict, seconds: float) -> tuple[float, float] | None:
    """The traced window on the trace's clock: the program holds a capture
    open with one ``time.sleep(seconds)``, which the Python tracer records.
    None when no such frame is found (the window then spans the device's
    first to last operation, which misses idle time at either end)."""
    best = None
    for events in host.values():
        for name, start, dur in events:
            if name.endswith("sleep") and abs(dur - seconds) < 0.2 * seconds \
                    and (best is None or dur > best[1] - best[0]):
                best = (start, start + dur)
    return best


def reduce(trace: dict, groups: dict[str, list[str]], window=None
           ) -> dict | None:
    """Everything the layer metrics read, from one loaded trace, averaged
    over its device planes: busy and window seconds, per-group and
    per-operation self seconds, idle gaps, executed programs.  None when no
    operation ran on a device."""
    per_dev = []
    for plane, lines in sorted(trace["devices"].items()):
        ops = lines.get(OPS_LINE) or []
        if window:
            ops = [e for e in ops if e[1] + e[2] >= window[0]
                   and e[1] <= window[1]]
        if not ops:
            continue
        busy, span = busy_seconds(ops, window)
        names = by_name(ops)
        per_dev.append({
            "plane": plane, "busy_s": busy, "window_s": span,
            "ops": names, "groups": group_seconds(names, groups),
            "gaps": idle_gaps(ops, window)[:50],
            "modules": sorted(lines.get(MODULES_LINE) or [],
                              key=lambda e: e[1]),
        })
    if not per_dev:
        return None
    n = len(per_dev)
    first = per_dev[0]
    return {
        "n_devices": n,
        "busy_s": sum(d["busy_s"] for d in per_dev) / n,
        "window_s": sum(d["window_s"] for d in per_dev) / n,
        "ops": first["ops"], "groups": first["groups"],
        "gaps": first["gaps"], "modules": first["modules"],
        "host": trace["host"],
    }


def group_busy_share(profile: dict | None, group: str) -> float | None:
    """A kernel group's self time over device busy time, in per cent."""
    if not profile or not profile["busy_s"]:
        return None
    return 100.0 * profile["groups"].get(group, 0.0) / profile["busy_s"]


def host_frames_at(host: dict, t: float, program_files: set[str]) -> str:
    """What the host was doing at trace time ``t`` (``host``: the threads'
    frames as ``columns`` gives them): of every thread's open
    Python frames, the deepest one in a file of the program (the profiler
    names a frame ``$file.py:line function``), from the thread that entered
    its frame last, followed by that thread's innermost frame of all."""
    import numpy as np

    best = None
    for cols in host.values():
        open_ = np.nonzero((cols["start"] <= t) & (cols["end"] >= t))[0]
        if not len(open_):
            continue
        mine = [i for i in open_ if cols["file"][i] in program_files]
        if not mine:
            continue
        deepest = max(mine, key=lambda i: cols["start"][i])
        inner = max(open_, key=lambda i: cols["start"][i])
        if best is None or cols["start"][deepest] > best[0]:
            label = cols["name"][deepest]
            if inner != deepest:
                label += " > " + cols["name"][inner]
            best = (cols["start"][deepest], label)
    return best[1] if best else "no frame of the program open"


def columns(events) -> dict:
    """A thread's Python frames as parallel columns, for ``host_frames_at``."""
    import numpy as np

    names = [e[0][1:] for e in events]
    return {"name": names,
            "file": [n.split(":", 1)[0] for n in names],
            "start": np.array([e[1] for e in events]),
            "end": np.array([e[1] + e[2] for e in events])}


def label_gaps(gaps, host: dict, program_files: set[str], top: int = 5
               ) -> list[list]:
    """``idle_gaps`` of the breakdown: the idle seconds of the (at most 50)
    longest gaps summed by what the host was doing at each gap's middle,
    then the longest single gaps."""
    cols = {k: columns(v) for k, v in host.items()}
    labelled = [(host_frames_at(cols, s + d / 2, program_files), d)
                for s, d in gaps]
    sums: dict[str, float] = {}
    for what, d in labelled:
        sums[what] = sums.get(what, 0.0) + d
    out = [[f"sum over gaps: {k}", v]
           for k, v in sorted(sums.items(), key=lambda kv: -kv[1])[:top]]
    out += [[f"longest gap: {what}", d] for what, d in labelled[:top]]
    return out
