#!/usr/bin/env python3
"""The done stamps of one ``/debug/profile`` capture against the device's
own line (PR 54; ``obs/devtime.py``).

    python tools/stamp_check.py <dir or .xplane.pb> [--json out.json]

The jit registry's watcher waits for each dispatch's result inside
``phase("device_done")``: the capture holds every stamp as an
``lfkt.device_done`` host event whose END is the stamp, on the clock the
``XLA Modules`` line is on.  For each stamp this takes the module that ended
before it, in the order of enqueueing (``match``: the stamped program, its
name says which), the stamp's lag behind that end, and the stretch of the
device's line since the stamped module before: the program's own module,
every OTHER module that ran in between by name (work that reached the device
outside the registry: it lies in this program's interval) and what was idle.  Per program: how many, the
mean interval between stamps, the mean module, and what else the intervals
hold.  A builder's tool; the benchmark's reader of the lag is
``benchmarks/layer_metrics/done_stamp_lag_p90_ms.py``.
"""

from __future__ import annotations

import argparse
import bisect
import glob
import json
import os
import re
import sys

EVENT = "lfkt.device_done"


def load(path: str):
    """(modules [(name, start_s, dur_s)] of the first device plane, stamp
    ends [s]) of one trace file."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")   # this process holds no chip
    from jax.profiler import ProfileData

    modules, stamps = [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:") and not modules:
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules = [
                        (e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                        for e in line.events]
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                stamps += [(e.start_ns + e.duration_ns) * 1e-9
                           for e in line.events if e.name == EVENT]
    return sorted(modules, key=lambda m: m[1]), sorted(stamps)


def program_of(module: str) -> str:
    """``jit_prefill_chunk_jit(1234)`` -> ``prefill_chunk_jit``."""
    return re.sub(r"^jit_+|\(\d+\)$", "", module)


#: the entry programs that carry a stamp, by their modules' names
STAMPED = re.compile(r"prefill_chunk_jit|prefill_jit|sample_jit|_write_lane"
                     r"|generate_chunk|first_sample|load_stack|linear_int8")
#: a stamp further than this behind a module's end is not that module's
MAX_LAG_S = 0.02


def match(modules, stamps):
    """[(stamp, index of its module)]: stamps and stamped programs both
    come in the order of enqueueing, so each stamp takes the EARLIEST
    stamped module not yet taken that ended at most ``MAX_LAG_S`` before it
    (three short programs that end inside one lag keep their order); a
    module no stamp reaches (its wait began before the capture) is passed
    over."""
    mine = sorted((s + d, i) for i, (n, s, d) in enumerate(modules)
                  if STAMPED.search(n))
    out, k = [], 0
    for stamp in stamps:
        while k < len(mine) and mine[k][0] < stamp - MAX_LAG_S:
            k += 1
        if k < len(mine) and mine[k][0] <= stamp:
            out.append((stamp, mine[k][1]))
            k += 1
    return out


def check(modules, stamps) -> dict:
    ends = [s + d for _, s, d in modules]
    rows: dict[str, dict] = {}
    prev_end = prev_stamp = None      # the stamped module before
    taken = set()
    for stamp, own in match(modules, stamps):
        taken.add(own)
        name, _, dur = modules[own]
        row = rows.setdefault(program_of(name), {
            "lag_ms": [], "module_s": [], "interval_s": [],
            "others_s": {}, "idle_s": 0.0})
        row["lag_ms"].append((stamp - ends[own]) * 1e3)
        row["module_s"].append(dur)
        if prev_end is not None:
            # what the registry calls this program's interval, while the
            # device is busy: the stamp before -> this stamp
            row["interval_s"].append(stamp - prev_stamp)
            busy = dur
            for other, s, d in modules:
                if prev_end < s + d < ends[own]:
                    key = program_of(other)
                    row["others_s"][key] = row["others_s"].get(key, 0.0) + d
                    busy += d
            row["idle_s"] += max(0.0, (ends[own] - prev_end) - busy)
        prev_end, prev_stamp = ends[own], stamp
    out = {}
    for prog, r in sorted(rows.items(), key=lambda kv: -sum(kv[1]["module_s"])):
        n_i = len(r["interval_s"])
        lag = sorted(r["lag_ms"])
        out[prog] = {
            "stamps": len(lag),
            "module_mean_ms": 1e3 * sum(r["module_s"]) / len(lag),
            "module_sum_s": sum(r["module_s"]),
            "interval_mean_ms": 1e3 * sum(r["interval_s"]) / n_i if n_i else None,
            "interval_sum_s": sum(r["interval_s"]),
            "intervals": n_i,
            "lag_p50_ms": lag[len(lag) // 2], "lag_max_ms": lag[-1],
            "other_modules_s": dict(sorted(r["others_s"].items(),
                                           key=lambda kv: -kv[1])),
            "idle_s": r["idle_s"]}
    unstamped: dict[str, list] = {}
    for i, (name, _, d) in enumerate(modules):
        if i not in taken:
            u = unstamped.setdefault(program_of(name), [0, 0.0])
            u[0] += 1
            u[1] += d
    return {"programs": out,
            "modules_without_a_stamp": {
                k: {"n": n, "sum_s": s} for k, (n, s) in
                sorted(unstamped.items(), key=lambda kv: -kv[1][1])},
            "n_stamps": len(stamps), "n_modules": len(modules)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("where", help="a .xplane.pb, or a directory to search "
                                  "for the newest one")
    ap.add_argument("--json", help="also write the whole result here")
    args = ap.parse_args(argv)
    path = args.where
    if os.path.isdir(path):
        found = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                          recursive=True)
        if not found:
            print(f"no .xplane.pb under {path}", file=sys.stderr)
            return 2
        path = max(found, key=os.path.getmtime)
    result = check(*load(path))
    result["file"] = path
    if args.json:
        with open(args.json, "w") as f:
            json.dump(result, f, indent=1)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
