"""Self time of the device operations whose names match a pattern, over
device busy time, in the mid-window capture.  Beside ``kernels/*.json``:
a group there takes an operation from every group after it in name order,
so a metric that splits or overlaps a group keeps its pattern with itself
and reads ``run["profile"]["ops"]`` through this."""

from __future__ import annotations

import re


def busy_share(profile: dict | None, pattern: str) -> float | None:
    """Per cent of busy time in operations matching ``pattern``; None
    without a capture, or where no operation matches (a program whose
    kernels carry no such name)."""
    if not profile or not profile["busy_s"]:
        return None
    rx = re.compile(pattern)
    secs = [s for name, s in profile["ops"].items() if rx.search(name)]
    if not secs:
        return None
    return 100.0 * sum(secs) / profile["busy_s"]
