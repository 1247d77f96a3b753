"""Self time of the device operations whose names match a pattern, over
device busy time, in the mid-window capture.  Beside ``kernels/*.json``:
a group there takes an operation from every group after it in name order,
so a metric that splits or overlaps a group keeps its pattern with itself
and reads ``run["profile"]["ops"]`` through this."""

from __future__ import annotations

import re

from xplane import group_busy_share


def _no_match(run: dict, metric: str) -> None:
    """A share of 0.0 because nothing matched (a file without tensors of
    that type, or a kernel that changed its name) says so on the
    diagnostics line: ``notes.no_match`` lists the metrics."""
    run.setdefault("notes", {}).setdefault("no_match", []).append(metric)


def busy_share(run: dict, metric: str, pattern: str) -> float | None:
    """Per cent of busy time in operations matching ``pattern``, for the
    reader of ``metric``: 0.0 where the capture holds no such operation,
    with the metric's name in ``notes.no_match``.  None only without a
    capture."""
    profile = run.get("profile")
    if not profile or not profile["busy_s"]:
        return None
    rx = re.compile(pattern)
    secs = [s for name, s in profile["ops"].items() if rx.search(name)]
    if not secs:
        _no_match(run, metric)
    return 100.0 * sum(secs) / profile["busy_s"]


def group_share(run: dict, metric: str, group: str) -> float | None:
    """``xplane.group_busy_share`` of a ``kernels/*.json`` group, with the
    same note where the group took no operation."""
    share = group_busy_share(run.get("profile"), group)
    if share == 0.0:
        _no_match(run, metric)
    return share
