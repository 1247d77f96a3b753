"""scheduler: median over requests of the share of the ``first_token``
span (the return of the last prefill slice's dispatch -> the first token
on the host) that its children cover: ``device.<program>``, the jit
registry's device intervals inside it, and ``host_fetch``, the last of
them to the span's end (``obs/trace.py end_first_token``, PR 54).  What is
left is device idle time inside the wait that no program's interval and no
fetch accounts for.  0.0 on a program whose ``first_token`` has no
children (nothing of it is named); None without a ``first_token`` span.
Leaves ``run["notes"]["first_token_inside_ms"]``: the medians per child
name; and ``run["notes"]["slice_device_ms_by_offset"]``: the median
``device_s`` of the ``prefill_slice`` spans by their ``offset`` (a slice
deeper in a prompt reads more context and runs longer than the median
one).  program_span."""
from metrics import percentile
from spans import named


def read(run):
    shares, by_name = [], {}
    for s in named(run["traces"], "first_token"):
        if not s["duration_s"]:
            continue
        covered, at = 0.0, s["start"]
        mine = {}
        for c in sorted(s.get("children") or [], key=lambda c: c["start"]):
            name = c["name"]
            if c.get("end") is None or not (
                    name.startswith("device.") or name == "host_fetch"):
                continue
            a, b = max(c["start"], at), min(c["end"], s["end"])
            if b > a:
                covered += b - a
                at = b
            mine[name] = mine.get(name, 0.0) + (c["end"] - c["start"]) * 1e3
        shares.append(100.0 * covered / s["duration_s"])
        for name, ms in mine.items():
            by_name.setdefault(name, []).append(ms)
    if by_name:
        run["notes"]["first_token_inside_ms"] = {
            name: percentile(v, 50) for name, v in sorted(by_name.items())}
    by_offset = {}
    for s in named(run["traces"], "prefill_slice"):
        attrs = s.get("attrs") or {}
        if "device_s" in attrs and "offset" in attrs:
            by_offset.setdefault(attrs["offset"], []).append(
                attrs["device_s"] * 1e3)
    if by_offset:
        run["notes"]["slice_device_ms_by_offset"] = {
            str(off): percentile(v, 50) for off, v in sorted(by_offset.items())}
    return percentile(shares, 50)
