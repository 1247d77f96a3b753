"""Cut a recorded ``.xplane.pb`` down to a few tens of milliseconds, so that
a real trace of the chip can be kept beside the tests (a whole capture is
tens of megabytes, most of it the Python tracer's frames).

    python benchmarks/tests/data/cut_trace.py <in.xplane.pb> <out.xplane.pb> [seconds [start]]

Keeps, from the middle of the capture (or from ``start``): every event of
the device planes' lines that lies wholly inside the slice (a ``while`` that
does not is dropped and its body's operations stand alone), the executed
programs that overlap it, and the host threads' Python frames that are open
in it (at most 4000, longest first).  Names, starts and durations are as recorded;
the file is re-encoded through the profiler's own text format.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
import xplane  # noqa: E402


def _quote(s: str) -> str:
    return '"' + s.replace("\\", "\\\\").replace('"', '\\"') + '"'


def _plane(pid: int, name: str, lines: dict) -> str:
    ids, out = {}, [f"planes {{ id: {pid} name: {_quote(name)}"]
    for lid, (lname, events) in enumerate(sorted(lines.items()), 1):
        out.append(f" lines {{ id: {lid} name: {_quote(lname)} timestamp_ns: 0")
        for ename, start, dur in events:
            mid = ids.setdefault(ename, len(ids) + 1)
            out.append(f"  events {{ metadata_id: {mid} "
                       f"offset_ps: {round(start * 1e12)} "
                       f"duration_ps: {round(dur * 1e12)} }}")
        out.append(" }")
    for ename, mid in ids.items():
        out.append(f" event_metadata {{ key: {mid} value {{ id: {mid} "
                   f"name: {_quote(ename)} }} }}")
    out.append("}")
    return "\n".join(out)


def main(src: str, dst: str, seconds: float = 0.045, start=None) -> None:
    from jax.profiler import ProfileData

    trace = xplane.load(src)
    ops = next(iter(trace["devices"].values()))[xplane.OPS_LINE]
    w0, w1 = xplane.window_of(ops)
    a = (w0 + w1) / 2 if start is None else start
    b = a + seconds

    def inside(e):
        return e[1] + e[2] >= a and e[1] <= b

    planes = []
    for i, (name, lines) in enumerate(sorted(trace["devices"].items())):
        planes.append(_plane(i + 1, name, {
            ln: [e for e in evs if inside(e) and (
                ln == xplane.MODULES_LINE or (e[1] >= a and e[1] + e[2] <= b))]
            for ln, evs in lines.items()}))
    host = {}
    for thread, evs in trace["host"].items():
        keep = sorted((e for e in evs if inside(e)), key=lambda e: -e[2])[:4000]
        if keep:
            host[thread] = sorted(keep, key=lambda e: e[1])
    planes.append(_plane(len(planes) + 1, "/host:CPU", host))
    blob = ProfileData.text_proto_to_serialized_xspace("\n".join(planes))
    with open(dst, "wb") as f:
        f.write(blob)
    print(f"{dst}: {len(blob)} bytes, {seconds}s from {a:.3f}s")


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2], *(float(x) for x in sys.argv[3:5]))
