"""``cut_trace.py`` for a capture that holds the program's own phases
(``lfkt.*`` host events, ``benchmarks/annotations.py``): the same slice of
the device planes and of the host threads' Python frames, plus every
``lfkt.`` event that overlaps it, on the line of the thread that recorded
it (an event's stats, such as ``rid``, are not kept).

    python benchmarks/tests/data/cut_annotated.py <in.xplane.pb> <out.xplane.pb> [seconds [start]]

The slice starts 15 ms before the capture's longest idle gap of the device
(or at ``start``), so that it holds a gap and the phases around it.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import cut_trace  # noqa: E402  (puts benchmarks/ on the path)
import annotations  # noqa: E402
import xplane  # noqa: E402


def main(src: str, dst: str, seconds: float = 0.1, start=None) -> None:
    from jax.profiler import ProfileData

    trace = xplane.load(src)
    ops = next(iter(trace["devices"].values()))[xplane.OPS_LINE]
    if start is None:
        start = xplane.idle_gaps(ops)[0][0] - 0.015
    a, b = start, start + seconds

    def inside(e):
        return e[1] + e[2] >= a and e[1] <= b

    planes = []
    for i, (name, lines) in enumerate(sorted(trace["devices"].items())):
        planes.append(cut_trace._plane(i + 1, name, {
            ln: [e for e in evs if inside(e) and (
                ln == xplane.MODULES_LINE or (e[1] >= a and e[1] + e[2] <= b))]
            for ln, evs in lines.items()}))
    host = {}
    for thread, evs in trace["host"].items():
        keep = sorted((e for e in evs if inside(e)), key=lambda e: -e[2])[:4000]
        if keep:
            host[thread] = keep
    for thread, *event in annotations.events(src):
        if inside(event):
            host.setdefault(thread, []).append(tuple(event))
    host = {t: sorted(evs, key=lambda e: e[1]) for t, evs in host.items()}
    planes.append(cut_trace._plane(len(planes) + 1, "/host:CPU", host))
    blob = ProfileData.text_proto_to_serialized_xspace("\n".join(planes))
    with open(dst, "wb") as f:
        f.write(blob)
    print(f"{dst}: {len(blob)} bytes, {seconds}s from {a:.3f}s")


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2], *(float(x) for x in sys.argv[3:5]))
