#!/usr/bin/env python3
"""trace_report — latency waterfalls from lfkt-obs /debug/traces JSON.

The RUNBOOK's slow-request triage flow ("Triaging a slow request",
docs/RUNBOOK.md): pull a trace, see WHERE the time went — httpd read vs
queue vs prefill vs decode vs SSE write — as an ASCII timeline plus phase
percentages, without a tracing backend.

Usage::

    # newest traces from a live server (summaries + the slowest's waterfall)
    python tools/trace_report.py --url http://localhost:8000

    # one specific request
    python tools/trace_report.py --url http://localhost:8000 --trace <id>

    # offline: a saved /debug/traces/<id> (or /debug/traces) JSON document
    python tools/trace_report.py --file trace.json

Cross-process (fleet) waterfalls: point --file at a saved stitched
document, or use ``tools/fleet_trace.py`` which collects the fragments
from the router/peers and renders through the same code.

stdlib only (urllib), no jax import — safe on a serving pod.
"""

from __future__ import annotations

import argparse
import json
import sys
import urllib.request

WIDTH = 56          # timeline columns
INDENT = 2          # per-depth indent in the name column
NAME_COL = 26


def _fetch(url: str) -> dict:
    with urllib.request.urlopen(url, timeout=10) as r:
        return json.loads(r.read().decode())


def _walk(span: dict, depth: int = 0):
    yield span, depth
    for child in span.get("children", ()):
        yield from _walk(child, depth + 1)


def _fmt_ms(seconds: float | None) -> str:
    return "     ?" if seconds is None else f"{seconds * 1000.0:6.1f}"


def _fmt_bytes(b) -> str:
    """Compact byte count for event suffixes (page moves, headroom)."""
    if not isinstance(b, (int, float)):
        return "?"
    if b >= 1e9:
        return f"{b / 1e9:.2f}GB"
    if b >= 1e6:
        return f"{b / 1e6:.1f}MB"
    return f"{b / 1e3:.0f}KB"


def render_trace(trace: dict) -> str:
    """One trace's ASCII waterfall + phase percentages.

    ``trace`` is the /debug/traces/{id} document (trace_id, meta, root)
    — or a STITCHED fleet document (router ``/debug/fleet/traces/{id}``,
    obs/fleettrace.py): grafted fragment roots carry ``process``/``hop``
    attrs and render behind a hop-boundary rule, so one waterfall shows
    the router, the owning replica, and the prefill/migration tiers on
    one clock.  Spans with no end (request still in flight / producer
    died) render to the trace's horizon with a ``…`` marker.
    """
    root = trace["root"]
    t0 = root["start"]
    horizon = root.get("end") or max(
        (s.get("end") or s["start"] for s, _ in _walk(root)), default=t0)
    total = max(horizon - t0, 1e-9)

    lines = []
    meta = trace.get("meta") or {}
    head = [f"trace {trace.get('trace_id', '?')}"]
    for k in ("route", "engine", "lane", "status"):
        if meta.get(k) is not None:
            head.append(f"{k}={meta[k]}")
    if trace.get("stitched"):
        head.append(f"processes={','.join(trace.get('processes') or [])}")
        if trace.get("orphans"):
            head.append(f"orphans={len(trace['orphans'])}")
    lines.append("  ".join(head))
    lines.append(f"total {total * 1000.0:.1f} ms"
                 + ("" if root.get("end") else "  (in flight)"))
    lines.append("")

    #: name | start-ms | dur-ms | timeline bar
    phase_seconds: dict[str, float] = {}
    for span, depth in _walk(root):
        attrs = span.get("attrs") or {}
        if attrs.get("process") is not None:
            # a stitched fragment's root: everything under this line ran
            # in ANOTHER process, linked by the wire/header hop named here
            label = f"─ hop: {attrs['process']}"
            if attrs.get("orphan"):
                label += " (orphan)"
            lines.append(f"{label[:NAME_COL]:<{NAME_COL}} {'':>6} {'':>6} "
                         f"|{'┈' * WIDTH}|")
        start = span["start"] - t0
        end = (span.get("end") or horizon) - t0
        dur = max(end - start, 0.0)
        open_marker = "" if span.get("end") else "…"
        if depth == 1:      # direct children of the root ARE the phases
            phase_seconds[span["name"]] = (
                phase_seconds.get(span["name"], 0.0) + dur)
        lo = min(int(start / total * WIDTH), WIDTH - 1)
        hi = max(min(int(end / total * WIDTH + 0.999), WIDTH), lo + 1)
        glyph, label, extra = "█", span["name"], ""
        if span["name"] == "prefill_slice":
            # one slice's host dispatch (▒, offset-labeled): the slices
            # tile the prefill span and what lies between them is the
            # admission waiting — the round-6 overlap picture
            glyph, label = "▒", f"slice@{attrs.get('offset', '?')}"
            extra = f"  n={attrs.get('tokens', '?')}"
        elif attrs.get("tokens") is not None:
            extra = f"  t={attrs['tokens']}"
        bar = " " * lo + glyph * (hi - lo) + " " * (WIDTH - hi)
        name = (" " * (depth * INDENT) + label)[:NAME_COL]
        lines.append(f"{name:<{NAME_COL}} {_fmt_ms(start)} "
                     f"{_fmt_ms(dur)} |{bar}|{open_marker}{extra}")
        def duration_bar(at, host_s, glyph, label, suffix):
            # a timed event rendered as a bar ENDING at its timestamp
            # (producers stamp the event after the work), so back-to-back
            # events visibly tile their parent span
            mark = min(int(at / total * WIDTH), WIDTH - 1)
            lo = max(0, min(int((at - host_s) / total * WIDTH), mark))
            ebar = (" " * lo + glyph * max(mark - lo + 1, 1)
                    + " " * (WIDTH - mark - 1))[:WIDTH]
            ename = (" " * ((depth + 1) * INDENT) + "* " + label)[:NAME_COL]
            lines.append(f"{ename:<{NAME_COL}} {_fmt_ms(at - host_s)} "
                         f"{_fmt_ms(host_s)} |{ebar}|  {suffix}")

        for ev in span.get("events", ()):
            at = ev["at"] - t0
            host_s = ev.get("host_s")
            if ev["name"] in ("kv_restore", "kv_spill", "kv_spill_restore") \
                    and host_s is not None:
                # paged-KV page movement (░, parallel/kvpool.py): the
                # copy/DMA cost — with its byte count — in the same
                # waterfall as the prefill slices it delays
                suffix = f"pages={ev.get('pages', '?')}"
                if ev.get("bytes") is not None:
                    suffix += f" {_fmt_bytes(ev['bytes'])}"
                duration_bar(at, host_s, "░", ev["name"], suffix)
                continue
            if ev["name"] in ("disagg_recv", "kv_migrate_pull",
                              "handshake") and host_s is not None:
                # wire-delivered KV pages (▓): a disagg prefill transfer
                # (serving/disagg/) or a fleet migration pull
                # (serving/fleet/migrate.py) — the hop's cost next to
                # the local restore/suffix-prefill it buys; the dial
                # handshake renders the same way (first-hop cost)
                suffix = (f"pages={ev.get('pages', '?')}"
                          f" t={ev.get('tokens', '?')}"
                          if ev["name"] != "handshake"
                          else f"peer={ev.get('peer', '?')}")
                if ev.get("bytes") is not None:
                    suffix += f" {_fmt_bytes(ev['bytes'])}"
                if ev.get("reason") is not None:
                    suffix += f" reason={ev['reason']}"
                duration_bar(at, host_s, "▓", ev["name"], suffix)
                continue
            mark = min(int(at / total * WIDTH), WIDTH - 1)
            tick = " " * mark + "▲" + " " * (WIDTH - mark - 1)
            ename = (" " * ((depth + 1) * INDENT) + "* " + ev["name"])[:NAME_COL]
            suffix = ""
            if ev["name"] == "kv_pages":
                # serve-side wire.send progress marks (prefiller.py /
                # migrate.py): one PAGE group on the wire per tick
                suffix = (f"  pages={ev.get('pages', '?')}"
                          f" {_fmt_bytes(ev.get('bytes'))}")
            if ev["name"] == "mem_pressure":
                # lfkt-mem: the admission controller cut its budget on
                # low HBM headroom — the byte counts explain the slower
                # admissions that follow in this waterfall
                suffix = (f"  headroom={_fmt_bytes(ev.get('headroom_bytes'))}"
                          f"/{_fmt_bytes(ev.get('limit_bytes'))}")
            lines.append(
                f"{ename:<{NAME_COL}} {_fmt_ms(at)} {'':>6} |{tick}|{suffix}")

    if phase_seconds:
        lines.append("")
        lines.append("phase breakdown:")
        accounted = 0.0
        for name, dur in sorted(phase_seconds.items(), key=lambda kv: -kv[1]):
            accounted += dur
            lines.append(f"  {name:<20} {dur * 1000.0:8.1f} ms "
                         f"{dur / total * 100.0:5.1f}%")
        other = max(total - accounted, 0.0)
        lines.append(f"  {'(unattributed)':<20} {other * 1000.0:8.1f} ms "
                     f"{other / total * 100.0:5.1f}%")
    return "\n".join(lines)


def render_listing(doc: dict) -> str:
    """The /debug/traces summary table (newest first)."""
    rows = [f"{'trace_id':<34} {'route':<20} {'ms':>8}  spans"]
    for s in doc.get("traces", ()):
        dur = s.get("duration_s")
        rows.append(
            f"{s['trace_id']:<34} "
            f"{str((s.get('meta') or {}).get('route', '?')):<20} "
            f"{dur * 1000.0 if dur is not None else -1.0:8.1f}  "
            f"{s.get('spans', '?')}")
    return "\n".join(rows)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="trace_report")
    ap.add_argument("--url", help="server base URL (http://host:port)")
    ap.add_argument("--trace", help="trace id to render")
    ap.add_argument("--file", help="saved /debug/traces[/{id}] JSON")
    args = ap.parse_args(argv)

    if args.file:
        doc = json.load(open(args.file, encoding="utf-8"))
    elif args.url:
        base = args.url.rstrip("/")
        if args.trace:
            doc = _fetch(f"{base}/debug/traces/{args.trace}")
        else:
            doc = _fetch(f"{base}/debug/traces")
    else:
        ap.error("one of --url or --file is required")
        return 2

    if "root" in doc:                       # a single trace document
        print(render_trace(doc))
        return 0
    print(render_listing(doc))
    traces = doc.get("traces") or []
    if traces:
        slowest = max(traces,
                      key=lambda s: s.get("duration_s") or -1.0)
        if args.url and slowest.get("duration_s") is not None:
            print()
            print("slowest completed request:")
            print(render_trace(_fetch(
                f"{args.url.rstrip('/')}/debug/traces/"
                f"{slowest['trace_id']}")))
    return 0


if __name__ == "__main__":
    sys.exit(main())
