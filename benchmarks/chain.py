"""The time to first token as the program's spans tell it, link by link:
``queue`` (app) -> ``pending`` (lane engine) -> ``prefill`` {``tokenize``,
``prefill_slice``..., what the admission waited between them,
``first_token``} -> the ``first_content`` event on the ``stream`` span.
Medians (and means) over the window's requests, in ms, kept in
``run["notes"]["ttft_chain_ms"]`` beside the client's own ``ttft_p50_ms``,
so that a traced run says how much of what the client saw the spans
cover."""

from __future__ import annotations

from metrics import percentile
from spans import walk

INSIDE = ("tokenize", "prefill_slice", "first_token")


def links(trace: dict) -> dict | None:
    """One request's links in seconds; None until it has a closed
    ``prefill`` and a ``first_content`` mark."""
    root = trace.get("root")
    if not root:
        return None
    by: dict[str, list] = {}
    for s in walk(root):
        if s.get("end") is not None:
            by.setdefault(s["name"], []).append(s)
    prefill = (by.get("prefill") or [None])[0]
    mark = next((e["at"] for s in by.get("stream", [])
                 for e in s.get("events") or []
                 if e["name"] == "first_content"), None)
    if prefill is None or mark is None:
        return None
    kids = {n: sum(c["duration_s"] for c in prefill.get("children") or []
                   if c.get("name") == n and c.get("end") is not None)
            for n in INSIDE}
    out = {"queue": sum(s["duration_s"] for s in by.get("queue", [])),
           "pending": sum(s["duration_s"] for s in by.get("pending", [])),
           "prefill": prefill["duration_s"], **kids,
           "between_slices": prefill["duration_s"] - sum(kids.values()),
           "to_first_content": mark - prefill["end"],
           "server_total": mark - root["start"]}
    out["chain"] = out["queue"] + out["pending"] + out["prefill"] \
        + out["to_first_content"]
    out["outside_chain"] = out["server_total"] - out["chain"]
    return out


def note(run: dict) -> dict | None:
    """Medians of every link over the window's requests, written once to
    ``run["notes"]["ttft_chain_ms"]``; None for a program without the
    spans."""
    notes = run["notes"]
    if "ttft_chain_ms" not in notes:
        rows = [r for r in map(links, run["traces"]) if r]
        if not rows:
            return None
        doc = {k: percentile([r[k] * 1e3 for r in rows], 50) for k in rows[0]}
        # medians do not add (``chain`` is the median of the requests' own
        # sums); means do, so they are kept beside them
        doc["mean"] = {k: sum(r[k] for r in rows) * 1e3 / len(rows)
                       for k in rows[0]}
        doc["client_ttft_p50"] = (run.get("e2e") or {}).get("ttft_p50_ms")
        doc["requests"] = len(rows)
        notes["ttft_chain_ms"] = doc
    return notes["ttft_chain_ms"]
