"""Walking the program's request span trees (``/debug/traces/{id}``)."""

from __future__ import annotations


def walk(span: dict):
    yield span
    for child in span.get("children") or []:
        yield from walk(child)


def named(traces, name: str):
    """Every finished span called ``name`` in the given trace documents."""
    for tr in traces:
        root = tr.get("root")
        if not root:
            continue
        for s in walk(root):
            if s.get("name") == name and s.get("end") is not None:
                yield s


def decode_chunks(traces):
    """(start, end, tokens decoded in it) of every decode chunk.  The
    program stamps each chunk with the request's running token count, so a
    chunk's own tokens are the difference to the chunk before it (the first
    follows the one token prefill sampled)."""
    for tr in traces:
        root = tr.get("root")
        if not root:
            continue
        prev = 1
        for s in sorted((s for s in walk(root)
                         if s.get("name") == "decode_chunk"
                         and s.get("end") is not None),
                        key=lambda s: s["start"]):
            tokens = (s.get("attrs") or {}).get("tokens")
            if tokens is None:
                continue
            if tokens > prev:
                yield s["start"], s["end"], tokens - prev
            prev = tokens
