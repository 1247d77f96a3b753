"""End-to-end metric arithmetic, on the client's records alone.

Copied in spirit from ``bench_server.py`` (TTFT at the first *content*
chunk) and made independent of how the server groups tokens into SSE
chunks: the program delivers one content chunk per decode chunk
(``LFKT_DECODE_CHUNK`` tokens), so tokens are counted from ``usage`` and
spread over a request's chunks, and nothing here assumes a chunk size.
"""

from __future__ import annotations

import math

MIN_TPOT_TOKENS = 8       # requests with fewer output tokens have no tpot
MIN_GAPS = 400            # fewer gaps than this hold no 99th percentile


def percentile(values, q: float) -> float | None:
    """Linear-interpolated percentile, ``q`` in 0..100; None when empty."""
    xs = sorted(values)
    if not xs:
        return None
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def failure(rec) -> str | None:
    """Why this request counts as failed, or None.  A request the benchmark
    itself cut at the window's end failed only if it had already gone
    wrong."""
    if rec.error:
        return rec.error
    if rec.status is not None and rec.status != 200:
        return f"status {rec.status}"
    if rec.cut:
        return None
    if rec.status is None:
        return "no response"
    if not rec.done:
        return "stream ended without [DONE]"
    if rec.completion_tokens is None:
        return "no usage chunk"
    if rec.completion_tokens < rec.max_tokens and rec.finish != "stop":
        return (f"{rec.completion_tokens} tokens of {rec.max_tokens}, "
                f"finish {rec.finish!r}")
    return None


def finished(records):
    return [r for r in records if r.done and failure(r) is None
            and r.completion_tokens]


def ttfts_ms(records) -> list[float]:
    """Due (open loop) or sent (closed loop) to the first content chunk."""
    return [(r.chunks[0] - r.due) * 1e3 for r in records
            if r.chunks and failure(r) is None]


def tpots_ms(records) -> list[float]:
    """(last chunk - first chunk) / (completion_tokens - 1), per finished
    request with at least MIN_TPOT_TOKENS output tokens."""
    return [(r.chunks[-1] - r.chunks[0]) * 1e3 / (r.completion_tokens - 1)
            for r in finished(records)
            if r.completion_tokens >= MIN_TPOT_TOKENS and len(r.chunks) > 1]


def gaps_ms(records) -> list[float]:
    """Every gap between consecutive content chunks of one stream, pooled."""
    out = []
    for r in records:
        if failure(r) is None:
            out += [(b - a) * 1e3 for a, b in zip(r.chunks, r.chunks[1:])]
    return out


def tokens_per_later_chunk(records) -> float:
    """Output tokens per content chunk after a request's first (which holds
    the one token prefill samples), pooled over finished requests."""
    toks = sum(r.completion_tokens - 1 for r in finished(records))
    chunks = sum(len(r.chunks) - 1 for r in finished(records))
    return toks / chunks if chunks > 0 else 1.0


def tokens_in_window(records, t0: float, t1: float) -> float:
    """Output tokens whose chunk arrived inside [t0, t1].  A finished
    request's tokens after the first are spread evenly over its later
    chunks; a request cut at the window's end gets the pooled share."""
    pooled = tokens_per_later_chunk(records)
    total = 0.0
    for r in records:
        if failure(r) is not None or not r.chunks:
            continue
        if r.done and r.completion_tokens and len(r.chunks) > 1:
            per = (r.completion_tokens - 1) / (len(r.chunks) - 1)
        else:
            per = pooled
        for i, t in enumerate(r.chunks):
            if t0 <= t <= t1:
                total += 1.0 if i == 0 else per
    return total


def end_to_end(records, t0: float, t1: float) -> dict:
    """Every end-to-end metric this benchmark knows, by name; None where
    the window does not support it."""
    gaps = gaps_ms(records)
    return {
        "ttft_p50_ms": percentile(ttfts_ms(records), 50),
        "tpot_p50_ms": percentile(tpots_ms(records), 50),
        "itl_p99_ms": percentile(gaps, 99) if len(gaps) >= MIN_GAPS else None,
        "out_tok_s": tokens_in_window(records, t0, t1) / (t1 - t0),
    }


def lateness_ms(lateness) -> dict:
    """How late the open loop's generator sent its arrivals."""
    ms = [x * 1e3 for x in lateness]
    return {"n": len(ms), "p50_ms": percentile(ms, 50),
            "p99_ms": percentile(ms, 99), "max_ms": max(ms, default=None)}
