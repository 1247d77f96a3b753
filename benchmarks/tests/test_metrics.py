"""The end-to-end arithmetic on synthetic event lists."""

import pytest

import metrics
from client import Record


def rec(due, chunks, completion=None, max_tokens=None, done=True, **kw):
    return Record(index=0, due=due, sent=due, status=200, chunks=list(chunks),
                  completion_tokens=completion, done=done, finish="length",
                  max_tokens=max_tokens if max_tokens is not None
                  else (completion or 0), **kw)


def test_percentile_interpolates():
    assert metrics.percentile([], 50) is None
    assert metrics.percentile([3.0], 99) == 3.0
    assert metrics.percentile([1, 2, 3, 4], 50) == 2.5
    assert metrics.percentile(range(101), 99) == 99


def test_ttft_from_due_not_from_sent():
    r = rec(10.0, [10.5, 11.0], completion=9)
    r.sent = 10.3                      # the open loop sent it late
    assert metrics.ttfts_ms([r]) == [pytest.approx(500.0)]


def test_tpot_uses_usage_not_chunk_count():
    # 17 tokens in 3 chunks (1 + 8 + 8), 0.8 s from first to last chunk
    r = rec(0.0, [1.0, 1.4, 1.8], completion=17)
    assert metrics.tpots_ms([r]) == [pytest.approx(800.0 / 16)]
    short = rec(0.0, [1.0, 1.1], completion=7)
    assert metrics.tpots_ms([short]) == []     # under MIN_TPOT_TOKENS


def test_gaps_are_pooled_over_requests():
    a = rec(0.0, [1.0, 1.25, 1.5], completion=17)
    b = rec(0.0, [2.0, 2.5], completion=9)
    assert sorted(metrics.gaps_ms([a, b])) == [pytest.approx(250.0)] * 2 + [
        pytest.approx(500.0)]


def test_window_token_counting_spreads_usage_over_chunks():
    # finished: 1 + 8 + 8 tokens; only the last two chunks are in the window
    a = rec(0.0, [0.5, 1.5, 2.5], completion=17)
    # cut at the window's end: no usage; its later chunk gets the pooled 8
    b = rec(0.0, [1.2, 2.2], done=False, cut=True, max_tokens=64)
    assert metrics.tokens_per_later_chunk([a, b]) == pytest.approx(8.0)
    got = metrics.tokens_in_window([a, b], 1.0, 3.0)
    assert got == pytest.approx(16.0 + 1.0 + 8.0)


def test_failures():
    ok = rec(0.0, [1.0, 2.0], completion=9)
    assert metrics.failure(ok) is None
    eos = rec(0.0, [1.0], completion=3, max_tokens=9)
    eos.finish = "stop"
    assert metrics.failure(eos) is None
    short = rec(0.0, [1.0], completion=3, max_tokens=9)
    assert "3 tokens of 9" in metrics.failure(short)
    no_done = rec(0.0, [1.0], completion=9, done=False)
    assert metrics.failure(no_done) == "stream ended without [DONE]"
    cut = rec(0.0, [1.0], done=False, cut=True, max_tokens=9)
    assert metrics.failure(cut) is None
    busy = rec(0.0, [], done=False)
    busy.status = 503
    assert metrics.failure(busy) == "status 503"
    err = rec(0.0, [1.0], completion=9, error="Generation timed out")
    assert metrics.failure(err) == "Generation timed out"
    # a failed request never lends its chunks to a latency
    assert metrics.ttfts_ms([short, busy]) == []


def test_itl_needs_enough_gaps():
    few = [rec(0.0, [1.0, 1.1, 1.2], completion=17)]
    assert metrics.end_to_end(few, 0.0, 2.0)["itl_p99_ms"] is None
    many = [rec(0.0, [i * 0.1 for i in range(metrics.MIN_GAPS + 1)],
                completion=8 * metrics.MIN_GAPS + 1)]
    assert metrics.end_to_end(many, 0.0, 100.0)["itl_p99_ms"] == \
        pytest.approx(100.0)


def test_lateness():
    out = metrics.lateness_ms([0.001, 0.002, 0.010])
    assert out["n"] == 3 and out["max_ms"] == pytest.approx(10.0)
    assert out["p50_ms"] == pytest.approx(2.0)
