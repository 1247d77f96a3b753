"""lfkt-obs tier-1 gates (ISSUE 4): tracing, metrics, structured logging.

Four layers:

1. **Metrics registry** — legal Prometheus exposition (HELP + one TYPE
   per family, cumulative ``_bucket{le=...}`` histograms, derived
   p50/p95/p99), labeled series, and the runtime catalog enforcement
   (unregistered/mis-typed names raise).
2. **Tracer unit behavior** — deterministic sampling, ring eviction
   bounds, W3C ``traceparent`` ingest, idempotent finish, global-event
   fan-in, and the zero-cost guarantee for sampled-out requests.
3. **Engine span trees** — both engines (serial, continuous) produce a
   complete, monotonic, nested span tree; concurrent load against a real
   :class:`ContinuousEngine` through the real server yields one complete
   tree per sampled request.
4. **Server surface** — /debug endpoints, response headers, request-id
   stamped JSON access logs, and the generated docs table staying in
   sync with the catalog.
"""

from __future__ import annotations

import asyncio
import importlib.util
import io
import json
import logging
import os
import re
import time

import httpx
import pytest

from llama_fastapi_k8s_gpu_tpu.engine import ContinuousEngine, Engine, FakeEngine
from llama_fastapi_k8s_gpu_tpu.obs.catalog import METRICS, markdown_table
from llama_fastapi_k8s_gpu_tpu.obs.logctx import (
    JsonFormatter,
    bind_request_id,
    current_request_id,
    setup_json_logging,
)
from llama_fastapi_k8s_gpu_tpu.obs.trace import Tracer, parse_traceparent
from llama_fastapi_k8s_gpu_tpu.server.app import create_app
from llama_fastapi_k8s_gpu_tpu.testing import write_tiny_llama_gguf
from llama_fastapi_k8s_gpu_tpu.utils.config import Settings
from llama_fastapi_k8s_gpu_tpu.utils.metrics import Metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MSGS = [{"role": "user", "content": "Say something."}]
BODY = {
    "bot_profile": {"name": "Alice.f",
                    "appearance": "tall,slim,blonde,cats,rain"},
    "user_profile": {"name": "Bob"},
    "context": [{"turn": "user", "message": "hi"}],
}
#: the tiny byte-level test tokenizer spends ~1 token per character, so
#: the real-model tests need a short explicit system prompt to fit the
#: tiny model's 128-token context (the default persona is ~430 chars)
TINY_BODY = {**BODY, "bot_profile": {**BODY["bot_profile"],
                                     "system_prompt": "Be brief."}}

EPS = 0.05   # span timestamp slack (clock reads happen around the work)


# ---------------------------------------------------------------------------
# layer 1: the metrics registry
# ---------------------------------------------------------------------------

_SAMPLE_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[a-zA-Z0-9_]+=\"[^\"]*\""
    r"(,[a-zA-Z0-9_]+=\"[^\"]*\")*\})? (-?[0-9]+(\.[0-9e+-]+)?)$")


def validate_exposition(text: str) -> dict:
    """Assert ``text`` is legal Prometheus exposition; returns
    family -> type.  A real scraper's constraints: HELP/TYPE once per
    family, every sample attributable to a typed family, no stray
    ``_min/_max/_avg`` pseudo-series."""
    types: dict[str, str] = {}
    helps: set[str] = set()
    for ln in text.rstrip("\n").splitlines():
        if ln.startswith("# HELP "):
            name = ln.split()[2]
            assert name not in helps, f"duplicate HELP for {name}"
            helps.add(name)
        elif ln.startswith("# TYPE "):
            _, _, name, mtype = ln.split()
            assert mtype in ("counter", "gauge", "histogram"), ln
            assert name not in types, f"duplicate TYPE for {name}"
            assert name in helps, f"TYPE before HELP for {name}"
            types[name] = mtype
        else:
            m = _SAMPLE_RE.match(ln)
            assert m, f"illegal sample line: {ln!r}"
            base = m.group(1)
            fam = base
            for suffix in ("_bucket", "_sum", "_count"):
                stem = base[: -len(suffix)] if base.endswith(suffix) else None
                if stem and types.get(stem) == "histogram":
                    fam = stem
            assert fam in types, f"sample {ln!r} has no TYPE"
    for name in types:
        assert not name.endswith(("_min", "_max", "_avg")), (
            f"summary-hack pseudo-series {name} survived")
    return types


def test_render_is_legal_exposition_with_histograms():
    m = Metrics()
    m.inc("requests_rejected_total")
    m.inc("http_requests_total", route="/response", code="200")
    m.set_gauge("queue_depth", 3)
    for v in (0.004, 0.03, 0.03, 0.2, 0.2, 0.2, 0.7, 3.0, 100.0):
        m.observe("queue_wait_seconds", v)
    text = m.render()
    types = validate_exposition(text)
    assert types["queue_wait_seconds"] == "histogram"
    assert types["queue_depth"] == "gauge"
    # cumulative buckets ending at le="+Inf" == count
    buckets = re.findall(
        r'queue_wait_seconds_bucket\{le="([^"]+)"\} (\d+)', text)
    counts = [int(c) for _, c in buckets]
    assert buckets[-1][0] == "+Inf"
    assert counts == sorted(counts), "buckets must be cumulative"
    assert counts[-1] == 9
    assert "queue_wait_seconds_count 9" in text
    # derived quantiles present, typed as their own gauge families
    assert types["queue_wait_seconds_p50"] == "gauge"
    assert types["queue_wait_seconds_p95"] == "gauge"
    assert types["queue_wait_seconds_p99"] == "gauge"


def test_labeled_series_render_and_quantiles_bracket_observations():
    m = Metrics()
    m.observe("request_seconds", 0.08, route="/response")
    m.observe("request_seconds", 0.08, route="/response")
    m.observe("request_seconds", 22.0, route="/response")
    m.observe("request_seconds", 0.001, route="/health")
    text = m.render()
    assert 'request_seconds_bucket{route="/response",le="0.1"} 2' in text
    assert 'request_seconds_count{route="/response"} 3' in text
    assert 'request_seconds_count{route="/health"} 1' in text
    p50 = float(re.search(
        r'request_seconds_p50\{route="/response"\} ([0-9.]+)', text).group(1))
    p99 = float(re.search(
        r'request_seconds_p99\{route="/response"\} ([0-9.]+)', text).group(1))
    assert 0.05 <= p50 <= 0.1       # inside the 0.08 observation's bucket
    assert 10.0 <= p99 <= 25.0      # inside the 22 s observation's bucket


def test_runtime_catalog_enforcement():
    m = Metrics()
    with pytest.raises(KeyError, match="not in the catalog"):
        m.inc("request_rejected_total")          # typo'd (singular)
    with pytest.raises(KeyError, match="is a counter"):
        m.set_gauge("requests_rejected_total", 1)
    with pytest.raises(KeyError, match="takes labels"):
        m.inc("http_requests_total")             # labels missing
    with pytest.raises(KeyError, match="takes labels"):
        m.observe("queue_wait_seconds", 0.1, route="/x")   # stray label
    # declared prefix family admits runtime-synthesized names
    m.set_gauge("scheduler_lanes_live", 2)
    m.set_gauge("scheduler_lane_prefix_hits", 5)
    assert "scheduler_lanes_live 2" in m.render()


def test_quantile_uses_target_buckets_own_lower_bound():
    """Empty lower buckets must not drag the interpolation floor to 0:
    5 observations all inside (1.0, 2.5] give histogram_quantile p50 of
    exactly 1.75 (code-review regression)."""
    m = Metrics()
    for v in (1.5, 1.8, 2.0, 2.2, 2.4):
        m.observe("queue_wait_seconds", v)
    text = m.render()
    p50 = float(re.search(r"queue_wait_seconds_p50 ([0-9.]+)",
                          text).group(1))
    assert p50 == pytest.approx(1.75)
    assert p50 >= 1.5        # never below the smallest observation's bucket


def test_every_catalog_histogram_declares_buckets():
    for metric in METRICS.values():
        if metric.mtype == "histogram":
            assert metric.buckets, metric.name
            assert list(metric.buckets) == sorted(metric.buckets)


# ---------------------------------------------------------------------------
# layer 2: tracer unit behavior
# ---------------------------------------------------------------------------

def test_sampling_zero_is_disarmed_and_lock_free():
    t = Tracer(sample=0.0, ring=8)
    t._lock = None          # any lock use would AttributeError
    assert t.start() is None
    t.annotate_inflight("watchdog_trip", reason="x")   # no-op, no lock
    t.finish(None)          # None-tolerant


def test_sampling_is_deterministic_by_counter():
    t = Tracer(sample=0.25, ring=64)
    drawn = [t.start() is not None for _ in range(16)]
    assert sum(drawn) == 4                      # exactly every 4th
    assert drawn == [False, False, False, True] * 4


def test_ring_eviction_bounds():
    t = Tracer(sample=1.0, ring=4)
    ids = []
    for _ in range(10):
        tr = t.start()
        ids.append(tr.trace_id)
        t.finish(tr)
    assert t.stats()["ring_used"] == 4
    kept = [s["trace_id"] for s in t.traces()]
    assert kept == list(reversed(ids[-4:]))     # newest first, oldest evicted
    assert t.get(ids[0]) is None                # evicted
    assert t.get(ids[-1]) is not None


def test_traceparent_ingest_and_propagation():
    tp = "00-" + "ab" * 16 + "-" + "12" * 8 + "-01"
    assert parse_traceparent(tp) == ("ab" * 16, "12" * 8)
    for bad in (None, "", "garbage", "01-" + "ab" * 16 + "-" + "12" * 8
                + "-01", "00-" + "0" * 32 + "-" + "12" * 8 + "-01"):
        assert parse_traceparent(bad) is None
    t = Tracer(sample=1.0, ring=4)
    tr = t.start(traceparent=tp)
    assert tr.trace_id == "ab" * 16
    assert tr.parent_span_id == "12" * 8
    out = tr.traceparent()
    assert out.startswith("00-" + "ab" * 16 + "-")
    assert out.split("-")[2] == tr.root.span_id
    # a fresh trace mints valid ids
    tr2 = t.start()
    assert parse_traceparent(tr2.traceparent()) == (tr2.trace_id,
                                                    tr2.root.span_id)


def test_finish_idempotent_and_annotate_targets_only_inflight():
    t = Tracer(sample=1.0, ring=8)
    tr_live, tr_done = t.start(), t.start()
    t.finish(tr_done)
    t.annotate_inflight("watchdog_trip", reason="stall")
    t.finish(tr_live)
    t.finish(tr_live)                            # idempotent
    assert t.stats()["ring_used"] == 2
    live = [e["name"] for e in tr_live.root.events]
    done = [e["name"] for e in tr_done.root.events]
    assert "watchdog_trip" in live and "watchdog_trip" not in done


def test_health_watchdog_and_fault_events_attach_to_inflight_traces():
    """The process-level fan-in: health transitions, watchdog trips and
    fault injections ride the module TRACER (the one the serving stack
    shares) into every in-flight trace as events."""
    from llama_fastapi_k8s_gpu_tpu.engine.watchdog import Watchdog
    from llama_fastapi_k8s_gpu_tpu.obs.trace import TRACER
    from llama_fastapi_k8s_gpu_tpu.utils.faults import FAULTS
    from llama_fastapi_k8s_gpu_tpu.utils.health import (
        DEGRADED,
        READY,
        HealthMonitor,
    )

    tr = TRACER.start("request")
    assert tr is not None, "module tracer must default to sample=1.0"
    try:
        h = HealthMonitor()
        h.transition(READY, "engine loaded")
        h.transition(DEGRADED, "drill")
        eng = FakeEngine()
        wd = Watchdog(eng, h, Metrics())
        wd.handle_trip("stalled_decode: drill")
        FAULTS.arm("decode_step:slow:delay=0")
        try:
            FAULTS.fire("decode_step")
        finally:
            FAULTS.disarm()
    finally:
        TRACER.finish(tr)
    events = [e["name"] for e in tr.root.events]
    assert "health_transition" in events
    assert "watchdog_trip" in events
    assert "fault_fired" in events
    trip = next(e for e in tr.root.events if e["name"] == "watchdog_trip")
    assert "stalled_decode" in trip["reason"]


def test_events_fan_into_private_tracers_too():
    """create_app(tracer=...) installs private tracers; process-level
    events (health/watchdog/faults) must reach their in-flight traces,
    not only the module default's (code-review regression)."""
    from llama_fastapi_k8s_gpu_tpu.utils.health import READY, HealthMonitor

    t = Tracer(sample=1.0, ring=4)
    tr = t.start()
    try:
        HealthMonitor().transition(READY, "fan-in probe")
    finally:
        t.finish(tr)
    assert any(e["name"] == "health_transition" for e in tr.root.events)


def test_finish_sweeps_open_spans_closed():
    """A producer error path that leaves a span open (a prefill that
    raised) must not export end=null: finish closes it at the root's end
    with an ``auto_closed`` stamp, so waterfalls never show a phantom
    still-running phase on a completed request."""
    t = Tracer(sample=1.0, ring=4)
    tr = t.start()
    dangling = tr.span("engine")
    closed = tr.span("queue")
    closed.end()
    t.finish(tr)
    d = tr.to_dict()
    spans = {c["name"]: c for c in d["root"]["children"]}
    assert spans["engine"]["end"] == d["root"]["end"]
    assert spans["engine"]["attrs"].get("auto_closed") is True
    assert "auto_closed" not in spans["queue"]["attrs"]
    assert dangling.t1 is not None


def test_node_cap_counts_drops():
    from llama_fastapi_k8s_gpu_tpu.obs.trace import MAX_NODES_PER_TRACE
    t = Tracer(sample=1.0, ring=2)
    tr = t.start()
    for i in range(MAX_NODES_PER_TRACE + 50):
        tr.span(f"s{i}")
    d = tr.to_dict()
    assert len(d["root"]["children"]) == MAX_NODES_PER_TRACE - 1
    assert d["dropped_nodes"] == 51


def test_span_ids_cost_one_random_read_a_trace(monkeypatch):
    """16 lowercase hex characters, never zero, distinct within the trace,
    and no ``uuid4`` (a read of the kernel's pool) per span: one per
    trace.  ``traceparent`` stays valid."""
    import llama_fastapi_k8s_gpu_tpu.obs.trace as trace_mod

    reads = []
    real = trace_mod.uuid.uuid4

    def counted():
        reads.append(1)
        return real()

    monkeypatch.setattr(trace_mod.uuid, "uuid4", counted)
    t = Tracer(sample=1.0, ring=2)
    tr = t.start()
    spans = [tr.root] + [tr.span(f"s{i}") for i in range(40)]
    spans += [spans[1].child("kid"), spans[-1].child("kid")]
    assert len(reads) == 1
    ids = [sp.span_id for sp in spans]
    assert len(set(ids)) == len(ids)
    for i in ids:
        assert len(i) == 16 and i == i.lower() and int(i, 16) != 0
    assert parse_traceparent(tr.traceparent()) == (tr.trace_id,
                                                   tr.root.span_id)
    assert parse_traceparent(trace_mod.span_traceparent(spans[5])) == \
        (tr.trace_id, spans[5].span_id)
    # another trace counts up from another base
    assert t.start().root.span_id != tr.root.span_id
    # an ingested trace keeps the caller's trace id and still gets a base
    tp = "00-" + "ab" * 16 + "-" + "12" * 8 + "-01"
    tr3 = t.start(traceparent=tp)
    assert tr3.trace_id == "ab" * 16 and int(tr3.root.span_id, 16) != 0


@pytest.mark.parametrize("base", [0, (1 << 64) - 1, (1 << 64) - 3])
def test_span_ids_wrap_and_are_never_zero(base):
    import llama_fastapi_k8s_gpu_tpu.obs.trace as trace_mod

    tr = trace_mod.Trace()
    tr._span_base = base
    ids = [tr._new_span_id() for _ in range(6)]
    assert all(len(i) == 16 and int(i, 16) != 0 for i in ids)


# -- the device inside first_token (PR 54): obs/trace.py end_first_token ----

def _ring_of(monkeypatch, intervals):
    """A private jit registry whose ring holds ``intervals``:
    (program, rid, dispatch return, done) in the device's order."""
    from llama_fastapi_k8s_gpu_tpu.obs import devtime

    reg = devtime.DevtimeRegistry(armed=True, stamps=True)
    with reg._lock:
        for name, rid, t_ret, done in intervals:
            reg._close([name, rid, t_ret, None, None], done)
    monkeypatch.setattr(devtime, "DEVTIME", reg)
    return reg


def _kids(span):
    return [(c.name, round(c.t0 - span.t0, 3), round(c.t1 - span.t0, 3),
             c.attrs.get("own")) for c in span.children]


def test_first_token_children_are_the_ring_clipped_to_the_span(monkeypatch):
    from llama_fastapi_k8s_gpu_tpu.obs.trace import end_first_token

    tr = Tracer(sample=1.0, ring=2).start()
    me, t0 = tr.trace_id, time.time() - 10.0
    _ring_of(monkeypatch, [
        ("lane_decode_chunk", "", t0 - 9.0, t0 - 5.0),   # before: not a child
        ("prefill_chunk", me, t0 - 3.0, t0 + 1.0),       # clipped at the start
        ("prefill_chunk", me, t0 - 2.9, t0 + 2.0),
        ("prefill_chunk", "someone-else", t0 - 2.8, t0 + 2.5),
        ("first_sample", me, t0 - 0.1, t0 + 2.6),
        ("lane_decode_chunk", "", t0 + 2.7, t0 + 4.0),   # every lane's: no own
    ])
    prefill = tr.span("prefill", t0=t0 - 4.0)
    fspan = prefill.child("first_token", t0=t0)
    end_first_token(fspan, prefill, waves=2)
    assert fspan.attrs == {"waves": 2}
    kids = _kids(fspan)
    assert kids[:5] == [
        ("device.prefill_chunk", 0.0, 1.0, True),
        ("device.prefill_chunk", 1.0, 2.0, True),
        ("device.prefill_chunk", 2.0, 2.5, False),
        ("device.first_sample", 2.5, 2.6, True),
        ("device.lane_decode_chunk", 2.7, 4.0, None),
    ]
    assert kids[5][0] == "host_fetch" and kids[5][1] == 4.0
    assert fspan.children[5].t1 == fspan.t1 and len(kids) == 6
    # the whole interval rides as `seconds`, clipped or not
    assert fspan.children[0].attrs["seconds"] == pytest.approx(4.0)
    assert "own" not in fspan.children[4].attrs
    # a child is a span like every other: name, start, end, its own id
    d = fspan.to_dict()
    assert all(c["span_id"] and c["end"] >= c["start"] >= d["start"]
               for c in d["children"])


def test_first_token_ends_after_an_interval_that_outlives_it(monkeypatch):
    """An interval still open on the device when the token is on the host
    is not in the ring yet; one that ended later than now is clipped."""
    from llama_fastapi_k8s_gpu_tpu.obs.trace import end_first_token

    tr = Tracer(sample=1.0, ring=2).start()
    t0 = time.time() - 1.0
    _ring_of(monkeypatch, [("prefill_chunk", tr.trace_id, t0, t0 + 3600.0)])
    fspan = tr.span("first_token", t0=t0)
    end_first_token(fspan)
    (kid, fetch) = fspan.children
    assert kid.t1 == fetch.t0 == fetch.t1 == fspan.t1


def test_first_token_without_a_stamp_is_the_bare_span(monkeypatch):
    """The tracer armed on a registry that stamped nothing (LFKT_DEVTIME=0,
    a private tracer beside ``LFKT_TRACE_SAMPLE=0``): no child is made up."""
    from llama_fastapi_k8s_gpu_tpu.obs.trace import end_first_token

    _ring_of(monkeypatch, [])
    tr = Tracer(sample=1.0, ring=2).start()
    fspan = tr.span("first_token", t0=time.time() - 1.0)
    end_first_token(fspan, None, None, deferred=False)
    assert fspan.children == [] and fspan.t1 is not None
    assert fspan.attrs == {"deferred": False}


def test_each_slice_span_gets_its_own_programs_interval(monkeypatch):
    """Matched by the return of the dispatch, which lies inside the
    slice's span; slices that were done before ``first_token`` opened (one
    a wave beside live lanes) are found as well."""
    from llama_fastapi_k8s_gpu_tpu.obs.trace import end_first_token

    tr = Tracer(sample=1.0, ring=2).start()
    me, t0 = tr.trace_id, time.time() - 10.0
    prefill = tr.span("prefill", t0=t0)
    s1 = prefill.child("prefill_slice", t0=t0 + 0.10)
    s1.end(t0 + 0.12)
    other = prefill.child("tokenize", t0=t0)
    other.end(t0 + 0.1)
    s2 = prefill.child("prefill_slice", t0=t0 + 2.00)
    s2.end(t0 + 2.02)
    _ring_of(monkeypatch, [
        ("prefill_chunk", me, t0 + 0.11, t0 + 1.0),
        ("lane_decode_chunk", "", t0 + 0.5, t0 + 1.8),
        ("prefill_chunk", "someone-else", t0 + 2.01, t0 + 2.5),
        ("prefill_chunk", me, t0 + 2.015, t0 + 3.0),
    ])
    fspan = prefill.child("first_token", t0=t0 + 2.02)
    end_first_token(fspan, prefill)
    assert s1.attrs == {"device_s": pytest.approx(0.89),
                        "done_at": pytest.approx(t0 + 1.0)}
    assert s2.attrs == {"device_s": pytest.approx(0.5),
                        "done_at": pytest.approx(t0 + 3.0)}
    assert other.attrs == {}
    assert [k[0] for k in _kids(fspan)] == [
        "device.prefill_chunk", "device.prefill_chunk", "host_fetch"]


def test_first_token_children_stop_at_the_node_cap(monkeypatch):
    from llama_fastapi_k8s_gpu_tpu.obs.trace import (MAX_NODES_PER_TRACE,
                                                     end_first_token)

    tr = Tracer(sample=1.0, ring=2).start()
    t0 = time.time() - 100.0
    _ring_of(monkeypatch, [("lane_decode_chunk", "", t0 + 0.1 * i,
                            t0 + 0.1 * (i + 1))
                           for i in range(MAX_NODES_PER_TRACE + 40)])
    fspan = tr.span("first_token", t0=t0)
    end_first_token(fspan)
    d = tr.to_dict()
    assert len(fspan.children) == MAX_NODES_PER_TRACE - 2   # root + itself
    assert d["dropped_nodes"] == 42 + 1                     # + host_fetch
    assert fspan.t1 is not None


@pytest.mark.anyio
async def test_metrics_and_debug_compiles_carry_the_device_seconds():
    from llama_fastapi_k8s_gpu_tpu.obs.devtime import DEVTIME

    DEVTIME.register_program("stamped_toy", kind="entry")
    DEVTIME.register_program("unstamped_toy", kind="entry")
    with DEVTIME._lock:
        DEVTIME._close(["stamped_toy", "", 1.0, None, None], 1.0)
        last = DEVTIME._last_done
        DEVTIME._close(["stamped_toy", "", last + 1.0, None, None],
                       last + 1.25)
    app = create_app(engine=FakeEngine(reply="hey"))
    metrics, compiles = await _serve(app, [("get", "/metrics", {}),
                                           ("get", "/debug/compiles", {})])
    text = metrics.text
    types = validate_exposition(text)
    assert types["jit_device_seconds_total"] == "gauge"
    assert types["jit_device_intervals_total"] == "gauge"
    secs = re.search(r'^jit_device_seconds_total\{program="stamped_toy"\} '
                     r'(\S+)$', text, re.M)
    assert float(secs.group(1)) == pytest.approx(0.25, abs=1e-6)
    assert 'jit_device_intervals_total{program="stamped_toy"} 2' in text
    # a program that was never stamped exports no zero series
    assert 'program="unstamped_toy"} ' in text      # its dispatches
    assert 'jit_device_seconds_total{program="unstamped_toy"}' not in text
    doc = compiles.json()
    row = {p["name"]: p for p in doc["programs"]}["stamped_toy"]
    assert row["device_seconds_total"] == pytest.approx(0.25, abs=1e-6)
    assert row["intervals"] == 2 and row["stamped"] is True
    assert set(doc["stamps"]) == {"armed", "misses", "pending", "ring"}
    for name in ("jit_device_seconds_total", "jit_device_intervals_total"):
        assert name in METRICS and METRICS[name].labels == ("program",)


# ---------------------------------------------------------------------------
# layer 3: engine span trees (all four engines; ISSUE 4 acceptance)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def model_path(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("model") / "tiny.gguf")
    write_tiny_llama_gguf(path)
    return path


@pytest.fixture(scope="module")
def cengine(model_path):
    eng = ContinuousEngine(model_path, batch_size=4, n_ctx=128,
                           decode_chunk=4, max_gen_tokens=16,
                           prefill_buckets=(32, 64, 128))
    yield eng
    eng.shutdown()


def _spans_by_name(node: dict, out=None) -> dict:
    out = {} if out is None else out
    out.setdefault(node["name"], []).append(node)
    for c in node["children"]:
        _spans_by_name(c, out)
    return out


def _assert_monotonic_nested(node: dict, lo: float, hi: float, path="root"):
    """Every span [start, end] sits inside its parent's window (±EPS) and
    ends after it starts."""
    assert node["start"] >= lo - EPS, f"{path}/{node['name']} starts early"
    assert node["end"] is not None, f"{path}/{node['name']} never ended"
    assert node["end"] >= node["start"], f"{path}/{node['name']} negative"
    assert node["end"] <= hi + EPS, f"{path}/{node['name']} outlives parent"
    for c in node["children"]:
        _assert_monotonic_nested(c, node["start"], node["end"],
                                 f"{path}/{node['name']}")


def _assert_engine_tree(trace_dict: dict, want_decode_chunks: bool = True):
    root = trace_dict["root"]
    names = _spans_by_name(root)
    assert "prefill" in names, sorted(names)
    prefill = names["prefill"][0]
    assert prefill["attrs"]["n_prompt"] > 0
    assert prefill["attrs"].get("ttft_s") is not None
    if want_decode_chunks:
        assert "decode_chunk" in names, sorted(names)
    _assert_monotonic_nested(root, root["start"], root["end"])


def test_serial_engine_span_tree(model_path):
    eng = Engine(model_path, n_ctx=128, decode_chunk=4, max_gen_tokens=16,
                 prefill_buckets=(32, 64, 128))
    t = Tracer(sample=1.0, ring=4)
    tr = t.start()
    out = eng.create_chat_completion(MSGS, temperature=0.0, max_tokens=12,
                                     trace=tr)
    t.finish(tr)
    assert out["usage"]["completion_tokens"] >= 1
    d = tr.to_dict()
    _assert_engine_tree(d)
    names = _spans_by_name(d["root"])
    engine_span = names["engine"][0]
    assert engine_span["attrs"]["engine"] == "Engine"
    assert engine_span["attrs"]["completion_tokens"] >= 1
    # streaming rides the same classes
    tr2 = t.start()
    list(eng.create_chat_completion(MSGS, stream=True, temperature=0.0,
                                    max_tokens=8, trace=tr2))
    t.finish(tr2)
    _assert_engine_tree(tr2.to_dict())


def test_continuous_engine_span_tree(cengine):
    t = Tracer(sample=1.0, ring=8)
    tr = t.start()
    out = cengine.submit(MSGS, temperature=0.0, max_tokens=8,
                         trace=tr).result(timeout=120)
    t.finish(tr)
    assert out["usage"]["completion_tokens"] >= 1
    d = tr.to_dict()
    names = _spans_by_name(d["root"])
    for want in ("pending", "prefill", "decode"):
        assert want in names, sorted(names)
    assert "decode_chunk" in names
    decode = names["decode"][0]
    assert decode["attrs"]["lane"] in range(4)
    assert decode["attrs"]["finish"] in ("stop", "length")
    _assert_monotonic_nested(d["root"], d["root"]["start"], d["root"]["end"])
    assert d["meta"]["engine"] == "ContinuousEngine"


def test_zero_cost_when_sampled_out(model_path, monkeypatch):
    """LFKT_TRACE_SAMPLE=0 ⇒ the decode path may not construct a single
    span or touch a trace lock: poison Span construction and generate."""
    import llama_fastapi_k8s_gpu_tpu.obs.trace as trace_mod

    eng = Engine(model_path, n_ctx=128, decode_chunk=4, max_gen_tokens=16,
                 prefill_buckets=(32, 64, 128))
    t = Tracer(sample=0.0, ring=4)
    assert t.start() is None

    def boom(*a, **kw):
        raise AssertionError("span constructed for a sampled-out request")

    monkeypatch.setattr(trace_mod.Span, "__init__", boom)
    out = eng.create_chat_completion(MSGS, temperature=0.0, max_tokens=8,
                                     trace=t.start())
    assert out["usage"]["completion_tokens"] >= 1


# ---------------------------------------------------------------------------
# layer 3b: concurrent load through the real server on ContinuousEngine
# ---------------------------------------------------------------------------

@pytest.mark.anyio
async def test_concurrent_load_trace_completeness(cengine):
    """N parallel requests against a real ContinuousEngine through the
    real server: every sampled request yields a COMPLETE span tree —
    request → queue → pending → prefill → decode(+chunks) — with
    monotonic, properly nested timestamps (ISSUE 4 acceptance)."""
    tracer = Tracer(sample=1.0, ring=64)
    app = create_app(engine=cengine,
                     settings=Settings(batch_size=4, max_queue_size=32,
                                       timeout_seconds=120),
                     tracer=tracer)
    transport = httpx.ASGITransport(app=app)
    async with transport:
        await app.router.startup()
        async with httpx.AsyncClient(transport=transport,
                                     base_url="http://test") as client:
            results = await asyncio.gather(*[
                client.post("/response", json=TINY_BODY) for _ in range(8)])
        await app.router.shutdown()
    assert [r.status_code for r in results] == [200] * 8
    rids = {r.headers["x-request-id"] for r in results}
    assert len(rids) == 8
    stats = tracer.stats()
    assert stats["inflight"] == 0
    for rid in rids:
        tr = tracer.get(rid)
        assert tr is not None, f"request {rid} left no trace"
        d = tr.to_dict()
        assert d["finished"]
        names = _spans_by_name(d["root"])
        for want in ("queue", "pending", "prefill", "decode",
                     "decode_chunk"):
            assert want in names, (rid, sorted(names))
        assert d["root"]["attrs"]["status"] == 200
        assert d["root"]["attrs"]["route"] == "/response"
        _assert_monotonic_nested(d["root"], d["root"]["start"],
                                 d["root"]["end"])
        assert d["meta"]["tokens"] >= 1


# ---------------------------------------------------------------------------
# layer 4: server surface — debug endpoints, headers, logs, docs
# ---------------------------------------------------------------------------

async def _serve(app, calls):
    transport = httpx.ASGITransport(app=app)
    out = []
    async with transport:
        await app.router.startup()
        async with httpx.AsyncClient(transport=transport,
                                     base_url="http://test") as client:
            for method, path, kw in calls:
                out.append(await getattr(client, method)(path, **kw))
        await app.router.shutdown()
    return out


@pytest.mark.anyio
async def test_debug_endpoints_and_headers():
    tracer = Tracer(sample=1.0, ring=8)
    app = create_app(engine=FakeEngine(reply="hey"), tracer=tracer)
    tp = "00-" + "cd" * 16 + "-" + "34" * 8 + "-01"
    r1, listing, missing = await _serve(app, [
        ("post", "/response", {"json": BODY,
                               "headers": {"traceparent": tp}}),
        ("get", "/debug/traces", {}),
        ("get", "/debug/traces/deadbeef", {}),
    ])
    # traceparent ingested: its trace id IS the request id
    assert r1.headers["x-request-id"] == "cd" * 16
    assert r1.headers["traceparent"].startswith("00-" + "cd" * 16 + "-")
    assert missing.status_code == 404
    doc = listing.json()
    ids = [s["trace_id"] for s in doc["traces"]]
    assert "cd" * 16 in ids
    assert doc["stats"]["ring_used"] >= 1
    # the full tree is servable by id
    full, = await _serve(app, [("get", f"/debug/traces/{'cd' * 16}", {})])
    tree = full.json()
    assert tree["parent_span_id"] == "34" * 8
    assert tree["root"]["name"] == "request"
    assert _spans_by_name(tree["root"]).get("queue")


@pytest.mark.anyio
async def test_debug_requests_snapshot_during_flight():
    tracer = Tracer(sample=1.0, ring=8)
    app = create_app(engine=FakeEngine(reply="ok", delay=0.5),
                     tracer=tracer)
    transport = httpx.ASGITransport(app=app)
    async with transport:
        await app.router.startup()
        async with httpx.AsyncClient(transport=transport,
                                     base_url="http://test") as client:
            task = asyncio.create_task(client.post("/response", json=BODY))
            await asyncio.sleep(0.15)     # mid-generation
            snap = (await client.get("/debug/requests")).json()["requests"]
            inflight = [s for s in snap if s["name"] == "request"
                        and s.get("route") == "/response"]
            assert inflight, snap
            assert inflight[0]["age_s"] > 0
            assert inflight[0]["deadline_remaining_s"] is not None
            r = await task
            assert r.status_code == 200
        await app.router.shutdown()
    assert tracer.stats()["inflight"] == 0


@pytest.mark.anyio
async def test_request_id_in_json_log_records():
    stream = io.StringIO()
    from llama_fastapi_k8s_gpu_tpu.obs.logctx import access_logger

    handler = setup_json_logging(access_logger, stream)
    access_logger.setLevel(logging.INFO)
    try:
        tracer = Tracer(sample=1.0, ring=8)
        app = create_app(engine=FakeEngine(reply="yo"), tracer=tracer)
        r, = await _serve(app, [("post", "/response", {"json": BODY})])
    finally:
        access_logger.removeHandler(handler)
    records = [json.loads(ln) for ln in stream.getvalue().splitlines()]
    access = [rec for rec in records if rec.get("route") == "/response"]
    assert access, records
    rec = access[-1]
    assert rec["request_id"] == r.headers["x-request-id"]
    assert rec["status"] == 200
    assert rec["logger"] == "lfkt.access"
    assert rec["duration_s"] >= 0


def test_request_id_contextvar_scoping():
    assert current_request_id() == "-"
    with bind_request_id("req-123"):
        assert current_request_id() == "req-123"
        rec = logging.LogRecord("x", logging.INFO, __file__, 1, "m", (), None)
        assert json.loads(JsonFormatter().format(rec))["request_id"] == \
            "req-123"
    assert current_request_id() == "-"


@pytest.mark.anyio
async def test_sampled_out_requests_still_get_request_ids():
    tracer = Tracer(sample=0.0, ring=8)
    app = create_app(engine=FakeEngine(reply="hi"), tracer=tracer)
    r1, r2, listing = await _serve(app, [
        ("post", "/response", {"json": BODY}),
        ("post", "/response", {"json": BODY}),
        ("get", "/debug/traces", {}),
    ])
    assert r1.headers["x-request-id"] != r2.headers["x-request-id"]
    assert "traceparent" not in r1.headers       # no trace to propagate
    assert listing.json()["traces"] == []


def test_docs_metrics_table_is_generated_from_catalog():
    """The docs/OBSERVABILITY.md metrics table IS the catalog generator's
    output (OBS002's docs coverage, pinned byte-for-byte)."""
    doc = open(os.path.join(REPO, "docs", "OBSERVABILITY.md"),
               encoding="utf-8").read()
    begin = "<!-- metrics:begin (generated - do not hand-edit) -->"
    assert begin in doc and "<!-- metrics:end -->" in doc
    block = doc.split(begin)[1].split("<!-- metrics:end -->")[0].strip()
    assert block == markdown_table().strip(), (
        "docs/OBSERVABILITY.md metrics table is stale: regenerate with "
        "python -m llama_fastapi_k8s_gpu_tpu.obs.catalog")


# ---------------------------------------------------------------------------
# tools/trace_report.py — the RUNBOOK waterfall renderer
# ---------------------------------------------------------------------------

def _load_trace_report():
    spec = importlib.util.spec_from_file_location(
        "trace_report", os.path.join(REPO, "tools", "trace_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_trace_report_waterfall(model_path):
    eng = Engine(model_path, n_ctx=128, decode_chunk=4, max_gen_tokens=16,
                 prefill_buckets=(32, 64, 128))
    t = Tracer(sample=1.0, ring=4)
    tr = t.start()
    tr.root.set(route="/response")
    tr.note(route="/response")
    eng.create_chat_completion(MSGS, temperature=0.0, max_tokens=8, trace=tr)
    t.finish(tr)
    mod = _load_trace_report()
    text = mod.render_trace(tr.to_dict())
    assert tr.trace_id in text
    for phase in ("engine", "prefill", "decode_chunk"):
        assert phase in text, text
    assert "phase breakdown:" in text
    assert re.search(r"engine\s+ +[0-9.]+ ms +[0-9.]+%", text)
    assert "█" in text
    listing = mod.render_listing({"traces": t.traces()})
    assert tr.trace_id in listing


@pytest.mark.anyio
async def test_server_installs_metrics_sink_and_slice_histogram(model_path):
    """The app injects its Metrics registry into the engine at startup
    (engine.metrics_sink); a sliced prefill then lands observations in the
    prefill_slice_seconds histogram on /metrics."""
    from tests.test_server import lifespan_client, make_client

    eng = Engine(model_path, n_ctx=128, decode_chunk=4, max_gen_tokens=16,
                 prefill_buckets=(32, 64, 128), prefix_cache=False,
                 prefill_chunk=16, prefill_overlap=2)
    app, transport = make_client(eng)
    async with transport:
        await app.router.startup()
        async with await lifespan_client(app, transport) as client:
            assert eng.metrics_sink is app.state.metrics
            body = dict(TINY_BODY)
            body["context"] = [{"turn": "user",
                                "message": "one two three four five " * 2}]
            r = await client.post("/response", json=body)
            assert r.status_code == 200
            m = (await client.get("/metrics")).text
            assert "# TYPE prefill_slice_seconds histogram" in m
            count = re.search(r"prefill_slice_seconds_count (\d+)", m)
            assert count is not None and int(count.group(1)) >= 2
        await app.router.shutdown()


def test_trace_report_renders_prefill_slice_overlap(model_path):
    """A sliced prefill's per-slice events render as ▒ duration bars
    (offset-labeled) tiling the prefill span — the round-6 overlap view."""
    eng = Engine(model_path, n_ctx=128, decode_chunk=4, max_gen_tokens=16,
                 prefill_buckets=(32, 64, 128), prefix_cache=False,
                 prefill_chunk=16, prefill_overlap=2)
    t = Tracer(sample=1.0, ring=4)
    tr = t.start()
    eng.create_chat_completion(
        [{"role": "user", "content": "one two three four five six " * 2}],
        temperature=0.0, max_tokens=4, trace=tr)
    t.finish(tr)
    mod = _load_trace_report()
    text = mod.render_trace(tr.to_dict())
    assert "▒" in text, text
    slices = re.findall(r"slice@(\d+)", text)
    assert len(slices) >= 2, text                 # multi-slice prompt
    assert [int(s) for s in slices] == sorted(int(s) for s in slices)
    assert re.search(r"slice@\d+.*n=\d+", text)   # token count rides along
