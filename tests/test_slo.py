"""lfkt-perf SLO gates (ISSUE 7): burn-rate math + the /debug surface.

Three layers:

1. **Burn-rate math units** — bucket interpolation exactness, window
   baseline selection with injected clocks (window units: a 60 s window
   diffs against the snapshot ~60 s back, not since boot), all three SLO
   kinds (latency, floor, ratio), per-series worst-bucket reporting, and
   the warn-vs-breach multi-window verdict.
2. **Gauge export** — ``slo_burn_rate{slo=,window=}`` lands in legal
   exposition on the bound registry.
3. **Server surface** — /debug/slo and /debug/compiles schemas over the
   real app, /debug/profile's opt-in gating, and the ISSUE acceptance:
   a recompile storm arising while a request is in flight is visible in
   /metrics, in /debug/slo, AND as an event on the in-flight trace.
"""

from __future__ import annotations

import asyncio

import httpx
import pytest

from llama_fastapi_k8s_gpu_tpu.engine import FakeEngine
from llama_fastapi_k8s_gpu_tpu.obs.devtime import DEVTIME, DevtimeRegistry
from llama_fastapi_k8s_gpu_tpu.obs.slo import SLOEngine, SLOS, _n_at_or_below
from llama_fastapi_k8s_gpu_tpu.obs.trace import Tracer
from llama_fastapi_k8s_gpu_tpu.server.app import create_app
from llama_fastapi_k8s_gpu_tpu.utils.metrics import Metrics

BODY = {
    "bot_profile": {"name": "Alice.f",
                    "appearance": "tall,slim,blonde,cats,rain"},
    "user_profile": {"name": "Bob"},
    "context": [{"turn": "user", "message": "hi"}],
}

#: thresholds aligned to engine_ttft_seconds bucket bounds for exactness
THRESHOLDS = {"ttft_p95": 0.25, "decode_floor": 10.0,
              "error_rate": 0.01, "queue_p95": 0.25}


def _engine(m, windows=(60.0, 600.0), devtime=None):
    return SLOEngine(m, windows=list(windows), thresholds=THRESHOLDS,
                     devtime=devtime or DevtimeRegistry(armed=True,
                                                        budget=32))


def _slo(doc, name):
    return next(s for s in doc["slos"] if s["name"] == name)


# ---------------------------------------------------------------------------
# layer 1: burn-rate math
# ---------------------------------------------------------------------------

def test_n_at_or_below_interpolation():
    bounds = (0.1, 0.2, 0.4)
    #           <=0.1  <=0.2  <=0.4  +Inf
    deltas = [4, 2, 2, 2]
    # exact at a bound: cumulative counts
    assert _n_at_or_below(bounds, deltas, 10, 0.2) == 6
    # mid-bucket: linear interpolation inside (0.2, 0.4]
    assert _n_at_or_below(bounds, deltas, 10, 0.3) == pytest.approx(7.0)
    # above the largest finite bound: everything
    assert _n_at_or_below(bounds, deltas, 10, 9.9) == 10
    # empty window
    assert _n_at_or_below(bounds, [0, 0, 0, 0], 0, 0.2) == 0.0


def test_latency_slo_burns_when_tail_exceeds_threshold():
    m = Metrics()
    s = _engine(m)
    s.evaluate(now=0.0)                    # baseline: both windows realize
    for _ in range(18):
        m.observe("engine_ttft_seconds", 0.05, bucket="128", model="m")
    for _ in range(2):                     # 10% of events over the bound
        m.observe("engine_ttft_seconds", 1.8, bucket="128", model="m")
    doc = s.evaluate(now=1_000.0)
    ttft = _slo(doc, "ttft_p95")
    for ev in ttft["windows"].values():
        # bad_frac 0.1 over a 0.05 budget = burn 2.0
        assert ev["burn_rate"] == pytest.approx(2.0, rel=1e-3)
        assert ev["worst_series"] == "128,m"
        assert "truncated" not in ev       # both windows genuinely elapsed
    assert ttft["verdict"] == "breach"     # burning on EVERY window
    assert doc["verdict"] == "breach"


def test_window_units_short_burn_is_warn_not_breach():
    """903 good requests over 10 minutes, then 3 slow ones in the last
    minute: the 60 s window burns hard, the 600 s window stays inside
    budget — verdict 'warn' (fast burn that has not lasted)."""
    m = Metrics()
    s = _engine(m, windows=(60.0, 600.0))
    s.evaluate(now=0.0)                           # baseline A (empty)
    for _ in range(903):
        m.observe("engine_ttft_seconds", 0.05, bucket="128", model="m")
    s.evaluate(now=540.0)                         # baseline B (all good)
    for _ in range(3):
        m.observe("engine_ttft_seconds", 1.8, bucket="128", model="m")
    doc = s.evaluate(now=600.0)
    ttft = _slo(doc, "ttft_p95")
    assert ttft["windows"]["60s"]["burn_rate"] >= 1.0        # 3/3 bad
    assert ttft["windows"]["600s"]["burn_rate"] < 1.0        # 3/906 bad
    assert ttft["verdict"] == "warn"
    assert doc["verdict"] == "warn"


def test_floor_slo_counts_slow_decodes_as_bad():
    m = Metrics()
    s = _engine(m)
    s.evaluate(now=0.0)                    # baseline: both windows realize
    for _ in range(8):
        m.observe("engine_decode_tokens_per_sec", 50.0, model="m")
    for _ in range(2):                     # below the 10 tok/s floor
        m.observe("engine_decode_tokens_per_sec", 2.0, model="m")
    doc = s.evaluate(now=700.0)
    floor = _slo(doc, "decode_floor")
    ev = floor["windows"]["60s"]
    assert ev["burn_rate"] >= 1.0 and ev["bad"] == pytest.approx(2.0)
    assert floor["verdict"] == "breach"


def test_truncated_window_cannot_confirm_breach():
    """A pod restarted into a latency blip must page 'warn', not
    'breach': with process age below the long window both windows hold
    the same evidence, so the long window cannot play its independent
    confirm-the-burn-lasted role."""
    m = Metrics()
    s = _engine(m)                         # windows 60 s / 600 s
    s.evaluate(now=0.0)                    # boot snapshot
    for _ in range(20):
        m.observe("engine_ttft_seconds", 1.8, bucket="128", model="m")  # all bad
    doc = s.evaluate(now=120.0)            # 2 min after boot
    ttft = _slo(doc, "ttft_p95")
    assert ttft["windows"]["60s"]["burn_rate"] >= 1.0
    assert ttft["windows"]["600s"]["burn_rate"] >= 1.0
    assert ttft["windows"]["600s"]["truncated"] is True
    assert "truncated" not in ttft["windows"]["60s"]
    assert ttft["verdict"] == "warn"
    assert doc["verdict"] == "warn"
    # once the burn has genuinely lasted the long window, it breaches
    for _ in range(20):
        m.observe("engine_ttft_seconds", 1.8, bucket="128", model="m")
    doc = s.evaluate(now=650.0)
    assert _slo(doc, "ttft_p95")["verdict"] == "breach"


def test_ratio_slo_5xx_over_total():
    m = Metrics()
    s = _engine(m)
    for _ in range(98):
        m.inc("http_requests_total", route="/response", code="200")
    m.inc("http_requests_total", route="/response", code="503")
    m.inc("http_requests_total", route="/response", code="500")
    doc = s.evaluate(now=7.0)
    err = _slo(doc, "error_rate")
    ev = err["windows"]["60s"]
    # 2/100 over a 0.01 budget = burn 2.0
    assert ev["burn_rate"] == pytest.approx(2.0, rel=1e-3)
    assert ev["bad"] == 2 and ev["total"] == 100


def test_ratio_slo_excludes_self_monitoring_routes():
    """Scrape + probe traffic (guaranteed 200s at a fixed cadence) must
    not dilute the user-facing 5xx ratio: a quiet pod whose only real
    request failed is burning its whole budget, not 1/141 of it."""
    m = Metrics()
    s = _engine(m)
    for _ in range(100):
        m.inc("http_requests_total", route="/metrics", code="200")
        m.inc("http_requests_total", route="/health/ready", code="200")
    m.inc("http_requests_total", route="/debug/slo", code="200")
    m.inc("http_requests_total", route="/response", code="500")
    doc = s.evaluate(now=7.0)
    ev = _slo(doc, "error_rate")["windows"]["60s"]
    assert ev["total"] == 1 and ev["bad"] == 1      # only /response counted
    assert ev["burn_rate"] >= 1.0


def test_worst_bucket_series_wins():
    m = Metrics()
    s = _engine(m)
    for _ in range(10):
        m.observe("engine_ttft_seconds", 0.05, bucket="128", model="m")   # healthy
    for _ in range(10):
        m.observe("engine_ttft_seconds", 1.8, bucket="1024", model="m")   # all bad
    doc = s.evaluate(now=3.0)
    ev = _slo(doc, "ttft_p95")["windows"]["60s"]
    assert ev["worst_series"] == "1024,m"
    assert ev["series"]["128,m"] == 0.0
    assert ev["series"]["1024,m"] == pytest.approx(20.0, rel=1e-3)


def test_no_traffic_is_ok_not_breach():
    m = Metrics()
    doc = _engine(m).evaluate(now=1.0)
    assert doc["verdict"] == "ok"
    for s in doc["slos"]:
        assert s["verdict"] == "ok"


def test_every_cataloged_slo_references_a_real_family():
    from llama_fastapi_k8s_gpu_tpu.obs.catalog import lookup

    for slo in SLOS:
        assert lookup(slo.metric) is not None, slo.name


# ---------------------------------------------------------------------------
# layer 2: gauge export
# ---------------------------------------------------------------------------

def test_export_publishes_burn_rate_gauges():
    m = Metrics()
    s = _engine(m)
    for _ in range(5):
        m.observe("queue_wait_seconds", 5.0)       # way past 0.25 s bound
    s.export(now=2.0)
    text = m.render()
    line = next(ln for ln in text.splitlines()
                if ln.startswith('slo_burn_rate{slo="queue_p95"'
                                 ',window="60s",scope="pod"}'))
    assert float(line.split()[-1]) >= 1.0


# ---------------------------------------------------------------------------
# layer 3: server surface + the storm acceptance criterion
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
async def test_debug_slo_and_compiles_schemas():
    app = create_app(engine=FakeEngine(reply="hey"),
                     tracer=Tracer(sample=1.0, ring=8))
    r, slo, compiles, metrics = await _serve(app, [
        ("post", "/response", {"json": BODY}),
        ("get", "/debug/slo", {}),
        ("get", "/debug/compiles", {}),
        ("get", "/metrics", {}),
    ])
    assert r.status_code == 200
    doc = slo.json()
    assert set(doc) == {"now", "windows", "slos", "recompile", "verdict"}
    assert [s["name"] for s in doc["slos"]] == [s.name for s in SLOS]
    for s in doc["slos"]:
        assert set(s["windows"]) == set(doc["windows"])
        for ev in s["windows"].values():
            assert {"burn_rate", "bad", "total",
                    "worst_series", "window_s"} <= set(ev)
    assert {"budget", "storms", "storms_total",
            "verdict"} <= set(doc["recompile"])
    comp = compiles.json()
    assert set(comp) == {"armed", "budget", "storms_total",
                         "events_dropped", "stamps", "executable_store",
                         "degrades", "programs", "persistent_cache"}
    assert comp["executable_store"] is None      # off where the cache is
    assert set(comp["stamps"]) == {"armed", "misses", "pending", "ring"}
    assert set(comp["persistent_cache"]) == {"dir", "requests", "hits",
                                             "misses"}
    for d in comp["degrades"]:   # the kernel-degrade attribution ledger
        assert {"program", "reason", "count"} <= set(d)
    for p in comp["programs"]:
        assert {"name", "kind", "compiles", "dispatches", "loaded", "built",
                "load_seconds_total", "build_seconds_total",
                "signatures", "signature_list"} <= set(p)
    # the scrape carries the devtime + slo families
    text = metrics.text
    assert "slo_burn_rate{" in text
    assert "xla_recompile_storms_total" in text
    assert "executable_load_failures_total 0" in text


@pytest.mark.anyio
async def test_debug_profile_is_opt_in(monkeypatch):
    monkeypatch.delenv("LFKT_PROFILE_DIR", raising=False)
    app = create_app(engine=FakeEngine(reply="x"))
    r403, rbad, rnan, rinf = await _serve(app, [
        ("get", "/debug/profile", {}),
        ("get", "/debug/profile?seconds=banana", {}),
        ("get", "/debug/profile?seconds=nan", {}),
        ("get", "/debug/profile?seconds=inf", {}),
    ])
    assert r403.status_code == 403
    assert rbad.status_code in (400, 403)     # parse rejects before gating
    # nan/inf parse as floats but slide through min() clamps (nan<x is
    # False) — they must 400, never hold the capture lock for the max
    assert rnan.status_code == 400
    assert rinf.status_code == 400


@pytest.mark.anyio
async def test_debug_profile_captures_when_armed(monkeypatch, tmp_path):
    monkeypatch.setenv("LFKT_PROFILE_DIR", str(tmp_path / "xprof"))
    app = create_app(engine=FakeEngine(reply="x"))
    r, = await _serve(app, [("get", "/debug/profile?seconds=0.05", {})])
    assert r.status_code == 200
    doc = r.json()
    # "seconds" is the clamped capture window (deterministic); "wall_s"
    # additionally counts profiler start/stop, which serializes every
    # retained event and is unbounded on a long-lived process
    assert doc["ok"] is True and doc["seconds"] == 0.05
    assert doc["wall_s"] > 0


@pytest.mark.anyio
async def test_storm_visible_in_metrics_slo_and_inflight_trace():
    """ISSUE 7 acceptance: a recompile storm while a request is in flight
    shows up in /metrics, /debug/slo, and as events on the request's own
    trace — all three surfaces, one storm."""
    tracer = Tracer(sample=1.0, ring=8)
    app = create_app(engine=FakeEngine(reply="ok", delay=0.6),
                     tracer=tracer)
    old_budget = DEVTIME.budget
    DEVTIME.reset()
    DEVTIME.configure(budget=1)
    transport = httpx.ASGITransport(app=app)
    try:
        async with transport:
            await app.router.startup()
            async with httpx.AsyncClient(transport=transport,
                                         base_url="http://test") as client:
                task = asyncio.create_task(client.post("/response",
                                                       json=BODY))
                await asyncio.sleep(0.15)          # request now in flight
                # the first loaded from the executable store, the second
                # built for it: compile events both (PR 55)
                DEVTIME.record_compile("stormy", "f32[1]", 0.2, "loaded")
                DEVTIME.record_compile("stormy", "f32[2]", 0.2, "built")
                metrics = (await client.get("/metrics")).text
                slo = (await client.get("/debug/slo")).json()
                r = await task
            await app.router.shutdown()
        assert r.status_code == 200
        assert "xla_recompile_storms_total 1" in metrics
        assert 'xla_compiles_total{program="stormy"} 2' in metrics
        assert 'executables_loaded_total{program="stormy"} 1' in metrics
        assert 'executables_built_total{program="stormy"} 1' in metrics
        assert 'executable_load_seconds_total{program="stormy"} 0.2' \
            in metrics
        assert 'xla_compile_seconds_count{program="stormy"} 2' in metrics
        assert slo["recompile"]["verdict"] == "storm"
        assert slo["recompile"]["storms"][0]["program"] == "stormy"
        assert slo["verdict"] in ("warn", "breach")
        tr = tracer.get(r.headers["x-request-id"])
        assert tr is not None
        events = [e for e in tr.root.events if e["name"] == "recompile_storm"]
        assert events and events[0]["program"] == "stormy"
    finally:
        DEVTIME.reset()
        DEVTIME.configure(budget=old_budget)
