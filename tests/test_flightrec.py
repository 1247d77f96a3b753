"""lfkt-mem: the incident flight recorder (ISSUE 10).

Layers:

1. **Recorder unit** — atomic schema-valid bundles, the bounded on-disk
   ring, per-kind debounce, cross-process sequence continuation, the
   log-tail ring, schema-drift detection.
2. **Trigger points** — watchdog trip / DEAD escalation
   (engine/watchdog.py), device OOM via the heartbeat
   (utils/health.py), SLO breach (obs/slo.py).
3. **Tools** — tools/incident_report.py rendering + the ``--validate``
   schema gate wired into tools/ci_gate.py.
4. **Acceptance drill** — an injected decode fault on a real
   ContinuousEngine trips the watchdog and produces EXACTLY ONE bundle
   carrying the tripping request's trace, the memory ledger and the
   health transition — readable back through ``/debug/incidents/{id}``
   after the engine recovered.
5. **Disarmed cost** — no ``LFKT_INCIDENT_DIR`` = a single attribute
   read; poisoned-recorder pin.
"""

from __future__ import annotations

import importlib.util
import json
import logging
import os
import time

import httpx
import pytest

from llama_fastapi_k8s_gpu_tpu.engine import ContinuousEngine, FakeEngine
from llama_fastapi_k8s_gpu_tpu.engine.watchdog import Watchdog
from llama_fastapi_k8s_gpu_tpu.obs.devtime import DevtimeRegistry
from llama_fastapi_k8s_gpu_tpu.obs.flightrec import (
    KINDS,
    SCHEMA,
    FlightRecorder,
    validate_bundle,
)
from llama_fastapi_k8s_gpu_tpu.obs.slo import SLOEngine
from llama_fastapi_k8s_gpu_tpu.obs.trace import Tracer
from llama_fastapi_k8s_gpu_tpu.server.app import create_app
from llama_fastapi_k8s_gpu_tpu.testing import write_tiny_llama_gguf
from llama_fastapi_k8s_gpu_tpu.utils.faults import FAULTS, FaultError, SimulatedOOM
from llama_fastapi_k8s_gpu_tpu.utils.health import (
    DEGRADED,
    READY,
    Heartbeat,
    HealthMonitor,
)
from llama_fastapi_k8s_gpu_tpu.utils.metrics import Metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FLIGHTREC_PATH = "llama_fastapi_k8s_gpu_tpu.obs.flightrec.FLIGHTREC"
MSGS = [{"role": "user", "content": "Say something."}]


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _wait(pred, timeout=30.0, what=""):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.02)
    raise AssertionError(f"timed out waiting for {what}")


@pytest.fixture()
def recorder(tmp_path, monkeypatch):
    """A fresh ARMED process recorder on a tmp ring dir, installed as the
    module global (trigger points resolve it at call time); the log-ring
    handler is detached on teardown."""
    rec = FlightRecorder(directory=str(tmp_path / "ring"), ring=8,
                         debounce_s=0.0, log_lines=50)
    monkeypatch.setattr(FLIGHTREC_PATH, rec)
    yield rec
    rec.configure(directory="")          # removes the root log handler


@pytest.fixture(autouse=True)
def _disarm_faults():
    FAULTS.disarm()
    yield
    FAULTS.disarm()


# ---------------------------------------------------------------------------
# layer 1: recorder unit
# ---------------------------------------------------------------------------

def test_record_writes_schema_valid_atomic_bundle(recorder):
    rid = recorder.record("watchdog_trip", "drill reason",
                          extra={"k": "v"})
    assert rid == "inc-000001-watchdog_trip"
    files = os.listdir(recorder._dir)
    assert files == [rid + ".json"]          # no .tmp left behind
    doc = recorder.get(rid)
    assert validate_bundle(doc) == []
    assert doc["kind"] == "watchdog_trip"
    assert doc["reason"] == "drill reason"
    assert doc["extra"] == {"k": "v"}
    assert doc["memory"]["schema"] == 1      # the live ledger rides along
    assert isinstance(doc["traces"], list)
    assert recorder.recorded_total == 1
    # summaries list newest first
    recorder.record("slo_breach", "second")
    assert [s["id"] for s in recorder.list()] == [
        "inc-000002-slo_breach", rid]
    # id grammar enforced: no path escape through get()
    assert recorder.get("../../etc/passwd") is None
    assert recorder.get("inc-zzz-nope") is None


def test_ring_prunes_oldest_and_seq_survives_restart(recorder):
    recorder.configure(ring=2)
    for i, kind in enumerate(("watchdog_trip", "slo_breach",
                              "resource_exhausted")):
        assert recorder.record(kind, f"r{i}") is not None
    names = sorted(os.listdir(recorder._dir))
    assert len(names) == 2                       # oldest pruned
    assert names[0].startswith("inc-000002-")
    # a NEW recorder on the same dir (post-restart process) continues the
    # sequence instead of overwriting the previous crash's evidence
    rec2 = FlightRecorder(directory=recorder._dir, ring=8, debounce_s=0.0,
                          log_lines=10)
    try:
        assert rec2.record("dead_escalation", "after restart") \
            == "inc-000004-dead_escalation"
    finally:
        rec2.configure(directory="")


def test_debounce_per_kind(recorder):
    recorder.configure(debounce_s=60.0)
    assert recorder.record("watchdog_trip", "first") is not None
    assert recorder.record("watchdog_trip", "burst repeat") is None
    assert recorder.debounced_total == 1
    # a DIFFERENT kind is not debounced by the first
    assert recorder.record("resource_exhausted", "oom") is not None


def test_failed_write_rolls_back_debounce(recorder, monkeypatch):
    """A write failure (disk full during the very incident being
    recorded) must not burn the debounce window: the next trigger of the
    same kind retries instead of being silently suppressed."""
    recorder.configure(debounce_s=600.0)
    real_write = recorder._write
    calls = {"n": 0}

    def flaky(incident_id, bundle):
        calls["n"] += 1
        if calls["n"] == 1:
            raise OSError("disk full")
        real_write(incident_id, bundle)

    monkeypatch.setattr(recorder, "_write", flaky)
    assert recorder.record("watchdog_trip", "first attempt") is None
    assert recorder.record("watchdog_trip", "retry") is not None
    assert recorder.recorded_total == 1


def test_failed_write_leaves_no_tmp_file(recorder, monkeypatch):
    """A write that fails at the atomic rename removes its temp file:
    disk-full retries mint new ids, and leaked .tmp files would compound
    the very disk pressure that failed the write."""
    def no_replace(src, dst):
        raise OSError("disk full")

    monkeypatch.setattr(os, "replace", no_replace)
    assert recorder.record("watchdog_trip", "r") is None
    monkeypatch.undo()
    assert [n for n in os.listdir(recorder._dir)
            if n.startswith(".tmp-")] == []
    # ...a stray tmp from a previous crash is swept at the first WRITE of
    # an arming — never by merely (re)arming, which is what a read-only
    # tool (incident_report / ci_gate) does by importing the module with
    # LFKT_INCIDENT_DIR set: a reader must not delete a live recorder's
    # in-progress temp file
    stray = os.path.join(recorder._dir, ".tmp-inc-000009-slo_breach.json")
    open(stray, "w").close()
    recorder.configure(directory=recorder._dir)
    recorder.list()
    assert os.path.exists(stray)
    assert recorder.record("slo_breach", "sweep trigger") is not None
    assert not os.path.exists(stray)


def test_install_never_pins_unweakrefable_engine(recorder):
    """install()'s contract is WEAK references: an engine that cannot be
    weakly referenced is dropped (bundles go without scheduler stats),
    never pinned for the process lifetime by the global recorder."""
    recorder.install(engine=(1, 2, 3))     # tuples are un-weakref-able
    assert recorder._engine_ref is None
    doc = recorder.get(recorder.record("watchdog_trip", "r"))
    assert doc["scheduler"] is None


def test_log_tail_rides_the_bundle(recorder):
    logging.getLogger("lfkt.test").warning("breadcrumb %d", 42)
    doc = recorder.get(recorder.record("slo_breach", "r"))
    assert any("breadcrumb 42" in line["message"]
               for line in doc["log_tail"])


def test_validate_bundle_catches_drift(recorder):
    doc = recorder.get(recorder.record("watchdog_trip", "r"))
    assert validate_bundle(doc) == []
    assert any("drift" in v for v in validate_bundle(
        {**doc, "schema": SCHEMA + 1}))
    assert any("kind" in v for v in validate_bundle(
        {**doc, "kind": "novel_kind"}))
    assert any("'traces'" in v for v in validate_bundle(
        {k: v for k, v in doc.items() if k != "traces"}))
    assert validate_bundle([1, 2]) == ["bundle is not a JSON object"]


# ---------------------------------------------------------------------------
# layer 2: trigger points
# ---------------------------------------------------------------------------

def test_watchdog_trip_and_dead_escalation_record(recorder):
    eng = FakeEngine()
    health = HealthMonitor()
    health.transition(READY, "up")
    wd = Watchdog(eng, health, Metrics(), poll_seconds=10.0,
                  backoff_seconds=0.01, max_recoveries=1)
    wd.handle_trip("stalled_decode: drill")
    docs = [recorder.get(s["id"]) for s in recorder.list()]
    trips = [d for d in docs if d["kind"] == "watchdog_trip"]
    assert len(trips) == 1
    assert "stalled_decode" in trips[0]["reason"]
    assert trips[0]["extra"]["watchdog"]["trips"] == 1
    # health rides the bundle's top-level field via the refs the watchdog
    # installed at construction — captured mid-trip, i.e. DEGRADED
    assert trips[0]["health"]["state"] == DEGRADED
    # exhaust the budget: the DEAD escalation writes its own bundle kind
    wd.handle_trip("stalled_decode: again")
    docs = [recorder.get(s["id"]) for s in recorder.list()]
    assert [d["kind"] for d in docs].count("dead_escalation") == 1


def test_heartbeat_oom_signature_records(recorder):
    hb = Heartbeat()
    hb.record_error(ValueError("ordinary bug"))
    assert recorder.recorded_total == 0          # only the OOM signature
    hb.record_error(SimulatedOOM("RESOURCE_EXHAUSTED: simulated OOM"))
    docs = [recorder.get(s["id"]) for s in recorder.list()]
    assert [d["kind"] for d in docs] == ["resource_exhausted"]
    assert "RESOURCE_EXHAUSTED" in docs[0]["reason"]


def test_slo_breach_records_with_verdict(recorder):
    m = Metrics()
    s = SLOEngine(m, windows=[60.0, 600.0],
                  thresholds={"ttft_p95": 1.0, "decode_floor": 10.0,
                              "error_rate": 0.01, "queue_p95": 0.5},
                  devtime=DevtimeRegistry(armed=True, budget=32))
    s.evaluate(now=0.0)                          # realize both baselines
    for _ in range(8):
        m.observe("engine_decode_tokens_per_sec", 50.0, model="m")
    for _ in range(2):                           # under the 10 tok/s floor
        m.observe("engine_decode_tokens_per_sec", 2.0, model="m")
    doc = s.evaluate(now=700.0)
    assert doc["verdict"] == "breach"
    # the capture+write runs on a short worker thread (the evaluate call
    # sites are async handlers): wait for the bundle, not for luck
    _wait(lambda: recorder.recorded_total == 1, timeout=10,
          what="breach bundle write")
    docs = [recorder.get(x["id"]) for x in recorder.list()]
    assert [d["kind"] for d in docs] == ["slo_breach"]
    assert "decode_floor" in docs[0]["reason"]
    assert docs[0]["extra"]["slo"]["verdict"] == "breach"
    # one bundle per breach EPISODE: the persisting breach re-evaluated
    # on later scrapes must not flood the bounded ring (recorder debounce
    # is 0 here — the edge detector alone holds the line)
    for t in (710.0, 720.0, 730.0):
        assert s.evaluate(now=t)["verdict"] == "breach"
        time.sleep(0.05)
    assert recorder.recorded_total == 1
    # recovery re-arms the detector: a NEW episode records a new bundle
    for _ in range(400):
        m.observe("engine_decode_tokens_per_sec", 50.0, model="m")
    assert s.evaluate(now=1500.0)["verdict"] != "breach"
    for _ in range(3):
        m.observe("engine_decode_tokens_per_sec", 2.0, model="m")
    assert s.evaluate(now=2200.0)["verdict"] == "breach"
    _wait(lambda: recorder.recorded_total == 2, timeout=10,
          what="second-episode bundle write")


# ---------------------------------------------------------------------------
# layer 3: tools — incident_report + the ci_gate schema step
# ---------------------------------------------------------------------------

def test_incident_report_validate_and_render(recorder, capsys):
    rid = recorder.record("watchdog_trip", "drill")
    tool = _load_tool("incident_report")
    assert tool.SCHEMA == SCHEMA                 # tool pins the package
    assert tool.validate(recorder._dir) == 0
    # plant drift: the gate must fail loudly
    bad = recorder.get(rid)
    bad["schema"] = 99
    with open(os.path.join(recorder._dir, rid + ".json"), "w") as f:
        json.dump(bad, f)
    assert tool.validate(recorder._dir) == 1
    out = capsys.readouterr().out
    assert "drift" in out and "FAIL" in out
    # no dir configured = trivially OK (the common CI case)
    assert tool.validate("") == 0
    assert tool.validate(str(recorder._dir) + "-nonexistent") == 0
    # renderers run on a real bundle
    good = {**bad, "schema": SCHEMA}
    text = tool.render_bundle(good)
    assert "watchdog_trip" in text and "memory ledger" in text
    assert "drill" in tool.render_listing(recorder._dir)


def test_ci_gate_includes_incident_schema_check():
    gate = _load_tool("ci_gate")
    assert "incident-schema" in [name for name, _ in gate.CHECKS]


# ---------------------------------------------------------------------------
# layer 4: the acceptance drill (ISSUE 10)
# ---------------------------------------------------------------------------

@pytest.mark.anyio
async def test_fault_drill_one_bundle_readable_after_recovery(
        recorder, tmp_path):
    """Injected decode fault → watchdog trip → EXACTLY ONE bundle with
    the tripping request's trace, the memory ledger and the health
    transition — readable through /debug/incidents/{id} after the
    engine recovered in place."""
    path = str(tmp_path / "tiny-drill.gguf")
    write_tiny_llama_gguf(path)
    eng = ContinuousEngine(path, batch_size=2, n_ctx=128,
                           decode_chunk=4, max_gen_tokens=16,
                           prefill_buckets=(32, 64, 128))
    health = HealthMonitor()
    health.transition(READY, "up")
    wd = Watchdog(eng, health, Metrics(), stall_seconds=30,
                  poll_seconds=0.05, backoff_seconds=0.05,
                  max_recoveries=3)
    tracer = Tracer(sample=1.0, ring=8)
    try:
        # the tripping request rides a real trace, still in flight when
        # the scheduler loop dies
        FAULTS.arm("decode_step:error:times=1")
        tr = tracer.start("request")
        tr.note(route="/response")
        fut = eng.submit(MSGS, temperature=0.0, max_tokens=8, trace=tr)
        with pytest.raises(Exception):
            fut.result(timeout=60)
        assert isinstance(eng.failure(), FaultError)

        wd.start()
        _wait(lambda: wd.recoveries >= 1 and health.state == READY,
              what="trip + in-process recovery")

        # exactly one bundle: the trip's (no DEAD, no OOM signature)
        summaries = recorder.list()
        assert len(summaries) == 1
        doc = recorder.get(summaries[0]["id"])
        assert validate_bundle(doc) == []
        assert doc["kind"] == "watchdog_trip"
        assert "scheduler_died" in doc["reason"]
        # the tripping request's trace rides the bundle
        assert tr.trace_id in [t.get("trace_id") for t in doc["traces"]]
        # the memory ledger at capture time
        assert doc["memory"]["armed"] is True
        assert any(r["component"] == "weights"
                   for r in doc["memory"]["components"])
        # the health transition that shed the traffic
        trail = [t["to"] for t in doc["health"]["transitions"]]
        assert DEGRADED in trail
        # and the live scheduler stats via the same installed refs
        assert "lanes_live" in doc["scheduler"]
        tracer.finish(tr)

        # same engine object, recovered: serving again...
        out = eng.create_chat_completion(MSGS, temperature=0.0,
                                         max_tokens=4)
        assert out["usage"]["completion_tokens"] >= 1

        # ...and the bundle reads back through the server surface
        app = create_app(engine=eng)
        transport = httpx.ASGITransport(app=app)
        async with transport:
            await app.router.startup()
            async with httpx.AsyncClient(transport=transport,
                                         base_url="http://t") as client:
                listing = (await client.get("/debug/incidents")).json()
                assert listing["armed"] is True
                assert [s["id"] for s in listing["incidents"]] == \
                    [doc["id"]]
                one = await client.get(f"/debug/incidents/{doc['id']}")
                assert one.status_code == 200
                got = one.json()
                assert got["kind"] == "watchdog_trip"
                assert got["id"] == doc["id"]
                missing = await client.get(
                    "/debug/incidents/inc-999999-watchdog_trip")
                assert missing.status_code == 404
            await app.router.shutdown()
    finally:
        FAULTS.disarm()
        wd.stop()
        eng.shutdown()


# ---------------------------------------------------------------------------
# layer 5: disarmed cost (poisoned-recorder pin)
# ---------------------------------------------------------------------------

def test_disarmed_recorder_is_poison_proof(monkeypatch):
    """No LFKT_INCIDENT_DIR: record() keys off one attribute read — a
    poisoned recorder must never capture, list files, or touch disk,
    even when every trigger point fires."""
    rec = FlightRecorder(directory="", ring=8, debounce_s=0.0,
                         log_lines=10)
    assert rec.armed is False

    def boom(*a, **kw):
        raise AssertionError("disarmed flight recorder was touched")

    monkeypatch.setattr(rec, "_capture", boom)
    monkeypatch.setattr(rec, "_write", boom)
    monkeypatch.setattr(rec, "_list_files", boom)
    monkeypatch.setattr(FLIGHTREC_PATH, rec)
    assert rec.record("watchdog_trip", "r") is None
    # the heartbeat OOM hook fires through the same guard
    hb = Heartbeat()
    hb.record_error(SimulatedOOM("RESOURCE_EXHAUSTED: simulated"))
    assert rec.recorded_total == 0
    # no log handler was ever installed while disarmed
    assert rec._log_handler is None


def test_kinds_are_closed_set(recorder):
    assert recorder.record("made_up_kind", "r") is None
    assert set(KINDS) == {"watchdog_trip", "dead_escalation",
                          "resource_exhausted", "slo_breach",
                          "disagg_peer_dead", "fleet_peer_ejected"}
