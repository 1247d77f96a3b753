"""Fault-injection-driven resilience suite (tier-1, CPU, deterministic).

Covers the layer ISSUE 2 added over the reference's let-the-pod-die story:
the health state machine and its probe split, the env-armed fault injector,
the engine watchdog (stall / burst / scheduler-death detection, bounded
recovery, DEAD escalation), deadline/abort propagation into every engine's
decode loop, and the flagship in-process lifecycle on a real
ContinuousEngine: fault → trip → DEGRADED (readiness 503, liveness 200) →
recovery → READY, no process restart.
"""

from __future__ import annotations

import os
import subprocess
import sys
import time

import pytest

from llama_fastapi_k8s_gpu_tpu.engine import ContinuousEngine, Engine
from llama_fastapi_k8s_gpu_tpu.engine.fake import FakeEngine
from llama_fastapi_k8s_gpu_tpu.engine.watchdog import Watchdog
from llama_fastapi_k8s_gpu_tpu.testing import write_tiny_llama_gguf
from llama_fastapi_k8s_gpu_tpu.utils.faults import (
    FAULTS,
    FaultError,
    FaultInjector,
    SimulatedOOM,
)
from llama_fastapi_k8s_gpu_tpu.utils.health import (
    DEAD,
    DEGRADED,
    DRAINING,
    READY,
    STARTING,
    DeadlineExceeded,
    EngineUnavailable,
    Heartbeat,
    HealthMonitor,
)
from llama_fastapi_k8s_gpu_tpu.utils.metrics import Metrics

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MSGS = [{"role": "user", "content": "Say something."}]


@pytest.fixture(autouse=True)
def _disarm_faults():
    """No fault armed leaks across tests."""
    FAULTS.disarm()
    yield
    FAULTS.disarm()


# ---------------------------------------------------------------------------
# health state machine
# ---------------------------------------------------------------------------

def test_health_state_machine_lifecycle():
    h = HealthMonitor()
    assert h.state == STARTING
    assert not h.ready() and h.alive()          # starting: not ready, alive
    assert h.transition(READY, "engine loaded")
    assert h.ready() and h.alive()
    assert h.transition(DEGRADED, "watchdog trip")
    assert not h.ready() and h.alive()          # degraded: shed, don't kill
    assert h.transition(READY, "recovered")
    assert h.ready()
    snap = h.snapshot()
    assert snap["state"] == READY
    assert snap["reason"] == "recovered"
    assert [t["to"] for t in snap["transitions"]] == [READY, DEGRADED, READY]


def test_health_dead_is_terminal():
    h = HealthMonitor()
    h.transition(READY, "up")
    h.transition(DEAD, "budget exhausted")
    assert not h.alive() and not h.ready()
    assert not h.transition(READY, "necromancy")       # refused
    assert h.state == DEAD
    assert h.transition(DEAD, "still dead")            # self-transition ok


def test_health_draining_only_yields_to_dead():
    h = HealthMonitor()
    h.transition(READY, "up")
    h.transition(DRAINING, "sigterm")
    assert not h.ready() and h.alive()
    assert not h.transition(READY, "no: draining pod must not re-advertise")
    assert h.transition(DEAD, "drain escalated")
    assert not h.alive()


def test_health_rejects_unknown_state():
    with pytest.raises(ValueError):
        HealthMonitor().transition("ZOMBIE")


# ---------------------------------------------------------------------------
# fault injector
# ---------------------------------------------------------------------------

def test_faults_inert_by_default():
    inj = FaultInjector()
    for _ in range(100):
        inj.fire("decode_step")     # never raises, never sleeps
    assert not inj.armed()


def test_faults_after_times_script():
    inj = FaultInjector()
    inj.arm("decode_step:error:after=2:times=1")
    inj.fire("decode_step")         # hit 1: pass-through
    inj.fire("decode_step")         # hit 2: pass-through
    with pytest.raises(FaultError):
        inj.fire("decode_step")     # hit 3: fires
    inj.fire("decode_step")         # hit 4: budget spent, inert again
    assert inj.stats()["decode_step"]["fired"] == 1


def test_faults_oom_and_slow_modes():
    inj = FaultInjector()
    inj.arm("load:oom")
    with pytest.raises(SimulatedOOM, match="RESOURCE_EXHAUSTED"):
        inj.fire("load")
    inj.arm("prefill:slow:delay=0.1:times=1")
    t0 = time.monotonic()
    inj.fire("prefill")
    assert time.monotonic() - t0 >= 0.1


def test_faults_reject_bad_specs():
    inj = FaultInjector()
    with pytest.raises(ValueError):
        inj.arm("nonsense_point:error")
    with pytest.raises(ValueError):
        inj.arm("decode_step:explode")
    with pytest.raises(ValueError):
        inj.arm("decode_step:error:bogus=1")


# ---------------------------------------------------------------------------
# watchdog against a minimal engine contract
# ---------------------------------------------------------------------------

class _ContractEngine:
    """Smallest thing the watchdog can supervise."""

    def __init__(self, recover_ok=True):
        self.heartbeat = Heartbeat()
        self.recover_ok = recover_ok
        self.recoveries = 0
        self.failed: list = []

    def recover(self):
        self.recoveries += 1
        return self.recover_ok

    def fail_inflight(self, exc):
        self.failed.append(exc)


def _wait(pred, timeout=5.0, what=""):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return
        time.sleep(0.01)
    raise AssertionError(f"timed out waiting for {what}")


def test_watchdog_trips_on_stall_and_recovers():
    eng = _ContractEngine()
    health = HealthMonitor()
    health.transition(READY, "up")
    m = Metrics()
    wd = Watchdog(eng, health, m, stall_seconds=0.05, poll_seconds=0.02,
                  backoff_seconds=0.01, max_recoveries=5).start()
    try:
        eng.heartbeat.enter()       # in-flight work...
        time.sleep(0.06)            # ...with no progress: a stall
        _wait(lambda: wd.recoveries >= 1 and health.state == READY,
              what="stall trip + recovery")
        assert eng.recoveries >= 1
        assert eng.failed and isinstance(eng.failed[0], EngineUnavailable)
        assert "stalled_decode" in wd.last_trip_reason
        trail = [t["to"] for t in health.snapshot()["transitions"]]
        assert DEGRADED in trail and trail[-1] == READY
        assert "watchdog_trips_total" in m.render()
        assert "watchdog_recoveries_total" in m.render()
    finally:
        wd.stop()


def test_watchdog_trips_on_error_burst():
    eng = _ContractEngine()
    health = HealthMonitor()
    health.transition(READY, "up")
    wd = Watchdog(eng, health, Metrics(), poll_seconds=0.02,
                  error_burst=3, error_window=5.0, backoff_seconds=0.01)
    try:
        for _ in range(3):
            eng.heartbeat.record_error(RuntimeError("step blew up"))
        reason = wd.check()
        assert reason is not None and "exception_burst" in reason
        wd.handle_trip(reason)
        assert health.state == READY        # recovered (recover_ok fake)
        assert eng.recoveries == 1
    finally:
        wd.stop()


def test_watchdog_burst_on_busy_engine_recovers_in_place():
    """A transient exception burst on an engine that is still serving
    (recover() refuses: loop alive / lock held) must NOT walk to DEAD —
    the trip consumes the burst evidence and, with no remaining fault
    signature, the watchdog re-readies in place (code-review finding:
    the old behavior re-tripped on the same stale errors every poll and
    deterministically killed a healthy pod)."""
    eng = _ContractEngine(recover_ok=False)   # "busy": refuses re-init
    health = HealthMonitor()
    health.transition(READY, "up")
    wd = Watchdog(eng, health, Metrics(), poll_seconds=0.02,
                  error_burst=3, error_window=30.0, backoff_seconds=0.01,
                  max_recoveries=2)
    try:
        for _ in range(3):
            eng.heartbeat.record_error(RuntimeError("transient device error"))
        reason = wd.check()
        assert reason is not None and "exception_burst" in reason
        wd.handle_trip(reason)
        assert health.state == READY          # re-readied in place, not DEAD
        assert wd.recoveries == 1
        assert wd.check() is None             # evidence consumed: no re-trip
    finally:
        wd.stop()


def test_watchdog_escalates_to_dead_when_recovery_fails():
    eng = _ContractEngine(recover_ok=False)
    health = HealthMonitor()
    health.transition(READY, "up")
    wd = Watchdog(eng, health, Metrics(), stall_seconds=0.03,
                  poll_seconds=0.02, backoff_seconds=0.01,
                  max_recoveries=2).start()
    try:
        eng.heartbeat.enter()       # permanent wedge, recovery always fails
        _wait(lambda: health.state == DEAD, what="escalation to DEAD")
        assert not health.alive()
        assert wd.trips == 3        # 2 failed recoveries + the fatal trip
        assert "max_recoveries_exceeded" in health.snapshot()["reason"]
    finally:
        wd.stop()


def test_watchdog_forgets_trips_after_healthy_window():
    """The DEAD escalation budget is per incident, not per process
    lifetime: after trip_forget_seconds of trip-free READY serving the
    window resets, so isolated transient incidents days apart can never
    accumulate into a needless pod restart."""
    eng = _ContractEngine()
    health = HealthMonitor()
    health.transition(READY, "up")
    wd = Watchdog(eng, health, Metrics(), stall_seconds=0.05,
                  poll_seconds=0.02, backoff_seconds=0.01,
                  max_recoveries=1, trip_forget_seconds=0.2).start()
    try:
        for incident in range(3):     # each would escalate if accumulated
            eng.heartbeat.enter()
            time.sleep(0.06)          # stall → trip → recover (fake resets)
            _wait(lambda: health.state == READY and eng.heartbeat.busy_count() == 0,
                  what=f"recovery from incident {incident}")
            _wait(lambda: wd.trips_window == 0, timeout=5,
                  what=f"trip window forgotten after incident {incident}")
        assert health.state == READY
        assert wd.trips == 3 and wd.recoveries == 3
    finally:
        wd.stop()


def test_failed_mid_recovery_does_not_go_zombie_ready(tmp_path):
    """If the device re-init inside ContinuousEngine.recover() fails (the
    likely condition recovery runs under — OOM), the fault signature must
    survive: the engine keeps refusing submissions and the watchdog must
    NOT declare an in-place recovery over a scheduler-less zombie."""
    path = str(tmp_path / "tiny-zombie.gguf")
    write_tiny_llama_gguf(path)
    eng = ContinuousEngine(path, batch_size=2, n_ctx=64,
                           decode_chunk=2, max_gen_tokens=8,
                           prefill_buckets=(32, 64))
    health = HealthMonitor()
    health.transition(READY, "up")
    wd = Watchdog(eng, health, Metrics(), poll_seconds=0.05,
                  backoff_seconds=0.01, max_recoveries=10)
    try:
        FAULTS.arm("decode_step:error:times=1")
        fut = eng.submit(MSGS, temperature=0.0, max_tokens=8)
        with pytest.raises(Exception):
            fut.result(timeout=60)
        assert eng.failure() is not None

        def broken_recover_locked():
            raise RuntimeError("RESOURCE_EXHAUSTED: re-init OOM")

        eng._recover_locked = broken_recover_locked
        reason = wd.check()
        assert reason is not None
        wd.handle_trip(reason)
        # recovery failed mid re-init: fault signature intact, still shed
        assert eng.failure() is not None
        assert health.state == DEGRADED
        with pytest.raises(EngineUnavailable):
            eng.submit(MSGS, max_tokens=4)
    finally:
        FAULTS.disarm()
        wd.stop()
        eng.shutdown()


# ---------------------------------------------------------------------------
# deadline / abort propagation per engine
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def serial_engine(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("model") / "tiny-res.gguf")
    write_tiny_llama_gguf(path)
    return Engine(path, n_ctx=256, decode_chunk=4, max_gen_tokens=128,
                  prefill_buckets=(32, 64, 128, 256))


def test_serial_engine_deadline_stops_decode(serial_engine):
    out = serial_engine.create_chat_completion(
        MSGS, temperature=0.0, max_tokens=100, deadline=time.time())
    assert out["choices"][0]["finish_reason"] == "deadline"
    # at most the prefill token + the already-dispatched first chunk
    assert out["usage"]["completion_tokens"] <= 1 + serial_engine.decode_chunk


def test_serial_engine_abort_stops_within_one_chunk(serial_engine):
    calls = {"decode": 0, "abort": 0}
    orig = serial_engine._decode_chunk_call

    def counting(*a, **kw):
        calls["decode"] += 1
        return orig(*a, **kw)

    def abort():
        calls["abort"] += 1
        return calls["abort"] > 2      # let ~2 chunks run, then disconnect

    serial_engine._decode_chunk_call = counting
    try:
        out = serial_engine.create_chat_completion(
            MSGS, temperature=0.0, max_tokens=100, abort=abort)
    finally:
        serial_engine._decode_chunk_call = orig
    assert out["choices"][0]["finish_reason"] == "deadline"
    assert out["usage"]["completion_tokens"] < 100
    # the loop checks abort before each dispatch: after it fires, no
    # further chunk is dispatched
    assert calls["decode"] <= 4, calls


def test_serial_engine_no_deadline_is_unchanged(serial_engine):
    """Default path (no deadline/abort) must be byte-identical."""
    a = serial_engine.create_chat_completion(MSGS, temperature=0.0,
                                             max_tokens=12, seed=7)
    b = serial_engine.create_chat_completion(MSGS, temperature=0.0,
                                             max_tokens=12, seed=7,
                                             deadline=None, abort=None)
    assert a["choices"][0]["message"] == b["choices"][0]["message"]
    assert a["choices"][0]["finish_reason"] == b["choices"][0]["finish_reason"]


@pytest.fixture(scope="module")
def cont_engine(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("model") / "tiny-cont-res.gguf")
    write_tiny_llama_gguf(path)
    eng = ContinuousEngine(path, batch_size=2, n_ctx=128,
                           decode_chunk=4, max_gen_tokens=32,
                           prefill_buckets=(32, 64, 128))
    yield eng
    eng.shutdown()


def test_continuous_deadline_expired_in_queue(cont_engine):
    fut = cont_engine.submit(MSGS, temperature=0.0, max_tokens=8,
                             deadline=time.time() - 1)
    with pytest.raises(DeadlineExceeded):
        fut.result(timeout=60)
    # the engine keeps serving afterwards (no lane leaked)
    ok = cont_engine.create_chat_completion(MSGS, temperature=0.0,
                                            max_tokens=4)
    assert ok["usage"]["completion_tokens"] >= 1


def test_continuous_deadline_mid_generation_frees_lane(cont_engine):
    t0 = time.time()
    fut = cont_engine.submit(MSGS, temperature=0.0, max_tokens=32,
                             deadline=time.time() + 0.2)
    try:
        out = fut.result(timeout=60)
        # fast box: finished inside the deadline — a legal outcome
        assert out["object"] == "chat.completion"
    except DeadlineExceeded:
        # the deadline path must resolve promptly, not at token budget
        assert time.time() - t0 < 30
    _wait(lambda: cont_engine.scheduler_stats()["lanes_live"] == 0,
          timeout=30, what="lane freed after deadline")
    ok = cont_engine.create_chat_completion(MSGS, temperature=0.0,
                                            max_tokens=4)
    assert ok["usage"]["completion_tokens"] >= 1


# ---------------------------------------------------------------------------
# the flagship: fault → trip → DEGRADED → bounded recovery → READY, one
# process, a real scheduler engine
# ---------------------------------------------------------------------------

def test_continuous_watchdog_full_lifecycle(tmp_path):
    path = str(tmp_path / "tiny-lifecycle.gguf")
    write_tiny_llama_gguf(path)
    eng = ContinuousEngine(path, batch_size=2, n_ctx=128,
                           decode_chunk=4, max_gen_tokens=16,
                           prefill_buckets=(32, 64, 128))
    health = HealthMonitor()
    health.transition(READY, "up")
    m = Metrics()
    wd = Watchdog(eng, health, m, stall_seconds=30, poll_seconds=0.05,
                  backoff_seconds=0.05, max_recoveries=3)
    try:
        # healthy baseline
        ok = eng.create_chat_completion(MSGS, temperature=0.0, max_tokens=4)
        assert ok["usage"]["completion_tokens"] >= 1

        # one injected decode-step fault kills the scheduler loop; the
        # in-flight future must fail loudly, not hang
        FAULTS.arm("decode_step:error:times=1")
        fut = eng.submit(MSGS, temperature=0.0, max_tokens=8)
        with pytest.raises(Exception):
            fut.result(timeout=60)
        assert isinstance(eng.failure(), FaultError)

        # submissions during the outage get the 503-mapped error class
        with pytest.raises(EngineUnavailable):
            eng.submit(MSGS, max_tokens=4)

        # the watchdog detects the death, degrades, recovers, re-readies.
        # (Wait on recoveries, not trips: trips increments before the
        # DEGRADED transition, so "trips>=1 and READY" can race with the
        # still-initial READY state; recoveries increments only after the
        # recovered-READY transition is next.)
        wd.start()
        _wait(lambda: wd.recoveries >= 1 and health.state == READY,
              timeout=30, what="trip + in-process recovery")
        trail = [t["to"] for t in health.snapshot()["transitions"]]
        assert DEGRADED in trail and trail[-1] == READY
        assert "scheduler_died" in wd.last_trip_reason
        rendered = m.render()
        assert "watchdog_trips_total 1" in rendered
        assert "watchdog_recoveries_total 1" in rendered

        # same process, same engine object: serving again
        assert eng.failure() is None
        out = eng.create_chat_completion(MSGS, temperature=0.0, max_tokens=4)
        assert out["usage"]["completion_tokens"] >= 1
    finally:
        FAULTS.disarm()
        wd.stop()
        eng.shutdown()


def test_continuous_recover_refused_after_deliberate_shutdown(tmp_path):
    path = str(tmp_path / "tiny-shut.gguf")
    write_tiny_llama_gguf(path)
    eng = ContinuousEngine(path, batch_size=2, n_ctx=64,
                           decode_chunk=2, max_gen_tokens=8,
                           prefill_buckets=(32, 64))
    eng.shutdown()
    assert eng.recover() is False      # a deliberate stop is not a fault


# ---------------------------------------------------------------------------
# server integration: error-class mapping + probe routes
# ---------------------------------------------------------------------------

@pytest.mark.anyio
async def test_engine_unavailable_maps_to_503():
    from tests.test_server import BODY, lifespan_client, make_client

    engine = FakeEngine(fail=EngineUnavailable("recovery in progress"))
    app, transport = make_client(engine)
    async with transport:
        await app.router.startup()
        async with await lifespan_client(app, transport) as client:
            r = await client.post("/response", json=BODY)
            assert r.status_code == 503
            assert "Engine unavailable" in r.json()["detail"]
            m = await client.get("/metrics")
            assert "engine_unavailable_total 1" in m.text
        await app.router.shutdown()


@pytest.mark.anyio
async def test_probe_routes_follow_state_machine():
    from tests.test_server import lifespan_client, make_client

    engine = FakeEngine()
    app, transport = make_client(engine)
    async with transport:
        await app.router.startup()
        async with await lifespan_client(app, transport) as client:
            r = await client.get("/health/ready")
            assert r.status_code == 200 and r.json()["state"] == READY
            assert (await client.get("/health/live")).status_code == 200

            app.state.health.transition(DEGRADED, "watchdog trip: test")
            r = await client.get("/health/ready")
            assert r.status_code == 503          # shed traffic...
            assert r.json()["state"] == DEGRADED
            assert (await client.get("/health/live")).status_code == 200  # ...but live
            h = await client.get("/health")
            assert h.status_code == 200
            assert h.json()["state"] == DEGRADED
            assert h.json()["resilience"]["health"]["reason"] \
                == "watchdog trip: test"
            m = await client.get("/metrics")
            assert "health_state 2" in m.text    # DEGRADED code

            app.state.health.transition(DEAD, "budget exhausted")
            assert (await client.get("/health/ready")).status_code == 503
            assert (await client.get("/health/live")).status_code == 503
        await app.router.shutdown()


@pytest.mark.anyio
async def test_watchdog_started_and_stopped_by_app_lifecycle():
    from tests.test_server import lifespan_client, make_client

    engine = FakeEngine()
    app, transport = make_client(engine)
    async with transport:
        await app.router.startup()
        assert app.state.watchdog is not None       # FakeEngine has a heartbeat
        assert app.state.engine_kw["deadline"] is True
        async with await lifespan_client(app, transport) as client:
            assert (await client.get("/health/ready")).status_code == 200
        await app.router.shutdown()
        assert app.state.watchdog is None            # stopped and cleared


# ---------------------------------------------------------------------------
# the drill script (tools/fault_drill.py) stays green in the tier-1 gate
# ---------------------------------------------------------------------------

def test_fault_drill_script():
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "fault_drill.py")],
        capture_output=True, text=True, cwd=ROOT, timeout=180,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stdout + r.stderr
    assert "PASS: READY → DEGRADED → READY" in r.stdout
