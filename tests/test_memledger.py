"""lfkt-mem: the live HBM memory ledger (ISSUE 10).

Layers:

1. **Registry semantics** — component-catalog enforcement (runtime twin
   of lfkt-lint OBS003), weakref pruning, duplicate-row merging, the
   disarmed stub.
2. **Pressure / fit check** — injected device stats drive the admission
   controller's memory signal and the registry's pre-load refusal.
3. **Engine wiring** — all four engines register their surfaces; the
   continuous scheduler cuts its budget, counts the event and stamps
   in-flight traces on the rising edge of memory pressure.
4. **Acceptance** — on a CPU two-model registry with paging on, the
   /debug/memory component sum matches ``jax.live_arrays()`` ground
   truth within 5%, with the residual line carrying the remainder.
5. **Disarmed cost** — ``LFKT_MEM_LEDGER=0`` takes no locks and
   allocates nothing on the decode path (poisoned-ledger pin, the
   ``LFKT_TRACE_SAMPLE=0`` precedent).
"""

from __future__ import annotations

import asyncio
import gc

import httpx
import pytest

from llama_fastapi_k8s_gpu_tpu.engine import (
    ContinuousEngine,
    Engine,
    FakeEngine,
)
from llama_fastapi_k8s_gpu_tpu.engine.continuous import AdmissionController
from llama_fastapi_k8s_gpu_tpu.obs.memledger import MemLedger
from llama_fastapi_k8s_gpu_tpu.obs.trace import Tracer
from llama_fastapi_k8s_gpu_tpu.serving import ModelRegistry, ModelSpec
from llama_fastapi_k8s_gpu_tpu.serving.registry import WeightBudgetError
from llama_fastapi_k8s_gpu_tpu.server.app import create_app
from llama_fastapi_k8s_gpu_tpu.testing import write_tiny_llama_gguf
from llama_fastapi_k8s_gpu_tpu.utils.metrics import Metrics

MSGS = [{"role": "user", "content": "Say something."}]
LEDGER_PATH = "llama_fastapi_k8s_gpu_tpu.obs.memledger.MEMLEDGER"


@pytest.fixture(scope="module")
def model_path(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("memledger") / "tiny.gguf")
    write_tiny_llama_gguf(path)
    return path


@pytest.fixture(scope="module")
def ggufs(tmp_path_factory):
    d = tmp_path_factory.mktemp("memledger-mm")
    pa, pb = str(d / "a.gguf"), str(d / "b.gguf")
    write_tiny_llama_gguf(pa, seed=0)
    write_tiny_llama_gguf(pb, seed=7)
    return pa, pb


@pytest.fixture()
def ledger(monkeypatch):
    """A fresh armed process ledger: engines built inside the test
    register here (module-level MEMLEDGER is resolved at call time), so
    other modules' long-lived fixture engines never pollute the rows."""
    led = MemLedger(armed=True, pressure_fraction=0.05)
    monkeypatch.setattr(LEDGER_PATH, led)
    return led


class _Owner:
    def __init__(self, name=""):
        self.model_name = name


# ---------------------------------------------------------------------------
# layer 1: registry semantics
# ---------------------------------------------------------------------------

def test_tree_nbytes_counts_physical_shards():
    """Byte providers and the device ground truth must speak the same
    unit — PHYSICAL bytes: a replicated array costs one copy per device
    (what memory_stats sees), a sharded one exactly its pieces.  On a
    multi-chip mesh, logical .nbytes would understate replication and
    drive the residual negative by ~(N-1)/N."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from llama_fastapi_k8s_gpu_tpu.obs.memledger import tree_nbytes

    devs = jax.devices()
    assert len(devs) == 8                     # conftest virtual devices
    mesh = Mesh(np.array(devs), ("d",))
    repl = jax.device_put(jnp.ones((8, 4)),
                          NamedSharding(mesh, PartitionSpec()))
    assert tree_nbytes({"x": repl}) == repl.nbytes * 8
    shard = jax.device_put(jnp.ones((8, 4)),
                           NamedSharding(mesh, PartitionSpec("d")))
    assert tree_nbytes({"x": shard}) == shard.nbytes
    assert tree_nbytes(None) == 0
    assert tree_nbytes({"scalar": 3}) == 0    # non-array leaves are free


def test_unknown_component_and_residual_refused(ledger):
    with pytest.raises(KeyError):
        ledger.register_component("phantom_surface", _Owner(), lambda o: 1)
    with pytest.raises(KeyError):        # computed, never registered
        ledger.register_component("residual", _Owner(), lambda o: 1)


def test_rows_merge_prune_and_model_attribution(ledger):
    a, b = _Owner("m1"), _Owner("m1")
    ledger.register_component("weights", a, lambda o: 100)
    ledger.register_component("weights", a, lambda o: 100)   # idempotent
    ledger.register_component("weights", b, lambda o: 50)
    pool = _Owner("")
    ledger.register_component(
        "kv_arena_used", pool, lambda o: {"alpha": 10, "beta": 0, "": 5})
    ledger.register_component("host_spill", pool, lambda o: 7)
    rows = {(r["component"], r["model"]): r for r in ledger._rows()}
    # same (component, model) merges by summing; zero rows are dropped
    assert rows[("weights", "m1")]["bytes"] == 150
    assert rows[("kv_arena_used", "alpha")]["bytes"] == 10
    assert rows[("kv_arena_used", "")]["bytes"] == 5
    assert ("kv_arena_used", "beta") not in rows
    assert rows[("host_spill", "")]["device"] is False
    # a raising provider is skipped, never raises through telemetry
    bad = _Owner("boom")
    ledger.register_component("kv_ring", bad,
                              lambda o: (_ for _ in ()).throw(ValueError()))
    assert ("kv_ring", "boom") not in {
        (r["component"], r["model"]) for r in ledger._rows()}
    # weakref pruning: a collected owner's rows vanish
    del b
    gc.collect()
    assert {(r["component"], r["model"]): r["bytes"]
            for r in ledger._rows()}[("weights", "m1")] == 100


def test_always_component_reports_zero_not_absence(ledger):
    """kv_arena_free at 0 IS the exhaustion alert: always-components keep
    their row (and gauge series) at zero instead of vanishing into
    'no data' at the exact moment the RUNBOOK triage needs them."""
    pool, eng = _Owner(), _Owner("m")
    ledger.register_component("kv_arena_free", pool, lambda o: 0)
    ledger.register_component("weights", eng, lambda o: 0)
    rows = {(r["component"], r["model"]): r["bytes"]
            for r in ledger._rows()}
    assert rows[("kv_arena_free", "")] == 0      # reported at zero
    assert ("weights", "m") not in rows          # ordinary zero row drops


def test_snapshot_residual_and_disarmed_stub(ledger):
    ledger.stats_fn = lambda: {"bytes_in_use": 1000, "bytes_limit": 4000}
    w, s = _Owner("m"), _Owner()     # weakly held: keep them alive
    ledger.register_component("weights", w, lambda o: 600)
    ledger.register_component("host_spill", s, lambda o: 50)
    doc = ledger.snapshot()
    assert doc["armed"] and doc["schema"] == 1
    assert doc["ground_truth"]["source"] == "device.memory_stats"
    assert doc["attributed_bytes"] == 600        # host tier excluded
    assert doc["host_bytes"] == 50
    assert doc["residual_bytes"] == 400          # truth - attributed
    assert doc["headroom"]["bytes"] == 3000
    assert doc["headroom"]["fraction"] == 0.75
    ledger.configure(armed=False)
    assert ledger.snapshot() == {"schema": 1, "armed": False}


# ---------------------------------------------------------------------------
# layer 2: pressure + fit check
# ---------------------------------------------------------------------------

def test_pressure_thresholds_and_latch(ledger):
    assert ledger.pressure() is False          # CPU: no stats, latches
    ledger.stats_fn = lambda: {"bytes_in_use": 98, "bytes_limit": 100}
    assert ledger.pressure() is True           # 2% free < 5%
    assert ledger.last_headroom == (2, 100)
    ledger.stats_fn = lambda: {"bytes_in_use": 10, "bytes_limit": 100}
    assert ledger.pressure() is False
    ledger.configure(armed=False)
    ledger.stats_fn = lambda: (_ for _ in ()).throw(AssertionError("boom"))
    assert ledger.pressure() is False          # disarmed: never touches it


def test_zero_bytes_in_use_does_not_latch_stats_off(ledger, monkeypatch):
    """A device that reports memory stats with ZERO bytes in use (the
    registry's pre-load fit check runs before the first allocation) must
    not be mistaken for a stat-less backend: only the ABSENCE of the
    field latches, or pressure()/fit_check() would be dead for the
    process lifetime on exactly the hardware they target."""
    monkeypatch.setattr(ledger, "_raw_device_stats",
                        lambda: {"bytes_in_use": 0, "bytes_limit": 100})
    assert ledger.fit_check(500, label="big") is not None   # 500 > 100
    assert ledger._no_device_stats is False
    assert ledger.pressure() is False                       # 100% free
    # a genuinely stat-less backend still latches after one probe
    monkeypatch.setattr(ledger, "_raw_device_stats", lambda: None)
    ledger._no_device_stats = False
    assert ledger._device_stats() == {}
    assert ledger._no_device_stats is True


def test_fit_check_refusal_names_label(ledger):
    assert ledger.fit_check(10**9, label="big") is None   # no stats: pass
    ledger.stats_fn = lambda: {"bytes_in_use": 900, "bytes_limit": 1000}
    assert ledger.fit_check(50, label="small") is None
    msg = ledger.fit_check(500, label="bigmodel")
    assert msg is not None and "bigmodel" in msg and "HBM" in msg
    ledger.configure(armed=False)
    assert ledger.fit_check(500, label="bigmodel") is None


def test_registry_preload_fit_check_refuses(ledger, ggufs):
    """serving/registry.py asks the ledger BEFORE build(): a manifest
    that cannot physically fit refuses without paying the load."""
    pa, pb = ggufs
    ledger.stats_fn = lambda: {"bytes_in_use": 999, "bytes_limit": 1000}
    built = []

    def build(spec, path, shared_pool):      # must never run
        built.append(spec.name)
        raise AssertionError("build ran past a failing fit check")

    with pytest.raises(WeightBudgetError) as ei:
        ModelRegistry.from_specs(
            [ModelSpec("alpha", pa), ModelSpec("beta", pb)], build,
            default_model="alpha")
    assert "alpha" in str(ei.value) and "fit check" in str(ei.value)
    assert built == []


# ---------------------------------------------------------------------------
# layer 3: engine wiring
# ---------------------------------------------------------------------------

def _components(ledger):
    return {(r["component"], r["model"]) for r in ledger._rows()}


def test_both_engines_register_surfaces(ledger, model_path):
    eng = Engine(model_path, n_ctx=128, prefill_buckets=(32,))
    name = eng.model_name
    assert {("weights", name), ("kv_ring", name)} <= _components(ledger)
    assert ("kv_lanes", name) not in _components(ledger)
    rows = {(r["component"], r["model"]): r["bytes"]
            for r in ledger._rows()}
    assert rows[("kv_ring", name)] > 0

    cont = ContinuousEngine(model_path, batch_size=2, n_ctx=128,
                            decode_chunk=4, max_gen_tokens=8,
                            prefill_buckets=(32, 64, 128))
    try:
        comps = _components(ledger)
        assert ("kv_scratch", name) in comps
        assert ("kv_lanes", name) in comps
        rows = {(r["component"], r["model"]): r["bytes"]
                for r in ledger._rows()}
        # two lanes of the ring and their bookkeeping
        assert rows[("kv_lanes", name)] > 2 * rows[("kv_scratch", name)]
    finally:
        cont.shutdown()
    del eng


def test_paged_pool_registers_arena_rows(ledger, model_path):
    eng = Engine(model_path, n_ctx=128, prefill_buckets=(32,),
                 kv_paged=True, kv_page_tokens=16, kv_pool_pages=8)
    out = eng.create_chat_completion(MSGS, temperature=0.0, max_tokens=4)
    assert out["usage"]["completion_tokens"] >= 1
    rows = {(r["component"], r["model"]): r["bytes"]
            for r in ledger._rows()}
    assert rows[("kv_arena_used", "")] > 0       # default namespace
    assert rows[("kv_arena_free", "")] > 0
    # used + free == the whole arena, always
    assert rows[("kv_arena_used", "")] + rows[("kv_arena_free", "")] == \
        eng._kvpool.arena_nbytes


def test_admission_controller_mem_pressure_forces_cut():
    ctl = AdmissionController(chunk=64, lanes=4, base=512)
    for _ in range(4):                   # idle lanes: budget grows
        ctl.observe_wave(1, 0.0, 0.1)
    grown = ctl.budget
    assert grown > 512
    # memory pressure cuts EVEN under idle-growth conditions
    assert ctl.observe_wave(1, 0.0, 0.1, mem_pressure=True) == \
        max(grown // 2, 64)
    for _ in range(10):
        ctl.observe_wave(1, 0.0, 0.1, mem_pressure=True)
    assert ctl.budget == 64              # floored at one slice, never 0


def test_continuous_wave_consults_ledger_and_annotates(ledger, model_path):
    """The scheduler passes the ledger's verdict into the controller,
    publishes mem_pressure in scheduler_stats, bumps the cataloged
    counter, and stamps in-flight traces ONCE per rising edge."""
    eng = ContinuousEngine(model_path, batch_size=2, n_ctx=128,
                           decode_chunk=4, max_gen_tokens=16,
                           prefill_buckets=(32, 64, 128))
    m = Metrics()
    eng.metrics_sink = m
    tracer = Tracer(sample=1.0, ring=8)
    try:
        base_budget = eng._adm_budget
        ledger.stats_fn = lambda: {"bytes_in_use": 99, "bytes_limit": 100}
        tr = tracer.start("request")
        out = eng.create_chat_completion(MSGS, temperature=0.0,
                                         max_tokens=8, trace=tr)
        assert out["usage"]["completion_tokens"] >= 1
        # the post-drain bookkeeping wave may have republished stats with
        # no chunk in flight; the edge detector and the cut budget carry
        # the deterministic evidence
        assert eng._mem_hot_prev is True
        assert eng._adm_budget < base_budget      # cut toward the floor
        snap = m.snapshot()
        assert snap["mem_pressure_events_total"][()] == 1.0  # rising edge
        events = [e for e in tr.root.events if e["name"] == "mem_pressure"]
        assert len(events) == 1
        assert events[0]["headroom_bytes"] == 1
        assert events[0]["limit_bytes"] == 100
        tracer.finish(tr)
    finally:
        eng.shutdown()


# ---------------------------------------------------------------------------
# layer 4: acceptance — two-model paged reconciliation through the server
# ---------------------------------------------------------------------------

def _settled_truth(ledger, patience_s: float = 5.0) -> dict:
    """The process-wide ground truth once it has stopped moving.  It counts
    EVERY live array of the process, and under ``--dist load`` a worker runs
    this test behind whatever files it was handed: an engine of theirs that
    is still shutting down (a scheduler thread that holds its lanes' state
    for a moment longer) frees tens of MB a moment later, which read as
    NEGATIVE growth of this test's registry (PR 56: "the process grew
    -51559544", on the parent too).  Two equal readings 0.2 s apart."""
    import time

    deadline = time.time() + patience_s
    gc.collect()
    truth = ledger.ground_truth()
    while time.time() < deadline:
        time.sleep(0.2)
        gc.collect()
        again = ledger.ground_truth()
        if again["bytes"] == truth["bytes"]:
            break
        truth = again
    return truth


@pytest.mark.anyio
async def test_two_model_paged_reconciliation_within_5pct(ledger, ggufs):
    """ISSUE 10 acceptance: CPU two-model registry, paging on — the
    /debug/memory component sum explains the registry's allocations to
    within 5% of jax.live_arrays() ground truth, and the residual line
    carries exactly the remainder (the pre-existing process bytes)."""
    # ``before`` is taken AFTER an app has started (ROADMAP C0): the
    # start-up's own collection (server/app.py ``_settle_heap``) and its
    # rebinding of the process-wide sinks free arrays that earlier tests of
    # this worker still held through cycles (52 MB in the driver's runs),
    # which then read as NEGATIVE growth of this test's registry.  A
    # throwaway app over a fake engine pays for that first.
    warm = create_app(engine=FakeEngine(reply="hi"))
    async with httpx.ASGITransport(app=warm):
        await warm.router.startup()
        await warm.router.shutdown()
    del warm
    before = _settled_truth(ledger)
    assert before["source"] == "jax.live_arrays"
    pa, pb = ggufs
    specs = [ModelSpec("alpha", pa), ModelSpec("beta", pb)]

    def build(spec, path, shared_pool):
        return Engine(path, n_ctx=128, prefill_buckets=(32,),
                      kv_paged=True, kv_page_tokens=8, kv_pool_pages=32,
                      kv_pool=shared_pool, kv_namespace=spec.name)

    reg = ModelRegistry.from_specs(specs, build, default_model="alpha")
    # populate the shared arena under BOTH namespaces
    msgs = [{"role": "user", "content": "the quick brown fox jumps over"}]
    for model in ("alpha", "beta"):
        out = reg.create_chat_completion(msgs, model=model,
                                         temperature=0.0, max_tokens=6)
        assert out["usage"]["completion_tokens"] >= 1

    app = create_app(engine=reg)
    transport = httpx.ASGITransport(app=app)
    async with transport:
        await app.router.startup()
        async with httpx.AsyncClient(transport=transport,
                                     base_url="http://t") as client:
            doc = (await client.get("/debug/memory")).json()
            metrics = (await client.get("/metrics")).text
        await app.router.shutdown()

    assert doc["armed"] and doc["schema"] == 1
    comps = {(r["component"], r["model"]): r["bytes"]
             for r in doc["components"]}
    # per-model weights AND per-namespace arena attribution
    assert comps[("weights", "alpha")] > 0
    assert comps[("weights", "beta")] > 0
    assert comps[("kv_arena_used", "alpha")] > 0
    assert comps[("kv_arena_used", "beta")] > 0
    # the reconciliation: everything the registry added is attributed
    truth = doc["ground_truth"]
    assert truth["source"] == "jax.live_arrays"
    attributed = doc["attributed_bytes"]
    grown = truth["bytes"] - before["bytes"]
    assert attributed > 0
    assert abs(grown - attributed) / attributed < 0.05, (
        f"ledger explains {attributed} bytes but the process grew "
        f"{grown} (pre-existing {before['bytes']})")
    # the residual line carries the remainder, exactly
    assert doc["residual_bytes"] == truth["bytes"] - attributed
    # fragmentation present for the paged pool
    assert doc["fragmentation"]["largest_free_run"] >= 1
    assert 0.0 <= doc["fragmentation"]["ratio"] <= 1.0
    # and the same rows flow as hbm_bytes gauges at /metrics
    assert 'hbm_bytes{component="weights",model="alpha"}' in metrics
    assert 'hbm_bytes{component="kv_arena_used",model="beta"}' in metrics
    assert 'hbm_bytes{component="residual",model=""}' in metrics


def test_ns_page_counters_match_tree_walk(ledger, ggufs):
    """The ledger's per-namespace page counters are maintained
    incrementally (so a scrape never walks the radix tree under the
    allocation lock); they must agree with a fresh DFS after a workload
    that commits, evicts, spills and restores across two namespaces."""
    pa, pb = ggufs
    ea = Engine(pa, n_ctx=128, prefill_buckets=(32,), kv_paged=True,
                kv_page_tokens=8, kv_pool_pages=12, kv_spill_pages=8,
                kv_namespace="alpha")
    eb = Engine(pb, n_ctx=128, prefill_buckets=(32,), kv_paged=True,
                kv_pool=ea._kvpool, kv_page_tokens=8, kv_namespace="beta")
    pool = ea._kvpool
    prompts = ["the quick brown fox jumps over", "a completely different",
               "yet another conversation about", "and one more for luck"]
    for i, text in enumerate(prompts):      # 12-page pool: forces
        eng = (ea, eb)[i % 2]               # eviction + spill traffic
        eng.create_chat_completion([{"role": "user", "content": text}],
                                   temperature=0.0, max_tokens=4)
    # re-run the first prompt: spill-restore path
    ea.create_chat_completion([{"role": "user", "content": prompts[0]}],
                              temperature=0.0, max_tokens=4)
    fast = pool._ledger_used()
    slow = pool._ledger_used_slow()
    fast.pop("(unindexed)", None)
    assert fast == slow, (fast, slow, pool.stats())
    assert pool.counters["evictions"] > 0    # the workload really churned
    pool.reset()
    assert pool._ledger_used() == {}


def test_pool_fragmentation_math(ledger, model_path):
    eng = Engine(model_path, n_ctx=128, prefill_buckets=(32,),
                 kv_paged=True, kv_page_tokens=16, kv_pool_pages=8)
    pool = eng._kvpool
    with pool._lock:
        pool._free = [0, 1, 2, 5, 7]
    occ = pool.occupancy()
    assert occ["largest_free_run"] == 3
    assert occ["pages_free"] == 5


# ---------------------------------------------------------------------------
# layer 5: per-model token metering (satellite)
# ---------------------------------------------------------------------------

@pytest.mark.anyio
async def test_token_usage_counters_per_model():
    app = create_app(engine=FakeEngine(reply="hey"))
    body = {
        "bot_profile": {"name": "Alice.f",
                        "appearance": "tall,slim,blonde,cats,rain",
                        "system_prompt": "Be brief."},
        "user_profile": {"name": "Bob"},
        "context": [{"turn": "user", "message": "hi"}],
    }
    transport = httpx.ASGITransport(app=app)
    async with transport:
        await app.router.startup()
        async with httpx.AsyncClient(transport=transport,
                                     base_url="http://t") as client:
            assert (await client.post("/response",
                                      json=body)).status_code == 200
            r = await client.post("/v1/chat/completions", json={
                "messages": [{"role": "user", "content": "hi"}]})
            assert r.status_code == 200
            metrics = (await client.get("/metrics")).text
        await app.router.shutdown()
    # both served requests metered under the model label, prompt AND
    # completion sides (FakeEngine reports 1/1 usage per request)
    assert 'tokens_prompt_total{model="fake"} 2' in metrics
    assert 'tokens_generated_total{model="fake"} 2' in metrics


@pytest.mark.anyio
async def test_hbm_gauges_drop_vanished_rows(ledger):
    """The hbm_bytes family is rebuilt whole each scrape: a row whose
    source vanished (collected engine, drained tier) must drop its
    series, not freeze at its last value — stale rows would make the
    component sum exceed ground truth."""
    app = create_app(engine=FakeEngine(reply="ok"))
    owner = _Owner("ghost")
    ledger.register_component("weights", owner, lambda o: 12345)
    transport = httpx.ASGITransport(app=app)
    async with transport:
        await app.router.startup()
        async with httpx.AsyncClient(transport=transport,
                                     base_url="http://t") as client:
            first = (await client.get("/metrics")).text
            assert 'hbm_bytes{component="weights",model="ghost"} 12345' \
                in first
            del owner
            gc.collect()
            second = (await client.get("/metrics")).text
            assert 'model="ghost"' not in second
        await app.router.shutdown()


# ---------------------------------------------------------------------------
# layer 6: disarmed cost (poisoned-ledger pin)
# ---------------------------------------------------------------------------

def test_disarmed_decode_path_is_poison_proof(ledger, model_path,
                                              monkeypatch):
    """LFKT_MEM_LEDGER=0: the per-wave pressure consult is ONE attribute
    read returning False — a poisoned ledger (every internal raises)
    must never be touched by a full continuous generation."""
    ledger.configure(armed=False)

    def boom(*a, **kw):
        raise AssertionError("disarmed memory ledger was touched")

    monkeypatch.setattr(ledger, "_device_stats", boom)
    monkeypatch.setattr(ledger, "_rows", boom)
    monkeypatch.setattr(ledger, "ground_truth", boom)
    eng = ContinuousEngine(model_path, batch_size=2, n_ctx=128,
                           decode_chunk=4, max_gen_tokens=16,
                           prefill_buckets=(32, 64, 128))
    try:
        out = eng.create_chat_completion(MSGS, temperature=0.0, max_tokens=8)
        assert out["usage"]["completion_tokens"] >= 1
        assert eng.scheduler_stats()["mem_pressure"] == 0
        assert ledger.snapshot() == {"schema": 1, "armed": False}
    finally:
        eng.shutdown()
