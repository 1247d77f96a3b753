"""The start-up timeline (utils/startup.py): process start to the READY flip
as ``/health`` ``engine.startup``, ``Engine.load_phases`` as a view of it,
the warm-up split by what compiled, and the six per-layer readers of
``benchmarks/layer_metrics/`` that read it (tier-1 collects nothing under
``benchmarks/tests/``, so they are held here)."""

from __future__ import annotations

import importlib.util
import json
import os
import socket
import subprocess
import sys
import time
import urllib.request

import httpx
import pytest

from llama_fastapi_k8s_gpu_tpu.engine import ContinuousEngine, Engine, FakeEngine
from llama_fastapi_k8s_gpu_tpu.obs.devtime import DEVTIME
from llama_fastapi_k8s_gpu_tpu.server.app import create_app
from llama_fastapi_k8s_gpu_tpu.testing import write_tiny_llama_gguf
from llama_fastapi_k8s_gpu_tpu.utils import startup
from llama_fastapi_k8s_gpu_tpu.utils.config import Settings
from llama_fastapi_k8s_gpu_tpu.utils.startup import (
    CompileMeter, Phase, Timeline, legacy_load_phases, process_start)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIELDS = ("process_start_unix", "process_start_from", "ready_unix", "ready_s",
          "phases", "unnamed_s", "clock")
LEGACY = ("tokenizer_s", "probes_s", "params_s", "params_prep_s",
          "params_stack_s", "warmup_s")
READERS = ("program_ready_s", "runtime_start_s", "probes_s",
           "warmup_compile_s", "setup_outside_ready_s", "startup_named_share")
FILE_PHASES = ("gguf_open", "tokenizer", "probes", "params")
#: n_ctx 144: a ring no other test file builds, so the jit caches (one a
#: process, and a worker runs whatever files ``--dist load`` hands it) hold
#: none of these engines' programs when the first of them warms up
KW = dict(n_ctx=144, decode_chunk=4, max_gen_tokens=16, prefill_buckets=(32,))
#: the warm-up's steps as the code has them, by engine kind
STEPS = {"serial": ["request", "buckets", "reuse_buckets"],
         "lanes": ["lanes_round", "stream_round", "slice_shapes", "drain",
                   "lane_copy"]}


def assert_sound(doc: dict) -> None:
    """Top-level phases ordered, none overlapping the next, and their
    seconds with ``unnamed_s`` adding up to ``ready_s``."""
    phases = doc["phases"]
    assert phases
    starts = [p["start_s"] for p in phases]
    assert starts == sorted(starts)
    for a, b in zip(phases, phases[1:]):
        assert a["start_s"] + a["seconds"] <= b["start_s"] + 0.002, (a, b)
    assert all(p["seconds"] >= 0 for p in phases)
    assert doc["unnamed_s"] == pytest.approx(
        doc["ready_s"] - sum(p["seconds"] for p in phases), abs=1e-6)
    assert doc["unnamed_s"] >= -0.002 * len(phases)


def warmed(eng):
    """(engine, the jit registry's compile ledger before and after its
    warm-up)."""
    before = DEVTIME.compile_ledger()
    eng.warmup()
    return eng, before, DEVTIME.compile_ledger()


@pytest.fixture(scope="module")
def gguf(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("startup") / "tiny.gguf")
    write_tiny_llama_gguf(path)
    return path


@pytest.fixture(scope="module")
def engines(gguf):
    """{kind: (engine, ledger before, ledger after)}: a file-loaded and an
    in-memory engine, serial and on lanes, each warmed once."""
    made = {"serial-file": warmed(Engine(gguf, weight_format="q4k", **KW))}
    parts = made["serial-file"][0]
    made["lanes-file"] = warmed(ContinuousEngine(
        gguf, batch_size=2, weight_format="q4k", **KW))
    made["serial-parts"] = warmed(Engine.from_parts(
        parts.params, parts.cfg, parts.tokenizer, parts.template_kind,
        **{k: v for k, v in KW.items() if k != "n_ctx"}))
    made["lanes-parts"] = warmed(ContinuousEngine(
        None, batch_size=2, _parts=(parts.params, parts.cfg, parts.tokenizer,
                                    parts.template_kind), **KW))
    yield made
    for kind, (eng, _, _) in made.items():
        if kind.startswith("lanes"):
            eng.shutdown()


KINDS = ("serial-file", "lanes-file", "serial-parts", "lanes-parts")


# -- the record --------------------------------------------------------------

def test_phases_are_ordered_and_add_up_with_unnamed_to_ready():
    tl = Timeline(1000.0, "proc_stat")
    tl.phase("b", 1002.0, 1003.5)
    tl.phase("a", 1000.25, 1002.0, why="first")
    tl.ready_unix = 1004.0
    doc = tl.doc()
    assert [p["name"] for p in doc["phases"]] == ["a", "b"]
    assert doc["phases"][0] == {"name": "a", "start_s": 0.25, "seconds": 1.75,
                                "attrs": {"why": "first"}}
    assert doc["ready_s"] == 4.0 and doc["unnamed_s"] == 0.75
    assert doc["clock"] == "time.time"
    assert_sound(doc)


def test_a_phase_run_again_replaces_the_first_and_an_open_one_is_left_out():
    tl = Timeline(0.0, "proc_stat")
    tl.phase("warmup", 1.0, 2.0)
    tl.phase("warmup", 3.0, 5.0)
    tl.phase("still_open", 6.0)
    assert [(p["name"], p["seconds"]) for p in tl.doc()["phases"]] == [
        ("warmup", 2.0)]
    assert tl.doc()["ready_s"] is None and tl.doc()["unnamed_s"] is None


def test_a_child_leaves_its_parents_ends_alone():
    parent = Phase("warmup", 10.0)
    with parent.child("request") as kid:
        pass
    assert parent.children == [kid] and kid.t1 is not None
    assert parent.t1 is None


def test_only_the_served_timeline_has_an_origin(monkeypatch):
    def no_proc(*a, **kw):
        raise AssertionError("an engine's timeline reads no /proc")
    monkeypatch.setattr("builtins.open", no_proc)
    tl = Timeline()
    assert tl.process_start_unix is None and tl.source is None


def test_absorb_takes_a_timeline_and_nothing_else():
    tl, other = Timeline(0.0, "proc_stat"), Timeline(0.0, "proc_stat")
    other.phase("params", 1.0, 2.0)
    tl.absorb(other)
    tl.absorb(None)
    tl.absorb(object())
    assert [p.name for p in tl.phases] == ["params"]


@pytest.mark.parametrize("readable", [True, False], ids=["proc", "no_proc"])
def test_process_start_is_the_kernels_or_the_first_stamp(readable, monkeypatch):
    now = time.time()
    if not readable:
        def no_proc(*a, **kw):
            raise OSError("no /proc here")
        monkeypatch.setattr("builtins.open", no_proc)
    got, source = process_start(now)
    if readable and os.path.exists("/proc/self/stat"):
        assert source == "proc_stat"
        assert now - 86400 * 30 < got <= now    # this process is running
    else:
        assert (got, source) == (now, "package_import")


def test_the_kernels_start_time_is_good_to_a_tick():
    """A child's start by ``/proc/self/stat`` lies between the stamps taken
    around its ``Popen``, give or take a clock tick (10 ms)."""
    code = ("import sys, time; sys.path.insert(0, %r); "
            "from llama_fastapi_k8s_gpu_tpu.utils.startup import process_start;"
            " print(*process_start(time.time()))" % ROOT)
    t0 = time.time()
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=60, check=True).stdout.split()
    t1 = time.time()
    if out[1] != "proc_stat":
        pytest.skip("no /proc/self/stat here")
    assert t0 - 0.05 <= float(out[0]) <= t1


# -- an engine's stretch -----------------------------------------------------

@pytest.mark.parametrize("kind", KINDS)
def test_an_engine_has_a_timeline_with_file_phases_only_from_a_file(
        engines, kind):
    eng = engines[kind][0]
    names = [p.name for p in eng.startup.phases]
    want = ["cache_alloc", "warmup"]
    if kind.startswith("lanes"):    # a subclass's own stretch, beside it
        want[1:1] = ["lanes_alloc", "scheduler_start"]
    if kind.endswith("file"):
        want = list(FILE_PHASES) + want
    assert names == want
    assert eng.startup.process_start_unix is None
    served = Timeline(eng.startup.phases[0].t0, "proc_stat")
    served.absorb(eng.startup)
    served.ready_unix = eng.startup.get("warmup").t1
    assert_sound(served.doc())
    assert not eng.startup.get("cache_alloc").children


@pytest.mark.parametrize("key", LEGACY)
@pytest.mark.parametrize("kind", ["serial-file", "lanes-file"])
def test_the_legacy_load_phases_are_the_timelines_values_at_a_tenth(
        engines, kind, key):
    eng = engines[kind][0]
    top, _, kid = {"params_prep_s": "params.prep",
                   "params_stack_s": "params.stack"}.get(
        key, key[:-2]).partition(".")
    ph = eng.startup.get(top)
    if kid:
        ph = next(c for c in ph.children if c.name == kid)
    assert set(eng.load_phases) == set(LEGACY)
    assert eng.load_phases[key] == round(ph.t1 - ph.t0, 1)
    assert eng.load_phases == legacy_load_phases(eng.startup)


@pytest.mark.parametrize("kind", ["serial-parts", "lanes-parts"])
def test_an_in_memory_engine_views_its_warmup_alone(engines, kind):
    eng = engines[kind][0]
    assert set(eng.load_phases) == {"warmup_s"}
    assert eng.startup.phases       # a timeline, not an empty one


def test_the_params_children_cover_the_load(engines):
    ph = engines["serial-file"][0].startup.get("params")
    assert [c.name for c in ph.children] == ["prep", "head", "stack"]
    assert ph.t0 <= ph.children[0].t0 and ph.children[-1].t1 <= ph.t1
    for a, b in zip(ph.children, ph.children[1:]):
        assert a.t1 == b.t0


def test_the_probes_say_which_kernels_and_what_compiled(engines):
    attrs = engines["serial-file"][0].startup.get("probes").attrs
    assert attrs["kernels"] and attrs["experts"] is False
    assert attrs["compiled_uncached"] == attrs["cache_requests"] \
        - attrs["cache_hits"] - attrs["cache_misses"]


# -- the warm-up, split by what it did ---------------------------------------

@pytest.mark.parametrize("kind", KINDS)
def test_warmup_counts_what_the_jit_registry_counted(engines, kind):
    eng, before, after = engines[kind]
    ph = eng.startup.get("warmup")
    a = ph.attrs
    compiled = {n: c - before.get(n, (0, 0.0))[0] for n, (c, _) in after.items()}
    assert a["programs_compiled"] == sum(compiled.values())
    assert a["compile_s"] == pytest.approx(
        sum(s - before.get(n, (0, 0.0))[1] for n, (_, s) in after.items()),
        abs=0.002 * len(after))
    assert a["execute_s"] == pytest.approx(ph.t1 - ph.t0 - a["compile_s"],
                                           abs=0.002)
    assert a["compiled_uncached"] == a["cache_requests"] - a["cache_hits"] \
        - a["cache_misses"]
    # the executable store's part of them (PR 55): the tests keep out of
    # the persistent cache (conftest.py), so out of the store beside it
    assert {k: a[k] for k in ("programs_loaded", "load_s", "programs_built",
                              "build_s", "load_failures")} == {
        "programs_loaded": 0, "load_s": 0, "programs_built": 0,
        "build_s": 0, "load_failures": 0}
    tops = a["top_programs"]
    assert len(tops) <= 8
    assert [t["compile_s"] for t in tops] == sorted(
        (t["compile_s"] for t in tops), reverse=True)
    assert all(compiled[t["name"]] == t["compiles"] > 0 for t in tops)
    # the phase and the registry summed the same events: seconds where
    # and only where something compiled, whatever an earlier test of this
    # process left in the jit caches
    assert (a["compile_s"] > 0) == (a["programs_compiled"] > 0) \
        == bool(tops)
    if kind.endswith("file"):   # the first engine of its kind compiles
        assert a["programs_compiled"] >= 3 and a["compile_s"] > 0


@pytest.mark.parametrize("kind", KINDS)
def test_warmup_has_a_child_for_each_step(engines, kind):
    ph = engines[kind][0].startup.get("warmup")
    assert [c.name for c in ph.children] == STEPS[kind.split("-")[0]]
    assert ph.t0 <= ph.children[0].t0 and ph.children[-1].t1 <= ph.t1
    by = {c.name: c for c in ph.children}
    if kind.startswith("lanes"):
        assert by["slice_shapes"].attrs == {"n_shapes": 2}
    else:
        assert by["buckets"].attrs == {"n_buckets": 1}


def test_compiled_uncached_is_requests_less_hits_less_misses(monkeypatch):
    """A cached ``mistral`` start: 97 requests, 5 hits, 0 misses."""
    from llama_fastapi_k8s_gpu_tpu.utils import jaxcache

    seen = iter([{"dir": "x", "requests": 3, "hits": 1, "misses": 1},
                 {"dir": "x", "requests": 100, "hits": 6, "misses": 1}])
    monkeypatch.setattr(jaxcache, "compile_cache_stats", lambda: next(seen))
    assert CompileMeter().cache() == {
        "cache_requests": 97, "cache_hits": 5, "cache_misses": 0,
        "compiled_uncached": 92}


# -- behind the app ----------------------------------------------------------

async def health_of(app) -> dict:
    transport = httpx.ASGITransport(app=app)
    async with transport:
        await app.router.startup()
        async with httpx.AsyncClient(transport=transport,
                                     base_url="http://test") as client:
            r = await client.get("/health")
        await app.router.shutdown()
    assert r.status_code == 200 and r.json()["state"] == "READY"
    return r.json()["engine"]


@pytest.fixture(scope="module")
def served(engines):
    """{kind: ``/health`` ``engine`` at READY}: an in-memory engine handed
    to the app, a file engine built by the app's factory, a fake."""
    import anyio

    parts = engines["serial-parts"][0]

    def factory():
        return Engine.from_parts(
            parts.params, parts.cfg, parts.tokenizer, parts.template_kind,
            **{k: v for k, v in KW.items() if k != "n_ctx"})

    def warmed_factory():
        eng = factory()
        eng.warmup()
        return eng

    return {
        "handed": anyio.run(health_of, create_app(
            engine=parts, settings=Settings())),
        "lanes": anyio.run(health_of, create_app(
            engine=engines["lanes-parts"][0], settings=Settings())),
        "built": anyio.run(health_of, create_app(
            engine_factory=warmed_factory, settings=Settings())),
        "fake": anyio.run(health_of, create_app(
            engine_factory=FakeEngine, settings=Settings())),
    }


def test_the_start_ends_with_a_full_collection(monkeypatch):
    """READY comes right after one full pass of the collector, so that
    none falls due in the first requests (server/app.py ``_settle_heap``:
    a start that loaded its programs stops a few thousand objects short of
    the next pass's threshold)."""
    import anyio
    import gc

    from llama_fastapi_k8s_gpu_tpu.server import app as app_module

    seen = []

    def full_pass(phase, info):
        if phase == "stop" and info["generation"] == 2:
            seen.append(app.state.ready)

    app = create_app(engine_factory=FakeEngine, settings=Settings())
    gc.callbacks.append(full_pass)
    try:
        anyio.run(health_of, app)
    finally:
        gc.callbacks.remove(full_pass)
    assert False in seen            # a full pass before the READY flip
    assert app_module._settle_heap.__doc__


@pytest.mark.parametrize("field", FIELDS)
def test_health_at_ready_carries_every_field(served, field):
    doc = served["handed"]["startup"]
    assert doc[field] is not None
    assert doc["clock"] == "time.time"
    assert doc["ready_unix"] - doc["process_start_unix"] == pytest.approx(
        doc["ready_s"], abs=0.002)


@pytest.mark.parametrize("kind", ["handed", "lanes", "built", "fake"])
def test_the_served_timeline_adds_up(served, kind):
    doc = served[kind]["startup"]
    assert_sound(doc)
    names = [p["name"] for p in doc["phases"]]
    assert names[-1] == "app_start"
    if kind == "fake":      # nothing of its own: the factory's whole call
        assert names == ["engine_load", "app_start"]
    elif kind == "lanes":
        assert names == ["cache_alloc", "lanes_alloc", "scheduler_start",
                         "warmup", "app_start"]
    else:
        assert names == ["cache_alloc", "warmup", "app_start"]


def test_load_phases_stay_beside_the_timeline(served):
    assert served["handed"]["load_phases"].keys() == {"warmup_s"}
    warm = next(p for p in served["handed"]["startup"]["phases"]
                if p["name"] == "warmup")
    assert served["handed"]["load_phases"]["warmup_s"] == round(
        warm["seconds"], 1)
    assert warm["attrs"]["programs_compiled"] >= 0
    assert [c["name"] for c in warm["children"]] == STEPS["serial"]


def test_the_entry_point_names_what_the_process_pays_first(gguf):
    """``python -m llama_fastapi_k8s_gpu_tpu.server`` as a pod runs it:
    process start to READY without a hole."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT,
           "LFKT_MODEL_DIR": os.path.dirname(gguf),
           "LFKT_MODEL_NAME": os.path.basename(gguf),
           "LFKT_HOST": "127.0.0.1", "LFKT_PORT": str(port),
           "LFKT_MAX_CONTEXT_TOKENS": "128"}
    proc = subprocess.Popen([sys.executable, "-m",
                             "llama_fastapi_k8s_gpu_tpu.server"], cwd=ROOT,
                            env=env, stdout=subprocess.DEVNULL,
                            stderr=subprocess.DEVNULL)
    try:
        doc, deadline = None, time.time() + 240
        while doc is None and time.time() < deadline:
            assert proc.poll() is None, "the server exited"
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/health", timeout=5) as r:
                    doc = json.load(r)["engine"]["startup"]
            except OSError:
                time.sleep(0.25)
    finally:
        proc.terminate()
        proc.wait(timeout=60)
    assert doc is not None, "not READY in time"
    assert_sound(doc)
    names = [p["name"] for p in doc["phases"]]
    assert names[:5] == ["before_main", "imports", "backend_init",
                         "compile_cache", "engine_import"]
    assert names[-3:] == ["cache_alloc", "warmup", "app_start"]
    # the factory stamps its import of the engine package, nothing wider
    imp = doc["phases"][4]
    assert imp["start_s"] >= doc["phases"][3]["start_s"] \
        + doc["phases"][3]["seconds"]
    assert imp["start_s"] + imp["seconds"] <= doc["phases"][5]["start_s"] \
        + 0.002
    assert doc["process_start_from"] == "proc_stat"
    assert doc["phases"][2]["attrs"] == {"platform": "cpu"}
    assert doc["unnamed_s"] < 0.03 * doc["ready_s"]


# -- the six readers ---------------------------------------------------------

@pytest.fixture(autouse=True)
def benchmarks_on_the_path(monkeypatch):
    """The readers import their helpers (``startup_doc``) as ``run.py``
    lets them: from ``benchmarks/`` itself."""
    monkeypatch.syspath_prepend(os.path.join(ROOT, "benchmarks"))


def reader(name):
    path = os.path.join(ROOT, "benchmarks", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("layer_metric_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def canned(with_timeline: bool) -> dict:
    """The ``run`` dict as ``benchmarks/run.py`` hands it to the readers: a
    cached ``mistral`` start, or the parent's ``/health``."""
    engine = {"load_phases": {"tokenizer_s": 0.4, "probes_s": 4.1,
                              "params_s": 8.0, "warmup_s": 9.5}}
    if with_timeline:
        secs = {"before_main": 9.0, "imports": 1.5, "backend_init": 0.01,
                "compile_cache": 0.001, "engine_import": 2.0,
                "gguf_open": 0.1, "tokenizer": 0.4, "probes": 4.1,
                "params": 8.0, "attn_probes": 0.9, "cache_alloc": 0.5,
                "warmup": 9.5, "app_start": 0.01}
        phases, at = [], 0.0
        for name, s in secs.items():
            phases.append({"name": name, "start_s": round(at, 3),
                           "seconds": s})
            at += s
        # a start that loaded its executables (PR 55): every first call
        # of a signature is in compile_s, the store's part beside it
        phases[-2]["attrs"] = {"programs_compiled": 11, "compile_s": 5.25,
                               "programs_loaded": 5, "load_s": 4.4,
                               "programs_built": 0, "build_s": 0.0,
                               "load_failures": 0, "compiled_uncached": 92}
        engine["startup"] = {
            "process_start_unix": 1000.0, "ready_unix": 1000.0 + at + 0.5,
            "ready_s": round(at + 0.5, 3), "phases": phases,
            "unnamed_s": 0.5, "clock": "time.time"}
    return {"health": {"engine": engine}, "setup_s": 40.0, "ready_s": 37.5}


WANT = {"program_ready_s": 36.521, "runtime_start_s": 12.511, "probes_s": 4.1,
        "warmup_compile_s": 5.25, "setup_outside_ready_s": 2.5,
        "startup_named_share": 100.0 * (1 - 0.5 / 36.521)}


@pytest.mark.parametrize("name", READERS)
def test_a_reader_reads_a_number_off_the_timeline(name):
    got = reader(name)(canned(True))
    assert isinstance(got, float)
    assert got == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", READERS)
def test_a_reader_reads_nothing_off_the_parents_health(name):
    assert reader(name)(canned(False)) is None
    assert reader(name)({"health": {}, "setup_s": 40.0, "ready_s": 37.5}) \
        is None


@pytest.mark.parametrize("name", READERS)
def test_the_benchmark_declares_the_reader(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(m for m in json.load(f)["per_layer"]
                     if m["name"] == name)
    assert entry["layer"] == "load path (gguf/, native/, models/params.py)"
    assert entry["moves"] == "setup_s" and "workloads" not in entry
    assert entry["better"] == ("higher" if name == "startup_named_share"
                               else "lower")


def test_the_module_keeps_no_state():
    assert not [k for k, v in vars(startup).items()
                if isinstance(v, (Timeline, Phase, list))]
