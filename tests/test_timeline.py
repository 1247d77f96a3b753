"""One timeline (ISSUE 24): the request's time to first token as spans
that close, the lane scheduler's wave as counters, and the program's
phases inside a profiler capture.

1. **The span chain** — ``prefill``'s children ``tokenize``,
   ``prefill_slice`` (a span per slice) and ``first_token`` cover it
   without overlapping, in the lane engine and in the serial engine;
   ``first_token.deferred`` is true only when other lanes were live; the
   app stamps ``first_content`` on the ``stream`` span.
2. **The wave** — ``scheduler_stats()`` integrates the wave where it
   happens: live + idle lane-seconds is lanes x wave seconds, and
   ``admit_slices`` is the ``prefill_slice`` spans recorded.
   A request admitted beside live lanes rides the decode chunk of the pass
   that admits it (ISSUE 33): the round runs ahead of the chunk's dispatch,
   ``admit_chunks_behind`` stays 0, and its text is the one it has alone.
3. **Phases** — ``phase()`` is one shared no-op while the profiler cannot
   be armed (poisoned ``TraceAnnotation``) and emits ``lfkt.<name>`` with
   its attrs while it can (a recording annotation class: no profiler).
4. **The capture is the one asked for** — ``/debug/profile`` is not
   refused while an unstreamed generation runs; ``/health`` names the
   device.
"""

from __future__ import annotations

import asyncio
import threading

import httpx
import pytest

from llama_fastapi_k8s_gpu_tpu.engine import ContinuousEngine, Engine
from llama_fastapi_k8s_gpu_tpu.obs import trace as obs_trace
from llama_fastapi_k8s_gpu_tpu.obs.trace import Tracer, phase
from llama_fastapi_k8s_gpu_tpu.server.app import create_app
from llama_fastapi_k8s_gpu_tpu.testing import write_tiny_llama_gguf

MSGS = [{"role": "user", "content": "Say something."}]
LONG = [{"role": "user", "content": "one two three four five six " * 3}]
BODY = {
    "bot_profile": {"name": "Al", "appearance": "tall",
                    "system_prompt": "Be brief."},
    "user_profile": {"name": "Bob"},
    "context": [{"turn": "user", "message": "hi"}],
}
EPS = 1e-6
PREFILL_CHILDREN = ("tokenize", "prefill_slice", "first_token")


@pytest.fixture(scope="module")
def model_path(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("model") / "tiny.gguf")
    write_tiny_llama_gguf(path)
    return path


@pytest.fixture(scope="module")
def lanes(model_path):
    """Two lanes, 16-token slices: a LONG prompt admits in several."""
    eng = ContinuousEngine(model_path, batch_size=2, n_ctx=256,
                           decode_chunk=4, max_gen_tokens=64,
                           prefill_buckets=(32, 64, 128), prefill_chunk=16,
                           lane_prefix_cache=False)
    yield eng
    eng.shutdown()


@pytest.fixture(scope="module")
def serial(model_path):
    return Engine(model_path, n_ctx=256, decode_chunk=4, max_gen_tokens=64,
                  prefill_buckets=(32, 64, 128), prefix_cache=False,
                  prefill_chunk=16, prefill_overlap=2)


def _named(node: dict, name: str) -> list[dict]:
    out = [node] if node["name"] == name else []
    for c in node["children"]:
        out += _named(c, name)
    return out


def _traced(eng, msgs, tracer=None, **kw):
    """One traced, finished generation -> its trace document."""
    tracer = tracer or Tracer(sample=1.0, ring=8)
    tr = tracer.start()
    eng.create_chat_completion(msgs, temperature=0.0, trace=tr, **kw)
    tracer.finish(tr)
    return tr.to_dict()


def _assert_children_tile(prefill: dict) -> list[dict]:
    """``prefill``'s timeline children lie inside it, in order, without
    overlapping; what they leave uncovered is its self time (>= 0)."""
    kids = sorted((c for c in prefill["children"]
                   if c["name"] in PREFILL_CHILDREN),
                  key=lambda c: c["start"])
    assert [c["name"] for c in kids][0] == "tokenize"
    assert [c["name"] for c in kids][-1] == "first_token"
    at = prefill["start"] - EPS
    for c in kids:
        assert c["end"] is not None and "auto_closed" not in c["attrs"]
        assert c["start"] >= at, f"{c['name']} overlaps its elder sibling"
        at = c["end"] - EPS
    assert at <= prefill["end"] + EPS
    covered = sum(c["duration_s"] for c in kids)
    assert covered <= prefill["duration_s"] + 1e-4
    return kids


# ---------------------------------------------------------------------------
# 1. the span chain
# ---------------------------------------------------------------------------

def test_lane_request_span_chain(lanes):
    doc = _traced(lanes, LONG, max_tokens=6)
    root = doc["root"]
    pending, = _named(root, "pending")
    prefill, = _named(root, "prefill")
    assert pending["end"] <= prefill["start"] + EPS     # the chain's order
    kids = _assert_children_tile(prefill)
    tok = kids[0]
    assert tok["attrs"]["n_prompt"] == prefill["attrs"]["n_prompt"]
    slices = [c for c in kids if c["name"] == "prefill_slice"]
    assert len(slices) >= 2                             # multi-slice prompt
    assert [s["attrs"]["offset"] for s in slices] == \
        sorted(s["attrs"]["offset"] for s in slices)
    assert all(s["attrs"]["tokens"] == 16 and "wave" in s["attrs"]
               for s in slices)
    assert not [e for e in prefill["events"] if e["name"] == "prefill_slice"]
    first = kids[-1]
    # nothing else decoding: fetched at once, no wave passed
    assert first["attrs"] == {"deferred": False, "waves": 0}


def test_lane_first_token_deferred_only_beside_live_lanes(lanes):
    """A second request admitted while the first still decodes defers its
    first-token fetch to its lane's first harvest (``deferred`` true,
    ``waves`` >= 1); admitted into an idle engine it does not."""
    tracer = Tracer(sample=1.0, ring=8)
    tr_a, tr_b = tracer.start(), tracer.start()
    stream = lanes.create_chat_completion(
        MSGS, stream=True, temperature=0.0, max_tokens=60, trace=tr_a)
    next(stream)                       # A holds a lane and decodes
    lanes.create_chat_completion(MSGS, temperature=0.0, max_tokens=4,
                                 trace=tr_b)
    list(stream)
    tracer.finish(tr_a)
    tracer.finish(tr_b)
    a, b = tr_a.to_dict()["root"], tr_b.to_dict()["root"]
    first_a, = _named(a, "first_token")
    first_b, = _named(b, "first_token")
    assert first_a["attrs"]["deferred"] is False
    decode_a, = _named(a, "decode")
    beside = decode_a["start"] <= first_b["start"] <= decode_a["end"]
    assert first_b["attrs"]["deferred"] is beside
    assert (first_b["attrs"]["waves"] >= 1) is beside
    _assert_children_tile(_named(b, "prefill")[0])


def test_serial_request_span_chain(serial):
    doc = _traced(serial, LONG, max_tokens=6)
    prefill, = _named(doc["root"], "prefill")
    kids = _assert_children_tile(prefill)
    assert kids[0]["attrs"]["n_prompt"] == prefill["attrs"]["n_prompt"]
    assert kids[0]["start"] == prefill["start"]         # t0 precedes tokenize
    slices = [c for c in kids if c["name"] == "prefill_slice"]
    assert len(slices) >= 2
    assert kids[-1]["attrs"] == {"deferred": False, "waves": 0}


def test_serial_one_program_prompt_is_one_slice(model_path):
    """A prompt at or under the slice size runs as one program: one
    ``prefill_slice`` span of the whole bucket, so the chain still closes."""
    eng = Engine(model_path, n_ctx=128, decode_chunk=4, max_gen_tokens=16,
                 prefill_buckets=(32, 64, 128), prefix_cache=False)
    doc = _traced(eng, MSGS, max_tokens=4)
    prefill, = _named(doc["root"], "prefill")
    kids = _assert_children_tile(prefill)
    one, = [c for c in kids if c["name"] == "prefill_slice"]
    # (device_s / done_at: its program's device interval, tests/test_devtime.py)
    assert {k: one["attrs"][k] for k in ("offset", "tokens")} == \
        {"offset": 0, "tokens": prefill["attrs"]["bucket"]}
    assert set(one["attrs"]) <= {"offset", "tokens", "device_s", "done_at"}


@pytest.mark.anyio
@pytest.mark.parametrize("route", ["/response/stream", "/v1/chat/completions"])
async def test_first_content_event_on_the_stream_span(serial, route):
    tracer = Tracer(sample=1.0, ring=8)
    app = create_app(engine=serial, tracer=tracer)
    body = BODY if route == "/response/stream" else {
        "messages": MSGS, "stream": True, "max_tokens": 12,
        "temperature": 0.0}
    transport = httpx.ASGITransport(app=app)
    async with transport:
        await app.router.startup()
        async with httpx.AsyncClient(transport=transport,
                                     base_url="http://test") as client:
            r = await client.post(route, json=body)
            assert r.status_code == 200 and "[DONE]" in r.text
            rid = r.headers["x-request-id"]
            doc = (await client.get(f"/debug/traces/{rid}")).json()
        await app.router.shutdown()
    stream, = _named(doc["root"], "stream")
    marks = [e for e in stream["events"] if e["name"] == "first_content"]
    assert len(marks) == 1                   # the first content chunk only
    prefill, = _named(doc["root"], "prefill")
    assert prefill["end"] - EPS <= marks[0]["at"] <= stream["end"] + EPS


# ---------------------------------------------------------------------------
# 2. the wave, counted where it happens
# ---------------------------------------------------------------------------

def test_wave_counters_close(model_path):
    """On a fresh engine: live + idle lane-seconds == lanes x wave seconds;
    every slice dispatched is a ``prefill_slice`` span of some request;
    decode chunks name their wave and the slices queued ahead of them."""
    eng = ContinuousEngine(model_path, batch_size=2, n_ctx=256,
                           decode_chunk=4, max_gen_tokens=64,
                           prefill_buckets=(32, 64, 128), prefill_chunk=16,
                           lane_prefix_cache=False)
    try:
        tracer = Tracer(sample=1.0, ring=16)
        traces = [tracer.start() for _ in range(5)]
        futs = [eng.submit(LONG if i % 2 else MSGS, temperature=0.0,
                           max_tokens=6 + 3 * i, trace=tr)
                for i, tr in enumerate(traces)]
        for f in futs:
            f.result(timeout=120)
        for tr in traces:
            tracer.finish(tr)
        stats = None
        for _ in range(100):            # the loop's last stats swap
            stats = eng.scheduler_stats()
            if stats["lanes_live"] == 0 and stats["waves"]:
                break
            threading.Event().wait(0.02)
    finally:
        eng.shutdown()
    B = stats["batch_size"]
    assert stats["waves"] >= 2
    assert stats["waves"] <= stats["chunks_dispatched"] <= stats["waves"] + 1
    # lane_idle_seconds is exported rounded to the millisecond
    assert stats["lane_live_seconds"] + stats["lane_idle_seconds"] == \
        pytest.approx(B * stats["wave_seconds"], abs=1e-3)
    assert 0.0 <= stats["fetch_wait_seconds"] <= stats["wave_seconds"]
    assert 0.0 < stats["admit_seconds"] and 0.0 < stats["harvest_seconds"]
    docs = [tr.to_dict()["root"] for tr in traces]
    slices = [s for d in docs for s in _named(d, "prefill_slice")]
    assert stats["admit_slices"] == len(slices)
    assert stats["admit_tokens"] == sum(s["attrs"]["tokens"] for s in slices)
    chunks = [c for d in docs for c in _named(d, "decode_chunk")]
    assert chunks
    for d in docs:
        waves = [c["attrs"]["wave"] for c in _named(d, "decode_chunk")]
        assert waves == sorted(waves) and len(set(waves)) == len(waves)
    assert all(c["attrs"]["admit_slices"] >= 0 for c in chunks)
    # slices queued ahead of some chunk: never more than were dispatched
    by_wave = {c["attrs"]["wave"]: c["attrs"]["admit_slices"] for c in chunks}
    assert sum(by_wave.values()) <= stats["admit_slices"]


@pytest.mark.anyio
async def test_wave_counters_are_scheduler_gauges(lanes):
    """``scheduler_stats()``'s keys reach ``/metrics`` as ``scheduler_<key>``
    by the path that was there; the catalog says which are cumulative."""
    from llama_fastapi_k8s_gpu_tpu.obs.catalog import METRICS

    app = create_app(engine=lanes)
    transport = httpx.ASGITransport(app=app)
    async with transport:
        await app.router.startup()
        async with httpx.AsyncClient(transport=transport,
                                     base_url="http://test") as client:
            r = await client.post("/response", json=BODY)
            assert r.status_code == 200
            text = (await client.get("/metrics")).text
        await app.router.shutdown()
    for key in ("waves", "wave_seconds", "lane_live_seconds",
                "lane_idle_seconds", "fetch_wait_seconds", "admit_seconds",
                "admit_slices", "admit_tokens", "harvest_seconds",
                "chunks_dispatched"):
        assert f"\nscheduler_{key} " in text, key
    family = METRICS["scheduler_"]
    assert "cumulative" in family.help.lower()
    assert "wave_seconds" in family.help


# -- an admitted request rides the chunk of the pass that admits it --------

RIDE_SYS = ("You are a meticulous assistant who answers carefully. " * 3).strip()
RIDE_VARIANTS = {
    "plain": dict(lane_prefix_cache=False),
    "lane_reuse": dict(lane_prefix_cache=True),
    "paged": dict(kv_paged=True, kv_page_tokens=16, kv_pool_pages=64,
                  kv_spill_pages=16, prefix_min=16),
}


class _NeverStop:
    """The engine's tokenizer without its stop ids: the tiny model's greedy
    text ends after some 16 tokens, and the neighbour lane has to decode to
    its budget so that it is live for as long as the test admits beside it.
    Both engines of a comparison wear it, so the texts compared are cut by
    ``max_tokens`` alone, alike."""

    def __init__(self, inner):
        self._inner = inner

    def __getattr__(self, name):
        return getattr(self._inner, name)

    @property
    def stop_ids(self):
        return set()


def _ride_engine(model_path, variant):
    eng = ContinuousEngine(model_path, batch_size=2, n_ctx=512,
                           decode_chunk=4, max_gen_tokens=440,
                           prefill_buckets=(64, 128, 256, 512),
                           prefill_chunk=16, **RIDE_VARIANTS[variant])
    eng.tokenizer = _NeverStop(eng.tokenizer)
    return eng


def _ride_turns(reply=None):
    msgs = [{"role": "system", "content": RIDE_SYS},
            {"role": "user", "content": "Tell me something interesting."}]
    if reply is not None:
        msgs += [{"role": "assistant", "content": reply},
                 {"role": "user", "content": "And another one please."}]
    return msgs


def _two_turns(eng, tracer=None):
    """The conversation's two turns, one after the other -> (results,
    trace documents); the second turn re-sends the first's reply."""
    outs, docs, reply = [], [], None
    for _ in range(2):
        tr = tracer.start() if tracer is not None else None
        out = eng.create_chat_completion(_ride_turns(reply), temperature=0.0,
                                         max_tokens=8, trace=tr)
        reply = out["choices"][0]["message"]["content"]
        outs.append(out)
        if tr is not None:
            tracer.finish(tr)
            docs.append(tr.to_dict()["root"])
    return outs, docs


@pytest.mark.parametrize("variant", list(RIDE_VARIANTS))
def test_admitted_beside_a_live_lane_rides_the_chunk_of_its_pass(
        model_path, variant):
    """With one lane decoding, a request admitted beside it is live in the
    decode chunk dispatched by the pass that finished its admission: the
    round runs AHEAD of the chunk (``admit_chunks_behind`` 0 over the
    admissions beside live lanes), the first ``decode_chunk.wave`` of its
    ``decode`` span is the ``wave`` of its last ``prefill_slice`` (a slice
    carries the number of the chunk it is queued ahead of), that chunk's
    ``admit_slices`` counts the slices that carry its number, and the
    greedy texts are those of the same two turns on an engine that serves
    nothing else.  Several-slice prompts; the second turn reuses the
    first's KV where the variant has a prefix cache (lane claims, paged
    pool)."""
    alone = _ride_engine(model_path, variant)
    try:
        want, _ = _two_turns(alone)
    finally:
        alone.shutdown()
    eng = _ride_engine(model_path, variant)
    try:
        tracer = Tracer(sample=1.0, ring=8)
        tr_n = tracer.start()
        neighbour = eng.create_chat_completion(
            MSGS, stream=True, temperature=0.0, max_tokens=440, trace=tr_n)
        next(neighbour)                 # holds a lane and decodes to budget
        got, docs = _two_turns(eng, tracer)
        stats = eng.scheduler_stats()
        assert stats["lanes_live"] >= 1     # the neighbour outlived both
        neighbour.close()
    finally:
        eng.shutdown()
    tracer.finish(tr_n)
    numbered = [s["attrs"]["wave"] for d in docs + [tr_n.to_dict()["root"]]
                for s in _named(d, "prefill_slice")]
    assert [o["choices"][0]["message"]["content"] for o in got] == \
        [o["choices"][0]["message"]["content"] for o in want]
    assert [o["usage"] for o in got] == [o["usage"] for o in want]
    reused = [o["lfkt_timings"]["prefix_reused_tokens"] for o in got]
    assert reused == [o["lfkt_timings"]["prefix_reused_tokens"] for o in want]
    assert reused[0] == 0 and (reused[1] > 0) is (variant != "plain")
    assert stats["admits_beside_live"] == 2
    assert stats["admit_chunks_behind"] == 0
    for doc in docs:
        first, = _named(doc, "first_token")
        assert first["attrs"]["deferred"] is True
        slices = _named(doc, "prefill_slice")
        chunks = _named(doc, "decode_chunk")
        assert doc is not docs[0] or len(slices) >= 2
        wave = chunks[0]["attrs"]["wave"]
        assert slices[-1]["attrs"]["wave"] == wave
        # every slice queued since the chunk before carries this chunk's
        # number (the neighbour's too, where both were admitted into the
        # engine's first pass)
        assert chunks[0]["attrs"]["admit_slices"] == numbered.count(wave)


def test_chunks_behind_reads_one_in_the_order_before(model_path):
    """What the counter measures, shown on the order the loop had before
    ISSUE 33 (chunk first, admission round after), rebuilt here by moving
    the round behind the chunk's dispatch: every admission beside a live
    lane then sits behind one chunk that left in its own pass without its
    lane."""
    eng = _ride_engine(model_path, "plain")
    try:
        seen = {}
        admit_round, take = eng._admit_round, eng._take_expert_stats

        def later(slots):               # the loop's call: ahead of the chunk
            seen["slots"] = slots
            return False

        def after_dispatch(out):        # ... moved to just behind it
            toks = take(out)
            admit_round(seen["slots"])
            return toks

        eng._admit_round, eng._take_expert_stats = later, after_dispatch
        neighbour = eng.create_chat_completion(
            MSGS, stream=True, temperature=0.0, max_tokens=440)
        next(neighbour)
        outs, _ = _two_turns(eng)
        stats = eng.scheduler_stats()
        neighbour.close()
    finally:
        eng.shutdown()
    assert all(o["usage"]["completion_tokens"] == 8 for o in outs)
    assert stats["admits_beside_live"] == 2
    assert stats["admit_chunks_behind"] == 2


# ---------------------------------------------------------------------------
# 3. phases
# ---------------------------------------------------------------------------

class _Recorder:
    """Stands in for ``jax.profiler.TraceAnnotation``: records what a real
    capture would hold, with the nesting depth at entry."""

    seen: list = []
    depth = threading.local()

    def __init__(self, name, **attrs):
        self.name, self.attrs = name, attrs

    def __enter__(self):
        d = getattr(self.depth, "n", 0)
        type(self).seen.append((self.name, self.attrs, d))
        self.depth.n = d + 1
        return self

    def __exit__(self, *exc):
        self.depth.n -= 1
        return False


@pytest.fixture
def recorder(monkeypatch):
    _Recorder.seen = []
    monkeypatch.setattr(obs_trace, "_ANNOTATION", _Recorder)
    return _Recorder


def test_phase_off_is_the_shared_noop(monkeypatch, serial):
    """LFKT_PROFILE_DIR unset and the tracer off: one shared object, and no
    annotation is ever built — pinned by poisoning the class and serving a
    whole request.  With the tracer armed a phase that names a request
    keeps its rid on the thread and still builds no annotation."""
    import jax.profiler

    monkeypatch.delenv("LFKT_PROFILE_DIR", raising=False)
    monkeypatch.setattr(obs_trace, "_KEEP_RID", False)   # restored after
    assert obs_trace.arm_phases(tracing=False) is False

    class Poisoned:
        def __init__(self, *a, **kw):
            raise AssertionError("TraceAnnotation built while disarmed")

    # the name the program would import (JAX's own uses are jax._src's)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Poisoned)
    a, b = phase("wave", wave=1, lanes_live=2), phase("tokenize", rid="x")
    assert a is b is obs_trace._NO_PHASE
    with a:
        pass
    out = serial.create_chat_completion(LONG, temperature=0.0, max_tokens=6)
    assert out["usage"]["completion_tokens"] >= 1
    assert obs_trace.arm_phases(tracing=True) is False
    assert phase("wave", wave=1, lanes_live=2) is obs_trace._NO_PHASE
    with phase("tokenize", rid="x"):
        assert obs_trace.open_rid() == "x"
        with phase("fetch", wave=3):            # names no request: keeps it
            assert obs_trace.open_rid() == "x"
        with phase("admit_slice", rid="y", offset=0, tokens=16):
            assert obs_trace.open_rid() == "y"
        assert obs_trace.open_rid() == "x"
    assert obs_trace.open_rid() == ""
    out = serial.create_chat_completion(LONG, temperature=0.0, max_tokens=6)
    assert out["usage"]["completion_tokens"] >= 1


def test_arm_phases_follows_the_profile_knob(monkeypatch, tmp_path):
    import jax.profiler

    try:
        monkeypatch.setenv("LFKT_PROFILE_DIR", str(tmp_path))
        assert obs_trace.arm_phases() is True
        assert obs_trace._ANNOTATION is jax.profiler.TraceAnnotation
        with phase("tokenize", rid="abc"):      # outside a capture: harmless
            pass
        monkeypatch.delenv("LFKT_PROFILE_DIR")
        assert obs_trace.arm_phases() is False
        assert phase("tokenize") is obs_trace._NO_PHASE
    finally:
        obs_trace._ANNOTATION = None


def test_phase_on_emits_the_lane_loop(recorder, lanes):
    doc = _traced(lanes, LONG, max_tokens=10)
    seen = {}
    for name, attrs, depth in recorder.seen:
        seen.setdefault(name, []).append((attrs, depth))
    for name in ("lfkt.wave", "lfkt.dispatch_chunk", "lfkt.admit_slice",
                 "lfkt.fetch", "lfkt.harvest", "lfkt.tokenize"):
        assert name in seen, sorted(seen)
    wave_attrs, depth = seen["lfkt.wave"][0]
    assert depth == 0 and set(wave_attrs) == {"wave", "lanes_live"}
    assert wave_attrs["wave"] >= 1 and 0 <= wave_attrs["lanes_live"] <= 2
    # the wave's parts nest inside it; waves number on from each other
    assert all(d == 1 for _, d in seen["lfkt.dispatch_chunk"])
    assert all(d == 1 for _, d in seen["lfkt.fetch"])
    waves = [a["wave"] for a, _ in seen["lfkt.dispatch_chunk"]]
    assert waves == list(range(waves[0], waves[0] + len(waves)))
    # the request's trace id rides as rid
    slices = [a for a, _ in seen["lfkt.admit_slice"]]
    assert {a["rid"] for a in slices} == {doc["trace_id"]}
    assert all(a["tokens"] == 16 for a in slices)
    assert seen["lfkt.tokenize"][0][0]["rid"] == doc["trace_id"]


def test_phase_order_puts_the_admission_round_ahead_of_the_chunk(
        monkeypatch, model_path):
    """Inside one ``lfkt.wave`` the admission slices go out before the
    decode chunk (ISSUE 33), and the wave then fetches the chunk before."""
    eng = _ride_engine(model_path, "plain")
    _Recorder.seen = []         # armed after the engine: building one disarms
    monkeypatch.setattr(obs_trace, "_ANNOTATION", _Recorder)
    try:
        neighbour = eng.create_chat_completion(
            MSGS, stream=True, temperature=0.0, max_tokens=440)
        next(neighbour)                 # a lane decodes: waves run
        eng.create_chat_completion(LONG, temperature=0.0, max_tokens=4)
        neighbour.close()
    finally:
        eng.shutdown()
    waves, inside = [], None
    for name, _, depth in _Recorder.seen:
        if name == "lfkt.wave":
            inside = []
            waves.append(inside)
        elif depth == 1 and inside is not None and name in (
                "lfkt.admit_slice", "lfkt.dispatch_chunk", "lfkt.fetch"):
            inside.append(name.removeprefix("lfkt."))
    mixed = [w for w in waves if "admit_slice" in w and "dispatch_chunk" in w]
    assert mixed, waves
    for w in mixed:
        assert w.index("dispatch_chunk") > max(
            i for i, n in enumerate(w) if n == "admit_slice"), w
        assert "fetch" not in w or w.index("fetch") > w.index("dispatch_chunk")


def test_phase_on_emits_the_serial_engine(recorder, serial):
    doc = _traced(serial, LONG, max_tokens=10)
    mine = ("lfkt.tokenize", "lfkt.prefill_slice", "lfkt.decode_chunk",
            "lfkt.emit")      # (another test's lane loop may still drain)
    seen = [(n, a) for n, a, _ in recorder.seen if n in mine]
    names = [n for n, _ in seen]
    assert set(names) == set(mine), sorted(set(names))
    assert names.index("lfkt.tokenize") < names.index("lfkt.prefill_slice") \
        < names.index("lfkt.decode_chunk")
    assert all(a.get("rid") == doc["trace_id"] for _, a in seen)
    # per slice and per chunk, never per token
    n_chunks = len(_named(doc["root"], "decode_chunk"))
    assert names.count("lfkt.decode_chunk") == n_chunks
    assert names.count("lfkt.prefill_slice") == \
        len(_named(doc["root"], "prefill_slice"))


# ---------------------------------------------------------------------------
# 4. the capture is the one asked for; /health names the device
# ---------------------------------------------------------------------------

@pytest.mark.anyio
async def test_debug_profile_not_refused_during_unstreamed_generation(
        monkeypatch, tmp_path, serial):
    """``LFKT_PROFILE_DIR`` arms ``/debug/profile`` and nothing else: an
    unstreamed generation holds no profile of its own (``maybe_profile``
    is gone), so the operator's capture starts while it runs."""
    monkeypatch.setenv("LFKT_PROFILE_DIR", str(tmp_path / "xprof"))
    app = create_app(engine=serial)
    running, captured = threading.Event(), threading.Event()
    real = serial._generate_locked

    def slow(*a, **kw):
        running.set()
        captured.wait(30)               # the generation outlasts the capture
        return real(*a, **kw)

    monkeypatch.setattr(serial, "_generate_locked", slow)
    transport = httpx.ASGITransport(app=app)
    async with transport:
        await app.router.startup()
        async with httpx.AsyncClient(transport=transport,
                                     base_url="http://test") as client:
            gen = asyncio.create_task(client.post("/response", json=BODY))
            while not running.is_set():
                await asyncio.sleep(0.01)
            prof = await client.get("/debug/profile?seconds=0.05")
            assert not gen.done()       # the capture ran beside it
            captured.set()
            r = await gen
        await app.router.shutdown()
    assert r.status_code == 200
    assert prof.status_code == 200 and prof.json()["ok"] is True


@pytest.mark.anyio
async def test_health_names_the_device(serial):
    import jax

    app = create_app(engine=serial)
    transport = httpx.ASGITransport(app=app)
    async with transport:
        await app.router.startup()
        async with httpx.AsyncClient(transport=transport,
                                     base_url="http://test") as client:
            eng = (await client.get("/health")).json()["engine"]
        await app.router.shutdown()
    dev = jax.local_devices()[0]
    assert eng["platform"] == dev.platform
    assert eng["device_kind"] == dev.device_kind
    assert eng["device_count"] == len(jax.local_devices())
    assert "peak_bytes_in_use" in eng   # None where the backend keeps none
