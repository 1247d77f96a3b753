"""Multi-model, multi-tenant serving (ISSUE 9; docs/MULTIMODEL.md).

Two tiny random-weight GGUFs (same geometry, different seeds — so their
KV for identical token ids DIFFERS, making cross-namespace leakage
observable) drive the registry through every acceptance surface:

- manifest grammar + weight-budget refusal (serving/manifest.py,
  serving/registry.py);
- bit-identical greedy parity per model vs single-model baselines, on
  the serial engine and the continuous scheduler;
- a SHARED paged KV pool with per-model radix namespaces: cross-model
  page occupancy, zero phantom prefix hits across tenants;
- the OpenAI-compatible facade (/v1/models, /v1/chat/completions
  streaming + non-streaming + usage counts) through the real server,
  with /response + /health single-model behavior untouched.
"""

from __future__ import annotations

import asyncio
import json

import httpx
import pytest

from llama_fastapi_k8s_gpu_tpu.engine import ContinuousEngine, Engine, FakeEngine
from llama_fastapi_k8s_gpu_tpu.serving import (
    ModelRegistry,
    ModelSpec,
    UnknownModelError,
    WeightBudgetError,
    parse_manifest,
    pick_default,
)
from llama_fastapi_k8s_gpu_tpu.server.app import create_app
from llama_fastapi_k8s_gpu_tpu.testing import write_tiny_llama_gguf
from llama_fastapi_k8s_gpu_tpu.utils.config import Settings

MSGS = [{"role": "user", "content": "hello there"}]
MSGS2 = [{"role": "user", "content": "something else"}]


@pytest.fixture(scope="module")
def ggufs(tmp_path_factory):
    d = tmp_path_factory.mktemp("mm")
    pa, pb = str(d / "a.gguf"), str(d / "b.gguf")
    write_tiny_llama_gguf(pa, seed=0)
    write_tiny_llama_gguf(pb, seed=7)
    return pa, pb


def _serial(path, **kw):
    return Engine(path, n_ctx=128, prefill_buckets=(32,), **kw)


def _greedy(engine, messages=MSGS, n=8, **kw):
    out = engine.create_chat_completion(messages, max_tokens=n,
                                        temperature=0.0, **kw)
    return out["choices"][0]["message"]["content"], out


# ---------------------------------------------------------------------------
# manifest grammar
# ---------------------------------------------------------------------------

def test_manifest_grammar_roundtrip():
    specs = parse_manifest(
        "llama=models/a.gguf:n_ctx=2048;kv_dtype=int8, mistral=/abs/b.gguf")
    assert specs == [
        ModelSpec("llama", "models/a.gguf",
                  {"n_ctx": 2048, "kv_dtype": "int8"}),
        ModelSpec("mistral", "/abs/b.gguf", {}),
    ]
    assert pick_default(specs) == "llama"
    assert pick_default(specs, "mistral") == "mistral"
    assert specs[1].resolved_path("models") == "/abs/b.gguf"
    assert specs[0].resolved_path("md") == "md/models/a.gguf" or \
        specs[0].resolved_path("md").endswith("a.gguf")


@pytest.mark.parametrize("bad", [
    "noequals",                      # no path
    "a=x.gguf:bogus=1",              # unknown override key
    "a=x.gguf:spec_decode=lookup",   # overrides of what PR 32 removed
    "a=x.gguf:spec_draft=4",
    "a=x.gguf:decode_layer_unroll=4",
    "a=x.gguf:n_ctx=abc",            # uncastable override
    "a=x.gguf,a=y.gguf",             # duplicate alias
    "bad name=x.gguf",               # illegal alias chars
    "a=",                            # empty path
    " , ",                           # nothing at all
])
def test_manifest_grammar_rejects(bad):
    with pytest.raises(ValueError) as ei:
        parse_manifest(bad)
    assert "LFKT_MODELS" in str(ei.value)
    # an override's refusal names it
    assert bad.partition(":")[2].partition("=")[0] in str(ei.value)


def test_default_model_must_be_in_manifest():
    specs = parse_manifest("a=x.gguf")
    with pytest.raises(ValueError):
        pick_default(specs, "zzz")


# ---------------------------------------------------------------------------
# weight budget
# ---------------------------------------------------------------------------

def test_weight_budget_refusal_names_the_offender(ggufs):
    pa, pb = ggufs
    specs = [ModelSpec("alpha", pa), ModelSpec("beta", pb)]
    one_model = _serial(pa)
    per_model = one_model.weight_bytes
    assert per_model > 0

    def build(spec, path, shared_pool):
        return _serial(path)

    # budget fits exactly one model: loading the second must refuse with
    # per-model attribution, not OOM at first traffic
    with pytest.raises(WeightBudgetError) as ei:
        ModelRegistry.from_specs(
            specs, build, default_model="alpha",
            weight_budget_bytes=int(per_model * 1.5))
    msg = str(ei.value)
    assert "beta" in msg and "alpha" in msg and "LFKT_HBM_WEIGHT_BUDGET_MB" in msg

    # a budget that fits the set loads it
    reg = ModelRegistry.from_specs(
        specs, build, default_model="alpha",
        weight_budget_bytes=int(per_model * 3))
    rows = reg.models()
    assert [r["name"] for r in rows] == ["alpha", "beta"]
    assert all(r["weight_bytes"] == per_model for r in rows)
    assert all(r["state"] == "ready" for r in rows)


# ---------------------------------------------------------------------------
# routing + serial greedy parity
# ---------------------------------------------------------------------------

def test_serial_registry_parity_and_routing(ggufs):
    pa, pb = ggufs
    base_a, _ = _greedy(_serial(pa))
    base_b, _ = _greedy(_serial(pb))
    assert base_a != base_b          # different weights actually differ

    reg = ModelRegistry({"alpha": _serial(pa), "beta": _serial(pb)}, "alpha")
    got_a, out_a = _greedy(reg, model="alpha")
    got_b, out_b = _greedy(reg, model="beta")
    got_default, _ = _greedy(reg)    # no model= -> default alias
    assert got_a == base_a           # bit-identical greedy per model
    assert got_b == base_b
    assert got_default == base_a
    # responses echo the manifest alias, not the GGUF's embedded name
    assert out_a["model"] == "alpha" and out_b["model"] == "beta"
    assert out_a["lfkt_timings"]["model"] == "alpha"

    with pytest.raises(UnknownModelError):
        reg.resolve("gamma")


# ---------------------------------------------------------------------------
# shared paged pool: cross-model occupancy, zero cross-namespace hits
# ---------------------------------------------------------------------------

def test_shared_pool_namespace_isolation(ggufs):
    pa, pb = ggufs
    specs = [ModelSpec("alpha", pa), ModelSpec("beta", pb)]

    def build(spec, path, shared_pool):
        return _serial(path, kv_paged=True, kv_page_tokens=8,
                       kv_pool_pages=32, prefix_cache=True, prefix_min=8,
                       kv_pool=shared_pool, kv_namespace=spec.name)

    reg = ModelRegistry.from_specs(specs, build, default_model="alpha")
    ea, eb = reg.resolve("alpha"), reg.resolve("beta")
    pool = ea._kvpool
    assert pool is eb._kvpool        # ONE arena shared by both models

    # a long-ish prompt so the whole-page prefix is committable
    msgs = [{"role": "user", "content": "the quick brown fox jumps over"}]
    first_a, _ = _greedy(ea, msgs, n=6)
    occ_after_a = pool.occupancy()
    assert occ_after_a["pages_used"] > 0
    ids = ea.tokenize_messages(msgs)

    # beta sees NOTHING of alpha's identical token prefix (namespace
    # isolation: its KV for the same ids would be wrong)
    assert pool.match_len(ids, namespace="beta") == 0
    assert pool.match_len(ids, namespace="alpha") > 0
    hits_before = pool.stats()["hits"]
    first_b, _ = _greedy(eb, msgs, n=6)
    assert pool.stats()["hits"] == hits_before   # no phantom cross-hit

    # cross-model page occupancy: both models' pages resident in one arena
    occ_after_b = pool.occupancy()
    assert occ_after_b["pages_used"] > occ_after_a["pages_used"]
    assert occ_after_b["namespaces"] == 2

    # alpha's re-run takes a radix hit and stays bit-identical
    again_a, out = _greedy(ea, msgs, n=6)
    assert again_a == first_a
    assert pool.stats()["hits"] > hits_before
    assert out["lfkt_timings"]["prefix_reused_tokens"] > 0

    # and beta's generation was untouched by alpha's cache
    base_b, _ = _greedy(_serial(pb), msgs, n=6)
    assert first_b == base_b


def test_incompatible_geometry_degrades_to_private_pool(ggufs):
    pa, _ = ggufs
    ea = _serial(pa, kv_paged=True, kv_page_tokens=8, kv_pool_pages=16)
    # int8 KV has a different page layout: sharing must degrade (private
    # pool + attribution), never serve wrong bytes
    eb = _serial(pa, kv_paged=True, kv_page_tokens=8, kv_pool_pages=16,
                 kv_dtype="int8", kv_pool=ea._kvpool, kv_namespace="b")
    assert eb._kvpool is not ea._kvpool

    # the merged occupancy over split pools sums only the additive
    # fields; page geometry is listed per pool, never summed
    reg = ModelRegistry({"alpha": ea, "beta": eb}, "alpha")
    occ = reg.kv_pool_occupancy()
    assert occ["pools"] == 2
    assert occ["pages_total"] == 32                  # additive: 16 + 16
    assert "page_tokens" not in occ                  # non-additive
    assert [p["page_tokens"] for p in occ["per_pool"]] == [8, 8]
    assert all("page_bytes" in p for p in occ["per_pool"])


def test_registry_factory_mirrors_single_model_semantics(ggufs):
    """A 1-entry LFKT_MODELS manifest must keep the single-model
    factory's serving shape: the lane engine for LFKT_BATCH_SIZE > 1, the
    serial engine for 1 (one choice for both factories: server/app.py
    ``_build_engine``)."""
    from llama_fastapi_k8s_gpu_tpu.server.app import _registry_factory

    pa, _ = ggufs
    reg = _registry_factory(Settings(
        models=f"solo={pa}", batch_size=2,
        max_context_tokens=128, prefill_buckets="32"))
    try:
        assert type(reg.resolve(None)).__name__ == "ContinuousEngine"
        assert reg.resolve(None).batch_size == 2
        assert reg.model_names() == ["solo"]
    finally:
        reg.resolve(None).shutdown()

    reg = _registry_factory(Settings(
        models=f"solo={pa}", batch_size=1,
        max_context_tokens=128, prefill_buckets="32"))
    assert type(reg.resolve(None)).__name__ == "Engine"


# ---------------------------------------------------------------------------
# continuous scheduler: interleaved multi-model lanes, greedy parity
# ---------------------------------------------------------------------------

def _continuous(path, **kw):
    return ContinuousEngine(path, n_ctx=128, prefill_buckets=(32,),
                            batch_size=2, prefill_chunk=16, **kw)


def test_continuous_registry_interleaves_models(ggufs):
    pa, pb = ggufs
    single_a = _continuous(pa)
    single_b = _continuous(pb)
    try:
        base_a = single_a.submit(MSGS, max_tokens=8,
                                 temperature=0.0).result(timeout=120)
        base_b = single_b.submit(MSGS2, max_tokens=8,
                                 temperature=0.0).result(timeout=120)
    finally:
        single_a.shutdown()
        single_b.shutdown()

    reg = ModelRegistry({"alpha": _continuous(pa),
                         "beta": _continuous(pb)}, "alpha")
    try:
        # both models' lanes in flight concurrently from one process:
        # the schedulers interleave their waves on the device queue
        futs = [
            reg.submit(MSGS, max_tokens=8, temperature=0.0, model="alpha"),
            reg.submit(MSGS2, max_tokens=8, temperature=0.0, model="beta"),
            reg.submit(MSGS, max_tokens=8, temperature=0.0, model="alpha"),
            reg.submit(MSGS2, max_tokens=8, temperature=0.0, model="beta"),
        ]
        outs = [f.result(timeout=240) for f in futs]
        want_a = base_a["choices"][0]["message"]["content"]
        want_b = base_b["choices"][0]["message"]["content"]
        assert outs[0]["choices"][0]["message"]["content"] == want_a
        assert outs[2]["choices"][0]["message"]["content"] == want_a
        assert outs[1]["choices"][0]["message"]["content"] == want_b
        assert outs[3]["choices"][0]["message"]["content"] == want_b
        assert outs[0]["model"] == "alpha" and outs[1]["model"] == "beta"

        # merged scheduler stats: per-model keys + the fleet-level HPA
        # gauges (admission budget, idle lane-seconds)
        stats = reg.scheduler_stats()
        assert stats["models"] == 2
        assert "alpha_lanes_live" in stats and "beta_lanes_live" in stats
        assert "adm_budget_tokens" in stats and "lane_idle_seconds" in stats
    finally:
        reg.shutdown()


# ---------------------------------------------------------------------------
# the OpenAI facade through the real server
# ---------------------------------------------------------------------------

def _client(engine, **settings_kw):
    app = create_app(engine=engine, settings=Settings(**settings_kw))
    return app, httpx.ASGITransport(app=app)


@pytest.fixture(scope="module")
def served_registry(ggufs):
    pa, pb = ggufs
    return ModelRegistry({"alpha": _serial(pa), "beta": _serial(pb)},
                         "alpha")


@pytest.mark.anyio
async def test_v1_models_lists_manifest(served_registry):
    app, transport = _client(served_registry)
    async with transport:
        await app.router.startup()
        async with httpx.AsyncClient(transport=transport,
                                     base_url="http://t") as c:
            r = await c.get("/v1/models")
            assert r.status_code == 200
            doc = r.json()
            assert doc["object"] == "list"
            assert [m["id"] for m in doc["data"]] == ["alpha", "beta"]
            assert all(m["object"] == "model" for m in doc["data"])
        await app.router.shutdown()


@pytest.mark.anyio
async def test_v1_chat_completion_non_streaming_usage(served_registry):
    app, transport = _client(served_registry)
    async with transport:
        await app.router.startup()
        async with httpx.AsyncClient(transport=transport,
                                     base_url="http://t") as c:
            r = await c.post("/v1/chat/completions", json={
                "model": "beta", "max_tokens": 6, "temperature": 0.0,
                "messages": [{"role": "user", "content": "hi"}]})
            assert r.status_code == 200
            doc = r.json()
            assert doc["object"] == "chat.completion"
            assert doc["model"] == "beta"
            assert "lfkt_timings" not in doc
            u = doc["usage"]
            # usage counts come from the engine's own tokenize/decode
            assert u["prompt_tokens"] > 0
            assert 1 <= u["completion_tokens"] <= 6
            assert u["total_tokens"] == u["prompt_tokens"] + u["completion_tokens"]
            assert doc["choices"][0]["message"]["role"] == "assistant"
            assert doc["choices"][0]["finish_reason"] in ("stop", "length")
        await app.router.shutdown()


@pytest.mark.anyio
async def test_v1_chat_completion_streaming_schema(served_registry):
    app, transport = _client(served_registry)
    async with transport:
        await app.router.startup()
        async with httpx.AsyncClient(transport=transport,
                                     base_url="http://t") as c:
            r = await c.post("/v1/chat/completions", json={
                "model": "alpha", "max_tokens": 6, "temperature": 0.0,
                "stream": True,
                "stream_options": {"include_usage": True},
                "messages": [{"role": "user", "content": "hi"}]})
            assert r.status_code == 200
            assert r.headers["content-type"].startswith("text/event-stream")
            events = [e for e in r.text.split("\n\n") if e.startswith("data: ")]
            assert events[-1] == "data: [DONE]"
            chunks = [json.loads(e[6:]) for e in events[:-1]]
            # final usage chunk (stream_options.include_usage), empty choices
            usage = chunks[-1]
            assert usage["choices"] == [] and "usage" in usage
            assert usage["usage"]["total_tokens"] == (
                usage["usage"]["prompt_tokens"]
                + usage["usage"]["completion_tokens"])
            body = chunks[:-1]
            assert all(ch["object"] == "chat.completion.chunk" for ch in body)
            assert all(ch["model"] == "alpha" for ch in body)
            assert body[0]["choices"][0]["delta"] == {"role": "assistant"}
            assert body[-1]["choices"][0]["finish_reason"] in ("stop", "length")
            assert all("lfkt_timings" not in ch for ch in body)
        await app.router.shutdown()


@pytest.mark.anyio
async def test_v1_unknown_model_openai_error_body(served_registry):
    app, transport = _client(served_registry)
    async with transport:
        await app.router.startup()
        async with httpx.AsyncClient(transport=transport,
                                     base_url="http://t") as c:
            r = await c.post("/v1/chat/completions", json={
                "model": "gamma",
                "messages": [{"role": "user", "content": "hi"}]})
            assert r.status_code == 400
            err = r.json()["error"]
            assert err["type"] == "invalid_request_error"
            assert err["code"] == "model_not_found"
            assert "gamma" in err["message"] and "alpha" in err["message"]

            # n>1 and empty messages are structured 400s too
            r = await c.post("/v1/chat/completions", json={
                "n": 2, "messages": [{"role": "user", "content": "x"}]})
            assert r.status_code == 400
            assert r.json()["error"]["type"] == "invalid_request_error"
            r = await c.post("/v1/chat/completions", json={"messages": []})
            assert r.status_code == 400
        await app.router.shutdown()


@pytest.mark.anyio
async def test_response_model_field_routes_and_400s(served_registry):
    """/response accepts the optional model field (existing JSON error
    shape on an unknown alias) while the default body stays unchanged."""
    body = {
        "bot_profile": {"name": "Ada", "appearance": "a,b,c,d",
                        "system_prompt": "be brief"},
        "user_profile": {"name": "Sam"},
        "context": [{"turn": "user", "message": "hi"}],
    }
    app, transport = _client(served_registry)
    async with transport:
        await app.router.startup()
        async with httpx.AsyncClient(transport=transport,
                                     base_url="http://t") as c:
            r = await c.post("/response", json={**body, "model": "beta"})
            assert r.status_code == 200 and "response" in r.json()
            r = await c.post("/response", json=body)      # default model
            assert r.status_code == 200
            r = await c.post("/response", json={**body, "model": "gamma"})
            assert r.status_code == 400
            assert "unknown model" in r.json()["detail"]  # legacy shape
        await app.router.shutdown()


@pytest.mark.anyio
async def test_health_models_block_and_metrics_labels(served_registry):
    app, transport = _client(served_registry)
    async with transport:
        await app.router.startup()
        async with httpx.AsyncClient(transport=transport,
                                     base_url="http://t") as c:
            h = await c.get("/health")
            eng = h.json()["engine"]
            rows = eng["models"]
            assert [r["name"] for r in rows] == ["alpha", "beta"]
            assert all(r["weight_bytes"] > 0 for r in rows)
            assert all(r["state"] == "ready" for r in rows)
            assert all(r["quant"] for r in rows)
            assert eng["default_model"] == "alpha"

            await c.post("/v1/chat/completions", json={
                "model": "beta", "max_tokens": 4, "temperature": 0.0,
                "messages": [{"role": "user", "content": "hi"}]})
            m = (await c.get("/metrics")).text
            assert "models_loaded 2" in m
            assert 'model_weight_bytes{model="alpha"}' in m
            assert 'model_weight_bytes{model="beta"}' in m
            assert 'engine_ttft_seconds_count{bucket="32",model="beta"}' in m
            assert 'engine_decode_tokens_per_sec' in m
        await app.router.shutdown()


@pytest.mark.anyio
async def test_v1_single_model_engine_still_serves():
    """The facade works on single-model pods too: the engine's own name
    is the one listed/accepted model; other names 400."""
    engine = FakeEngine(reply="hey")
    engine.model_name = "solo"
    app, transport = _client(engine)
    async with transport:
        await app.router.startup()
        async with httpx.AsyncClient(transport=transport,
                                     base_url="http://t") as c:
            r = await c.get("/v1/models")
            assert [m["id"] for m in r.json()["data"]] == ["solo"]
            r = await c.post("/v1/chat/completions", json={
                "messages": [{"role": "user", "content": "hi"}]})
            assert r.status_code == 200
            assert r.json()["choices"][0]["message"]["content"] == "hey"
            r = await c.post("/v1/chat/completions", json={
                "model": "other",
                "messages": [{"role": "user", "content": "hi"}]})
            assert r.status_code == 400
            assert r.json()["error"]["code"] == "model_not_found"
        await app.router.shutdown()


@pytest.mark.anyio
async def test_v1_oversized_prompt_is_400_not_500(ggufs):
    pa, _ = ggufs
    reg = ModelRegistry({"alpha": _serial(pa)}, "alpha")
    app, transport = _client(reg)
    async with transport:
        await app.router.startup()
        async with httpx.AsyncClient(transport=transport,
                                     base_url="http://t") as c:
            r = await c.post("/v1/chat/completions", json={
                "messages": [{"role": "user", "content": "x" * 2000}]})
            assert r.status_code == 400
            err = r.json()["error"]
            assert err["type"] == "invalid_request_error"
            assert "context window" in err["message"]
        await app.router.shutdown()


@pytest.mark.anyio
async def test_debug_requests_rows_carry_model(ggufs):
    """/debug/requests rows gain the model name: the trace meta carries
    it from the engine's identity attrs."""
    pa, _ = ggufs
    slow = FakeEngine(reply="z" * 50, chunk_delay=0.05)
    reg = ModelRegistry({"alpha": slow}, "alpha")
    app, transport = _client(reg)
    async with transport:
        await app.router.startup()
        async with httpx.AsyncClient(transport=transport,
                                     base_url="http://t") as c:
            task = asyncio.create_task(c.post("/response/stream", json={
                "bot_profile": {"name": "A", "appearance": "a,b",
                                "system_prompt": "s"},
                "user_profile": {"name": "U"},
                "context": [{"turn": "user", "message": "hi"}],
                "model": "alpha",
            }))
            rows = []
            for _ in range(100):
                await asyncio.sleep(0.02)
                rows = (await c.get("/debug/requests")).json()["requests"]
                if any(r.get("model") == "alpha" for r in rows):
                    break
            assert any(r.get("model") == "alpha" for r in rows), rows
            await task
        await app.router.shutdown()
