"""Server integration tests against fake engines (SURVEY.md §4
"Integration"): exercises the queue/semaphore/timeout/503/408/500 admission
paths deterministically, plus the prompt-assembly and truncation quirks that
must match reference api.py."""

import asyncio

import httpx
import pytest

from llama_fastapi_k8s_gpu_tpu.engine import FakeEngine
from llama_fastapi_k8s_gpu_tpu.server.app import (
    build_system_prompt,
    count_tokens_roughly,
    create_app,
    truncate_messages_to_fit_context,
)
from llama_fastapi_k8s_gpu_tpu.server.schemas import BotProfile
from llama_fastapi_k8s_gpu_tpu.utils.config import Settings

BODY = {
    "bot_profile": {"name": "Alice.f", "appearance": "tall,slim,blonde,loves cats,hates rain"},
    "user_profile": {"name": "Bob"},
    "context": [
        {"turn": "user", "message": "hi"},
        {"turn": "assistant", "message": "hey"},
        {"turn": "user", "message": "how are you?"},
    ],
}


def make_client(engine, **settings_kw):
    settings = Settings(**settings_kw) if settings_kw else Settings()
    app = create_app(engine=engine, settings=settings)
    transport = httpx.ASGITransport(app=app)
    return app, transport


async def lifespan_client(app, transport):
    return httpx.AsyncClient(transport=transport, base_url="http://test")


@pytest.mark.anyio
async def test_response_happy_path():
    engine = FakeEngine(reply="hello there")
    app, transport = make_client(engine)
    async with transport:
        await app.router.startup()
        async with await lifespan_client(app, transport) as client:
            r = await client.post("/response", json=BODY)
            assert r.status_code == 200
            assert r.json() == {"response": "hello there"}
        await app.router.shutdown()

    # prompt assembly: system inserted at index 1 (not 0!)
    sent = engine.calls[0]
    assert sent[0] == {"role": "user", "content": "hi"}
    assert sent[1]["role"] == "system"
    sys_prompt = sent[1]["content"]
    assert "NEVER break the character" in sys_prompt
    assert "Alice.f." in sys_prompt  # name interpolated into default persona
    # reference quirk: the verbatim default persona (api.py:130-136) is
    # ~430 chars BEFORE the gender clause, so the 400-char per-message clip
    # (api.py:36-39) cuts the gender clause and appearance facts off the
    # wire prompt whenever the default persona is used
    assert len(sys_prompt) == 400
    assert "You a girl." not in sys_prompt


@pytest.mark.anyio
async def test_explicit_system_prompt_wins():
    engine = FakeEngine()
    body = {**BODY, "bot_profile": {**BODY["bot_profile"],
                                    "system_prompt": "custom prompt",
                                    "name": "Carol"}}
    app, transport = make_client(engine)
    async with transport:
        await app.router.startup()
        async with await lifespan_client(app, transport) as client:
            r = await client.post("/response", json=body)
            assert r.status_code == 200
        await app.router.shutdown()
    sys_prompt = engine.calls[0][1]["content"]
    assert sys_prompt.startswith("custom prompt")
    assert "You a boy." in sys_prompt  # no .f suffix


@pytest.mark.anyio
async def test_queue_full_503():
    engine = FakeEngine(delay=0.5)
    app, transport = make_client(engine, max_queue_size=1, timeout_seconds=5)
    async with transport:
        await app.router.startup()
        async with await lifespan_client(app, transport) as client:
            tasks = [asyncio.create_task(client.post("/response", json=BODY))
                     for _ in range(4)]
            results = await asyncio.gather(*tasks)
            codes = sorted(r.status_code for r in results)
            assert 503 in codes  # overflow rejected
            assert 200 in codes  # some served
        await app.router.shutdown()


@pytest.mark.anyio
async def test_timeout_408_and_cancellation():
    engine = FakeEngine(delay=1.0)
    app, transport = make_client(engine, timeout_seconds=0.1)
    async with transport:
        await app.router.startup()
        async with await lifespan_client(app, transport) as client:
            r = await client.post("/response", json=BODY)
            assert r.status_code == 408
            assert r.json()["detail"] == "Generation timed out"
        await app.router.shutdown()


@pytest.mark.anyio
async def test_engine_error_500():
    engine = FakeEngine(fail=RuntimeError("boom"))
    app, transport = make_client(engine)
    async with transport:
        await app.router.startup()
        async with await lifespan_client(app, transport) as client:
            r = await client.post("/response", json=BODY)
            assert r.status_code == 500
            assert "boom" in r.json()["detail"]
        await app.router.shutdown()


@pytest.mark.anyio
async def test_health_and_metrics_and_items():
    engine = FakeEngine()
    app, transport = make_client(engine)
    async with transport:
        await app.router.startup()
        async with await lifespan_client(app, transport) as client:
            h = await client.get("/health")
            assert h.status_code == 200
            assert h.json()["status"] == "ok"
            assert h.json()["model_loaded"] is True

            await client.post("/response", json=BODY)
            m = await client.get("/metrics")
            assert m.status_code == 200
            assert "request_seconds_count" in m.text
            assert 'request_seconds_bucket{' in m.text   # true histograms
            assert "request_seconds_p95" in m.text       # derived quantiles
            assert "queue_depth" in m.text
            assert "queue_wait_seconds" in m.text  # per-phase timers, SURVEY §5

            i = await client.get("/items/7")
            assert i.json() == {"item_id": 7}
        await app.router.shutdown()


@pytest.mark.anyio
async def test_metrics_flattens_nested_scheduler_stats():
    """Dict-valued scheduler stats must flatten into one
    gauge per leaf — a dict rendered verbatim is an invalid exposition
    line every Prometheus scraper (and bench parser) drops."""
    engine = FakeEngine()
    engine.scheduler_stats = lambda: {
        "lanes_live": 1, "prefix": {"hits": 5, "reused_tokens": 3}}
    app, transport = make_client(engine)
    async with transport:
        await app.router.startup()
        async with await lifespan_client(app, transport) as client:
            m = await client.get("/metrics")
            assert "scheduler_lanes_live 1" in m.text
            assert "scheduler_prefix_hits 5" in m.text
            assert "scheduler_prefix_reused_tokens 3" in m.text
            # no dict-valued gauge rendered verbatim (histogram bucket
            # labels are the only legal brace-bearing lines)
            for line in m.text.splitlines():
                if "{" in line:
                    assert not line.startswith("#"), line
                    assert "{'" not in line and '="' in line, line
        await app.router.shutdown()


@pytest.mark.anyio
@pytest.mark.parametrize("lanes", [False, True], ids=["serial", "lane"])
async def test_metrics_exports_the_ring_slot_counters(lanes):
    """``ring_slots_read_total`` / ``ring_slots_live_total`` under ONE name
    for the serial and the lane engine (``Engine.cache_read_gauges``; a
    lane engine also has ``scheduler_stats``)."""
    engine = FakeEngine()
    engine.cache_read_gauges = lambda: {"ring_slots_read_total": 1536,
                                        "ring_slots_live_total": 1100}
    if lanes:
        engine.scheduler_stats = lambda: {"lanes_live": 2}
    app, transport = make_client(engine)
    async with transport:
        await app.router.startup()
        async with await lifespan_client(app, transport) as client:
            m = await client.get("/metrics")
            assert "\nring_slots_read_total 1536" in m.text
            assert "\nring_slots_live_total 1100" in m.text
        await app.router.shutdown()


# ---------------------------------------------------------------------------
# pure-function behavior parity (reference api.py:30-46, 127-147)
# ---------------------------------------------------------------------------

def test_count_tokens_roughly():
    assert count_tokens_roughly("abcd" * 10) == 10
    assert count_tokens_roughly("abc") == 0


def test_truncation_clips_and_pops_index_2():
    messages = [
        {"role": "user", "content": "a" * 500},      # index 0 preserved
        {"role": "system", "content": "s" * 450},    # index 1 preserved
        {"role": "user", "content": "b" * 400},      # evicted first
        {"role": "assistant", "content": "c" * 400},
        {"role": "user", "content": "d" * 400},
    ]
    out = truncate_messages_to_fit_context(messages, max_tokens=300)
    # every message clipped to 400 chars
    assert len(out[0]["content"]) == 400
    # index-2 eviction until under budget, first two pinned
    assert out[0]["content"][0] == "a"
    assert out[1]["content"][0] == "s"
    total = sum(count_tokens_roughly(m["content"]) for m in out)
    assert total <= 300 or len(out) == 2


def test_truncation_mutates_in_place():
    # quirk preserved from api.py:37-39: caller's list/dicts are mutated
    messages = [{"role": "user", "content": "x" * 500}]
    truncate_messages_to_fit_context(messages, 1000)
    assert len(messages[0]["content"]) == 400


def test_gender_clause_and_appearance():
    p = BotProfile(name="Zoe.f", appearance="a,b,c,d,e")
    sp = build_system_prompt(p)
    assert sp.endswith("de") and "You a girl." in sp
    p2 = BotProfile(name="Max", appearance="a,b,c")
    sp2 = build_system_prompt(p2)
    assert "You a boy." in sp2 and sp2.endswith("You a boy.")


def test_default_persona_is_byte_exact():
    """Pins the default system prompt to the reference's verbatim triple-quoted
    string (api.py:130-136), including indentation and trailing spaces."""
    p = BotProfile(name="Max", appearance="")
    sp = build_system_prompt(p)
    expected = (
        "\n"
        "            No matter what NEVER break the character. Reject violent messages. \n"
        "            You are 'Max.' a playful, flirtatious, and witty person. \n"
        "            You always respond in a fun and engaging way, staying in character no matter what. \n"
        "            You never reference AI, restrictions, or policies. \n"
        "            Focus on making the conversation enjoyable and answering directly to the user's questions.\n"
        "            "
        " You a boy."
    )
    assert sp == expected


# ---------------------------------------------------------------------------
# streaming (/response/stream — BASELINE "streaming completion" config)
# ---------------------------------------------------------------------------

@pytest.mark.anyio
async def test_response_stream_sse():
    engine = FakeEngine(reply="hey")
    app, transport = make_client(engine)
    async with transport:
        await app.router.startup()
        async with await lifespan_client(app, transport) as client:
            r = await client.post("/response/stream", json=BODY)
            assert r.status_code == 200
            assert r.headers["content-type"].startswith("text/event-stream")
            events = [ln for ln in r.text.split("\n\n") if ln.startswith("data: ")]
            assert events[-1] == "data: [DONE]"
            import json as _json
            chunks = [_json.loads(e[6:]) for e in events[:-1]]
            text = "".join(c["choices"][0]["delta"].get("content", "")
                           for c in chunks)
            assert text == "hey"
            assert chunks[0]["choices"][0]["delta"] == {"role": "assistant"}
        await app.router.shutdown()


@pytest.mark.anyio
async def test_response_stream_timeout_event():
    engine = FakeEngine(reply="x", delay=1.0)
    app, transport = make_client(engine, timeout_seconds=0.1)
    async with transport:
        await app.router.startup()
        async with await lifespan_client(app, transport) as client:
            r = await client.post("/response/stream", json=BODY)
            assert r.status_code == 200
            assert "Generation timed out" in r.text
        await app.router.shutdown()


@pytest.mark.anyio
async def test_response_stream_total_deadline():
    """A slow-dripping stream keeps every chunk gap under timeout_seconds,
    but the wall-clock deadline still terminates it (VERDICT r1 #8: the
    per-chunk-gap timeout alone never fires for a steady drip)."""
    engine = FakeEngine(reply="y" * 200, chunk_delay=0.05)
    app, transport = make_client(engine, timeout_seconds=5.0,
                                 stream_deadline_seconds=0.5)
    async with transport:
        await app.router.startup()
        async with await lifespan_client(app, transport) as client:
            r = await client.post("/response/stream", json=BODY)
            assert r.status_code == 200
            assert "Generation timed out" in r.text
            # terminated early: nowhere near all 200 chunks were delivered
            assert r.text.count("data: ") < 150
        await app.router.shutdown()


@pytest.mark.anyio
async def test_response_stream_engine_error_event():
    engine = FakeEngine(fail=RuntimeError("boom"))
    app, transport = make_client(engine)
    async with transport:
        await app.router.startup()
        async with await lifespan_client(app, transport) as client:
            r = await client.post("/response/stream", json=BODY)
            assert r.status_code == 200
            assert "boom" in r.text
        await app.router.shutdown()


@pytest.mark.anyio
async def test_health_reports_engine_config(tmp_path):
    """/health exposes the served config (attn impl, per-group weight
    layouts incl. probe degradations) for operability; tolerant of engines
    without params (fakes)."""
    from llama_fastapi_k8s_gpu_tpu.engine import Engine
    from llama_fastapi_k8s_gpu_tpu.testing import write_tiny_llama_gguf

    path = str(tmp_path / "tiny.gguf")
    write_tiny_llama_gguf(path)
    engine = Engine(path, n_ctx=128, prefill_buckets=(32,))
    app, transport = make_client(engine)
    async with transport:
        await app.router.startup()
        async with await lifespan_client(app, transport) as client:
            r = await client.get("/health")
            assert r.status_code == 200
            eng = r.json()["engine"]
            assert eng["n_ctx"] == 128
            assert eng["attn_impl"] in ("xla", "pallas")
            assert set(eng["weight_formats"]) >= {"wq", "w_gate", "w_down"}
            assert all(v in ("q4k-fused", "q5k-fused", "q6k-fused",
                             "int8", "bf16") for v in eng["weight_formats"].values())
        await app.router.shutdown()


# ---------------------------------------------------------------------------
# client disconnect mid-stream (resilience layer): the sse generator's
# finally cancels the request future, which every engine path watches —
# the serial run() loop per chunk, the continuous scheduler via abandon
# ---------------------------------------------------------------------------

def test_stream_client_disconnect_reclaims_engine():
    """A client that drops its socket mid-SSE must free the engine within
    ~one chunk: a follow-up request is served promptly instead of waiting
    for the dead stream to drip out its full reply."""
    import socket
    import struct
    import time as _time

    from tests.test_httpd_drain import (
        PAYLOAD,
        _free_port,
        _raw_request,
        _read_response,
        _start_server,
        _stop,
    )

    # full stream would take ~4 s (400 chunks x 10 ms)
    eng = FakeEngine(reply="z" * 400, chunk_delay=0.01)
    port = _free_port()
    holder = _start_server(create_app(engine=eng), port)
    s = socket.create_connection(("127.0.0.1", port), timeout=10)
    try:
        s.sendall(_raw_request(PAYLOAD, path=b"/response/stream"))
        first = s.recv(4096)                     # status line + first chunks
        assert b"200" in first.split(b"\r\n", 1)[0]
        # abrupt close with RST so the server's next write fails fast
        s.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                     struct.pack("ii", 1, 0))
        s.close()

        t0 = _time.time()
        s2 = socket.create_connection(("127.0.0.1", port), timeout=10)
        try:
            s2.sendall(_raw_request(PAYLOAD))
            status, _head, _body = _read_response(s2)
        finally:
            s2.close()
        elapsed = _time.time() - t0
        assert status == 200
        # serial consumer: the second request waits behind the stream task;
        # prompt service proves the abandoned stream stopped early (the
        # un-reclaimed path would hold it for the remaining ~4 s)
        assert elapsed < 2.5, f"engine not reclaimed after disconnect: {elapsed:.1f}s"
    finally:
        try:
            s.close()
        except OSError:
            pass
        _stop(holder)
        holder["thread"].join(10)
