"""FastAPI serving layer.

Preserves the reference's externally observable behavior line by line
(reference api.py; SURVEY.md §2A #3-#8):

- ``POST /response`` with the same schema, the same system-prompt assembly
  quirks (insert at index 1, ``.f`` name-suffix gender clause,
  ``appearance.split(",")[3:]`` fact append — api.py:127-147), the same
  truncation (400-char clip, chars/4 estimate, pop-index-2 loop —
  api.py:30-46), and the same admission control: bounded queue(5) → 503,
  single consumer + semaphore(1) → strictly serial generation, 25 s future
  timeout → 408 with cancellation, engine errors → 500 (api.py:80-173).
- the vestigial ``GET /items/{item_id}`` echo route (api.py:175-177).
- the request-timing log middleware (api.py:179-194).

Additions the reference advertises but lacks (SURVEY.md §2C): ``GET /health``
(model/device/queue state, wired for k8s probes) and ``GET /metrics``
(Prometheus text).  All constants are env-overridable with identical defaults
(utils/config.py).
"""

from __future__ import annotations

import asyncio
import gc
import inspect
import logging
import math
import signal
import time
from datetime import datetime

import json

from .asgikit import (
    HTTPException,
    JSONResponse,
    MicroAPI,
    PlainTextResponse,
    Request,
    StreamingResponse,
)

import uuid

from .. import T_IMPORTED
from .. import native as _native
from ..obs import flightrec as _flightrec
from ..obs import memledger as _memledger
from ..obs.devtime import DEVTIME
from ..obs.logctx import access_logger, bind_request_id, sanitize_text
from ..obs.slo import SLOEngine
from ..obs.trace import TRACER, Tracer
from ..serving.fleet.affinity import AFFINITY_KEY_HEADER, PRIOR_OWNER_HEADER
from ..utils.config import Settings, get_settings
from ..utils.faults import FAULTS
from ..utils.jaxcache import compile_cache_stats
from ..utils.health import (
    READY,
    STARTING,
    STATE_CODES,
    DeadlineExceeded,
    EngineUnavailable,
    HealthMonitor,
)
from ..utils.metrics import Metrics
from ..utils.startup import Timeline, process_start
from .schemas import BotMessageRequest, ChatCompletionRequest

logging.basicConfig(level=logging.INFO)
logger = logging.getLogger(__name__)

_STREAM_DONE = object()  # consumer→handler sentinel: stream finished cleanly


def _openai_error_body(status: int, message: str, code: str | None = None
                       ) -> dict:
    """The OpenAI-style error envelope served on every ``/v1/*`` failure
    (docs/MULTIMODEL.md facade mapping): 4xx are the caller's fault
    (``invalid_request_error``; 408 keeps its own type so SDK retry
    policies can tell a timeout from a bad request), 5xx are ours."""
    if status >= 500 or status == 503:
        etype = "server_error"
    elif status == 408:
        etype = "timeout_error"
    else:
        etype = "invalid_request_error"
    return {"error": {"message": message, "type": etype,
                      "param": None, "code": code}}


def _openai_http_error(e: HTTPException) -> JSONResponse:
    msg = e.detail if isinstance(e.detail, str) else json.dumps(e.detail)
    return JSONResponse(
        _openai_error_body(e.status_code, msg,
                           getattr(e, "openai_code", None)),
        e.status_code)


def _settle_heap() -> None:
    """One full collection as the start ends, so that none falls due among
    the first requests.  CPython runs a full pass when the objects that
    outlived two young passes exceed a quarter of the heap the LAST full
    pass walked, and on a serving pod a pass holds the interpreter lock,
    and the scheduler thread with it, for 0.36-0.85 s (about 145 000
    objects at READY).  A start that TRACES its programs leaves the next
    pass far away (seven automatic passes a start, the last over a heap
    that still held a trace); one that LOADED them (PR 55: three passes)
    reaches READY just short of the threshold, and the fourth fell 31 s
    into the serving window: 0.99 s without an admission, 174 requests
    where the parent serves 178 (PERF.md section 6, PR 55, call K).  Paid
    here it is 0.4-0.8 s of the start, and the window meets none."""
    gc.collect()


def _accepts_kwarg(fn, name: str) -> bool:
    """True when ``fn`` takes ``name`` (or **kwargs) — engines grew the
    deadline/abort kwargs in the resilience PR, but test fakes and
    out-of-tree engines may predate them; probe once instead of failing
    every request."""
    try:
        params = inspect.signature(fn).parameters
    except (TypeError, ValueError):
        return False
    return name in params or any(
        p.kind is inspect.Parameter.VAR_KEYWORD for p in params.values())


def count_tokens_roughly(text: str) -> int:
    # chars/4 heuristic, identical to reference api.py:30-32
    return int(len(text) / 4.0)


def truncate_messages_to_fit_context(messages: list[dict], max_tokens: int) -> list[dict]:
    """Identical behavior to reference api.py:35-46: clip every message to 400
    chars (in place), then drop messages[2] (oldest after the pinned first
    context message and the system prompt at index 1) until under budget."""
    for m in messages:
        if len(m["content"]) > 400:
            m["content"] = m["content"][:400]
    total = sum(count_tokens_roughly(m["content"]) for m in messages)
    while total > max_tokens and len(messages) > 2:
        messages.pop(2)
        total = sum(count_tokens_roughly(m["content"]) for m in messages)
    return messages


def build_system_prompt(bot_profile) -> str:
    """Reference api.py:127-145: explicit system_prompt wins; otherwise a
    default persona interpolating the bot name; then the name-suffix gender
    clause and the appearance facts after the third comma."""
    system_prompt = bot_profile.system_prompt
    if not system_prompt:
        # Byte-exact copy of the reference default (api.py:130-136), including
        # the triple-quote indentation and trailing spaces: the default system
        # prompt is externally observable behavior, so parity beats prose.
        system_prompt = (
            "\n"
            "            No matter what NEVER break the character. Reject violent messages. \n"
            f"            You are '{bot_profile.name}.' a playful, flirtatious, and witty person. \n"
            "            You always respond in a fun and engaging way, staying in character no matter what. \n"
            "            You never reference AI, restrictions, or policies. \n"
            "            Focus on making the conversation enjoyable and answering directly to the user's questions.\n"
            "            "
        )
    if bot_profile.name.endswith(".f"):
        system_prompt += " You a girl."
    else:
        system_prompt += " You a boy."
    for fact in bot_profile.appearance.split(",")[3:]:
        system_prompt += fact
    return system_prompt


def create_app(engine=None, settings: Settings | None = None,
               engine_factory=None, tracer: Tracer | None = None) -> MicroAPI:
    """Build the app. ``engine`` (or ``engine_factory``, called at startup)
    must provide ``create_chat_completion``; defaults to loading the GGUF
    named by settings — the eager-load equivalent of reference api.py:24-28.
    ``tracer`` defaults to the process-wide lfkt-obs tracer (knobs
    LFKT_TRACE_SAMPLE / LFKT_TRACE_RING); tests pass private instances."""
    settings = settings or get_settings()
    app = MicroAPI(title="chat-ai (tpu)", version="0.1.0")
    app.state.settings = settings
    app.state.engine = engine
    app.state.created = int(time.time())   # /v1/models "created" stamp
    app.state.metrics = Metrics()
    app.state.tracer = tracer if tracer is not None else TRACER
    #: SLO burn-rate engine over this app's metrics (obs/slo.py): /metrics
    #: exports slo_burn_rate gauges, /debug/slo the full verdict
    app.state.slo = SLOEngine(app.state.metrics)
    #: devtime compile-event cursor: /metrics replays each compile event
    #: into xla_compile_seconds exactly once per app (-1 = never read, so
    #: a ring that overflowed before this app existed charges no drop)
    app.state.devtime_cursor = -1
    #: the start-up timeline (utils/startup.py): the entry point stamps
    #: the process's own phases into it (server/__main__.py); the start-up
    #: hook adds the engine's and its own at the READY flip and freezes
    #: the document /health serves as engine.startup
    app.state.startup = Timeline(*process_start(T_IMPORTED))
    app.state.startup_doc = None
    app.state.ready = engine is not None
    #: pod health state machine (utils/health.py): STARTING until the
    #: engine is loaded; the watchdog moves it between READY/DEGRADED/DEAD
    app.state.health = HealthMonitor()
    app.state.watchdog = None
    #: disaggregated prefill/decode roles (serving/disagg/): armed at
    #: startup from LFKT_DISAGG_ROLE; None = the single-process path
    app.state.disagg = None
    #: fleet KV migration (serving/fleet/migrate.py): armed at startup
    #: from LFKT_MIGRATE; None = warm pages die with this pod
    app.state.migration = None
    #: live manifest reload (serving/registry.py reload_manifest): one
    #: reload at a time — POST /admin/models/reload and SIGHUP share it
    app.state.reload_busy = asyncio.Lock()
    app.state.engine_kw = {}   # which resilience kwargs the engine accepts
    # strong refs to fire-and-forget tasks: the loop holds only weak refs,
    # so an unreferenced task can be garbage-collected mid-flight (losing
    # its inflight permit and stranding its caller)
    app.state.bg_tasks = set()

    def _spawn(coro) -> asyncio.Task:
        task = asyncio.ensure_future(coro)
        app.state.bg_tasks.add(task)
        task.add_done_callback(app.state.bg_tasks.discard)
        return task

    def _queue_span(rd, now: float) -> None:
        """Record the admission-queue wait (enqueue → consumer pickup) on
        the request's trace; no-op for sampled-out requests."""
        tr = rd.get("trace")
        if tr is not None:
            tr.span("queue", t0=rd["enqueued_at"]).end(now)

    async def consumer():
        """Single drain task: strict FIFO (reference api.py:80-107).  A
        lane engine (``submit``) takes each request as it comes and admits
        it into a free lane; the serial engine generates one request at a
        time."""
        queue = app.state.queue
        semaphore = app.state.semaphore
        while True:
            rd = await queue.get()
            now = time.time()
            app.state.metrics.observe(
                "queue_wait_seconds", now - rd["enqueued_at"])
            _queue_span(rd, now)
            if rd["future"].cancelled():
                logger.info("Future was cancelled before processing; skipping.")
            elif hasattr(app.state.engine, "submit"):
                # slot scheduler: forward without a barrier — the engine
                # admits into free lanes at chunk boundaries.  In-flight
                # count is capped at batch_size so the bounded queue is
                # still the back-pressure surface (503 on overflow);
                # without the cap the engine's pending queue would absorb
                # unlimited work and 503 could never fire.
                if "stream_queue" in rd:
                    # streams ride scheduler lanes concurrently with batched
                    # requests; each holds an inflight permit so the bounded
                    # queue (503) stays the back-pressure surface for them too
                    await app.state.inflight.acquire()  # lfkt: transfers[inflight] -- permit released in _stream_task's finally
                    _spawn(_stream_task(rd))
                else:
                    await app.state.inflight.acquire()  # lfkt: transfers[inflight] -- permit released in _forward_to_scheduler's finally
                    _spawn(_forward_to_scheduler(rd))
            elif "stream_queue" in rd:
                try:
                    await _truncate_and_stream(rd, semaphore)
                except Exception as e:  # noqa: BLE001 — never kill the consumer
                    logger.error("Error during streamed generation: %s", e)
                    try:
                        rd["stream_queue"].put_nowait(e)
                    except Exception:  # noqa: BLE001
                        pass
            else:       # per-request isolation (reference semantics)
                try:
                    resp, err = await _truncate_and_generate(
                        rd, semaphore), None
                except Exception as e:  # noqa: BLE001
                    resp, err = None, e
                if rd["future"].cancelled():
                    logger.info("Future cancelled during processing; "
                                "%s dropped.", "error" if err else "result")
                elif err is not None:
                    rd["future"].set_exception(err)
                else:
                    rd["future"].set_result(resp)
            queue.task_done()

    def _model_label(obj=None) -> str:
        """Bounded-cardinality ``model`` label value: the per-request model
        from a response/timings dict when present, else the engine's (or
        the registry's default) name — one series per served model."""
        name = None
        if isinstance(obj, dict):
            name = obj.get("model")
        if not name:
            name = getattr(app.state.engine, "model_name", None)
        return str(name or "")

    def _observe_engine_timings(m, answer=None):
        """Record per-phase engine timings: prefer the per-request values
        attached to the response (no shared-state read-back); fall back to
        the engine's last_timings for paths that predate the attachment."""
        timings = answer.get("lfkt_timings") if isinstance(answer, dict) else None
        if timings is None:
            timings = getattr(app.state.engine, "last_timings", None)
        if timings:
            # per-prefill-bucket TTFT series, labeled per model: the SLO
            # engine evaluates each label series separately, so a
            # 32k-prompt (or one misbehaving co-resident model's)
            # violation cannot hide under the rest (docs/SLO.md —
            # worst_series now names the worst bucket AND model)
            model = _model_label(timings)
            m.observe("engine_ttft_seconds", timings["ttft_s"],
                      bucket=str(timings.get("bucket", 0)), model=model)
            if timings["tokens_per_sec"]:
                m.observe("engine_decode_tokens_per_sec",
                          timings["tokens_per_sec"], model=model)
            reused = timings.get("prefix_reused_tokens", 0)
            if reused:  # prompt-prefix KV reuse: prompt tokens NOT re-prefilled
                m.inc("prefix_cache_hits_total")
                m.inc("prefix_cache_reused_tokens_total", reused)

    def _meter_tokens(m, prompt: int, completion: int, model: str) -> None:
        """Per-model token metering (tokens_prompt_total /
        tokens_generated_total): multi-tenant billing from the engines'
        own usage counts, so nobody has to scrape /v1 response bodies."""
        if prompt:
            m.inc("tokens_prompt_total", prompt, model=model)
        if completion:
            m.inc("tokens_generated_total", completion, model=model)

    def _answer_to_text(answer, m) -> str:
        """OpenAI-shaped dict → concatenated choice text (reference
        api.py:65-74 semantics, incl. the dict typecheck → 500)."""
        if not isinstance(answer, dict):
            logger.error("Unexpected response type: %s. Response: %s",
                         type(answer), answer)
            raise HTTPException(status_code=500,
                                detail="Unexpected response from model")
        usage = answer.get("usage") or {}
        if usage.get("completion_tokens"):
            m.inc("generated_tokens_total", usage["completion_tokens"])
        _meter_tokens(m, usage.get("prompt_tokens", 0),
                      usage.get("completion_tokens", 0),
                      _model_label(answer))
        return "".join(c["message"]["content"]
                       for c in answer.get("choices", []) if "message" in c)

    def _answer_openai(answer, m) -> dict:
        """/v1 facade result: the engine's OpenAI-shaped completion dict
        verbatim (usage counts come straight from the engine's timings),
        minus the internal ``lfkt_timings`` rider."""
        if not isinstance(answer, dict):
            logger.error("Unexpected response type: %s. Response: %s",
                         type(answer), answer)
            raise HTTPException(status_code=500,
                                detail="Unexpected response from model")
        usage = answer.get("usage") or {}
        if usage.get("completion_tokens"):
            m.inc("generated_tokens_total", usage["completion_tokens"])
        _meter_tokens(m, usage.get("prompt_tokens", 0),
                      usage.get("completion_tokens", 0),
                      _model_label(answer))
        answer = dict(answer)
        answer.pop("lfkt_timings", None)
        return answer

    def _finish_answer(rd, answer, m):
        """Shape one engine answer for its caller: the /v1 facade gets the
        OpenAI dict, the /response path its concatenated text."""
        if rd.get("openai"):
            return _answer_openai(answer, m)
        return _answer_to_text(answer, m)

    def _gen_kwargs(rd) -> dict:
        """Sampling/budget kwargs for one request: the pod's serving
        defaults (reference api.py:59-62), overridden by the request's own
        OpenAI fields when the /v1 facade set them (rd["params"])."""
        kw = dict(
            temperature=settings.temperature,
            top_p=settings.top_p,
            frequency_penalty=settings.frequency_penalty,
            presence_penalty=settings.presence_penalty,
        )
        kw.update(rd.get("params") or {})
        return kw

    def _validate_model(model: str | None) -> str | None:
        """400 for a model alias this pod does not serve.  Routed through
        the registry's manifest when one is loaded; a single-model process
        serves only its own name (or no name at all)."""
        if model is None:
            return None
        eng = app.state.engine
        has = getattr(eng, "has_model", None)
        if callable(has):
            if not has(model):
                known = ", ".join(eng.model_names())
                e = HTTPException(
                    status_code=400,
                    detail=f"unknown model {model!r}; this pod serves: "
                           f"{known}")
                e.openai_code = "model_not_found"
                raise e
            return model
        name = getattr(eng, "model_name", None)
        if name is not None and model != name:
            e = HTTPException(
                status_code=400,
                detail=f"unknown model {model!r}; this pod serves: {name}")
            e.openai_code = "model_not_found"
            raise e
        return model

    def _resilience_kw(rd) -> dict:
        """Deadline/abort/trace propagation kwargs for engines that accept
        them: the request's admission deadline, a did-the-caller-give-up
        callback (so a timed-out or disconnected request frees the engine
        within one decode step — the reference decoded to budget), and the
        request's trace for the engine's span tree (lfkt-obs)."""
        kw = {}
        if app.state.engine_kw.get("deadline"):
            kw["deadline"] = rd.get("deadline")
        if app.state.engine_kw.get("abort"):
            kw["abort"] = rd["future"].cancelled
        if app.state.engine_kw.get("trace"):
            kw["trace"] = rd.get("trace")
        return kw

    async def _truncate_and_generate(rd, semaphore) -> str:
        m = app.state.metrics
        async with semaphore:  # one generation at a time (reference api.py:50)
            try:
                # /v1 requests ride "raw": OpenAI clients manage their own
                # history, so the reference's 400-char clip + index-2
                # eviction must not rewrite their messages
                if rd.get("raw"):
                    messages = rd["messages"]
                else:
                    messages = truncate_messages_to_fit_context(
                        rd["messages"], settings.max_context_tokens)
                ckw = _gen_kwargs(rd)
                if app.state.engine_kw.get("model"):
                    ckw["model"] = rd.get("model")
                t0 = time.time()
                answer = await asyncio.to_thread(
                    lambda: app.state.engine.create_chat_completion(
                        messages=messages,
                        stream=False,
                        **ckw,
                        **_resilience_kw(rd),
                    ))
                m.observe("generation_seconds", time.time() - t0,
                          model=_model_label(answer))
                _observe_engine_timings(m, answer)
                return _finish_answer(rd, answer, m)
            except HTTPException:
                raise
            except ValueError as e:
                if rd.get("openai"):
                    # client input error (oversized prompt, bad params):
                    # the facade's structured 400, not a 500
                    raise HTTPException(status_code=400,
                                        detail=str(e)) from e
                m.inc("engine_errors_total")
                logger.error("Error during message generation: %s", e)
                raise HTTPException(
                    status_code=500,
                    detail=f"Error during message generation: {str(e)}",
                ) from e
            except EngineUnavailable as e:
                # watchdog trip / recovery in progress: retryable 503, not
                # the "this request hit a bug" 500
                m.inc("engine_unavailable_total")
                logger.error("Engine unavailable: %s", e)
                raise HTTPException(
                    status_code=503, detail=f"Engine unavailable: {e}") from e
            except DeadlineExceeded as e:
                m.inc("requests_timed_out_total")
                raise HTTPException(
                    status_code=408, detail="Generation timed out") from e
            except Exception as e:  # noqa: BLE001 — 500 semantics, api.py:76-78
                m.inc("engine_errors_total")
                logger.error("Error during message generation: %s", e)
                raise HTTPException(
                    status_code=500,
                    detail=f"Error during message generation: {str(e)}",
                ) from e

    async def _stream_task(rd):
        """Continuous mode: stream via a scheduler lane (no global semaphore —
        lanes already bound concurrency). Holds one inflight permit."""
        try:
            await _truncate_and_stream(rd, None)
        except Exception as e:  # noqa: BLE001 — surfaced on the SSE channel
            logger.error("Error during streamed generation: %s", e)
            try:
                rd["stream_queue"].put_nowait(e)
            except Exception:  # noqa: BLE001
                pass
        finally:
            app.state.inflight.release()

    async def _forward_to_scheduler(rd):
        """Continuous mode: one request → one scheduler lane, no barrier.
        Holds one ``app.state.inflight`` permit (acquired by the consumer).
        If the client's future is cancelled (408 timeout / disconnect) the
        lane is abandoned so it frees at the next chunk boundary instead of
        decoding to budget."""
        m = app.state.metrics
        try:
            try:
                if rd.get("raw"):
                    messages = rd["messages"]
                else:
                    messages = truncate_messages_to_fit_context(
                        rd["messages"], settings.max_context_tokens)
                t0 = time.time()
                engine = app.state.engine
                sub_kw = _gen_kwargs(rd)
                if app.state.engine_kw.get("submit_deadline"):
                    sub_kw["deadline"] = rd.get("deadline")
                if app.state.engine_kw.get("submit_trace"):
                    sub_kw["trace"] = rd.get("trace")
                if app.state.engine_kw.get("submit_model"):
                    sub_kw["model"] = rd.get("model")
                engine_fut = engine.submit(  # lfkt: transfers[engine_fut] -- the scheduler owns the lane: it resolves/reclaims the future via its _items registry even when a failure here skips the await (PR-2 semantics)
                    messages,
                    **sub_kw,
                )
                if hasattr(engine, "abandon"):
                    rd["future"].add_done_callback(
                        lambda f: engine.abandon(engine_fut)
                        if f.cancelled() else None)
                answer = await asyncio.wrap_future(engine_fut)
                m.observe("generation_seconds", time.time() - t0,
                          model=_model_label(answer))
                _observe_engine_timings(m, answer)
                result = _finish_answer(rd, answer, m)
                err = None
            except HTTPException as e:
                result, err = None, e
            except ValueError as e:
                if rd.get("openai"):
                    result, err = None, HTTPException(status_code=400,
                                                      detail=str(e))
                else:
                    m.inc("engine_errors_total")
                    logger.error("Error during message generation: %s", e)
                    result, err = None, HTTPException(
                        status_code=500,
                        detail=f"Error during message generation: {str(e)}")
            except EngineUnavailable as e:
                # watchdog trip failed this future / scheduler restarting:
                # retryable 503 (the reference's only answer was pod death)
                m.inc("engine_unavailable_total")
                logger.error("Engine unavailable: %s", e)
                result, err = None, HTTPException(
                    status_code=503, detail=f"Engine unavailable: {e}")
            except DeadlineExceeded:
                m.inc("requests_timed_out_total")
                result, err = None, HTTPException(
                    status_code=408, detail="Generation timed out")
            except Exception as e:  # noqa: BLE001 — 500 semantics, api.py:76-78
                m.inc("engine_errors_total")
                logger.error("Error during message generation: %s", e)
                result, err = None, HTTPException(
                    status_code=500,
                    detail=f"Error during message generation: {str(e)}")
            if rd["future"].cancelled():
                logger.info("Future cancelled during processing; result dropped.")
            elif err is not None:
                rd["future"].set_exception(err)
            else:
                rd["future"].set_result(result)
        finally:
            app.state.inflight.release()

    async def _truncate_and_stream(rd, semaphore):
        """Run one streaming generation, forwarding engine chunks to the
        handler's queue from the worker thread.

        ``semaphore=None`` (continuous mode) streams through a scheduler
        lane with no global serialization.  When the client abandons the
        stream (timeout/disconnect cancels ``rd["future"]``) the engine
        iterator is closed, which frees the lane/slot at the next chunk
        boundary — on EVERY engine: serial engines used to run to
        completion with chunks dropped (the reference's
        no-mid-generation-abort behavior, api.py:97-100, affordable only
        because its engine idles anyway), but a serial engine here blocks
        the whole consumer while it decodes to budget for nobody."""
        m = app.state.metrics
        chunk_q = rd["stream_queue"]
        loop = asyncio.get_running_loop()
        timings_box: list = []

        async def _go():
            if rd.get("raw"):
                messages = rd["messages"]
            else:
                messages = truncate_messages_to_fit_context(
                    rd["messages"], settings.max_context_tokens)

            def run():
                try:
                    ckw = _gen_kwargs(rd)
                    if app.state.engine_kw.get("model"):
                        ckw["model"] = rd.get("model")
                    it = app.state.engine.create_chat_completion(
                        messages=messages,
                        stream=True,
                        **ckw,
                        **_resilience_kw(rd))
                    try:
                        for chunk in it:
                            if rd["future"].cancelled():
                                return   # closes it → engine frees the lane
                            t = chunk.pop("lfkt_timings", None)
                            if t is not None:
                                timings_box.append(t)
                                # the /v1 stream's optional usage chunk
                                # (stream_options.include_usage) reads the
                                # finished request's token counts off here
                                rd["timings"] = t
                            loop.call_soon_threadsafe(chunk_q.put_nowait, chunk)
                        loop.call_soon_threadsafe(
                            chunk_q.put_nowait, _STREAM_DONE)
                    finally:
                        it.close()
                except Exception as e:  # noqa: BLE001 — surfaced as SSE error
                    loop.call_soon_threadsafe(chunk_q.put_nowait, e)

            t0 = time.time()
            await asyncio.to_thread(run)
            m.observe("generation_seconds", time.time() - t0,
                      model=_model_label(
                          timings_box[0] if timings_box else None))
            m.inc("streamed_generations_total")
            _observe_engine_timings(
                m, {"lfkt_timings": timings_box[0]} if timings_box else None)
            if timings_box:
                # streamed responses never pass through _answer_to_text:
                # meter them from the engine's own timings rider
                t = timings_box[0]
                _meter_tokens(m, t.get("prompt_tokens", 0),
                              t.get("completion_tokens", 0),
                              _model_label(t))

        if semaphore is None:
            await _go()
        else:
            async with semaphore:
                await _go()

    @app.on_event("startup")
    async def startup_event():
        app.state.queue = asyncio.Queue(maxsize=settings.max_queue_size)
        app.state.semaphore = asyncio.Semaphore(1)
        # continuous mode: at most batch_size forwarded-but-unfinished
        # requests, so the bounded queue stays the back-pressure surface
        app.state.inflight = asyncio.Semaphore(max(1, settings.batch_size))
        app.state.health.transition(STARTING, "model loading")
        tl = app.state.startup
        t_hook = time.time()
        if app.state.engine is None:
            factory = engine_factory or _default_engine_factory(settings)
            loop = asyncio.get_running_loop()
            app.state.engine = await loop.run_in_executor(None, factory)
            t_built = time.time()
            if not isinstance(getattr(app.state.engine, "startup", None),
                              Timeline):
                # a registry, a fake: the factory's whole call (an engine
                # stamps its own stretch, _default_engine_factory its import)
                tl.phase("engine_load", t_hook, t_built)
            t_hook = t_built
        engine = app.state.engine
        # which resilience kwargs this engine accepts (probed once; fakes
        # and out-of-tree engines may predate the deadline/abort contract)
        ccc = getattr(engine, "create_chat_completion", None)
        # multi-model routing: ONLY a registry (has_model is its marker)
        # takes the model= kwarg — plain engines never see it (the alias
        # was validated at admission, so not forwarding is correct), and
        # a signature probe would lie for engines with **kwargs
        # passthroughs (ContinuousEngine.create_chat_completion forwards
        # **kw into submit/submit_stream, which refuse model=)
        is_registry = callable(getattr(engine, "has_model", None))
        app.state.engine_kw = {
            "deadline": ccc is not None and _accepts_kwarg(ccc, "deadline"),
            "abort": ccc is not None and _accepts_kwarg(ccc, "abort"),
            "trace": ccc is not None and _accepts_kwarg(ccc, "trace"),
            "model": ccc is not None and is_registry,
            "submit_deadline": hasattr(engine, "submit") and _accepts_kwarg(
                engine.submit, "deadline"),
            "submit_trace": hasattr(engine, "submit") and _accepts_kwarg(
                engine.submit, "trace"),
            "submit_model": hasattr(engine, "submit") and is_registry,
        }
        # engines observe prefill-slice timings straight into the app's
        # registry (obs/catalog.py prefill_slice_seconds); attribute
        # injection, not an import, so library/bench engines stay free
        if hasattr(engine, "metrics_sink"):
            engine.metrics_sink = app.state.metrics
        # hand the flight recorder the process context its bundles carry
        # (weakly held; obs/flightrec.py) — a later app wins, which is
        # exactly the live serving app.  The fleet provider is read
        # lazily at capture time so it sees the migration manager built
        # a few lines below (and its last-served affinity-key digest —
        # the attribution linking a replica's bundle to the conversation
        # and peers involved in the incident).
        def _replica_fleet_context(state=app.state):
            out = {"role": "replica",
                   "self": settings.migrate_self or None}
            mig = getattr(state, "migration", None)
            if mig is not None:
                out["migration"] = mig.status()
            return out

        _flightrec.FLIGHTREC.install(health=app.state.health, engine=engine,
                                     fleet=_replica_fleet_context)
        # disaggregated prefill/decode (serving/disagg/): arm the page
        # service and/or the remote-prefill client.  Misconfiguration
        # (no paged pool, registry, missing peer) refuses startup loudly
        # — the LFKT_WORKERS idiom — instead of serving half a fleet.
        if settings.disagg_role != "off":
            from ..serving.disagg import build_roles

            app.state.disagg = build_roles(
                settings.disagg_role, engine, settings,
                metrics=app.state.metrics, health=app.state.health)
        # fleet KV migration (serving/fleet/migrate.py): page service +
        # pull client, then scale-out warm-up BEFORE the READY flip so a
        # freshly scaled replica's first routed turn lands on a warm
        # radix tree.  Warm-up is bounded by the drain budget and every
        # failed pull inside it degrades with attribution — a cold or
        # absent fleet delays readiness by at most the budget.
        if settings.migrate:
            from ..serving.fleet.migrate import build_migration

            app.state.migration = await asyncio.to_thread(
                build_migration, engine, settings,
                metrics=app.state.metrics, health=app.state.health)
            await asyncio.to_thread(app.state.migration.warm_up)
        tl.absorb(getattr(engine, "startup", None))
        _settle_heap()
        app.state.ready = True
        app.state.health.transition(READY, "engine loaded")
        tl.ready_unix = time.time()
        tl.phase("app_start", t_hook, tl.ready_unix)
        app.state.startup_doc = tl.doc()
        if settings.watchdog and getattr(engine, "heartbeat", None) is None \
                and callable(getattr(engine, "models", None)):
            # multi-model registry: the engine watchdog is single-engine
            # (one heartbeat, one recovery contract) and gates off here
            # with attribution; per-engine scheduler deaths still surface
            # as EngineUnavailable 503s on their own submit paths
            logger.info("multi-model registry loaded: engine watchdog "
                        "gates off (single-engine contract — "
                        "docs/MULTIMODEL.md)")
        if settings.watchdog and getattr(engine, "heartbeat", None) is not None:
            # local import: engine.watchdog pulls the (jax-heavy) engine
            # package, which this module otherwise defers to the factory
            from ..engine.watchdog import Watchdog

            app.state.watchdog = Watchdog(
                engine, app.state.health, app.state.metrics,
                stall_seconds=settings.watchdog_stall_seconds,
                poll_seconds=settings.watchdog_poll_seconds,
                max_recoveries=settings.watchdog_max_recoveries,
                error_burst=settings.watchdog_error_burst,
                error_window=settings.watchdog_error_window,
                backoff_seconds=settings.watchdog_backoff_seconds,
                backoff_max=settings.watchdog_backoff_max,
            ).start()
        app.state.consumer_task = asyncio.create_task(consumer())
        # SIGHUP = re-read LFKT_MODELS and converge the running registry
        # to it (the POST /admin/models/reload twin for operators who
        # patch the pod env / mounted config rather than POSTing —
        # docs/MULTIMODEL.md "Live manifest reload").  Registered only
        # where signals are available (main thread); no-op refusal with
        # attribution on single-model pods.
        if hasattr(signal, "SIGHUP"):
            try:
                asyncio.get_running_loop().add_signal_handler(
                    signal.SIGHUP,
                    lambda: _spawn(_reload_from_env("SIGHUP")))
            except (NotImplementedError, RuntimeError, ValueError):
                # non-main thread (tests/embedding) or unsupported
                # platform: the admin route remains the reload surface
                pass

    @app.on_event("shutdown")
    async def shutdown_event():
        if app.state.watchdog is not None:
            # stop() joins the watchdog thread — a blocking wait that
            # must not run on the event loop (lfkt-lint ASY001): the
            # loop keeps draining in-flight responses while the join
            # rides a worker thread
            watchdog, app.state.watchdog = app.state.watchdog, None
            await asyncio.to_thread(watchdog.stop)
        if app.state.disagg is not None:
            disagg, app.state.disagg = app.state.disagg, None
            await asyncio.to_thread(disagg.close)
        if app.state.migration is not None:
            migration, app.state.migration = app.state.migration, None
            await asyncio.to_thread(migration.close)

    def _enqueue_rd(request: Request, messages: list[dict],
                    extra: dict | None = None, *, model: str | None = None,
                    params: dict | None = None, raw: bool = False,
                    openai: bool = False) -> dict:
        """Admission core shared by /response and the /v1 facade: enqueue
        ``messages`` with a future, 503 on overflow.  ``raw`` skips the
        reference truncation quirks (OpenAI clients own their history);
        ``params`` carries per-request sampling overrides; ``openai``
        shapes the result as the full completion dict."""
        queue = request.app.state.queue
        m = request.app.state.metrics
        now = time.time()
        # per-request deadline: the admission timeout (or the stream's
        # wall-clock budget) becomes an absolute deadline threaded into the
        # engine (deadline propagation), so a timed-out request frees its
        # lane/slot within one decode step instead of generating for nobody
        budget = (settings.stream_deadline_seconds
                  if extra and "stream_queue" in extra
                  else settings.timeout_seconds)
        trace = request.scope.get("lfkt.trace")
        rd = {
            "messages": messages,
            "future": asyncio.get_running_loop().create_future(),
            "enqueued_at": now,
            "deadline": now + budget,
            "trace": trace,
            "model": model,
            "params": params,
            "raw": raw,
            "openai": openai,
            **(extra or {}),
        }
        try:
            queue.put_nowait(rd)
        except asyncio.QueueFull:
            m.inc("requests_rejected_total")
            if trace is not None:
                trace.event("admission_rejected", queue_depth=queue.qsize())
            raise HTTPException(status_code=503,
                                detail="Server too busy. Please try again later.")
        if trace is not None:
            trace.note(deadline=rd["deadline"])
            if model is not None:
                trace.note(model=model)
        m.set_gauge("queue_depth", queue.qsize())
        return rd

    async def _migrate_hook(request: Request, messages: list[dict],
                            raw: bool = False) -> None:
        """Pull-on-remap (serving/fleet/migrate.py): when the fleet
        router stamped this request, record the conversation's affinity
        key (graceful drain's candidate set) and — if a prior owner is
        named — pull its radix pages over the disagg wire BEFORE the
        prefill that would otherwise recompute them.  Never raises and
        never blocks past the migration hop budget: a failed pull is an
        attributed degrade to a colder (but correct) local prefill."""
        mgr = request.app.state.migration
        if mgr is None:
            return
        headers = request.headers
        key = headers.get(AFFINITY_KEY_HEADER, "")
        prior = headers.get(PRIOR_OWNER_HEADER, "")
        if not key and not prior:
            return
        engine = request.app.state.engine
        tokenize = getattr(engine, "tokenize_messages", None)
        if tokenize is None:
            return
        try:
            # mirror the prompt the engine will actually see: the
            # reference truncation mutates in place, so feed it copies
            msgs = messages if raw else truncate_messages_to_fit_context(
                [dict(m) for m in messages], settings.max_context_tokens)
            ids = await asyncio.to_thread(tokenize, msgs)
        except Exception:  # noqa: BLE001 — a tokenizer quirk must not
            # fail admission; the request just prefills cold
            return
        ns = str(getattr(engine, "_kv_ns", "") or "")
        if key:
            mgr.record_prompt(key, ns, ids)
        if prior:
            await asyncio.to_thread(
                mgr.pull_for_request, prior, ns, ids,
                time.time() + settings.timeout_seconds,
                request.scope.get("lfkt.trace"))

    async def _admit(request_body: BotMessageRequest, request: Request,
                     extra: dict | None = None) -> dict:
        """Shared admission for both response endpoints: assemble messages
        (system prompt inserted at index 1 — quirk preserved from reference
        api.py:147), validate the optional model alias (400 in the existing
        {"detail": ...} shape), enqueue with a future, 503 on overflow."""
        model = _validate_model(request_body.model)
        messages = [
            {"role": message.turn, "content": message.message}
            for message in request_body.context
        ]
        system_prompt = build_system_prompt(request_body.bot_profile)
        messages.insert(1, {"role": "system", "content": system_prompt})
        await _migrate_hook(request, messages)
        return _enqueue_rd(request, messages, extra, model=model)

    @app.post("/response")
    async def generate_response(request_body: BotMessageRequest, request: Request):
        m = request.app.state.metrics
        rd = await _admit(request_body, request)
        future = rd["future"]
        try:
            response = await asyncio.wait_for(future, timeout=settings.timeout_seconds)
            return {"response": response}
        except asyncio.TimeoutError:
            logger.warning("Generation timed out")
            m.inc("requests_timed_out_total")
            future.cancel()
            raise HTTPException(status_code=408, detail="Generation timed out")
        except HTTPException:
            raise
        except Exception as e:  # noqa: BLE001 — api.py:171-173
            logger.error("Internal server error: %s", e)
            raise HTTPException(status_code=500,
                                detail=f"Internal server error: {str(e)}")

    @app.post("/response/stream")
    async def generate_response_stream(request_body: BotMessageRequest,
                                       request: Request):
        """Streaming variant of ``/response`` (BASELINE config "streaming
        completion"): same admission control (queue slot, 503 on overflow),
        same prompt assembly; emits server-sent events with OpenAI chunk
        dicts, terminated by ``data: [DONE]``.  Two timeouts bound the
        stream: the per-chunk gap (timeout_seconds, like the non-stream 408)
        AND a total wall-clock deadline (stream_deadline_seconds) so a
        slow-dripping generation cannot hold its queue slot forever."""
        m = request.app.state.metrics
        rd = await _admit(request_body, request,
                          extra={"stream_queue": asyncio.Queue()})
        loop = asyncio.get_running_loop()
        deadline = loop.time() + settings.stream_deadline_seconds
        trace = rd.get("trace")

        async def sse():
            # the SSE write phase outlives the middleware (chunks are sent
            # after the handler returns), so the stream span AND the trace
            # itself are closed here, in the generator's finally
            sspan = trace.span("stream") if trace is not None else None
            first_content = True    # not written yet (tracing only)
            n_events = 0
            try:
                while True:
                    gap = min(settings.timeout_seconds, deadline - loop.time())
                    try:
                        if gap <= 0:
                            raise asyncio.TimeoutError
                        chunk = await asyncio.wait_for(
                            rd["stream_queue"].get(), timeout=gap)
                    except asyncio.TimeoutError:
                        m.inc("requests_timed_out_total")
                        if sspan is not None:
                            sspan.event("stream_timeout")
                        yield ("data: "
                               + json.dumps({"error": "Generation timed out"})
                               + "\n\n")
                        return
                    if chunk is _STREAM_DONE:
                        yield "data: [DONE]\n\n"
                        return
                    if isinstance(chunk, Exception):
                        yield ("data: "
                               + json.dumps({"error": str(chunk)}) + "\n\n")
                        return
                    n_events += 1
                    if sspan is not None and first_content:
                        first_content = _mark_first_content(sspan, chunk)
                    yield "data: " + json.dumps(chunk) + "\n\n"
            finally:
                # runs on timeout, error, AND client disconnect (the ASGI
                # layer closes this generator when the transport drops):
                # cancelling the future is the one signal every engine path
                # watches, so the lane/slot is reclaimed within one decode
                # step instead of streaming to a dead socket until budget
                if not rd["future"].done():
                    rd["future"].cancel()
                if sspan is not None:
                    sspan.set(events=n_events)
                    sspan.end()
                app.state.tracer.finish(trace)

        return StreamingResponse(sse())

    # -- OpenAI-compatible facade (docs/MULTIMODEL.md) ---------------------
    # Same admission path as /response (bounded queue → 503, future
    # timeout → 408, scheduler lanes in continuous mode) behind the wire
    # contract OpenAI SDKs speak: model routing, chat.completion /
    # chat.completion.chunk envelopes, usage counts from the engine's own
    # timings, and the {"error": {...}} body on every failure.

    @app.get("/v1/models")
    async def v1_models():
        """The served model manifest, OpenAI list-shaped: one row per
        ROUTABLE registry alias (single-model pods list their one model).
        Mid-reload rows — ``loading`` (weights still coming up) and
        ``draining`` (leaving; new requests already 400) — are /health's
        business: advertising them here would invite traffic the router
        cannot place."""
        eng = app.state.engine
        models_fn = getattr(eng, "models", None)
        if callable(models_fn):
            names = [r["name"] for r in models_fn()
                     if r.get("state") in (None, "ready", "loaded")]
        else:
            names = [getattr(eng, "model_name", None)
                     or app.state.settings.model_name]
        return {
            "object": "list",
            "data": [{"id": n, "object": "model",
                      "created": app.state.created, "owned_by": "lfkt"}
                     for n in names],
        }

    # -- live manifest reload (serving/registry.py; docs/MULTIMODEL.md) ----
    async def _do_reload(manifest: str, default: str) -> dict:
        """Run one registry reload on a worker thread (loads/warmups take
        seconds-minutes; traffic on the live models keeps flowing)."""
        eng = app.state.engine
        reload_fn = getattr(eng, "reload_manifest", None)
        if not callable(reload_fn):
            raise HTTPException(
                status_code=400,
                detail="live reload requires manifest serving: this pod "
                       "runs a single engine (set LFKT_MODELS — "
                       "docs/MULTIMODEL.md)")
        if not manifest:
            raise HTTPException(
                status_code=400,
                detail="no manifest: pass {\"models\": \"name=path,...\"} "
                       "or set LFKT_MODELS on the pod")
        return await asyncio.to_thread(
            reload_fn, manifest, default,
            drain_seconds=settings.reload_drain_seconds)

    async def _reload_from_env(origin: str) -> None:
        """The SIGHUP path: env is re-read at signal time, so editing the
        pod's LFKT_MODELS (mounted-config pattern) then HUPing converges
        the registry without a restart."""
        from ..utils.config import get_settings as _fresh_settings

        live = _fresh_settings()
        async with app.state.reload_busy:
            try:
                doc = await _do_reload(live.models, live.default_model)
                # model names may come from a POSTed manifest
                logger.info("%s reload: added=%s removed=%s default=%s",
                            origin, sanitize_text(str(doc["added"])),
                            sanitize_text(
                                str([r["name"] for r in doc["removed"]])),
                            sanitize_text(doc["default_model"]))
            except HTTPException as e:
                logger.error("%s reload refused: %s", origin, e.detail)
            except Exception as e:  # noqa: BLE001 — a failed background
                # reload must be loud but never kill the serving loop
                logger.error("%s reload failed: %s", origin, e)

    @app.post("/admin/models/reload")
    async def admin_models_reload(request: Request):
        """Diff a new ``LFKT_MODELS`` manifest against the running
        registry and converge to it live: additions load under the fit
        check + weight budget (409 on refusal, running set untouched),
        removals drain their in-flight requests and radix namespace
        before the weights release.  Body (all optional): ``models`` (the
        manifest string; default = the pod's current LFKT_MODELS env,
        re-read), ``default_model``.  Returns the registry's reload
        report.  409 while another reload runs."""
        from ..serving import WeightBudgetError
        from ..utils.config import get_settings as _fresh_settings

        try:
            body = await request.json()
        except ValueError:
            raise HTTPException(status_code=400, detail="body must be JSON")
        body = body if isinstance(body, dict) else {}
        live = _fresh_settings()
        manifest = body.get("models") or live.models
        default = body.get("default_model") or live.default_model
        if app.state.reload_busy.locked():
            raise HTTPException(
                status_code=409,
                detail="a reload is already in progress; retry after it "
                       "completes (/health models rows show the "
                       "transition)")
        async with app.state.reload_busy:
            try:
                return await _do_reload(manifest, default)
            except WeightBudgetError as e:
                raise HTTPException(status_code=409, detail=str(e))
            except ValueError as e:
                raise HTTPException(status_code=400, detail=str(e))

    @app.get("/admin/migrate/hot")
    async def admin_migrate_hot(request: Request):
        """This pod's hottest cached prefixes (``KVPool.hot_prefixes``)
        — what a scale-out peer pre-pulls during warm-up
        (serving/fleet/migrate.py).  ``?k=N`` bounds the list (default
        LFKT_MIGRATE_TOP_K).  404-shaped refusal when migration is off:
        a mixed-rollout fleet must get attribution, not a hang."""
        mgr = app.state.migration
        if mgr is None:
            raise HTTPException(
                status_code=404,
                detail="KV migration is off on this pod (LFKT_MIGRATE=1 "
                       "arms it — docs/RUNBOOK.md 'Surviving pod churn')")
        from urllib.parse import parse_qs

        q = parse_qs(request.url.query)
        try:
            k = int(q.get("k", [mgr.top_k])[0])
        except ValueError:
            raise HTTPException(status_code=400, detail="k must be an int")
        pool = getattr(app.state.engine, "_kvpool", None)
        rows = (await asyncio.to_thread(pool.hot_prefixes, k)
                if pool is not None else [])
        return {"prefixes": rows}

    @app.post("/admin/migrate/pull")
    async def admin_migrate_pull(request: Request):
        """Commanded pull — the receiving half of a peer's graceful
        drain (serving/fleet/migrate.py ``drain_push``): the DRAINING
        pod names itself (``peer`` = its page-service wire addr) and the
        conversation (``namespace`` + ``ids``); this pod pulls the pages
        over the wire while the peer still lives.  Deadline-bounded and
        never a hang; a failed pull answers ``covered: 0`` with the
        degrade attributed in this pod's counters."""
        mgr = app.state.migration
        if mgr is None:
            raise HTTPException(
                status_code=404,
                detail="KV migration is off on this pod (LFKT_MIGRATE=1 "
                       "arms it — docs/RUNBOOK.md 'Surviving pod churn')")
        try:
            body = await request.json()
        except ValueError:
            raise HTTPException(status_code=400, detail="body must be JSON")
        body = body if isinstance(body, dict) else {}
        peer = str(body.get("peer") or "")
        ids = body.get("ids")
        if ":" not in peer or not isinstance(ids, list) or not ids:
            raise HTTPException(
                status_code=400,
                detail="body needs peer (host:port of the drain side's "
                       "page service) and ids (non-empty token list)")
        deadline = body.get("deadline")
        covered = await asyncio.to_thread(
            mgr.pull, peer, [int(t) for t in ids],
            namespace=str(body.get("namespace") or ""), reason="drain",
            deadline=float(deadline) if deadline is not None else None)
        return {"covered": covered}

    def _v1_params(body: ChatCompletionRequest) -> dict:
        """The request's explicitly-set sampling fields (unset ones fall
        back to the pod's serving defaults in _gen_kwargs)."""
        return {k: v for k, v in dict(
            temperature=body.temperature,
            top_p=body.top_p,
            frequency_penalty=body.frequency_penalty,
            presence_penalty=body.presence_penalty,
            max_tokens=body.max_tokens,
            stop=body.stop,
            seed=body.seed,
        ).items() if v is not None}

    def _v1_sse(rd, include_usage: bool):
        """/v1 streaming body: engine chunks as ``chat.completion.chunk``
        SSE events, OpenAI error envelopes on failure, an optional final
        usage chunk (stream_options.include_usage), then ``[DONE]``.
        Mirrors /response/stream's timeout/disconnect reclamation: the
        generator's finally cancels the future, which every engine path
        watches."""
        m = app.state.metrics
        loop = asyncio.get_running_loop()
        deadline = loop.time() + settings.stream_deadline_seconds
        trace = rd.get("trace")

        async def sse():
            sspan = trace.span("stream") if trace is not None else None
            first_content = True    # not written yet (tracing only)
            n_events = 0
            last = None
            try:
                while True:
                    gap = min(settings.timeout_seconds, deadline - loop.time())
                    try:
                        if gap <= 0:
                            raise asyncio.TimeoutError
                        chunk = await asyncio.wait_for(
                            rd["stream_queue"].get(), timeout=gap)
                    except asyncio.TimeoutError:
                        m.inc("requests_timed_out_total")
                        if sspan is not None:
                            sspan.event("stream_timeout")
                        yield ("data: " + json.dumps(_openai_error_body(
                            408, "Generation timed out")) + "\n\n")
                        return
                    if chunk is _STREAM_DONE:
                        t = rd.get("timings")
                        if include_usage and t is not None and last is not None:
                            p, c = t.get("prompt_tokens", 0), \
                                t.get("completion_tokens", 0)
                            yield "data: " + json.dumps({
                                "id": last.get("id"),
                                "object": "chat.completion.chunk",
                                "created": last.get("created"),
                                "model": last.get("model"),
                                "choices": [],
                                "usage": {"prompt_tokens": p,
                                          "completion_tokens": c,
                                          "total_tokens": p + c},
                            }) + "\n\n"
                        yield "data: [DONE]\n\n"
                        return
                    if isinstance(chunk, Exception):
                        status = 400 if isinstance(chunk, ValueError) else 500
                        yield ("data: " + json.dumps(_openai_error_body(
                            status, str(chunk))) + "\n\n")
                        return
                    last = chunk
                    n_events += 1
                    if sspan is not None and first_content:
                        first_content = _mark_first_content(sspan, chunk)
                    yield "data: " + json.dumps(chunk) + "\n\n"
            finally:
                if not rd["future"].done():
                    rd["future"].cancel()
                if sspan is not None:
                    sspan.set(events=n_events)
                    sspan.end()
                app.state.tracer.finish(trace)

        return StreamingResponse(sse())

    @app.post("/v1/chat/completions")
    async def v1_chat_completions(body: ChatCompletionRequest,
                                  request: Request):
        """OpenAI-compatible chat completions: non-streaming returns the
        engine's completion dict (usage counts from its timings);
        ``stream: true`` emits ``chat.completion.chunk`` SSE.  Unknown
        ``model`` → 400 with code ``model_not_found``."""
        m = request.app.state.metrics
        try:
            if body.n != 1:
                raise HTTPException(
                    status_code=400,
                    detail="n must be 1: this server returns a single "
                           "choice per request")
            if not body.messages:
                raise HTTPException(status_code=400,
                                    detail="messages must be non-empty")
            model = _validate_model(body.model)
            params = _v1_params(body)
            messages = [{"role": msg.role, "content": msg.content}
                        for msg in body.messages]
            await _migrate_hook(request, messages, raw=True)
            if body.stream:
                rd = _enqueue_rd(request, messages,
                                 {"stream_queue": asyncio.Queue()},
                                 model=model, params=params, raw=True,
                                 openai=True)
                return _v1_sse(rd, include_usage=bool(
                    body.stream_options and
                    body.stream_options.include_usage))
            rd = _enqueue_rd(request, messages, model=model, params=params,
                             raw=True, openai=True)
            try:
                answer = await asyncio.wait_for(
                    rd["future"], timeout=settings.timeout_seconds)
            except asyncio.TimeoutError:
                logger.warning("Generation timed out")
                m.inc("requests_timed_out_total")
                rd["future"].cancel()
                raise HTTPException(status_code=408,
                                    detail="Generation timed out")
            return JSONResponse(answer)
        except HTTPException as e:
            return _openai_http_error(e)
        except Exception as e:  # noqa: BLE001 — facade contract: every
            # failure wears the OpenAI error envelope, including bugs
            logger.error("Internal server error: %s", e)
            return _openai_http_error(HTTPException(
                status_code=500, detail=f"Internal server error: {str(e)}"))

    def _resilience_info() -> dict:
        """Error-class + watchdog block for /health: the state machine,
        the trip/recovery counters, and the last engine error."""
        st = app.state
        info: dict = {"health": st.health.snapshot()}
        wd = st.watchdog
        if wd is not None:
            info["watchdog"] = {
                "trips": wd.trips,
                "recoveries": wd.recoveries,
                "max_recoveries": wd.max_recoveries,
                "last_trip_reason": wd.last_trip_reason,
                "stall_seconds": wd.stall_seconds,
            }
        hb = getattr(st.engine, "heartbeat", None)
        if hb is not None:
            info["engine_errors"] = {
                "total": hb.errors_total,
                "last": hb.last_error,
            }
        if FAULTS.armed():        # drills only: never present in production
            info["faults_armed"] = FAULTS.stats()
        return info

    @app.get("/health/ready")
    async def health_ready():
        """Readiness probe: 200 only in READY — a DEGRADED or DRAINING pod
        sheds traffic (503) while staying alive.  Helm's readinessProbe
        and startupProbe point here (helm/templates/deployment.yaml)."""
        h = app.state.health
        ok = h.ready()
        snap = h.snapshot()
        body = {"ready": ok, "state": snap["state"], "reason": snap["reason"]}
        return JSONResponse(body, 200 if ok else 503)

    @app.get("/health/live")
    async def health_live():
        """Liveness probe: 503 only in DEAD (recovery budget exhausted) —
        a briefly degraded pod recovering in-process must NOT be killed
        mid-recovery.  Helm's livenessProbe points here."""
        h = app.state.health
        ok = h.alive()
        snap = h.snapshot()
        body = {"alive": ok, "state": snap["state"], "reason": snap["reason"]}
        return JSONResponse(body, 200 if ok else 503)

    @app.get("/health")
    async def health():
        """Advertised by the reference README (README.md:14) but never
        implemented (SURVEY.md §3.5); the operator-facing health document.
        k8s probes use the split routes (/health/ready, /health/live) so
        "briefly degraded" and "kill me" are distinct answers."""
        st = app.state
        queue_depth = st.queue.qsize() if hasattr(st, "queue") else None
        if not st.ready:
            raise HTTPException(status_code=503, detail="model loading")
        eng = st.engine
        engine_info = None
        if eng is not None:
            cfg = getattr(eng, "cfg", None)
            # which linear layout each weight group actually serves with
            # (fused kernels may have probe-degraded to int8 — visible here)
            fmt = None
            params = getattr(eng, "params", None)
            if isinstance(params, dict) and "layers" in params:
                from ..models.params import flat_layers
                from ..serving.registry import linear_kind

                fmt = {
                    name: linear_kind(leaf)
                    for name, leaf in flat_layers(params["layers"])
                    if isinstance(leaf, dict)
                }
            engine_info = {
                "model": getattr(eng, "model_name", None),
                "n_ctx": getattr(cfg, "n_ctx", None),
                "attn_impl": getattr(cfg, "attn_impl", None),
                # what serves the vocabulary head (``_head_kernel``)
                "head_kernel": _head_kernel(params),
                # and the layers' stacked Q6_K linears (``_q6k_kernel``)
                "q6k_kernel": _q6k_kernel(params),
                # who stores a decode step's K/V row in the ring: the
                # decode kernel, or XLA (docs/KV_CACHE.md)
                "ring_write": _ring_write(cfg),
                "weight_formats": fmt,
                # KV-cache dtype + resident HBM bytes: the kv_dtype=int8
                # capacity win, verifiable per pod (docs/KV_CACHE.md)
                "kv_dtype": getattr(cfg, "kv_dtype", None),
                "kv_cache_bytes": getattr(eng, "kv_cache_bytes", None),
                # how the weights got here: per-phase load/warm-up seconds
                # and the native packer library (None = numpy codecs)
                "load_phases": getattr(eng, "load_phases", None),
                # process start to the READY flip, phase by phase, on
                # time.time() (docs/OBSERVABILITY.md "Start-up timeline")
                "startup": st.startup_doc,
                "native_lib": _native.loaded_path(),
                # the device as JAX reports it + peak device memory
                **_device_info(),
            }
            # paged KV pool occupancy (LFKT_KV_PAGED): pages used/free/
            # pinned, the spill tier, and the hit/eviction counters —
            # the "is my pool sized right" answer next to kv_cache_bytes
            # (docs/RUNBOOK.md "Sizing the KV page pool")
            occ = getattr(eng, "kv_pool_occupancy", None)
            if callable(occ):
                engine_info["kv_pool"] = occ()
            # a cache that is no ring (``CacheKind.health``): its sizes, and
            # the reuse it does without as a property of the cache; absent
            # on a ring, whose /health is what it was
            kind = getattr(eng, "cache_kind", None)
            if kind:
                engine_info["cache"] = kind
            # which of the kind's own reads serves (a latent ring's prefill
            # slices: ``latent_slice_read``); no key for most kinds
            engine_info.update(getattr(eng, "cache_engine_health", None) or {})
            # a routed file's grouped expert call: the slots of a decode
            # step's grid, of which it walks those in use
            # (expert_slots_skipped_total counts the rest); no key on a
            # dense block
            slots = getattr(eng, "expert_slots", 0)
            if slots:
                engine_info["expert_slots"] = slots
            # and, where the layer is compacted to the rows that reach a held
            # expert, the rows a step offers it (expert_rows_skipped_total
            # counts those it never multiplied)
            rows = getattr(eng, "expert_rows", 0)
            if rows:
                engine_info["expert_rows"] = rows
            # and the bodies those calls run, by family (a build's kernel
            # read without a trace, as ``head_kernel`` is)
            kernel = getattr(eng, "expert_kernel", None)
            if kernel:
                engine_info["expert_kernel"] = kernel
            # per cent of the resident fused planes' bytes that are zero
            # fill (a K filled up to the kernels' 2048 tile: the loader's
            # own sum); no key where no plane is fused
            fill = getattr(eng, "weight_fill_share", None)
            if fill is not None:
                engine_info["weight_fill_share"] = fill
            # a vocabulary the tokenizer cannot cut at spaces pays the
            # whole-text merge loop on every prompt (tokenizer/spm.py);
            # absent where it can
            fallback = getattr(eng, "tokenizer_fallback", None)
            if fallback:
                engine_info["tokenizer"] = fallback
            # multi-model registry: one row per served model (name, quant,
            # weight bytes, load state — docs/MULTIMODEL.md) next to the
            # kv_pool block; absent on single-model pods, whose /health is
            # byte-for-byte the pre-registry document
            models_fn = getattr(eng, "models", None)
            if callable(models_fn):
                engine_info["models"] = models_fn()
                engine_info["default_model"] = getattr(
                    eng, "default_model", None)
        doc = {
            "status": "ok",
            "state": st.health.state,
            "model_loaded": eng is not None,
            "queue_depth": queue_depth,
            "max_queue_size": st.settings.max_queue_size,
            "engine": engine_info,
            "resilience": _resilience_info(),
        }
        # disaggregated prefill/decode tier block (serving/disagg/): the
        # role, the page service's counters, and — on the decode side —
        # the peer state + the attributed reason pages stopped coming
        # (docs/RUNBOOK.md "Operating a split prefill/decode fleet");
        # absent on role=off pods, whose /health is byte-for-byte the
        # pre-disagg document
        if st.disagg is not None:
            doc["disagg"] = st.disagg.status()
        # fleet KV migration block (serving/fleet/migrate.py): the page
        # service's wire addr (peers resolve it through THIS document —
        # ephemeral ports are discovery, not config), every pull/push
        # counter, and the last attributed degrade; absent with
        # LFKT_MIGRATE off, keeping /health byte-identical
        if st.migration is not None:
            doc["migration"] = st.migration.status()
        return doc

    @app.get("/metrics")
    async def metrics():
        m = app.state.metrics
        if hasattr(app.state, "queue"):
            m.set_gauge("queue_depth", app.state.queue.qsize())
        # health/resilience gauges (error classes counters — timeouts,
        # 503s, watchdog trips/recoveries — are inc'd at their sites)
        m.set_gauge("health_state", STATE_CODES[app.state.health.state])
        hb = getattr(app.state.engine, "heartbeat", None)
        if hb is not None:
            m.set_gauge("engine_inflight", hb.busy_count())
            m.set_gauge("engine_error_count", hb.errors_total)
        kv_bytes = getattr(app.state.engine, "kv_cache_bytes", None)
        if kv_bytes is not None:
            m.set_gauge("kv_cache_bytes", kv_bytes)
        # multi-model capacity gauges (docs/MULTIMODEL.md): how many
        # models this pod serves and each one's resident weight bytes
        models_fn = getattr(app.state.engine, "models", None)
        if callable(models_fn):
            rows = models_fn()
            m.set_gauge("models_loaded", len(rows))
            for r in rows:
                m.set_gauge("model_weight_bytes", r["weight_bytes"],
                            model=r["name"])
        elif app.state.engine is not None:
            m.set_gauge("models_loaded", 1)
            wb = getattr(app.state.engine, "weight_bytes", 0)
            if wb:
                m.set_gauge("model_weight_bytes", wb,
                            model=_model_label())
        # paged KV pool occupancy gauges (the event counters —
        # misses/evictions/spills/restores + the reuse histogram — are
        # inc'd at event time by the pool through the injected sink)
        occ = getattr(app.state.engine, "kv_pool_occupancy", None)
        pool = occ() if callable(occ) else None
        if pool is not None:
            m.set_gauge("kv_pool_pages_used", pool["pages_used"])
            m.set_gauge("kv_pool_pages_free", pool["pages_free"])
        stats = getattr(app.state.engine, "scheduler_stats", None)
        if stats is not None:
            snap = stats()
            for k, v in snap.items():
                if isinstance(v, dict):   # nested stats: flatten
                    for kk, vv in v.items():  # — a dict-valued gauge renders
                        m.set_gauge(f"scheduler_{k}_{kk}", vv)  # invalid lines
                else:
                    m.set_gauge(f"scheduler_{k}", v)
            # first-class prefill-pipeline gauges (obs/catalog.py): the
            # admission controller's live budget + cumulative idle
            # lane-seconds, promoted out of the scheduler_ prefix family
            # so dashboards need no family-scrape to alert on them
            if "adm_budget_tokens" in snap:
                m.set_gauge("admission_budget_tokens",
                            snap["adm_budget_tokens"])
            if "lane_idle_seconds" in snap:
                m.set_gauge("lane_idle_seconds", snap["lane_idle_seconds"])
        # the decode steps' read of the KV ring against what was live
        # (Engine.cache_counts; the lane engine adds at each chunk's
        # harvest) and the same for a cache that is no ring, each kind under
        # its own names (Engine.cache_read_gauges)
        reads = getattr(app.state.engine, "cache_read_gauges", None)
        for name, value in (reads() if reads is not None else {}).items():
            # a name may carry labels (sparse_queries_total{branch="dense"})
            base, _, labels = name.partition("{")
            m.set_gauge(base, value, **dict(
                pair.split("=", 1) for pair in
                labels.rstrip("}").replace('"', "").split(",") if pair))
        fill = getattr(app.state.engine, "weight_fill_share", None)
        if fill is not None:
            m.set_gauge("weight_fill_share", fill)
        # routed layers (a file with experts): cumulative counters of the
        # decode chunks that have finished, folded here and not on the
        # decode path (engine/expert_counters.py)
        ec = getattr(app.state.engine, "expert_counters", None)
        if ec is not None:
            snap = ec.snapshot()
            m.set_gauge("expert_layer_steps_total", snap["layer_steps"])
            m.set_gauge("experts_read_total", snap["experts_read"])
            m.set_gauge("expert_slots_skipped_total", snap["slots_skipped"])
            m.set_gauge("expert_rows_skipped_total", snap["rows_skipped"])
            for e, n in enumerate(snap["picks"]):
                m.set_gauge("expert_picks_total", n, expert=str(e))
            m.set_gauge("expert_picks_routed_total", snap["picks_total"])
            m.set_gauge("expert_picks_held_total", snap["picks_held"])
            m.set_gauge("expert_picks_zero_total", snap["picks_zero"])
        # lfkt-mem: live HBM attribution gauges (obs/memledger.py) — one
        # series per (component, model), residual = ground truth minus the
        # attributed sum, headroom only where the backend reports limits.
        # The families are rebuilt WHOLE from the ledger each scrape: a
        # vanished row (drained spill tier, collected engine) must drop
        # its series, not freeze at its last value.  The reset→rebuild→
        # render sequence is atomic because this handler has NO await
        # between here and render() (one event loop, LFKT_WORKERS=1) —
        # inserting an await in between would let a concurrent scrape
        # render the family half-built
        m.reset_family("hbm_bytes")
        m.reset_family("hbm_headroom_bytes")
        if _memledger.MEMLEDGER.armed:
            mdoc = _memledger.MEMLEDGER.snapshot()
            for row in mdoc["components"]:
                m.set_gauge("hbm_bytes", row["bytes"],
                            component=row["component"], model=row["model"])
            if mdoc["residual_bytes"] is not None:
                m.set_gauge("hbm_bytes", mdoc["residual_bytes"],
                            component="residual", model="")
            if mdoc["headroom"] is not None:
                m.set_gauge("hbm_headroom_bytes", mdoc["headroom"]["bytes"])
        if _flightrec.FLIGHTREC.armed:
            m.set_gauge("incidents_total",
                        _flightrec.FLIGHTREC.recorded_total)
        # disagg wire liveness (the event counters — pages/bytes/
        # fallbacks — are inc'd at event time by the roles via the sink)
        dis = app.state.disagg
        if dis is not None and dis.client is not None:
            m.set_gauge("disagg_peer_connected",
                        1.0 if dis.client.connected() else 0.0)
        tstats = app.state.tracer.stats()
        m.set_gauge("trace_ring_used", tstats["ring_used"])
        m.set_gauge("traces_started_total", tstats["started_total"])
        m.set_gauge("traces_sampled_out_total", tstats["sampled_out_total"])
        # compile/dispatch attribution (obs/devtime.py): per-program
        # counters as snapshots, compile walls replayed into the histogram
        # exactly once via the app's event cursor
        for prog, c in DEVTIME.counters().items():
            m.set_gauge("xla_compiles_total", c["compiles"], program=prog)
            m.set_gauge("jit_dispatches_total", c["dispatches"],
                        program=prog)
            if c["intervals"]:      # stamped while the tracer is armed
                m.set_gauge("jit_device_seconds_total", c["device_s"],
                            program=prog)
                m.set_gauge("jit_device_intervals_total", c["intervals"],
                            program=prog)
        for prog, (n_l, s_l, n_b, s_b) in DEVTIME.store_ledger().items():
            if n_l or n_b:      # the executable store had a part in it
                m.set_gauge("executables_loaded_total", n_l, program=prog)
                m.set_gauge("executable_load_seconds_total", s_l,
                            program=prog)
                m.set_gauge("executables_built_total", n_b, program=prog)
                m.set_gauge("executable_build_seconds_total", s_b,
                            program=prog)
        store = DEVTIME.store
        m.set_gauge("executable_load_failures_total",
                    0 if store is None else store.load_failures)
        m.set_gauge("xla_recompile_storms_total", DEVTIME.storms_total)
        cursor, events = DEVTIME.events_since(app.state.devtime_cursor)
        app.state.devtime_cursor = cursor
        for ev in events:
            m.observe("xla_compile_seconds", ev["wall_s"],
                      program=ev["program"])
        m.set_gauge("xla_compile_events_dropped_total",
                    DEVTIME.events_dropped)
        # SLO burn rates over the series recorded above (obs/slo.py)
        app.state.slo.export()
        return PlainTextResponse(m.render())

    # -- lfkt-obs debug surface (docs/OBSERVABILITY.md) --------------------
    @app.get("/debug/traces")
    async def debug_traces():
        """Recent completed traces (newest first) + tracer stats; feed the
        JSON to tools/trace_report.py for latency waterfalls."""
        t = app.state.tracer
        return {"stats": t.stats(), "traces": t.traces()}

    @app.get("/debug/traces/{trace_id}")
    async def debug_trace(trace_id: str):
        """One trace's full span tree (in-flight or completed)."""
        tr = app.state.tracer.get(trace_id)
        if tr is None:
            raise HTTPException(status_code=404,
                                detail=f"no trace {trace_id!r} in the ring")
        return tr.to_dict()

    @app.get("/debug/requests")
    async def debug_requests():
        """In-flight request snapshot: engine, slot/lane, deadline
        remaining, tokens so far — the live answer to "what is this pod
        doing right now"."""
        return {"requests": app.state.tracer.inflight()}

    @app.get("/debug/compiles")
    async def debug_compiles():
        """The devtime program registry (obs/devtime.py): every registered
        jit program with its compile count, dispatch count, and the
        static-shape signatures it compiled — the "what is this pod
        recompiling" answer (docs/RUNBOOK.md recompile-storm runbook) —
        plus where the persistent compile cache lives and how often it
        hit (utils/jaxcache.py)."""
        return {**DEVTIME.snapshot(),
                "persistent_cache": compile_cache_stats()}

    @app.get("/debug/slo")
    async def debug_slo():
        """The SLO verdict document (obs/slo.py; docs/SLO.md): per-SLO
        multi-window burn rates with per-series detail, plus the devtime
        recompile-storm state.  ``verdict`` is the pod's one-word answer:
        ok | warn | breach."""
        return app.state.slo.evaluate()

    @app.get("/debug/memory")
    async def debug_memory():
        """The live HBM memory ledger (obs/memledger.py): per-component
        attribution with a residual line reconciled against device ground
        truth, headroom, and — when the paged KV pool serves — arena
        fragmentation (largest contiguous free run vs free pages).  The
        "where did my HBM go" answer (docs/RUNBOOK.md 'Diagnosing HBM
        OOM')."""
        doc = _memledger.MEMLEDGER.snapshot()
        occ = getattr(app.state.engine, "kv_pool_occupancy", None)
        pool = occ() if callable(occ) else None
        if pool is not None and doc.get("armed"):
            free = pool.get("pages_free")
            run = pool.get("largest_free_run")
            doc["kv_pool"] = pool
            if free and run is not None:
                doc["fragmentation"] = {
                    "pages_free": free,
                    "largest_free_run": run,
                    # 0 = one contiguous run; →1 = maximally shattered
                    "ratio": round(1.0 - run / free, 4),
                }
        return doc

    @app.get("/debug/incidents")
    async def debug_incidents():
        """The incident flight recorder's on-disk ring (obs/flightrec.py):
        bundle summaries, newest first.  Empty (armed: false) until
        LFKT_INCIDENT_DIR is set."""
        fr = _flightrec.FLIGHTREC
        # bundle summaries come off DISK (full-ring reads, potentially
        # MBs of traces): a worker thread, never the event loop — this
        # endpoint gets hit exactly when the pod is already degraded
        incidents = await asyncio.to_thread(fr.list) if fr.armed else []
        return {"armed": fr.armed,
                "recorded_total": fr.recorded_total,
                "debounced_total": fr.debounced_total,
                "incidents": incidents}

    @app.get("/debug/incidents/{incident_id}")
    async def debug_incident(incident_id: str):
        """One full incident bundle read back from disk: memory ledger,
        in-flight traces at capture time, scheduler stats, health
        transitions, recompile-storm state, log tail."""
        doc = await asyncio.to_thread(_flightrec.FLIGHTREC.get, incident_id)
        if doc is None:
            raise HTTPException(
                status_code=404,
                detail=f"no incident {incident_id!r} in the ring")
        return doc

    @app.get("/debug/profile")
    async def debug_profile(request: Request):
        """Bounded on-demand XProf capture (utils/tracing.py).  Opt-in:
        403 until LFKT_PROFILE_DIR is set; 409 while a capture runs;
        ``?seconds=`` clamps to the capture bounds.  The capture blocks a
        worker thread, never the event loop."""
        from urllib.parse import parse_qs

        from ..utils.tracing import (
            ProfileBusy,
            ProfileDisabled,
            capture_profile,
        )

        q = parse_qs(request.url.query)
        try:
            seconds = float(q.get("seconds", ["2.0"])[0])
        except ValueError:
            raise HTTPException(status_code=400,
                                detail="seconds must be a number")
        if not math.isfinite(seconds):
            # nan/inf slide through min() clamps (nan<x is False) and
            # would hold the exclusive capture lock for the full maximum
            raise HTTPException(status_code=400,
                                detail="seconds must be finite")
        try:
            return await asyncio.to_thread(capture_profile, seconds)
        except ProfileDisabled as e:
            raise HTTPException(status_code=403, detail=str(e))
        except ProfileBusy as e:
            raise HTTPException(status_code=409, detail=str(e))

    @app.get("/items/{item_id}")
    async def read_item(item_id: int):
        # vestigial echo route kept for OpenAPI-surface parity (api.py:175-177)
        return {"item_id": item_id}

    def _route_template(method: str, path: str) -> str:
        """The matched route's path template — the bounded-cardinality
        ``route`` label value (``/items/{item_id}``, never ``/items/7``)."""
        for route in app.router.routes:
            if route.method == method and route.match(method, path) is not None:
                return route.path
        return "unmatched"

    @app.middleware("http")
    async def log_request_time(request: Request, call_next):
        start_time = time.time()
        tracer = app.state.tracer
        # request identity: ingest the client's W3C traceparent (its trace
        # id becomes ours) or mint one; sampled-out requests still get a
        # request id for log stamping, just no span tree
        trace = tracer.start("request", t0=start_time,
                             traceparent=request.headers.get("traceparent"))
        rid = trace.trace_id if trace is not None else uuid.uuid4().hex
        request.scope["lfkt.trace"] = trace
        route = _route_template(request.method, request.url.path)
        if trace is not None:
            trace.root.set(method=request.method, route=route)
            trace.note(route=route)
            httpd_read = request.scope.get("lfkt.httpd_read")
            if httpd_read is not None:
                # the in-tree httpd's head+body read window (slowloris
                # territory), handed through the ASGI scope
                trace.span("httpd.read", t0=httpd_read[0]).end(httpd_read[1])
        def finalize(status: int) -> None:
            time_of_day = datetime.now().strftime("%Y-%m-%d %H:%M:%S")
            process_time = time.time() - start_time
            app.state.metrics.observe("request_seconds", process_time,
                                      route=route)
            app.state.metrics.inc("http_requests_total", route=route,
                                  code=str(status))
            # structured access line: JSON under setup_json_logging, and
            # the request id rides every record either way
            access_logger.info(
                "Request at %s: %s %s completed in %.4fs",
                time_of_day, request.method, request.url, process_time,
                extra={"route": route, "method": request.method,
                       "status": status,
                       "duration_s": round(process_time, 6)},
            )
            if trace is not None:
                trace.root.set(status=status)

        with bind_request_id(rid):
            try:
                response = await call_next(request)
            except BaseException:
                # a middleware-layer failure: the outer handler shapes the
                # response; account for the request and close its trace
                finalize(500)
                tracer.finish(trace)
                raise
            finalize(response.status_code)
        response.headers.setdefault("x-request-id", rid)
        if trace is not None:
            response.headers.setdefault("traceparent", trace.traceparent())
            if not isinstance(response, StreamingResponse):
                # streaming responses finish their trace in the SSE
                # generator's finally (the body outlives this middleware)
                tracer.finish(trace)
        return response

    return app


def _mark_first_content(sspan, chunk) -> bool:
    """Stamp the traced ``stream`` span with ``first_content`` at the write
    of the first chunk that carries text — where the client's clock for
    the time to first token stops.  Returns whether it is still to come."""
    try:
        if not chunk["choices"][0]["delta"].get("content"):
            return True
    except (KeyError, IndexError, TypeError, AttributeError):
        return True
    sspan.event("first_content")
    return False


def _head_kernel(params) -> str | None:
    """``/health`` ``engine.head_kernel``: what serves the vocabulary head
    (serving/registry.py ``head_kind``: ``q6k-head`` for a Q6_K head in the
    split layout, any other head what ``weight_formats`` would say of it, a
    tied or float head ``bf16``); None without parameters."""
    from ..serving.registry import head_kind

    leaf = params.get("output") if isinstance(params, dict) else None
    return head_kind(leaf) if isinstance(leaf, dict) else None


def _q6k_kernel(params) -> str | None:
    """``/health`` ``engine.q6k_kernel``: the body the layers' stacked Q6_K
    linears run (serving/registry.py ``stacked_q6k_kind``: ``q6k-int`` for
    the split layout, the integer dequantization the head and the grouped
    expert calls share); None without parameters or without such a tensor."""
    if not isinstance(params, dict) or "layers" not in params:
        return None
    from ..models.params import flat_layers
    from ..serving.registry import stacked_q6k_kind

    return stacked_q6k_kind(leaf for _, leaf in flat_layers(params["layers"])
                            if isinstance(leaf, dict))


def _ring_write(cfg) -> str | None:
    """``/health`` ``engine.ring_write``: who stores a decode step's K and V
    row in the ring, ``kernel`` or ``xla`` (models/llama.py
    ``ring_write_impl``: the configuration decides, as it decides
    ``attn_impl``); None without a model configuration or a ring."""
    from ..models.config import ModelConfig

    if not isinstance(cfg, ModelConfig):
        return None
    from ..models.llama import ring_write_impl

    return ring_write_impl(cfg)


def _device_info() -> dict:
    """What runs this engine, as JAX reports it: platform, device kind and
    count, and the peak device memory of the process so far (``/health``
    ``engine``; None where the backend keeps no memory statistics)."""
    import jax

    devs = jax.local_devices()
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [int(p) for p in peaks if p is not None]
    return {"platform": devs[0].platform, "device_kind": devs[0].device_kind,
            "device_count": len(devs),
            "peak_bytes_in_use": max(peaks) if peaks else None}


def _base_engine_kwargs(settings: Settings) -> dict:
    """Engine-constructor kwargs shared by the single-model factory and
    every registry entry (which then applies its manifest overrides)."""
    return dict(
        n_ctx=settings.max_context_tokens,
        weight_format=settings.weight_format,
        decode_chunk=settings.decode_chunk,
        prefill_buckets=settings.prefill_bucket_list,
        max_gen_tokens=settings.max_gen_tokens,
        attn_impl=settings.attn_impl,
        kv_dtype=settings.kv_dtype,
        prefix_cache=settings.prefix_cache,
        prefill_chunk=settings.prefill_chunk,
        prefill_overlap=settings.prefill_overlap,
        kv_paged=settings.kv_paged,
        kv_page_tokens=settings.kv_page_tokens,
        kv_pool_pages=settings.kv_pool_pages,
        kv_spill_pages=settings.kv_spill_pages,
    )


def _registry_factory(settings: Settings):
    """LFKT_MODELS is set: load the manifest into a ModelRegistry
    (serving/registry.py) — N engines sharing the chip, the paged KV pool
    (per-model namespaces) and an explicit HBM weight budget, all with
    the SAME scheduler shape (lanes/chunks/admission come from the
    process-wide knobs; per-model overrides are whitelisted engine knobs
    only — serving/manifest.py)."""
    from ..serving import ModelRegistry, parse_manifest, pick_default

    specs = parse_manifest(settings.models)
    default = pick_default(specs, settings.default_model)

    def build(spec, path, shared_pool):
        kw = _base_engine_kwargs(settings)
        kw.update(spec.overrides)
        kw["kv_pool"] = shared_pool
        kw["kv_namespace"] = spec.name
        return _build_engine(settings, path, kw)

    reg = ModelRegistry.from_specs(
        specs, build, default_model=default, model_dir=settings.model_dir,
        weight_budget_bytes=int(settings.hbm_weight_budget_mb * 1e6))
    reg.warmup()
    return reg


def _build_engine(settings: Settings, path, kw: dict):
    """The one place an engine class is chosen: the serial ``Engine`` for
    one lane, ``ContinuousEngine`` for ``LFKT_BATCH_SIZE`` > 1.  ``kw``:
    :func:`_base_engine_kwargs`, with a registry entry's overrides."""
    from ..engine import ContinuousEngine, Engine

    if settings.batch_size > 1:
        return ContinuousEngine(
            path, batch_size=settings.batch_size,
            adm_budget=settings.adm_budget,
            adm_controller=settings.adm_controller,
            adm_ema_alpha=settings.adm_ema_alpha,
            lane_prefix_cache=settings.lane_prefix_cache, **kw)
    return Engine(path, **kw)


def _default_engine_factory(settings: Settings):
    def factory():
        t_import = time.time()
        from .. import engine  # noqa: F401  (the jax-heavy import, timed)

        t_imported = time.time()
        if settings.models:
            # multi-model manifest: the registry replaces the single
            # engine; empty LFKT_MODELS keeps this path byte-for-byte
            return _registry_factory(settings)
        eng = _build_engine(settings, settings.model_path,
                            _base_engine_kwargs(settings))
        # the load thread's first import of engine/, models/, ops/pallas/
        eng.startup.phase("engine_import", t_import, t_imported)
        eng.warmup()
        return eng
    return factory


app = create_app()
