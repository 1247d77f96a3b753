"""ContinuousEngine: slot-based continuous batching over B lanes.

Covers: greedy parity with the serial Engine, more requests than lanes
(lane reuse), per-request error isolation, cancellation freeing a lane,
and the server's no-barrier forwarding path.
"""

from __future__ import annotations

import asyncio
import functools
import time

import numpy as np
import pytest

from llama_fastapi_k8s_gpu_tpu.engine import ContinuousEngine, Engine
from llama_fastapi_k8s_gpu_tpu.testing import write_tiny_llama_gguf

MSGS = [{"role": "user", "content": "Say something."}]


@pytest.fixture(scope="module")
def cengine(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("model") / "tiny.gguf")
    write_tiny_llama_gguf(path)
    eng = ContinuousEngine(path, batch_size=4, n_ctx=128,
                           decode_chunk=4, max_gen_tokens=16,
                           prefill_buckets=(32, 64, 128))
    yield eng
    eng.shutdown()


def test_greedy_parity_with_serial(cengine, tmp_path):
    path = str(tmp_path / "tiny.gguf")
    write_tiny_llama_gguf(path)
    serial = Engine(path, n_ctx=128, decode_chunk=4, max_gen_tokens=16,
                    prefill_buckets=(32, 64, 128))
    a = serial.create_chat_completion(MSGS, temperature=0.0, max_tokens=8)
    b = cengine.create_chat_completion(MSGS, temperature=0.0, max_tokens=8)
    assert a["choices"][0]["message"]["content"] == \
        b["choices"][0]["message"]["content"]


def test_more_requests_than_lanes(cengine):
    """8 requests over 4 lanes: all complete; lanes are reused."""
    futs = [cengine.submit(
        [{"role": "user", "content": f"request number {i}"}],
        temperature=0.0, max_tokens=4 + (i % 3)) for i in range(8)]
    outs = [f.result(timeout=120) for f in futs]
    assert all(o["object"] == "chat.completion" for o in outs)
    assert all(o["usage"]["completion_tokens"] >= 1 for o in outs)


def test_concurrent_admissions_in_one_round_are_correct(cengine, tmp_path):
    """Several COMPLETE admissions can now land in one scheduler iteration
    (_admit_round budget).  Every request in a 12-wide wave of distinct
    short prompts must produce exactly the serial engine's greedy output —
    pinning that back-to-back admissions through the shared scratch cache
    never bleed into each other."""
    path = str(tmp_path / "tiny.gguf")
    write_tiny_llama_gguf(path)
    serial = Engine(path, n_ctx=128, decode_chunk=4, max_gen_tokens=16,
                    prefill_buckets=(32, 64, 128))
    prompts = [[{"role": "user", "content": f"wave {i} " * (1 + i % 4)}]
               for i in range(12)]
    want = [serial.create_chat_completion(p, temperature=0.0, max_tokens=6)
            ["choices"][0]["message"]["content"] for p in prompts]
    futs = [cengine.submit(p, temperature=0.0, max_tokens=6) for p in prompts]
    got = [f.result(timeout=120)["choices"][0]["message"]["content"]
           for f in futs]
    assert got == want


def test_submissions_are_deterministic_under_concurrency(cengine):
    """A request's greedy output must not depend on lane neighbors."""
    solo = cengine.create_chat_completion(MSGS, temperature=0.0, max_tokens=8)
    futs = [cengine.submit(
        [{"role": "user", "content": f"noise {i} " * (i + 1)}],
        temperature=0.0, max_tokens=8) for i in range(3)]
    crowd = cengine.create_chat_completion(MSGS, temperature=0.0, max_tokens=8)
    for f in futs:
        f.result(timeout=120)
    assert solo["choices"][0]["message"]["content"] == \
        crowd["choices"][0]["message"]["content"]


def test_oversized_prompt_errors_alone(cengine):
    bad = cengine.submit([{"role": "user", "content": "x" * 600}])
    good = cengine.submit(MSGS, temperature=0.0, max_tokens=4)
    with pytest.raises(ValueError, match="exceed context window"):
        bad.result(timeout=60)
    assert good.result(timeout=120)["usage"]["completion_tokens"] >= 1


def test_cancelled_before_admission_is_skipped(cengine):
    # saturate lanes so a queued request can be cancelled pre-admission
    blockers = [cengine.submit(MSGS, temperature=0.0, max_tokens=12)
                for _ in range(4)]
    victim = cengine.submit(MSGS, max_tokens=4)
    cancelled = victim.cancel()
    done = [b.result(timeout=120) for b in blockers]
    assert all(d["object"] == "chat.completion" for d in done)
    if cancelled:
        assert victim.cancelled()
    else:  # raced: it got admitted first — must still complete
        assert victim.result(timeout=120)["object"] == "chat.completion"


def test_batch_facade_isolates_errors(cengine):
    outs = cengine.create_chat_completions(
        [[{"role": "user", "content": "x" * 600}], MSGS],
        temperature=0.0, max_tokens=4)
    assert "error" in outs[0]
    assert outs[1]["object"] == "chat.completion"


@pytest.mark.anyio
async def test_server_forwards_without_barrier():
    from tests.test_server import BODY, lifespan_client, make_client

    class RecordingContinuous:
        """submit-capable fake: resolves each future independently."""

        def __init__(self):
            self.n = 0
            self.last_timings = None

        def submit(self, messages, **kw):
            from concurrent.futures import Future

            self.n += 1
            f = Future()
            f.set_result({
                "object": "chat.completion",
                "choices": [{"message": {"role": "assistant",
                                         "content": f"c{self.n}"}}],
                "usage": {"completion_tokens": 1},
            })
            return f

    engine = RecordingContinuous()
    app, transport = make_client(engine, batch_size=4)
    async with transport:
        await app.router.startup()
        async with await lifespan_client(app, transport) as client:
            rs = await asyncio.gather(
                *[client.post("/response", json=BODY) for _ in range(5)])
            assert all(r.status_code == 200 for r in rs)
            assert engine.n == 5
        await app.router.shutdown()


def test_per_lane_sampling_isolation(cengine):
    """A greedy request's output must not change because a high-temperature
    neighbor was admitted mid-decode (per-lane sampling tensors)."""
    solo = cengine.create_chat_completion(MSGS, temperature=0.0, max_tokens=10)
    hot = [cengine.submit([{"role": "user", "content": f"hot {i}"}],
                          temperature=1.8, max_tokens=10, seed=i)
           for i in range(3)]
    cold = cengine.create_chat_completion(MSGS, temperature=0.0, max_tokens=10)
    for f in hot:
        f.result(timeout=120)
    assert solo["choices"][0]["message"]["content"] == \
        cold["choices"][0]["message"]["content"]


def test_max_tokens_one(cengine):
    out = cengine.create_chat_completion(MSGS, temperature=0.0, max_tokens=1)
    assert out["usage"]["completion_tokens"] == 1


def test_stream_via_lanes_matches_nonstream(cengine):
    """Streams ride scheduler lanes: chunk schema + greedy text parity."""
    ref = cengine.create_chat_completion(MSGS, temperature=0.0, max_tokens=8)
    chunks = list(cengine.create_chat_completion(
        MSGS, stream=True, temperature=0.0, max_tokens=8))
    assert chunks[0]["choices"][0]["delta"] == {"role": "assistant"}
    assert chunks[-1]["choices"][0]["finish_reason"] in ("stop", "length")
    assert chunks[-1]["lfkt_timings"]["completion_tokens"] >= 1
    text = "".join(c["choices"][0]["delta"].get("content", "")
                   for c in chunks)
    assert text == ref["choices"][0]["message"]["content"]


def test_stream_concurrent_with_batch(cengine):
    """A stream and batched futures decode concurrently in separate lanes;
    the stream's greedy text is unaffected by its neighbors."""
    solo = cengine.create_chat_completion(MSGS, temperature=0.0, max_tokens=10)
    it = cengine.create_chat_completion(
        MSGS, stream=True, temperature=0.0, max_tokens=10)
    futs = [cengine.submit([{"role": "user", "content": f"bg {i}"}],
                           temperature=1.5, max_tokens=10, seed=i)
            for i in range(3)]
    text = "".join(c["choices"][0]["delta"].get("content", "") for c in it)
    for f in futs:
        assert f.result(timeout=120)["object"] == "chat.completion"
    assert text == solo["choices"][0]["message"]["content"]


def test_abandon_frees_lane(cengine):
    """An abandoned request's future resolves cancelled at the next chunk
    boundary instead of decoding to budget (VERDICT r1 #6)."""
    import time as _time
    from concurrent.futures import CancelledError

    fut = cengine.submit(MSGS, temperature=0.0, max_tokens=100)
    for _ in range(500):                       # wait until admitted
        if fut.running():
            break
        _time.sleep(0.01)
    cengine.abandon(fut)
    try:
        out = fut.result(timeout=60)
    except CancelledError:
        out = None                             # the expected path
    else:                                      # rare race: finished first
        assert out["object"] == "chat.completion"
    # the engine keeps serving afterwards
    ok = cengine.create_chat_completion(MSGS, temperature=0.0, max_tokens=4)
    assert ok["usage"]["completion_tokens"] >= 1


def test_stream_close_abandons_lane(cengine):
    """Closing a stream iterator mid-generation frees its lane; the engine
    keeps serving."""
    it = cengine.create_chat_completion(
        MSGS, stream=True, temperature=0.0, max_tokens=100)
    next(it)
    next(it)
    it.close()
    ok = cengine.create_chat_completion(MSGS, temperature=0.0, max_tokens=4)
    assert ok["usage"]["completion_tokens"] >= 1


def test_per_request_top_k(cengine):
    """top_k rides per-lane as a traced mask: k=1 at high temperature must
    reduce to greedy (only the argmax candidate survives the mask)."""
    greedy = cengine.create_chat_completion(MSGS, temperature=0.0, max_tokens=8)
    k1 = cengine.create_chat_completion(MSGS, temperature=1.5, top_k=1,
                                        max_tokens=8, seed=123)
    assert k1["choices"][0]["message"]["content"] == \
        greedy["choices"][0]["message"]["content"]


def test_stop_prefix_holdback_helper():
    f = Engine._stop_prefix_holdback
    assert f("abc#", ["##"]) == 1      # "#" could begin "##": withhold
    assert f("abc", ["##"]) == 0
    assert f("ab", ["abc"]) == 2
    assert f("xyab", ["abc", "yabZ"]) == 3  # longest candidate wins
    assert f("abc", ["abc"]) == 0      # full match is a cut, not a holdback


def test_stream_stop_string_holdback(cengine):
    """A stop string spanning a chunk boundary must not leak its prefix to
    the stream: streamed text == non-stream text, cut before the stop."""
    base = cengine.create_chat_completion(MSGS, temperature=0.0, max_tokens=12)
    text = base["choices"][0]["message"]["content"]
    stop = text[3:6]
    assert len(stop) == 3
    ref = cengine.create_chat_completion(
        MSGS, temperature=0.0, max_tokens=12, stop=[stop])
    chunks = list(cengine.create_chat_completion(
        MSGS, stream=True, temperature=0.0, max_tokens=12, stop=[stop]))
    stext = "".join(c["choices"][0]["delta"].get("content", "")
                    for c in chunks)
    assert stext == ref["choices"][0]["message"]["content"]
    assert stop not in stext


def test_abandon_queued_request_resolves_future(cengine):
    """Abandoning a still-queued request must resolve its future (a hung
    future would leak the server's inflight permit forever)."""
    from concurrent.futures import CancelledError

    blockers = [cengine.submit(MSGS, temperature=0.0, max_tokens=30)
                for _ in range(4)]
    victim = cengine.submit(MSGS, max_tokens=4)
    cengine.abandon(victim)
    try:
        victim.result(timeout=60)      # must resolve either way — never hang
    except CancelledError:
        pass
    assert victim.done()
    for b in blockers:
        assert b.result(timeout=120)["object"] == "chat.completion"


def test_serial_stream_close_midway_keeps_engine_usable(tmp_path):
    """Closing the serial stream generator early must not poison the
    engine's cache buffer (prefill donates it; _finish restores it)."""
    path = str(tmp_path / "tiny.gguf")
    write_tiny_llama_gguf(path)
    serial = Engine(path, n_ctx=128, decode_chunk=4, max_gen_tokens=16,
                    prefill_buckets=(32, 64, 128))
    it = serial.create_chat_completion(MSGS, stream=True, temperature=0.0,
                                       max_tokens=12)
    next(it)
    next(it)
    it.close()
    out = serial.create_chat_completion(MSGS, temperature=0.0, max_tokens=4)
    assert out["usage"]["completion_tokens"] >= 1


def test_shutdown_resolves_outstanding(tmp_path):
    from llama_fastapi_k8s_gpu_tpu.engine import ContinuousEngine

    path = str(tmp_path / "tiny.gguf")
    write_tiny_llama_gguf(path)
    eng = ContinuousEngine(path, batch_size=2, n_ctx=64,
                           decode_chunk=2, max_gen_tokens=64,
                           prefill_buckets=(32, 64))
    futs = [eng.submit(MSGS, max_tokens=60) for _ in range(4)]
    eng.shutdown()
    for f in futs:  # must resolve (result, cancellation, or shutdown error)
        try:
            f.result(timeout=30)
        except Exception:
            pass
        assert f.done()


def test_decode_progresses_during_admission_wave(cengine):
    """VERDICT r2 weak #4: live lanes must keep decoding while a wave of
    admissions prefills.  Simulated slow prefills (wrapping
    _dispatch_prefill_chunk with a sleep; the test buckets are one slice
    each) must NOT serialize into one long decode stall: with one admission
    slice overlapped per chunk, a live stream's inter-chunk gap stays ~one
    admission, where the round-2 loop stalled for the whole wave."""
    import time as _time

    # steady-state warmup (the same hygiene as
    # test_chunked_prefill_bounds_stall_per_slice): a live stream plus
    # concurrent admissions compile every program the measured phase uses
    # — slice prefill, the deferred-first-token path, lane writes — so
    # the gap assertion measures scheduling, not first-use jit compiles
    # (the module fixture deliberately skips engine.warmup(); run solo,
    # this test would otherwise time ~3 s of compiles into one gap)
    warm_it = iter(cengine.submit_stream(
        [{"role": "user", "content": "warm stream"}],
        temperature=0.0, max_tokens=8))
    next(warm_it)
    warm = [cengine.submit([{"role": "user", "content": f"warm {j}"}],
                           temperature=0.0, max_tokens=2) for j in range(2)]
    list(warm_it)
    for f in warm:
        f.result(timeout=120)

    # delay sets the separation between the two outcomes: overlapped
    # admission gaps sit near ONE delay, the old serialized wave near
    # (n_wave-1) of them.  0.25 left the bound a scheduler hiccup away
    # from a healthy run on a loaded box (measured 0.78 vs 0.75); 0.4
    # keeps the same discrimination with ~2x noise margin
    delay = 0.4
    n_wave = 4
    orig = cengine._dispatch_prefill_chunk
    admitted = []

    def slow_chunk(adm):
        if admitted:          # first request admits fast; the wave is slow
            _time.sleep(delay)
        admitted.append(adm["n_prompt"])
        return orig(adm)

    cengine._dispatch_prefill_chunk = slow_chunk
    # pin the per-wave admission budget to ONE slice for this test (and
    # park the admission controller, which would otherwise rewrite the
    # budget every wave): the decode-overlap bound being verified is
    # per-admission; the default budget intentionally admits several short
    # requests per iteration
    # (test_concurrent_admissions_in_one_round_are_correct covers that)
    budget_saved = cengine._adm_budget
    ctl_saved = cengine._adm_ctl
    cengine._adm_ctl = None
    cengine._adm_budget = 1
    try:
        stream = cengine.submit_stream(
            [{"role": "user", "content": "stream me"}],
            temperature=0.0, max_tokens=14)
        it = iter(stream)
        next(it)                          # role chunk: admitted + decoding
        gaps = []
        t_prev = _time.perf_counter()
        wave = None
        for i, chunk in enumerate(it):
            now = _time.perf_counter()
            gaps.append(now - t_prev)
            t_prev = now
            if i == 0:                    # stream is live: launch the wave
                wave = [cengine.submit(
                    [{"role": "user", "content": f"wave {j}"}],
                    temperature=0.0, max_tokens=2) for j in range(n_wave)]
        assert wave is not None
        for f in wave:
            f.result(timeout=120)
        # old behavior: one gap of >= (n_wave-ish)*delay while the whole wave
        # prefills back-to-back; new behavior bounds any gap near one delay.
        assert max(gaps) < (n_wave - 1) * delay, gaps
    finally:
        cengine._dispatch_prefill_chunk = orig
        cengine._adm_ctl = ctl_saved
        cengine._adm_budget = budget_saved


def test_chunked_prefill_bounds_stall_per_slice(tmp_path):
    """A long-prompt admission prefills in slices: live lanes' inter-chunk
    gap is bounded by ~one slice, not the whole bucket (the second half of
    VERDICT r2 weak #4 — vLLM-style chunked prefill)."""
    import time as _time

    path = str(tmp_path / "tiny.gguf")
    write_tiny_llama_gguf(path)
    # static one-slice budget (controller off): this test pins the
    # per-SLICE stall bound; the controller's budget-driven multi-slice
    # interleave is covered by tests/test_admission.py
    eng = ContinuousEngine(path, batch_size=2, n_ctx=128,
                           decode_chunk=4, max_gen_tokens=24,
                           prefill_buckets=(64,), prefill_chunk=16,
                           adm_budget=16, adm_controller=False)
    try:
        # compile the slice/decode programs first so measured gaps are
        # steady-state scheduling, not first-use jit compiles
        eng.submit([{"role": "user", "content": "y " * 40}],
                   temperature=0.0, max_tokens=2).result(timeout=300)

        # delay sized so the two outcomes stay separated on a contended
        # full-suite box: per-slice interleaving gaps ≈ delay (+ scheduler
        # noise measured up to ~0.2 s), a monolithic 4-slice stall ≥
        # 4×delay = 1.0 s — the 3×delay bound sits between with margin
        # on both sides (0.15/0.45 flaked at 0.474 under suite load)
        delay = 0.25
        orig = eng._dispatch_prefill_chunk
        n_slices = []

        def slow_chunk(adm):
            if n_slices:                 # first admission (the stream) is fast
                _time.sleep(delay)
            n_slices.append(adm["offset"])
            return orig(adm)

        eng._dispatch_prefill_chunk = slow_chunk
        stream = eng.submit_stream(
            [{"role": "user", "content": "stream me"}],
            temperature=0.0, max_tokens=20)
        it = iter(stream)
        next(it)                          # admitted + decoding
        gaps = []
        t_prev = _time.perf_counter()
        fut = None
        for i, _chunk in enumerate(it):
            now = _time.perf_counter()
            gaps.append(now - t_prev)
            t_prev = now
            if i == 0:   # long prompt: bucket 64 / slice 16 = 4 slices
                fut = eng.submit(
                    [{"role": "user", "content": "x " * 40}],
                    temperature=0.0, max_tokens=2)
        assert fut is not None
        fut.result(timeout=120)
        assert len([o for o in n_slices if o == 0]) >= 2  # 2nd admission ran
        # a 4-slice admission done in ONE stall would gap >= 4*delay; chunked
        # interleaving keeps every gap near one slice
        assert max(gaps) < 3 * delay, gaps
    finally:
        eng.shutdown()


def test_scheduler_stats_surface(cengine):
    """Occupancy stats for /metrics: keys present, consistent with config,
    and updated by the loop (lanes_live returns to 0 after drain)."""
    cengine.create_chat_completion(MSGS, temperature=0.0, max_tokens=4)
    stats = cengine.scheduler_stats()
    assert stats["batch_size"] == 4
    assert {"batch_size", "lanes_live", "pending",
            "admission_inflight"} <= set(stats)
    # prefill-pipeline surface: live admission budget (+ controller EMAs —
    # the default engine runs the controller) and cumulative idle seconds
    assert stats["adm_budget_tokens"] >= cengine._prefill_chunk
    assert 0.0 <= stats["adm_ema_idle"] <= 1.0
    assert 0.0 <= stats["adm_ema_pressure"] <= 1.0
    assert stats["lane_idle_seconds"] >= 0.0
    deadline = time.time() + 10
    while time.time() < deadline and cengine.scheduler_stats()["lanes_live"]:
        time.sleep(0.05)
    assert cengine.scheduler_stats()["lanes_live"] == 0
    assert cengine.scheduler_stats()["pending"] == 0


def test_ring_slot_counters_rise_with_the_lanes_positions(cengine):
    """``cache_counts`` (/metrics ``ring_slots_*_total``): per decode step, summed over the lanes that
    hold a request, the slots the attention read covered and the slots at
    or below the lane's position (n_ctx 128 is one block here, so a step
    reads 128 a lane).  Computed at harvest from host-held positions."""
    def totals():
        return cengine.cache_counts["read"], cengine.cache_counts["live"]

    deadline = time.time() + 10
    while time.time() < deadline and cengine.scheduler_stats()["lanes_live"]:
        time.sleep(0.05)
    read0, live0 = totals()
    out = cengine.create_chat_completion(MSGS, temperature=0.0, max_tokens=9)
    n_prompt = out["usage"]["prompt_tokens"]
    assert out["usage"]["completion_tokens"] == 9
    time.sleep(0.5)         # the pipelined in-flight chunk lands
    read, live = totals()
    read, live = read - read0, live - live0
    # the first token comes from prefill; tokens 2..9 take two chunks of 4
    # steps at positions n_prompt .. n_prompt + 7; the chunk that was
    # queued when the lane ended runs no step (the device knows the
    # lane's budget) and reads nothing
    assert read == 8 * 128
    assert live == sum(n_prompt + t + 1 for t in range(8))
    assert 0 < live < read


def test_outputs_independent_of_adm_budget(tmp_path):
    """The admission budget changes WHEN requests are admitted, never WHAT
    they produce: a wave of greedy requests must yield identical text at
    budget=1 (one slice per iteration, the round-3 behavior) and the
    default multi-admission budget."""
    path = str(tmp_path / "tiny.gguf")
    write_tiny_llama_gguf(path)
    prompts = [[{"role": "user", "content": f"budget wave {i} " * (1 + i % 3)}]
               for i in range(8)]

    def run(budget):
        eng = ContinuousEngine(path, batch_size=4, n_ctx=128,
                               decode_chunk=4, max_gen_tokens=16,
                               prefill_buckets=(32, 64, 128),
                               adm_budget=budget)
        try:
            if budget == 1:     # bypass the max(prefill_chunk, ...) clamp
                eng._adm_budget = 1
            futs = [eng.submit(p, temperature=0.0, max_tokens=8)
                    for p in prompts]
            return [f.result(timeout=300)["choices"][0]["message"]["content"]
                    for f in futs]
        finally:
            eng.shutdown()

    assert run(1) == run(512)


# ---------------------------------------------------------------------------
# lane-prefix KV reuse (LFKT_LANE_PREFIX_CACHE): a freed lane's finished
# conversation serves as the KV prefix for the next same-conversation
# admission — the scheduler's analogue of the serial engine's prompt cache
# ---------------------------------------------------------------------------

LP_SYS = ("You are a meticulous assistant who answers carefully. " * 4).strip()


def _lp_multiturn(reply=None, new="And another one please."):
    msgs = [
        {"role": "system", "content": LP_SYS},
        {"role": "user", "content": "Tell me something interesting please."},
    ]
    if reply is not None:
        msgs += [{"role": "assistant", "content": reply},
                 {"role": "user", "content": new}]
    return msgs


@pytest.fixture(scope="module")
def lp_engine(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("model") / "tiny-lp.gguf")
    write_tiny_llama_gguf(path)
    eng = ContinuousEngine(path, batch_size=2, n_ctx=512,
                           decode_chunk=4, max_gen_tokens=16,
                           prefill_chunk=16, lane_prefix_cache=True,
                           prefill_buckets=(64, 128, 256, 512))
    yield eng
    eng.shutdown()


def test_lane_prefix_reuse_fires_on_multiturn(lp_engine):
    t1 = lp_engine.create_chat_completion(_lp_multiturn(), temperature=0.0,
                                          max_tokens=8)
    assert t1["lfkt_timings"]["prefix_reused_tokens"] == 0
    reply = t1["choices"][0]["message"]["content"]
    t2 = lp_engine.create_chat_completion(_lp_multiturn(reply),
                                          temperature=0.0, max_tokens=8)
    reused = t2["lfkt_timings"]["prefix_reused_tokens"]
    assert reused >= lp_engine._prefill_chunk
    assert reused % lp_engine._prefill_chunk == 0      # chunk-aligned
    assert reused < t2["usage"]["prompt_tokens"]
    stats = lp_engine.scheduler_stats()
    assert stats["lane_prefix_hits"] >= 1
    assert stats["lane_prefix_reused_tokens"] >= reused
    assert t2["choices"][0]["message"]["content"]


def test_lane_prefix_repeated_reuse_stays_well_formed(lp_engine):
    """Back-to-back identical follow-ups keep reusing lane claims and keep
    producing complete responses.  (Cross-request token equality is NOT
    asserted: each request may reuse a different lane's claim — e.g. the
    previous request's own, which matches deeper — so the reused-KV
    prefixes differ by bf16 rounding and a near-tied greedy argmax can
    legitimately flip; the serial engine's tests pin reuse numerics.)"""
    t1 = lp_engine.create_chat_completion(_lp_multiturn(), temperature=0.0,
                                          max_tokens=8)
    reply = t1["choices"][0]["message"]["content"]
    for _ in range(3):
        out = lp_engine.create_chat_completion(_lp_multiturn(reply),
                                               temperature=0.0, max_tokens=8)
        assert out["lfkt_timings"]["prefix_reused_tokens"] >= \
            lp_engine._prefill_chunk
        assert out["choices"][0]["message"]["content"]
        assert out["usage"]["completion_tokens"] >= 1


def test_lane_prefix_explicit_seed_bypasses(lp_engine):
    t1 = lp_engine.create_chat_completion(_lp_multiturn(), temperature=0.0,
                                          max_tokens=8)
    reply = t1["choices"][0]["message"]["content"]
    t2 = lp_engine.create_chat_completion(_lp_multiturn(reply),
                                          temperature=0.0, max_tokens=8,
                                          seed=5)
    assert t2["lfkt_timings"]["prefix_reused_tokens"] == 0


def test_lane_prefix_divergent_prompt_no_reuse(lp_engine):
    lp_engine.create_chat_completion(_lp_multiturn(), temperature=0.0,
                                     max_tokens=8)
    other = [{"role": "system", "content": "Terse pirate bot speaks here."},
             {"role": "user", "content": "List three fruits right now."}]
    got = lp_engine.create_chat_completion(other, temperature=0.0,
                                           max_tokens=8)
    assert got["lfkt_timings"]["prefix_reused_tokens"] == 0
    assert got["choices"][0]["message"]["content"]


def test_lane_prefix_claim_bookkeeping_unit(lp_engine):
    """White-box: claim recording caps at the residency invariant and
    reuse lookup is chunk-aligned with the last-token guard."""
    import types

    chunk = lp_engine._prefill_chunk
    slot = types.SimpleNamespace(n_prompt=40, gens=[7, 8, 9],
                                 ids=list(range(40)))
    saved = list(lp_engine._lane_claims)
    try:
        lp_engine._free_lane(0, slot, [None, None])
        claim = lp_engine._lane_claims[0]
        # slots [0, 40+3-1): prompt + all gens except the last sampled one
        assert claim == list(range(40)) + [7, 8]
        # identical prompt: reuse rounds down to a chunk multiple and
        # never consumes the last real token
        ids = claim + [99] * 30
        reuse, src = lp_engine._find_lane_reuse(ids, len(ids))
        assert src == 0 and reuse == (len(claim) // chunk) * chunk
        # too-short share → no reuse
        reuse, src = lp_engine._find_lane_reuse([1] * 64, 64)
        assert reuse == 0 and src is None
    finally:
        lp_engine._lane_claims[:] = saved


def test_lane_prefix_a_live_lanes_prompt_is_a_claim(lp_engine):
    """Two requests behind one system line, sent together: the second is
    admitted while the first still decodes, and rides the first's prompt
    rows (a live lane writes from its prompt's end on, never below)."""
    before = lp_engine.scheduler_stats()["lane_prefix_hits"]
    saved = list(lp_engine._lane_claims)
    lp_engine._lane_claims[:] = [None] * len(saved)      # a cold engine
    long = "Answer with care and at length. " * 3
    a = lp_engine.submit(_lp_multiturn() + [
        {"role": "assistant", "content": long},
        {"role": "user", "content": "first caller"}],
        temperature=0.0, max_tokens=16)
    b = lp_engine.submit(_lp_multiturn() + [
        {"role": "assistant", "content": long},
        {"role": "user", "content": "second caller, other words"}],
        temperature=0.0, max_tokens=4)
    out_b, out_a = b.result(timeout=300), a.result(timeout=300)
    assert out_a["lfkt_timings"]["prefix_reused_tokens"] == 0
    reused = out_b["lfkt_timings"]["prefix_reused_tokens"]
    assert reused >= lp_engine._prefill_chunk
    assert reused % lp_engine._prefill_chunk == 0
    assert lp_engine.scheduler_stats()["lane_prefix_hits"] == before + 1
    assert out_b["choices"][0]["message"]["content"]


def test_lane_prefix_reuse_on_a_fresh_four_lane_engine(tmp_path):
    """The lane→scratch snapshot gather on the one device's lane state: a
    conversation's second turn rides the first's rows on an engine that
    has served nothing else."""
    path = str(tmp_path / "tiny-lp-fresh.gguf")
    write_tiny_llama_gguf(path)
    eng = ContinuousEngine(path, batch_size=4, n_ctx=512,
                           decode_chunk=4, max_gen_tokens=12,
                           prefill_chunk=16, lane_prefix_cache=True,
                           prefill_buckets=(64, 128, 256, 512))
    try:
        t1 = eng.create_chat_completion(_lp_multiturn(), temperature=0.0,
                                        max_tokens=8)
        reply = t1["choices"][0]["message"]["content"]
        t2 = eng.create_chat_completion(_lp_multiturn(reply),
                                        temperature=0.0, max_tokens=8)
        assert t2["lfkt_timings"]["prefix_reused_tokens"] >= 16
        assert t2["choices"][0]["message"]["content"]
    finally:
        eng.shutdown()


def test_lane_prefix_cache_defaults_on(tmp_path):
    """Round 6 flips LFKT_LANE_PREFIX_CACHE on: a default-constructed
    ContinuousEngine (and default Settings) serve with lane-claim reuse
    armed, and the interference regression that kept it off is guarded —
    a prefill-heavy admission wave through a default engine still matches
    the serial engine's greedy outputs request-for-request (reuse never
    fires across DISTINCT prompts; the multi-turn reuse path itself is
    covered by the lp_engine tests above)."""
    from llama_fastapi_k8s_gpu_tpu.utils.config import Settings, get_settings

    assert Settings.lane_prefix_cache is True
    assert get_settings().lane_prefix_cache is True

    path = str(tmp_path / "tiny-lp-default.gguf")
    write_tiny_llama_gguf(path)
    eng = ContinuousEngine(path, batch_size=2, n_ctx=128,
                           decode_chunk=4, max_gen_tokens=16,
                           prefill_buckets=(32, 64, 128))
    serial = Engine(path, n_ctx=128, decode_chunk=4, max_gen_tokens=16,
                    prefill_buckets=(32, 64, 128), prefix_cache=False)
    try:
        assert eng._lane_prefix is True          # the flipped default
        prompts = [[{"role": "user", "content": f"default wave {i} "
                     * (1 + i % 3)}] for i in range(6)]
        want = [serial.create_chat_completion(p, temperature=0.0,
                                              max_tokens=6)
                ["choices"][0]["message"]["content"] for p in prompts]
        futs = [eng.submit(p, temperature=0.0, max_tokens=6)
                for p in prompts]
        got = [f.result(timeout=120)["choices"][0]["message"]["content"]
               for f in futs]
        assert got == want
    finally:
        eng.shutdown()


def test_scratch_none_recovers(cengine):
    """A failed lane snapshot leaves _scratch_cache = None (the reuse path
    frees the old scratch BEFORE the copy so HBM never holds two rings —
    the 8-lane 16 GB OOM fix).  The next admission must lazily re-create
    it rather than crash the scheduler loop engine-wide."""
    cengine._scratch_cache = None
    out = cengine.create_chat_completion(
        [{"role": "user", "content": "recover please"}],
        temperature=0.0, max_tokens=4)
    assert out["usage"]["completion_tokens"] >= 1
    assert cengine._scratch_cache is not None


# ---------------------------------------------------------------------------
# disconnect/abandon reclaim bound (resilience layer): a dropped caller
# frees the engine within ~one decode chunk on every engine flavor
# ---------------------------------------------------------------------------

def test_abandon_stops_decode_within_one_chunk(cengine, monkeypatch):
    """After a stream is closed, the scheduler may finish the in-flight
    chunk plus the one pipelined behind it, then must stop dispatching
    (the abandoned lane is the only live one)."""
    from llama_fastapi_k8s_gpu_tpu.engine import continuous as cont

    calls = [0]
    orig = cont.batched_generate_chunk_perlane_jit

    def counting(*a, **kw):
        calls[0] += 1
        return orig(*a, **kw)

    monkeypatch.setattr(cont, "batched_generate_chunk_perlane_jit", counting)
    it = cengine.create_chat_completion(MSGS, stream=True, temperature=0.0,
                                        max_tokens=100)
    next(it)
    next(it)
    at_close = calls[0]
    it.close()                        # disconnect: abandon the lane
    # wait for dispatch quiescence (stats lag one loop iteration, so
    # polling lanes_live alone can read a stale zero mid-admission)
    deadline = time.time() + 20
    last, stable_since = calls[0], time.time()
    while time.time() < deadline:
        time.sleep(0.05)
        if calls[0] != last:
            last, stable_since = calls[0], time.time()
        elif time.time() - stable_since > 0.5:
            break
    assert cengine.scheduler_stats()["lanes_live"] == 0
    # in-flight + one pipelined chunk is the contract; slack for chunks
    # dispatched between the counter read and close() taking effect
    assert calls[0] - at_close <= 4, (calls[0], at_close)


def test_serial_stream_close_stops_decode_immediately(tmp_path):
    """Engine (serial): closing the stream iterator dispatches no further
    decode chunk — the generator dies at its yield point."""
    path = str(tmp_path / "tiny-close.gguf")
    write_tiny_llama_gguf(path)
    eng = Engine(path, n_ctx=128, decode_chunk=4, max_gen_tokens=100,
                 prefill_buckets=(32, 64, 128))
    calls = [0]
    orig = eng._decode_chunk_call

    def counting(*a, **kw):
        calls[0] += 1
        return orig(*a, **kw)

    eng._decode_chunk_call = counting
    it = eng.create_chat_completion(MSGS, stream=True, temperature=0.0,
                                    max_tokens=100)
    next(it)
    next(it)
    at_close = calls[0]
    it.close()
    assert calls[0] == at_close       # nothing dispatched after close
    out = eng.create_chat_completion(MSGS, temperature=0.0, max_tokens=4)
    assert out["usage"]["completion_tokens"] >= 1


# ---------------------------------------------------------------------------
# a lane's end on the device (PR 42): the chunk program carries what each
# lane has left to decode and stops stepping when no lane has anything left
# ---------------------------------------------------------------------------

@functools.cache
def _scan_chunk():
    """The chunk program as it was before the lanes' ends went to the
    device: a ``scan`` of ``n_steps`` whatever the lanes hold.  The
    reference the ``while_loop`` form is held to, token for token; behind
    the new signature (``left`` goes through untouched, no row is a pad)."""
    import jax

    from llama_fastapi_k8s_gpu_tpu.models.llama import forward
    from llama_fastapi_k8s_gpu_tpu.parallel.batched import step_bound
    from llama_fastapi_k8s_gpu_tpu.sampling.sample import (
        PENALTY_WINDOW, sample_chain)

    @functools.partial(jax.jit,
                       static_argnames=("cfg", "n_steps", "top_k", "stop_ids"))
    def scan_chunk(params, cfg, state, lane_st, left, n_steps, top_k=40,
                   live=None, stop_ids=()):
        def one_step(carry, _):
            bound = step_bound(cfg, carry["pos"], live)

            def single(token, pos, cache, window, wpos, key, st, live):
                logits, cache = forward(params, cfg, token[None], pos, cache,
                                        live=live, kv_bound=bound)
                key, sub = jax.random.split(key)
                tok = sample_chain(logits, window, sub, st, top_k=top_k)
                window = window.at[wpos % PENALTY_WINDOW].set(tok)
                return tok, pos + 1, cache, window, wpos + 1, key

            tok, pos, cache, window, wpos, key = jax.vmap(single)(
                carry["token"], carry["pos"], carry["cache"],
                carry["window"], carry["wpos"], carry["key"], lane_st, live)
            return {"cache": cache, "pos": pos, "token": tok,
                    "window": window, "wpos": wpos, "key": key}, tok

        state, toks = jax.lax.scan(one_step, state, None, length=n_steps)
        return state, left, toks

    return scan_chunk


@pytest.fixture(scope="module")
def lane_eng(tmp_path_factory):
    """One plain lane engine (one device), with every finished slot's
    tokens on record: ``eng.finished[response id] = (tokens, finish)``."""
    path = str(tmp_path_factory.mktemp("ends") / "tiny.gguf")
    write_tiny_llama_gguf(path)
    eng = ContinuousEngine(path, batch_size=4, n_ctx=128,
                           decode_chunk=4, max_gen_tokens=32,
                           prefill_buckets=(32, 64, 128))
    eng.finished = {}
    finish_slot = eng._finish_slot

    def recording(slot, finish):
        eng.finished[slot.cid] = (list(slot.gens), finish)
        return finish_slot(slot, finish)

    eng._finish_slot = recording
    yield eng
    eng.shutdown()


def _quiet(eng):
    """Wait until no lane is live and no chunk is in flight; the stats."""
    deadline = time.time() + 20
    while time.time() < deadline:
        st = eng.scheduler_stats()
        if not st["lanes_live"] and not st["pending"] \
                and st.get("chunks_dispatched", 0) == st.get("waves", 0):
            time.sleep(0.2)     # the queued chunk's harvest, one pass on
            # (an engine that has not run a pass yet shows no totals)
            return {**eng._zero_totals(), **eng.scheduler_stats()}
        time.sleep(0.02)
    raise AssertionError("the engine did not drain")


ENDS = ("budget", "stop_id", "stop_string", "abandoned", "queued")


@pytest.fixture(scope="module")
def mixed(lane_eng):
    """A mixed batch (five requests on four lanes, each with its own seed:
    three sampled, two greedy) whose requests end by budget, by a stop id,
    by a stop string, by being abandoned and, queued behind them, in a
    reused lane; run on the chunk program as it is and again on the
    ``scan`` form: {"got" | "want": {end: what the caller received}}."""
    from llama_fastapi_k8s_gpu_tpu.engine import continuous as cont

    eng = lane_eng
    msgs = {k: [{"role": "user", "content": f"{k} request " * (i + 1)}]
            for i, k in enumerate(ENDS)}
    # a stop id that the greedy answer samples as its sixth token or later
    probe = eng.create_chat_completion(msgs["stop_id"], temperature=0.0,
                                       max_tokens=14, seed=102)
    gens = eng.finished[probe["id"]][0]
    at = next(i for i in range(5, len(gens)) if gens[i] not in gens[:i])
    text = eng.create_chat_completion(
        msgs["stop_string"], temperature=0.0, max_tokens=14,
        seed=103)["choices"][0]["message"]["content"]
    stop_str = text[3:6]
    assert len(stop_str) == 3

    def batch():
        it = eng.create_chat_completion(
            msgs["abandoned"], stream=True, temperature=0.8, seed=104,
            max_tokens=30)
        futs = {
            "budget": eng.submit(msgs["budget"], temperature=0.8, seed=101,
                                 max_tokens=7),
            "stop_id": eng.submit(msgs["stop_id"], temperature=0.0, seed=102,
                                  max_tokens=14),
            "stop_string": eng.submit(msgs["stop_string"], temperature=0.0,
                                      seed=103, max_tokens=14,
                                      stop=[stop_str]),
            "queued": eng.submit(msgs["queued"], temperature=0.8, seed=105,
                                 max_tokens=9),
        }
        heard = [next(it)["choices"][0]["delta"] for _ in range(3)]
        it.close()
        out = {"abandoned": "".join(d.get("content", "") for d in heard)}
        for k, f in futs.items():
            o = f.result(timeout=120)
            out[k] = (o["choices"][0]["message"]["content"],
                      o["choices"][0]["finish_reason"],
                      o["usage"]["completion_tokens"])
        _quiet(eng)
        return out

    tok, program = eng.tokenizer, cont.batched_generate_chunk_perlane_jit
    tok.__class__ = type("OneStopId", (type(tok),), {
        "stop_ids": property(lambda self: {gens[at]})})
    try:
        before = _quiet(eng)
        got = batch()
        after = _quiet(eng)
        cont.batched_generate_chunk_perlane_jit = _scan_chunk()
        want = batch()
    finally:
        cont.batched_generate_chunk_perlane_jit = program
        tok.__class__ = type(tok).__mro__[1]
    return {"got": got, "want": want, "stop_at": at, "stop_str": stop_str,
            "disagreements": after["end_disagreements"]
            - before["end_disagreements"]}


@pytest.mark.parametrize("end", ENDS)
def test_token_streams_are_the_scan_forms_whatever_ends_a_request(mixed, end):
    """(a) What a caller receives does not depend on where the program
    stops stepping: the same text, finish and count as on the ``scan``
    form at the same seeds, for every kind of end; and the device and the
    harvest found every end at the same token."""
    got, want = mixed["got"][end], mixed["want"][end]
    assert got == want
    assert mixed["disagreements"] == 0
    if end == "abandoned":
        assert got                      # the caller heard text, then left
        return
    text, finish, n = got
    assert (finish, n) == {
        "budget": ("length", 7), "queued": ("length", 9),
        "stop_id": ("stop", mixed["stop_at"]),
        "stop_string": ("stop", n)}[end]
    if end == "stop_string":
        assert mixed["stop_str"] not in text and 1 <= n <= 14


def test_one_caller_waits_behind_no_chunk(lane_eng):
    """(b) One caller, three requests in a row: behind each request's end
    the queued chunk runs no step (``chunks_empty``), the steps run are
    the tokens decoded after each first token (plus the step that sampled
    a stop id), and EVERY later request's ``pending`` span is shorter
    than one decode step: it does not wait behind a chunk for nobody.
    The spans are a millisecond on the host's clock, and beside six busy
    workers a pass of three has a later span over a step one time in six
    on the parent's tree as on this one (PR 60, CHANGES.md): the three
    requests run again, at most twice, and one whole pass has to be under
    a step.  A wait behind a chunk is four steps long and in every pass."""
    from llama_fastapi_k8s_gpu_tpu.obs.trace import Tracer

    eng, tracer = lane_eng, Tracer(sample=1.0)
    before = _quiet(eng)
    steps, passes = 0, []
    for _ in range(3):
        pendings, step_s = [], []
        for i in range(3):
            tr = tracer.start()
            out = eng.create_chat_completion(
                [{"role": "user", "content": f"in a row {i}"}],
                temperature=0.0, max_tokens=10 + i, trace=tr)
            tracer.finish(tr)
            gens, finish = eng.finished[out["id"]]
            steps += max(len(gens) - 1, 0) + (finish == "stop" and bool(gens))
            pend = next(c for c in tr.to_dict()["root"]["children"]
                        if c["name"] == "pending")
            pendings.append(pend["end"] - pend["start"])
            t = out["lfkt_timings"]
            step_s.append(t["decode_s"] / max(t["completion_tokens"] - 1, 1))
        passes.append((pendings, step_s))
        if max(pendings[1:]) < min(step_s):
            break
    after = _quiet(eng)
    assert after["chunks_empty"] - before["chunks_empty"] >= 2 * len(passes)
    assert after["steps_run"] - before["steps_run"] == steps
    run = after["steps_run"] - before["steps_run"]
    skipped = after["steps_skipped"] - before["steps_skipped"]
    assert run + skipped == 4 * (after["chunks_dispatched"]
                                 - before["chunks_dispatched"])
    assert after["end_disagreements"] == before["end_disagreements"]
    pendings, step_s = passes[-1]
    assert max(pendings[1:]) < min(step_s), passes


def test_an_ended_lane_decodes_again_after_the_next_lane_write(lane_eng):
    """(c) A lane the device has ended is alive again once ``_write_lane``
    installs the next request in it: one caller's requests all take lane
    0, and each decodes to its own budget."""
    eng = lane_eng
    before = _quiet(eng)
    for n in (6, 9, 5):
        out = eng.create_chat_completion(
            [{"role": "user", "content": f"again {n}"}], temperature=0.0,
            max_tokens=n)
        gens, finish = eng.finished[out["id"]]
        assert finish == "stop" or len(gens) == n
        st = _quiet(eng)
        assert int(np.asarray(eng._lane_left)[0]) == 0   # ended again
    assert st["end_disagreements"] == before["end_disagreements"]


def _lane_program_case(lanes=3, n_ctx=96):
    import jax
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.models.config import ModelConfig
    from llama_fastapi_k8s_gpu_tpu.models.params import synth_params
    from llama_fastapi_k8s_gpu_tpu.parallel.batched import init_batched_state
    from llama_fastapi_k8s_gpu_tpu.sampling.sample import (
        SamplingParams, sampling_tensors)

    cfg = ModelConfig(vocab_size=64, dim=64, n_layers=2, n_heads=4,
                      n_kv_heads=2, ffn_dim=96, n_ctx=n_ctx)
    params = synth_params(cfg)
    st = jax.tree.map(lambda a: jnp.broadcast_to(a, (lanes,)),
                      sampling_tensors(SamplingParams(temperature=0.7)))

    def state():
        s = init_batched_state(cfg, lanes, seed=1)
        ks = jax.random.split(jax.random.PRNGKey(7), 2)
        s["cache"] = {k: jax.random.normal(kk, s["cache"][k].shape,
                                           jnp.bfloat16)
                      for k, kk in zip(("k", "v"), ks)}
        s["pos"] = jnp.asarray([20, 31, 33][:lanes], jnp.int32)
        s["token"] = jnp.asarray([5, 6, 7][:lanes], jnp.int32)
        return s

    return cfg, params, st, state


@pytest.mark.parametrize("entry", ["budget_1", "first_token_a_stop_id",
                                   "alive"])
def test_a_lane_ended_at_entry_is_never_stepped(entry):
    """(d) ``_write_lane`` with a budget of one token, or with a first
    token that is a stop id, leaves the lane ended: the chunk program runs
    no step for it (every row a pad, ``left`` stays 0).  With a budget and
    an ordinary first token it decodes."""
    import jax
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.engine.continuous import _write_lane
    from llama_fastapi_k8s_gpu_tpu.models.llama import init_cache
    from llama_fastapi_k8s_gpu_tpu.parallel.batched import (
        batched_generate_chunk_perlane_jit, init_lane_left)
    from llama_fastapi_k8s_gpu_tpu.sampling.sample import (
        PENALTY_WINDOW, SamplingParams, sampling_tensors)

    cfg, params, st, state = _lane_program_case()
    budget, stop_ids = {"budget_1": (1, (9,)), "alive": (6, (9,)),
                        "first_token_a_stop_id": (6, (9, 5))}[entry]
    s, st, left = _write_lane(
        state(), st, init_lane_left(3), jnp.int32(1), init_cache(cfg),
        jnp.int32(12), jnp.int32(5), jnp.full(PENALTY_WINDOW, -1, jnp.int32),
        jnp.int32(0), jax.random.PRNGKey(3),
        sampling_tensors(SamplingParams(temperature=0.0)),
        jnp.int32(budget - 1), stop_ids=stop_ids)
    want_left = 5 if entry == "alive" else 0
    assert np.asarray(left).tolist() == [0, want_left, 0]
    s, left, toks = batched_generate_chunk_perlane_jit(
        params, cfg, s, st, left, n_steps=4, top_k=40,
        live=np.array([True, True, True]), stop_ids=stop_ids)
    toks = np.asarray(toks)
    assert (toks[:, [0, 2]] == -1).all()
    if entry == "alive":
        col = toks[:, 1].tolist()
        ran = col.index(9) + 1 if 9 in col else 4   # 9 is a stop id here
        assert min(col[:ran]) >= 0 and set(col[ran:]) <= {-1}
        assert int(left[1]) == (0 if 9 in col else 1)
    else:
        assert (toks == -1).all() and int(np.asarray(left).max()) == 0
        assert int(s["pos"][1]) == 12           # not one step taken


@pytest.mark.parametrize("lanes_left", [(0, 0, 0), (0, 9, 0), (2, 9, 0),
                                        (3, 3, 3)],
                         ids=["all_ended", "one_alive", "one_ends_inside",
                              "all_end_inside"])
def test_the_chunk_program_against_its_scan_form(lanes_left):
    """(e) With every lane ended the program returns all-pad rows and
    every state leaf as it got it; an alive lane's tokens are bit-equal to
    the ``scan`` form's for as long as it is alive, the rows after its end
    (and every row of a step not run) are pads, and the state of a chunk
    that ran all its steps is the ``scan`` form's."""
    import jax
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.parallel.batched import (
        batched_generate_chunk_perlane_jit)

    cfg, params, st, state = _lane_program_case()
    live = np.array([True, True, True])
    left = jnp.asarray(lanes_left, jnp.int32)
    entry = jax.tree.map(np.asarray, state())
    _, _, want = _scan_chunk()(params, cfg, state(), st, left, n_steps=4,
                               live=live)
    s, left_out, got = batched_generate_chunk_perlane_jit(
        params, cfg, state(), st, left, n_steps=4, top_k=40, live=live)
    got, want = np.asarray(got), np.asarray(want)
    run = min(4, max(lanes_left))
    for lane, n in enumerate(lanes_left):
        n = min(n, 4)
        assert np.array_equal(got[:n, lane], want[:n, lane])
        assert (got[n:, lane] == -1).all()
    assert np.asarray(left_out).tolist() == [max(n - 4, 0)
                                             for n in lanes_left]
    assert int(s["pos"][0]) == 20 + run
    if run == 0:
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), b), s, entry)
    if run == 4:
        s_scan, _, _ = _scan_chunk()(params, cfg, state(), st, left,
                                     n_steps=4, live=live)
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), s, s_scan)


def test_a_stop_id_ends_a_lane_on_the_device():
    """(e) A lane that samples one of ``stop_ids`` is ended by that step:
    its ``left`` drops to 0 and its later rows are pads, while the lane
    beside it decodes on."""
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.parallel.batched import (
        batched_generate_chunk_perlane_jit)

    cfg, params, st, state = _lane_program_case()
    live = np.array([True, True, False])
    left = jnp.asarray([9, 9, 9], jnp.int32)
    _, _, free = batched_generate_chunk_perlane_jit(
        params, cfg, state(), st, left, n_steps=4, top_k=40, live=live)
    free = np.asarray(free)
    assert (free[:, 2] == -1).all()         # a lane that holds no request
    stop = int(free[1, 0])                  # lane 0's second token
    _, left_out, got = batched_generate_chunk_perlane_jit(
        params, cfg, state(), st, left, n_steps=4, top_k=40, live=live,
        stop_ids=(stop,))
    got = np.asarray(got)
    first = int(np.argmax(free[:, 0] == stop))
    assert np.array_equal(got[:first + 1, 0], free[:first + 1, 0])
    assert (got[first + 1:, 0] == -1).all() and int(left_out[0]) == 0
    if stop not in free[:, 1]:
        assert np.array_equal(got[:, 1], free[:, 1])
        assert int(left_out[1]) == 5


@pytest.mark.parametrize("block", ["dense", "routed"])
def test_the_per_step_counters_count_the_steps_run(block, lane_eng,
                                                   tmp_path):
    """(f) ``_note_ring_read`` (``cache_counts``) and a routed block's expert
    statistics count the steps the chunk program ran, not ``n_steps``: one
    request of 9 tokens takes two chunks of 4 steps, and the chunk queued
    behind them runs none."""
    if block == "dense":
        eng = lane_eng
    else:
        from llama_fastapi_k8s_gpu_tpu.testing import write_tiny_olmoe_gguf

        path = str(tmp_path / "tiny-olmoe.gguf")
        write_tiny_olmoe_gguf(path, seed=3)
        eng = ContinuousEngine(path, weight_format="q4k", n_ctx=128,
                               batch_size=3, decode_chunk=4)
    try:
        before = _quiet(eng)
        read0 = eng.cache_counts["read"]
        pairs0 = eng.expert_counters.snapshot(block=True)["layer_steps"] \
            if block == "routed" else 0
        out = eng.create_chat_completion(MSGS, temperature=0.0, max_tokens=9)
        after = _quiet(eng)
        n = out["usage"]["completion_tokens"]
        run = after["steps_run"] - before["steps_run"]
        assert run == (8 if n == 9 else run) and run < 12
        # n_ctx 128 is one block of the read: a step reads 128 slots a lane
        assert eng.cache_counts["read"] - read0 == 128 * run
        if block == "routed":
            snap = eng.expert_counters.snapshot(block=True)
            assert snap["layer_steps"] - pairs0 == eng.cfg.n_layers * run
    finally:
        if block == "routed":
            eng.shutdown()
