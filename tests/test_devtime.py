"""lfkt-perf devtime gates (ISSUE 7): compile/dispatch attribution.

Four layers:

1. **Wrapper units** — ``timed_jit`` counts compiles and dispatches
   exactly (cache-size probe and signature-set fallback), signatures are
   stable strings, the event ring replays each compile exactly once per
   cursor, ``reset`` keeps the sequence monotonic.
2. **Recompile-storm detector** — planted signature churn past the
   budget fires the counter, the structured-log warning, and the event
   fan-in onto every in-flight trace (the obs/trace.py
   ``annotate_all_inflight`` contract).
3. **Zero-cost disarm** — with ``LFKT_DEVTIME=0`` semantics the wrapper
   forwards untouched: a poisoned registry (every recording method
   raises) survives a full real-engine generation (the tracer's
   ``LFKT_TRACE_SAMPLE=0`` poisoned-Span analogue).
4. **Organic storm** — a real serial engine whose decode tail chunks
   churn static shapes trips the detector with no planted events at all.
"""

from __future__ import annotations

import logging

import jax
import jax.numpy as jnp
import pytest

from llama_fastapi_k8s_gpu_tpu.engine import Engine
from llama_fastapi_k8s_gpu_tpu.obs import devtime
from llama_fastapi_k8s_gpu_tpu.obs.devtime import (
    DEVTIME,
    DevtimeRegistry,
    _signature,
    timed_jit,
)
from llama_fastapi_k8s_gpu_tpu.obs.trace import Tracer
from llama_fastapi_k8s_gpu_tpu.testing import write_tiny_llama_gguf

MSGS = [{"role": "user", "content": "Say something."}]


@pytest.fixture()
def reg():
    """A private registry so units never race the process one."""
    return DevtimeRegistry(armed=True, budget=32)


@pytest.fixture(scope="module")
def model_path(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("model") / "tiny.gguf")
    write_tiny_llama_gguf(path)
    return path


# ---------------------------------------------------------------------------
# layer 1: wrapper units
# ---------------------------------------------------------------------------

def test_timed_jit_counts_compiles_and_dispatches(reg):
    f = reg.timed_jit("toy", jax.jit(lambda x: x + 1))
    f(jnp.ones(3))
    f(jnp.ones(3))        # cache hit: dispatch only
    f(jnp.ones(4))        # new shape: compile
    c = reg.counters()["toy"]
    assert c == {"compiles": 2, "dispatches": 3, "signatures": 2,
                 "storms": 0}
    snap = reg.snapshot()
    prog = next(p for p in snap["programs"] if p["name"] == "toy")
    assert prog["kind"] == "entry"
    assert prog["compile_seconds_total"] > 0
    sigs = [s["signature"] for s in prog["signature_list"]]
    assert any("[3]" in s for s in sigs) and any("[4]" in s for s in sigs)


def test_wrapper_output_and_kwargs_pass_through(reg):
    f = reg.timed_jit("passthru", jax.jit(lambda x, n=1: x * n))
    out = f(jnp.asarray([2.0]), n=jnp.asarray(3.0))
    assert float(out[0]) == 6.0


def test_timed_jit_refuses_a_callable_that_is_not_jitted(reg):
    """The wrapper counts compiles from the jit cache's own size; a plain
    function has none, and wrapping one is a programming error."""
    with pytest.raises(AttributeError):
        reg.timed_jit("plain", lambda x: x)


def test_another_name_for_the_same_placement_is_no_compile(reg):
    """The jit cache keys on the sharding OBJECT of every argument; on one
    device ``P()`` and ``P(None, None)`` are one placement, and the second
    entry lowers and compiles nothing: a dispatch, not a compile."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("dp", "tp"))
    f = reg.timed_jit("named", jax.jit(lambda x: x * 2))
    x = jnp.ones((4, 4))
    f(jax.device_put(x, NamedSharding(mesh, P())))
    grown = f._fn._cache_size()
    f(jax.device_put(x, NamedSharding(mesh, P(None, None))))
    assert f._fn._cache_size() == grown + 1           # the cache grew,
    c = reg.counters()["named"]
    assert c["compiles"] == 1 and c["dispatches"] == 2   # nothing compiled
    f(jnp.ones((8, 4)))                               # a new shape does
    assert reg.counters()["named"]["compiles"] == 2


def test_warm_dispatch_skips_signature_walk(reg, monkeypatch):
    """A dispatch that did not grow the jit cache must never pay the
    O(leaves) signature walk."""
    f = reg.timed_jit("warm", jax.jit(lambda x: x))
    f(jnp.ones(2))                           # the one compile
    monkeypatch.setattr(devtime, "_signature",
                        lambda *a: pytest.fail("signature on warm path"))
    f(jnp.ones(2))
    c = reg.counters()["warm"]
    assert c["dispatches"] == 2 and c["compiles"] == 1


def test_signature_describes_arrays_and_statics():
    sig = _signature((jnp.ones((2, 3), jnp.int32), 7, "mode"), {})
    assert "int32[2,3]" in sig and "7" in sig and "'mode'" in sig


def test_event_ring_replays_once_per_cursor(reg):
    f = reg.timed_jit("ev", jax.jit(lambda x: x))
    f(jnp.ones(1))
    cur, events = reg.events_since(0)
    assert [e["program"] for e in events] == ["ev"]
    cur2, again = reg.events_since(cur)
    assert again == [] and cur2 == cur
    f(jnp.ones(2))
    cur3, more = reg.events_since(cur)
    assert len(more) == 1 and more[0]["seq"] > cur
    # a stale (too-new) cursor after reset resets to replay-all
    reg.reset()
    f(jnp.ones(3))
    _, replay = reg.events_since(10 ** 9)
    assert len(replay) == 1


def test_reset_zeroes_ledgers_but_keeps_registration(reg):
    f = reg.timed_jit("r", jax.jit(lambda x: x))
    f(jnp.ones(1))
    reg.reset()
    assert reg.counters()["r"] == {"compiles": 0, "dispatches": 0,
                                   "signatures": 0, "storms": 0}
    f(jnp.ones(1))
    assert reg.counters()["r"]["dispatches"] == 1


def test_event_ring_overflow_is_counted_not_silent(reg):
    """A storm minting more compile events than the ring holds between
    two replays must surface the loss: events_dropped grows by the gap
    (xla_compile_seconds undercounts; xla_compiles_total stays exact),
    while reset-cleared events never count as dropped."""
    from llama_fastapi_k8s_gpu_tpu.obs.devtime import MAX_EVENTS

    reg.configure(budget=10 * MAX_EVENTS)          # no storm noise
    cursor, _ = reg.events_since(0)
    n = MAX_EVENTS + 40
    for i in range(n):
        reg.record_compile("flood", f"f32[{i}]", 0.001)
    cursor, events = reg.events_since(cursor)
    assert len(events) == MAX_EVENTS               # ring-bounded replay
    assert reg.events_dropped == 40                # the lost tail, counted
    assert reg.snapshot()["events_dropped"] == 40
    # exact ledger unaffected
    assert reg.counters()["flood"]["compiles"] == n
    # a reset clears deliberately — not a drop
    reg.reset()
    reg.record_compile("flood", "f32[0]", 0.001)
    cursor, events = reg.events_since(cursor)
    assert len(events) == 1 and reg.events_dropped == 0


def test_fresh_consumer_charges_no_drop_for_prehistory(reg):
    """A never-read consumer (cursor -1, a second app built after the
    ring already overflowed) replays the retained events without bumping
    events_dropped — those events were not lost between ITS scrapes."""
    from llama_fastapi_k8s_gpu_tpu.obs.devtime import MAX_EVENTS

    reg.configure(budget=10 * MAX_EVENTS)
    for i in range(MAX_EVENTS + 25):
        reg.record_compile("boot", f"f32[{i}]", 0.001)
    cursor, events = reg.events_since(-1)
    assert len(events) == MAX_EVENTS and reg.events_dropped == 0
    # from here it is an ordinary consumer: a real overflow DOES count
    for i in range(MAX_EVENTS + 7):
        reg.record_compile("boot", f"g32[{i}]", 0.001)
    cursor, events = reg.events_since(cursor)
    assert reg.events_dropped == 7


def test_reset_zeroes_the_signature_ledger(reg):
    """reset() must zero EVERY ledger including signature membership: a
    signature seen before the reset is new again after it."""
    f = reg.timed_jit("rf", jax.jit(lambda x: x))
    f(jnp.ones(2))
    assert reg.counters()["rf"]["compiles"] == 1
    reg.reset()
    f(jnp.ones(5))         # a fresh compile, post-reset
    assert reg.counters()["rf"]["compiles"] == 1
    assert reg.counters()["rf"]["signatures"] == 1


def test_register_program_inventory(reg):
    name = reg.register_program("inner_thing", site="tests")
    assert name == "inner_thing"
    prog = next(p for p in reg.snapshot()["programs"]
                if p["name"] == "inner_thing")
    assert prog["kind"] == "inner" and prog["site"] == "tests"


def test_package_entry_points_are_registered():
    """The serving programs the ISSUE names must exist in the process
    registry once their modules import (PERF001's runtime mirror)."""
    import llama_fastapi_k8s_gpu_tpu.engine.continuous  # noqa: F401
    import llama_fastapi_k8s_gpu_tpu.ops.pallas.kvquant  # noqa: F401
    import llama_fastapi_k8s_gpu_tpu.parallel.kvpool  # noqa: F401

    names = {p["name"] for p in DEVTIME.snapshot()["programs"]}
    for want in ("prefill", "prefill_chunk", "decode_chunk", "first_sample",
                 "batched_prefill", "batched_decode_chunk",
                 "lane_decode_chunk", "lane_write", "kvpool_store",
                 "kvpool_restore", "kvpool_upload", "kvpool_lane_store",
                 "flash_attention", "quantize_kv_pallas"):
        assert want in names, (want, sorted(names))


# ---------------------------------------------------------------------------
# layer 2: the recompile-storm detector (planted signature churn)
# ---------------------------------------------------------------------------

def test_storm_fires_past_budget_with_log_and_trace_fanin(caplog):
    reg = DevtimeRegistry(armed=True, budget=2)
    tracer = Tracer(sample=1.0, ring=4)
    inflight = tracer.start()            # a live request to be annotated
    with caplog.at_level(logging.WARNING,
                         logger="llama_fastapi_k8s_gpu_tpu.obs.devtime"):
        for i in range(4):
            reg.record_compile("churny", f"f32[{i}]", 0.01)
    assert reg.counters()["churny"]["storms"] == 2     # sigs 3 and 4
    assert reg.storms_total == 2
    storm, = reg.storms()
    assert storm["program"] == "churny" and storm["signatures"] == 4
    warnings = [r for r in caplog.records if "recompile storm" in r.message]
    assert warnings and warnings[0].program == "churny"
    tracer.finish(inflight)
    events = [e for e in inflight.root.events
              if e["name"] == "recompile_storm"]
    assert len(events) == 2
    assert events[0]["program"] == "churny"
    assert events[0]["budget"] == 2


def test_repeat_compiles_of_known_signature_do_not_storm(reg):
    reg.configure(budget=1)
    reg.record_compile("stable", "f32[8]", 0.01)
    for _ in range(5):
        reg.record_compile("stable", "f32[8]", 0.01)   # same sig re-traced
    assert reg.storms() == [] and reg.storms_total == 0
    assert reg.counters()["stable"]["compiles"] == 6


def test_signature_string_retention_is_bounded(reg):
    """A sustained storm must not grow process memory with multi-KB
    signature strings: the ledger retains at most MAX_SIGNATURES_SHOWN
    full strings per program while distinct counts (and therefore storm
    detection) stay exact via the hash set."""
    from llama_fastapi_k8s_gpu_tpu.obs.devtime import MAX_SIGNATURES_SHOWN

    reg.configure(budget=10_000)                  # no storm noise
    n = MAX_SIGNATURES_SHOWN + 40
    for i in range(n):
        reg.record_compile("churn", f"f32[{i}]" * 50, 0.001)
    prog = next(p for p in reg.snapshot()["programs"]
                if p["name"] == "churn")
    assert prog["signatures"] == n                # exact distinct count
    assert prog["compiles"] == n
    assert len(prog["signature_list"]) == MAX_SIGNATURES_SHOWN
    # newest survive, oldest evicted
    assert any(f"[{n - 1}]" in s["signature"]
               for s in prog["signature_list"])
    # a re-compile of an evicted signature is still known: no double count
    reg.record_compile("churn", "f32[0]" * 50, 0.001)
    assert reg.counters()["churn"]["signatures"] == n


# ---------------------------------------------------------------------------
# layer 3: disarmed devtime allocates nothing on the decode path
# ---------------------------------------------------------------------------

def _poison(monkeypatch):
    def boom(*a, **kw):
        raise AssertionError("disarmed devtime touched its registry")

    monkeypatch.setattr(DEVTIME, "record_dispatch", boom)
    monkeypatch.setattr(DEVTIME, "record_compile", boom)
    monkeypatch.setattr("llama_fastapi_k8s_gpu_tpu.obs.devtime._signature",
                        boom)


def test_disarmed_wrapper_is_poison_proof(monkeypatch):
    f = timed_jit("poisonable", jax.jit(lambda x: x + 1))
    DEVTIME.configure(armed=False)
    try:
        _poison(monkeypatch)
        out = f(jnp.ones(3))             # would raise if anything recorded
        assert float(out[0]) == 2.0
    finally:
        DEVTIME.configure(armed=True)


def test_disarmed_engine_decode_path_is_poison_proof(monkeypatch, model_path):
    """A full real-engine generation under a poisoned, disarmed registry:
    the LFKT_TRACE_SAMPLE=0 analogue — every wrapped entry point on the
    prefill + decode path forwards without touching devtime state."""
    eng = Engine(model_path, n_ctx=128, decode_chunk=4, max_gen_tokens=16,
                 prefill_buckets=(32, 64, 128))
    DEVTIME.configure(armed=False)
    try:
        _poison(monkeypatch)
        out = eng.create_chat_completion(MSGS, temperature=0.0, max_tokens=8)
        assert out["usage"]["completion_tokens"] >= 1
    finally:
        DEVTIME.configure(armed=True)


# ---------------------------------------------------------------------------
# layer 4: an organic storm on a real engine (no planted events)
# ---------------------------------------------------------------------------

def test_storm_detected_on_real_engine_tail_chunk_churn(model_path):
    """``n_steps`` is a static argument of the decode_chunk program.  A
    BUDGET tail (max_tokens % decode_chunk) no longer mints a signature —
    the engine dispatches a full chunk and drops the surplus on the host
    (PR 22: on the chip that compile cost the first request its timeout).
    The RING's own end still shortens the last chunk, by n_prompt mod
    decode_chunk: with the budget pinned to 1, the second distinct ring
    tail is a storm — detected at the compile itself, i.e. within the very
    request that churned."""
    eng = Engine(model_path, n_ctx=64, decode_chunk=8, max_gen_tokens=64,
                 prefill_buckets=(32, 64), prefix_cache=False)
    old_budget = DEVTIME.budget
    DEVTIME.reset()
    DEVTIME.configure(budget=1)
    try:
        # budget tails of 0, 3 and 5: one n_steps signature all the same
        for n in (8, 3, 5):
            eng.create_chat_completion(MSGS, temperature=0.0, max_tokens=n)
        assert DEVTIME.storms() == []
        # generate to the end of the ring from two prompt lengths (52 and
        # 53 tokens of the 64: ring tails of 2 and 1 steps): two MORE
        # n_steps signatures -> storm
        for extra in ("!" * 15, "!" * 16):
            eng.create_chat_completion(
                [{"role": "user", "content": MSGS[0]["content"] + extra}],
                temperature=0.0)
        storms = {s["program"] for s in DEVTIME.storms()}
        assert "decode_chunk" in storms, DEVTIME.snapshot()["programs"]
        assert DEVTIME.storms_total >= 1
    finally:
        DEVTIME.reset()
        DEVTIME.configure(budget=old_budget)
