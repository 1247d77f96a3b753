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

import contextlib
import logging
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from llama_fastapi_k8s_gpu_tpu.engine import ContinuousEngine, Engine
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
    """A private registry so units never race the process one (no done
    stamps: the stamp tests build their own, with a watcher each)."""
    return DevtimeRegistry(armed=True, budget=32, stamps=False)


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
                 "storms": 0, "device_s": 0.0, "intervals": 0}
    snap = reg.snapshot()
    prog = next(p for p in snap["programs"] if p["name"] == "toy")
    assert prog["kind"] == "entry"
    assert prog["compile_seconds_total"] > 0
    # no executable store (the tests keep out of the persistent cache):
    # both compiles went through the jit (tests/test_execstore.py has the
    # store's side)
    assert (prog["loaded"], prog["built"]) == (0, 0)
    assert prog["load_seconds_total"] == prog["build_seconds_total"] == 0
    assert snap["executable_store"] is None
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
                                   "signatures": 0, "storms": 0,
                                   "device_s": 0.0, "intervals": 0}
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
                 "lane_decode_chunk", "lane_write", "kvpool_store",
                 "kvpool_restore", "kvpool_upload", "kvpool_lane_store",
                 "flash_attention", "quantize_kv_pallas"):
        assert want in names, (want, sorted(names))
    # the cycle scheduler's and the sequence-parallel engine's programs
    # left with them (PR 60)
    assert not names & {"batched_prefill", "batched_decode_chunk",
                        "batched_first_sample", "sp_prefill",
                        "sp_decode_chunk"}


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

def _poison(monkeypatch, off: str):
    """``off`` = "registry" (LFKT_DEVTIME=0: nothing may be recorded) or
    "tracer" (LFKT_TRACE_SAMPLE=0: dispatches are counted, but no stamp is
    taken, no leaf looked for, no request read off the thread; the one
    place that starts the watcher thread is ``_stamp``)."""
    def boom(*a, **kw):
        raise AssertionError(f"devtime with the {off} off touched its "
                             "registry")

    if off == "registry":
        monkeypatch.setattr(DEVTIME, "record_dispatch", boom)
        monkeypatch.setattr(DEVTIME, "record_compile", boom)
        monkeypatch.setattr(devtime, "_signature", boom)
    for name in ("_stamp", "_watch", "_close"):
        monkeypatch.setattr(DEVTIME, name, boom)
    monkeypatch.setattr(devtime, "_first_leaf", boom)
    monkeypatch.setattr(devtime, "open_rid", boom)


def _drained(timeout: float = 20.0):
    """Until the process registry's watcher has closed what is pending."""
    t_end = time.time() + timeout
    while DEVTIME.snapshot()["stamps"]["pending"] and time.time() < t_end:
        time.sleep(0.002)
    assert DEVTIME.snapshot()["stamps"]["pending"] == 0


@contextlib.contextmanager
def _off(which: str):
    was = DEVTIME.stamps
    if which == "registry":
        DEVTIME.configure(armed=False)
    else:
        DEVTIME.configure(stamps=False)
    try:
        yield
    finally:
        DEVTIME.configure(armed=True, stamps=was)


@pytest.mark.parametrize("off", ["registry", "tracer"])
def test_disarmed_wrapper_is_poison_proof(monkeypatch, off):
    f = timed_jit("poisonable", jax.jit(lambda x: x + 1))
    jax.block_until_ready(f(jnp.ones(3)))    # compiled while armed
    _drained()
    with _off(off):
        _poison(monkeypatch, off)
        before = DEVTIME.counters()["poisonable"]["dispatches"]
        out = f(jnp.ones(3))             # would raise if anything recorded
        assert float(out[0]) == 2.0
        assert DEVTIME.counters()["poisonable"]["dispatches"] - before == \
            (off == "tracer")


@pytest.mark.parametrize("off", ["registry", "tracer"])
def test_disarmed_engine_decode_path_is_poison_proof(monkeypatch, model_path,
                                                     off):
    """A full real-engine generation under a poisoned, disarmed registry:
    the LFKT_TRACE_SAMPLE=0 analogue — every wrapped entry point on the
    prefill + decode path forwards without touching devtime state (with
    only the tracer off: without touching the stamps' state)."""
    eng = Engine(model_path, n_ctx=128, decode_chunk=4, max_gen_tokens=16,
                 prefill_buckets=(32, 64, 128))
    _drained()
    with _off(off):
        _poison(monkeypatch, off)
        out = eng.create_chat_completion(MSGS, temperature=0.0, max_tokens=8)
        assert out["usage"]["completion_tokens"] >= 1


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


# ---------------------------------------------------------------------------
# layer 5: device-done stamps (PR 54) -- intervals from fake results whose
# ``block_until_ready`` the test releases, on a clock the test sets
# ---------------------------------------------------------------------------

class _Result:
    """A fake device result: ready when the test says so, or deleted (a
    later program took it by donation)."""

    def __init__(self, deleted: bool = False):
        self.ready = threading.Event()
        self.deleted = deleted

    def block_until_ready(self):
        if self.deleted:
            raise RuntimeError("Array has been deleted.")
        assert self.ready.wait(20), "the test never released this result"
        return self


class _Entry:
    """What ``timed_jit`` wraps, as far as it looks: a callable with a
    ``_cache_size``.  Returns what it is given."""

    def _cache_size(self):
        return 1

    def __call__(self, out):
        return out


class _Stamped:
    """A private registry with its stamps on, a clock the test sets and one
    wrapped entry per program name."""

    def __init__(self):
        self.reg = DevtimeRegistry(armed=True, budget=32, stamps=True)
        self.now = 0.0
        self.reg.clock = lambda: self.now
        self._entries = {}

    def dispatch(self, program: str, at: float, out="result", leaf=0):
        """One dispatch that returns at ``at``; the fake result."""
        if out == "result":
            out = _Result()
        if program not in self._entries:
            self._entries[program] = self.reg.timed_jit(program, _Entry(),
                                                        leaf=leaf)
        self.now = at
        return self._entries[program](out)

    def release(self, result: _Result, at: float, closes: int = 1):
        """The device finishes ``result`` at ``at``; waits until ``closes``
        more dispatches are off the pending list (intervals or misses)."""
        left = len(self.reg._pending) - closes
        self.now = at
        result.ready.set()
        self.settle(left)

    def settle(self, pending: int):
        t_end = time.time() + 20
        while len(self.reg._pending) > pending and time.time() < t_end:
            time.sleep(0.001)
        assert len(self.reg._pending) == pending

    def intervals(self):
        return [(n, s, e) for n, _, s, e, _ in self.reg.intervals_since(0.0)]


@pytest.fixture()
def stamped():
    return _Stamped()


def test_intervals_follow_the_order_of_enqueueing(stamped):
    """One chip runs its programs in the order they were enqueued: B's
    interval starts at A's done, not at B's own dispatch."""
    a = stamped.dispatch("prefill_chunk", at=10.0)
    b = stamped.dispatch("first_sample", at=11.0)
    stamped.release(a, at=15.0)
    stamped.release(b, at=18.0)
    assert stamped.intervals() == [("prefill_chunk", 10.0, 15.0),
                                   ("first_sample", 15.0, 18.0)]
    c = stamped.reg.counters()
    assert (c["prefill_chunk"]["device_s"], c["prefill_chunk"]["intervals"]) \
        == (5.0, 1)
    assert (c["first_sample"]["device_s"], c["first_sample"]["intervals"]) \
        == (3.0, 1)


def test_a_dispatch_into_an_idle_device_starts_at_its_return(stamped):
    a = stamped.dispatch("decode_chunk", at=10.0)
    stamped.release(a, at=12.0)
    b = stamped.dispatch("decode_chunk", at=30.0)     # 18 s of idle device
    stamped.release(b, at=31.5)
    assert stamped.intervals() == [("decode_chunk", 10.0, 12.0),
                                   ("decode_chunk", 30.0, 31.5)]
    assert stamped.reg.counters()["decode_chunk"]["device_s"] == 3.5


def test_a_result_released_early_still_waits_for_the_one_before(stamped):
    """The watcher waits on the oldest dispatch: B ready before A stamps
    nothing, and once A is done B's interval is what is left of it."""
    a = stamped.dispatch("a", at=40.0)
    b = stamped.dispatch("b", at=41.0)
    stamped.now = 43.0
    b.ready.set()
    time.sleep(0.05)
    assert stamped.intervals() == [] and len(stamped.reg._pending) == 2
    stamped.release(a, at=45.0, closes=2)
    assert stamped.intervals() == [("a", 40.0, 45.0), ("b", 45.0, 45.0)]


@pytest.mark.parametrize("out", [_Result(deleted=True), (), None, {}],
                         ids=["deleted", "empty_tuple", "none", "no_leaf"])
def test_a_result_without_a_leaf_to_wait_on_is_a_counted_miss(stamped, out):
    """A donated or deleted leaf, a result with no array in it: one miss,
    no interval, and never an exception on the serving thread.  Its time
    lies in the next stamped program's interval."""
    first = stamped.dispatch("prefill_chunk", at=1.0)
    stamped.release(first, at=2.0)
    assert stamped.dispatch("lost", at=2.5, out=out) is out
    stamped.settle(0)
    assert stamped.reg.snapshot()["stamps"]["misses"] == 1
    after = stamped.dispatch("lane_decode_chunk", at=3.0)
    stamped.release(after, at=6.0)
    assert stamped.intervals() == [("prefill_chunk", 1.0, 2.0),
                                   ("lane_decode_chunk", 3.0, 6.0)]
    assert stamped.reg.counters()["lost"] == {
        "compiles": 0, "dispatches": 1, "signatures": 0, "storms": 0,
        "device_s": 0.0, "intervals": 0}


def test_a_leaf_that_cannot_be_found_never_raises_on_the_serving_thread(
        stamped, monkeypatch):
    def boom(out, index):
        raise TypeError("not a pytree")

    monkeypatch.setattr(devtime, "_first_leaf", boom)
    out = _Result()
    assert stamped.dispatch("odd", at=1.0, out=out) is out
    assert stamped.reg.snapshot()["stamps"] == {
        "armed": True, "misses": 1, "pending": 0, "ring": 0}


def test_a_program_without_a_stamp_says_so_and_is_never_pending(stamped):
    """``leaf=None``: every result is donated onward."""
    out = stamped.dispatch("lane_cache_copy", at=1.0, out=_Result(),
                           leaf=None)
    assert len(stamped.reg._pending) == 0 and not out.ready.is_set()
    stamped.dispatch("prefill_chunk", at=2.0, out=_Result())
    rows = {p["name"]: p for p in stamped.reg.snapshot()["programs"]}
    assert rows["lane_cache_copy"]["stamped"] is False
    assert rows["lane_cache_copy"]["dispatches"] == 1
    assert rows["prefill_chunk"]["stamped"] is True
    assert stamped.reg._watcher.name == "lfkt-device-done"
    assert stamped.reg._watcher.daemon


def test_the_leaf_is_the_first_array_of_the_named_element(stamped):
    cache = {"k": _Result(deleted=True)}            # donated onward
    rows = _Result()
    stamped.dispatch("lane_decode_chunk", at=1.0,
                     out=(cache, (rows, _Result(deleted=True))), leaf=1)
    stamped.release(rows, at=4.0)
    assert stamped.intervals() == [("lane_decode_chunk", 1.0, 4.0)]
    assert stamped.reg.snapshot()["stamps"]["misses"] == 0


def test_the_thread_that_fetched_the_result_stamps_it_itself(stamped):
    """The fetch was the wait: ``fetched`` closes everything enqueued up to
    that result at once, so a span that ends at the fetch never closes
    ahead of its last child; what was enqueued after it stays pending."""
    a = stamped.dispatch("prefill_chunk", at=1.0)
    tok = stamped.dispatch("first_sample", at=1.5)
    later = stamped.dispatch("lane_write", at=1.6)
    stamped.now = 5.0
    stamped.reg.fetched(tok)
    assert stamped.intervals() == [("prefill_chunk", 1.0, 5.0),
                                   ("first_sample", 5.0, 5.0)]
    assert [e[0] for e in stamped.reg._pending] == ["lane_write"]
    stamped.reg.fetched(tok)                 # again, or unknown: nothing
    stamped.reg.fetched(object())
    assert len(stamped.intervals()) == 2
    a.ready.set()                # the watcher wakes on what is closed
    tok.ready.set()
    stamped.release(later, at=6.0)
    assert stamped.intervals()[-1] == ("lane_write", 5.0, 6.0)
    assert stamped.reg.snapshot()["stamps"]["misses"] == 0


def test_the_watchers_own_earlier_stamp_is_kept(stamped):
    """A fetch that finds a result the watcher has just seen ready takes
    the watcher's time, the earlier one."""
    with stamped.reg._lock:
        stamped.reg._close(["p", "", 1.0, None, 3.0], 4.0)
    assert stamped.intervals() == [("p", 1.0, 3.0)]


def test_the_ring_is_bounded_and_the_sums_are_not(monkeypatch):
    monkeypatch.setattr(devtime, "MAX_INTERVALS", 4)
    s = _Stamped()
    for i in range(10):
        s.release(s.dispatch("step", at=float(i)), at=i + 0.5)
    assert [iv[1] for iv in s.intervals()] == [6.0, 7.0, 8.0, 9.0]
    c = s.reg.counters()["step"]
    assert (c["intervals"], c["device_s"]) == (10, 5.0)
    assert [iv[2] for iv in s.reg.intervals_since(8.0)] == [8.0, 9.0]
    assert s.reg.intervals_since(99.0) == []


def test_a_device_that_hangs_grows_no_list(stamped, monkeypatch):
    monkeypatch.setattr(devtime, "MAX_PENDING", 3)
    held = [stamped.dispatch("stuck", at=float(i)) for i in range(5)]
    assert len(stamped.reg._pending) == 3
    assert stamped.reg.snapshot()["stamps"]["misses"] == 2
    for r in held:
        r.ready.set()
    stamped.settle(0)


def test_the_request_open_on_the_thread_rides_the_interval(stamped):
    from llama_fastapi_k8s_gpu_tpu.obs import trace as obs_trace

    was = obs_trace._KEEP_RID
    obs_trace._KEEP_RID = True
    try:
        with obs_trace.phase("admit_slice", rid="abc", offset=0, tokens=16):
            a = stamped.dispatch("prefill_chunk", at=1.0)
        b = stamped.dispatch("lane_decode_chunk", at=1.5)
    finally:
        obs_trace._KEEP_RID = was
    stamped.release(a, at=2.0)
    stamped.release(b, at=3.0)
    assert [(iv[0], iv[1], iv[4]) for iv in stamped.reg.intervals_since(0)] \
        == [("prefill_chunk", "abc", 1.0), ("lane_decode_chunk", "", 1.5)]


def test_reset_drops_stamps_and_a_late_watcher_closes_nothing(stamped):
    a = stamped.dispatch("p", at=1.0)
    stamped.release(stamped.dispatch("q", at=1.0), at=1.0, closes=0)
    stamped.reg.reset()
    a.ready.set()
    time.sleep(0.05)
    assert stamped.intervals() == []
    assert stamped.reg.counters()["p"]["intervals"] == 0
    b = stamped.dispatch("p", at=7.0)
    stamped.release(b, at=8.0)
    assert stamped.intervals() == [("p", 7.0, 8.0)]


def test_stamps_need_the_registry_and_the_tracer():
    assert DevtimeRegistry(armed=True, stamps=True).stamps is True
    assert DevtimeRegistry(armed=True, stamps=False).stamps is False
    assert DevtimeRegistry(armed=False, stamps=True).stamps is False
    reg = DevtimeRegistry(armed=True, stamps=False)
    f = reg.timed_jit("toy", jax.jit(lambda x: x + 1))
    f(jnp.ones(2))
    assert reg._watcher is None and reg.counters()["toy"]["intervals"] == 0
    reg.configure(stamps=True)
    jax.block_until_ready(f(jnp.ones(2)))
    t_end = time.time() + 20
    while reg.counters()["toy"]["intervals"] < 1 and time.time() < t_end:
        time.sleep(0.001)
    c = reg.counters()["toy"]
    assert c["intervals"] == 1 and c["device_s"] >= 0.0
    assert reg._watcher.is_alive()


# -- the engines: a traced request's first_token names what ran inside it ----

LONG = [{"role": "user", "content": "Tell me about "
         + " ".join(f"thing{i}" for i in range(10))}]


class _HeldWatcher:
    """Holds the process registry's watcher off its stamps while a request
    runs, so that the fetch of the first token closes every interval of
    the request at one known moment (on the CPU a tiny program may be done
    inside its dispatch, and the watcher's stamp then races the span's
    start): the test is of structure, not of a share."""

    def __enter__(self):
        self.gate = threading.Event()
        real = DEVTIME.clock

        def held():
            if threading.current_thread() is DEVTIME._watcher:
                self.gate.wait(30)
            return real()

        DEVTIME.clock = held
        self._real = real
        return self

    def __exit__(self, *exc):
        DEVTIME.clock = self._real
        self.gate.set()


def _first_token_of(trace_dict):
    def find(node, name):
        if node["name"] == name:
            return node
        for c in node["children"]:
            got = find(c, name)
            if got:
                return got
    prefill = find(trace_dict["root"], "prefill")
    return prefill, find(prefill, "first_token")


def _traced_request(eng, tracer):
    tr = tracer.start()
    with _HeldWatcher():
        out = eng.create_chat_completion(LONG, temperature=0.0, max_tokens=6,
                                         trace=tr)
    tracer.finish(tr)
    assert out["usage"]["completion_tokens"] >= 1
    return tr.to_dict()


def _assert_first_token_names_its_inside(doc):
    prefill, ft = _first_token_of(doc)
    kids = ft["children"]
    names = [k["name"] for k in kids]
    slices = [k for k in prefill["children"] if k["name"] == "prefill_slice"]
    assert len(slices) >= 2
    # every slice of the request, its sample, then the host's part; in the
    # device's order, inside the span, none overlapping the next
    assert names[:len(slices)] == ["device.prefill_chunk"] * len(slices)
    assert "device.first_sample" in names and names[-1] == "host_fetch"
    own = [k for k in kids if k["attrs"].get("own")]
    assert [k["name"] for k in own][:len(slices) + 1] == \
        ["device.prefill_chunk"] * len(slices) + ["device.first_sample"]
    for a, b in zip(kids, kids[1:]):
        assert ft["start"] <= a["start"] <= a["end"] <= b["start"]
    assert kids[-1]["end"] == ft["end"]
    assert all(k["attrs"]["seconds"] >= 0 for k in kids[:-1])
    # each slice's own program: its interval and when it was done
    for s in slices:
        assert s["attrs"]["device_s"] >= 0.0
        assert s["attrs"]["done_at"] >= s["end"]
    assert slices[-1]["attrs"]["done_at"] <= ft["end"]


def test_serial_first_token_has_its_device_programs_inside(model_path):
    eng = Engine(model_path, n_ctx=256, decode_chunk=4, max_gen_tokens=16,
                 prefill_chunk=16, prefix_cache=False)
    assert DEVTIME.stamps
    doc = _traced_request(eng, Tracer(sample=1.0, ring=4))
    _assert_first_token_names_its_inside(doc)
    _, ft = _first_token_of(doc)
    assert ft["attrs"]["deferred"] is False


def test_lane_first_token_has_its_device_programs_inside(model_path):
    eng = ContinuousEngine(model_path, batch_size=2, n_ctx=256,
                           decode_chunk=4, max_gen_tokens=16,
                           prefill_chunk=16, prefix_cache=False)
    try:
        assert DEVTIME.stamps
        doc = _traced_request(eng, Tracer(sample=1.0, ring=4))
    finally:
        eng.shutdown()
    _assert_first_token_names_its_inside(doc)
    _, ft = _first_token_of(doc)
    names = [k["name"] for k in ft["children"]]
    # the lane write is the request's own too, queued behind its sample
    if "device.lane_write" in names:
        assert names.index("device.lane_write") > \
            names.index("device.first_sample")
    rows = {p["name"]: p for p in DEVTIME.snapshot()["programs"]}
    assert rows["lane_cache_copy"]["stamped"] is False
    assert rows["lane_write"]["stamped"] is True
    assert rows["prefill_chunk"]["intervals"] >= 2
