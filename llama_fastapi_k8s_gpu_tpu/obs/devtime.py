"""Compile & dispatch attribution — THE jit program registry (lfkt-perf).

The serving stack's hot path is a handful of jitted programs (prefill /
decode-chunk programs, the continuous scheduler's lane ops, the KV pool's
page-copy programs) plus trace-inner dispatch sites (fused quantized
matmuls, flash attention, KV write-quantize) that compile *as part of*
whichever host program traces them.  Before this module nothing could
answer "what did this pod compile, when, and how often is it
recompiling" — the exact failure mode (silent recompile storms, extra
per-chunk dispatches) that erases kernel-level wins without failing a
single test.

Two registration forms, one registry:

- :func:`timed_jit` wraps a HOST jit entry point.  Every call increments
  the program's dispatch count; a call that grew the underlying jit cache
  (``fn._cache_size()``) AND handed a lowering to the compiler (JAX's own
  ``backend_compile`` event fired on this thread inside the call) is a
  compile event: the program records the static-shape
  signature and the call's wall time (first-dispatch wall ≈ compile wall,
  the standard attribution), and the event is exported to the
  ``xla_compiles_total`` / ``xla_compile_seconds`` /
  ``jit_dispatches_total`` catalog families by the server's /metrics
  render.
- :func:`register_program` declares a TRACE-INNER dispatch site (a
  ``jax.jit``/``pallas_call`` that only ever runs inside another traced
  program — fused matmul builders, flash attention, write-quantize).
  Inner programs compile as part of their enclosing entry's compile wall;
  registration makes them inventory-visible at ``/debug/compiles`` and
  satisfies lfkt-lint PERF001 (every jit/pallas entry point must be
  registered — lint/perf.py).

Recompile storms: a program whose distinct-signature set grows past
``LFKT_RECOMPILE_BUDGET`` is flagged on the spot — a counter, a
structured-log warning, and a ``recompile_storm`` event annotated onto
every in-flight trace (obs/trace.py fan-in), so the requests a storm
stalled carry the explanation in their own span trees.

Zero cost when disarmed (``LFKT_DEVTIME=0``): the wrapper's first check
is a plain attribute read and the call forwards untouched — no signature,
no lock, no allocation (pinned by the poisoned-registry test in
tests/test_devtime.py, the tracer's ``LFKT_TRACE_SAMPLE=0`` analogue).

Device seconds by program (PR 54).  While the tracer is armed
(``LFKT_TRACE_SAMPLE`` > 0) every dispatch of an entry program also hands
one leaf of its result to the registry's single watcher thread, which
waits for it and stamps the host clock: ``done``.  One chip runs its
programs in the order they were enqueued, so a program's **interval** is
``[max(previous done, its dispatch's return), done]``: summed per program
(``device_s``, ``intervals``; ``jit_device_seconds_total`` /
``jit_device_intervals_total``) and kept, the last :data:`MAX_INTERVALS`
of them, in a ring the ``first_token`` span takes its ``device.<program>``
children from (obs/trace.py ``end_first_token``).  An interval is an UPPER
bound on its program: work that reaches the device outside the registry
(an eager ``jnp`` call, a transfer, a program without a stamp) lies in the
interval of the next stamped program.  With the tracer off the wrapper pays one attribute read
and the watcher thread does not exist.

The executable store (PR 55; utils/execstore.py, the compile layer's).
Where the persistent compile cache is on (utils/jaxcache.py
``setup_compile_cache``), the first call of a signature computes a key
WITHOUT tracing, and either loads the program's executable from ``<cache
dir>/executables`` or builds it once (``fn.lower(...).compile()``), writes
it there and dispatches through that ``Compiled`` from then on.  What
dispatches a call is :class:`StoredJit`'s decision, made before this
module looks at ``_armed``: the registry only records what the decision
was.  A first-seen signature is a compile event either way (``compiles``,
``compile_s``: the first call's wall, as before); ``loaded`` / ``load_s``
and ``built`` / ``build_s`` say which of them came through the store and
how.  **A start that loaded its programs ran no trace**: what only a trace
writes reads zero there, and is no fault: the inner sites' registrations
(``register_program`` at trace time: the ``programs`` list of
``/debug/compiles`` holds the entry programs alone), the degrade entries a
trace would add (the probes' own are recorded before any program, and are
in the key), and JAX's ``requests`` / ``hits`` for the stored programs
(utils/jaxcache.py ``cache_counts``).

Determinism dividend: because compile/dispatch counts are exact and
device-independent, tier-1 pins them on CPU (tests/test_perf_pins.py) —
a silent recompile or a stray extra dispatch per decode chunk fails a
CPU test long before it burns a chip session.
"""

from __future__ import annotations

import functools
import logging
import threading
import time
from collections import OrderedDict, deque

from ..utils.execstore import (BUILT, JIT, LOADED, NOT_KEPT, ExecStore,
                               StoredJit)
from .trace import annotate_all_inflight, open_rid, phase

logger = logging.getLogger(__name__)

#: bounded compile-event ring: /metrics replays events it has not seen yet
#: into the xla_compile_seconds histogram via a per-consumer cursor
MAX_EVENTS = 1024
#: full signature STRINGS retained per program (newest first out) — the
#: /debug/compiles display and the per-signature compile walls.  Distinct
#: counts and storm detection stay exact past this via a per-program set
#: of signature hashes (8 bytes each): a sustained storm costs the ledger
#: ~a word per mint, not a multi-KB string — negligible next to the
#: compiled executable jax itself retains for every one of them.
MAX_SIGNATURES_SHOWN = 64
#: device intervals ``(program, rid, start, end, dispatch return)`` kept
#: for the spans (newest last); the per-program sums are exact past it
MAX_INTERVALS = 4096
#: dispatches whose result the watcher has not seen ready yet; past it a
#: dispatch costs a counted miss (a device that hangs must not grow a list)
MAX_PENDING = 4096

ENTRY = "entry"    # host-dispatched jit program (wrapped by timed_jit)
INNER = "inner"    # trace-inner dispatch site (compiles inside its caller)


def _describe_leaf(leaf) -> str:
    """One signature atom: ``dtype[shape]`` for arrays, ``repr`` for plain
    scalars/strings, ``TypeName#hash`` for hashable statics (ModelConfig),
    ``TypeName`` otherwise.  Metadata only — never forces a device sync."""
    if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
        return f"{leaf.dtype}[{','.join(str(d) for d in leaf.shape)}]"
    if isinstance(leaf, (bool, int, float, str)) or leaf is None:
        return repr(leaf)
    try:
        h = hash(leaf)
    except TypeError:
        return type(leaf).__name__
    return f"{type(leaf).__name__}#{h & 0xFFFFFFFF:08x}"


def _signature(args: tuple, kwargs: dict) -> str:
    """Static-shape signature of one dispatch — the (shapes, dtypes,
    statics) key a jit cache distinguishes programs by, rendered as a
    stable string.  Computed only on actual compile events (rare), so the
    lazy jax import and the O(leaves) tree walk never ride a steady-state
    (sub-millisecond) dispatch."""
    import jax

    leaves = jax.tree_util.tree_leaves((args, kwargs))
    return ";".join(_describe_leaf(leaf) for leaf in leaves)


class _Program:
    """One registered program's ledger."""

    __slots__ = ("name", "kind", "site", "signatures", "sig_seen",
                 "compiles", "dispatches", "compile_s", "storms",
                 "device_s", "intervals", "stamped",
                 "loads", "load_s", "builds", "build_s")

    def __init__(self, name: str, kind: str, site: str | None):
        self.name = name
        self.kind = kind
        self.site = site
        #: signature -> {"wall_s": first-compile wall, "count": compiles};
        #: bounded to MAX_SIGNATURES_SHOWN full strings (oldest evicted)
        self.signatures: OrderedDict[str, dict] = OrderedDict()
        #: hashes of every distinct signature ever seen — exact
        #: distinct/storm accounting without retaining the strings
        self.sig_seen: set[int] = set()
        self.compiles = 0
        self.dispatches = 0
        self.compile_s = 0.0
        self.storms = 0
        #: of ``compiles`` / ``compile_s``: the first calls that loaded an
        #: executable from the store, and those that built and wrote one
        self.loads = self.builds = 0
        self.load_s = self.build_s = 0.0
        #: sum and count of the program's device intervals (stamps armed)
        self.device_s = 0.0
        self.intervals = 0
        #: False for an entry whose every result is donated onward: it
        #: carries no stamp, its time lies in the next program's interval
        self.stamped = True


def _first_leaf(out, index: int):
    """The array a dispatch's ``done`` is read from: the first leaf of
    element ``index`` of a tuple result (of the result itself otherwise)."""
    import jax

    if isinstance(out, tuple):
        out = out[index]
    leaves = jax.tree_util.tree_leaves(out)
    return leaves[0] if leaves else None


class DevtimeRegistry:
    """The process-wide compile/dispatch ledger (module instance:
    :data:`DEVTIME`).  Producers are engine worker threads, the continuous
    scheduler thread, and load-time code; consumers are /metrics,
    /debug/compiles, /debug/slo and the tier-1 perf pins."""

    # every mutable table goes through one mutex (lfkt-lint LOCK001);
    # _armed is the single hot-path bool, read without the lock by design
    _GUARDED_BY = {"_programs": "_lock", "_events": "_lock",
                   "_seq": "_lock", "storms_total": "_lock",
                   "events_dropped": "_lock", "_floor": "_lock",
                   "_degrades": "_lock", "_pending": "_lock",
                   "_ring": "_lock", "_last_done": "_lock",
                   "stamp_misses": "_lock", "_watcher": "_lock"}
    _SHARED_ATOMIC = ("_armed", "_stamps", "budget", "_store")

    def __init__(self, armed: bool | None = None, budget: int | None = None,
                 stamps: bool | None = None, store: ExecStore | None = None):
        if armed is None or budget is None or stamps is None:
            from ..utils.config import knob

            if armed is None:
                armed = bool(knob("LFKT_DEVTIME"))
            if budget is None:
                budget = int(knob("LFKT_RECOMPILE_BUDGET"))
            if stamps is None:      # the tracer's own knob: no second one
                stamps = float(knob("LFKT_TRACE_SAMPLE")) > 0.0
        self._lock = threading.Lock()
        self._programs: dict[str, _Program] = {}
        self._events: deque[dict] = deque(maxlen=MAX_EVENTS)
        self._seq = 0                  # monotonic event id (survives reset)
        self.storms_total = 0
        #: events a consumer found already evicted from the ring (cursor
        #: gap) — nonzero means xla_compile_seconds undercounts vs the
        #: exact xla_compiles_total ledger: a storm minted >MAX_EVENTS
        #: compiles inside one scrape interval and the tail was lost
        self.events_dropped = 0
        self._floor = 0        # events at or below this were reset, not dropped
        #: degrade ledger: {(program, reason) -> count} decisions where a
        #: registered program was NOT served (probe failure, ineligible
        #: config) and a slower path took over — the /debug/compiles
        #: attribution the kernel-degrade contract (KER002) promises.
        #: Bounded: distinct (program, reason) pairs are capped; repeats
        #: only bump counts (trace-time producers, never the hot path).
        self._degrades: OrderedDict[tuple, dict] = OrderedDict()
        self.budget = max(1, int(budget))
        self._armed = bool(armed)
        # -- device-done stamps (armed with the tracer) ---------------------
        #: the clock of the stamps: the spans' (obs/trace.py), not the
        #: compile walls' perf_counter
        self.clock = time.time
        #: dispatches in enqueue order whose result is not known ready:
        #: [program, rid, dispatch return, leaf, the watcher's own stamp]
        self._pending: deque[list] = deque()
        self._ring: deque[tuple] = deque(maxlen=MAX_INTERVALS)
        self._last_done = 0.0
        #: dispatches that got no interval: a leaf already deleted or
        #: donated, a result without an array, a full pending list
        self.stamp_misses = 0
        self._wake = threading.Condition(self._lock)
        self._watcher: threading.Thread | None = None
        #: the second hot-path bool: read only where ``_armed`` is true
        self._stamps = bool(stamps)
        #: the executable store, or None: every program on the jit path
        self._store = store

    # -- configuration (tests + ops) ---------------------------------------
    def configure(self, armed: bool | None = None,
                  budget: int | None = None,
                  stamps: bool | None = None) -> None:
        if armed is not None:
            self._armed = bool(armed)
        if budget is not None:
            self.budget = max(1, int(budget))
        if stamps is not None:
            self._stamps = bool(stamps)

    def use_store(self, path: str | None) -> ExecStore | None:
        """Keep entry programs' executables under ``path`` from now on
        (``None``: no store; utils/jaxcache.py calls this where the
        persistent compile cache is on).  Signatures a wrapper already
        dispatches keep the executable they have."""
        if path is None:
            self._store = None
        elif self._store is None or self._store.path != path:
            self._store = ExecStore(path)
        return self._store

    @property
    def store(self) -> ExecStore | None:
        return self._store

    @property
    def armed(self) -> bool:
        return self._armed

    @property
    def stamps(self) -> bool:
        """Whether a dispatch is stamped: the registry and the tracer are
        both armed."""
        return self._armed and self._stamps

    def reset(self) -> None:
        """Zero every ledger (tests).  The event sequence stays monotonic
        so /metrics cursors held by live apps never replay old events."""
        with self._lock:
            for p in self._programs.values():
                p.signatures.clear()
                p.sig_seen.clear()
                p.compiles = p.dispatches = p.storms = p.intervals = 0
                p.compile_s = p.device_s = 0.0
                p.loads = p.builds = 0
                p.load_s = p.build_s = 0.0
            self._pending.clear()
            self._ring.clear()
            self._last_done = 0.0
            self.stamp_misses = 0
            self._events.clear()
            self._degrades.clear()
            self.storms_total = 0
            self.events_dropped = 0
            self._floor = self._seq    # cleared events are not "dropped"

    # -- registration ------------------------------------------------------
    def _program(self, name: str, kind: str,
                 site: str | None) -> _Program:  # lfkt: holds[_lock]
        p = self._programs.get(name)
        if p is None:
            p = self._programs[name] = _Program(name, kind, site)
        elif site is not None and p.site is None:
            p.site = site
        return p

    def register_program(self, name: str, kind: str = INNER,
                         site: str | None = None) -> str:
        """Declare a program without wrapping it (trace-inner dispatch
        sites).  Idempotent; returns the name so call sites can use it as
        an expression."""
        with self._lock:
            self._program(name, kind, site)
        return name

    def timed_jit(self, name: str, fn, site: str | None = None,
                  leaf: int | None = 0, key=None):
        """Wrap a host jit entry point.  Re-wrapping under the same name
        (lru-cached factories minting one jit per config key) merges
        into one program ledger — exactly what storm detection wants.

        ``key``: what ``fn``'s closure holds that a trace reads, by value
        (a factory's own arguments), for the
        executable store's key.  A jit over a closure that passes none is
        never stored (utils/execstore.py).

        ``leaf``: which element of a tuple result the ``done`` stamp waits
        on (its first array; default the first of all).  It must NOT be one
        a later program takes by donation, or the wait finds it deleted: a
        prefill has its logits, a chunk program its rows.  ``None``: every
        result is donated onward, the program carries no stamp
        (``/debug/compiles`` says so) and its time lies in the next
        program's interval."""
        with self._lock:
            self._program(name, ENTRY, site).stamped = leaf is not None
        return _TimedJit(self, name, fn, leaf, key)

    #: distinct (program, reason) degrade pairs retained; repeats past the
    #: bound still count into the OLDEST entry's overflow marker
    MAX_DEGRADES = 32

    def record_degrade(self, program: str, reason: str) -> None:
        """Attribute one degrade decision: ``program`` exists in the
        inventory but a slower path is serving in its place (Mosaic probe
        failure, ineligible weights/config).  Trace/probe-time producer —
        a retrace of the same decision bumps the count, it never grows
        the ledger.  Surfaced in :meth:`snapshot` (``/debug/compiles``)
        so "why is this pod not running kernel X" is answerable from the
        pod itself."""
        key = (program, str(reason)[:400])
        with self._lock:
            self._program(program, INNER, None)   # inventory-visible
            entry = self._degrades.get(key)
            if entry is not None:
                entry["count"] += 1
                return
            if len(self._degrades) >= self.MAX_DEGRADES:
                # keep the ledger bounded; fold the tail into a marker
                key = (program, "(degrade ledger full — older distinct "
                                "reasons folded)")
                entry = self._degrades.get(key)
                if entry is not None:
                    entry["count"] += 1
                    return
            self._degrades[key] = {"program": key[0], "reason": key[1],
                                   "count": 1, "at": time.time()}

    def degrades(self) -> list[dict]:
        """The degrade ledger (insertion order), as /debug/compiles
        shows it."""
        with self._lock:
            return [dict(v) for v in self._degrades.values()]

    # -- producer API ------------------------------------------------------
    def record_dispatch(self, name: str, n: int = 1) -> None:
        with self._lock:
            self._program(name, ENTRY, None).dispatches += n

    def record_compile(self, name: str, signature: str, wall_s: float,
                       how: str | None = None) -> None:
        """Record one compile event: the first call of a signature, whose
        wall is ``wall_s``.  ``how``: :data:`LOADED` (the executable came
        from the store), :data:`BUILT` (built and written there), None (the
        jit path).  Storm side effects (log + trace fan-in) fire outside
        the lock."""
        storm = None
        with self._lock:
            p = self._program(name, ENTRY, None)
            sig_h = hash(signature)
            known = sig_h in p.sig_seen
            if known:
                entry = p.signatures.get(signature)
                if entry is not None:     # display entry may be evicted
                    entry["count"] += 1
            else:
                p.sig_seen.add(sig_h)
                p.signatures[signature] = {"wall_s": round(wall_s, 6),
                                           "count": 1}
                while len(p.signatures) > MAX_SIGNATURES_SHOWN:
                    p.signatures.popitem(last=False)
            p.compiles += 1
            p.compile_s += wall_s
            if how == LOADED:
                p.loads += 1
                p.load_s += wall_s
            elif how == BUILT:
                p.builds += 1
                p.build_s += wall_s
            self._seq += 1
            self._events.append({"seq": self._seq, "program": name,
                                 "wall_s": wall_s, "signature": signature,
                                 "how": how, "at": time.time()})
            if not known and len(p.sig_seen) > self.budget:
                p.storms += 1
                self.storms_total += 1
                storm = {"program": name, "signatures": len(p.sig_seen),
                         "budget": self.budget}
        if storm is not None:
            logger.warning(
                "recompile storm: program %s minted signature #%d "
                "(budget %d) — static shapes are churning "
                "(docs/RUNBOOK.md 'Diagnosing a recompile storm')",
                storm["program"], storm["signatures"], storm["budget"],
                extra=storm)
            annotate_all_inflight("recompile_storm", **storm)

    # -- device-done stamps ------------------------------------------------
    def _stamp(self, name: str, index: int, out) -> None:
        """Hand one dispatch to the watcher (the serving thread's part of
        a stamp: the dispatch's return time, the request open on this
        thread, one leaf of the result).  Never raises: a result that
        yields no leaf is a counted miss."""
        t_ret = self.clock()
        try:
            leaf = _first_leaf(out, index)
        except Exception:  # noqa: BLE001 — telemetry must never fail serving
            leaf = None
        with self._lock:
            if leaf is None or len(self._pending) >= MAX_PENDING:
                self.stamp_misses += 1
                return
            self._pending.append([name, open_rid(), t_ret, leaf, None])
            if self._watcher is None or not self._watcher.is_alive():
                self._watcher = threading.Thread(
                    target=self._watch, name="lfkt-device-done", daemon=True)
                self._watcher.start()
            self._wake.notify()

    def _watch(self) -> None:  # lfkt: blocks-under[_lock] -- Condition.wait on the registry's own lock RELEASES it while the watcher idles; the device wait itself runs off the lock
        """The watcher thread: wait for the oldest pending result (GIL
        released), stamp the clock, close its interval.  The wait stands
        inside ``phase("device_done")``, so a ``/debug/profile`` capture
        holds the stamp on the device trace's clock, its END beside the
        end of the program on the ``XLA Modules`` line."""
        while True:
            with self._lock:
                while not self._pending:
                    self._wake.wait()
                entry = self._pending[0]
            try:
                with phase("device_done", program=entry[0], rid=entry[1]):
                    entry[3].block_until_ready()
                    entry[4] = self.clock()
            except Exception:  # noqa: BLE001 — a deleted or donated leaf
                pass
            with self._lock:
                # fetched() may have closed it, reset() dropped it
                if self._pending and self._pending[0] is entry:
                    self._pending.popleft()
                    if entry[4] is None:
                        self.stamp_misses += 1
                    else:
                        self._close(entry, entry[4])
            entry = None      # hold no result while waiting for the next

    def _close(self, entry: list, t: float) -> None:  # lfkt: holds[_lock]
        """One dispatch's interval: from the later of the previous ``done``
        and its own return (a dispatch into an idle device) to ``t``."""
        name, rid, t_ret, _, seen = entry
        if seen is not None:       # the watcher saw it ready before ``t``
            t = min(t, seen)
        start = max(self._last_done, t_ret)
        end = max(t, start)
        p = self._program(name, ENTRY, None)
        p.device_s += end - start
        p.intervals += 1
        self._ring.append((name, rid, start, end, t_ret))
        self._last_done = end

    def fetched(self, leaf) -> None:
        """The calling thread has just read ``leaf`` on the host: its
        program, and every one enqueued before it, is done NOW.  The fetch
        was the wait, so this thread knows before the watcher does; it
        closes those intervals itself, and a span that ends at the fetch
        never closes ahead of its last child."""
        if not self.stamps:
            return
        t = self.clock()
        with self._lock:
            for i, entry in enumerate(self._pending):
                if entry[3] is leaf:
                    break
            else:
                return
            for _ in range(i + 1):
                self._close(self._pending.popleft(), t)

    def intervals_since(self, t0: float) -> list[tuple]:
        """The ring's intervals that end at or after ``t0``, oldest first:
        ``(program, rid, start, end, dispatch return)`` on :attr:`clock`."""
        out = []
        with self._lock:
            for iv in reversed(self._ring):    # ends never decrease
                if iv[3] < t0:
                    break
                out.append(iv)
        out.reverse()
        return out

    # -- consumers ---------------------------------------------------------
    def counters(self) -> dict[str, dict]:
        """{program: {"compiles", "dispatches", "signatures", "storms",
        "device_s", "intervals"}} — the cheap ledger for /metrics gauges
        and the tier-1 perf pins."""
        with self._lock:
            return {name: {"compiles": p.compiles,
                           "dispatches": p.dispatches,
                           "signatures": len(p.sig_seen),
                           "storms": p.storms,
                           "device_s": p.device_s,
                           "intervals": p.intervals}
                    for name, p in self._programs.items()}

    def compile_ledger(self) -> dict[str, tuple[int, float]]:
        """{program: (compiles, compile seconds)} so far: two readings
        around a phase give what compiled in it (utils/startup.py
        CompileMeter: the warm-up's ``programs_compiled``, ``compile_s``)."""
        with self._lock:
            return {name: (p.compiles, p.compile_s)
                    for name, p in self._programs.items()}

    def store_ledger(self) -> dict[str, tuple[int, float, int, float]]:
        """{program: (loads, load seconds, builds, build seconds)} so far:
        the part of :meth:`compile_ledger` that went through the
        executable store (CompileMeter: ``programs_loaded`` ...)."""
        with self._lock:
            return {name: (p.loads, p.load_s, p.builds, p.build_s)
                    for name, p in self._programs.items()}

    def store_totals(self) -> dict:
        """``programs_loaded`` / ``load_s`` / ``programs_built`` /
        ``build_s`` over every program, and the store's ``load_failures``
        (0 without a store)."""
        rows = self.store_ledger().values()
        store = self._store
        return {"programs_loaded": sum(r[0] for r in rows),
                "load_s": round(sum(r[1] for r in rows), 3),
                "programs_built": sum(r[2] for r in rows),
                "build_s": round(sum(r[3] for r in rows), 3),
                "load_failures": 0 if store is None else store.load_failures}

    def events_since(self, cursor: int) -> tuple[int, list[dict]]:
        """Compile events newer than ``cursor`` (bounded ring) + the new
        cursor — /metrics replays them into the xla_compile_seconds
        histogram exactly once per consumer.  A cursor gap (the oldest
        retained event is not the consumer's next) means the ring
        overflowed between replays — a storm minting >MAX_EVENTS compiles
        inside one scrape interval — and is surfaced rather than silently
        skipped: ``events_dropped`` grows by the gap and a warning names
        the undercounting series.  A negative cursor marks a NEVER-read
        consumer (a freshly built app in a process whose ring already
        overflowed): it replays the retained events and charges no gap —
        those events were not lost between ITS scrapes."""
        fresh = cursor < 0
        lost = 0
        with self._lock:
            if cursor > self._seq:          # stale cursor across a reset
                cursor = 0
            if self._events and not fresh:
                oldest = self._events[0]["seq"]
                lost = max(0, (oldest - 1) - max(cursor, self._floor))
                if lost:
                    self.events_dropped += lost
            events = [dict(e) for e in self._events if e["seq"] > cursor]
            new_cursor = self._seq
        if lost:
            logger.warning(
                "compile-event ring overflowed: %d event(s) evicted before "
                "replay — xla_compile_seconds undercounts this interval "
                "(xla_compiles_total stays exact)", lost,
                extra={"events_dropped": lost})
        return new_cursor, events

    def storms(self) -> list[dict]:
        """Programs currently past the signature budget (the /debug/slo
        recompile verdict input)."""
        with self._lock:
            return [{"program": p.name, "signatures": len(p.sig_seen),
                     "budget": self.budget, "storms": p.storms}
                    for p in self._programs.values()
                    if len(p.sig_seen) > self.budget]

    def snapshot(self) -> dict:
        """The full /debug/compiles document: program inventory with
        per-signature compile walls (display-bounded)."""
        # copy-then-release (lfkt-lint LOCK006): O(programs) field copies
        # under the lock; the sort and document assembly run OFF it so a
        # /debug/compiles read never stalls a compile-event record
        with self._lock:
            rows = [(p.name, p.kind, p.site, p.compiles, p.dispatches,
                     p.compile_s, len(p.sig_seen), p.storms,
                     dict(p.signatures), p.device_s, p.intervals, p.stamped,
                     p.loads, p.load_s, p.builds, p.build_s)
                    for p in self._programs.values()]
            degrades = [dict(v) for v in self._degrades.values()]
            armed = self._armed
            storms_total = self.storms_total
            dropped = self.events_dropped
            stamps = {"armed": armed and self._stamps,
                      "misses": self.stamp_misses,
                      "pending": len(self._pending),
                      "ring": len(self._ring)}
        programs = []
        for name, kind, site, compiles, dispatches, compile_s, n_sigs, \
                storms, signatures, device_s, intervals, stamped, \
                loads, load_s, builds, build_s in sorted(rows):
            sigs = [{"signature": s, **meta}
                    for s, meta in signatures.items()]
            programs.append({
                "name": name, "kind": kind, "site": site,
                "compiles": compiles, "dispatches": dispatches,
                "compile_seconds_total": round(compile_s, 6),
                # of the two above: first calls that loaded the executable
                # from the store, and those that built and wrote it
                "loaded": loads, "load_seconds_total": round(load_s, 6),
                "built": builds, "build_seconds_total": round(build_s, 6),
                "signatures": n_sigs,
                "storms": storms,
                # an upper bound: unregistered device work and a program
                # without a stamp lie in the NEXT stamped interval
                "device_seconds_total": round(device_s, 6),
                "intervals": intervals,
                "stamped": stamped if kind == ENTRY else None,
                "signature_list": sigs,   # ledger bounds retention
            })
        store = self._store
        return {"armed": armed, "budget": self.budget,
                "storms_total": storms_total,
                "events_dropped": dropped,
                "stamps": stamps,
                "executable_store": None if store is None else {
                    **store.stats(), **self.store_totals()},
                "degrades": degrades,
                "programs": programs}


class _Handed(threading.local):
    """This thread's count of lowerings handed to the compiler (JAX's
    ``backend_compile`` duration event: fired around the compile or the
    persistent cache's answer, on the thread that called the jit)."""
    n = 0


_HANDED = _Handed()


def _on_jax_event(event, *_a, **_k) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _HANDED.n += 1


@functools.cache
def _listen() -> None:
    """Hear JAX's compile events, once a process.  The jit cache alone
    over-counts: it keys on every argument's sharding OBJECT, and on one
    device ``P()``, ``P(None, None)`` and ``P('dp', None)`` are one
    placement under three names (an output takes the name of whichever
    input it matches), so a state that passes through two programs grows
    each one's cache by entries that lower and compile nothing."""
    import jax

    jax.monitoring.register_event_duration_secs_listener(_on_jax_event)


class _TimedJit:
    """The per-entry-point wrapper ``timed_jit`` returns.  Call-compatible
    with the wrapped jit function; donation, static args and sharding all
    pass through untouched (the wrapper never copies or inspects buffers
    beyond shape/dtype metadata).

    WHAT dispatches a call is the compile layer's to say
    (utils/execstore.py :class:`StoredJit`: the jit itself, or a
    ``Compiled`` that was loaded from the executable store or built for
    it), and it says so before this wrapper looks at ``_armed``: an armed
    registry adds the counters, the compile events and the stamps, and
    changes no dispatch."""

    __slots__ = ("_reg", "_name", "_fn", "_probe", "_leaf", "__wrapped__",
                 "_prog")

    def __init__(self, reg: DevtimeRegistry, name: str, fn,
                 leaf: int | None = 0, key=None):
        self._reg = reg
        self._name = name
        self._fn = fn
        self._leaf = leaf
        self.__wrapped__ = fn
        # jax's PjitFunction exposes its compiled-variant count
        self._probe = fn._cache_size
        self._prog = StoredJit(name, fn, key)
        _listen()

    def __call__(self, *args, **kwargs):
        reg = self._reg
        store, prog = reg._store, self._prog
        if store is None or prog.on_jit:
            run = JIT
        else:
            run = prog.find(args, kwargs)
            if run is None:
                return self._first_call(reg, store, args, kwargs)
        if not reg._armed:          # disarmed: the same dispatch, and the
            if run is JIT:          # registry untouched (poisoned-reg test)
                return self._fn(*args, **kwargs)
            return run(*prog.dynamic(args), **prog.dynamic_kw(kwargs))
        if run is JIT:
            return self._call_jit(reg, args, kwargs)
        out = run(*prog.dynamic(args), **prog.dynamic_kw(kwargs))
        return self._dispatched(reg, out)

    def _call_jit(self, reg: DevtimeRegistry, args: tuple, kwargs: dict):
        probe = self._probe
        before = probe()
        handed = _HANDED.n
        t0 = time.perf_counter()
        out = self._fn(*args, **kwargs)
        dt = time.perf_counter() - t0
        if probe() > before and _HANDED.n > handed:
            reg.record_compile(self._name, _signature(args, kwargs), dt)
        return self._dispatched(reg, out)

    def _dispatched(self, reg: DevtimeRegistry, out):
        reg.record_dispatch(self._name)
        if reg._stamps and self._leaf is not None:   # the tracer is armed
            reg._stamp(self._name, self._leaf, out)
        return out

    def _first_call(self, reg: DevtimeRegistry, store: ExecStore,
                    args: tuple, kwargs: dict):
        """A signature the program has not seen: the store's layer loads
        or builds it; a compile event either way, with ``how``."""
        t0 = time.perf_counter()
        prog, armed = self._prog, reg._armed
        run, how = prog.first(store, args, kwargs, reg.degrades())
        if run is JIT and how is None and armed:   # the jit's own first
            return self._call_jit(reg, args, kwargs)   # call says what it did
        if run is JIT:
            # built here and not kept: the jit finds that executable in
            # its own caches and hands the compiler nothing
            out = self._fn(*args, **kwargs)
        else:
            out = run(*prog.dynamic(args), **prog.dynamic_kw(kwargs))
        if not armed:
            return out
        if how is not None:
            reg.record_compile(self._name, _signature(args, kwargs),
                               time.perf_counter() - t0,
                               how if how != NOT_KEPT else None)
        return self._dispatched(reg, out)


#: THE process-wide registry: entry points wrap themselves through it at
#: import, /metrics + /debug/compiles read it, tier-1 pins its counters.
DEVTIME = DevtimeRegistry()


def timed_jit(name: str, fn, site: str | None = None,
              leaf: int | None = 0, key=None):
    """Module-level convenience: wrap ``fn`` as program ``name`` on the
    process registry (the form every entry-point module uses)."""
    return DEVTIME.timed_jit(name, fn, site=site, leaf=leaf, key=key)


def register_program(name: str, kind: str = INNER,
                     site: str | None = None) -> str:
    """Module-level convenience: declare a trace-inner dispatch site on
    the process registry (lfkt-lint PERF001's registration form)."""
    return DEVTIME.register_program(name, kind=kind, site=site)
