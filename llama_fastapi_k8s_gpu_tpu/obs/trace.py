"""Request-scoped span trees: the lfkt-obs tracer.

The reference's only instrument is one request-timing log line (reference
api.py:179-194); nothing answers "where did THIS slow request spend its
time".  This module produces, per sampled request, a span tree covering
the whole serving path — httpd read, admission, queue wait, prefill/TTFT,
per-decode-chunk, SSE write — with watchdog trips, health transitions and
fault injections attached as events, kept in a bounded ring and exported
as JSON at ``GET /debug/traces`` (+ ``/debug/traces/{id}`` and the
in-flight ``/debug/requests`` snapshot, server/app.py).

Design constraints:

- **Zero dependencies** (stdlib only) and **zero cost when sampled out**:
  :meth:`Tracer.start` returns ``None`` for an unsampled request and every
  producer guards with ``if trace is not None`` — the decode hot path then
  pays one ``is None`` test per *chunk*, no allocation, no lock (guarded
  by tests/test_obs.py and the JIT purity lint: nothing here is reachable
  from a jit trace).
- **Thread-safe for sampled requests**: a trace is written by the handler
  coroutine, an engine worker/scheduler thread, and (for events) the
  watchdog thread; each trace carries its own small lock.  Spans are
  appended once per phase or per decode chunk — never per token.
- **W3C trace-context interop**: ``traceparent`` request headers are
  ingested (the incoming trace id becomes this trace's id, the incoming
  span id its remembered parent) and a valid ``traceparent`` for the
  request's root span is exported for response propagation.
- **One clock with the device trace**: :func:`phase` puts the program's
  own phases (a scheduler wave, a prefill slice, a tokenizer call) into a
  ``/debug/profile`` capture as ``lfkt.<name>`` host events, so an idle
  gap of the device is read against what the program says it was doing.
  The annotation is written only while the profiler can be
  (``LFKT_PROFILE_DIR``); while the tracer is armed a phase that names a
  request also keeps its ``rid`` on the thread, where the jit registry's
  device-done stamp reads it (obs/devtime.py).  With both off every call
  returns one shared no-op object.
- **The device inside ``first_token``**: :func:`end_first_token` closes
  the span with the registry's device intervals as children
  (``device.<program>``, ``host_fetch``), so that the one span that holds
  most of a long prompt's first token names what ran inside it.
"""

from __future__ import annotations

import contextlib
import itertools
import threading
import time
import uuid
import weakref
from collections import OrderedDict, deque

#: hard ceiling on spans+events per trace: a runaway generation must not
#: grow one trace without bound (past it, drops are counted, not stored)
MAX_NODES_PER_TRACE = 512

_TRACEPARENT_VERSION = "00"


_SPAN_ID_MASK = (1 << 64) - 1


def parse_traceparent(value: str | None) -> tuple[str, str] | None:
    """(trace_id, parent_span_id) from a W3C ``traceparent`` header, or
    None when absent/malformed (a bad header must never fail a request —
    it just starts a fresh trace)."""
    if not value:
        return None
    parts = value.strip().split("-")
    if len(parts) < 4 or parts[0] != _TRACEPARENT_VERSION:
        return None
    trace_id, span_id = parts[1].lower(), parts[2].lower()
    if len(trace_id) != 32 or len(span_id) != 16:
        return None
    try:
        if int(trace_id, 16) == 0 or int(span_id, 16) == 0:
            return None
    except ValueError:
        return None
    return trace_id, span_id


def span_traceparent(span: "Span | Trace | None") -> str | None:
    """The W3C ``traceparent`` naming ``span`` as the parent — the stamp
    an outbound hop (disagg REQ, migration REQ, router proxy attempt)
    carries so the serving side's span tree links under this exact node.
    None-tolerant: sampled-out callers pass their ``span=None`` straight
    through and the wire field rides as null (zero-cost contract).
    Accepts a :class:`Trace` too (some producers hand the whole trace
    around rather than a span — app.py's migrate hook does)."""
    if span is None:
        return None
    if isinstance(span, Trace):
        return span.traceparent()
    return (f"{_TRACEPARENT_VERSION}-{span._trace.trace_id}"
            f"-{span.span_id}-01")


class Span:
    """One timed phase of a request.  Built by :meth:`Trace.span` /
    :meth:`Span.child`; closed with :meth:`end` (idempotent)."""

    __slots__ = ("name", "span_id", "t0", "t1", "attrs", "events",
                 "children", "_trace")

    def __init__(self, name: str, trace: "Trace", t0: float | None = None):
        self.name = name
        self.span_id = trace._new_span_id()
        self.t0 = time.time() if t0 is None else t0
        self.t1: float | None = None
        self.attrs: dict = {}
        self.events: list[dict] = []
        self.children: list[Span] = []
        self._trace = trace

    # -- producer API -------------------------------------------------------
    def child(self, name: str, t0: float | None = None) -> "Span":
        sp = Span(name, self._trace, t0=t0)
        tr = self._trace
        with tr._lock:
            if tr._nodes < MAX_NODES_PER_TRACE:
                tr._nodes += 1
                self.children.append(sp)
            else:
                tr._dropped += 1
        return sp

    def set(self, **attrs) -> "Span":
        tr = self._trace
        with tr._lock:
            self.attrs.update(attrs)
        return self

    def event(self, name: str, **attrs) -> None:
        tr = self._trace
        with tr._lock:
            if tr._nodes < MAX_NODES_PER_TRACE:
                tr._nodes += 1
                self.events.append(
                    {"name": name, "at": time.time(), **attrs})
            else:
                tr._dropped += 1

    def end(self, t1: float | None = None) -> None:
        if self.t1 is None:
            self.t1 = time.time() if t1 is None else t1

    # -- export -------------------------------------------------------------
    def to_dict(self) -> dict:
        return {
            "name": self.name,
            "span_id": self.span_id,
            "start": self.t0,
            "end": self.t1,
            "duration_s": (self.t1 - self.t0) if self.t1 is not None else None,
            "attrs": dict(self.attrs),
            "events": list(self.events),
            "children": [c.to_dict() for c in self.children],
        }


class Trace:
    """One request's span tree plus the live metadata ``/debug/requests``
    snapshots (engine, lane, deadline, tokens so far)."""

    __slots__ = ("trace_id", "parent_span_id", "root", "meta",
                 "_lock", "_nodes", "_dropped", "finished",
                 "_span_base", "_span_ids")

    def __init__(self, name: str = "request",
                 traceparent: str | None = None,
                 t0: float | None = None):
        # ONE random read a trace (uuid4 reads the kernel's pool; a read per
        # span was the longest idle gap of a capture, 0.0295 s: ledger,
        # PR 53, `sala.longdoc-1`): its 128 bits are the trace id, its low
        # 64 the base the trace's span ids count up from
        seed = uuid.uuid4().int
        self._span_base = seed & _SPAN_ID_MASK
        self._span_ids = itertools.count(1)   # next() is atomic under the GIL
        ingested = parse_traceparent(traceparent)
        if ingested is not None:
            self.trace_id, self.parent_span_id = ingested
        else:
            self.trace_id, self.parent_span_id = f"{seed:032x}", None
        self._lock = threading.Lock()
        self._nodes = 1
        self._dropped = 0
        self.finished = False
        self.root = Span(name, self, t0=t0)
        #: live request metadata, overwritten in place (cheap single-key
        #: stores) — NOT part of the span tree
        self.meta: dict = {}

    def _new_span_id(self) -> str:
        """16 lowercase hex characters, never zero (W3C), distinct within
        the trace: the base plus a counter, no system call."""
        n = (self._span_base + next(self._span_ids)) & _SPAN_ID_MASK
        return f"{n or 1:016x}"

    # -- producer API -------------------------------------------------------
    def span(self, name: str, t0: float | None = None) -> Span:
        return self.root.child(name, t0=t0)

    def event(self, name: str, **attrs) -> None:
        self.root.event(name, **attrs)

    def note(self, **meta) -> None:
        """Update the live ``/debug/requests`` metadata (engine, lane,
        deadline, tokens...).  Single dict stores; no span allocation."""
        with self._lock:
            self.meta.update(meta)

    def traceparent(self) -> str:
        """A W3C traceparent naming this trace's root span (propagation)."""
        return (f"{_TRACEPARENT_VERSION}-{self.trace_id}"
                f"-{self.root.span_id}-01")

    # -- export -------------------------------------------------------------
    def to_dict(self) -> dict:
        with self._lock:
            d = {
                "trace_id": self.trace_id,
                "parent_span_id": self.parent_span_id,
                "finished": self.finished,
                "dropped_nodes": self._dropped,
                "meta": dict(self.meta),
                "root": self.root.to_dict(),
            }
        return d

    def summary(self) -> dict:
        r = self.root
        with self._lock:
            return {
                "trace_id": self.trace_id,
                "name": r.name,
                "start": r.t0,
                "duration_s": (r.t1 - r.t0) if r.t1 is not None else None,
                "finished": self.finished,
                "spans": self._nodes,
                "meta": dict(self.meta),
            }

    def _close_open_spans(self) -> None:
        """End every still-open span at the root's end time, stamped
        ``auto_closed`` — error paths (a prefill that raised, a scheduler
        that died mid-admission) must not export half-open spans that
        waterfall tools render as still-running phases."""
        t1 = self.root.t1
        with self._lock:
            stack = [self.root]
            while stack:
                s = stack.pop()
                if s.t1 is None:
                    s.t1 = t1
                    s.attrs.setdefault("auto_closed", True)
                stack.extend(s.children)


class Tracer:
    """Sampling decision + in-flight registry + bounded completed-trace ring.

    ``sample`` (LFKT_TRACE_SAMPLE): fraction of requests traced — 1.0
    traces everything, 0 disarms the tracer entirely (``start`` returns
    None before taking any lock).  Sampling is deterministic-by-counter so
    a 0.25 sample traces exactly every 4th request (testable, no RNG).
    ``ring`` (LFKT_TRACE_RING): completed traces kept for /debug/traces.
    """

    # start/finish run on the event loop; annotate_inflight on watchdog/
    # health threads; /debug reads on the loop — all table access is
    # lock-guarded (lfkt-lint LOCK001).  _armed is a single bool read on
    # the hot path (GIL-atomic by design).
    _GUARDED_BY = {"_ring": "_lock", "_inflight": "_lock",
                   "_count": "_lock", "started_total": "_lock",
                   "sampled_out_total": "_lock"}
    _SHARED_ATOMIC = ("_armed",)

    def __init__(self, sample: float | None = None, ring: int | None = None):
        if sample is None or ring is None:
            from ..utils.config import knob

            if sample is None:
                sample = knob("LFKT_TRACE_SAMPLE")
            if ring is None:
                ring = knob("LFKT_TRACE_RING")
        self.sample = max(0.0, min(1.0, float(sample)))
        self.ring = max(1, int(ring))
        self._lock = threading.Lock()
        self._ring: deque[Trace] = deque(maxlen=self.ring)
        self._inflight: OrderedDict[str, Trace] = OrderedDict()
        self._count = 0
        self.started_total = 0
        self.sampled_out_total = 0
        #: the hot-path guard: False means start() returns None without
        #: touching the lock and annotate_inflight is a no-op
        self._armed = self.sample > 0.0
        with _REGISTRY_LOCK:
            # process-wide event fan-in (annotate_all_inflight): watchdog/
            # health/fault events reach EVERY live tracer's in-flight
            # traces, including private instances tests hand to create_app
            _TRACERS.add(self)

    # -- lifecycle ----------------------------------------------------------
    def start(self, name: str = "request",
              traceparent: str | None = None,
              t0: float | None = None) -> Trace | None:
        """Begin a trace for one request, or None when sampled out."""
        if not self._armed:
            return None
        with self._lock:
            self._count += 1
            if self.sample < 1.0:
                # deterministic counter sampling: trace request i iff the
                # integral of the rate crosses an integer at i
                if int(self._count * self.sample) == int(
                        (self._count - 1) * self.sample):
                    self.sampled_out_total += 1
                    return None
            tr = Trace(name, traceparent=traceparent, t0=t0)
            self.started_total += 1
            self._inflight[tr.trace_id] = tr
        return tr

    def start_linked(self, name: str,
                     traceparent: str | None,
                     t0: float | None = None) -> Trace | None:
        """Begin a SERVER-SIDE trace fragment under a remote parent, or
        None.  Unlike :meth:`start` this is parent-based sampling: the
        client's decision propagates — we trace iff armed AND the wire
        actually carried valid trace context.  Running the counter
        sampler here would randomly orphan hops of requests the client
        sampled in, which is worse than either extreme."""
        if not self._armed or parse_traceparent(traceparent) is None:
            return None
        with self._lock:
            tr = Trace(name, traceparent=traceparent, t0=t0)
            self.started_total += 1
            self._inflight[tr.trace_id] = tr
        return tr

    def finish(self, trace: Trace | None) -> None:
        """Close a trace's root span and move it to the ring (idempotent;
        None-tolerant so callers never need their own sampled-out guard).
        Any span a producer's error path left open is swept closed at the
        root's end time (``auto_closed``)."""
        if trace is None:
            return
        trace.root.end()
        with self._lock:
            if trace.finished:
                return
            trace.finished = True
            self._inflight.pop(trace.trace_id, None)
            self._ring.append(trace)
        trace._close_open_spans()

    # -- global event fan-in (watchdog / health / faults) --------------------
    def annotate_inflight(self, name: str, **attrs) -> None:
        """Attach an event to every in-flight trace: watchdog trips,
        health transitions and fault injections are process-level facts
        that explain whatever requests they overlapped."""
        if not self._armed:
            return
        with self._lock:
            traces = list(self._inflight.values())
        for tr in traces:
            tr.event(name, **attrs)

    # -- /debug reads -------------------------------------------------------
    def get(self, trace_id: str) -> Trace | None:
        with self._lock:
            tr = self._inflight.get(trace_id)
            if tr is not None:
                return tr
            for t in self._ring:
                if t.trace_id == trace_id:
                    return t
        return None

    def traces(self) -> list[dict]:
        """Newest-first summaries of the completed ring."""
        with self._lock:
            ring = list(self._ring)
        return [t.summary() for t in reversed(ring)]

    def inflight(self) -> list[dict]:
        """Live-request snapshot for /debug/requests."""
        now = time.time()
        with self._lock:
            traces = list(self._inflight.values())
        out = []
        for t in traces:
            meta = dict(t.meta)
            deadline = meta.pop("deadline", None)
            out.append({
                "trace_id": t.trace_id,
                "name": t.root.name,
                "age_s": now - t.root.t0,
                "deadline_remaining_s":
                    (deadline - now) if deadline is not None else None,
                **meta,
            })
        return out

    def stats(self) -> dict:
        with self._lock:
            return {
                "sample": self.sample,
                "ring": self.ring,
                "ring_used": len(self._ring),
                "inflight": len(self._inflight),
                "started_total": self.started_total,
                "sampled_out_total": self.sampled_out_total,
            }


# -- phases: the program's own names inside the profiler's trace ----------

#: the disarmed :func:`phase`: one shared, reusable context manager that
#: does nothing (no allocation, no lock, no jax import)
_NO_PHASE = contextlib.nullcontext()
#: ``jax.profiler.TraceAnnotation`` while the profiler can be armed, else
#: None.  Resolved by :func:`arm_phases` when an engine is built — never
#: per call: ``phase`` reads this one module attribute (GIL-atomic).
_ANNOTATION = None
#: whether a phase that names a request keeps its ``rid`` on the thread:
#: while the tracer is armed (resolved with ``_ANNOTATION``)
_KEEP_RID = False


class _OpenRid(threading.local):
    """The ``rid`` of the innermost open phase of this thread that named
    one; "" outside any (a decode chunk is every lane's)."""
    rid = ""


_OPEN = _OpenRid()


def open_rid() -> str:
    """The request the calling thread is working for, as its open phases
    say (the jit registry reads it at each stamped dispatch)."""
    return _OPEN.rid


class _RidPhase:
    """A phase that names a request, while the tracer is armed: keeps the
    ``rid`` on the thread for the block, around the profiler's annotation
    where there is one."""

    __slots__ = ("_rid", "_ann", "_outer")

    def __init__(self, rid: str, ann):
        self._rid = rid
        self._ann = ann

    def __enter__(self):
        self._outer = _OPEN.rid
        _OPEN.rid = self._rid
        if self._ann is not None:
            self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        _OPEN.rid = self._outer
        if self._ann is not None:
            self._ann.__exit__(*exc)
        return False


def arm_phases(tracing: bool | None = None) -> bool:
    """Resolve, once per engine construction, what :func:`phase` does.  It
    writes the annotation iff ``LFKT_PROFILE_DIR`` is set (the knob that
    arms ``GET /debug/profile`` — a phase outside a capture costs the
    annotation's enter/exit and records nothing): returns that.  It keeps
    a request's ``rid`` on the thread iff the tracer is armed (``tracing``;
    default: the process tracer, ``LFKT_TRACE_SAMPLE`` > 0), so that an
    operator with spans and no profiler gets the same ``device.*``
    children."""
    global _ANNOTATION, _KEEP_RID
    from ..utils.tracing import profile_dir

    if profile_dir():
        from jax.profiler import TraceAnnotation

        _ANNOTATION = TraceAnnotation
    else:
        _ANNOTATION = None
    _KEEP_RID = TRACER._armed if tracing is None else bool(tracing)
    return _ANNOTATION is not None


def rid(node: "Span | Trace | None") -> str:
    """The trace id a phase carries as its ``rid`` stat; "" for a request
    that is sampled out (the phase still names the work)."""
    if node is None:
        return ""
    return (node if isinstance(node, Trace) else node._trace).trace_id


def phase(name: str, **attrs):
    """Context manager naming one phase of the program on the calling
    thread inside a profiler capture: a host event ``lfkt.<name>`` whose
    stats are ``attrs`` (a request's trace id rides as ``rid``, so a gap
    in the device trace leads to ``/debug/traces/{rid}``).  Sites are per
    wave, per slice or per request — never per token.  While the tracer
    is armed a phase with a ``rid`` also keeps it on the thread
    (:func:`open_rid`).  Disarmed, the shared no-op."""
    ann = _ANNOTATION
    if _KEEP_RID and "rid" in attrs:
        return _RidPhase(attrs["rid"], None if ann is None
                         else ann("lfkt." + name, **attrs))
    if ann is None:
        return _NO_PHASE
    return ann("lfkt." + name, **attrs)


# -- the device inside a first_token span ---------------------------------

def end_first_token(fspan: Span, prefill: Span | None = None, token=None,
                    **attrs) -> None:
    """Close a request's ``first_token`` span (the return of its last
    prefill slice's dispatch -> its first token on the host) with what the
    device did inside it, from the jit registry's interval ring
    (obs/devtime.py): a child ``device.<program>`` for every interval that
    overlaps the span, clipped to it, in the device's order, with
    ``seconds`` (the whole interval) and ``own`` (the dispatch was this
    request's: its slices and its sample; absent where the dispatch named
    no request, as a decode chunk of every lane); then ``host_fetch``,
    from the last of them to the span's end: the transfer, the wake-up and
    whatever the host did before it looked.  Beside live lanes the
    children are the other lanes' chunk and slices the token sat behind.

    ``token``: the first token's device array where the calling thread has
    just fetched it.  The fetch was the wait, so the thread stamps that
    program itself (``DEVTIME.fetched``) and the span never closes ahead
    of its last child.  Every ``prefill_slice`` child of ``prefill`` (the
    span's parent) also gets ``device_s`` / ``done_at`` of its own program
    (matched by the return of its dispatch, which lies inside the slice's
    span).

    An interval is an upper bound on its program: eager device work (a
    ``jnp.asarray``, a ``PRNGKey``), transfers and programs without a
    stamp lie in the interval of the next stamped one."""
    from .devtime import DEVTIME

    mine = fspan._trace.trace_id
    if token is not None:
        DEVTIME.fetched(token)
    t1 = time.time()
    ivs = DEVTIME.intervals_since(
        min(fspan.t0, prefill.t0) if prefill is not None else fspan.t0)
    if prefill is not None:
        own = [iv for iv in ivs if iv[1] == mine]
        for s in prefill.children:
            if s.name != "prefill_slice" or s.t1 is None:
                continue
            for _, _, start, end, t_ret in own:
                if s.t0 <= t_ret <= s.t1:
                    s.set(device_s=round(end - start, 6), done_at=end)
                    break
    last = None
    for name, rid, start, end, _ in ivs:
        if end < fspan.t0 or start > t1:
            continue
        last = min(end, t1)
        kid = fspan.child("device." + name, t0=max(start, fspan.t0))
        kid.end(last)
        kid.set(seconds=round(end - start, 6),
                **({"own": rid == mine} if rid else {}))
    if last is not None:
        fspan.child("host_fetch", t0=last).end(t1)
    fspan.set(**attrs).end(t1)


#: every live Tracer, for the process-level event fan-in; weak so a
#: test's discarded private tracer does not outlive its test
_REGISTRY_LOCK = threading.Lock()
_TRACERS: "weakref.WeakSet[Tracer]" = weakref.WeakSet()


def annotate_all_inflight(name: str, **attrs) -> None:
    """Attach an event to every in-flight trace of EVERY live tracer —
    the watchdog/health/fault fan-in.  Process-level facts must reach
    private tracers too (create_app(tracer=...)), not just the module
    default; each tracer's own ``_armed`` guard keeps this free when
    tracing is off."""
    with _REGISTRY_LOCK:
        tracers = list(_TRACERS)
    for t in tracers:
        t.annotate_inflight(name, **attrs)


def all_inflight_trees(limit: int = 32) -> list[dict]:
    """Full span trees of every in-flight trace across EVERY live tracer
    — the incident flight recorder's trace capture (obs/flightrec.py):
    the request a watchdog trip interrupted must ride the bundle even
    when the server was handed a private tracer.  Bounded: a bundle is a
    post-mortem, not a dump."""
    with _REGISTRY_LOCK:
        tracers = list(_TRACERS)
    out: list[dict] = []
    for t in tracers:
        with t._lock:
            traces = list(t._inflight.values())
        for tr in traces:
            out.append(tr.to_dict())
            if len(out) >= limit:
                return out
    return out


#: process-wide default tracer the serving stack shares: the server starts
#: traces on it (unless create_app was handed a private instance), engines
#: attach spans to the handed-down Trace objects, and the watchdog/health/
#: fault layers annotate whatever is in flight across all live tracers.
#: Built from the env knobs at import.
TRACER = Tracer()
