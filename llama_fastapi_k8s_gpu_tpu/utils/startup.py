"""The start-up timeline: process start to the READY flip, phase by phase.

``setup`` is what an operator feels as "pod start to READY" and the one
end-to-end number most of which no counter inside the program used to
cover.  A :class:`Timeline` is a list of :class:`Phase` stamps on ONE clock,
``time.time()`` (the clock a launcher's own stamps are on), opened and
closed where the work happens:

- ``server/__main__.py`` stamps what the process pays before a byte of the
  model is read (``before_main``: process start to this package's import;
  ``imports``, ``backend_init``, ``compile_cache``) and hands its timeline
  to the app;
- every engine owns one with no origin of its own (``Engine.startup``:
  ``gguf_open`` ... ``warmup``); ``Engine.load_phases`` is a view of it
  (:func:`legacy_load_phases`);
- the app merges the two at the READY flip and serves the frozen document
  as ``/health`` ``engine.startup`` (:meth:`Timeline.doc`;
  docs/OBSERVABILITY.md "Start-up timeline").

A phase that does not apply is absent, never 0.  Nothing here wraps a jit
or runs on a request's path: :class:`CompileMeter` READS the two ledgers
the program already keeps (``obs/devtime.py``, ``utils/jaxcache.py``)
before and after a phase.
"""

from __future__ import annotations

import os
import time

CLOCK = "time.time"


def process_start(fallback: float) -> tuple[float, str]:
    """(unix seconds at which the kernel started this process, where that
    came from).  ``/proc/self/stat`` field 22 is the start in clock ticks
    after boot (good to 10 ms; ``/proc/stat`` ``btime`` is whole seconds),
    read against ``CLOCK_BOOTTIME`` now.  Anywhere that cannot be read the
    caller's own earliest stamp stands in, and the document says so."""
    try:
        with open("/proc/self/stat") as f:
            # the command name (field 2) may hold spaces and parentheses
            fields = f.read().rsplit(")", 1)[1].split()
        ticks = int(fields[19])          # field 22; fields[0] is field 3
        age = time.clock_gettime(time.CLOCK_BOOTTIME) \
            - ticks / os.sysconf("SC_CLK_TCK")
        now = time.time()
        if 0.0 <= age and now - age <= fallback:
            return now - age, "proc_stat"
    except (OSError, ValueError, IndexError, AttributeError):
        pass
    return fallback, "package_import"


class Phase:
    """One named stretch of start-up: opened at ``t0``, closed at ``t1``
    (unix seconds), with free ``attrs`` and child phases.  A context
    manager: leaving the block closes it.  ``meter`` has closing add what
    compiled meanwhile to ``attrs``: ``"cache"`` the persistent cache's
    four counters, ``"programs"`` all of :meth:`CompileMeter.read`."""

    __slots__ = ("name", "t0", "t1", "attrs", "children", "_meter")

    def __init__(self, name: str, t0: float | None = None,
                 t1: float | None = None, meter: str | None = None, **attrs):
        self.name = name
        self.t0 = time.time() if t0 is None else t0
        self.t1 = t1
        self.attrs = attrs
        self.children: list[Phase] = []
        self._meter = (CompileMeter(), meter == "programs") if meter else None

    def __enter__(self) -> "Phase":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def close(self) -> None:
        self.t1 = time.time()
        if self._meter is not None:
            meter, programs = self._meter
            self.attrs.update(meter.read(self.seconds) if programs
                              else meter.cache())

    @property
    def seconds(self) -> float:
        return (time.time() if self.t1 is None else self.t1) - self.t0

    def child(self, name: str, **kw) -> "Phase":
        """Open a child now."""
        kid = Phase(name, **kw)
        self.children.append(kid)
        return kid

    def doc(self, origin: float) -> dict:
        out = {"name": self.name, "start_s": round(self.t0 - origin, 3),
               "seconds": round(self.seconds, 3)}
        if self.attrs:
            out["attrs"] = dict(self.attrs)
        if self.children:
            out["children"] = [c.doc(origin) for c in self.children]
        return out


class Timeline:
    """Top-level phases in the order they were opened.  One writer at a
    time by construction (the entry point, then the load thread, then the
    app's start-up hook); readers take :meth:`doc` after the READY flip.
    Only the timeline that is served has an origin
    (``Timeline(*process_start(...))``); an engine's holds stamps alone."""

    def __init__(self, process_start_unix: float | None = None,
                 source: str | None = None):
        self.process_start_unix = process_start_unix
        self.source = source
        self.ready_unix: float | None = None
        self.phases: list[Phase] = []

    def phase(self, name: str, t0: float | None = None,
              t1: float | None = None, **kw) -> Phase:
        """Open (or, with both ends given, record) the phase ``name``."""
        return self.add(Phase(name, t0, t1, **kw))

    def add(self, ph: Phase) -> Phase:
        """Take ``ph`` in; a second phase of a name replaces the first (a
        warm-up run again)."""
        self.phases = [p for p in self.phases if p.name != ph.name] + [ph]
        return ph

    def get(self, name: str) -> Phase | None:
        return next((p for p in self.phases if p.name == name), None)

    def absorb(self, other) -> None:
        """Take ``other``'s phases in (an engine's, at the READY flip);
        anything that is no timeline (a fake engine's attribute) is not."""
        if isinstance(other, Timeline):
            for ph in other.phases:
                self.add(ph)

    def doc(self) -> dict:
        """The ``/health`` ``engine.startup`` document.  ``unnamed_s`` is
        ``ready_s`` less the top-level seconds AS PRINTED, so the printed
        numbers add up."""
        origin = self.process_start_unix
        end = self.ready_unix
        phases = [p.doc(origin) for p in sorted(
            (p for p in self.phases if p.t1 is not None),
            key=lambda p: p.t0)]
        out = {"process_start_unix": round(origin, 3),
               "process_start_from": self.source, "clock": CLOCK,
               "ready_unix": None, "ready_s": None, "phases": phases,
               "unnamed_s": None}
        if end is not None:
            ready_s = round(end - origin, 3)
            out.update(ready_unix=round(end, 3), ready_s=ready_s,
                       unnamed_s=round(
                           ready_s - sum(p["seconds"] for p in phases), 3))
        return out


#: ``Engine.load_phases`` key -> (top-level phase, child or None): the six
#: keys ``/health`` ``engine.load_phases`` has had since PR 22, read by
#: ``benchmarks/layer_metrics/load_s.py`` and ``warmup_s.py``
_LEGACY = {"tokenizer_s": ("tokenizer", None), "probes_s": ("probes", None),
           "params_s": ("params", None), "params_prep_s": ("params", "prep"),
           "params_stack_s": ("params", "stack"),
           "warmup_s": ("warmup", None)}


def legacy_load_phases(tl: Timeline) -> dict:
    """The view ``Engine.load_phases`` returns: the legacy keys at their
    0.1 s rounding, each present iff its phase ran to its end."""
    out = {}
    for key, (top, kid) in _LEGACY.items():
        ph = tl.get(top)
        if ph is not None and kid is not None:
            ph = next((c for c in ph.children if c.name == kid), None)
        if ph is not None and ph.t1 is not None:
            out[key] = round(ph.seconds, 1)
    return out


class CompileMeter:
    """What compiled between construction and :meth:`read`, by the
    program's own two ledgers: the jit registry (``obs/devtime.py``:
    entry programs' compile counts and first-dispatch walls) and JAX's
    persistent-cache events (``utils/jaxcache.py``).  A compile request
    that is neither a hit nor a miss was compiled anew and not written
    back: under the cache's 0.5 s floor, paid again by every start.  Of
    the programs compiled, the executable store's part (utils/execstore.py):
    ``programs_loaded`` / ``load_s`` read their executable from it,
    ``programs_built`` / ``build_s`` built and wrote one (the rest, under
    the floor, went through the jit as before); ``load_failures`` counts
    files that did not load."""

    def __init__(self):
        from ..obs.devtime import DEVTIME
        from .jaxcache import compile_cache_stats

        self._ledger = DEVTIME.compile_ledger
        self._store = DEVTIME.store_totals
        self._cache = compile_cache_stats
        self._ledger0 = self._ledger()
        self._store0 = self._store()
        self._cache0 = self._cache()

    def cache(self) -> dict:
        """``cache_requests`` / ``cache_hits`` / ``cache_misses`` /
        ``compiled_uncached`` since construction."""
        now = self._cache()
        d = {k: now[k] - self._cache0[k]
             for k in ("requests", "hits", "misses")}
        return {"cache_requests": d["requests"], "cache_hits": d["hits"],
                "cache_misses": d["misses"],
                "compiled_uncached": d["requests"] - d["hits"] - d["misses"]}

    def read(self, seconds: float, top: int = 8) -> dict:
        """The attributes of a phase that lasted ``seconds``: the cache
        counters, ``programs_compiled`` and ``compile_s`` (the registry's
        deltas: every first call of a signature and its wall),
        ``execute_s`` (the phase less ``compile_s``), the store's part of
        them and the ``top`` programs by compile seconds."""
        rows = []
        for name, (n, s) in self._ledger().items():
            n0, s0 = self._ledger0.get(name, (0, 0.0))
            if n > n0:
                rows.append({"name": name, "compiles": n - n0,
                             "compile_s": round(s - s0, 3)})
        rows.sort(key=lambda r: -r["compile_s"])
        compile_s = round(sum(r["compile_s"] for r in rows), 3)
        store = self._store()
        return {"programs_compiled": sum(r["compiles"] for r in rows),
                "compile_s": compile_s,
                "execute_s": round(seconds - compile_s, 3),
                **{k: round(v - self._store0[k], 3)
                   for k, v in store.items()},
                **self.cache(), "top_programs": rows[:top]}
