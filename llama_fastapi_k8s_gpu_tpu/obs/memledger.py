"""Live HBM memory ledger — lfkt-mem's accounting half (ISSUE 10).

PR 9 made HBM the contended resource: N models' weights, one shared
paged KV arena, dense rings and the continuous scheduler's scratch now
partition a single chip's memory — but the only accounting was a
load-time weight budget.  At serve time an OOM, a mysteriously shrinking
pool, or a leaked ring was invisible until the process died.  This
module is the process-wide **component registry** (mirroring the devtime
program registry, obs/devtime.py): every device-allocation surface
registers a live byte-count provider with attribution, and the ledger
reconciles the sum against device ground truth so unattributed bytes are
a *visible gauge* (the ``residual`` line), not a silent gap.

Registration (:func:`register_component`): a component name from the
:data:`~.catalog.MEM_COMPONENTS` catalog (enforced at runtime —
``KeyError`` — and statically by lfkt-lint OBS003), an owner (held by
**weakref**: a dead engine's rows vanish with it, so tests and watchdog
re-inits never accumulate ghost attribution), and a provider
``fn(owner) -> int | dict[model, int]`` reading *shape metadata only*
(``.nbytes`` is safe even on donated buffers — the kv_cache_bytes
precedent).  Providers run at snapshot time (scrapes, ``/debug/memory``,
incident capture), never on the decode path.

Ground truth: ``device.memory_stats()['bytes_in_use']`` where the
backend reports it (TPU), else the sum over ``jax.live_arrays()`` (CPU
tests — exact for the single-process case).  The reconciliation is
pinned by tests/test_memledger.py: on a CPU two-model paged registry the
component sum matches live-array ground truth within 5%.

Pressure: :meth:`MemLedger.pressure` is the AdmissionController's memory
signal (engine/continuous.py) — True when device headroom drops under
``LFKT_MEM_PRESSURE_FRACTION`` of the HBM limit, so the scheduler stops
feeding prefill into a chip about to OOM.  It only ever consults
``memory_stats`` (never the O(arrays) live-array walk) and latches off
where the backend has no stats, so a CPU pod pays one failed probe ever.

Zero cost when disarmed (``LFKT_MEM_LEDGER=0``): ``pressure()`` returns
False on a single attribute read — no lock, no allocation — and
``snapshot()`` returns a two-key stub; pinned by the poisoned-ledger
test (the tracer's ``LFKT_TRACE_SAMPLE=0`` analogue).
"""

from __future__ import annotations

import logging
import threading
import weakref

from .catalog import MEM_COMPONENTS

logger = logging.getLogger(__name__)

#: /debug/memory document schema (tools and tests pin it)
SCHEMA = 1


def _physical_nbytes(leaf) -> int:
    """PHYSICAL resident bytes of one array: per-shard size × addressable
    shard count, so a replicated array counts one copy per device and a
    sharded one its pieces — matching what the devices' ``memory_stats``
    count (the reconciliation baseline).  Computed from SHARDING METADATA
    only: materializing ``addressable_shards[i].data`` would cache
    per-device view arrays on the parent, permanently double-counting
    every provider-visited array in the ``jax.live_arrays()`` ground
    truth.  Falls back to the logical ``.nbytes`` for non-array leaves
    and donated buffers whose sharding is no longer readable."""
    try:
        sharding = leaf.sharding
        n = leaf.dtype.itemsize
        for d in sharding.shard_shape(leaf.shape):
            n *= d
        return int(n) * len(sharding.addressable_devices)
    except Exception:  # noqa: BLE001 — scalar leaf / donated buffer
        return int(getattr(leaf, "nbytes", 0) or 0)


def tree_nbytes(tree) -> int:
    """Total physical bytes over a pytree's array leaves (0 for None).
    Shape/placement metadata only — safe on donated buffers, never a
    device sync."""
    if tree is None:
        return 0
    import jax

    return sum(_physical_nbytes(leaf) for leaf in jax.tree.leaves(tree))


class MemLedger:
    """The process-wide memory ledger (module instance: :data:`MEMLEDGER`).

    Producers register at engine/pool construction; consumers are
    ``/debug/memory``, the ``hbm_bytes`` gauges at ``/metrics``, the
    flight recorder's incident bundles, and the admission controller's
    pressure signal."""

    # the entry table is appended at construction time and pruned at
    # snapshot time from scrape threads: one mutex (lfkt-lint LOCK001).
    # _armed / pressure_fraction / the stats latch are single-word
    # hot-path reads by design.
    _GUARDED_BY = {"_entries": "_lock"}
    _SHARED_ATOMIC = ("_armed", "pressure_fraction", "_no_device_stats",
                      "last_headroom", "stats_fn")

    def __init__(self, armed: bool | None = None,
                 pressure_fraction: float | None = None):
        if armed is None or pressure_fraction is None:
            from ..utils.config import knob

            if armed is None:
                armed = bool(knob("LFKT_MEM_LEDGER"))
            if pressure_fraction is None:
                pressure_fraction = float(knob("LFKT_MEM_PRESSURE_FRACTION"))
        self._lock = threading.Lock()
        #: (component, weakref(owner), provider) — owners are engines and
        #: KV pools; a collected owner's rows disappear at the next prune
        self._entries: list[tuple] = []
        self._armed = bool(armed)
        self.pressure_fraction = max(0.0, min(1.0, float(pressure_fraction)))
        #: latched after the first failed memory_stats probe: the pressure
        #: check must never pay a per-wave exception on stat-less backends
        self._no_device_stats = False
        #: (free_bytes, limit_bytes) from the most recent successful
        #: device-stats read — the mem_pressure trace event's byte counts
        self.last_headroom: tuple[int, int] | None = None
        #: test seam: () -> memory_stats-shaped dict (injected fake HBM
        #: limits); None = the real device
        self.stats_fn = None

    # -- configuration (tests + ops) ---------------------------------------
    def configure(self, armed: bool | None = None,
                  pressure_fraction: float | None = None) -> None:
        if armed is not None:
            self._armed = bool(armed)
        if pressure_fraction is not None:
            self.pressure_fraction = max(0.0, min(1.0,
                                                  float(pressure_fraction)))

    @property
    def armed(self) -> bool:
        return self._armed

    def reset(self) -> None:
        """Drop every registration (tests)."""
        with self._lock:
            self._entries = []

    # -- registration ------------------------------------------------------
    def register_component(self, component: str, owner, provider) -> None:
        """Register one allocation surface.  ``provider(owner)`` returns
        live bytes — an int (the row's model label is the owner's
        ``model_name``) or a ``{model: bytes}`` dict (per-namespace
        surfaces).  The owner is weakly held; registration is idempotent
        per (component, owner)."""
        spec = MEM_COMPONENTS.get(component)
        if spec is None or component == "residual":
            raise KeyError(
                f"memory component {component!r} is not in the "
                "MEM_COMPONENTS catalog (obs/catalog.py); register it "
                "before reporting it" if spec is None else
                "the 'residual' component is computed by the ledger, "
                "never registered")
        ref = weakref.ref(owner)
        with self._lock:
            for comp, r, _fn in self._entries:
                if comp == component and r() is owner:
                    return
            self._entries.append((component, ref, provider))

    # -- consumers ---------------------------------------------------------
    def _rows(self) -> list[dict]:
        """Live attribution rows, duplicate (component, model) keys merged
        by summing (two engines serving the same alias must not fight
        over one gauge series).  Dead owners are pruned; a raising
        provider is skipped — telemetry must never fail serving."""
        with self._lock:
            entries = list(self._entries)
        merged: dict[tuple, int] = {}
        dead = False
        for component, ref, provider in entries:
            owner = ref()
            if owner is None:
                dead = True
                continue
            try:
                val = provider(owner)
            except Exception:  # noqa: BLE001 — telemetry must never fail
                logger.exception("memory-ledger provider for %r raised",
                                 component)
                continue
            spec = MEM_COMPONENTS[component]
            if isinstance(val, dict):
                items = val.items()
            else:
                items = ((getattr(owner, "model_name", "") or "", val),)
            for model, b in items:
                b = max(0, int(b or 0))
                if b == 0 and not spec.always:
                    # zero rows drop (an absent scratch ring is not a
                    # row) — EXCEPT always-components, whose zero is the
                    # alert condition (an exhausted free list must read
                    # 0, not "no data")
                    continue
                key = (component, str(model))
                merged[key] = merged.get(key, 0) + b
        if dead:
            with self._lock:
                self._entries = [e for e in self._entries
                                 if e[1]() is not None]
        return [{"component": c, "model": m, "bytes": b,
                 "device": MEM_COMPONENTS[c].device}
                for (c, m), b in sorted(merged.items())]

    def _raw_device_stats(self):
        """The real device probe, summed over the local devices (separate
        so tests can pin the latch semantics without faking a backend).
        Providers report physical bytes across every shard, so the
        baseline is every local device's in-use/limit."""
        try:
            import jax

            in_use = limit = 0
            seen = False
            for d in jax.local_devices():
                st = d.memory_stats()
                if not st or "bytes_in_use" not in st:
                    continue
                seen = True
                in_use += int(st["bytes_in_use"])
                limit += int(st.get("bytes_limit") or 0)
            if not seen:
                return None
            out = {"bytes_in_use": in_use}
            if limit:
                out["bytes_limit"] = limit
            return out
        except Exception:  # noqa: BLE001 — backend has no stats
            return None

    def _device_stats(self) -> dict:
        if self.stats_fn is not None:
            try:
                return dict(self.stats_fn() or {})
            except Exception:  # noqa: BLE001 — test seam, same contract
                return {}
        if self._no_device_stats:
            return {}
        stats = self._raw_device_stats()
        # a backend WITH memory stats may legitimately report ZERO bytes
        # in use (the registry's pre-load fit check runs before the first
        # allocation) — only the absence of the field marks a stat-less
        # backend; latching on falsy 0 would disable pressure() and
        # fit_check() for the process lifetime on exactly the hardware
        # they target
        if not stats or "bytes_in_use" not in stats:
            self._no_device_stats = True
            return {}
        return dict(stats)

    def ground_truth(self) -> dict:
        """What the device says is resident: ``memory_stats`` where the
        backend reports it, else the exact sum over ``jax.live_arrays()``
        (CPU) — the reconciliation baseline the residual line is computed
        against."""
        stats = self._device_stats()
        if stats:
            limit = stats.get("bytes_limit")
            return {"source": "device.memory_stats",
                    "bytes": int(stats["bytes_in_use"]),
                    "limit": int(limit) if limit else None}
        try:
            import jax

            # same physical (per-shard) measure as the providers, so the
            # two sides of the reconciliation can never disagree about
            # what a replicated array "costs"
            total = sum(_physical_nbytes(a) for a in jax.live_arrays())
        except Exception:  # noqa: BLE001 — jax-less process (tools)
            return {"source": "unavailable", "bytes": None, "limit": None}
        return {"source": "jax.live_arrays", "bytes": int(total),
                "limit": None}

    def snapshot(self) -> dict:
        """The full ``/debug/memory`` core document: attribution rows,
        ground truth, the residual line, and headroom."""
        if not self._armed:
            return {"schema": SCHEMA, "armed": False}
        rows = self._rows()
        truth = self.ground_truth()
        attributed = sum(r["bytes"] for r in rows if r["device"])
        host = sum(r["bytes"] for r in rows if not r["device"])
        residual = (truth["bytes"] - attributed
                    if truth["bytes"] is not None else None)
        headroom = None
        if truth["bytes"] is not None and truth["limit"]:
            headroom = {
                "bytes": truth["limit"] - truth["bytes"],
                "limit": truth["limit"],
                "fraction": round(
                    (truth["limit"] - truth["bytes"]) / truth["limit"], 4),
                "pressure_fraction": self.pressure_fraction,
            }
        return {
            "schema": SCHEMA,
            "armed": True,
            "components": rows,
            "attributed_bytes": attributed,
            "host_bytes": host,
            "ground_truth": truth,
            "residual_bytes": residual,
            "headroom": headroom,
        }

    # -- the admission controller's signal (engine/continuous.py) ----------
    def pressure(self) -> bool:
        """True when device HBM headroom is under ``pressure_fraction``
        of the limit.  Disarmed: one attribute read, no lock, no
        allocation (poisoned-ledger pin).  Stat-less backends (CPU)
        latch False after a single probe."""
        if not self._armed:
            return False
        stats = self._device_stats()
        limit = stats.get("bytes_limit")
        if not limit:
            return False
        free = int(limit) - int(stats.get("bytes_in_use", 0))
        self.last_headroom = (free, int(limit))
        return free < self.pressure_fraction * int(limit)

    def fit_check(self, est_bytes: int, label: str = "") -> str | None:
        """Pre-load fit check (serving/registry.py): would loading
        ``est_bytes`` more clearly overrun the device?  Returns the
        refusal message, or None when it fits / cannot be judged (no
        device stats — the weight *budget* still applies there)."""
        if not self._armed or est_bytes <= 0:
            return None
        stats = self._device_stats()
        limit = stats.get("bytes_limit")
        if not limit:
            return None
        free = int(limit) - int(stats.get("bytes_in_use", 0))
        need = int(est_bytes)
        if need <= free:
            return None
        return (f"pre-load fit check: loading {label or 'model'!s} needs "
                f"~{need / 1e6:.0f}MB but the device reports only "
                f"{free / 1e6:.0f}MB of {limit / 1e6:.0f}MB HBM free — "
                "shrink the manifest, the KV arena, or quantize harder "
                "(docs/RUNBOOK.md 'Diagnosing HBM OOM')")


#: THE process-wide ledger: engines and pools register at construction,
#: /metrics + /debug/memory + incident bundles read it, the continuous
#: scheduler consults pressure() once per wave.
MEMLEDGER = MemLedger()


def register_component(component: str, owner, provider) -> None:
    """Module-level convenience: register on the CURRENT process ledger
    (resolved at call time so tests can swap :data:`MEMLEDGER`)."""
    MEMLEDGER.register_component(component, owner, provider)
