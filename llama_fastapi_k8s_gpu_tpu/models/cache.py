"""The cache KIND of a sequence as ONE object (docs/KV_CACHE.md "Cache
kinds"): ``ModelConfig.cache_kind`` names the kind, :func:`cache_of` maps
the name to its :class:`CacheKind`, here and nowhere else, and everything
outside ``models/`` asks the object and never tests the name.  Each kind's
instance is ``CACHE`` in the module that holds its code (the ring's in
models/llama.py): a new kind is one module and one row of :data:`_MODULES`.
"""

from __future__ import annotations

import collections
import dataclasses
import importlib
import types
from typing import Callable, Mapping

from .config import (
    CONV_RING, LATENT_RING, RING, SSM_RING, SSM_WINDOW_SHARED, STATE_RING,
    WINDOW_GLOBAL_RING, WINDOW_SUMMARIES, ModelConfig)

#: what an engine can ASK of a cache kind, in the order the asks are
#: checked, each with the setting as its operator wrote it; ``supports``
#: answers all but ``slice``
FEATURES = {
    "int8": "LFKT_KV_DTYPE=int8",
    "paged": "LFKT_KV_PAGED=1",
    "slice": "LFKT_PREFILL_CHUNK={}",
}

#: the counters every kind keeps, a ring's (0 for good where there is none):
#: slots its decode steps read / needed, K rows the decode kernel stored
RING_GAUGES = {"ring_slots_read_total": "read",
               "ring_slots_live_total": "live",
               "ring_rows_written_total": "rows_written"}


@dataclasses.dataclass(frozen=True, eq=False)   # compared by identity
class CacheKind:
    """One cache kind: functions of the configuration, and flags.  The
    defaults are the ring's answers where a kind can share them."""

    name: str    # ``ModelConfig.cache_kind``
    arch: str    # the architecture a refusal names
    init: Callable      # (cfg, dtype) -> the leaves of one sequence
    nbytes: Callable    # (cfg) -> their bytes
    #: (cfg, pos (B,), live (B,) bool | None) -> a lane step's ``kv_bound``
    step_bound: Callable
    #: {``int8``, ``paged``} -> True, or why not:
    #: the end of "<SETTING> cannot serve architecture '<arch>': <reason>"
    supports: Mapping
    #: (cfg) -> the decode kernel's block where a decode step runs it, else 0
    decode_kernel_block: Callable
    #: (counts, cfg, wanted, n_steps, live): one decode chunk's reads into
    #: the counters, for the sequences at positions ``wanted`` under the
    #: bound of the lanes dispatched ``live`` (default ``wanted``)
    note_decode: Callable
    #: the stack that runs the kind, called as ``models/llama.py forward``
    #: is; None: that file's one stack
    forward: Callable | None = None
    #: (cfg, chunk) -> why a prefill slice of ``chunk`` tokens cannot serve
    #: the kind (the same sentence's end), or None
    slice_rule: Callable = lambda cfg, chunk: None
    #: the cache can be rolled back to a prefix of what it holds: what
    #: prefix reuse and a lane claim ask of it
    rolls_back: bool = False
    #: a prompt is always prefilled in slices (a pass must lie inside a
    #: window, or be small enough for per-query masks)
    always_slices: bool = True
    #: the decode kernel, where it serves, stores the step's row too
    #: (False: XLA writes before the call; None: there is no ring)
    kernel_writes: bool | None = True
    #: (cfg, asked) -> the ``attn_impl`` the engine resolves from there
    attn_impl: Callable = lambda cfg, asked: asked
    #: (cfg, asked, attn_impl, probed: list) -> (cfg, attn_impl) after the
    #: compile probes of the kind's OWN kernels, their names appended
    probe_kernels: Callable = lambda cfg, asked, attn_impl, probed: (
        cfg, attn_impl)
    #: (cfg) -> the widest prefill slice the block takes (engine/slices.py)
    widest_slice: Callable = lambda cfg: 0
    #: (cfg, engine) -> the /health ``engine.cache`` block (the ring: none)
    health: Callable = lambda cfg, engine: None
    #: (cfg) -> further keys of /health ``engine``, beside the ``cache``
    #: block: which of the kind's own reads serves
    engine_health: Callable = lambda cfg: {}
    #: the kind's OWN counters beside :data:`RING_GAUGES`: {/metrics name
    #: (obs/catalog.py): the key of :meth:`new_counts` it reads}
    own_gauges: Mapping = types.MappingProxyType({})
    #: (counts, cfg, n_prompt, slices) -> the traced ``prefill`` span's
    #: attributes, counting what the prefill does; ``slices``: its plan
    #: [(offset, tokens)], None for an untraced request
    note_prefill: Callable = lambda counts, cfg, n_prompt, slices: {}
    #: ``note_prefill`` COUNTS (not only names what a traced span shows), so
    #: it is given the plan of an untraced request's slices too
    counts_prefill: bool = False
    #: (counts, cfg, tokens): one dispatched prefill program of ``tokens``
    #: rows into the counters; ``cfg``: the one the program was built for
    #: (``slice_cfg``).  Returns None, or the layer applications the
    #: program ran where that is not ``tokens`` x the stack's
    note_slice: Callable = lambda counts, cfg, tokens: None
    #: (cfg, holds_last) -> the configuration a prefill slice's program is
    #: built for, by whether the slice holds its prompt's last token: a kind
    #: whose upper layers write no cache runs the others on part of the
    #: stack (a SECOND program a slice shape in the warm-up); most: ``cfg``
    slice_cfg: Callable = lambda cfg, holds_last: cfg
    #: (counts, cfg, lanes, n_steps): the lanes a decode chunk's program
    #: stepped, whether they hold a request or not, into the counters
    note_lanes: Callable = lambda counts, cfg, lanes, n_steps: None
    #: (live rows at the chunk's end) -> a ``decode_chunk`` span's attributes
    decode_span_attrs: Callable = lambda pos: {}
    #: (cfg) -> attributes that EVERY traced ``prefill`` and ``decode_chunk``
    #: span of the kind carries (the ring: a looped stack's passes)
    span_attrs: Callable = lambda cfg: {}
    #: (cfg) -> the architecture a refusal names, where the kind serves
    #: more than ``arch`` (the latent ring: ``longcat-flash`` too)
    arch_for: Callable | None = None
    #: (cfg) -> the object that answers for THIS configuration, where files
    #: of the kind differ in what their cache holds (the latent ring: a
    #: second leaf of index keys, with counters of its own); None: this one
    variant: Callable | None = None

    def arch_of(self, cfg: ModelConfig) -> str:
        return self.arch_for(cfg) if self.arch_for else self.arch

    def new_counts(self) -> collections.Counter:
        """The kind's counters at 0 (``Engine.cache_counts``; ``update``
        ADDS a chunk's or a prompt's counts)."""
        return collections.Counter(dict.fromkeys(
            (*RING_GAUGES.values(), *self.own_gauges.values()), 0))

    def gauges(self, counts: dict) -> dict:
        """The counters under their /metrics names."""
        return {name: counts[key]
                for name, key in (RING_GAUGES | dict(self.own_gauges)).items()}


#: the module that holds each kind's ``CACHE``
_MODULES = {RING: "llama", WINDOW_SUMMARIES: "eva", STATE_RING: "sala",
            LATENT_RING: "mla", WINDOW_GLOBAL_RING: "hybrid",
            CONV_RING: "lfm2", SSM_WINDOW_SHARED: "phi4flash",
            SSM_RING: "jamba"}


def cache_of(cfg: ModelConfig) -> CacheKind:
    """The kind of cache a sequence of this configuration holds.  The
    module is imported on first use: the kinds' modules import
    models/llama.py, which calls this."""
    kind = importlib.import_module(
        "." + _MODULES[cfg.cache_kind], __package__).CACHE
    return kind.variant(cfg) if kind.variant else kind
