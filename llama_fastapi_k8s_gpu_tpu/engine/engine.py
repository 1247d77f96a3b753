"""The in-tree replacement for ``llama_cpp.Llama``.

The reference constructs ``Llama(model_path, n_gpu_layers=-1, n_ctx=1024)`` at
import time and calls ``create_chat_completion(...)`` from a worker thread
(reference api.py:24-28, 55-63).  This class preserves that contract —
eager load, blocking thread-safe generation, OpenAI-shaped responses and
streaming chunks (SURVEY.md §2B) — on a JAX/TPU runtime:

- load: GGUF mmap → dequant → HBM-resident params (bf16 or int8 by size);
- prefill: jit'd, prompt length padded to the nearest bucket so the set of
  compiled shapes is fixed (TTFT never pays a cold compile after warmup);
- decode: on-device scanned chunks of N tokens per host round-trip, KV cache
  and state donated so steady-state decode is allocation-free;
- sampling: llama.cpp-parity chain; defaults match llama-cpp-python 0.2.77
  (the reference relies on those defaults for top_k/min_p/repeat_penalty).
"""

from __future__ import annotations

import codecs
import collections
import dataclasses
import logging
import threading
import time
import uuid
from collections import deque
from typing import Iterator, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..gguf import GGUFFile
from ..models.cache import FEATURES, cache_of
from ..models.config import ModelConfig
from ..models.generate import (
    generate_chunk_jit,
    init_state,
    prefill_chunk_jit,
    prefill_jit,
    sample_jit,
    split_chunk_out,
)
from ..models.llama import init_cache, layer_passes
from ..models.params import load_params, synth_params
from ..sampling.sample import SamplingParams, sampling_tensors, seed_window
from ..tokenizer import apply_chat_template, detect_chat_template, tokenizer_from_gguf
from ..tokenizer.chat_template import named_template
from ..obs.memledger import register_component, tree_nbytes
from ..obs.trace import arm_phases, end_first_token, phase, rid
from ..utils.faults import FAULTS
from ..utils.health import DeadlineExceeded, Heartbeat
from .expert_counters import ExitMass, ExpertCounters
from .slices import plan_slices, slice_shapes, wide_width
from ..utils.jaxcache import setup_compile_cache
from ..utils.startup import Phase, Timeline, legacy_load_phases

logger = logging.getLogger(__name__)

DEFAULT_BUCKETS = (128, 256, 512, 1024)


# -- memory-ledger providers (obs/memledger.py): called at snapshot time
# from scrape/incident threads; plain metadata reads of live attributes,
# so they need no lock (the kv_cache_bytes precedent) -----------------------

def _ledger_weight_bytes(eng: "Engine") -> int:
    # tree_nbytes, not weight_bytes: the ledger reconciles against what
    # the devices physically hold, so tp-replicated leaves count one
    # copy per chip (weight_bytes stays the LOGICAL figure the registry's
    # budget is defined over)
    return tree_nbytes(getattr(eng, "params", None))


def _ledger_ring_bytes(eng: "Engine") -> int:
    return tree_nbytes(getattr(eng, "_cache", None))


class _TextEmitter:
    """Incremental text emission of the decode loop (:meth:`Engine._run`):
    append-only token list → (ready_text, stop_hit) increments via an
    incremental UTF-8 decoder with stop-string prefix holdback, plus the
    final flush."""

    def __init__(self, engine: "Engine", stops):
        self._eng = engine
        self._stops = stops
        self._dec = codecs.getincrementaldecoder("utf-8")(errors="replace")
        self._sent_bytes = 0
        self._held = ""        # withheld text (possible stop-string prefix)
        self._n_emitted = 0    # characters already yielded

    def process(self, gen: list, live: bool) -> tuple[str, bool]:
        """One decode of the token stream → (ready_text, stop_hit).

        On a stop hit nothing is emitted (``final`` produces the clipped
        tail).  When ``live`` is False only the stop check runs — returned
        text would be dropped by the caller, so it must not be counted as
        emitted.  The caller MUST yield a non-empty ``ready_text``."""
        eng = self._eng
        bts = eng.tokenizer.decode_bytes(gen)
        text = bts.decode("utf-8", errors="replace")
        if eng._find_stop_str(text, self._stops) != -1:
            return "", True
        if not live:
            return "", False
        self._held += self._dec.decode(bts[self._sent_bytes:])
        self._sent_bytes = len(bts)
        hold = eng._stop_prefix_holdback(self._held, self._stops)
        ready = self._held[:len(self._held) - hold]
        self._held = self._held[len(self._held) - hold:]
        self._n_emitted += len(ready)
        return ready, False

    def step(self, gen: list, done: bool, finish: str) -> tuple[str, str, bool]:
        """One emission step with the callers' shared hit convention applied:
        returns (ready_text, finish, done) — a stop hit forces
        ``("", "stop", True)``.  Extracted so the call sites (the loop tail
        and the first-token early emit in :meth:`Engine._run`) cannot
        drift."""
        ready, hit = self.process(gen, live=not done)
        if hit:
            return "", "stop", True
        return ready, finish, done

    def final(self, gen: list, finish: str) -> tuple[str, str]:
        """(text_tail, finish) once generation has ended: decode the whole
        stream, clip at a stop string, return what was never emitted."""
        text = self._eng._decode_text(gen)
        cut = self._eng._find_stop_str(text, self._stops)
        if cut != -1:
            text = text[:cut]
            finish = "stop"
        tail = text[self._n_emitted:] if len(text) > self._n_emitted else ""
        return tail, finish


class Engine:
    """Loads a GGUF model and serves chat completions on the local device(s)."""

    # -- lock discipline (machine-checked: lfkt-lint LOCK001-004, see
    # docs/RUNBOOK.md "Lock discipline annotations") ----------------------
    # _lock is the single-generator mutex: the KV ring and its prefix
    # claim may only change under it.  _id_lock is the tiny counter lock
    # shared with scheduler threads (seed sequence, last-timings swap).
    _GUARDED_BY = {
        "_cache": "_lock",
        "_prefix_ids": "_lock",
        "_paged_lease": "_lock",
        "_requests": "_id_lock",
        "last_timings": "_id_lock",
    }

    def __init__(
        self,
        model_path: str | None,
        n_ctx: int = 1024,
        weight_format: str = "auto",
        decode_chunk: int = 8,  # see utils/config.py: the chunk is also
        #                         the continuous scheduler's cadence
        #                         (larger measured -33% aggregate there)
        prefill_buckets: Sequence[int] = DEFAULT_BUCKETS,
        max_gen_tokens: int = 512,
        seed: int = 0,
        attn_impl: str = "auto",  # auto | xla | pallas (prefill flash kernel)
        kv_dtype: str | None = None,  # bf16 | int8 KV cache; None keeps the
        #                               cfg's value (docs/KV_CACHE.md)
        prefix_cache: bool = True,  # reuse the previous request's KV prefix
        prefix_min: int = 32,       # shortest common prefix worth reusing
        prefill_chunk: int = 256,   # prefill slice size: the continuous
        #                             scheduler's admission slices AND the
        #                             serial overlapped bucket slices
        prefill_overlap: int = 2,   # un-synced prefill slices in flight
        #                             (0 = monolithic bucket prefill)
        kv_paged: bool = False,     # block-paged KV pool + radix prefix
        #                             cache (parallel/kvpool.py); the dense
        #                             ring stays the default A/B control
        kv_page_tokens: int = 128,  # token slots per pool page
        kv_pool_pages: int = 0,     # pool size in pages (0 = auto)
        kv_spill_pages: int = 0,    # host-RAM spill tier capacity (0 = off)
        *,
        kv_pool=None,               # adopt a shared KVPool (multi-model
        #                             registry, docs/MULTIMODEL.md) instead
        #                             of building a private one
        kv_namespace: str | None = None,  # this engine's prefix-cache
        #                             namespace in the (shared) pool —
        #                             prefixes NEVER match across
        #                             namespaces (tenant isolation)
        _parts: tuple | None = None,  # (params, cfg, tokenizer, template_kind)
    ):
        FAULTS.fire("load")   # injection point: weight-load / re-init failure
        self.n_ctx = n_ctx
        self.decode_chunk = decode_chunk
        self.max_gen_tokens = max_gen_tokens
        #: prefill slice size shared by the serial overlapped path and the
        #: continuous scheduler's chunked admission (engine/continuous.py)
        self._prefill_chunk = max(1, int(prefill_chunk))
        self._prefill_overlap = max(0, int(prefill_overlap))
        #: optional utils.metrics.Metrics the server injects after
        #: construction (server/app.py) — engines observe prefill-slice
        #: timings into it; None (tests, benches, library use) is free
        self.metrics_sink = None
        #: progress pulse for the engine watchdog (engine/watchdog.py):
        #: one beat per device step, busy brackets around generations,
        #: an error ring for burst detection.  Engines never import the
        #: watchdog — this object is the entire interface.
        self.heartbeat = Heartbeat()
        # validated BEFORE the weight load: a typo'd LFKT_KV_DTYPE must
        # fail in milliseconds, not after a multi-GB load per crash loop
        if kv_dtype is not None and kv_dtype not in ("bf16", "int8"):
            raise ValueError(f"kv_dtype must be bf16|int8, got {kv_dtype!r}")
        self._lock = threading.Lock()
        self._expert_counters: ExpertCounters | None = None
        self._exit_mass: ExitMass | None = None
        # prompt tokens prefilled (padding included), by the width of the
        # program that took them: ``wide`` is more than the narrow width
        # (engine/slices.py); /metrics prefill_slice_tokens_total{width=}
        self.slice_tokens = {"narrow": 0, "wide": 0}
        self._base_seed = seed
        # request counter: shared by the serial path (caller thread) and the
        # continuous scheduler thread; _next_seed() is the only writer and
        # takes _id_lock so concurrent submitters never reuse a seed
        self._id_lock = threading.Lock()
        self._requests = 0
        #: per-phase wall timings of the most recent completed request
        #: (ttft_s, decode_s, completion_tokens, tokens_per_sec) — the
        #: per-phase timers SURVEY.md §5 calls for; scraped into /metrics.
        #: Written via _record_timings (atomic dict swap under _id_lock);
        #: per-request timings also ride in each response dict under
        #: "lfkt_timings" so callers never need this shared field.
        self.last_timings: dict | None = None
        setup_compile_cache()
        # lfkt.* phases emit iff /debug/profile can be armed, and keep a
        # request's rid for the device-done stamps iff the tracer is
        arm_phases()

        #: this engine's stretch of the start-up timeline (utils/startup.py):
        #: ``gguf_open`` ... ``warmup``, each stamped where the work
        #: happens; an in-memory (_parts) engine has no file phases.  The
        #: app takes it into ``/health`` ``engine.startup`` at the READY
        #: flip; ``load_phases`` is a view of it
        self.startup = tl = Timeline()
        #: whether the file's own chat template names a kind known here
        #: (else detect_chat_template fell back, and /health says so)
        self._template_named = True
        if _parts is not None:
            self.params, self.cfg, self.tokenizer, self.template_kind = _parts
            self.model_name = "in-memory"
        else:
            with tl.phase("gguf_open") as opened:
                gf = GGUFFile(model_path)
                self.model_name = gf.metadata.get("general.name", model_path)
                self.cfg = ModelConfig.from_gguf(gf, n_ctx=n_ctx)
            with tl.phase("tokenizer"):
                self.tokenizer = tokenizer_from_gguf(gf)
            if weight_format == "auto":
                # bf16 params ≈ 2 bytes/weight; small models keep exact
                # bf16.  Large models on TPU serve "q4k": Q4_K/Q6_K tensors
                # stay fused (~5 / ~7 bit/weight, ~0.55x the int8 path's
                # HBM bytes), and anything else falls back to int8 per
                # tensor.  On CPU
                # (tests) the interpret-mode kernels are slow, so big
                # models requantize to int8 instead.
                if self.cfg.n_linear_weights * 2 <= 4e9:
                    weight_format = "bf16"
                elif jax.default_backend() == "tpu":
                    weight_format = "q4k"
                else:
                    weight_format = "int8"
            fused_types = None
            fused_experts = True
            if weight_format == "q4k":
                present = {t.ggml_type for t in gf.tensors.values()}
                with tl.phase("probes", meter="cache") as probes:
                    weight_format, fused_types = self._probe_fused_format(
                        present)
                    if self.cfg.n_experts and weight_format == "q4k":
                        # the grouped expert kernels: probed only for a
                        # file that has experts (a dense pod's start pays
                        # nothing)
                        from ..ops.pallas.probe import probe_fused_experts

                        err = probe_fused_experts()
                        if err is not None:
                            fused_experts = False
                            logger.error(
                                "grouped expert kernels failed their "
                                "compile probe; experts load dequantized: "
                                "%s", err)
                    probes.attrs.update(
                        kernels=sorted(t.name for t in fused_types or ()),
                        experts=bool(self.cfg.n_experts))
            with tl.phase("params") as loading:
                sub: dict = {}
                fill: dict = {}
                self.params = load_params(gf, self.cfg, weight_format,
                                          fused_types=fused_types,
                                          phases_out=sub,
                                          fused_experts=fused_experts,
                                          fill_out=fill)
                #: per cent of the resident fused planes' bytes that are
                #: zero fill (a K filled up to the kernels' tile, rows to
                #: their N), by the loader's own sum; None where no plane
                #: is fused.  /health ``engine.weight_fill_share``
                self.weight_fill_share = round(
                    100.0 * fill["fill_bytes"] / fill["plane_bytes"], 3) \
                    if fill.get("plane_bytes") else None
                loading.children = [Phase(k, t0, t1)
                                    for k, (t0, t1) in sub.items()]
            template = gf.metadata.get("tokenizer.chat_template")
            self.template_kind = detect_chat_template(
                template, self.tokenizer)
            # detect_chat_template falls back on "mistral" in silence: a
            # file whose own template is not known says so in /health
            self._template_named = named_template(template) is not None
            logger.info(
                "loaded %s (%s, %d layers, fmt=%s) in %.1fs",
                model_path, gf.architecture, self.cfg.n_layers, weight_format,
                loading.t1 - opened.t0,
            )
        if kv_dtype is not None and kv_dtype != self.cfg.kv_dtype:
            self.cfg = dataclasses.replace(self.cfg, kv_dtype=kv_dtype)
        #: the cache kind's object (models/cache.py), resolved ONCE: every
        #: question about the kind is asked of it, none tests its name
        self.cache = cache_of(self.cfg)
        #: what the kind counts of its decode steps' and prefills' reads
        #: (host arithmetic from tracked positions; a lane engine adds at
        #: each chunk's harvest): /metrics via :meth:`cache_read_gauges`
        self.cache_counts = self.cache.new_counts()
        # layer applications the programs ran, host arithmetic beside the
        # cache kind's own (``layer_passes_total``, ``decode_lane_steps_
        # total``: the scheduler thread alone adds, /metrics reads an int)
        self.pass_counts = collections.Counter(
            lane_steps=0, decode=0, prefill=0)
        self._refuse_unsupported({
            "slice": self._prefill_chunk,
            "int8": self.cfg.kv_dtype == "int8", "paged": bool(kv_paged)})
        asked_attn = attn_impl
        attn_impl = self.cache.attn_impl(self.cfg, attn_impl)
        # the compile probes of the attention side: one phase of the
        # timeline when any of them ran
        probing = Phase("attn_probes", meter="cache", kernels=[])
        if self.cfg.kv_dtype == "int8":
            # compile-probe the KV write-quantize kernel NOW: a Mosaic
            # failure degrades writes to the identical XLA formulation
            # instead of crash-looping the pod at its first prefill
            from ..ops.pallas.kvquant import force_xla_quant
            from ..ops.pallas.probe import probe_kv_quant

            probing.attrs["kernels"].append("kv_quant")
            err = probe_kv_quant()
            if err is not None:
                force_xla_quant(True)
                logger.error("pallas kv-quantize kernel failed its compile "
                             "probe; cache writes quantize via XLA: %s", err)
        if attn_impl == "auto":
            # the flash kernel wants lane-aligned heads; anything else (tiny
            # test models, CPU runs) stays on the XLA score-matrix path
            attn_impl = (
                "pallas"
                if jax.default_backend() == "tpu" and self.cfg.head_dim % 128 == 0
                else "xla"
            )
        if attn_impl not in ("xla", "pallas"):
            raise ValueError(f"attn_impl must be auto|xla|pallas, got {attn_impl!r}")
        if attn_impl == "pallas":
            # compile-probe the flash kernel NOW (ops/pallas/probe.py): a
            # Mosaic lowering failure degrades to the XLA path with correct
            # attribution instead of crash-looping the pod at warmup.  An
            # int8 cache serves prefill through the fused-dequant variant,
            # a different Mosaic program — probe the one we'll run.
            from ..ops.pallas.probe import probe_flash_attention

            probing.attrs["kernels"].append("flash_attention")
            err = probe_flash_attention(
                quantized=self.cfg.kv_dtype == "int8")
            if err is not None:
                logger.error("pallas flash attention failed its compile "
                             "probe; serving with attn_impl=xla: %s", err)
                attn_impl = "xla"
        self.cfg, attn_impl = self.cache.probe_kernels(   # the kind's own
            self.cfg, asked_attn, attn_impl, probing.attrs["kernels"])
        if probing.attrs["kernels"]:
            probing.close()
            tl.add(probing)
        if attn_impl != self.cfg.attn_impl:
            self.cfg = dataclasses.replace(self.cfg, attn_impl=attn_impl)
        self.prefill_buckets = sorted(b for b in prefill_buckets if b <= self.cfg.n_ctx)
        if not self.prefill_buckets or self.prefill_buckets[-1] < self.cfg.n_ctx:
            self.prefill_buckets.append(self.cfg.n_ctx)
        # a prompt's slices where nobody decodes behind them (engine/
        # slices.py): the wide width, or the narrow one where the block
        # takes no wider slice
        self._wide_slice = wide_width(self._prefill_chunk,
                                      self.cache.widest_slice(self.cfg))
        # the configuration a slice's program is built for, by whether the
        # slice holds its prompt's last token (``CacheKind.slice_cfg``: a
        # kind whose upper layers write no cache stops the others early);
        # most kinds: this one configuration twice
        self._slice_cfgs = {last: self.cache.slice_cfg(self.cfg, last)
                            for last in (True, False)}
        # the serial ring here and the paged pool below; a subclass's lanes
        # are a phase of their own (``lanes_alloc``, ``scheduler_start``)
        alloc = tl.phase("cache_alloc")
        self._cache = init_cache(self.cfg)
        # -- prompt-prefix KV reuse (serial engine only) -------------------
        # The reference's engine re-evaluates the whole prompt every call;
        # llama.cpp exposes prompt caching for exactly this workload (the
        # persona + full chat history are re-sent verbatim each turn,
        # reference api.py:44-63).  Here the serial engine remembers which
        # token ids' KV entries are resident in its ring after each request
        # and, when the next prompt shares that prefix, prefills only the
        # suffix via prefill_chunk_jit — multi-turn TTFT then scales with
        # the NEW turn's length, not the whole history.  The lane engine
        # reuses per lane instead (engine/continuous.py lane claims).
        # (off for a cache that cannot be rolled back to a prefix: a
        # property of the kind, its /health says so)
        self._prefix_cache = bool(prefix_cache) and type(self) is Engine \
            and self.cache.rolls_back
        self._prefix_min = max(1, int(prefix_min))
        #: token ids whose KV occupy ring slots [0, len) — only ever read
        #: and written under self._lock (the single-generator invariant)
        self._prefix_ids: list[int] = []
        # -- block-paged KV pool + shared radix prefix index (ROADMAP item
        # 2; gated behind LFKT_KV_PAGED, dense ring is the A/B control) ----
        # One prefix-reuse implementation per mode: paging replaces the
        # serial single-claim above (and the continuous engine's lane
        # claims) with the process-wide radix index — shared system
        # prompts prefill once per process, multi-turn requests resume
        # from their last committed page.
        paged = bool(kv_paged)
        self._kv_paged = paged
        #: the in-flight request's pinned pool pages (exactly one live
        #: lease: the serial engines generate one request at a time).
        #: Lease lifecycle — acquire in _paged_reuse, store here
        #: (the handoff), release in _drop_lease on every exit incl.
        #: exceptions — is machine-checked by lfkt-lint RES001
        #: (docs/LINT.md), the PR-6 leak class made static.
        self._paged_lease = None
        #: prefix-cache namespace: every pool index operation is keyed by
        #: it, so co-resident models sharing one arena can never match
        #: each other's token prefixes (the registry passes the manifest
        #: model name; single-model engines use the default namespace)
        self._kv_ns = kv_namespace or ""
        if paged:
            self._prefix_cache = False
            if kv_pool is not None \
                    and not kv_pool.compatible(self.cfg, kv_page_tokens):
                # shared multi-model pool: N models partition one HBM page
                # budget dynamically instead of each provisioning
                # worst-case — but only an identical per-page cache
                # geometry can share the arena.  Gate off with attribution:
                # this model serves from a private pool instead of failing
                # the whole fleet.
                logger.warning(
                    "model %r (n_layers=%d, n_kv_heads=%d, head_dim=%d, "
                    "kv_dtype=%s) cannot share the KV page arena: cache "
                    "page geometry differs from the pool's — serving it "
                    "from a private pool (docs/MULTIMODEL.md)",
                    self.model_name, self.cfg.n_layers,
                    self.cfg.n_kv_heads, self.cfg.head_dim,
                    self.cfg.kv_dtype)
                kv_pool = None
            if kv_pool is not None:
                self._kvpool = kv_pool
            else:
                from ..parallel.kvpool import KVPool

                self._kvpool = KVPool(
                    self.cfg, page_tokens=kv_page_tokens,
                    n_pages=kv_pool_pages, spill_pages=kv_spill_pages,
                    sink_host=self)
        else:
            self._kvpool = None
        #: disaggregated prefill/decode (serving/disagg/): the decode
        #: replica's remote-prefill client, installed by install_disagg()
        #: when LFKT_DISAGG_ROLE is decode|both.  None (the default) is
        #: THE off state — the serving paths gate on a single attribute
        #: read, so a role=off pod pays nothing (poisoned-client pin,
        #: tests/test_disagg.py).
        self._disagg = None
        # -- lfkt-mem: report this engine's allocation surfaces into the
        # process memory ledger (obs/memledger.py).  Weakly held — a
        # discarded engine's rows vanish with it; providers read live
        # shape metadata at snapshot time, never on the decode path.
        # (The pool registers itself; subclasses add their own surfaces.)
        register_component("weights", self, _ledger_weight_bytes)
        register_component("kv_ring", self, _ledger_ring_bytes)
        alloc.close()

    @property
    def load_phases(self) -> dict:
        """The six keys ``/health`` ``engine.load_phases`` has always had
        (``tokenizer_s``, ``probes_s``, ``params_s``, ``params_prep_s``,
        ``params_stack_s``, ``warmup_s``; 0.1 s), read off the timeline:
        one source.  An in-memory engine has ``warmup_s`` alone."""
        return legacy_load_phases(self.startup)

    def _refuse_unsupported(self, asks: dict) -> None:
        """What was asked for that the cache kind cannot serve
        (``CacheKind.supports``, ``slice_rule``) is refused at start by
        name, never degraded to.  ``asks``: {feature: the value asked for,
        or False}; checked in the order of ``FEATURES``."""
        for feature, setting in FEATURES.items():
            if not asks.get(feature):
                continue
            reason = self.cache.slice_rule(self.cfg, asks[feature]) \
                if feature == "slice" else self.cache.supports[feature]
            if reason not in (None, True):
                raise ValueError(
                    f"{setting.format(asks[feature])} cannot serve "
                    f"architecture {self.cache.arch_of(self.cfg)!r}: {reason}")

    @property
    def cache_kind(self) -> dict | None:
        """The /health ``engine.cache`` block of a cache that is no ring
        (``CacheKind.health``; None for the ring, whose /health is as it was)."""
        return self.cache.health(self.cfg, self)

    @property
    def cache_engine_health(self) -> dict:
        """Further keys of /health ``engine`` that the cache kind owns
        (``CacheKind.engine_health``; none for most kinds)."""
        return self.cache.engine_health(self.cfg)

    @property
    def tokenizer_fallback(self) -> str | None:
        """The /health ``engine.tokenizer`` line of a SentencePiece
        vocabulary that tokenizer/spm.py cannot cut at spaces (None
        otherwise: /health is what it was), so that a pod whose prompts
        pay the whole-text merge loop can be told from one whose do not."""
        if getattr(self.tokenizer, "cuts_at_spaces", True):
            return None
        return ("whole-text merge loop: a vocabulary entry holds a space "
                "after another character, so no piece is cut or remembered")

    # ------------------------------------------------------------------
    @property
    def kv_cache_bytes(self) -> int:
        """Logical HBM bytes of EVERY resident KV ring this engine holds:
        the serial ring, the lane engine's batched state, and the
        continuous scheduler's persistent prefill scratch — summed from the
        live pytrees so the /health and /metrics figure matches what
        actually sits in HBM (docs/KV_CACHE.md lane-headroom math).
        ``.nbytes`` is shape metadata, safe even on donated buffers."""
        total = 0
        for cache in (getattr(self, "_cache", None),
                      getattr(self, "_scratch_cache", None),
                      (getattr(self, "_bstate", None) or {}).get("cache")):
            if cache is not None:
                total += sum(leaf.nbytes for leaf in jax.tree.leaves(cache))
        pool = getattr(self, "_kvpool", None)
        if pool is not None:
            total += pool.arena_nbytes
        return total

    @property
    def weight_bytes(self) -> int:
        """Resident HBM bytes of this model's weights (shape metadata,
        summed over the params pytree) — the multi-model registry's HBM
        weight-budget unit and the ``model_weight_bytes`` gauge."""
        params = getattr(self, "params", None)
        if params is None:
            return 0
        return sum(leaf.nbytes for leaf in jax.tree.leaves(params))

    def kv_pool_occupancy(self) -> dict | None:
        """Paged-pool occupancy + event counters — the /health ``kv_pool``
        block and the ``kv_pool_pages_{used,free}`` gauges; None when
        ``LFKT_KV_PAGED`` is off."""
        pool = getattr(self, "_kvpool", None)
        if pool is None:
            return None
        return {**pool.occupancy(), **pool.stats()}

    # ------------------------------------------------------------------
    @classmethod
    def from_parts(cls, params, cfg: ModelConfig, tokenizer,
                   template_kind: str = "llama3", **kw) -> "Engine":
        """Build from in-memory parts (tests, benches, synthetic models)."""
        eng = cls(None, n_ctx=cfg.n_ctx,
                  _parts=(params, cfg, tokenizer, template_kind), **kw)
        return eng

    @classmethod
    def synthetic(cls, cfg: ModelConfig, tokenizer, fmt: str = "bf16",
                  seed: int = 0, **kw) -> "Engine":
        return cls.from_parts(synth_params(cfg, fmt=fmt, seed=seed), cfg,
                              tokenizer, **kw)

    # ------------------------------------------------------------------
    @staticmethod
    def _probe_fused_format(present_types: set | None = None) -> tuple:
        """Compile-probe the fused Q4_K/Q5_K/Q6_K kernels — only those whose
        GGML type actually appears in ``present_types`` (the loaded file's
        tensors), so a Q4_K_M pod never pays a Q5_K probe compile.  Returns
        ("q4k", {types whose probe passed}): a Mosaic failure in ONE kernel
        degrades only that format's tensors to int8, and all failing
        degrades the whole load — instead of crash-looping the pod
        (SURVEY.md §5 "Failure detection"; the reference has no analogue
        because llama.cpp ships precompiled kernels)."""
        from ..gguf.constants import GGMLType
        from ..ops.pallas.probe import (
            probe_fused_q4k,
            probe_fused_q5k,
            probe_fused_q6k,
            probe_fused_q8,
        )

        passed = set()
        probed = set()
        for name, gtype, probe in (
                ("Q4_K", GGMLType.Q4_K, probe_fused_q4k),
                ("Q5_K", GGMLType.Q5_K, probe_fused_q5k),
                ("Q6_K", GGMLType.Q6_K, probe_fused_q6k),
                ("Q8_0", GGMLType.Q8_0, probe_fused_q8)):
            if present_types is not None and gtype not in present_types:
                continue
            probed.add(gtype)
            err = probe()
            if err is None:
                passed.add(gtype)
            else:
                logger.error("fused %s kernel failed its compile probe; "
                             "its tensors load as int8 instead: %s", name, err)
        if not probed:
            # No fused-eligible quantized tensors in the file at all — the
            # F16 (or BF16) GGUF variant of BASELINE config #3.  Decision:
            # serve int8.  8B bf16 weights are ~16 GB and cannot share
            # v5e's 16 GB HBM with the KV cache; per-channel int8 requant
            # (on device, load_params) halves bytes/token and runs the MXU
            # int8 path at ~85% of its bandwidth roofline (docs/PERF.md).
            logger.info(
                "no fused-eligible quantized tensors in the file; serving "
                "weight_format=int8 (on-device per-channel requant — the "
                "documented decision for F16/BF16 GGUFs, docs/PERF.md)")
            return "int8", None
        if not passed:
            return "int8", None
        return "q4k", frozenset(passed)

    def warmup(self):
        """Compile every shape a request can hit so no request pays a cold
        compile — the TPU analogue of the reference's eager model load —
        as the ``warmup`` phase of the start-up timeline: a child per step
        (:meth:`_warmup_steps`), and in ``attrs`` what compiled meanwhile
        by the program's own ledgers (utils/startup.py CompileMeter)."""
        with self.startup.phase("warmup", meter="programs") as ph:
            what = self._warmup_steps(ph)
        a = ph.attrs
        logger.info("warmup done in %.1fs (%s; %d programs compiled in "
                    "%.1fs: %d loaded from the executable store in %.1fs, "
                    "%d built for it in %.1fs, %d files did not load; %d of "
                    "%d compile requests under the cache's floor)",
                    ph.seconds, what, a["programs_compiled"], a["compile_s"],
                    a["programs_loaded"], a["load_s"], a["programs_built"],
                    a["build_s"], a["load_failures"],
                    a["compiled_uncached"], a["cache_requests"])

    def _warmup_steps(self, ph) -> str:  # lfkt: blocks-under[_lock] -- warmup compiles and syncs under the engine lock by design: a request must never race a half-warmed cache
        """The serial engine's warm-up, every (bucket, chunk) shape; returns
        what the log line says was warmed."""
        msgs = [{"role": "user", "content": "hi hi hi hi hi hi hi hi"}]
        # TWO full decode chunks, not one: the devtime compile pins
        # (tests/test_perf_pins.py) hold warmup to "compiles everything
        # steady-state decode runs", the second chunk's signature included
        with ph.child("request"):
            self.create_chat_completion(msgs,
                                        max_tokens=2 * self.decode_chunk + 1,
                                        temperature=0.0)
        with self._lock:   # uncontended at warmup; the ring-write invariant
            #                (writes to _cache only under _lock) stays intact
            with ph.child("buckets",
                          n_buckets=len(self.prefill_buckets) - 1) as bk:
                # compile the program(s) each bucket actually serves with:
                # the monolithic prefill of a small bucket, and ONE call
                # for every slice shape the plan can cut the sliced
                # buckets into (_slices_prefill, engine/slices.py)
                sliced = []
                for b in self.prefill_buckets[1:]:
                    if self._slices_prefill(b):
                        sliced.append(b)
                        continue
                    logits, self._cache = self._prefill_padded(
                        [0] * (b - 1), b - 1, b, self._cache)
                    jax.block_until_ready(logits)
                if sliced:
                    self._cache, bk.attrs["n_shapes"] = \
                        self._warm_slice_shapes(sliced, self._cache)
                    jax.block_until_ready(self._cache)
            if self._prefix_cache or self._kv_paged:
                # compile the suffix pass for every bucket a reuse suffix can
                # land in (all but the largest — _prefix_reuse_len only grants
                # reuse when the suffix bucket is strictly smaller than the
                # prompt's; the paged radix path shares the same suffix-bucket
                # contract), preserving the no-cold-compile-after-warmup
                # invariant on the reuse path too.  Also drops the claim over
                # the garbage the raw bucket loop above wrote into the ring.
                # (Pool page-copy programs are NOT part of this warmed set:
                # they compile on first use — parallel/kvpool.py.)
                with ph.child("reuse_buckets",
                              n_buckets=len(self.prefill_buckets) - 1):
                    for b in self.prefill_buckets[:-1]:
                        logits, self._cache = prefill_chunk_jit(
                            self.params, self.cfg, jnp.zeros((b,), jnp.int32),
                            jnp.int32(0), jnp.int32(b - 1), self._cache)
                        jax.block_until_ready(logits)
                self._prefix_ids = []
        return f"{len(self.prefill_buckets)} prefill buckets"

    def _warm_slice_shapes(self, buckets, cache):
        """One ``prefill_chunk`` call for every slice shape the plan can
        emit for ``buckets`` (engine/slices.py ``slice_shapes``): what both
        engines' warm-ups compile, once a shape.  Returns (the cache the
        calls were queued on, how many shapes)."""
        shapes = slice_shapes(buckets, self._prefill_chunk, self._wide_slice)
        # (a kind that stops some slices early: a second program a shape)
        cfgs = list(dict.fromkeys(self._slice_cfgs.values()))
        for C in shapes:
            for scfg in cfgs:
                _, cache = prefill_chunk_jit(
                    self.params, scfg, jnp.zeros((C,), jnp.int32),
                    jnp.int32(0), jnp.int32(C - 1), cache)
        return cache, len(shapes) * len(cfgs)

    def _slices_prefill(self, bucket: int) -> bool:
        """Whether a ``bucket``-sized prompt prefills as overlapped slices
        (vs one monolithic program).  Buckets at or under the slice size
        gain nothing from slicing and keep the single-program path."""
        return bucket > self._prefill_chunk and (
            self.cache.always_slices
            or self._prefill_overlap > 0)

    def _observe_slice(self, dt: float) -> None:
        """Feed one prefill-slice host wall time into the server's metrics
        (``prefill_slice_seconds``); free when no sink is installed."""
        m = self.metrics_sink
        if m is not None:
            try:
                m.observe("prefill_slice_seconds", dt)
            except Exception:  # noqa: BLE001 — telemetry must never fail serving
                pass

    def _count_slice(self, tokens: int, scfg=None) -> None:
        """One prefill program's tokens into :attr:`slice_tokens`, and
        into the cache kind's own counters.  ``scfg``: the configuration
        the program was built for (:attr:`_slice_cfgs`; default the
        engine's)."""
        self.slice_tokens[
            "wide" if tokens > self._prefill_chunk else "narrow"] += tokens
        run = self.cache.note_slice(self.cache_counts, scfg or self.cfg,
                                    tokens)
        # (a kind that runs some slices on part of the stack says how much)
        self.pass_counts["prefill"] += tokens * layer_passes(self.cfg) \
            if run is None else run

    def _count_lane_steps(self, lane_steps: int) -> None:
        """Decode steps of the lanes whose rows were wanted, and the layer
        applications they took, into :attr:`pass_counts`."""
        self.pass_counts["lane_steps"] += lane_steps
        self.pass_counts["decode"] += lane_steps * layer_passes(self.cfg)

    def _slice_attrs(self, scfg) -> dict:
        """What a traced ``prefill_slice`` span says of a program that is
        not the whole stack's (``CacheKind.slice_cfg``); nothing of one
        that is."""
        return {"lower_only": True} if scfg.lower_only else {}

    @staticmethod
    def _slice_span(pspan, t_s: float, t_e: float, offset: int, tokens: int,
                    **attrs) -> None:
        """One prefill program's host dispatch (start -> return of the jit
        call) as a ``prefill_slice`` child of the traced ``prefill`` span;
        nothing for a request that is sampled out."""
        if pspan is not None:
            pspan.child("prefill_slice", t0=t_s).set(
                offset=offset, tokens=tokens, **attrs).end(t_e)

    def _prefill_padded(self, ids: list, n_prompt: int, bucket: int,
                        cache, pspan=None):  # lfkt: holds[_lock]
        """Bucket prefill, monolithic or sliced: returns (logits, cache).

        The sliced path is the round-6 double-buffered pipeline: the padded
        prompt is prepared ONCE as a host int32 array, then each slice is a
        zero-copy view dispatched through ``prefill_chunk_jit`` — slice
        ``i+1``'s host prep (view + device enqueue) overlaps slice ``i``'s
        device compute because dispatch is async.  ``prefill_overlap``
        bounds the un-synced slices in flight (the oldest slice's logits
        are blocked on past the bound) so a 32k prompt cannot queue
        hundreds of slices on the device.  Slicing stops at the
        slice containing the last real token, exactly like the continuous
        scheduler's admission machine: pure-padding slices would only
        write cache garbage that is never attended.

        Greedy-bit-identity with the monolithic program is pinned by
        tests/test_prefill_pipeline.py on both engines.
        """
        if not self._slices_prefill(bucket):
            t_s = time.time()
            padded = ids + [0] * (bucket - n_prompt)
            with phase("prefill_slice", rid=rid(pspan), offset=0,
                       tokens=bucket):
                out = prefill_jit(
                    self.params, self.cfg, jnp.asarray(padded, jnp.int32),
                    jnp.int32(n_prompt), cache)
            # the one-program prompt: one slice
            self._slice_span(pspan, t_s, time.time(), 0, bucket)
            self._count_slice(bucket)
            return out
        padded_np = np.zeros((bucket,), np.int32)
        padded_np[:n_prompt] = ids
        logits = None
        inflight: deque = deque()
        last = n_prompt - 1
        # nobody decodes behind a slice of this engine: wide first, narrow
        # for the tail (engine/slices.py)
        for off, n in plan_slices(0, n_prompt, bucket, self._prefill_chunk,
                                  self._wide_slice):
            t_s = time.time()
            sl = jnp.asarray(padded_np[off:off + n])
            li = min(max(last - off, 0), n - 1)
            scfg = self._slice_cfgs[off <= last < off + n]
            with phase("prefill_slice", rid=rid(pspan), offset=off, tokens=n):
                lg, cache = prefill_chunk_jit(
                    self.params, scfg, sl, jnp.int32(off), jnp.int32(li),
                    cache)
                if off <= last < off + n:
                    logits = lg
                inflight.append(lg)
                if len(inflight) > self._prefill_overlap:
                    # double-buffer bound: wait for the OLDEST slice so at
                    # most `overlap` slices are queued un-synced on the device
                    jax.block_until_ready(inflight.popleft())
            t_e = time.time()
            self._observe_slice(t_e - t_s)
            self._slice_span(pspan, t_s, t_e, off, n,
                             **self._slice_attrs(scfg))
            self._count_slice(n, scfg)
        return logits, cache

    def _decode_chunk_call(self, state, st, n_steps: int, top_k: int,
                           pos: int):
        """Dispatch one decode chunk; ``pos``: the host-tracked position of
        its first step (what :attr:`cache_counts` are computed from:
        nothing is fetched)."""
        state, out = generate_chunk_jit(self.params, self.cfg, state, st,
                                        n_steps=n_steps, top_k=top_k)
        self.cache.note_decode(self.cache_counts, self.cfg, [pos], n_steps)
        self.cache.note_lanes(self.cache_counts, self.cfg, 1, n_steps)
        self._count_lane_steps(n_steps)
        return state, self._take_expert_stats(out)

    def cache_read_gauges(self) -> dict:
        """:attr:`cache_counts` under their /metrics names (``ring_slots_*``
        for every engine, 0 on a cache that is no ring, and the kind's own);
        beside them the prompt tokens prefilled by slice width
        (:attr:`slice_tokens`) and a SentencePiece tokenizer's pieces."""
        out = self.cache.gauges(self.cache_counts)
        out.update({f'prefill_slice_tokens_total{{width="{w}"}}': n
                    for w, n in self.slice_tokens.items()})
        counts = getattr(self.tokenizer, "piece_counts", None)
        if counts is not None:
            (out["tokenizer_pieces_total"],
             out["tokenizer_memo_hits_total"]) = counts()
        passes = getattr(self, "pass_counts", None)
        if passes is not None:
            out.update({
                'layer_passes_total{phase="decode"}': passes["decode"],
                'layer_passes_total{phase="prefill"}': passes["prefill"],
                "decode_lane_steps_total": passes["lane_steps"]})
        mass = getattr(self, "exit_mass", None)
        if mass is not None:
            out.update({f'ut_exit_mass_total{{pass="{t}"}}': p
                        for t, p in enumerate(mass.snapshot())})
        return out

    def _take_expert_stats(self, chunk_out):
        """A decode chunk's tokens; a routed block's counters go to
        :attr:`expert_counters` on the way (still on the device), a looped
        stack's exit masses to :attr:`exit_mass`."""
        tokens, stats = split_chunk_out(chunk_out)
        if stats is not None:
            (self.expert_counters or self.exit_mass).push(stats)
        return tokens

    @property
    def exit_mass(self):
        """The exit gate's cumulative mass a pass (engine/expert_counters.py
        ``ExitMass``), None where layers run once."""
        if self.cfg.ut_steps == 1:
            return None
        if self._exit_mass is None:
            self._exit_mass = ExitMass(self.cfg.ut_steps)
        return self._exit_mass

    @property
    def expert_counters(self):
        """The routed layers' cumulative counters
        (engine/expert_counters.py), None for a dense block.  Made on
        first use: the configuration is final only after ``__init__``."""
        if not self.cfg.n_experts:
            return None
        if self._expert_counters is None:
            self._expert_counters = ExpertCounters(
                self.cfg.n_held, self.expert_slots,
                zero=bool(self.cfg.n_zero_experts), n_rows=self.expert_rows)
        return self._expert_counters

    @property
    def _expert_families(self) -> tuple:
        """The grouped-call families of the file's expert tensors, in the
        layers' order; () where any serves dequantized and for a dense
        block."""
        from ..models.params import flat_layers
        from ..ops.pallas.experts import family_of

        fams = tuple(family_of(leaf) for name, leaf
                     in flat_layers(self.params["layers"])
                     if name.endswith("_exps"))
        return fams if all(fams) else ()

    @property
    def _grouped_step(self) -> tuple | None:
        """(tokens, picks a token) of a decode step's grouped expert call;
        None where the experts serve dequantized and for a dense block."""
        if not self._expert_families:
            return None
        return getattr(self, "batch_size", 1), self.cfg.n_experts_used

    @property
    def expert_kernel(self) -> str | None:
        """The bodies the grouped expert calls run, by family (/health
        ``engine.expert_kernel``: ``q4k-int+q6k-int`` on a Q4_K_M file;
        named where they are chosen, ops/pallas/experts.py ``FAMILIES``);
        None without such a call."""
        from ..ops.pallas.experts import FAMILIES

        return "+".join(FAMILIES[f].body for f in
                        sorted(set(self._expert_families))) or None

    @property
    def expert_slots(self) -> int:
        """The slots of a decode step's grouped expert call (``T``: /health
        ``engine.expert_slots``), which ends its grid at those in use; 0
        without such a call."""
        from ..ops.pallas.experts import decode_slots

        step = self._grouped_step
        return decode_slots(self.cfg.n_held, *step) if step else 0

    @property
    def expert_rows(self) -> int:
        """The (token, pick) rows of a decode step's routed layer where it
        is compacted to the rows that reach a held expert (/health
        ``engine.expert_rows``); 0 where the layer is built as it always
        was (64 rows or fewer) and without a grouped call."""
        from ..ops.pallas.experts import compacted_rows

        step = self._grouped_step
        return compacted_rows(*step) if step else 0

    def _next_seed(self) -> int:
        with self._id_lock:
            s = self._base_seed + self._requests
            self._requests += 1
            return s

    def _record_timings(self, timings: dict) -> None:
        with self._id_lock:
            self.last_timings = timings

    def _note_error(self, exc: BaseException) -> None:
        """Record an engine-side failure on the heartbeat for the watchdog's
        burst detector.  ValueError is a *client* input error (oversized
        prompt, bad params) — a burst of bad requests must never count as
        engine failure, or abusive traffic could trip the watchdog."""
        if isinstance(exc, ValueError):
            return
        self.heartbeat.record_error(exc)

    # -- watchdog recovery ---------------------------------------------
    def recover(self) -> bool:
        """Re-initialize serving state after a watchdog trip (bounded
        recovery, engine/watchdog.py).  The serial engine's mutable state
        is the KV ring and its prefix claim; params are immutable so a
        fresh ring is a full re-init.  Refuses (returns False) while a
        generation holds the lock — the cache cannot be swapped under a
        live decode, and a permanently held lock means a wedged device
        call, which only a pod restart (DEAD) clears."""
        FAULTS.fire("recover")   # injection point: recovery that fails
        if not self._lock.acquire(blocking=False):
            return False
        try:
            self._recover_locked()
            self.heartbeat.reset()
            return True
        finally:
            self._lock.release()

    def _recover_locked(self) -> None:  # lfkt: holds[_lock]
        """Engine-specific state re-init, called with the lock held."""
        self._cache = init_cache(self.cfg)
        self._prefix_ids = []
        if self._kvpool is not None:
            # lane/ring contents are of unknown validity after a trip —
            # nothing resident (or pinned) is trustworthy
            self._drop_lease()
            self._kvpool.reset()

    @staticmethod
    def _deadline_hit(ctx) -> bool:
        """Per-request deadline/abort propagation: True when the caller's
        deadline passed or its abort callback fired — the decode loops
        check this once per chunk so a timed-out or disconnected request
        abandons the device within one decode step instead of generating
        to budget (the reference's engine always ran to completion,
        api.py:97-100, which only its strictly serial engine could
        afford)."""
        abort = ctx.get("abort")
        if abort is not None and abort():
            return True
        deadline = ctx.get("deadline")
        return deadline is not None and time.time() > deadline

    def _bucket_for(self, n: int) -> int:
        for b in self.prefill_buckets:
            if n <= b:
                return b
        return self.cfg.n_ctx

    def tokenize_messages(self, messages: Sequence[dict]) -> list[int]:
        return apply_chat_template(self.tokenizer, messages, kind=self.template_kind)

    def _tokenize_counted(self, messages) -> tuple[list[int], dict]:
        """:meth:`tokenize_messages`, and what the tokenizer's memo of
        pieces did for this call as the ``tokenize`` span's attributes
        (tokenizer/spm.py; none for a tokenizer that keeps no memo)."""
        counts = getattr(self.tokenizer, "piece_counts", None)
        if counts is None:
            return self.tokenize_messages(messages), {}
        p0, h0 = counts(thread_only=True)
        ids = self.tokenize_messages(messages)
        p1, h1 = counts(thread_only=True)
        return ids, {"pieces": p1 - p0, "memo_hits": h1 - h0}

    # ------------------------------------------------------------------
    def create_chat_completion(
        self,
        messages: Sequence[dict],
        stream: bool = False,
        temperature: float = 0.2,
        top_p: float = 0.95,
        top_k: int = 40,
        min_p: float = 0.05,
        frequency_penalty: float = 0.0,
        presence_penalty: float = 0.0,
        repeat_penalty: float = 1.1,
        max_tokens: int | None = None,
        stop: Sequence[str] | str | None = None,
        seed: int | None = None,
        deadline: float | None = None,
        abort=None,
        trace=None,
    ):
        """OpenAI-chat-shaped completion (dict), or an iterator of chunks when
        ``stream=True`` (reference call site: api.py:55-63; chunk schema per
        SURVEY.md §2B "Streaming").  Safe to call from a worker thread.

        ``deadline`` (absolute ``time.time()`` seconds) and ``abort`` (a
        callable returning True when the caller gave up) propagate the
        server's admission timeout/disconnect into the decode loop: the
        generation stops within one decode chunk of either firing, with
        ``finish_reason="deadline"``.  ``trace`` (an obs.trace.Trace, or
        None when the request is sampled out) receives the engine's span
        tree — prefill and per-decode-chunk timings; every producer site
        guards on None so an untraced request allocates nothing."""
        if stop is None:
            stop = []
        elif isinstance(stop, str):
            stop = [stop]
        sp = SamplingParams(
            temperature=temperature, top_p=top_p, top_k=top_k, min_p=min_p,
            frequency_penalty=frequency_penalty, presence_penalty=presence_penalty,
            repeat_penalty=repeat_penalty,
        )
        if stream:
            return self._generate_stream(messages, sp, max_tokens, stop, seed,
                                         deadline=deadline, abort=abort,
                                         trace=trace)
        return self._generate(messages, sp, max_tokens, stop, seed,
                              deadline=deadline, abort=abort, trace=trace)

    def _trace_attrs(self) -> dict:
        """Engine-identity attributes stamped on a traced request's
        ``engine`` span."""
        return {"engine": type(self).__name__, "model": self.model_name}

    # ------------------------------------------------------------------
    def _start(self, messages, sp: SamplingParams, seed,
               espan=None, pre_ids=None):  # lfkt: holds[_lock]
        """Shared prefill + first-token path. Returns a mutable gen context.
        ``espan`` (the traced request's ``engine`` span, or None) grows a
        ``prefill`` child covering tokenize → first sampled token.
        ``pre_ids`` is the prompt already tokenized by the pre-lock
        disagg hop (_remote_prefill) so the request never pays the chat
        template + tokenizer twice."""
        t0 = time.time()
        self.heartbeat.beat()
        FAULTS.fire("prefill")
        if pre_ids is not None:
            ids, t_tok = pre_ids, None
        else:
            with phase("tokenize", rid=rid(espan)):
                ids, memo = self._tokenize_counted(messages)
            t_tok = time.time()
        n_prompt = len(ids)
        if n_prompt >= self.cfg.n_ctx:
            raise ValueError(
                f"Requested tokens ({n_prompt}) exceed context window of {self.cfg.n_ctx}"
            )
        bucket = self._bucket_for(n_prompt)
        st = sampling_tensors(sp)

        explicit_seed = seed is not None
        if seed is None:
            seed = self._next_seed()
        else:
            self._next_seed()  # keep the auto-seed sequence advancing

        # an explicit seed is a reproducibility request: the reuse pass
        # scores bf16-rounded cached KV where full prefill scores fresh
        # f32 K/V, so a near-tied logit can flip — same-seed calls must
        # instead be bit-identical, so they always take the full prefill
        reuse = 0 if explicit_seed else \
            self._prefix_reuse_len(ids, n_prompt, bucket)
        pspan = None
        if espan is not None:
            pspan = espan.child("prefill", t0=t0)
            if t_tok is not None:
                pspan.child("tokenize", t0=t0).set(
                    n_prompt=n_prompt, **memo).end(t_tok)
        if self._kv_paged and not explicit_seed:
            # paged mode: the shared radix index replaces the single-claim
            # reuse above (restores matched pages into the ring and pins
            # them for this request — parallel/kvpool.py)
            reuse = self._paged_reuse(ids, n_prompt, bucket, pspan)
        if pspan is not None:
            pspan.set(n_prompt=n_prompt, bucket=bucket, reused=reuse)
        self._note_prefill(n_prompt, pspan, reuse)
        # claim nothing while this request is in flight: an exception past
        # this point must not leave a stale prefix claim over a cache whose
        # contents are indeterminate
        self._prefix_ids = []
        if reuse:
            suffix = ids[reuse:]
            s = len(suffix)
            sbucket = self._bucket_for(s)
            t_s = time.time()
            with phase("prefill_slice", rid=rid(pspan), offset=reuse,
                       tokens=sbucket):
                logits, cache = prefill_chunk_jit(
                    self.params, self.cfg,
                    jnp.asarray(suffix + [0] * (sbucket - s), jnp.int32),
                    jnp.int32(reuse), jnp.int32(s - 1), self._cache)
            # the suffix pass after a prefix reuse
            self._slice_span(pspan, t_s, time.time(), reuse, sbucket)
            self._count_slice(sbucket)
        else:
            logits, cache = self._prefill_padded(
                ids, n_prompt, bucket, self._cache, pspan=pspan)
        # last slice dispatched -> first token on the host (the lane
        # engine may defer this fetch behind decode waves; here never)
        fspan = pspan.child("first_token").set(deferred=False, waves=0) \
            if pspan is not None else None
        window, wpos = seed_window(ids)
        key = jax.random.PRNGKey(seed)
        with phase("first_sample", rid=rid(pspan)):
            token, window, wpos, key = sample_jit(
                logits, window, wpos, key, st, self.cfg, top_k=sp.top_k)
        state = {
            "cache": cache,
            "pos": jnp.int32(n_prompt),
            "token": token,
            "window": window,
            "wpos": wpos,
            "key": key,
        }
        first = int(token)  # device sync: first token is now materialized
        ttft_s = time.time() - t0
        if pspan is not None:
            # the fetch above was the wait: the sample's program is done
            end_first_token(fspan, pspan, token)
            pspan.set(ttft_s=round(ttft_s, 6))
            pspan.end()
        return {
            "state": state, "st": st, "sp": sp, "n_prompt": n_prompt,
            "ids": [], "prompt_ids": ids, "first": first, "t0": t0,
            "reused": reuse, "ttft_s": ttft_s, "span": espan,
            "bucket": bucket,
        }

    def _note_prefill(self, n_prompt: int, pspan=None, reused: int = 0,
                      alone: bool = True) -> None:
        """What a prompt's prefill does to the cache, by the kind: counted,
        and on the traced ``prefill`` span.  ``reused``: the prefix no slice
        computes; ``alone``: whether nobody decodes behind the slices."""
        slices = plan_slices(
            reused, n_prompt, self.cfg.n_ctx, self._prefill_chunk,
            self._wide_slice, alone) \
            if pspan is not None or self.cache.counts_prefill else None
        attrs = self.cache.note_prefill(self.cache_counts, self.cfg,
                                        n_prompt, slices)
        if pspan is not None:
            pspan.set(**attrs, **self.cache.span_attrs(self.cfg))

    def _prefix_reuse_len(self, ids: list, n_prompt: int, bucket: int) -> int:
        """Longest usable common prefix of ``ids`` vs the KV resident in the
        ring, or 0 when reuse is off / too short / wouldn't shrink the
        prefill bucket.  Always leaves ≥1 token to prefill (the suffix pass
        must emit the last prompt token's logits)."""
        if not self._prefix_cache:
            return 0
        prev = self._prefix_ids
        lim = min(len(prev), n_prompt - 1)
        i = 0
        while i < lim and prev[i] == ids[i]:
            i += 1
        if i < self._prefix_min:
            return 0
        # The padded suffix slice [reuse, reuse + sbucket) must stay inside
        # the KV ring: dynamic_update_slice CLAMPS an out-of-range write
        # start, which would silently overwrite valid prefix slots with KV
        # whose RoPE positions disagree (code-review r4 finding).  Near the
        # context limit the reuse is therefore shortened to n_ctx - sbucket
        # (re-prefilling a little more) rather than dropped.  Smallest
        # bucket first: it admits the longest reuse.
        for b in self.prefill_buckets:
            if b >= bucket:
                break  # suffix pads into the same program: no cycles saved
            r = min(i, self.cfg.n_ctx - b)
            if r >= self._prefix_min and n_prompt - r <= b:
                return r
        return 0

    def _drop_lease(self) -> None:  # lfkt: holds[_lock]
        """Unpin the current request's pool pages (idempotent)."""
        if self._paged_lease is not None:
            self._kvpool.release(self._paged_lease)
            self._paged_lease = None

    def _paged_reuse(self, ids: list, n_prompt: int, bucket: int,
                     pspan=None) -> int:  # lfkt: holds[_lock]
        """Radix-tree prefix reuse (LFKT_KV_PAGED): the longest cached
        whole-page prefix that fits the suffix-bucket contract (exactly
        :meth:`_prefix_reuse_len`'s constraints, page-aligned), restored
        contiguously into the ring and pinned for the request's lifetime.
        Returns the reused token count (0 = full prefill)."""
        self._drop_lease()   # a prior request's exception may have leaked
        pool = self._kvpool
        T = pool.page_tokens
        i = min(pool.match_len(ids, namespace=self._kv_ns), n_prompt - 1)
        r_fit = 0
        # same clamp as _prefix_reuse_len: the padded suffix slice
        # [reuse, reuse + sbucket) must stay inside the ring, and the
        # suffix must land in a strictly smaller bucket — plus page
        # alignment, since pages are the restore grain
        for b in self.prefill_buckets:
            if b >= bucket:
                break
            r = (min(i, self.cfg.n_ctx - b) // T) * T
            if r >= max(self._prefix_min, T) and n_prompt - r <= b:
                r_fit = r
                break
        if r_fit == 0:
            pool.note_miss()
            return 0
        lease = pool.acquire(ids, r_fit, span=pspan, namespace=self._kv_ns)
        if lease is None:    # raced an eviction / spill-restore failed
            return 0
        self._paged_lease = lease
        # the ring is donated into the copy: drop our ref across the call
        # so a mid-copy failure cannot leave a dead donated buffer as
        # self._cache (the next request would trip over it) — rebuild
        # cold instead, exactly like _reinit, and propagate
        cache, self._cache = self._cache, None
        try:
            self._cache = pool.restore(lease, cache, span=pspan)
        except Exception:
            self._drop_lease()
            self._cache = init_cache(self.cfg)
            raise
        if pspan is not None:
            pspan.set(reused_pages=len(lease.page_ids), matched_tokens=i)
        return lease.tokens

    # -- disaggregated prefill/decode (serving/disagg/) -----------------
    def install_disagg(self, client) -> None:
        """Arm remote prefill (LFKT_DISAGG_ROLE=decode|both): admitted
        prompts hop to the prefill tier, whose pages import into the
        local pool's radix, so :meth:`_paged_reuse` (and the continuous
        scheduler's admission reuse) restores them like any local
        commit.  Requires the paged pool — pages ARE the wire format."""
        if self._kvpool is None:
            raise ValueError(
                "install_disagg requires LFKT_KV_PAGED=1: the disagg "
                "wire ships KV pool pages (docs/RUNBOOK.md 'Operating a "
                "split prefill/decode fleet')")
        self._disagg = client

    def _remote_prefill(self, messages, deadline, trace) -> list | None:
        """One bounded remote-prefill hop for the serial path, BEFORE the
        generation lock (in `both` mode the loopback page service takes
        that lock to prefill — holding it here would deadlock).  Never
        raises: tokenize errors re-raise properly inside _start, and the
        client degrades every wire failure to local prefill itself.
        Returns the tokenized prompt so _start never re-tokenizes (None
        when tokenization failed — _start then raises the real error)."""
        try:
            ids = self.tokenize_messages(messages)
        except Exception:  # noqa: BLE001 — _start re-raises the real error
            return None
        try:
            if len(ids) >= self.cfg.n_ctx:
                return ids              # _start's oversized-prompt 400
            span = trace.span("disagg") if trace is not None else None
            try:
                self._disagg.prefetch(ids, namespace=self._kv_ns,
                                      deadline=deadline, span=span)
            finally:
                if span is not None:
                    span.end()
        except Exception:  # noqa: BLE001 — remote prefill is an
            # optimization: any failure here must degrade to the local
            # prefill _start runs anyway, never fail the request
            logger.exception("disagg prefetch failed; serving local "
                             "prefill")
        return ids

    def _remote_prefill_ids(self, ids, deadline, span=None) -> None:
        """Tokenized variant (the continuous scheduler's admission path,
        engine/continuous.py _begin_admission).  Same never-raise
        contract as :meth:`_remote_prefill`."""
        try:
            self._disagg.prefetch(ids, namespace=self._kv_ns,
                                  deadline=deadline, span=span)
        except Exception:  # noqa: BLE001 — degrade to local prefill
            logger.exception("disagg prefetch failed; serving local "
                             "prefill")

    def prefill_to_pages(self, ids, *, namespace: str = "",  # lfkt: blocks-under[_lock] -- the serial engine's lock IS the request serialization: prefill syncs and pool spills run under it by design
                         deadline=None):
        """The prefill TIER's page service (serving/disagg/prefiller.py):
        ensure the whole-page prefix of ``ids`` is committed in the
        local pool — consulting the tier's own radix first, so a system
        prompt hot across many decode replicas prefills once per tier,
        then prefilling into the serial ring (which serves nothing else
        on a prefill-role pod) — pin it, export host page stacks,
        release.  Returns ``(leaves, tokens, first_token)`` or None when
        no whole page is exportable; ``first_token`` is the prompt's
        greedy continuation when this call ran the prefill (advisory —
        the decode side samples its own first token from the restored
        prefix, bit-identical by the suffix-prefill contract), else
        None."""
        pool = self._kvpool
        if pool is None:
            raise ValueError(
                "prefill_to_pages requires LFKT_KV_PAGED=1 (pages are "
                "the disagg wire format)")
        T = pool.page_tokens
        ids = list(ids)
        n_prompt = len(ids)
        if n_prompt >= self.cfg.n_ctx:
            raise ValueError(
                f"Requested tokens ({n_prompt}) exceed context window "
                f"of {self.cfg.n_ctx}")
        keep = (n_prompt // T) * T
        if keep < T:
            return None                  # prompt shorter than one page
        first_token = None
        with self._lock:
            self.heartbeat.enter()
            try:
                have = pool.match_len(ids[:keep], namespace=namespace)
                if have < keep:
                    if deadline is not None and time.time() > deadline:
                        # PR-2 deadline propagation spans the hop: the
                        # decode side already abandoned this request
                        raise DeadlineExceeded(
                            "deadline expired before remote prefill")
                    self.heartbeat.beat()
                    FAULTS.fire("prefill")
                    bucket = self._bucket_for(n_prompt)
                    logits, cache = self._prefill_padded(
                        ids, n_prompt, bucket, self._cache)
                    self._cache = cache
                    self._prefix_ids = []
                    first_token = int(jnp.argmax(logits))
                    pool.commit(ids[:keep], self._cache,
                                namespace=namespace)
                # commit may have degraded to the leading portion that
                # fit (squeezed pool): export what the index truly holds
                have = min(pool.match_len(ids[:keep], namespace=namespace),
                           keep)
                if have < T:
                    return None
                lease = pool.acquire(ids[:keep], have, namespace=namespace)
                if lease is None:        # raced an eviction: a miss, not
                    return None          # an error — the peer falls back
                try:
                    leaves = pool.export_pages(lease)
                    tokens = lease.tokens
                finally:
                    pool.release(lease)
                return leaves, tokens, first_token
            finally:
                self.heartbeat.leave()

    def _finish(self, ctx) -> dict:  # lfkt: holds[_lock]
        """Return the cache buffer for reuse; finalize per-phase timings.
        Returns the timings dict (also published to :attr:`last_timings`)."""
        self._cache = ctx["state"]["cache"]
        decode_s = time.time() - ctx["t0"] - ctx["ttft_s"]
        n = len(ctx["ids"])
        if self._kv_paged:
            # commit the conversation's whole-page prefix to the shared
            # pool (pages already cached are deduplicated, so a multi-turn
            # follow-up stores only its delta) and unpin this request's
            # lease.  Ring residency is the same claim as below: slots
            # [0, n_prompt + n - 1) hold prompt + generated tokens except
            # the last sampled one.
            keep = ctx["n_prompt"] + max(n - 1, 0)
            self._kvpool.commit((ctx["prompt_ids"] + ctx["ids"])[:keep],
                                self._cache, span=ctx.get("span"),
                                namespace=self._kv_ns)
            self._drop_lease()
        elif self._prefix_cache:
            # ring slots [0, n_prompt + n - 1) now hold prompt + all
            # generated tokens except the last sampled one (its KV write
            # happens only when it is fed — which a finished request never
            # does); pipelined overshoot writes land past this.
            keep = ctx["n_prompt"] + max(n - 1, 0)
            self._prefix_ids = (ctx["prompt_ids"] + ctx["ids"])[:keep]
        timings = {
            "ttft_s": ctx["ttft_s"],
            "decode_s": decode_s,
            "prompt_tokens": ctx["n_prompt"],
            "completion_tokens": n,
            "prefix_reused_tokens": ctx.get("reused", 0),
            # prompt bucket for the per-bucket TTFT series (obs/slo.py)
            "bucket": ctx.get("bucket", 0),
            # model label for the per-model metric series (multi-model
            # serving, docs/MULTIMODEL.md)
            "model": self.model_name,
            # first token came out of prefill; the decode phase produced n-1
            "tokens_per_sec": (n - 1) / decode_s if n > 1 and decode_s > 0 else 0.0,
        }
        self._record_timings(timings)
        espan = ctx.get("span")
        if espan is not None:
            espan.set(**{k: round(v, 6) if isinstance(v, float) else v
                         for k, v in timings.items() if not isinstance(v, dict)})
            espan.end()
        return timings

    def _token_budget(self, max_tokens, n_prompt):
        budget = self.max_gen_tokens if max_tokens is None else max_tokens
        return max(0, min(budget, self.cfg.n_ctx - n_prompt - 1))

    def _decode_text(self, all_ids):
        return self.tokenizer.decode(all_ids, skip_special=True)

    @staticmethod
    def _find_stop_str(text: str, stops) -> int:
        cut = -1
        for s in stops:
            i = text.find(s)
            if i != -1 and (cut == -1 or i < cut):
                cut = i
        return cut

    @staticmethod
    def _stop_prefix_holdback(text: str, stops) -> int:
        """Length of the longest suffix of ``text`` that is a proper prefix
        of a stop string.  Stream emission withholds it until the next chunk
        resolves whether the stop completes — otherwise a stop spanning a
        chunk boundary would leak its first characters to the client, making
        streamed text diverge from the batch decode."""
        best = 0
        for s in stops:
            for k in range(min(len(s) - 1, len(text)), best, -1):
                if text.endswith(s[:k]):
                    best = k
                    break
        return best

    def _next_steps(self, produced: int, pos: int, budget: int) -> int:
        """Size of the next decode chunk given host-tracked progress (no
        device sync: ``pos`` is n_prompt + decoded count, tracked on host).

        While the budget has tokens left the chunk is a FULL
        ``decode_chunk``, and the caller drops the surplus of the last one:
        ``n_steps`` is a static argument, so a budget tail of 1..chunk-1
        steps was a program warm-up never compiled — on the chip the first
        512-token request met a 7-step tail, compiled for ~19 s mid-request
        and ran into the 25 s timeout.  The surplus steps' cache writes are
        as harmless as those behind a stop token (see :meth:`_run`).  Only
        the ring's own last slots still shorten a chunk."""
        if budget - produced <= 0:
            return 0
        return max(0, min(self.decode_chunk, self.cfg.n_ctx - pos - 1))

    def _run(self, ctx, max_tokens, stops):
        """Generate tokens; yields (new_text, done, finish_reason) increments.

        Decode is **pipelined**: chunk k+1 is dispatched to the device before
        chunk k's tokens are fetched to the host, so the host↔device
        round-trip overlaps with compute.
        If a stop lands mid-chunk, the cache writes of the chunk dispatched
        ahead are harmless — attention masks by position and every request
        re-prefills and reseeds the sampler window, so stale slots are never
        read.

        Text increments are produced by an incremental UTF-8 decoder over
        the (append-only) token byte stream, so the streamed concatenation
        is byte-identical to the one-shot decode even when a multi-byte
        character spans a chunk boundary.
        """
        stop_ids = self.tokenizer.stop_ids
        budget = self._token_budget(max_tokens, ctx["n_prompt"])
        gen: list[int] = []
        em = _TextEmitter(self, stops)
        finish = "length"
        first = ctx["first"]
        if budget <= 0:
            yield "", True, "length"
            return
        if first in stop_ids:
            yield "", True, "stop"
            return
        gen.append(first)

        # host-tracked cache position = the device's next-slot-to-write after
        # prefill (state["pos"] == n_prompt); starting one higher made the
        # capacity clamp in _next_steps a token stricter than pre-pipelining
        pos = ctx["n_prompt"]
        n_cur = self._next_steps(len(gen), pos, budget)
        pending = None
        if n_cur > 0:
            ctx["state"], pending = self._decode_chunk_call(
                ctx["state"], ctx["st"], n_cur, ctx["sp"].top_k, pos)

        done = pending is None
        # Emit the first sampled token's text NOW — chunk 1 is already
        # dispatched and overlaps with this yield.  Before this, the first
        # content increment waited a full decode-chunk device round trip
        # (~chunk×t_tok + RTT), which dominated server-level TTFT: the
        # first token was materialized in _start but sat unemitted.
        ready, finish, done = em.step(gen, done, finish)
        if ready:
            yield ready, False, finish
        espan = ctx.get("span")   # None when untraced: no span allocation,
        #                           no trace lock, anywhere in this loop
        req = rid(espan)
        while not done:
            if self._deadline_hit(ctx):
                finish = "deadline"   # caller timed out/disconnected: free
                break                 # the device within one decode chunk
            self.heartbeat.beat()
            FAULTS.fire("decode_step")
            cspan = espan.child("decode_chunk") if espan is not None else None
            with phase("decode_chunk", rid=req):   # dispatch + fetch
                # dispatch the NEXT chunk before touching the host copy of
                # the current one (betting that no stop token appears)
                pos += n_cur
                n_nxt = self._next_steps(len(gen) + n_cur, pos, budget)
                nxt = None
                if n_nxt > 0:
                    ctx["state"], nxt = self._decode_chunk_call(
                        ctx["state"], ctx["st"], n_nxt, ctx["sp"].top_k, pos)

                for t in np.asarray(pending).tolist():   # host sync
                    if len(gen) >= budget:   # surplus of the last chunk
                        break
                    if t in stop_ids:
                        finish = "stop"
                        done = True
                        break
                    gen.append(t)
            pending, n_cur = nxt, n_nxt
            if pending is None:
                done = True
            if cspan is not None:
                cspan.set(tokens=len(gen),
                          **self.cache.decode_span_attrs(pos),
                          **self.cache.span_attrs(self.cfg))
                cspan.end()
                ctx["trace"].note(tokens=len(gen))

            with phase("emit", rid=req):
                ready, finish, done = em.step(gen, done, finish)
            if ready:
                yield ready, False, finish

        ctx["ids"] = gen
        tail, finish = em.final(gen, finish)
        yield tail, True, finish

    # ------------------------------------------------------------------
    def _engine_span(self, trace, deadline):
        """Open the traced request's ``engine`` span (None passthrough)."""
        if trace is None:
            return None
        trace.note(deadline=deadline, tokens=0, **self._trace_attrs())
        return trace.span("engine").set(**self._trace_attrs())

    def _generate(self, messages, sp, max_tokens, stops, seed,  # lfkt: blocks-under[_lock] -- the serial engine's lock IS the request serialization: the whole generation (device syncs, drill sleeps, incident capture) runs under it by design
                  deadline=None, abort=None, trace=None) -> dict:
        # disagg decode role: one bounded remote-prefill hop BEFORE the
        # generation lock (loopback mode's page service needs it); role
        # off (`_disagg is None`, the default) costs this one attribute
        # read.  Explicit seeds bypass like every reuse path.
        pre_ids = None
        if self._disagg is not None and seed is None:
            pre_ids = self._remote_prefill(messages, deadline, trace)
        with self._lock:
            self.heartbeat.enter()
            try:
                return self._generate_locked(messages, sp, max_tokens, stops,
                                             seed, deadline, abort, trace,
                                             pre_ids=pre_ids)
            except Exception as e:  # noqa: BLE001 — burst detection, re-raised
                self._note_error(e)
                raise
            finally:
                self.heartbeat.leave()

    def _generate_locked(self, messages, sp, max_tokens, stops, seed,
                         deadline, abort, trace=None, pre_ids=None
                         ) -> dict:  # lfkt: holds[_lock]
        t0 = time.time()
        ctx = self._start(messages, sp, seed,
                          espan=self._engine_span(trace, deadline),
                          pre_ids=pre_ids)
        ctx["trace"] = trace
        ctx["deadline"] = deadline
        ctx["abort"] = abort
        parts = []
        finish = "stop"
        for text, done, fr in self._run(ctx, max_tokens, stops):
            parts.append(text)
            finish = fr
        timings = self._finish(ctx)
        content = "".join(parts)
        completion_tokens = len(ctx["ids"])
        logger.info("generation: %.2fs, finish=%s", time.time() - t0, finish)
        return {
            "lfkt_timings": timings,
            "id": f"chatcmpl-{uuid.uuid4().hex}",
            "object": "chat.completion",
            "created": int(time.time()),
            "model": self.model_name,
            "choices": [{
                "index": 0,
                "message": {"role": "assistant", "content": content},
                "finish_reason": finish,
            }],
            "usage": {
                "prompt_tokens": ctx["n_prompt"],
                "completion_tokens": completion_tokens,
                "total_tokens": ctx["n_prompt"] + completion_tokens,
            },
        }

    def _generate_stream(self, messages, sp, max_tokens, stops, seed,  # lfkt: blocks-under[_lock] -- the serial engine's lock IS the request serialization: the whole generation (device syncs, drill sleeps, incident capture) runs under it by design
                         deadline=None, abort=None,
                         trace=None) -> Iterator[dict]:
        # same pre-lock remote-prefill hop as _generate (one attribute
        # read when LFKT_DISAGG_ROLE is off)
        pre_ids = None
        if self._disagg is not None and seed is None:
            pre_ids = self._remote_prefill(messages, deadline, trace)
        with self._lock:
            self.heartbeat.enter()
            try:
                ctx = self._start(messages, sp, seed,
                                  espan=self._engine_span(trace, deadline),
                                  pre_ids=pre_ids)
            except Exception as e:  # noqa: BLE001 — burst detection, re-raised
                self.heartbeat.leave()
                self._note_error(e)
                raise
            ctx["trace"] = trace
            ctx["deadline"] = deadline
            ctx["abort"] = abort
            cid = f"chatcmpl-{uuid.uuid4().hex}"
            created = int(time.time())

            def chunk(delta: dict, finish=None):
                return {
                    "id": cid,
                    "object": "chat.completion.chunk",
                    "created": created,
                    "model": self.model_name,
                    "choices": [{
                        "index": 0, "delta": delta, "finish_reason": finish,
                    }],
                }

            finished = False
            try:
                yield chunk({"role": "assistant"})
                finish = "stop"
                for text, done, fr in self._run(ctx, max_tokens, stops):
                    finish = fr
                    if text:
                        yield chunk({"content": text})
                timings = self._finish(ctx)
                finished = True
                final = chunk({}, finish=finish)
                final["lfkt_timings"] = timings
                yield final
            except Exception as e:  # noqa: BLE001 — burst detection, re-raised
                self._note_error(e)
                raise
            finally:
                self.heartbeat.leave()
                if not finished:
                    # generator closed early (client gone): _finish must
                    # still run or self._cache would keep pointing at the
                    # buffer prefill donated, poisoning the next request
                    self._finish(ctx)
