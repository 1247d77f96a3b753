"""Continuous batching: slot-based scheduling over B lanes of one device.

The serial :class:`Engine` generates one request at a time.  This module
steps B sequences as one vmapped program (parallel/batched.py) and makes
the batch's B lanes **slots**: at every decode-chunk boundary finished
lanes are freed and waiting requests are admitted into them
(single-sequence prefill into a scratch cache, then a jit'd lane write into
the batched state).  Decode keeps running for whatever lanes are live, so
short requests exit early and long ones never block admission — the
vLLM-style serving loop, TPU-native: static shapes throughout, one compiled
program per (slice | chunk | lane-write) shape.  Decode efficiency is the
point: a single-sequence decode matvec cannot saturate HBM; B lanes
multiply decode throughput at nearly constant step latency (weights are
read once per step regardless of B).  Weights, the serial ring, the
scratch ring and the lane state all live plainly on the process's one
device: there is no mesh.

The reference's concurrency model (one generation at a time behind
Queue(5)+Semaphore(1), reference api.py:110-116) is the degenerate B=1 case;
back-pressure (503) and per-request timeouts stay at the server layer.
"""

from __future__ import annotations

import codecs
import functools
import logging
import queue as queue_mod
import threading
import time
import uuid
from concurrent.futures import CancelledError, Future
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..models.generate import prefill_chunk_jit, sample_jit
from ..models.llama import init_cache
from ..obs import memledger as _memledger
from ..obs.devtime import timed_jit
from ..obs.memledger import register_component, tree_nbytes
from ..obs.trace import (annotate_all_inflight, end_first_token, phase,
                         rid)
from ..parallel.batched import (
    batched_generate_chunk_perlane_jit, init_batched_state, init_lane_left,
    left_after)
from ..sampling.sample import SamplingParams, sampling_tensors, seed_window
from ..utils.faults import FAULTS
from ..utils.health import DeadlineExceeded, EngineUnavailable
from .engine import Engine
from .slices import next_slice

logger = logging.getLogger(__name__)


def _ledger_lane_bytes(eng: "ContinuousEngine") -> int:
    """Memory-ledger provider: the batched lane state's resident bytes,
    cache lanes + decode bookkeeping (snapshot-time metadata read —
    obs/memledger.py; ``.nbytes`` is shape metadata, safe even while a
    donating chunk program holds the buffers in flight)."""
    return tree_nbytes(getattr(eng, "_bstate", None))


def _ledger_scratch_bytes(eng: "ContinuousEngine") -> int:
    """Memory-ledger provider: the admission scratch ring's resident
    bytes (snapshot-time metadata read — obs/memledger.py)."""
    return tree_nbytes(getattr(eng, "_scratch_cache", None))


@functools.partial(jax.jit, static_argnames=("stop_ids",),
                   donate_argnames=("state", "lane_st"))
def _write_lane(state: dict, lane_st: dict, lane_left: jax.Array,
                lane: jax.Array, cache1: dict, pos, token, window, wpos, key,
                st: dict, left, stop_ids: tuple = ()):
    """Install a freshly prefilled sequence into batch lane ``lane``.
    ``cache1`` is NOT donated — the scheduler reuses it as the next
    admission's prefill scratch (no per-request cache allocation).  Leaf-
    generic over the cache pytree ({k, v} bf16 or the int8 four-leaf
    layout — models/llama.py init_cache).  ``left``: the tokens the
    request may decode after ``token``, its first.  It resets the lane's
    entry of ``lane_left`` (parallel/batched.py ``init_lane_left``), so a
    reused lane starts alive, unless its first token already ends it (a
    stop id, or no budget beyond it): the chunk program then never steps
    for it."""
    new_cache = jax.tree.map(
        lambda a, c: a.at[lane].set(c), state["cache"], cache1)
    new_state = {
        "cache": new_cache,
        "pos": state["pos"].at[lane].set(pos),
        "token": state["token"].at[lane].set(token),
        "window": state["window"].at[lane].set(window),
        "wpos": state["wpos"].at[lane].set(wpos),
        "key": state["key"].at[lane].set(key),
    }
    new_lane_st = jax.tree.map(
        lambda a, v: a.at[lane].set(v), lane_st, st)
    return new_state, new_lane_st, lane_left.at[lane].set(
        left_after(token, left, stop_ids))


# done stamp on ``lane_left``: the state and ``lane_st`` are donated onward
_write_lane = timed_jit("lane_write", _write_lane, site="engine.continuous",
                        leaf=2)


@jax.jit
def _lane_cache_copy_jit(cache: dict, lane) -> dict:
    """Snapshot one lane's KV ring into a scratch-shaped cache (lane-prefix
    reuse: the copy becomes the next admission's prefill scratch, so the
    suffix slices start from the reused history instead of position 0).
    Leaf-generic over the cache pytree (bf16 or int8 layout)."""
    return jax.tree.map(lambda a: a[lane], cache)


# no done stamp: its one result becomes the scratch the slices donate
_lane_cache_copy_jit = timed_jit("lane_cache_copy", _lane_cache_copy_jit,
                                 site="engine.continuous", leaf=None)


_STREAM_END = object()   # scheduler→stream-consumer sentinel


class AdmissionController:
    """Derives the scheduler's per-wave admission prefill-token budget from
    *measured* decode slack instead of the static ``LFKT_ADM_BUDGET``.

    The two signals, both free to measure on the scheduler thread:

    - **lane-idle fraction** — free lanes are lost throughput, so admission
      (refilling them) is the bottleneck: the budget should rise.
    - **decode pressure** — the fraction of the wave the scheduler spent
      *blocked* fetching the previous decode chunk.  A long fetch wait
      means the device was still busy when the host came back (decode is
      the bottleneck; prefill slices queued between chunks directly delay
      live lanes), so the budget should shrink.  A near-zero wait means
      the device sat idle waiting for the host — those admission slices
      were free, and more would be too.

    Both are EMA-smoothed (``alpha`` = LFKT_ADM_EMA_ALPHA; the EMAs SEED
    from the first observation, so the controller acts on measured state
    from wave one instead of riding an optimistic prior) and drive an
    AIMD update with the cut taking priority: sustained pressure halves
    the budget even while lanes sit idle (idle lanes under decode
    saturation mean decode can't keep up — feeding it more prefill is
    exactly the round-5 interference); otherwise idle lanes or plentiful
    slack grow it by one slice.  The floor is ONE slice per wave — an
    admission (deadline-bearing or not) always makes progress, so the
    controller can throttle but never starve (pinned by
    tests/test_admission.py).  Single-threaded by design: owned and
    driven by the scheduler loop.
    """

    #: ema_pressure below this means the device had idle headroom → grow
    SLACK_PRESSURE = 0.25
    #: ema_pressure above this means decode waits on the host's wave → cut
    HIGH_PRESSURE = 0.5

    def __init__(self, chunk: int, lanes: int, base: int,
                 alpha: float = 0.25, max_factor: int = 8):
        self.chunk = max(1, int(chunk))
        self.lanes = max(1, int(lanes))
        self.alpha = min(1.0, max(0.01, float(alpha)))
        self.min_budget = self.chunk              # ≥ one slice: no starvation
        self.max_budget = max(int(base), self.chunk) * max(1, int(max_factor))
        self.budget = min(max(int(base), self.min_budget), self.max_budget)
        self.ema_idle = 0.0       # seeded from the first observation
        self.ema_pressure = 0.0
        self.waves = 0

    def observe_wave(self, lanes_live: int, fetch_wait_s: float,
                     wave_s: float, mem_pressure: bool = False) -> int:
        """Fold one scheduler wave's measurements in; returns the budget
        for the NEXT wave.  ``mem_pressure`` is the memory ledger's HBM
        headroom verdict (obs/memledger.py): low headroom forces the cut
        branch regardless of idle lanes — admitting prefill into a chip
        about to OOM converts a latency problem into a dead pod."""
        a = self.alpha
        idle = 1.0 - min(lanes_live, self.lanes) / self.lanes
        pressure = min(1.0, fetch_wait_s / wave_s) if wave_s > 0 else 0.0
        if self.waves == 0:
            # seed, don't smooth: a controller born into saturation must
            # not spend ~1/alpha waves growing on an optimistic prior
            # (that ride IS the interference it exists to close, and the
            # watchdog-recovery path deliberately re-creates controllers
            # under live load)
            self.ema_idle, self.ema_pressure = idle, pressure
        else:
            self.ema_idle += a * (idle - self.ema_idle)
            self.ema_pressure += a * (pressure - self.ema_pressure)
        self.waves += 1
        if mem_pressure or self.ema_pressure > self.HIGH_PRESSURE:
            # decode saturates the device: halve, floor at one slice.
            # Takes PRIORITY over idle — free lanes under saturation mean
            # decode can't keep up, and more prefill only starves it.
            self.budget = max(self.budget // 2, self.min_budget)
        elif self.ema_idle > 0.01 or self.ema_pressure < self.SLACK_PRESSURE:
            # lanes idle (admission-bound) or decode slack to burn: grow
            self.budget = min(self.budget + self.chunk, self.max_budget)
        return self.budget

    def stats(self) -> dict:
        """Point-in-time introspection for scheduler_stats()/metrics."""
        return {
            "adm_budget_tokens": self.budget,
            "adm_ema_idle": round(self.ema_idle, 4),
            "adm_ema_pressure": round(self.ema_pressure, 4),
        }


class _Item:
    """One queued request: a future (non-stream) OR a chunk sink (stream)."""
    __slots__ = ("future", "messages", "sp", "max_tokens", "stops", "seed",
                 "sink", "abandoned", "deadline", "abort", "rid", "trace",
                 "t_enq")

    def __init__(self, future, messages, sp, max_tokens, stops, seed,
                 sink=None, deadline=None, abort=None, trace=None):
        self.future = future
        self.messages = messages
        self.sp = sp
        self.max_tokens = max_tokens
        self.stops = stops
        self.seed = seed
        self.sink = sink                    # queue.Queue for stream chunks
        self.abandoned = threading.Event()  # caller gave up: free the lane
        self.deadline = deadline            # absolute time.time() budget
        self.abort = abort                  # callable: caller gave up?
        self.rid = 0                        # registry key (abandon/fail_inflight)
        self.trace = trace                  # obs.trace.Trace | None (sampled out)
        self.t_enq = time.time()            # pending-span start (tracing only)


class _Slot:
    __slots__ = ("future", "gens", "budget", "n_prompt", "ids",
                 "first_token", "stops", "st", "sp", "t_admit", "ttft_s",
                 "sink", "abandoned", "dec", "n_emitted", "sent_bytes",
                 "held", "cid", "created", "finished", "pending_first",
                 "reused", "deadline", "abort", "trace", "pspan", "dspan",
                 "t_chunk", "fspan", "fwave")

    def __init__(self, item: _Item, budget, n_prompt, ids):
        self.future = item.future
        self.sink = item.sink
        self.abandoned = item.abandoned
        self.deadline = item.deadline
        self.abort = item.abort
        self.trace = item.trace   # span sinks (None when sampled out)
        self.pspan = None         # the admission's "prefill" span
        self.fspan = None         # its open "first_token" child
        self.fwave = 0            # chunks dispatched when fspan opened (the
        #                           lane's first chunk is the next one)
        self.dspan = None         # this slot's lane-occupancy "decode" span
        self.t_chunk = 0.0        # previous harvest time (chunk-span starts)
        self.finished = False   # set when resolved; the pipelined loop may
        #                         still hold this slot in an in-flight
        #                         chunk's lane snapshot — harvest skips it
        self.pending_first = False  # first token still on device (deferred
        #                             admission fetch); materialized at the
        #                             slot's first harvest
        self.gens: list[int] = []
        self.budget = budget
        self.n_prompt = n_prompt
        self.ids = ids
        self.reused = 0          # prompt tokens served from a lane claim
        # stream emission state: incremental UTF-8 decoder over the
        # append-only token byte stream (streamed text == batch decode)
        self.dec = codecs.getincrementaldecoder("utf-8")(errors="replace")
        self.n_emitted = 0
        self.sent_bytes = 0
        self.held = ""    # withheld text (possible stop-string prefix)
        self.cid = f"chatcmpl-{uuid.uuid4().hex}"
        self.created = int(time.time())


class ContinuousEngine(Engine):
    """An :class:`Engine` with ``batch_size`` lanes and a background
    scheduler thread with per-lane admission.

    Use :meth:`submit` (returns a ``concurrent.futures.Future`` resolving to
    the OpenAI-shaped dict) or the blocking ``create_chat_completion`` /
    ``create_chat_completions`` facades, which route through the scheduler.
    """

    # -- thread discipline (machine-checked: lfkt-lint LOCK001-004, see
    # docs/RUNBOOK.md "Lock discipline annotations") ----------------------
    # The scheduler thread OWNS the device state: unlike the serial engine
    # (whose callers mutate the ring under _lock), every serving-path write
    # to the state below happens on the lfkt-scheduler thread, so the
    # parent's lock mapping is replaced by thread confinement.  The only
    # cross-thread writes are in recover(), which runs strictly after the
    # thread is proven dead (join + alive/_loop_error guards).
    _GUARDED_BY = {
        "_bstate": None,            # scheduler-confined here (see above)
        "_cache": None,             # serial ring unused on the submit path
        "_prefix_ids": None,
        "_req_counter": "_id_lock",
    }
    _THREAD_ENTRIES = ("_loop",)
    _THREAD_CONFINED = (
        "_bstate", "_lane_st", "_lane_left", "_scratch_cache", "_adm",
        "_lane_claims",
        "_prefix_stats", "_stats", "_loop_error",
        "_adm_budget", "_lane_idle_s", "_mem_hot_prev", "_totals",
        "_slices_queued", "_wave_at_pass",
    )
    # cross-thread by design; individual operations are GIL-atomic
    # (dict/Queue/Event ops) or single reference stores
    # (cache_counts, pass_counts: the scheduler thread alone adds, /metrics
    # reads an int)
    _SHARED_ATOMIC = ("_items", "_pending", "_wake", "_stop", "_shutdown",
                      "_thread", "cache_counts", "pass_counts")

    def __init__(self, model_path: str | None, *, batch_size: int = 1,
                 max_top_k: int = 64,
                 prefill_chunk: int = 256, adm_budget: int = 512,
                 adm_controller: bool = True, adm_ema_alpha: float = 0.25,
                 lane_prefix_cache: bool = True, **kw):
        # the admission prompt-slice size doubles as the serial overlapped-
        # prefill slice size, so it lives on Engine (self._prefill_chunk)
        super().__init__(model_path, prefill_chunk=prefill_chunk, **kw)
        #: the lanes: how many sequences one decode chunk steps
        self.batch_size = int(batch_size)
        with self.startup.phase("lanes_alloc"):
            self._bstate = init_batched_state(self.cfg, self.batch_size)
        # lfkt-mem: the shared lane state is this engine's biggest serving
        # allocation — attribute it (the provider reads the live reference,
        # so watchdog re-inits stay correct automatically)
        register_component("kv_lanes", self, _ledger_lane_bytes)
        #: prefill-token budget per scheduler wave.  Static when the
        #: admission controller is off (LFKT_ADM_CONTROLLER=0): with short
        #: prompts several COMPLETE admissions fit one wave, and a long
        #: prompt consumes the budget in slices.  With the controller on
        #: (the default) this value is rewritten every wave from the EMA of
        #: measured lane-idle/decode-slack — see AdmissionController.
        self._adm_budget = max(self._prefill_chunk, adm_budget)
        self._adm_base = self._adm_budget      # controller re-init (recover)
        self._adm_alpha = adm_ema_alpha
        self._adm_ctl = AdmissionController(
            self._prefill_chunk, self.batch_size, self._adm_budget,
            alpha=adm_ema_alpha) if adm_controller else None
        #: cumulative idle lane-seconds (free lanes × wave wall), exported
        #: as scheduler_lane_idle_seconds / the lane_idle_seconds gauge
        self._lane_idle_s = 0.0
        #: the wave, counted where it happens: cumulative since start,
        #: one plain add per wave (or per slice) on the scheduler thread,
        #: exported through scheduler_stats() as scheduler_<key> gauges
        self._totals = self._zero_totals()
        #: prefill slices queued on the device since the last decode chunk
        #: was dispatched (the admission round runs ahead of its pass's
        #: chunk): that chunk's span names them as its wait
        self._slices_queued = 0
        #: ``chunks_dispatched`` as the current loop pass began: what an
        #: admission finished in the pass compares it with (chunks that
        #: left in the same pass without its lane)
        self._wave_at_pass = 0
        self._adm: dict | None = None   # in-flight chunked admission
        # -- lane-prefix reuse (default ON since round 6; the admission
        # -- controller closed the interference gap that kept it off) ------
        # A freed lane's KV ring still holds its finished conversation;
        # when the next admission's prompt shares that history (multi-turn
        # chat re-sends it verbatim, reference api.py:44-63), the claim is
        # snapshot into the scratch cache and only the suffix slices
        # prefill — directly attacking the scheduler's admission-prefill
        # bottleneck.  Reuse is chunk-aligned so the compiled slice-shape
        # set stays closed, skipped for explicit-seed requests (the serial
        # engine's reproducibility contract).  Claims are capped at
        # n_ctx-1: a freed lane keeps garbage-decoding in the shared
        # batched program, but those writes land at positions past the
        # claim (clamping to slot n_ctx-1 once pos overruns).
        # (Off for a cache that cannot be rolled back to a prefix, as the
        # serial engine's: a claim is by token position and a window restarts;
        # such a lane's walking position stays inside both stores by itself,
        # slot ``pos mod W``, and closes no window past the last closable one.)
        self._lane_prefix = bool(lane_prefix_cache) \
            and self.cache.rolls_back
        # paged mode (LFKT_KV_PAGED) folds the lane claims behind the
        # shared radix tree: one prefix-reuse implementation per mode (the
        # per-lane claim path remains the dense-ring default).  An
        # admission's reuse must stay aligned to BOTH the prefill slice
        # (every suffix slice shape inside the warmed compiled set) and
        # the page size (pages are the restore grain) — the lcm below.
        if self._kv_paged:
            self._lane_prefix = False
            import math

            self._paged_align = math.lcm(self._prefill_chunk,
                                         self._kvpool.page_tokens)
        self._lane_claims: list[list | None] = [None] * self.batch_size
        #: realized admission reuse, named for the implementation that
        #: served it — "lane_prefix" (dense claims) or "radix_prefix"
        #: (paged pool) — so a paged-vs-dense A/B never shows phantom
        #: activity under the other mode's stat
        self._reuse_stat = "radix_prefix" if self._kv_paged \
            else "lane_prefix"
        self._prefix_stats = {f"{self._reuse_stat}_hits": 0,
                              f"{self._reuse_stat}_reused_tokens": 0}
        sched = self.startup.phase("scheduler_start")
        self._scratch_cache = init_cache(self.cfg)
        # lfkt-mem: attribute the persistent prefill scratch (the lane
        # state is registered above; the serial ring by the base)
        register_component("kv_scratch", self, _ledger_scratch_bytes)
        #: previous wave's memory-pressure verdict: the rising edge emits
        #: ONE mem_pressure trace event + counter, not one per wave
        self._mem_hot_prev = False
        base_st = sampling_tensors(SamplingParams())
        self._lane_st = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (self.batch_size,)), base_st)
        #: each lane's end as the chunk program tracks it: the tokens it
        #: may still decode, 0 once it ended (parallel/batched.py
        #: ``init_lane_left``); the device's copy of the harvest's rule
        self._lane_left = init_lane_left(self.batch_size)
        # static top_k ceiling of the shared compiled decode program;
        # per-request k rides as a traced mask (sampling/sample.py) and is
        # effectively min(requested, ceiling)
        self._max_top_k = max(max_top_k, SamplingParams().top_k)
        self._req_counter = 0                # monotonic request id (abandon key)
        self._stats = {"lanes_live": 0, "pending": 0, "admission_inflight": 0}
        self._items: dict[int, _Item] = {}   # live request id → item (abandon)
        self._pending: queue_mod.Queue = queue_mod.Queue()
        self._wake = threading.Event()
        self._stop = False
        self._shutdown = False   # deliberate stop: recovery must refuse
        self._loop_error: BaseException | None = None
        self._thread = threading.Thread(
            target=self._loop, name="lfkt-scheduler", daemon=True)
        self._thread.start()
        sched.close()

    @property
    def _stop_ids(self) -> tuple:
        """The tokenizer's stop ids as the lane programs take them (a
        static argument): what the harvest tests a token against."""
        return tuple(sorted(self.tokenizer.stop_ids))

    @staticmethod
    def _zero_totals() -> dict:
        """The per-wave counters (docs/OBSERVABILITY.md "The wave").  A
        wave is one loop pass that dispatched a decode chunk: the
        admission slices queued ahead of it, the chunk, and the fetch +
        harvest of the chunk before it.  ``lane_live_seconds`` + the older
        ``lane_idle_seconds`` is ``batch_size`` x ``wave_seconds``.
        ``admits_beside_live`` counts admissions finished while other
        lanes decode (the deferred first token); ``admit_chunks_behind``
        sums, over them, the decode chunks the loop pass that finished the
        admission had already dispatched without its lane: 0 since the
        round runs ahead of the chunk, 1 each in the order before.
        ``steps_run`` / ``steps_skipped`` split the fetched chunks'
        ``decode_chunk`` steps into those the chunk program ran and those
        it left out because none of its lanes had anything left to decode
        (parallel/batched.py ``batched_generate_chunk_perlane_jit``);
        ``chunks_empty`` counts the chunks that ran none, one behind
        every request that ends with no other lane alive.
        ``end_disagreements``: lanes whose end the device and the harvest
        saw at different tokens (0: they apply one rule)."""
        return {"waves": 0, "wave_seconds": 0.0, "lane_live_seconds": 0.0,
                "fetch_wait_seconds": 0.0, "admit_seconds": 0.0,
                "admit_slices": 0, "admit_tokens": 0,
                "harvest_seconds": 0.0, "chunks_dispatched": 0,
                "admits_beside_live": 0, "admit_chunks_behind": 0,
                "steps_run": 0, "steps_skipped": 0, "chunks_empty": 0,
                "end_disagreements": 0}

    # ------------------------------------------------------------------
    def submit(self, messages: Sequence[dict], *, temperature: float = 0.2,
               top_p: float = 0.95, top_k: int = 40, min_p: float = 0.05,
               frequency_penalty: float = 0.0, presence_penalty: float = 0.0,
               repeat_penalty: float = 1.1, max_tokens: int | None = None,
               stop: Sequence[str] | str | None = None,
               seed: int | None = None,
               deadline: float | None = None, abort=None,
               trace=None) -> Future:
        """Queue one request; the scheduler admits it to a free lane.

        ``top_k`` is served per-request up to the engine's ``max_top_k``
        ceiling (the static k of the shared compiled program); larger values
        are effectively clamped to the ceiling.  ``deadline`` (absolute
        ``time.time()``) frees the request's lane within one decode chunk
        of expiry, resolving the future with :class:`DeadlineExceeded`.
        ``trace`` (obs.trace.Trace | None) collects the request's span
        tree: pending wait, chunked prefill, per-slot occupancy + decode
        chunks — produced on the scheduler thread."""
        item = self._enqueue(
            messages, temperature=temperature, top_p=top_p, top_k=top_k,
            min_p=min_p, frequency_penalty=frequency_penalty,
            presence_penalty=presence_penalty, repeat_penalty=repeat_penalty,
            max_tokens=max_tokens, stop=stop, seed=seed, deadline=deadline,
            abort=abort, trace=trace)
        fut = item.future
        fut._lfkt_req_id = item.rid
        fut.add_done_callback(
            lambda f, rid=item.rid: self._items.pop(rid, None))
        return fut

    def _enqueue(self, messages, *, temperature, top_p, top_k, min_p,
                 frequency_penalty, presence_penalty, repeat_penalty,
                 max_tokens, stop, seed, sink=None, deadline=None,
                 abort=None, trace=None) -> _Item:
        """Shared submit/submit_stream path: guards, param normalization,
        item construction, registry entry, enqueue + scheduler wake."""
        if self._loop_error is not None:
            raise EngineUnavailable("scheduler died") from self._loop_error
        if self._stop:
            raise EngineUnavailable("engine has been shut down")
        sp = SamplingParams(
            temperature=temperature, top_p=top_p, top_k=top_k, min_p=min_p,
            frequency_penalty=frequency_penalty,
            presence_penalty=presence_penalty, repeat_penalty=repeat_penalty,
        )
        if isinstance(stop, str):
            stop = [stop]
        item = _Item(None if sink is not None else Future(), list(messages),
                     sp, max_tokens, list(stop or []), seed, sink=sink,
                     deadline=deadline, abort=abort, trace=trace)
        if trace is not None:
            trace.note(deadline=deadline, tokens=0, **self._trace_attrs())
        with self._id_lock:
            self._req_counter += 1
            item.rid = self._req_counter
        # live-request registry: abandon() routes through it, and a watchdog
        # trip fails everything in it (fail_inflight) so no caller hangs on
        # a wedged scheduler.  Futures deregister via their done callback
        # (submit); streams deregister in the consumer generator's finally
        # (submit_stream).
        self._items[item.rid] = item
        self._pending.put(item)
        self._wake.set()
        return item

    def abandon(self, fut: Future) -> None:
        """Tell the scheduler the caller no longer wants ``fut``'s result:
        the request's lane is freed at the next chunk boundary instead of
        decoding to budget (the reference discards abandoned results but its
        serial engine idles anyway, reference api.py:97-100; here an occupied
        lane would delay other requests — VERDICT r1 #6)."""
        rid = getattr(fut, "_lfkt_req_id", None)
        item = self._items.get(rid) if rid is not None else None
        if item is not None:
            item.abandoned.set()

    def submit_stream(self, messages: Sequence[dict], *,
                      temperature: float = 0.2, top_p: float = 0.95,
                      top_k: int = 40, min_p: float = 0.05,
                      frequency_penalty: float = 0.0,
                      presence_penalty: float = 0.0,
                      repeat_penalty: float = 1.1,
                      max_tokens: int | None = None,
                      stop: Sequence[str] | str | None = None,
                      seed: int | None = None,
                      deadline: float | None = None, abort=None,
                      trace=None):
        """Queue one streaming request; returns an iterator of OpenAI chunk
        dicts produced as the request's lane decodes.  Closing the iterator
        abandons the request (its lane frees at the next chunk boundary).
        Defaults match :meth:`submit` (llama-cpp-python 0.2.77's)."""
        sink: queue_mod.Queue = queue_mod.Queue()
        item = self._enqueue(
            messages, temperature=temperature, top_p=top_p, top_k=top_k,
            min_p=min_p, frequency_penalty=frequency_penalty,
            presence_penalty=presence_penalty, repeat_penalty=repeat_penalty,
            max_tokens=max_tokens, stop=stop, seed=seed, sink=sink,
            deadline=deadline, abort=abort, trace=trace)

        def gen():
            try:
                while True:
                    chunk = sink.get()
                    if chunk is _STREAM_END:
                        return
                    if isinstance(chunk, BaseException):
                        raise chunk
                    yield chunk
            finally:
                item.abandoned.set()   # no-op if the stream finished cleanly
                self._items.pop(item.rid, None)
        return gen()

    def create_chat_completion(self, messages, stream: bool = False, **kw):
        if stream:  # streams ride scheduler lanes too (concurrent with
            return self.submit_stream(messages, **kw)  # batched requests)
        return self.submit(messages, **kw).result()

    def failure(self) -> BaseException | None:
        """Watchdog hook: the exception that killed the scheduler loop, or
        None while it is (believed) healthy."""
        return self._loop_error

    def fail_inflight(self, exc: BaseException) -> None:
        """Resolve every registered live request with ``exc`` (watchdog
        trip): callers get their 503 NOW instead of hanging on a wedged or
        dead scheduler until their own timeouts fire.  Items are marked
        abandoned so a still-running loop discards their lanes at the next
        harvest instead of double-resolving."""
        for item in list(self._items.values()):
            item.abandoned.set()
            if item.future is not None:
                if not item.future.done():
                    try:
                        item.future.set_exception(exc)
                    except Exception:  # noqa: BLE001 — lost race with the loop
                        pass
            elif item.sink is not None:
                item.sink.put(exc)

    def recover(self) -> bool:  # lfkt: noqa[LOCK002] -- writes scheduler-confined state only after the owning thread is proven dead (join + alive/_loop_error refusal guards above each write)
        """Bounded recovery (engine/watchdog.py): restart a *dead* scheduler
        on rebuilt device state.  Refuses while the loop thread is alive and
        unfailed — a wedged thread may still own the donated buffers, and
        restarting state under it would race; the watchdog then escalates
        to DEAD and the pod restart frees the device.  Also refuses after a
        deliberate :meth:`shutdown` (that is not a fault)."""
        FAULTS.fire("recover")   # injection point: recovery that fails
        if self._shutdown:
            return False
        if self._thread.is_alive() and self._loop_error is None:
            return False
        self._thread.join(timeout=2)
        if self._thread.is_alive():
            return False
        # fallible device re-init FIRST: if it raises (e.g. OOM — a likely
        # condition for recovery to run under), _loop_error must remain set
        # so the watchdog keeps seeing a dead engine and _enqueue keeps
        # refusing — clearing it early would leave a zombie with READY
        # probes and no scheduler thread, queueing every request into a
        # 408 (code-review r2 finding)
        with self._lock:
            self._recover_locked()          # fresh serial ring (+ pool)
        # a crash mid-chunk may have poisoned the donated lane state
        self._bstate = init_batched_state(self.cfg, self.batch_size)
        self._scratch_cache = init_cache(self.cfg)
        base_st = sampling_tensors(SamplingParams())
        self._lane_st = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (self.batch_size,)), base_st)
        self._lane_left = init_lane_left(self.batch_size)
        # re-init succeeded: clear the fault signature and restart
        self._loop_error = None
        self._stop = False
        self._adm = None
        self._items.clear()
        self._lane_claims = [None] * self.batch_size
        self._lane_idle_s = 0.0
        self._totals = self._zero_totals()
        self._slices_queued = 0
        self._wave_at_pass = 0
        if self._adm_ctl is not None:
            # fresh controller: post-recovery traffic should not inherit
            # the pre-crash EMAs (a wedged device reads as max pressure)
            self._adm_ctl = AdmissionController(
                self._prefill_chunk, self.batch_size, self._adm_base,
                alpha=self._adm_alpha)
            self._adm_budget = self._adm_ctl.budget
        else:
            self._adm_budget = self._adm_base
        self._stats = {"lanes_live": 0, "pending": 0, "admission_inflight": 0}
        self.heartbeat.reset()
        self._thread = threading.Thread(
            target=self._loop, name="lfkt-scheduler", daemon=True)
        self._thread.start()
        return True

    def create_chat_completions(self, batch_messages, **kw) -> list[dict]:
        futs = [self.submit(m, **kw) for m in batch_messages]
        out = []
        for f in futs:
            try:
                out.append(f.result())
            except ValueError as e:  # per-request input error, isolated
                out.append({"error": {"message": str(e),
                                      "type": "invalid_request_error"}})
        return out

    def shutdown(self):
        self._shutdown = True
        self._stop = True
        self._wake.set()
        self._thread.join(timeout=10)

    def _warmup_steps(self, ph) -> str:
        """Compile the scheduler's shapes: every admission prefill SLICE
        shape (the scheduler prefills via prefill_chunk_jit, not the serial
        engine's bucket-sized prefill_jit), first-token sampling, the lane
        write, and the batched decode chunk.  Streams ride the same lane
        programs, so one streamed request exercises (but doesn't extend)
        the compiled set."""
        msgs = [{"role": "user", "content": "hi"}]
        with ph.child("lanes_round"):
            futs = [self.submit(msgs, max_tokens=self.decode_chunk + 1,
                                temperature=0.0)
                    for _ in range(self.batch_size)]
            for f in futs:
                f.result()
        with ph.child("stream_round"):
            list(self.submit_stream(msgs, max_tokens=self.decode_chunk + 1,
                                    temperature=0.0))
        # every slice shape the plan can cut a prompt into (engine/
        # slices.py), one call a shape, compiled against a throwaway cache
        # (jit program caches are global, so the scheduler thread hits them
        # warm; its own scratch cache is never touched)
        with ph.child("slice_shapes") as slices:
            cache, slices.attrs["n_shapes"] = self._warm_slice_shapes(
                self.prefill_buckets, init_cache(self.cfg))
        with ph.child("drain"):   # the slices queued above, still running
            jax.block_until_ready(cache)
        # the throwaway ring goes BEFORE the snapshot below is made: a lane
        # engine whose caches fill the chip (six of 2 GB beside the file:
        # ``ouro``) has room for one spare cache at a time, not for two
        del cache
        if self._lane_prefix:
            # compile the lane→scratch snapshot gather (one program; the
            # suffix slice shapes are already in the warmed set above)
            with ph.child("lane_copy"):
                jax.block_until_ready(_lane_cache_copy_jit(
                    self._bstate["cache"], jnp.int32(0)))
        return f"{self.batch_size} lanes"

    # ------------------------------------------------------------------
    # scheduler internals (all device work on the scheduler thread)
    # ------------------------------------------------------------------

    # -- admission: a chunked-prefill state machine ---------------------
    # At most one admission is in flight; its prompt prefills in
    # ``prefill_chunk``-token slices, one slice per scheduler iteration, so
    # a 1024-token admission stalls live lanes' decode by ~one slice per
    # chunk boundary instead of a whole bucket (VERDICT r2 weak #4: vLLM's
    # chunked-prefill, TPU-static-shape edition — slice shapes come from
    # the fixed bucket set, so the compiled-program set stays closed).

    def _free_lane(self, lane: int, slot: _Slot, slots: list,
                   claim: bool = True) -> None:
        """Release ``slot``'s lane (no-op if it never occupied one) and
        record which token ids' KV remain valid there for prefix reuse —
        a lane claim in dense mode, a pool commit in paged mode.  The ONE
        place the free-lane invariant lives — every path that finishes a
        slot must come through here.  ``claim=False`` for error finishes
        (a device fault surfaced at fetch means the KV that prefill left
        in the lane is of unknown validity — it must not seed a later
        admission's reuse).

        Claim residency matches the serial engine's prefix cache
        (engine.py::_finish): ring slots [0, n_prompt + len(gens) - 1)
        hold prompt + generated tokens except the last sampled one; the
        pipelined loop's discarded decode of the freed lane writes only
        past that (capped at n_ctx-1 where overrun writes clamp)."""
        if slots[lane] is slot:
            slots[lane] = None
        keep = min(slot.n_prompt + max(len(slot.gens) - 1, 0),
                   self.cfg.n_ctx - 1)
        if self._kv_paged:
            if claim:
                # commit the finished conversation's whole-page prefix to
                # the shared pool straight from the batched lane (the
                # gather+slice+scatter fuse in one program — no lane-ring
                # copy is materialized); already-cached pages dedupe, so a
                # multi-turn follow-up stores only its delta
                self._kvpool.commit_lane(
                    (list(slot.ids) + slot.gens)[:keep],
                    self._bstate["cache"], lane, namespace=self._kv_ns)
            return
        if not self._lane_prefix:
            return
        if not claim:
            self._lane_claims[lane] = None
            return
        self._lane_claims[lane] = (list(slot.ids) + slot.gens)[:keep]

    def _find_lane_reuse(self, ids: list, n_prompt: int):
        """(reuse_len, source_lane) — the longest chunk-aligned usable
        claim prefix across the lanes (a freed lane's conversation, a live
        lane's prompt), or (0, None).  Chunk alignment keeps every suffix
        slice shape inside the warmed compiled set."""
        best, src = 0, None
        cap = n_prompt - 1   # ≥1 real token must prefill (last-token logits)
        for lane, claim in enumerate(self._lane_claims):
            if claim is None:
                continue
            lim = min(len(claim), cap)
            i = 0
            while i < lim and claim[i] == ids[i]:
                i += 1
            i = (i // self._prefill_chunk) * self._prefill_chunk
            if i > best:
                best, src = i, lane
        if best < self._prefill_chunk:
            return 0, None
        return best, src

    def _resolve_skipped(self, item: _Item, exc: BaseException | None = None
                         ) -> None:
        """Resolve an item the scheduler will never serve (abandoned,
        cancelled, or deadline-expired while queued) so no awaiter hangs."""
        if item.future is not None and not item.future.done():
            if exc is not None:
                try:
                    item.future.set_exception(exc)
                except Exception:  # noqa: BLE001 — lost race: already resolved
                    pass
            elif not item.future.cancel():
                item.future.set_exception(CancelledError())
        elif item.sink is not None:
            item.sink.put(exc if exc is not None else _STREAM_END)

    def _begin_admission(self, item: _Item, alone: bool = False
                         ) -> dict | None:
        """Guards + tokenize + machine setup (no device work yet).
        ``alone``: :meth:`_admit_step`'s."""
        if item.abandoned.is_set():
            self._resolve_skipped(item)
            return None
        if item.abort is not None and item.abort():
            self._resolve_skipped(item)
            return None
        if item.deadline is not None and time.time() > item.deadline:
            # expired while queued: never occupy a lane for a caller that
            # already gave up (deadline propagation, reference-parity 408)
            self._resolve_skipped(item, DeadlineExceeded(
                "request deadline expired before admission"))
            return None
        if item.future is not None and not item.future.set_running_or_notify_cancel():
            return None                                # cancelled while queued
        t0 = time.time()
        pspan = None
        if item.trace is not None:
            # pending: submit -> the scheduler picking this item up
            item.trace.span("pending", t0=item.t_enq).end(t0)
            pspan = item.trace.span("prefill", t0=t0)
        lease = None
        try:
            with phase("tokenize", rid=rid(item.trace)):
                ids, memo = self._tokenize_counted(item.messages)
            if pspan is not None:
                pspan.child("tokenize", t0=t0).set(
                    n_prompt=len(ids), **memo).end()
            if len(ids) >= self.cfg.n_ctx:
                raise ValueError(
                    f"Requested tokens ({len(ids)}) exceed context window "
                    f"of {self.cfg.n_ctx}")
            bucket = self._bucket_for(len(ids))
            # disagg decode role (serving/disagg/): one bounded remote-
            # prefill hop per admission — the peer's pages import into
            # the shared pool, so _paged_admission_reuse below restores
            # them and the suffix slices are all this wave prefills
            # locally (the "decode-only waves" shape).  Role off is one
            # attribute read; the client bounds the hop by the item's
            # deadline and degrades every failure to local prefill.
            if self._disagg is not None and item.seed is None:
                self._remote_prefill_ids(ids, item.deadline, pspan)
            reuse, src = 0, None
            if item.seed is None:
                # explicit seeds take the full prefill: the suffix pass
                # scores bf16-rounded reused KV, so a near-tied logit could
                # flip — same reproducibility contract as the serial engine
                if self._kv_paged:
                    reuse, lease = self._paged_admission_reuse(ids, pspan)
                elif self._lane_prefix:
                    reuse, src = self._find_lane_reuse(ids, len(ids))
            if lease is not None:
                # restore the matched pages straight into the scratch ring
                # (donated in place — no transient second ring, unlike the
                # lane snapshot below); the suffix slices then prefill
                # from offset ``reuse`` exactly like a lane-claim hit.
                # The scratch ref is dropped across the donating call: a
                # mid-copy failure must not leave a dead donated buffer
                # as self._scratch_cache (_dispatch_prefill_chunk
                # re-creates on None, same as the lane-snapshot path)
                scratch, self._scratch_cache = self._scratch_cache, None
                if scratch is None:
                    scratch = init_cache(self.cfg)
                self._scratch_cache = self._kvpool.restore(
                    lease, scratch, span=pspan)
            elif reuse:
                # snapshot the source lane's ring as this admission's
                # scratch; the functional gather captures the lane BEFORE
                # any later decode writes, so the claim region is stable.
                # Drop the old scratch FIRST: holding it across the copy
                # peaks HBM one full lane-ring higher, which is what tipped
                # the 8-lane 8B prefill arm into ResourceExhausted on 16 GB
                # (suite3 2026-08-01).  If the copy itself fails, scratch
                # stays None and _dispatch_prefill_chunk lazily re-creates
                # it — allocating a replacement HERE, inside the failure,
                # would be a second allocation on the same exhausted HBM.
                self._scratch_cache = None
                self._scratch_cache = _lane_cache_copy_jit(
                    self._bstate["cache"], jnp.int32(src))
                # stats are counted in _finish_admission: an item abandoned
                # mid-prefill (or failing later) must not inflate /metrics
            if pspan is not None:
                pspan.set(n_prompt=len(ids), bucket=bucket, reused=reuse)
            self._note_prefill(len(ids), pspan, reuse, alone)
            # host-side slice prep happens ONCE, here, while lanes decode:
            # one int32 array for the padded prompt; every slice dispatch
            # then takes a zero-copy view instead of re-converting a list
            # (the round-6 overlap of slice prep with device compute)
            padded = np.zeros((bucket,), np.int32)
            padded[:len(ids)] = ids
            return {
                "item": item, "ids": ids, "n_prompt": len(ids),
                "bucket": bucket,
                "padded": padded,
                "st": sampling_tensors(item.sp),
                "seed": item.seed if item.seed is not None else self._next_seed(),
                "t0": t0, "offset": reuse, "reused": reuse, "logits": None,
                "span": pspan, "lease": lease, "t_slice": t0,
            }
        except Exception as e:  # noqa: BLE001 — per-request isolation
            self._note_error(e)
            if lease is not None:
                self._kvpool.release(lease)
            if item.future is not None:
                item.future.set_exception(e)
            elif item.sink is not None:
                item.sink.put(e)
            return None

    def _paged_admission_reuse(self, ids: list, pspan=None):
        """(reuse_tokens, lease | None): the longest cached whole-page
        prefix aligned to ``_paged_align``, pinned.  No bucket constraint
        (admissions prefill in slices from the reuse offset) — the same
        cap and alignment contract as :meth:`_find_lane_reuse`, against
        the process-wide radix index instead of per-lane claims."""
        pool = self._kvpool
        i = min(pool.match_len(ids, namespace=self._kv_ns), len(ids) - 1)
        r = (i // self._paged_align) * self._paged_align
        if r < self._paged_align:
            pool.note_miss()
            return 0, None
        lease = pool.acquire(ids, r, span=pspan, namespace=self._kv_ns)
        if lease is None:      # raced an eviction / spill-restore failed
            return 0, None
        if pspan is not None:
            # guarded: between acquire and the handoff below, a raising
            # span setter is the ONE thing that could leak the pinned
            # pages — _begin_admission's cleanup releases its own `lease`
            # local, which is still None while this call is on the stack
            # (found by lfkt-lint RES001; regression-pinned in
            # tests/test_kv_paged_engines.py)
            try:
                pspan.set(reused_pages=len(lease.page_ids),
                          matched_tokens=i)
            except Exception:  # noqa: BLE001 — telemetry must never pin pages
                pass
        return r, lease

    def _release_adm_lease(self, adm) -> None:
        """Unpin an admission's pool pages (idempotent: the lease is
        consumed from the machine dict) — called from every admission
        exit: finish, abandon, dispatch failure."""
        lease = adm.pop("lease", None) if adm else None
        if lease is not None:
            self._kvpool.release(lease)

    def _dispatch_prefill_chunk(self, adm: dict) -> None:
        """Run ONE prompt slice through the model into the scratch cache.
        Keeps the logits of the slice containing the last real token.
        ``adm["alone"]`` (nobody decodes behind the slice: what
        :meth:`_admit_step` was called with) lets the plan cut it wide
        (engine/slices.py).

        The dispatch is async — its host wall (observed into the
        ``prefill_slice_seconds`` histogram and the request's
        ``prefill_slice`` span) is slice prep + device enqueue, overlapping
        the previous slice's / decode chunk's compute; a long wall means the
        device queue pushed back (the interference signal the admission
        controller is closing)."""
        t_s = time.time()
        self.heartbeat.beat()
        FAULTS.fire("prefill")
        if self._scratch_cache is None:
            # a failed lane snapshot (_begin_admission reuse path) dropped
            # the scratch; re-create it now that the failing allocation is
            # gone.  Prefill needs no zeroing: positions past the prompt
            # are never attended.
            self._scratch_cache = init_cache(self.cfg)
        off = adm["offset"]
        C = next_slice(off, adm["n_prompt"], adm["bucket"],
                       self._prefill_chunk, self._wide_slice, adm["alone"])
        sl = jnp.asarray(adm["padded"][off:off + C])
        li = min(max(adm["n_prompt"] - 1 - off, 0), C - 1)
        # the program by whether the slice holds the prompt's last token
        scfg = self._slice_cfgs[off <= adm["n_prompt"] - 1 < off + C]
        with phase("admit_slice", rid=rid(adm["item"].trace), offset=off,
                   tokens=C):
            logits, cache = prefill_chunk_jit(
                self.params, scfg, sl, jnp.int32(off), jnp.int32(li),
                self._scratch_cache)
        self._scratch_cache = cache
        if off <= adm["n_prompt"] - 1 < off + C:
            adm["logits"] = logits
        adm["offset"] = off + C
        t_e = adm["t_slice"] = time.time()
        self._observe_slice(t_e - t_s)
        tot = self._totals
        tot["admit_slices"] += 1
        tot["admit_tokens"] += C
        self._count_slice(C, scfg)
        self._slices_queued += 1
        # wave: the decode chunk this slice is queued ahead of (the next
        # one dispatched), the number that chunk's decode_chunk spans carry
        self._slice_span(adm.get("span"), t_s, t_e, off, C,
                         wave=tot["chunks_dispatched"] + 1,
                         **self._slice_attrs(scfg))

    def _finish_admission(self, adm: dict, lane: int, slots: list) -> None:
        """Prefill complete: sample the first token, write the lane, install.

        When other lanes are decoding, the first-token fetch is DEFERRED
        (async copy now, materialized at the slot's first harvest): a
        blocking ``int(token)`` here drains the whole queued device
        pipeline through the dispatch round-trip on every admission, which
        under churn serializes the loop and starves live lanes (measured:
        batch-4 aggregate throughput below a single lane's).  The round
        runs AHEAD of its pass's decode chunk, so a blocking fetch beside
        live lanes would also stand in front of a chunk not yet
        dispatched: ``deferred`` is true whenever a lane holds a request.
        The lane is then live in the chunk dispatched right after the
        round, and its first token is handed over with that chunk's rows.
        With no live lanes nothing is starved (a chunk still in flight
        then carries only freed lanes' discarded rows), so the synchronous
        path keeps the tightest TTFT for unloaded traffic."""
        item = adm["item"]
        try:
            ids, n_prompt, st = adm["ids"], adm["n_prompt"], adm["st"]
            self._lane_claims[lane] = None   # lane overwritten below
            window, wpos = seed_window(ids)
            budget = min(self._token_budget(item.max_tokens, n_prompt),
                         max(0, self.cfg.n_ctx - 1 - n_prompt))
            with phase("first_sample", rid=rid(item.trace), lane=lane):
                token, window, wpos, key = sample_jit(
                    adm["logits"], window, wpos,
                    jax.random.PRNGKey(adm["seed"]), st, self.cfg,
                    top_k=self._max_top_k)
                # the lane's end goes to the device with it: the budget
                # less the first token, and whether that token already
                # ends it
                self._bstate, self._lane_st, self._lane_left = _write_lane(
                    self._bstate, self._lane_st, self._lane_left,
                    jnp.int32(lane), self._scratch_cache,
                    jnp.int32(n_prompt), token, window, wpos, key, st,
                    jnp.int32(budget - 1), stop_ids=self._stop_ids)
            if self._lane_prefix:
                # a LIVE lane is a claim too: its prompt's rows stay where
                # they are while it decodes (it writes from n_prompt on),
                # so requests that arrive together behind one system line
                # ride the first one's prefill instead of each repeating it
                # (16 callers at once, 8k shared: 2 s of prefill each, the
                # last one past the server's timeout).  _free_lane extends
                # the claim by what was generated, or drops it.
                self._lane_claims[lane] = ids
            slot = _Slot(item, budget, n_prompt, ids)
            slot.stops = item.stops
            slot.st = st
            slot.sp = item.sp
            slot.t_admit = adm["t0"]
            slot.pspan = adm.get("span")
            deferred = any(s is not None for s in slots)
            if slot.pspan is not None:
                # last slice dispatched -> first token on the host
                slot.fspan = slot.pspan.child(
                    "first_token", t0=adm["t_slice"]).set(deferred=deferred)
                slot.fwave = self._totals["chunks_dispatched"]
            slot.reused = adm.get("reused", 0)
            if slot.reused:     # count only realized reuse (lane written)
                self._prefix_stats[f"{self._reuse_stat}_hits"] += 1
                self._prefix_stats[
                    f"{self._reuse_stat}_reused_tokens"] += slot.reused
            if deferred:
                tot = self._totals
                tot["admits_beside_live"] += 1
                tot["admit_chunks_behind"] += \
                    tot["chunks_dispatched"] - self._wave_at_pass
                try:
                    token.copy_to_host_async()
                except Exception:  # noqa: BLE001 — optional fast path
                    pass
                slot.first_token = token        # device array
                slot.ttft_s = None              # set at materialize
                slot.pending_first = True
                self._open_decode_span(lane, slot)
                slots[lane] = slot
                return
            slot.first_token = int(token)   # host sync: prefill done = TTFT
            slot.ttft_s = time.time() - adm["t0"]
            self._end_prefill_span(slot, token)
            if slot.sink is not None:       # stream: open the chunk stream
                slot.sink.put(self._chunk(slot, {"role": "assistant"}))
            self._install(lane, slots, slot)
        except Exception as e:  # noqa: BLE001 — per-request isolation
            self._note_error(e)
            if adm.get("span") is not None:
                adm["span"].set(error=str(e)).end()
            if item.future is not None and not item.future.done():
                item.future.set_exception(e)
            elif item.sink is not None:
                item.sink.put(e)
        finally:
            # the lease's job ends once the restored scratch has been
            # written into the lane (or the admission failed): unpin so
            # the pages become evictable again
            self._release_adm_lease(adm)

    def _end_prefill_span(self, slot: _Slot, token=None) -> None:
        """Close the admission's ``prefill`` span at TTFT.  Idempotent —
        the deadline/abandon path in _harvest re-runs it after a normal
        close, and the tokens=1 note must not clobber the per-chunk token
        counts recorded since — so the span reference is consumed here.
        ``token``: the first token's device array, where the caller has
        just fetched it (``first_token`` then holds what the device ran
        inside it: obs/trace.py ``end_first_token``)."""
        if slot.pspan is not None:
            if slot.fspan is not None:
                end_first_token(
                    slot.fspan, slot.pspan, token,
                    waves=self._totals["chunks_dispatched"] - slot.fwave)
                slot.fspan = None
            if slot.ttft_s is not None:
                slot.pspan.set(ttft_s=round(slot.ttft_s, 6))
            slot.pspan.end()
            slot.pspan = None
            slot.trace.note(tokens=1)

    def _materialize_first(self, lane: int, slot: _Slot, slots: list) -> None:
        """Deferred-admission bookkeeping, run at the slot's first harvest:
        the harvest of the chunk dispatched right after the round that
        admitted it (its sample was queued ahead of that chunk, so this
        fetch does not wait on new device work): first-token value, TTFT,
        stream open, first stop/budget checks."""
        slot.pending_first = False
        token = slot.first_token            # the device array
        try:
            slot.first_token = int(token)
        except Exception as e:  # noqa: BLE001 — per-request isolation
            self._note_error(e)
            slot.finished = True
            self._end_prefill_span(slot)
            self._free_lane(lane, slot, slots, claim=False)
            if slot.sink is not None:
                slot.sink.put(e)
            elif not slot.future.done():
                slot.future.set_exception(e)
            return
        slot.ttft_s = time.time() - slot.t_admit
        self._end_prefill_span(slot, token)
        if slot.sink is not None:
            slot.sink.put(self._chunk(slot, {"role": "assistant"}))
        self._install(lane, slots, slot)

    def _open_decode_span(self, lane: int, slot: _Slot) -> None:
        """Start the slot's lane-occupancy ``decode`` span when it takes a
        lane; per-chunk children hang off it at every harvest.  Idempotent:
        a deferred admission passes here twice (lane assignment in
        _finish_admission, then _install at first harvest) and must not
        leak a second, never-ended span."""
        if slot.trace is not None and slot.dspan is None:
            slot.trace.note(lane=lane)
            slot.dspan = slot.trace.span("decode").set(lane=lane)
            slot.t_chunk = time.time()

    def _close_decode_span(self, slot: _Slot, finish: str) -> None:
        if slot.dspan is not None:
            slot.dspan.set(finish=finish, tokens=len(slot.gens))
            slot.dspan.end()
            slot.dspan = None

    def _chunk(self, slot: _Slot, delta: dict, finish=None) -> dict:
        return {
            "id": slot.cid,
            "object": "chat.completion.chunk",
            "created": slot.created,
            "model": self.model_name,
            "choices": [{
                "index": 0, "delta": delta, "finish_reason": finish,
            }],
        }

    def _emit_stream(self, slot: _Slot, done: bool) -> str | None:
        """Push the newly decoded text increment to the stream sink.  Returns
        "stop" if a stop string was hit (caller finishes the slot)."""
        bts = self.tokenizer.decode_bytes(slot.gens)
        text = bts.decode("utf-8", errors="replace")
        cut = self._find_stop_str(text, slot.stops)
        hit = cut != -1
        if hit:
            text = text[:cut]
        if done or hit:             # flush: emit exactly up to the final text
            if len(text) > slot.n_emitted:
                slot.sink.put(
                    self._chunk(slot, {"content": text[slot.n_emitted:]}))
                slot.n_emitted = len(text)
        else:
            slot.held += slot.dec.decode(bts[slot.sent_bytes:])
            slot.sent_bytes = len(bts)
            hold = self._stop_prefix_holdback(slot.held, slot.stops)
            ready = slot.held[:len(slot.held) - hold]
            slot.held = slot.held[len(slot.held) - hold:]
            if ready:
                slot.sink.put(self._chunk(slot, {"content": ready}))
                slot.n_emitted += len(ready)
        return "stop" if hit else None

    def _slot_timings(self, slot: _Slot) -> dict:
        decode_s = time.time() - slot.t_admit - slot.ttft_s
        n = len(slot.gens)
        return {
            "ttft_s": slot.ttft_s, "decode_s": decode_s,
            "prompt_tokens": slot.n_prompt, "completion_tokens": n,
            "prefix_reused_tokens": slot.reused,
            # prompt bucket for the per-bucket TTFT series (obs/slo.py)
            "bucket": self._bucket_for(slot.n_prompt),
            # model label for the per-model metric series (multi-model)
            "model": self.model_name,
            "tokens_per_sec": (n - 1) / decode_s
            if n > 1 and decode_s > 0 else 0.0,
        }

    def _finish_slot(self, slot: _Slot, finish: str):
        slot.finished = True
        timings = self._slot_timings(slot)
        self._record_timings(timings)
        self._close_decode_span(slot, finish)
        if slot.sink is not None:
            hit = self._emit_stream(slot, done=True)
            final = self._chunk(slot, {}, finish=hit or finish)
            final["lfkt_timings"] = timings
            slot.sink.put(final)
            slot.sink.put(_STREAM_END)
            return
        text = self._decode_text(slot.gens)
        cut = self._find_stop_str(text, slot.stops)
        if cut != -1:
            text = text[:cut]
            finish = "stop"
        if slot.future.done():
            # resolved externally (watchdog fail_inflight / deadline) while
            # this chunk was in flight: the result has nowhere to go
            return
        slot.future.set_result({
            "lfkt_timings": timings,
            "id": slot.cid,
            "object": "chat.completion",
            "created": slot.created,
            "model": self.model_name,
            "choices": [{
                "index": 0,
                "message": {"role": "assistant", "content": text},
                "finish_reason": finish,
            }],
            "usage": {
                "prompt_tokens": slot.n_prompt,
                "completion_tokens": len(slot.gens),
                "total_tokens": slot.n_prompt + len(slot.gens),
            },
        })

    def _install(self, lane: int, slots: list, slot: _Slot) -> None:
        """Post-prefill bookkeeping for a freshly admitted slot: first-token
        stop/budget checks, stream open, and lane assignment."""
        stop_ids = self.tokenizer.stop_ids
        first = slot.first_token
        if slot.budget <= 0:
            self._finish_slot(slot, "length")
        elif first in stop_ids:
            self._finish_slot(slot, "stop")
        else:
            slot.gens.append(first)
            if len(slot.gens) >= slot.budget:
                self._finish_slot(slot, "length")
            elif (slot.sink is not None
                  and self._emit_stream(slot, done=False) == "stop"):
                self._finish_slot(slot, "stop")
            else:
                self._open_decode_span(lane, slot)
                slots[lane] = slot
        if slot.finished:
            # finished at install (or never occupied the lane): its prompt
            # KV is a valid reuse claim (the first sampled token's KV was
            # never fed/written)
            self._free_lane(lane, slot, slots)

    def _admit_step(self, slots: list, alone: bool = False) -> int | None:
        """One unit of admission progress: begin the next queued item (and
        dispatch its first prefill slice), or dispatch the in-flight
        admission's next slice — finishing it (sample + lane write) when the
        last slice lands.  Returns the number of prefill tokens dispatched
        (0 for bookkeeping-only progress), or None when there is nothing
        to do.  ``alone``: no lane holds a request and no chunk is in
        flight (the idle branch of :meth:`_loop`), so nobody waits behind
        the slice."""
        if self._adm is None:
            if not any(s is None for s in slots):
                return None                     # no free lane to admit into
            try:
                item = self._pending.get_nowait()
            except queue_mod.Empty:
                return None
            self._adm = self._begin_admission(item, alone)
            if self._adm is None:
                return 0                        # item resolved/skipped: progress
        adm = self._adm
        if adm["item"].abandoned.is_set():       # caller gave up mid-prefill
            if adm.get("span") is not None:
                adm["span"].set(abandoned=True).end()
            self._release_adm_lease(adm)
            self._resolve_skipped(adm["item"])
            self._adm = None
            return 0
        off_before = adm["offset"]
        adm["alone"] = alone
        try:
            self._dispatch_prefill_chunk(adm)
        except Exception as e:  # noqa: BLE001 — per-request isolation: a
            item = adm["item"]  # failed admission must not kill the scheduler
            self._adm = None
            self._release_adm_lease(adm)
            self._note_error(e)
            if adm.get("span") is not None:
                adm["span"].set(error=str(e)).end()
            if item.future is not None:
                item.future.set_exception(e)
            elif item.sink is not None:
                item.sink.put(e)
            return 0
        # stop at the slice containing the last REAL token: pure-padding
        # slices would only write cache garbage decode overwrites anyway,
        # while costing one scheduler iteration of TTFT each under load
        if adm["offset"] >= adm["n_prompt"]:
            self._adm = None
            lane = next(i for i, s in enumerate(slots) if s is None)
            self._finish_admission(adm, lane, slots)
        return adm["offset"] - off_before

    def _admit_round(self, slots: list) -> bool:
        """Admissions for ONE scheduler wave: admission progress — complete
        short admissions AND successive slices of one long prompt — is
        taken until the per-wave prefill-token budget runs out or the
        lanes/queue are exhausted.  At most one admission is ever
        mid-prompt, so prefill slices of different requests never
        interleave on the device queue and the single scratch cache stays
        safe: a completed admission's lane write is dispatched BEFORE the
        next admission's first slice.  With the admission controller ON a
        long prompt advances by up to ``budget`` tokens per wave (round 5
        advanced exactly one slice per wave regardless of budget, which
        put a 32k admission ~128 decode waves away from its first token);
        the controller shrinks the budget back toward one slice when that
        interleaving pressures live lanes' decode.  With the controller
        OFF (LFKT_ADM_CONTROLLER=0) a mid-prompt admission still yields
        after ONE slice — the static mode IS the pre-round-6 behavior,
        so it stays a valid A/B control arm (nothing then adapts the
        budget down if a big static number turned out to stall decode).
        Returns True if any progress was made."""
        budget = self._adm_budget
        progressed = False
        t0 = time.time()
        while budget > 0:
            spent = self._admit_step(slots)
            if spent is None:
                break
            progressed = True
            budget -= spent
            if self._adm is not None and self._adm_ctl is None:
                break   # static mode: long admission yields after one slice
        self._totals["admit_seconds"] += time.time() - t0
        return progressed

    def _note_mem_pressure(self) -> None:
        """Rising edge of the HBM-pressure signal: count it and stamp
        every in-flight trace with the headroom numbers — the budget cuts
        this wave starts are then self-explaining in the waterfall
        (tools/trace_report.py renders mem_pressure with byte counts)."""
        hr = _memledger.MEMLEDGER.last_headroom
        attrs = {}
        if hr is not None:
            attrs = {"headroom_bytes": hr[0], "limit_bytes": hr[1]}
        logger.warning(
            "HBM memory pressure: admission budget cut (headroom %s of "
            "%s bytes — docs/RUNBOOK.md 'Diagnosing HBM OOM')",
            attrs.get("headroom_bytes", "?"), attrs.get("limit_bytes", "?"))
        annotate_all_inflight("mem_pressure", **attrs)
        m = self.metrics_sink
        if m is not None:
            try:
                m.inc("mem_pressure_events_total")
            except Exception:  # noqa: BLE001 — telemetry must never fail serving
                pass

    def scheduler_stats(self) -> dict:
        """Point-in-time scheduler occupancy for ``/metrics`` (lanes_live,
        pending queue depth, whether an admission prefill is in flight,
        the live admission budget and its controller EMAs) and the
        wave's cumulative counters (``_zero_totals`` + lane-idle seconds)
        — the observability the lane model adds over the reference's
        single queue-depth number.  Written once per loop iteration;
        reads are a dict swap, no lock needed."""
        out = {"batch_size": self.batch_size, **self._stats}
        if self._lane_prefix or self._kv_paged:
            out.update(self._prefix_stats)
        return out

    def _harvest(self, pre: list, chunk: "np.ndarray", slots: list,
                 wave: int = 0, admit_slices: int = 0) -> None:
        """Fold one fetched decode chunk into its lanes' slots.

        ``pre`` is the lane snapshot taken when the chunk was DISPATCHED —
        with the pipelined loop that is one iteration ago, so a lane's slot
        may have finished (found in the previous chunk) while this chunk
        was already queued on the device; its rows are discarded
        (``slot.finished``).  An end the device saw too (a budget that ran
        out, a stop id: parallel/batched.py
        ``batched_generate_chunk_perlane_jit`` carries what each lane has
        left) cost no step but beside lanes still alive: the rows of a lane
        past its end and of the steps not run hold the pad -1, and a chunk
        none of whose lanes was alive ran no step (``chunks_empty``).  The
        rows with a token in them are the steps run (``steps_run``; the
        rest ``steps_skipped``), and the read counters count those.  The
        host's own test of stop id and budget stays and has to find a
        lane's end where its pads begin (``end_disagreements`` counts the
        lanes where it did not; the host's verdict stands, and a lane the
        device alone ended is finished, so that it cannot wait for steps
        that never come).  An end only the host sees (a stop STRING, a
        deadline, an abandoned caller) leaves the lane alive on the device
        for the one chunk already queued.  A lane admitted by the round that
        ran ahead of the chunk's dispatch is in ``pre`` with its first
        token still on the device (``pending_first``): token 1 and the
        chunk's rows are folded in together here.  Abandoned requests
        (client timeout / disconnect) free their lane here instead of
        decoding to budget: unlike the reference's serial engine
        (api.py:97-100, where a discarded generation delays nobody), an
        occupied lane would hold up waiting requests.

        ``wave`` / ``admit_slices`` (the chunk's dispatch number and the
        prefill slices queued on the device between the chunk before and
        it: those of its own pass's admission round, whose
        ``prefill_slice`` spans carry the same ``wave``) ride on each
        traced lane's ``decode_chunk`` span: a long chunk names its cause."""
        stop_ids = self.tokenizer.stop_ids
        now = time.time()
        # a step that ran has an alive lane's token (>= 0) in its row
        run = int(np.count_nonzero(chunk.max(axis=1) >= 0))
        tot = self._totals
        tot["steps_run"] += run
        tot["steps_skipped"] += len(chunk) - run
        tot["chunks_empty"] += run == 0
        self._count_lane_steps(run * self._note_ring_read(pre, run))
        for lane in range(len(pre)):
            slot = pre[lane]
            if slot is None or slot.finished:
                continue
            expired = slot.deadline is not None and now > slot.deadline
            if expired or slot.abandoned.is_set() or (
                    slot.abort is not None and slot.abort()) or (
                    slot.future is not None and slot.future.cancelled()):
                # checked BEFORE materializing a deferred first token: an
                # abandoned slot's stream would otherwise be opened (role
                # chunk nobody reads) at the cost of a blocking int() fetch.
                # Deadline expiry rides the same path: the lane frees at
                # this chunk boundary instead of decoding to budget.
                slot.finished = True
                exc = DeadlineExceeded(
                    "request deadline expired mid-generation") if expired \
                    else None
                self._end_prefill_span(slot)
                self._close_decode_span(
                    slot, "deadline" if expired else "abandoned")
                if slot.sink is not None:
                    slot.sink.put(exc if exc is not None else _STREAM_END)
                elif not slot.future.done():
                    # resolve so a caller still awaiting (e.g. via
                    # asyncio.wrap_future) unblocks as cancelled/timed out
                    if exc is not None:
                        slot.future.set_exception(exc)
                    else:
                        slot.future.set_exception(CancelledError())
                self._free_lane(lane, slot, slots)
                continue
            if slot.pending_first:
                # deferred admission: its sample was queued ahead of the
                # chunk just fetched, its lane's first — materialize the
                # first token now, then fold in this chunk's rows (its
                # tokens 2..n for this lane)
                self._materialize_first(lane, slot, slots)
                if slot.finished:
                    continue
            finish = None
            rows = chunk[:, lane].tolist()
            n = 0                   # rows of this lane folded in
            for t in rows:
                if t < 0:           # pad: the device ended the lane here
                    break
                n += 1
                if t in stop_ids:
                    finish = "stop"
                    break
                slot.gens.append(t)
                if len(slot.gens) >= slot.budget:
                    finish = "length"
                    break
            # one rule on both sides: the lane's next row, where the chunk
            # has one, is a pad exactly when the host ends the lane here
            if n < len(rows) and (rows[n] < 0) == (finish is None):
                tot["end_disagreements"] += 1
                logger.error(
                    "lane %d: the device and the harvest end the request "
                    "at different tokens (host: %s after %d of rows %s)",
                    lane, finish, n, rows)
                finish = finish or "length"
            if slot.dspan is not None:
                cspan = slot.dspan.child(
                    "decode_chunk", t0=slot.t_chunk).set(
                    tokens=len(slot.gens), wave=wave,
                    admit_slices=admit_slices, kind="chunk",
                    # the lane's own live rows at the chunk's end: what
                    # its attention needed of the read
                    **self.cache.decode_span_attrs(
                        slot.n_prompt + len(slot.gens)),
                    **self.cache.span_attrs(self.cfg))
                cspan.end(now)
                slot.t_chunk = now
                slot.trace.note(tokens=len(slot.gens))
            if finish is not None:
                self._finish_slot(slot, finish)
                self._free_lane(lane, slot, slots)
            elif slot.sink is not None:
                if self._emit_stream(slot, done=False) == "stop":
                    self._finish_slot(slot, "stop")
                    self._free_lane(lane, slot, slots)
        self._totals["harvest_seconds"] += time.time() - now

    def _note_ring_read(self, pre: list, n_steps: int) -> int:
        """Count one decode chunk's attention read against what it needed
        (``CacheKind.note_decode``): summed over the lanes whose rows are
        still wanted, under the bound of the lanes the chunk was dispatched
        as live (``pre``).  From the positions the host holds before the
        chunk's tokens are folded in (prompt + generated so far: the slot
        of the chunk's first step); nothing is fetched.  Returns the lanes
        whose rows were wanted (what ``layer_passes_total`` counts by)."""
        at = [None if s is None else s.n_prompt + max(len(s.gens) - 1, 0)
              for s in pre]
        wanted = [p for slot, p in zip(pre, at)
                  if slot is not None and not slot.finished]
        self.cache.note_decode(self.cache_counts, self.cfg, wanted, n_steps,
                               [p for p in at if p is not None])
        self.cache.note_lanes(self.cache_counts, self.cfg, len(pre), n_steps)
        return len(wanted)

    def _loop(self):
        B = self.batch_size
        slots: list[_Slot | None] = [None] * B
        pending = None   # (lane snapshot, un-fetched device tokens)
        t_prev_wave = time.time()   # decode-wave clock (controller signals)
        try:
            while not self._stop:
                if not any(s is not None for s in slots) and pending is None:
                    # nothing decoding: admission prefills stall nobody;
                    # drive the machine at full speed until a lane fills
                    progressed = False
                    while not any(s is not None for s in slots):
                        if self._admit_step(slots, alone=True) is None:
                            break
                        progressed = True
                    if not any(s is not None for s in slots):
                        if not progressed:
                            self._wake.wait(timeout=0.05)
                            self._wake.clear()
                        continue
                    t_prev_wave = time.time()   # lanes just filled: new wave

                # ---- one pass: admission, then one decode chunk for every
                # live lane, then the PREVIOUS chunk's fetch + harvest.
                # Dispatch is async AND pipelined one chunk deep: this
                # pass's chunk queues on the device BEFORE the previous
                # chunk's tokens are fetched, so the host round-trip
                # (dispatch latency) overlaps device compute instead of
                # serializing with it.  Cost of the pipeline: the host
                # learns of a request's end one chunk late, when the next
                # chunk is queued with its lane in it.  An end the device
                # can see (the budget ran out, a stop id: the chunk program
                # carries what each lane has ``left`` to decode) costs nothing
                # where no other lane is alive: the chunk stops at the last
                # token, the queued one runs no step, and its fetch below
                # returns at once, so the next request does not wait in
                # ``_pending`` behind a chunk for nobody.  Beside lanes
                # still alive the ended lane steps on with them, its rows
                # pads.  An end only the host sees (a stop STRING, a
                # deadline, an abandoned caller) keeps the old price: the
                # lane decodes one extra chunk before it is freed, its
                # rows discarded.
                # Admission goes AHEAD of the chunk: while the device still
                # runs the previous chunk, the host tokenizes and queues the
                # round's slices, first-token samples and lane writes, and
                # the chunk dispatched right after carries every lane the
                # round filled.  Behind the chunk, each admission would wait
                # one whole wave more for its first token, behind a chunk
                # that left a millisecond earlier without its lane.
                tot = self._totals
                self._wave_at_pass = tot["chunks_dispatched"]
                # lfkt.wave in a capture: this pass's work, from the
                # admission round to the previous chunk's harvest
                # (lanes_live: lanes holding a request as the pass begins)
                with phase("wave", wave=tot["chunks_dispatched"] + 1,
                           lanes_live=sum(s is not None for s in slots)):
                    # ---- admission prefills, up to the per-pass token
                    # budget (several complete short admissions, or slices
                    # of a long one); they queue behind the chunk in flight
                    # and ahead of the one dispatched below.  Chunked
                    # prefill bounds what a pass puts in front of the live
                    # lanes' next chunk to the budget even for full-bucket
                    # prompts.
                    self._admit_round(slots)

                    if any(s is not None for s in slots):
                        # lanes live in THIS chunk, the round's admissions
                        # among them (pending_first: their first token is
                        # read at this chunk's harvest, one pass on); pre[]
                        # snapshots who gets the chunk's rows
                        pre = list(slots)
                        FAULTS.fire("decode_step")
                        tot["chunks_dispatched"] += 1
                        wave = tot["chunks_dispatched"]
                        with phase("dispatch_chunk", wave=wave,
                                   lanes_live=sum(s is not None for s in pre)):
                            # the step is told which lanes hold a
                            # request: its attention reads the ring up to
                            # the largest LIVE position (a freed lane's
                            # walks on), and a routed block's other rows
                            # reach no expert
                            live = np.array([s is not None for s in pre])
                            self._bstate, self._lane_left, out = \
                                batched_generate_chunk_perlane_jit(
                                    self.params, self.cfg, self._bstate,
                                    self._lane_st, self._lane_left,
                                    n_steps=self.decode_chunk,
                                    top_k=self._max_top_k, live=live,
                                    stop_ids=self._stop_ids)
                            toks = self._take_expert_stats(out)
                        # (lanes, tokens, dispatch number, slices queued on
                        # the device between the chunk before and this one:
                        # this pass's round, or an idle engine's admissions)
                        dispatched = (pre, toks, wave, self._slices_queued)
                        self._slices_queued = 0
                    else:
                        dispatched = None

                    # ---- harvest the PREVIOUS chunk (fetch blocks only until
                    # that chunk is done; the one dispatched above keeps the
                    # device busy meanwhile).  The fetch's blocking time IS the
                    # decode-pressure signal: a long wait means the device was
                    # still decoding when the host came back (admission slices
                    # queued this wave delay THIS wave's chunk, surfacing here
                    # one wave later); a near-zero wait means the device sat
                    # idle — the admission controller converts that slack into
                    # budget.
                    fetch_wait = 0.0
                    if pending is not None:
                        t_f = time.time()
                        with phase("fetch", wave=pending[2]):
                            chunk_np = np.asarray(pending[1])
                        fetch_wait = time.time() - t_f
                        with phase("harvest", wave=pending[2]):
                            self._harvest(pending[0], chunk_np, slots,
                                          wave=pending[2],
                                          admit_slices=pending[3])
                now = time.time()
                wave_s = max(now - t_prev_wave, 0.0)
                t_prev_wave = now
                mem_hot = False
                if dispatched is not None:
                    live_wave = sum(s is not None for s in dispatched[0])
                    # idle lane-seconds: free lanes while others decode are
                    # lost throughput (the admission controller's raw signal)
                    self._lane_idle_s += (B - live_wave) * wave_s
                    tot["waves"] += 1
                    tot["wave_seconds"] += wave_s
                    tot["lane_live_seconds"] += live_wave * wave_s
                    tot["fetch_wait_seconds"] += fetch_wait
                    if self._adm_ctl is not None:
                        # HBM headroom joins the wave signals (lfkt-mem):
                        # disarmed/stat-less, pressure() is one attribute
                        # read returning False — nothing on this path
                        # allocates (poisoned-ledger pin)
                        mem_hot = _memledger.MEMLEDGER.pressure()
                        self._adm_budget = self._adm_ctl.observe_wave(
                            live_wave, fetch_wait, wave_s,
                            mem_pressure=mem_hot)
                        if mem_hot and not self._mem_hot_prev:
                            self._note_mem_pressure()
                        self._mem_hot_prev = mem_hot
                pending = dispatched
                stats = {
                    "lanes_live": sum(s is not None for s in slots),
                    "pending": self._pending.qsize(),
                    "admission_inflight": int(self._adm is not None),
                    "adm_budget_tokens": self._adm_budget,
                    "lane_idle_seconds": round(self._lane_idle_s, 3),
                    "mem_pressure": int(mem_hot),
                    **tot,
                }
                if self._adm_ctl is not None:
                    stats.update(self._adm_ctl.stats())
                self._stats = stats
                # watchdog pulse: a beat per loop iteration, busy = queued +
                # occupied work.  A loop wedged inside a device call stops
                # beating with busy > 0 — the stall signature.
                self.heartbeat.beat()
                self.heartbeat.set_busy(
                    self._stats["lanes_live"] + self._stats["pending"]
                    + self._stats["admission_inflight"])
        except BaseException as e:  # noqa: BLE001 — fail all, loudly
            self._loop_error = e
            self.heartbeat.record_error(e)
            logger.exception("scheduler loop died")
        finally:
            # graceful stop AND crash both resolve every outstanding request:
            # a caller blocked in Future.result() or sink.get() must not hang
            err = self._loop_error or RuntimeError("engine has been shut down")
            if self._adm is not None:       # admission mid-prefill: resolve it
                item = self._adm["item"]
                self._release_adm_lease(self._adm)
                self._adm = None
                if item.sink is not None:
                    item.sink.put(err if self._loop_error else _STREAM_END)
                elif not item.future.done():
                    item.future.set_exception(err)
            for s in slots:
                if s is None:
                    continue
                if s.sink is not None:
                    s.sink.put(err if self._loop_error else _STREAM_END)
                elif not s.future.done():
                    s.future.set_exception(err)
            while True:
                try:
                    item = self._pending.get_nowait()
                except queue_mod.Empty:
                    break
                if item.sink is not None:
                    item.sink.put(err if self._loop_error else _STREAM_END)
                elif not item.future.done() and not item.future.cancel():
                    item.future.set_exception(err)
            # zero the occupancy gauges LAST (after the drain): a dead loop
            # must not keep reporting pre-crash lanes_live/pending/
            # admission_inflight to /metrics, masking the outage from
            # dashboards built on them
            self._stats = {"lanes_live": 0, "pending": 0,
                           "admission_inflight": 0}
            self.heartbeat.set_busy(0)
