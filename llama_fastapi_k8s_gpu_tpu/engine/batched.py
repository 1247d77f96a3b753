"""Mesh-batched engine: B concurrent chat completions in one program.

The reference scales concurrent load with 4 shared-nothing single-GPU pods
behind a k8s Service (reference helm/values.yaml:17; SURVEY.md §2A
"Parallelism strategies") — each pod still generates strictly serially
(Semaphore(1), reference api.py:114).  The TPU-native equivalent for the
"concurrent /response load on v5e-4" config (BASELINE.json) batches
requests *inside* one process instead: requests coalesce into a batch of B
sequences, vmap-lifted over the model (parallel/batched.py) and laid out on
a dp×tp ``jax.sharding.Mesh`` — the batch dim shards over ``dp`` chips, the
model over ``tp``, XLA inserts the ICI collectives.

Decode efficiency is the point: a single-sequence decode matvec cannot
saturate HBM/MXU; batching B requests multiplies decode throughput at
nearly constant step latency (weights are read once per step regardless of
B).  FIFO admission order is preserved by the server's consumer, which
drains up to B queued requests per cycle (server/app.py).
"""

from __future__ import annotations

import functools
import logging
import time
import uuid
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..parallel.batched import (
    batched_generate_chunk_jit,
    batched_prefill_jit,
    init_batched_state,
)
from ..obs.devtime import timed_jit
from ..parallel.mesh import make_mesh, shard_params, state_shardings
from ..sampling.sample import (
    PENALTY_WINDOW,
    SamplingParams,
    sample_chain,
    sampling_tensors,
    seed_window,
)
from ..obs.memledger import register_component
from ..parallel.batched import state_nbytes
from ..utils.faults import FAULTS
from .engine import Engine

logger = logging.getLogger(__name__)


def _ledger_lane_bytes(eng: "MeshEngine") -> int:
    """Memory-ledger provider: the batched lane state's resident bytes
    (snapshot-time metadata read — obs/memledger.py)."""
    return state_nbytes(getattr(eng, "_bstate", None))


@functools.partial(jax.jit, static_argnames=("top_k",))
def _batched_first_sample(logits, windows, wposes, keys, st, top_k=40):
    """Sample the first token of every sequence from prefill logits."""

    def single(lg, window, wpos, key):
        key, sub = jax.random.split(key)
        tok = sample_chain(lg, window, sub, st, top_k=top_k)
        window = window.at[wpos % PENALTY_WINDOW].set(tok)
        return tok, window, wpos + 1, key

    return jax.vmap(single)(logits, windows, wposes, keys)


_batched_first_sample = timed_jit("batched_first_sample",
                                  _batched_first_sample,
                                  site="engine.batched")


def _refuse_fused_on_a_tpu_mesh(params: dict, dp: int, tp: int) -> None:
    """Found on four v5e chips (PR 22): a program that spans devices cannot
    hold a fused K-quant matmul.  The kernels reach the partitioner through
    ``custom_partitioning``, whose callback jax 0.9.0 never registers with
    the TPU plugin (``make_tpu_client`` skips the plugin callbacks), so XLA
    meets the raw call and stops with "Custom emitter for
    CustomSPMDPartitioning not found" — at the warm-up compile, after the
    whole load.  Say so here instead.  The CPU backend partitions them fine
    (tests/test_parallel.py), which is how this went unseen."""
    from ..models.params import flat_layers
    from ..parallel.mesh import _fused_key

    if dp * tp == 1 or jax.default_backend() != "tpu":
        return
    fused = sorted(name for name, leaf in flat_layers(params["layers"])
                   if isinstance(leaf, dict) and _fused_key(leaf))
    if fused:
        raise RuntimeError(
            f"a {dp}x{tp} TPU mesh cannot serve fused K-quant weights "
            f"({', '.join(fused)}): jax {jax.__version__} does not register "
            "custom_partitioning with the TPU plugin, so the fused matmuls "
            "do not compile in a program that spans devices.  Serve "
            "LFKT_WEIGHT_FORMAT=int8 (or bf16) on a mesh, or one chip per "
            "process with LFKT_MESH_TP=1")


class MeshEngine(Engine):
    """An :class:`Engine` that serves batches of requests over a device mesh.

    ``create_chat_completion`` still works (batch of one).  The batch entry
    point is :meth:`create_chat_completions`, which the server's consumer
    feeds with up-to-``batch_size`` queued requests at a time.
    """

    # the batched state joins the serial ring under the generation mutex
    # (lfkt-lint LOCK001; docs/RUNBOOK.md "Lock discipline annotations")
    _GUARDED_BY = {"_bstate": "_lock"}

    #: asked of the cache kind (Engine._refuse_unsupported) beside a mesh axis:
    #: prompts enter as one vmapped pass over a whole bucket, not in slices
    _asks = {"cycle": True}

    def __init__(self, model_path: str | None, *, dp: int | None = None,
                 tp: int = 1, batch_size: int | None = None, **kw):
        avail = max(1, len(jax.devices()) // tp)
        if dp is None:
            if batch_size is None:
                dp = avail
            else:  # largest device count the batch shards evenly over
                dp = max(d for d in range(1, avail + 1) if batch_size % d == 0)
        if dp * tp > 1:
            # The flash prefill kernel is a bare pallas_call with no
            # partitioning rule: JAX refuses to lower it into a program
            # that spans devices ("Mosaic kernels cannot be automatically
            # partitioned").  A mesh of more than one device therefore
            # serves the XLA score-matrix attention; asking for the kernel
            # by name is refused here instead of at the warm-up compile.
            if kw.get("attn_impl", "auto") == "pallas":
                raise ValueError(
                    f"attn_impl='pallas' cannot serve a {dp}x{tp} device "
                    "mesh: the flash kernel has no partitioning rule; use "
                    "attn_impl='auto' (resolves to 'xla' on a mesh) or 'xla'")
            kw["attn_impl"] = "xla"
        if tp > 1:
            self._asks = {**self._asks, "tp": tp}
        super().__init__(model_path, **kw)
        with self.startup.phase("lanes_alloc"):
            self.mesh = make_mesh(dp=dp, tp=tp)
            self.batch_size = batch_size or dp
            if self.batch_size % dp:
                raise ValueError(
                    f"batch_size {self.batch_size} must be divisible by "
                    f"dp={dp}")
            _refuse_fused_on_a_tpu_mesh(self.params, dp, tp)
            self.params = shard_params(self.params, self.mesh)
            state = init_batched_state(self.cfg, self.batch_size)
            self._bstate = jax.device_put(
                state, state_shardings(self.cfg, self.mesh, batched=True))
        # lfkt-mem: the shared lane state is this engine family's biggest
        # serving allocation — attribute it (provider reads the live
        # reference, so watchdog re-inits stay correct automatically)
        register_component("kv_lanes", self, _ledger_lane_bytes)

    def _recover_locked(self) -> None:  # lfkt: holds[_lock]
        """Watchdog recovery: a crash mid-cycle may have poisoned the donated
        batched state, so rebuild it (sharded) along with the serial ring."""
        super()._recover_locked()
        state = init_batched_state(self.cfg, self.batch_size)
        self._bstate = jax.device_put(
            state, state_shardings(self.cfg, self.mesh, batched=True))

    # ------------------------------------------------------------------
    def _warmup_steps(self, ph) -> str:
        """Compile every shape a request can hit: the batched prefill for
        every bucket + the batched decode chunk, AND the serial path (the
        server's /response/stream uses Engine's streaming generation)."""
        msgs = [{"role": "user", "content": "hi"}]
        # TWO full decode chunks: chunk 2's donated state carries jit-chosen
        # shardings, a distinct compile the one-chunk warmup used to leave
        # for the first real request (devtime pin, tests/test_perf_pins.py)
        with ph.child("batch_round"):
            self.create_chat_completions([msgs] * self.batch_size,
                                         max_tokens=2 * self.decode_chunk + 1,
                                         temperature=0.0)
        with self._lock:   # uncontended at warmup; keeps the _bstate
            #                write invariant (writes only under _lock)
            with ph.child("batch_buckets",
                          n_buckets=len(self.prefill_buckets) - 1):
                for bucket in self.prefill_buckets[1:]:
                    tokens = jnp.zeros((self.batch_size, bucket), jnp.int32)
                    lengths = jnp.ones((self.batch_size,), jnp.int32)
                    _, caches = batched_prefill_jit(
                        self.params, self.cfg, tokens, lengths,
                        self._bstate["cache"])
                    self._bstate["cache"] = caches
        # serial buckets + decode chunk (streaming path)
        serial = super()._warmup_steps(ph)
        return (f"dp={self.mesh.shape['dp']} tp={self.mesh.shape['tp']} "
                f"batch={self.batch_size}, {serial}")

    # ------------------------------------------------------------------
    def create_chat_completions(  # lfkt: blocks-under[_lock] -- the mesh engine serializes whole batches under its lock by design: drill sleeps and incident capture ride the generation path
        self,
        batch_messages: Sequence[Sequence[dict]],
        *,
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
        deadlines: Sequence[float | None] | None = None,
        aborts: Sequence | None = None,
        traces: Sequence | None = None,
    ) -> list[dict]:
        """Generate up to ``batch_size`` completions in one batched program.
        Returns one OpenAI-shaped dict per input, in order.

        ``deadlines``/``aborts`` are per-entry: entry ``b`` stops
        accumulating tokens (``finish_reason="deadline"``) within one
        decode chunk of its deadline passing or its abort callback firing
        — its lane keeps stepping on-device (vmap advances every lane) but
        the cycle ends as soon as every live entry is done, so one
        timed-out request no longer pins the whole batch to its budget."""
        if not batch_messages:
            return []
        if len(batch_messages) > self.batch_size:
            raise ValueError(
                f"batch of {len(batch_messages)} exceeds batch_size {self.batch_size}")
        if stop is None:
            stop = []
        elif isinstance(stop, str):
            stop = [stop]
        sp = SamplingParams(
            temperature=temperature, top_p=top_p, top_k=top_k, min_p=min_p,
            frequency_penalty=frequency_penalty, presence_penalty=presence_penalty,
            repeat_penalty=repeat_penalty,
        )
        with self._lock:
            self.heartbeat.enter()
            try:
                return self._generate_batch(list(batch_messages), sp,
                                            max_tokens, stop, seed,
                                            deadlines=deadlines, aborts=aborts,
                                            traces=traces)
            except Exception as e:  # noqa: BLE001 — burst detection, re-raised
                self._note_error(e)
                raise
            finally:
                self.heartbeat.leave()

    # ------------------------------------------------------------------
    @staticmethod
    def _lane_expired(b: int, deadlines, aborts, now: float) -> bool:
        if aborts is not None and b < len(aborts) and aborts[b] is not None \
                and aborts[b]():
            return True
        return (deadlines is not None and b < len(deadlines)
                and deadlines[b] is not None and now > deadlines[b])

    def _generate_batch(self, batch_messages, sp, max_tokens, stops, seed,
                        deadlines=None, aborts=None,
                        traces=None):  # lfkt: holds[_lock]
        B = self.batch_size
        n_real = len(batch_messages)
        # per-entry engine spans: entry b's trace gets its own span tree
        # even though the cycle's device work is shared (the shared-timing
        # caveat is stamped as an attr); None everywhere when untraced
        espans: list = [None] * B
        if traces is not None:
            for b, tr in enumerate(traces[:B]):
                if tr is not None:
                    tr.note(lane=b, tokens=0, **self._trace_attrs())
                    espans[b] = tr.span("engine").set(
                        lane=b, shared_cycle=True, **self._trace_attrs())
        dummy = [self.tokenizer.bos_id or 0]
        # An oversized prompt is that request's own input error — it must not
        # fail its batch neighbors (reference semantics are per-request,
        # api.py:76-78).  Replace it with a dummy slot and report per-entry.
        ids_list, errors = [], {}
        for i, m in enumerate(batch_messages):
            ids = self.tokenize_messages(m)
            if len(ids) >= self.cfg.n_ctx:
                errors[i] = (f"Requested tokens ({len(ids)}) exceed context "
                             f"window of {self.cfg.n_ctx}")
                ids = dummy
            ids_list.append(ids)
        # pad the batch with a minimal dummy prompt (static batch shape)
        ids_list += [dummy] * (B - n_real)
        if seed is None:
            seed = self._next_seed()
        else:
            self._next_seed()
        with self._id_lock:  # advance past the whole batch
            self._requests += n_real - 1

        bucket = self._bucket_for(max(len(i) for i in ids_list))
        lengths = jnp.asarray([len(i) for i in ids_list], jnp.int32)
        tokens = jnp.asarray(
            [i + [0] * (bucket - len(i)) for i in ids_list], jnp.int32)
        st = sampling_tensors(sp)

        t0 = time.time()
        state = self._bstate
        logits, caches = batched_prefill_jit(
            self.params, self.cfg, tokens, lengths, state["cache"])
        windows, wposes = zip(*(seed_window(i) for i in ids_list))
        keys = jax.random.split(jax.random.PRNGKey(seed), B)
        toks, windows, wposes, keys = _batched_first_sample(
            logits, jnp.stack(windows), jnp.stack(wposes), keys, st,
            top_k=sp.top_k)
        state = {
            "cache": caches, "pos": lengths, "token": toks,
            "window": windows, "wpos": wposes, "key": keys,
        }
        first = np.asarray(toks).tolist()  # host sync: TTFT for the batch
        ttft = time.time() - t0
        for b, es in enumerate(espans):
            if es is not None:
                es.child("prefill", t0=t0).set(
                    n_prompt=len(ids_list[b]), bucket=bucket,
                    ttft_s=round(ttft, 6)).end()

        stop_ids = self.tokenizer.stop_ids
        # Per-lane budget AND per-lane cache capacity: lane b may store
        # n_ctx-1-len_b new tokens regardless of its neighbors' prompt
        # lengths (a global clamp would let the longest prompt truncate
        # everyone).  Lanes that exhaust their own capacity keep decoding
        # on-device (vmap advances every lane) — their writes clamp to the
        # last slot of their own cache and their tokens are discarded here;
        # the next batch re-prefills, so the garbage is never read.
        budgets = [
            min(self._token_budget(max_tokens, len(i)),
                max(0, self.cfg.n_ctx - 1 - len(i)))
            for i in ids_list
        ]
        gens: list[list[int]] = []
        done = [False] * B
        finishes = ["length"] * B                     # same default as Engine._run
        for b, tok in enumerate(first):
            if b >= n_real or b in errors or budgets[b] <= 0:
                gens.append([])
                done[b] = True
            elif tok in stop_ids:
                gens.append([])
                done[b] = True
                finishes[b] = "stop"
            else:
                gens.append([tok])

        while not all(done):
            # deadline/abort propagation: expired entries stop accumulating
            # (and can end the cycle) within one decode chunk
            now = time.time()
            for b in range(B):
                if not done[b] and self._lane_expired(b, deadlines, aborts, now):
                    done[b] = True
                    finishes[b] = "deadline"
            if all(done):
                break
            self.heartbeat.beat()
            FAULTS.fire("decode_step")
            remaining = max(budgets[b] - len(gens[b]) for b in range(B) if not done[b])
            n_steps = min(self.decode_chunk, remaining)
            if n_steps <= 0:
                break                                 # capacity: "length"
            t_chunk = time.time()
            state, out = batched_generate_chunk_jit(
                self.params, self.cfg, state, st,
                n_steps=n_steps, top_k=sp.top_k)
            toks = self._take_expert_stats(out)
            chunk = np.asarray(toks)                  # (n_steps, B) host sync
            for b in range(B):
                if done[b]:
                    continue
                for t in chunk[:, b].tolist():
                    if t in stop_ids:
                        done[b] = True
                        finishes[b] = "stop"
                        break
                    if len(gens[b]) >= budgets[b]:
                        done[b] = True
                        break
                    gens[b].append(t)
                if len(gens[b]) >= budgets[b]:
                    done[b] = True
                if espans[b] is not None:
                    espans[b].child("decode_chunk", t0=t_chunk).set(
                        tokens=len(gens[b])).end()
                    traces[b].note(tokens=len(gens[b]))

        self._bstate = state                          # reuse buffers
        for b, es in enumerate(espans):
            if es is not None:
                es.set(finish=finishes[b], completion_tokens=len(gens[b]))
                es.end()
        decode_s = time.time() - t0 - ttft
        total_new = sum(len(g) for g in gens[:n_real])
        timings = {
            "ttft_s": ttft, "decode_s": decode_s,
            "prompt_tokens": int(sum(len(i) for i in ids_list[:n_real])),
            # shared cycle: every lane prefilled in one bucket program
            "bucket": bucket,
            # model label for the per-model metric series (multi-model)
            "model": self.model_name,
            "completion_tokens": total_new,
            "tokens_per_sec": (total_new - n_real) / decode_s
            if decode_s > 0 and total_new > n_real else 0.0,
        }
        self._record_timings(timings)

        out = []
        for b in range(n_real):
            if b in errors:
                out.append({"error": {"message": errors[b],
                                      "type": "invalid_request_error"}})
                continue
            text = self._decode_text(gens[b])
            cut = self._find_stop_str(text, stops)
            finish = finishes[b]
            if cut != -1:
                text = text[:cut]
                finish = "stop"
            out.append({
                "lfkt_timings": timings,  # batch-level (one shared cycle)
                "id": f"chatcmpl-{uuid.uuid4().hex}",
                "object": "chat.completion",
                "created": int(time.time()),
                "model": self.model_name,
                "choices": [{
                    "index": 0,
                    "message": {"role": "assistant", "content": text},
                    "finish_reason": finish,
                }],
                "usage": {
                    "prompt_tokens": len(ids_list[b]),
                    "completion_tokens": len(gens[b]),
                    "total_tokens": len(ids_list[b]) + len(gens[b]),
                },
            })
        return out
