"""The lane engine's device programs: B sequences stepped as one.

``vmap`` lifts the single-sequence model (models/llama.py) over a batch
axis, so one jit'd program serves B concurrent sequences on the one device
-- the TPU-native replacement for the reference's "4 independent single-GPU
pods" data parallelism (SURVEY.md §2A).  The state lives where the serial
engine's ring lives: plainly on the process's device, no mesh
(engine/continuous.py allocates it).

Every entry point here donates its ``state`` pytree: callers own the
rebind-from-result contract, machine-checked at every call site by
lfkt-lint DON001-002 (the donor registry is scraped from these
``donate_argnames`` declarations -- docs/LINT.md).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..models.cache import cache_of
from ..models.config import ModelConfig
from ..models.generate import chunk_out
from ..models.llama import (  # noqa: F401  (``live_bound``: the tests' name)
    forward, has_step_stats, init_cache, lanes_step_stats, live_bound,
    step_stats_zeros)
from ..obs.devtime import timed_jit
from ..sampling.sample import PENALTY_WINDOW, sample_chain


def init_batched_state(cfg: ModelConfig, batch: int, seed: int = 0) -> dict:
    """Batched generation state: every per-sequence leaf grows a leading
    ``batch`` dim.  The cache leaves' token axis therefore sits at axis 3
    — the paged KV pool's lane-store op (parallel/kvpool.py
    ``_store_lane_pages_jit``) indexes the batch dim away and slices that
    axis directly out of this layout, so a freed lane's conversation is
    committed to the pool without ever materializing a lane-ring copy."""
    cache = init_cache(cfg)
    return {
        "cache": jax.tree.map(lambda x: jnp.broadcast_to(x, (batch,) + x.shape), cache),
        "pos": jnp.zeros(batch, jnp.int32),
        "token": jnp.zeros(batch, jnp.int32),
        "window": jnp.full((batch, PENALTY_WINDOW), -1, jnp.int32),
        "wpos": jnp.zeros(batch, jnp.int32),
        "key": jax.random.split(jax.random.PRNGKey(seed), batch),
    }


def step_bound(cfg: ModelConfig, pos: jax.Array, live=None):
    """What ``forward`` takes as a lane step's ``kv_bound``, by the cache
    kind and the read that serves it (``CacheKind.step_bound``;
    models/llama.py ``ring_step_bound`` for a ring, models/eva.py and
    models/sala.py ``live_bounds`` for theirs)."""
    return cache_of(cfg).step_bound(cfg, pos, live)


def init_lane_left(batch: int) -> jax.Array:
    """What the lane engine's chunk program knows of its lanes' ENDS, kept
    beside the batched state: per lane the tokens it may still decode,
    (B,) int32.  0: the
    lane has ended (its budget ran out or it sampled a stop id) or holds
    no request, as every lane at the start; the scheduler's lane write
    (engine/continuous.py ``_write_lane``) brings a lane to life with its
    request's budget."""
    return jnp.zeros(batch, jnp.int32)


def left_after(token: jax.Array, left: jax.Array, stop_ids: tuple) -> jax.Array:
    """What is left to a lane that has ``left`` tokens of budget after
    sampling ``token``: nothing once the token is one of ``stop_ids`` (a
    small static tuple).  The rule of the scheduler's harvest (``t in
    stop_ids`` / ``len(gens) >= budget``), on the device."""
    stop = jnp.zeros(jnp.shape(token), bool)
    for s in stop_ids:
        stop |= token == s
    return jnp.where(stop, 0, left)


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "n_steps", "top_k", "stop_ids"),
    donate_argnames=("state",),
)
def batched_generate_chunk_perlane_jit(params, cfg: ModelConfig, state: dict,
                                       lane_st: dict, left: jax.Array,
                                       n_steps: int, top_k: int = 40,
                                       live=None, stop_ids: tuple = ()):
    """B sequences × up to ``n_steps`` decode+sample steps on device with
    **per-lane** sampling knobs (``lane_st`` leaves have a leading B dim) —
    the continuous scheduler admits requests with different
    temperatures/penalties into neighboring lanes.  (top_k stays a shared
    static: ``lax.top_k`` needs a
    static k; see ContinuousEngine.submit.)  ``live`` (B,) bool: the lanes
    that hold a request (None: all).  A step's attention reads the ring as
    :func:`step_bound` says (a lane that holds none reads nothing, or up
    to the live lanes' bound); of a routed block the others' rows also
    reach no expert, so a step reads what its live lanes picked.

    The chunk steps only while some lane has something left to decode:
    ``left`` (:func:`init_lane_left`) carries each lane's end across
    chunks, a lane is ALIVE while it is live and has ``left`` > 0, and
    the loop runs while ``i < n_steps`` and any lane is alive.  Each step
    takes one off the ``left`` of the lanes it stepped alive, all of it
    where the lane sampled one of ``stop_ids``.  The host learns of a
    lane's end one chunk late (the loop of engine/continuous.py is
    pipelined one chunk deep); the chunk it has already dispatched for
    that lane then runs NO step where no other lane is alive, and
    returns the state as it got it.

    Returns (state, left, tokens (n_steps, B)) (for a routed block the
    tokens with the counters summed over the steps RUN:
    models/generate.py ``chunk_out``).  A row of a lane that was not
    alive in its step, and every row of a step not run, holds the pad -1:
    the rows with a token in them are the steps run, read by the host
    from the one array it fetches anyway.  The tokens of a lane while it
    is alive are those of the serial chunk's ``scan`` (models/generate.py):
    the same sampling chain on the same keys."""

    def alive_of(left):
        return left > 0 if live is None else live & (left > 0)

    def one_step(loop):
        i, carry, left, toks, *rows = loop
        bound = step_bound(cfg, carry["pos"], live)

        def single(token, pos, cache, window, wpos, key, st, live):
            logits, cache, *stats = forward(
                params, cfg, token[None], pos, cache, live=live,
                with_stats=has_step_stats(cfg), kv_bound=bound)
            key, sub = jax.random.split(key)
            tok = sample_chain(logits, window, sub, st, top_k=top_k)
            window = window.at[wpos % PENALTY_WINDOW].set(tok)
            return (tok, pos + 1, cache, window, wpos + 1, key, *stats)

        tok, pos, cache, window, wpos, key, *stats = jax.vmap(single)(
            carry["token"], carry["pos"], carry["cache"],
            carry["window"], carry["wpos"], carry["key"], lane_st, live,
        )
        new_carry = {"cache": cache, "pos": pos, "token": tok,
                     "window": window, "wpos": wpos, "key": key}
        alive = alive_of(left)
        # the counters are of the step, the same in every lane (a looped
        # stack's exit masses: of the lanes alive in it)
        return (i + 1, new_carry,
                jnp.where(alive, left_after(tok, left - 1, stop_ids), left),
                toks.at[i].set(jnp.where(alive, tok, -1)),
                *(r.at[i].set(lanes_step_stats(cfg, s, alive))
                  for r, s in zip(rows, stats)))

    def more(loop):
        return (loop[0] < n_steps) & jnp.any(alive_of(loop[2]))

    # a while_loop, which a scan is already (the cache rides its carry),
    # and not a cond around each step of a scan: a conditional that
    # returns the cache from two branches is where a copy would appear
    # (a step not run leaves its row of tokens pads, of counters zeros)
    rows = [step_stats_zeros(cfg, n_steps)] if has_step_stats(cfg) else []
    _, state, left, toks, *rows = jax.lax.while_loop(
        more, one_step,
        (jnp.int32(0), state, left,
         jnp.full((n_steps, left.shape[0]), -1, jnp.int32), *rows))
    return state, left, chunk_out(toks, *rows)


batched_generate_chunk_perlane_jit = timed_jit(
    "lane_decode_chunk", batched_generate_chunk_perlane_jit,
    site="parallel.batched", leaf=1)      # done stamp on ``left``
