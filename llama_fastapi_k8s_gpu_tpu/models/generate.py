"""On-device autoregressive generation.

The hot loop the reference runs inside llama.cpp's C++ decode (SURVEY.md §3.2
"THE hot loop") becomes a ``lax.scan`` over decode steps: embed → layers →
logits → sampling chain → next token, entirely on device.  The host only sees
a chunk of ``n_steps`` sampled tokens per dispatch (checks stop conditions,
streams text out), so per-token host↔device round-trips — the classic TPU
decode-latency killer — are amortized away.  The KV cache and generation
state are donated across chunks, so decode is allocation-free at steady
state.  The ``donate_argnames`` declarations below are the source of
truth for lfkt-lint's DON donor registry: a caller that reads the
donated cache/state after dispatch (or keeps a stale alias) fails
tier-1 statically (DON001-002, docs/LINT.md).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..obs.devtime import timed_jit
from ..sampling.sample import PENALTY_WINDOW, sample_chain
from .config import ModelConfig
from .llama import forward, has_step_stats, init_cache, prefill


def init_state(cfg: ModelConfig, cache=None, seed: int = 0) -> dict:
    """Generation state pytree (cache + position + sampling state)."""
    return {
        "cache": cache if cache is not None else init_cache(cfg),
        "pos": jnp.int32(0),                # next cache slot to write
        "token": jnp.int32(0),              # token to feed next
        "window": jnp.full(PENALTY_WINDOW, -1, jnp.int32),
        "wpos": jnp.int32(0),
        "key": jax.random.PRNGKey(seed),
    }


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnames=("cache",))
def prefill_jit(params, cfg: ModelConfig, tokens, length, cache):
    """Bucketed prompt pass. tokens (S,) padded; length = real count.
    Returns (logits_at_last_real_token, cache)."""
    return prefill(params, cfg, tokens, length, cache)


prefill_jit = timed_jit("prefill", prefill_jit, site="models.generate")


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnames=("cache",))
def prefill_chunk_jit(params, cfg: ModelConfig, tokens, pos_offset, last_idx,
                      cache):
    """One slice of a chunked prompt pass: ``tokens`` (C,) enter the cache
    at ``pos_offset``; returns (logits at ``last_idx`` within the chunk,
    cache).  The continuous scheduler prefills admissions in these chunks
    so live lanes' decode interleaves instead of stalling for a whole
    bucket (engine/continuous.py); callers discard the logits of every
    chunk except the one containing the prompt's last real token."""
    return forward(params, cfg, tokens, pos_offset, cache, last_idx=last_idx)


prefill_chunk_jit = timed_jit("prefill_chunk", prefill_chunk_jit,
                              site="models.generate")


@functools.partial(jax.jit, static_argnames=("cfg", "top_k"))
def sample_jit(logits, window, wpos, key, st, cfg: ModelConfig, top_k: int = 40):
    """Sample the first token (from prefill logits) and update sampler state."""
    key, sub = jax.random.split(key)
    token = sample_chain(logits, window, sub, st, top_k=top_k)
    window = window.at[wpos % PENALTY_WINDOW].set(token)
    return token, window, wpos + 1, key


sample_jit = timed_jit("first_sample", sample_jit, site="models.generate")


def generate_chunk(params, cfg: ModelConfig, state: dict, st: dict,
                   n_steps: int, top_k: int = 40):
    """Pure ``n_steps`` decode+sample scan (the body of
    :func:`generate_chunk_jit`)."""

    def step(carry, _):
        logits, cache, *stats = forward(
            params, cfg, carry["token"][None], carry["pos"], carry["cache"],
            with_stats=has_step_stats(cfg))
        key, sub = jax.random.split(carry["key"])
        token = sample_chain(logits, carry["window"], sub, st, top_k=top_k)
        window = carry["window"].at[carry["wpos"] % PENALTY_WINDOW].set(token)
        new_carry = {
            "cache": cache,
            "pos": carry["pos"] + 1,
            "token": token,
            "window": window,
            "wpos": carry["wpos"] + 1,
            "key": key,
        }
        return new_carry, (token, *stats)

    state, ys = jax.lax.scan(step, state, None, length=n_steps)
    return state, chunk_out(*ys)


def chunk_out(tokens, stats=None):
    """What a decode chunk hands the host beside its state: the sampled
    tokens, and for a routed block (``cfg.n_experts``) the pair (tokens,
    the chunk's expert counters summed over its steps: llama.py
    ``expert_stats_len``).  :func:`split_chunk_out` takes it apart."""
    return tokens if stats is None else (tokens, jnp.sum(stats, axis=0))


def split_chunk_out(out):
    """(tokens, expert counters or None) of a decode chunk's output."""
    return out if isinstance(out, tuple) else (out, None)


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "n_steps", "top_k"),
    donate_argnames=("state",),
)
def generate_chunk_jit(params, cfg: ModelConfig, state: dict, st: dict,
                       n_steps: int, top_k: int = 40):
    """Run ``n_steps`` decode+sample steps on device.

    state["token"] is the most recently sampled (not yet decoded) token.
    Returns (new_state, tokens (n_steps,)) — the tokens sampled this chunk
    (for a routed block, with its counters: :func:`chunk_out`).
    """
    return generate_chunk(params, cfg, state, st, n_steps, top_k)


generate_chunk_jit = timed_jit("decode_chunk", generate_chunk_jit,
                               site="models.generate", leaf=1)  # its rows
