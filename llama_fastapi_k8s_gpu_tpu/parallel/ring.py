"""Ring attention — sequence/context parallelism over the ``sp`` mesh axis.

The reference has no long-context story at all: it *suppresses* sequence
length (n_ctx=1024, 400-char clips, oldest-message eviction — reference
api.py:27,37-46; SURVEY.md §5 "Long-context / sequence parallelism").  Here
long context is first-class: the token dimension (and the KV cache's n_ctx
dimension) shard over the ``sp`` mesh axis, and attention runs as a ring —
each device holds one KV chunk, computes a blockwise online-softmax update
against its current chunk, and passes the chunk to its neighbor with
``jax.lax.ppermute`` (ICI neighbor exchange), ``sp`` steps total.  No device
ever materializes more than 1/sp of the KV, so max context scales linearly
with the ring size.

Two ops, both ``shard_map``-ped and composable with ``tp`` (heads stay
sharded over ``tp`` inside the ring):

- :func:`ring_attention` — S queries (seq-sharded) over the full KV ring;
  the prefill path.
- :func:`sharded_decode_attention` — one query (replicated) over the
  seq-sharded KV cache, combined with a global log-sum-exp ``psum``; the
  decode path against an sp-sharded cache.

Model integration: ``attn_impl="ring"`` in ModelConfig routes
``models/llama.py`` attention here; :func:`sp_prefill` / :func:`sp_decode_step`
wrap the jit'd model entry points with the ring context (mesh + axis name,
needed at trace time).  The ``_sp_*_fn`` factories below are the
jit-factory form of lfkt-lint's DON donor registry (a donating jit over
a nested def, returned from an lru_cached builder): the wrapper
functions donate their cache/state transitively, and call sites are
held to the rebind contract (DON001-002, docs/LINT.md).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import threading

import jax
import jax.numpy as jnp

from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.config import ModelConfig
from ..obs.devtime import timed_jit
from ..ops.pallas.attention import DEFAULT_MASK_VALUE

_local = threading.local()


@contextlib.contextmanager
def ring_context(mesh: Mesh, axis_name: str = "sp"):
    """Makes (mesh, axis) visible to the model's ring-attention branch.
    Must be active while jit *traces* the model (the shard_map is baked into
    the compiled program; cached calls don't need it)."""
    prev = getattr(_local, "ctx", None)
    _local.ctx = (mesh, axis_name)
    try:
        yield
    finally:
        _local.ctx = prev


def current_ring_context():
    return getattr(_local, "ctx", None)


# ---------------------------------------------------------------------------
# shared GQA chunk-scores core (used by both the prefill ring and the decode
# LSE combine — one implementation so mask semantics can never desync)
# ---------------------------------------------------------------------------

def _group_queries(q: jax.Array, n_kv: int) -> jax.Array:
    """(S, H, hd) → (n_kv, group, S, hd) grouped-query layout."""
    S, H, hd = q.shape
    return q.reshape(S, n_kv, H // n_kv, hd).transpose(1, 2, 0, 3)


def _masked_chunk_scores(qg, k_chunk, v_chunk, q_pos, key_offset,
                         sm_scale, sliding_window):
    """Scores of grouped queries against one KV chunk whose global positions
    start at ``key_offset``, causal (+ optional sliding-window) masked.

    Returns ``(scores, vv)`` with scores (n_kv, group, S, C_loc) f32 and
    vv (n_kv, C_loc, hd) ready for the ``ngsc,nch->ngsh`` PV einsum.
    ``k_chunk``/``v_chunk`` are head-major (n_kv, C_loc, hd).
    """
    C_loc = k_chunk.shape[1]
    kk = k_chunk
    vv = v_chunk
    scores = jnp.einsum(
        "ngsh,nch->ngsc", qg, kk, preferred_element_type=jnp.float32
    ) * sm_scale
    key_pos = (key_offset + jnp.arange(C_loc))[None, :]
    mask = key_pos <= q_pos
    if sliding_window:
        mask &= key_pos > q_pos - sliding_window
    return jnp.where(mask[None, None], scores, DEFAULT_MASK_VALUE), vv


# ---------------------------------------------------------------------------
# prefill: seq-sharded queries over the rotating KV ring
# ---------------------------------------------------------------------------

def ring_attention(
    q: jax.Array,           # (S, n_heads, hd), seq-sharded over sp
    k: jax.Array,           # (n_kv, n_ctx, hd) head-major, seq-sharded over sp
    v: jax.Array,
    pos_offset: jax.Array,  # scalar int32: cache position of global q[0]
    sm_scale: float,
    sliding_window: int = 0,
) -> jax.Array:
    ctx = current_ring_context()
    if ctx is None:
        raise RuntimeError("ring_attention requires an active ring_context(mesh)")
    mesh, ax = ctx
    n_ring = mesh.shape[ax]

    def local_fn(q, k, v, pos_offset):
        # local shapes: q (S_loc, H_loc, hd), k/v (n_kv_loc, C_loc, hd)
        s_idx = jax.lax.axis_index(ax)
        S_loc, H, hd = q.shape
        n_kv, C_loc, _ = k.shape
        group = H // n_kv
        qg = _group_queries(q, n_kv)
        q_pos = (pos_offset + s_idx * S_loc + jnp.arange(S_loc))[:, None]

        perm = [(j, (j + 1) % n_ring) for j in range(n_ring)]
        m0 = jnp.full((n_kv, group, S_loc, 1), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((n_kv, group, S_loc, 1), jnp.float32)
        a0 = jnp.zeros((n_kv, group, S_loc, hd), jnp.float32)

        def step(i, carry):
            m, l, acc, k_cur, v_cur = carry
            src = jax.lax.rem(s_idx - i + n_ring, n_ring)  # chunk owner
            scores, vv = _masked_chunk_scores(
                qg, k_cur, v_cur, q_pos, src * C_loc, sm_scale, sliding_window)

            m_cur = jnp.max(scores, axis=-1, keepdims=True)
            m_new = jnp.maximum(m, m_cur)
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(scores - m_new)
            l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
            pv = jnp.einsum("ngsc,nch->ngsh", p.astype(vv.dtype), vv,
                            preferred_element_type=jnp.float32)
            acc_new = acc * alpha + pv
            # rotate the chunk to the next device (one extra hop at the end
            # keeps the loop shape static; the final permute is dead weight
            # XLA can overlap with the epilogue)
            k_nxt = jax.lax.ppermute(k_cur, ax, perm)
            v_nxt = jax.lax.ppermute(v_cur, ax, perm)
            return m_new, l_new, acc_new, k_nxt, v_nxt

        m, l, acc, _, _ = jax.lax.fori_loop(0, n_ring, step, (m0, l0, a0, k, v))
        l = jnp.where(l == 0.0, 1.0, l)
        out = (acc / l).astype(q.dtype)                    # (n_kv, group, S, hd)
        return out.transpose(2, 0, 1, 3).reshape(S_loc, H, hd)

    return shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P(ax, "tp", None), P("tp", ax, None), P("tp", ax, None), P()),
        out_specs=P(ax, "tp", None),
        check_vma=False,
    )(q, k, v, jnp.asarray(pos_offset, jnp.int32))


# ---------------------------------------------------------------------------
# decode: replicated query over the seq-sharded cache, LSE-combined
# ---------------------------------------------------------------------------

def sharded_decode_attention(
    q: jax.Array,           # (S, n_heads, hd) — S tiny (1), replicated over sp
    k: jax.Array,           # (n_kv, n_ctx, hd) head-major, seq-sharded over sp
    v: jax.Array,
    pos_offset: jax.Array,  # scalar: cache position of q[0]
    sm_scale: float,
    sliding_window: int = 0,
) -> jax.Array:
    ctx = current_ring_context()
    if ctx is None:
        raise RuntimeError("sharded_decode_attention requires ring_context(mesh)")
    mesh, ax = ctx

    def local_fn(q, k, v, pos_offset):
        s_idx = jax.lax.axis_index(ax)
        S, H, hd = q.shape
        n_kv, C_loc, _ = k.shape
        qg = _group_queries(q, n_kv)
        q_pos = (pos_offset + jnp.arange(S))[:, None]
        scores, vv = _masked_chunk_scores(
            qg, k, v, q_pos, s_idx * C_loc, sm_scale, sliding_window)

        m_loc = jnp.max(scores, axis=-1, keepdims=True)
        p = jnp.exp(scores - m_loc)
        l_loc = jnp.sum(p, axis=-1, keepdims=True)
        acc = jnp.einsum("ngsc,nch->ngsh", p.astype(vv.dtype), vv,
                         preferred_element_type=jnp.float32)
        # combine partial softmaxes across the ring with a global LSE
        m_glb = jax.lax.pmax(m_loc, ax)
        corr = jnp.exp(m_loc - m_glb)
        l_glb = jax.lax.psum(l_loc * corr, ax)
        acc_glb = jax.lax.psum(acc * corr, ax)
        l_glb = jnp.where(l_glb == 0.0, 1.0, l_glb)
        out = (acc_glb / l_glb).astype(q.dtype)
        return out.transpose(2, 0, 1, 3).reshape(S, H, hd)

    return shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P(None, "tp", None), P("tp", ax, None), P("tp", ax, None), P()),
        out_specs=P(None, "tp", None),
        check_vma=False,
    )(q, k, v, jnp.asarray(pos_offset, jnp.int32))


# ---------------------------------------------------------------------------
# model-level entry points
# ---------------------------------------------------------------------------

def sp_state_shardings(cfg: ModelConfig, mesh: Mesh) -> dict:
    """Head-major cache (L, n_kv, n_ctx, hd): n_ctx sharded over sp,
    kv-heads over tp.  Int8 caches shard the (L, n_kv, n_ctx) scale planes
    the same way minus the hd axis — the per-layer dequant before the ring
    collectives (models/llama.py) is elementwise, so it stays sp-local."""
    s4 = NamedSharding(mesh, P(None, "tp", "sp", None))
    if cfg.kv_dtype == "int8":
        s3 = NamedSharding(mesh, P(None, "tp", "sp"))
        return {"k_q": s4, "v_q": s4, "k_s": s3, "v_s": s3}
    return {"k": s4, "v": s4}



@functools.lru_cache(maxsize=32)
def _sp_prefill_fn(mesh: Mesh, axis_name: str, cfg: ModelConfig):
    """jit'd ring prefill, keyed on (mesh, axis, cfg) so a compiled program
    can never be reused under a different mesh (the ring context is only
    consulted at trace time)."""
    from ..models.llama import prefill as _prefill

    cfg = dataclasses.replace(cfg, attn_impl="ring")

    def fn(params, tokens, length, cache):
        with ring_context(mesh, axis_name):
            return _prefill(params, cfg, tokens, length, cache)

    return timed_jit("sp_prefill", jax.jit(fn, donate_argnames=("cache",)),
                     site="parallel.ring")


@functools.lru_cache(maxsize=32)
def _sp_decode_fn(mesh: Mesh, axis_name: str, cfg: ModelConfig):
    from ..models.llama import decode_step as _decode

    cfg = dataclasses.replace(cfg, attn_impl="ring")

    def fn(params, token, pos, cache):
        with ring_context(mesh, axis_name):
            return _decode(params, cfg, token, pos, cache)

    return timed_jit("sp_decode_step", jax.jit(fn, donate_argnames=("cache",)),
                     site="parallel.ring")


def sp_prefill(params, cfg: ModelConfig, tokens, length, cache, mesh: Mesh,
               axis_name: str = "sp"):
    """Sequence-parallel prompt pass: ``tokens`` (S,) with S % sp == 0,
    cache seq-sharded per :func:`sp_state_shardings` (donated).  Everything
    outside attention is per-token (GSPMD shards it for free); attention
    runs the ring."""
    return _sp_prefill_fn(mesh, axis_name, cfg)(params, tokens, length, cache)


def sp_decode_step(params, cfg: ModelConfig, token, pos, cache, mesh: Mesh,
                   axis_name: str = "sp"):
    """One decode step against a seq-sharded cache (sharded-LSE attention);
    the cache is donated, so steady-state decode is allocation-free."""
    return _sp_decode_fn(mesh, axis_name, cfg)(params, token, pos, cache)


@functools.lru_cache(maxsize=64)
def _sp_chunk_fn(mesh: Mesh, axis_name: str, cfg: ModelConfig,
                 n_steps: int, top_k: int):
    from ..models.generate import generate_chunk

    cfg = dataclasses.replace(cfg, attn_impl="ring")

    def fn(params, state, st):
        with ring_context(mesh, axis_name):
            return generate_chunk(params, cfg, state, st, n_steps, top_k)

    return timed_jit("sp_decode_chunk", jax.jit(fn, donate_argnames=("state",)),
                     site="parallel.ring", leaf=1)     # done stamp: its rows


def sp_generate_chunk(params, cfg: ModelConfig, state: dict, st: dict,
                      mesh: Mesh, n_steps: int, top_k: int = 40,
                      axis_name: str = "sp"):
    """``n_steps`` on-device decode+sample steps with sharded-LSE attention
    against the seq-sharded cache — the serving decode loop of the
    sequence-parallel engine (engine/sp.py).  State is donated; the sampled
    tokens (n_steps,) come back replicated."""
    return _sp_chunk_fn(mesh, axis_name, cfg, n_steps, top_k)(params, state, st)
