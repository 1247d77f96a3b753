"""The third cache KIND, and the first stack of TWO layer kinds
(``general.architecture = "minicpm-sala"``; ``cfg.mixers`` names each
layer's kind in the published order).

- ``"lin"``, a linear-attention layer ("lightning attention"): per head a
  float32 state ``S`` of ``head_dim x head_dim``, ``S_t = lambda_h S_(t-1)
  + k_t v_t^T``, ``o_t = head_dim^-1/2 q_t^T S_t``.  Its cache is ``S``
  alone, leaf ``state`` (L_lin, heads, hd, hd) float32, whatever the
  length.  A decode step is the recurrence (:func:`lin_step`); a prefill
  slice of C positions is the same sum in chunk form (:func:`lin_slice`):
  the causal scores inside the slice weighted by ``lambda^(i-j)``, the
  part before it ``lambda^(i+1) q_i^T S_in``, and ``S_out = lambda^n S_in
  + sum_j lambda^(n-1-j) k_j v_j^T`` over the slice's n REAL positions (a
  ring's slots past the prompt are masked by position for ever after; a
  state integrates what it is fed, so the padding of a bucket must not
  reach it).  A state cannot be rolled back, so nothing reuses a prefix;
  **the pass that starts at position 0 starts from a zero state**
  (admission prefills a scratch cache from position 0 and installs all of
  it in the lane: that is the reset of a freed lane).
- ``"sp"``, a block-sparse attention layer (InfLLM v2 as MiniCPM4 ships
  it) on a ring of ``n_kv_heads`` heads, no rotation: leaves ``k``/``v``
  (L_sp, n_kv, n_ctx, hd) as every ring, and ``kc`` (L_sp, n_kv, n_ctx /
  stride + 1, hd; the last entry a spare that only a step which closes
  nothing writes): entry e is the mean of the ``kernel`` keys that END at
  position ``stride (e + 1) - 1`` (the released ``kc_j``, j = e - (kernel /
  stride - 1)), written by the step or slice that writes that position,
  visible to a query at t once ``stride (e + 1) - 1 <= t``; entries e <
  kernel / stride - 1 have no full kernel and are never visible.  A small
  leaf ``kw`` (L_sp, n_kv, kernel, hd) holds the last ``kernel`` keys by
  position mod ``kernel``: what a closing decode step averages.  A query
  at t with ``t + 1 < dense_len`` is plain causal attention on the ring
  (``models/llama.py _ring_attention``: the decode kernel where it
  serves).  From ``dense_len`` on: softmax over the visible ``kc`` per
  query head, summed over the group, max over the entries that overlap a
  block of ``block`` positions (:func:`block_scores`); the blocks read are
  the first ``init_blocks``, those that hold the last ``window`` positions
  and the ``topk`` others that score highest, one set per KV head
  (:func:`select_blocks`); ONE softmax over the causal positions of those
  blocks.  A decode step GATHERS its ``n_select`` blocks from the ring
  (:func:`sparse_decode`: the read does not grow with ``n_ctx``); a
  prefill slice past ``dense_len`` selects per query and reads the union
  of its queries' blocks, which is the ring up to the slice, in chunks of
  keys under each query's own mask (:func:`sparse_slice`).

The rule for the branch is the query's POSITION (``t + 1 >= dense_len``),
which is what token-by-token generation gives in the released code (it
chooses per call by the length so far); one program serves every position.

Under ``vmap`` over lanes the branch taken is per lane, but whether a branch
runs at all is decided by UNBATCHED scalars (:func:`live_bounds`, as
``models/eva.py``'s), so a step with no live lane past ``dense_len`` runs no
selection and no gather, a lane past it reads nothing of the ring's dense
path, and in a step that has one the sparse branch runs lane after lane,
for the lanes that take it alone (:func:`_sparse_decode_vmappable`).  A lane's result does not depend on the other lanes: each
branch's arithmetic is the lane's own, the scalars only skip work whose
output the lane does not take.
"""

from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.linear import linear, linear_at
from .cache import CacheKind
from .config import STATE_RING, ModelConfig
from .llama import (
    _ring_attention, note_ring_decode, ring_kernel_block, rms_norm,
    rope_half)

logger = logging.getLogger(__name__)

LIN, SP = "lin", "sp"
HI = jax.lax.Precision.HIGHEST

#: keys a prefill slice past ``dense_len`` reads at a time (the loop's
#: scores are (n_kv, group, C, this) float32: 33 MB at 2 x 16 x 256)
SLICE_KEY_CHUNK = 1024


def runs(cfg: ModelConfig) -> list[tuple[str, int, int]]:
    """The stack as runs of one kind, in order: (kind, the run's first
    layer counted WITHIN its kind, layers in the run)."""
    out, seen = [], {LIN: 0, SP: 0}
    for m in cfg.mixers:
        if out and out[-1][0] == m:
            out[-1][2] += 1
        else:
            out.append([m, seen[m], 1])
        seen[m] += 1
    return [tuple(r) for r in out]


def n_blocks(cfg: ModelConfig) -> int:
    return cfg.n_ctx // cfg.sp_block


def n_kc(cfg: ModelConfig) -> int:
    return cfg.n_ctx // cfg.sp_stride


def n_select(cfg: ModelConfig) -> int:
    """Blocks a query's selection holds at most: the first ones, the
    window's (one more than it spans whole: it lies astride), the picked."""
    return min(cfg.sp_init_blocks + -(-cfg.sp_window // cfg.sp_block) + 1
               + cfg.sp_topk, n_blocks(cfg))


def decay_slopes(cfg: ModelConfig) -> np.ndarray:
    """(L_lin, heads) float32: ``lambda = exp(-slope)``.  The family's
    published schedule: ``2^(-8 (h + 1) / heads)`` scaled over the linear
    layers, in order, by ``1 - l / (L_lin - 1) + 1e-5``."""
    L, H = cfg.n_layers_of(LIN), cfg.lin_heads
    base = 2.0 ** (-8.0 * (np.arange(H) + 1) / H)
    depth = 1.0 - np.arange(L) / max(L - 1, 1) + 1e-5
    return (depth[:, None] * base[None, :]).astype(np.float32)


def decay_lambdas(cfg: ModelConfig) -> np.ndarray:
    """(L_lin, heads) float32: ``lambda`` itself, computed on the host in
    float64.  A decode step multiplies the state by it once a position: the
    TPU's ``exp`` is off by about 1e-6 of a value near 1, which 12336 steps
    of the last layers' heads (they hardly decay) carry to 1 % of the state
    (my chip run, PR 38); the chunk form takes ``exp(-slope n)`` once a
    slice and is not touched by it."""
    return np.exp(-decay_slopes(cfg).astype(np.float64)).astype(np.float32)


def init_cache(cfg: ModelConfig, dtype=jnp.bfloat16) -> dict:
    if cfg.kv_dtype not in ("bf16", "bfloat16"):
        raise ValueError(
            f"kv_dtype={cfg.kv_dtype!r} cannot hold architecture "
            "'minicpm-sala': its state + ring cache is float32 + bf16 only")
    hd = cfg.head_dim
    ring = (cfg.n_layers_of(SP), cfg.n_kv_heads, cfg.n_ctx, hd)
    return {"k": jnp.zeros(ring, dtype), "v": jnp.zeros(ring, dtype),
            "kc": jnp.zeros(ring[:2] + (n_kc(cfg) + 1, hd), dtype),
            "kw": jnp.zeros(ring[:2] + (cfg.sp_kernel, hd), dtype),
            "state": jnp.zeros((cfg.n_layers_of(LIN), cfg.lin_heads, hd, hd),
                               jnp.float32)}


def state_nbytes(cfg: ModelConfig) -> int:
    """One sequence's state over all linear layers (float32)."""
    return cfg.n_layers_of(LIN) * cfg.lin_heads * cfg.head_dim ** 2 * 4


def cache_nbytes(cfg: ModelConfig) -> int:
    ring = cfg.n_layers_of(SP) * cfg.n_kv_heads * cfg.head_dim * 2 \
        * (2 * cfg.n_ctx + n_kc(cfg) + 1 + cfg.sp_kernel)
    return ring + state_nbytes(cfg)


# ---------------------------------------------------------------------------
# bounds and host arithmetic
# ---------------------------------------------------------------------------

def is_sparse(pos, cfg: ModelConfig):
    return pos + 1 >= cfg.sp_dense_len


def live_bounds(pos: jax.Array, live, cfg: ModelConfig):
    """A lane step's ``kv_bound``: three UNBATCHED values, (the ring slot
    the XLA loop of the dense branch reads up to, or None where the decode
    kernel serves it and bounds each lane by itself; whether any live lane
    is past ``dense_len``; whether any is before it).  A freed lane keeps
    stepping and must drag neither read along."""
    lv = jnp.ones(pos.shape, bool) if live is None else live
    sp = is_sparse(pos, cfg)
    dense = lv & ~sp
    bound = None if ring_kernel_block(cfg) \
        else jnp.max(jnp.where(dense, pos, 0))
    return bound, jnp.any(lv & sp), jnp.any(dense)


def blocks_visible(pos: int, cfg: ModelConfig) -> int:
    return pos // cfg.sp_block + 1


def blocks_read(pos: int, cfg: ModelConfig) -> int:
    """Blocks the sparse branch's read covers for a query at ``pos``: what
    :func:`select_blocks` selects (every visible block while they are
    fewer)."""
    first_win = max((pos - cfg.sp_window + 1) // cfg.sp_block, 0)
    forced = set(range(min(cfg.sp_init_blocks, blocks_visible(pos, cfg)))) \
        | set(range(first_win, blocks_visible(pos, cfg)))
    return min(len(forced) + cfg.sp_topk, blocks_visible(pos, cfg))


def kc_closed(first: int, n: int, cfg: ModelConfig) -> int:
    """``kc`` entries with a full kernel that close at the positions
    ``[first, first + n)``."""
    lo = max(first, cfg.sp_kernel - 1)
    return max((first + n) // cfg.sp_stride - lo // cfg.sp_stride, 0)


def chunk_counts(positions: list[int], n_steps: int, cfg: ModelConfig) -> dict:
    """What ``n_steps`` decode steps did, summed over the live sequences
    that start the chunk at ``positions``: state updates (one a linear
    layer), queries of the sparse layers by branch, blocks the sparse
    branch's read covered / a causal read would (per sparse layer and KV
    head), ``kc`` entries written.  Host arithmetic from tracked positions
    for the counters of :data:`CACHE`: nothing fetched."""
    L_lin, L_sp = cfg.n_layers_of(LIN), cfg.n_layers_of(SP)
    out = {"state_updates": n_steps * len(positions) * L_lin,
           "queries_dense": 0, "queries_sparse": 0,
           "blocks_read": 0, "blocks_visible": 0, "kc_written": 0}
    for p0 in positions:
        out["kc_written"] += L_sp * kc_closed(p0, n_steps, cfg)
        for p in range(p0, p0 + n_steps):
            if is_sparse(p, cfg):
                out["queries_sparse"] += L_sp
                out["blocks_read"] += L_sp * cfg.n_kv_heads \
                    * blocks_read(p, cfg)
                out["blocks_visible"] += L_sp * cfg.n_kv_heads \
                    * blocks_visible(p, cfg)
            else:
                out["queries_dense"] += L_sp
    return out


def prefill_counts(n_prompt: int, cfg: ModelConfig) -> dict:
    """The same for a prompt's prefill: its sparse layers' queries by
    branch (positions), and the ``kc`` entries it closes."""
    L_sp = cfg.n_layers_of(SP)
    n_sp = max(n_prompt - (cfg.sp_dense_len - 1), 0)
    return {"queries_dense": L_sp * (n_prompt - n_sp),
            "queries_sparse": L_sp * n_sp,
            "kc_written": L_sp * kc_closed(0, n_prompt, cfg),
            "sparse_positions": n_sp,
            "kc_closed": kc_closed(0, n_prompt, cfg)}


# ---------------------------------------------------------------------------
# shared pieces
# ---------------------------------------------------------------------------

def _rms(x, w, eps):
    """RMSNorm over the last axis in float32, the result float32 (q/k norms
    and the output norm are float32 as the configuration states)."""
    x32 = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return x32 * inv * w.astype(jnp.float32)


def _add_branch(h, out, cfg: ModelConfig):
    return (h.astype(jnp.float32)
            + cfg.residual_scale * out.astype(jnp.float32)).astype(h.dtype)


def _pre(h, w, i, cfg: ModelConfig):
    hn = rms_norm(h, w["attn_norm"][i], cfg.rms_eps)

    def lin(x, name):
        with jax.named_scope(name):
            return linear_at(x, w[name], i)
    return hn, lin


def _finish(h, hn, ctx, lin, w, i, cfg: ModelConfig):
    """Gate, output projection, branch, then the feed-forward branch.
    ``ctx`` (S, dim) float32."""
    gate = jax.nn.sigmoid(lin(hn, "wg").astype(jnp.float32))
    h = _add_branch(h, lin((ctx * gate).astype(hn.dtype), "wo"), cfg)
    fn = rms_norm(h, w["ffn_norm"][i], cfg.rms_eps)
    gated = jax.nn.silu(lin(fn, "w_gate").astype(jnp.float32)).astype(fn.dtype)
    return _add_branch(h, lin(gated * lin(fn, "w_up"), "w_down"), cfg)


# ---------------------------------------------------------------------------
# the linear-attention layer
# ---------------------------------------------------------------------------

def starts_sequence(pos_offset):
    """Whether a pass starts its sequence, and so from a ZERO state whatever
    the leaf holds: the pass at position 0.  This is the reset of a freed
    lane and of the serial engine's cache between requests (nothing reuses
    a prefix of this cache, so every prefill starts here); a ring needs
    none, its stale slots are masked by position."""
    return pos_offset == 0


def lin_step(q, k, v, s_in, slope):
    """The recurrence, one position.  ``q``/``k``/``v`` (H, hd) (matmul
    inputs, bf16), ``s_in`` (H, hd, hd) f32, ``slope`` (H,).  Returns (o
    (H, hd) f32, s_out)."""
    with jax.named_scope("lin_state_step"):
        qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
        s_out = jnp.exp(-slope)[:, None, None] * s_in \
            + kf[:, :, None] * vf[:, None, :]
        o = jnp.sum(qf[:, :, None] * s_out, axis=1) * q.shape[-1] ** -0.5
    return o, s_out


def lin_slice(q, k, v, s_in, slope, n_valid):
    """The same sum over a slice in chunk form.  ``q``/``k``/``v`` (C, H,
    hd), ``n_valid``: the slice's real positions (the rest is a bucket's
    padding: it reaches no state and its outputs are not read).  Returns
    (o (C, H, hd) f32, s_out)."""
    C, H, hd = q.shape
    with jax.named_scope("lin_slice_scan"):
        idx = jnp.arange(C)
        valid = idx < n_valid
        k = jnp.where(valid[:, None, None], k, 0)
        qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
        ahead = idx[:, None] - idx[None, :]
        decay = jnp.where(
            ahead >= 0,
            jnp.exp(-slope[:, None, None] * jnp.maximum(ahead, 0)), 0.0)
        scores = jnp.einsum("ahd,bhd->hab", q, k,
                            preferred_element_type=jnp.float32)
        inside = jnp.einsum("hab,bhe->ahe", scores * decay, vf, precision=HI)
        before = jnp.einsum("ahd,hde->ahe", qf, s_in, precision=HI) \
            * jnp.exp(-slope[None, :, None] * (idx + 1)[:, None, None])
        o = (inside + before) * hd ** -0.5
        left = jnp.maximum(n_valid - 1 - idx, 0)
        wgt = jnp.where(valid[None, :],
                        jnp.exp(-slope[:, None] * left[None, :]), 0.0)
        s_out = jnp.exp(-slope * n_valid)[:, None, None] * s_in \
            + jnp.einsum("bhd,bhe->hde", kf * wgt.T[:, :, None], vf,
                         precision=HI)
    return o, s_out


#: positions of one piece of :func:`lin_pieces`: ``lin_slice`` is quadratic
#: in its positions (scores C x C a head in float32), so a slice wider than
#: this walks it piece by piece
LIN_PIECE = 256


def lin_pieces(q, k, v, s_in, slope, n_valid):
    """:func:`lin_slice` over a wide slice as a scan over pieces of
    :data:`LIN_PIECE` positions carrying the state: the same sum, piece for
    piece what as many narrow slices compute.  A slice that is no whole
    number of pieces (or one piece) goes to ``lin_slice`` whole."""
    C = q.shape[0]
    if C <= LIN_PIECE or C % LIN_PIECE:
        return lin_slice(q, k, v, s_in, slope, n_valid)
    n = C // LIN_PIECE

    def piece(s, x):
        qp, kp, vp, at = x
        o, s = lin_slice(qp, kp, vp, s, slope,
                         jnp.clip(n_valid - at, 0, LIN_PIECE))
        return s, o

    s_out, o = jax.lax.scan(piece, s_in, (
        *(x.reshape(n, LIN_PIECE, *x.shape[1:]) for x in (q, k, v)),
        jnp.arange(n, dtype=jnp.int32) * LIN_PIECE))
    return o.reshape(C, *o.shape[2:]), s_out


def lin_layer(h, w, i, cache, positions, pos_offset, n_valid,
              cfg: ModelConfig, live=None):
    """One linear-attention layer over S positions against layer ``i`` of
    the linear layers' stacked weights and of the ``state`` leaf.  Where
    the kernels serve (``cfg.attn_impl == "pallas"``: a TPU, as the ring's
    decode kernel) a decode step updates the stacked leaf in place
    (ops/pallas/linstate.py) and touches nothing of a lane whose ``live``
    is False; the plain XLA step serves the CPU."""
    S, H, hd = h.shape[0], cfg.lin_heads, cfg.head_dim
    hn, lin = _pre(h, w, i, cfg)
    q, k, v = (lin(hn, n).reshape(S, H, hd) for n in ("wq", "wk", "wv"))
    q = rope_half(_rms(q, w["attn_q_norm"][i], cfg.rms_eps), positions,
                  cfg.rope_theta).astype(hn.dtype)
    k = rope_half(_rms(k, w["attn_k_norm"][i], cfg.rms_eps), positions,
                  cfg.rope_theta).astype(hn.dtype)
    slope = jnp.asarray(decay_slopes(cfg))[i]
    if S == 1 and cfg.attn_impl == "pallas":
        from ..ops.pallas import use_interpret
        from ..ops.pallas.linstate import lin_state_step

        o, state = lin_state_step(
            q[0], k[0], v[0], cache["state"], i,
            True if live is None else live,
            jnp.asarray(decay_lambdas(cfg))[i], interpret=use_interpret())
        o = o[None]
    else:
        s_in = jax.lax.dynamic_index_in_dim(cache["state"], i, 0,
                                            keepdims=False)
        if S == 1:      # a step never starts a sequence: its prefill did
            o, s_out = lin_step(q[0], k[0], v[0], s_in, slope)
            o = o[None]
        else:
            s_in = jnp.where(starts_sequence(pos_offset), 0.0, s_in)
            o, s_out = lin_pieces(q, k, v, s_in, slope, n_valid)
        with jax.named_scope("lin_state_write"):
            state = jax.lax.dynamic_update_slice(
                cache["state"], s_out[None], (i, 0, 0, 0))
    o = _rms(o, w["attn_out_norm"][i], cfg.rms_eps).reshape(S, H * hd)
    return _finish(h, hn, o, lin, w, i, cfg), {**cache, "state": state}


# ---------------------------------------------------------------------------
# the block-sparse layer
# ---------------------------------------------------------------------------

def _kc_write(kc, kw, k_new, i, pos_offset, n_valid, cfg: ModelConfig):
    """Write the ``kc`` entries that close at the S new positions, and keep
    ``kw``, the last ``kernel`` keys by position mod ``kernel``, which is
    what a closing decode step averages (a window read out of the ring at
    a lane's own position would be a gather over ``n_ctx`` under ``vmap``,
    and the compiler then lays the whole ring out anew in every layer).
    ``k_new`` (n_kv, S, hd): the new keys as the ring holds them;
    ``n_valid``: how many of them are real.  Returns (kc, kw)."""
    n_kv, S, hd = k_new.shape
    K, St = cfg.sp_kernel, cfg.sp_stride
    with jax.named_scope("sparse_kc_write"):
        if S == 1:
            t = pos_offset
            kw = jax.lax.dynamic_update_slice(
                kw, k_new[None], (i, 0, t % K, 0))
            win = jax.lax.dynamic_index_in_dim(kw, i, 0, keepdims=True)
            mean = jnp.mean(win.astype(jnp.float32), axis=2, keepdims=True)
            # a step that closes no entry writes the leaf's SPARE last one
            # (nothing reads it): a write, never a read-select-write, whose
            # read at a lane's own index makes the compiler lay the lanes'
            # leaf out anew in every sparse layer
            e = jnp.where((t + 1) % St == 0,
                          jnp.clip((t + 1) // St - 1, 0, n_kc(cfg)),
                          n_kc(cfg))
            return jax.lax.dynamic_update_slice(
                kc, mean.astype(kc.dtype), (i, 0, e, 0)), kw
        if S % St:
            raise ValueError(
                f"architecture 'minicpm-sala': a pass of {S} positions is no "
                f"multiple of its compressed keys' stride ({St})")
        kw_l = jax.lax.dynamic_index_in_dim(kw, i, 0, keepdims=False)
        # the kernel reaches K - St keys back before the slice: entry m of
        # the slice ends at position pos_offset + St (m + 1) - 1
        back = (pos_offset - (K - St) + jnp.arange(K - St)) % K
        buf = jnp.concatenate([kw_l[:, back], k_new],
                              axis=1).astype(jnp.float32)
        sums = buf.reshape(n_kv, -1, St, hd).sum(axis=2)
        n = S // St
        means = sum(sums[:, x:x + n] for x in range(K // St)) / K
        kc = jax.lax.dynamic_update_slice(
            kc, means[None].astype(kc.dtype), (i, 0, pos_offset // St, 0))
        # slot r: the last REAL position of the slice that is r mod K, or
        # what the slot held
        last = pos_offset + n_valid - 1
        r = jnp.arange(K)
        at = last - (last - r) % K - pos_offset
        kw_l = jnp.where((at >= 0)[None, :, None],
                         k_new[:, jnp.clip(at, 0, S - 1)], kw_l)
        return kc, jax.lax.dynamic_update_slice(kw, kw_l[None], (i, 0, 0, 0))


def block_scores(qg, kc_l, t, cfg: ModelConfig):
    """Scores of the blocks for the queries ``qg`` (n_kv, group, S, hd) at
    positions ``t`` (S,), against one layer's ``kc_l`` (n_kv, E, hd):
    softmax over the visible entries per query head, summed over the
    group, and for each block the maximum over the entries whose kernel
    overlaps it.  (n_kv, S, n_blocks) float32, >= 0."""
    hd = qg.shape[-1]
    St, bs, r = cfg.sp_stride, cfg.sp_block // cfg.sp_stride, \
        cfg.sp_kernel // cfg.sp_stride
    with jax.named_scope("sparse_scores"):
        # (not the leaf's spare entry.)  Both sides as float32 OF bfloat16
        # values: the products are what a bfloat16 dot's are, and the
        # convert reads the stacked leaf where it lies; a bfloat16 dot on
        # the layer's slice made the compiler lay the lanes' whole leaf out
        # anew, layer-major, in every sparse layer
        kc_l = kc_l[:, :n_kc(cfg)].astype(jnp.float32)
        s = jnp.einsum("ngsh,neh->ngse", qg.astype(jnp.float32), kc_l,
                       preferred_element_type=jnp.float32) * hd ** -0.5
        e = jnp.arange(kc_l.shape[1])
        vis = (St * (e[None, :] + 1) - 1 <= t[:, None]) & (e[None, :] >= r - 1)
        p = jax.nn.softmax(jnp.where(vis, s, -1e30), axis=-1) * vis
        pg = jnp.sum(p, axis=1)                          # (n_kv, S, E)
        nb = pg.shape[-1] // bs
        per = pg.reshape(pg.shape[:-1] + (nb, bs))
        score = jnp.max(per, axis=-1)
        # entry bs b + bs + x (x < r - 1) lies in the next block's run and
        # its kernel reaches back into block b
        nxt = jnp.pad(per, ((0, 0), (0, 0), (0, 1), (0, 0)))[:, :, 1:]
        for x in range(r - 1):
            score = jnp.maximum(score, nxt[..., x])
    return score


def select_blocks(score, t, cfg: ModelConfig):
    """The blocks a query at ``t`` reads, one set per KV head: ``score``
    (..., n_blocks), ``t`` broadcastable against its leading axes.  The
    first ``init_blocks``, the blocks that hold the last ``window``
    positions, and the ``topk`` highest of the other visible ones.
    (..., n_blocks) bool."""
    B = cfg.sp_block
    with jax.named_scope("sparse_select"):
        b = jnp.arange(score.shape[-1])
        t = jnp.asarray(t)[..., None]
        visible = b <= t // B
        forced = visible & ((b < cfg.sp_init_blocks)
                            | (b >= (t - cfg.sp_window + 1) // B))
        cand = visible & ~forced
        sc = jnp.where(cand, score, -1.0)
        # exactly topk: neighbouring blocks share a kernel and so, often, a
        # score; of equal scores the lower block wins (top_k's order, and
        # the reference's stable sort)
        vals, idx = jax.lax.top_k(sc, min(cfg.sp_topk, sc.shape[-1]))
        picked = (idx[..., None] == b) & (vals[..., None] >= 0.0)
        return forced | jnp.any(picked, axis=-2)


def _layer_of(leaf, i):
    """Layer ``i`` of a stacked leaf (L, n_kv, ...) as a GATHER over (layer,
    head): a ``dynamic_slice`` of the lanes' leaf under ``vmap`` makes the
    TPU compiler lay the whole leaf out anew, layer-major, before every
    slice (33 MB copied in and out per sparse layer and step at 8 lanes);
    a gather reads where the leaf lies, as the selected blocks' does."""
    return leaf[i, jnp.arange(leaf.shape[1])]


def _all_visible(t, cfg: ModelConfig):
    return jnp.arange(n_blocks(cfg)) <= jnp.asarray(t)[..., None] \
        // cfg.sp_block


def _sparse_decode(q, kc_l, blocks_of, t, cfg: ModelConfig, out_dtype):
    """Scores, selection, and a GATHER of the selected blocks for ONE
    query ``q`` (n_heads, hd) at ``t``: ``kc_l`` its layer's compressed
    keys, ``blocks_of(name, heads, idx)`` the ring's blocks ``idx`` (n_kv,
    NS) of leaf ``name`` as (n_kv, NS, block, hd).  Returns (ctx (n_heads *
    hd,), picks (n_kv, n_blocks) bool)."""
    n_kv, hd, B = cfg.n_kv_heads, cfg.head_dim, cfg.sp_block
    group, NB, NS = cfg.n_heads // n_kv, n_blocks(cfg), n_select(cfg)
    qg = q.reshape(n_kv, group, hd)
    score = block_scores(qg[:, :, None], kc_l, jnp.reshape(t, (1,)), cfg)
    picks = select_blocks(score[:, 0], t, cfg)            # (n_kv, NB)
    # the picked blocks' numbers in rising order, then fill
    rank, idx = jax.lax.top_k(
        jnp.where(picks, NB - jnp.arange(NB), 0), NS)
    with jax.named_scope("sparse_read"):
        heads = jnp.arange(n_kv)[:, None]
        kb = blocks_of("k", heads, idx)                   # (n_kv, NS, B, hd)
        vb = blocks_of("v", heads, idx)
        key_pos = idx[..., None] * B + jnp.arange(B)
        mask = (rank > 0)[..., None] & (key_pos <= t)
        s = jnp.einsum("ngh,nsbh->ngsb", qg, kb,
                       preferred_element_type=jnp.float32) * hd ** -0.5
        s = jnp.where(mask[:, None], s, -jnp.inf).reshape(n_kv, group, -1)
        p = jax.nn.softmax(s, axis=-1).astype(vb.dtype)
        ctx = jnp.einsum("ngc,nch->ngh", p, vb.reshape(n_kv, NS * B, hd),
                         preferred_element_type=jnp.float32)
    return ctx.reshape(cfg.n_heads * hd).astype(out_dtype), picks


@functools.lru_cache(maxsize=8)
def _sparse_decode_vmappable(cfg: ModelConfig, out_dtype):
    """The sparse branch of one sequence's decode step, with its vmap rule:
    lanes ``vmap``ped over one step run ONE AFTER ANOTHER, each behind a
    real branch on its own ``active`` (it holds a request and stands past
    ``dense_len``), reading the lanes' stacked leaves where they lie
    (every index a gather's).  Left to ``vmap``, every lane of the step
    would gather its 98 blocks a layer, dead or before ``dense_len`` or
    not: 2.4 ms a step at 8 lanes where one lane's read takes 0.3 (my chip
    runs, PR 38)."""
    from jax.custom_batching import custom_vmap

    NB, blk, hd = n_blocks(cfg), cfg.sp_block, cfg.head_dim

    def in_blocks(leaf):       # (..., n_ctx, hd) -> (..., NB, block, hd)
        return leaf.reshape(leaf.shape[:-2] + (NB, blk, hd))

    @custom_vmap
    def one(q, k, v, kc, i, t, active):
        del active              # one sequence: its caller's branch decided
        leaves = {"k": in_blocks(k), "v": in_blocks(v)}
        return _sparse_decode(
            q, _layer_of(kc, i),
            lambda name, heads, idx: leaves[name][i, heads, idx], t, cfg,
            out_dtype)

    @one.def_vmap
    def _rule(axis_size, in_batched, q, k, v, kc, i, t, active):
        if in_batched[4] or not all(in_batched[:4] + in_batched[5:]):
            raise NotImplementedError(
                "sparse decode vmap: lanes over everything but the layer")
        leaves = {"k": in_blocks(k), "v": in_blocks(v)}
        heads_kc = jnp.arange(kc.shape[2])

        def lane(b, out):
            def run(out):
                ctx, picks = _sparse_decode(
                    q[b], kc[b, i, heads_kc],
                    lambda name, heads, idx: leaves[name][b, i, heads, idx],
                    t[b], cfg, out_dtype)
                return (jax.lax.dynamic_update_index_in_dim(out[0], ctx, b, 0),
                        jax.lax.dynamic_update_index_in_dim(out[1], picks, b,
                                                            0))
            return jax.lax.cond(active[b], run, lambda out: out, out)

        out = jax.lax.fori_loop(0, axis_size, lane, (
            jnp.zeros((axis_size, cfg.n_heads * hd), out_dtype),
            jnp.zeros((axis_size, cfg.n_kv_heads, NB), bool)))
        return out, (True, True)

    return one


def sparse_decode(q, cache, i, t, active, cfg: ModelConfig, out_dtype):
    """The sparse branch of a decode step at layer ``i``: ``q`` (n_heads,
    hd), ``active`` whether this sequence takes the branch (under ``vmap``
    a lane that does not costs nothing: :func:`_sparse_decode_vmappable`).
    Returns (ctx (n_heads * hd,), picks (n_kv, n_blocks) bool)."""
    return _sparse_decode_vmappable(cfg, jnp.dtype(out_dtype))(
        q, cache["k"], cache["v"], cache["kc"], i, jnp.asarray(t, jnp.int32),
        jnp.asarray(active, jnp.bool_))


def sparse_slice(q, cache, i, positions, cfg: ModelConfig, out_dtype):
    """A prefill slice with a query past ``dense_len``: every query selects
    its own blocks (a query before ``dense_len`` all it can see), and the
    slice reads the ring up to its last position in chunks of keys, each
    query under its own blocks' mask, with a running max and sum.  ``q``
    (S, n_heads, hd).  Returns (ctx (S, n_heads * hd), picks (n_kv, S,
    n_blocks) bool)."""
    S = q.shape[0]
    n_kv, hd, B = cfg.n_kv_heads, cfg.head_dim, cfg.sp_block
    group, n_ctx = cfg.n_heads // n_kv, cfg.n_ctx
    T = max(min(SLICE_KEY_CHUNK, n_ctx) // B, 1) * B
    qg = q.reshape(S, n_kv, group, hd).transpose(1, 2, 0, 3)
    kc_l = _layer_of(cache["kc"], i)
    score = block_scores(qg, kc_l, positions, cfg)        # (n_kv, S, NB)
    picks = jnp.where(is_sparse(positions, cfg)[None, :, None],
                      select_blocks(score, positions[None, :], cfg),
                      _all_visible(positions, cfg)[None])

    def chunk(j, carry):
        m, l, acc = carry
        lo = j * T
        at = jnp.minimum(lo, n_ctx - T)
        kb = jax.lax.dynamic_slice(
            cache["k"], (i, 0, at, 0), (1, n_kv, T, hd))[0]
        vb = jax.lax.dynamic_slice(
            cache["v"], (i, 0, at, 0), (1, n_kv, T, hd))[0]
        key_pos = at + jnp.arange(T)
        mine = jnp.repeat(jax.lax.dynamic_slice_in_dim(
            picks, at // B, T // B, axis=2), B, axis=2)   # (n_kv, S, T)
        mask = mine & (key_pos >= lo) & (key_pos <= positions[:, None])
        s = jnp.einsum("ngsh,nch->ngsc", qg, kb,
                       preferred_element_type=jnp.float32) * hd ** -0.5
        s = jnp.where(mask[:, None], s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "ngsc,nch->ngsh", p.astype(vb.dtype), vb,
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    with jax.named_scope("sparse_read"):
        n_chunks = jnp.minimum((positions[S - 1] + T) // T, -(-n_ctx // T))
        _, l, acc = jax.lax.fori_loop(0, n_chunks, chunk, (
            jnp.full((n_kv, group, S), -1e30, jnp.float32),
            jnp.zeros((n_kv, group, S), jnp.float32),
            jnp.zeros((n_kv, group, S, hd), jnp.float32)))
        ctx = acc / jnp.where(l > 0, l, 1.0)[..., None]
    return ctx.transpose(2, 0, 1, 3).reshape(
        S, cfg.n_heads * hd).astype(out_dtype), picks


def sp_layer(h, w, i, cache, positions, pos_offset, n_valid,
             cfg: ModelConfig, live=None, kv_bound=None):
    """One block-sparse layer over S positions against layer ``i`` of the
    sparse layers' stacked weights and of the ``k``/``v``/``kc`` leaves.
    Returns (h, cache, picks (n_kv, S, n_blocks) bool: the blocks each
    query read)."""
    S, n_kv, hd = h.shape[0], cfg.n_kv_heads, cfg.head_dim
    hn, lin = _pre(h, w, i, cfg)
    q = _rms(lin(hn, "wq").reshape(S, cfg.n_heads, hd),
             w["attn_q_norm"][i], cfg.rms_eps).astype(hn.dtype)
    k = _rms(lin(hn, "wk").reshape(S, n_kv, hd),
             w["attn_k_norm"][i], cfg.rms_eps)
    v = lin(hn, "wv").reshape(S, n_kv, hd)
    kh = k.astype(cache["k"].dtype).transpose(1, 0, 2)    # (n_kv, S, hd)
    vh = v.astype(cache["v"].dtype).transpose(1, 0, 2)
    with jax.named_scope("kv_write"):
        ck = jax.lax.dynamic_update_slice(
            cache["k"], kh[None], (i, 0, pos_offset, 0))
        cv = jax.lax.dynamic_update_slice(
            cache["v"], vh[None], (i, 0, pos_offset, 0))
    kc, kw = _kc_write(cache["kc"], cache["kw"], kh, i, pos_offset, n_valid,
                       cfg)
    cache = {**cache, "k": ck, "v": cv, "kc": kc, "kw": kw}
    last = positions[S - 1]
    sp = is_sparse(last, cfg)
    if kv_bound is None:            # one sequence: its own position decides
        bound, any_sp, any_dense = None, sp, ~sp
    else:
        bound, any_sp, any_dense = kv_bound
    width = cfg.n_heads * hd

    def dense(_):
        lv = ~sp if live is None else live & ~sp
        ctx = _ring_attention(
            q, jax.lax.dynamic_index_in_dim(ck, i, 0, keepdims=False),
            jax.lax.dynamic_index_in_dim(cv, i, 0, keepdims=False),
            None, None, cache, i, positions, pos_offset, bound,
            lv if S == 1 else None, cfg, hn.dtype)
        return ctx, _all_visible(positions, cfg)[None].repeat(n_kv, 0)

    def sparse(_):
        if S == 1:
            ctx, picks = sparse_decode(
                q[0], cache, i, pos_offset,
                sp if live is None else live & sp, cfg, hn.dtype)
            return ctx[None], picks[:, None]
        return sparse_slice(q, cache, i, positions, cfg, hn.dtype)

    def nothing(_):
        return (jnp.zeros((S, width), hn.dtype),
                jnp.zeros((n_kv, S, n_blocks(cfg)), bool))

    if S == 1:
        ctx_d, picks_d = jax.lax.cond(any_dense, dense, nothing, None)
        ctx_s, picks_s = jax.lax.cond(any_sp, sparse, nothing, None)
        ctx = jnp.where(sp, ctx_s, ctx_d)
        picks = jnp.where(sp, picks_s, picks_d)
    else:
        # a slice holds a query past dense_len or it does not: one branch
        ctx, picks = jax.lax.cond(sp, sparse, dense, None)
    return _finish(h, hn, ctx.astype(jnp.float32), lin, w, i, cfg), cache, \
        picks


# ---------------------------------------------------------------------------
# the stack
# ---------------------------------------------------------------------------

def forward(params: dict, cfg: ModelConfig, tokens, pos_offset, cache: dict,
            last_idx=None, return_all: bool = False, live=None,
            with_picks: bool = False, kv_bound=None, with_stats=False):
    """``models/llama.py forward`` for a file of two layer kinds: the runs
    of :func:`runs` in order, each a ``fori_loop`` over its kind's stacked
    weights and cache leaves addressed by the layer's number within the
    kind (no per-layer copy of a fused plane, no restack of a cache).
    ``with_picks`` appends the blocks every query of every sparse layer
    read, (L_sp, n_kv, S, n_blocks) bool.  ``kv_bound``: a lane step's
    :func:`live_bounds` (None: this sequence's own position decides).
    ``with_stats`` is ``llama.forward``'s: no routed layer, nothing added."""
    S = tokens.shape[0]
    for kind in (LIN, SP):
        for path, leaf in jax.tree_util.tree_flatten_with_path(
                params["layers"][kind])[0]:
            if leaf.shape[0] != cfg.n_layers_of(kind):
                raise ValueError(
                    f"stacked leaf {kind}{jax.tree_util.keystr(path)} has "
                    f"{leaf.shape[0]} layers but the file names "
                    f"{cfg.n_layers_of(kind)} of that kind")
    h = (jnp.take(params["tok_emb"], tokens, axis=0).astype(jnp.float32)
         * cfg.emb_scale).astype(jnp.bfloat16)
    positions = pos_offset + jnp.arange(S, dtype=jnp.int32)
    n_valid = jnp.int32(S) if last_idx is None else last_idx + 1
    picks = jnp.zeros((cfg.n_layers_of(SP), cfg.n_kv_heads, S,
                       n_blocks(cfg)), bool) if with_picks else None

    def lin_body(i, carry):
        h, cache, *rest = carry
        h, cache = lin_layer(h, params["layers"][LIN], jnp.int32(i), cache,
                             positions, pos_offset, n_valid, cfg, live)
        return (h, cache, *rest)

    def sp_body(i, carry):
        h, cache, *rest = carry
        h, cache, mine = sp_layer(h, params["layers"][SP], jnp.int32(i),
                                  cache, positions, pos_offset, n_valid, cfg,
                                  live, kv_bound)
        if rest:
            rest = [jax.lax.dynamic_update_slice(
                rest[0], mine[None], (i, 0, 0, 0))]
        return (h, cache, *rest)

    carry = (h, cache) if picks is None else (h, cache, picks)
    for kind, first, count in runs(cfg):
        carry = jax.lax.fori_loop(
            first, first + count, lin_body if kind == LIN else sp_body, carry)
    h, new_cache, *tail = carry

    out_w = params["output"]

    def head(x):
        hn = (rms_norm(x, params["out_norm"], cfg.rms_eps).astype(jnp.float32)
              * cfg.logit_scale).astype(jnp.bfloat16)
        with jax.named_scope("head"):
            if "w" in out_w:
                # float32 logits: bf16 inputs, the sums and the result f32
                return jax.lax.dot_general(
                    hn, out_w["w"], (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
            return linear(hn, out_w).astype(jnp.float32)

    if return_all:
        return (head(h), new_cache, *tail)
    if last_idx is None:
        last_idx = jnp.int32(S - 1)
    h_last = jax.lax.dynamic_slice_in_dim(h, last_idx, 1, axis=0)
    return (head(h_last)[0], new_cache, *tail)


def _slice_rule(cfg: ModelConfig, chunk: int) -> str | None:
    if chunk % cfg.sp_block:
        return ("a prefill slice must be a multiple of its sparse layers' "
                f"block ({cfg.sp_block})")


def _probe_kernels(cfg: ModelConfig, asked: str, attn_impl: str, probed):
    """The state's decode step is a kernel too (ops/pallas/linstate.py);
    it and the ring's kernels degrade together."""
    if attn_impl == "pallas":
        from ..ops.pallas.probe import probe_lin_state

        probed.append("lin_state")
        err = probe_lin_state()
        if err is not None:
            logger.error("pallas linear-state step failed its compile "
                         "probe; serving with attn_impl=xla: %s", err)
            attn_impl = "xla"
    return cfg, attn_impl


def _health(cfg: ModelConfig, engine) -> dict:
    return {
        "kind": STATE_RING,
        "linear_layers": cfg.n_layers_of(LIN),
        "sparse_layers": cfg.n_layers_of(SP),
        "state_bytes": state_nbytes(cfg),
        "compressed_keys": n_kc(cfg),
        "blocks_read_at_most": n_select(cfg),
        "dense_len": cfg.sp_dense_len,
        "prefix_reuse": "off: a state cannot be rolled back to a "
                        "shared prefix",
        "kv_paged": "refused at start",
        "chat_template": engine.template_kind + (
            "" if engine._template_named else
            " (fallback: the file names no template known here)")}


def _note_decode(counts: dict, cfg: ModelConfig, wanted: list, n_steps: int,
                 live: list | None = None) -> None:
    counts.update(chunk_counts(wanted, n_steps, cfg))
    # the ring counters: the ring layers' dense reads, before dense_len
    note_ring_decode(counts, cfg, wanted, n_steps, live,
                     until=cfg.sp_dense_len - 1)


def _note_prefill(counts, cfg: ModelConfig, n_prompt: int, slices) -> dict:
    c = prefill_counts(n_prompt, cfg)
    for k in ("queries_dense", "queries_sparse", "kc_written"):
        counts[k] += c[k]
    return {"kc_closed": c["kc_closed"],
            "sparse_positions": c["sparse_positions"]}


CACHE = CacheKind(
    name=STATE_RING, arch="minicpm-sala",
    init=init_cache, nbytes=cache_nbytes,
    forward=forward,   # two layer kinds: its own loop, embedding and head
    # the sparse layers' two branches, each skipped where no live lane
    # takes it
    step_bound=lambda cfg, pos, live: live_bounds(pos, live, cfg),
    supports={
        "int8": "its state + ring cache is float32 + bf16 only",
        "paged": "the pool pages runs of ring slots by token position, and "
                 "its linear layers keep a state that cannot be rolled back "
                 "to a shared prefix"},
    slice_rule=_slice_rule,
    probe_kernels=_probe_kernels,
    # the ring layers write before they call the kernel
    decode_kernel_block=ring_kernel_block, kernel_writes=False,
    health=_health,
    own_gauges={
        "lin_state_updates_total": "state_updates",
        'sparse_queries_total{branch="dense"}': "queries_dense",
        'sparse_queries_total{branch="sparse"}': "queries_sparse",
        "sparse_blocks_read_total": "blocks_read",
        "sparse_blocks_visible_total": "blocks_visible",
        "sparse_kc_written_total": "kc_written"},
    note_decode=_note_decode, note_prefill=_note_prefill)
