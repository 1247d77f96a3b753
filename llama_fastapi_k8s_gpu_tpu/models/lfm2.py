"""The sixth cache KIND: a MIXER kind per layer, a gated short convolution
or GQA attention, each with a cache of its own shape
(``general.architecture = "lfm2moe"``; ``cfg.mixers``; ``cache_kind``
``conv-state+ring``).

- A ``"conv"`` layer: ``[b, c, x] = W_in hn`` (one matrix, dim -> 3 dim);
  ``u_t = b_t * x_t``; ``v_t = sum_j k_j * u_(t - (L - 1) + j)`` over the
  ``L = cfg.conv_l_cache`` F32 depthwise taps ``k`` (dim, L), ``u_s = 0``
  before the sequence; the branch is ``W_out (c_t * v_t)``.  What a
  sequence carries from one pass to the next is the last ``L - 1`` inputs
  ``u`` of the taps, leaf ``conv`` (conv layers, L - 1, dim), oldest first,
  in the stream's dtype, whatever the context.  Unlike models/sala.py's
  state it is an INPUT of the layer, not a sum: a slice lays its own ``u``
  behind the carried rows and slides the taps over the run
  (:func:`conv_mix`), then carries out the last ``L - 1`` rows that are
  REAL (a bucket's padding past the prompt's end never reaches the leaf).
  **The pass that starts at position 0 starts from zero rows** (admission
  prefills a scratch cache from position 0 and installs all of it in the
  lane: that is the reset of a freed lane).  A lane that holds no request
  keeps its rows as they were.  The rows cannot be rolled back to an
  earlier position, so prefix reuse and lane claims are off for this kind.
- An ``"attn"`` layer: GQA with RMSNorm of Q and K over each head's width
  and rotate-half RoPE, on a ring ``k`` / ``v`` (attention layers, n_kv /
  pack, n_ctx, head_dim x pack).  A head narrower than the 128 lanes of a
  tile (64 here) would leave half of every tile of the ring empty and is
  no shape the decode kernel's copies take, so ``pack = 128 // head_dim``
  KV heads lie SIDE BY SIDE in one row (:func:`ring_pack`), and the ring
  is read as ``n_kv / pack`` heads of 128 (:func:`ring_view`) by
  models/llama.py's ring functions and kernels as they stand: a query head
  is laid into its KV head's columns of an otherwise zero row of 128
  (:func:`pack_queries`: the zeros add exactly nothing to a score), the
  softmax scale stays the narrow head's (``cfg.attn_scale``), and of the
  weighted sum of packed value rows the head keeps its own columns
  (:func:`unpack_context`).  The scores' and the sums' MXU passes are
  ``pack`` times what the heads need; the bytes read are the ring's.
- The feed-forward kind is the layer's too (models/routed.py): leading
  dense layers, then a float32 sigmoid router over ALL the experts, held
  whole, no shared expert.  Weights are FOUR stacks, by mixer kind
  (``conv`` | ``attn``: the mixer's matrices and its norm) and by
  feed-forward kind (``dense`` | ``moe``: ``ffn_norm`` and the rest); the
  stack is walked as runs of one (feed-forward, mixer) kind, each a
  ``fori_loop`` (:func:`runs`).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ..ops.linear import linear, linear_at
from .cache import CacheKind
from .config import ATTN, CONV, CONV_RING, ModelConfig
from .llama import (
    _kernel_decode, _ring_attention, expert_stats_len, note_ring_decode,
    ring_kernel_block, ring_step_bound, rms_norm, rope)
from .routed import (
    DENSE, MOE, check_stacks, expert_branch, moe_stats, n_moe_layers, swiglu)

#: lanes of a tile: the width a ring's row is filled up to with whole heads
_LANES = 128


def ring_pack(cfg: ModelConfig) -> int:
    """KV heads that lie side by side in one row of the ring: as many as
    fill a tile's 128 lanes where that is a whole number that divides the
    KV heads, else 1."""
    hd = cfg.head_dim
    pack = _LANES // hd if hd < _LANES and _LANES % hd == 0 else 1
    return pack if cfg.n_kv_heads % pack == 0 else 1


def ring_view(cfg: ModelConfig) -> ModelConfig:
    """The configuration as the attention layers' ring sees it (what
    models/llama.py's ring functions take): one kind of layer, as deep as
    there are attention layers, ``n_kv / pack`` KV heads of ``head_dim x
    pack`` at the narrow heads' softmax scale."""
    pack = ring_pack(cfg)
    return dataclasses.replace(
        cfg, mixers=(), conv_l_cache=0, n_layers=cfg.n_layers_of(ATTN),
        n_kv_heads=cfg.n_kv_heads // pack, head_width=cfg.head_dim * pack,
        attn_scale=cfg.head_dim ** -0.5)


def _segments(cfg: ModelConfig):
    """(n_heads, pack) bool: the columns of a packed row that are each
    query head's KV head's."""
    pack = ring_pack(cfg)
    kv = jnp.arange(cfg.n_heads) // (cfg.n_heads // cfg.n_kv_heads)
    return (kv % pack)[:, None] == jnp.arange(pack)[None, :]


def pack_queries(q, cfg: ModelConfig):
    """(S, n_heads, hd) -> (S, n_heads, hd x pack): each head in its KV
    head's columns of a row of zeros."""
    S, H, hd = q.shape
    seg = _segments(cfg)
    return jnp.where(seg[None, :, :, None], q[:, :, None, :],
                     jnp.zeros((), q.dtype)).reshape(S, H, -1)


def unpack_context(ctx, cfg: ModelConfig):
    """(S, n_heads x hd x pack) -> (S, n_heads x hd): of each head's sum of
    packed value rows, its own KV head's columns."""
    S, H, pack = ctx.shape[0], cfg.n_heads, ring_pack(cfg)
    seg = _segments(cfg)
    own = jnp.where(seg[None, :, :, None], ctx.reshape(S, H, pack, -1),
                    jnp.zeros((), ctx.dtype))
    return jnp.sum(own, axis=2).reshape(S, -1)    # one term is not zero


def runs(cfg: ModelConfig) -> list[tuple[str, str, int, int, int]]:
    """The stack as runs of one (feed-forward kind, mixer kind), in order:
    (ffn kind, mixer kind, the run's first layer counted within its
    feed-forward stack of weights, within its mixer kind's weights and
    cache leaves, layers in the run)."""
    out = []
    seen = {DENSE: 0, MOE: 0, CONV: 0, ATTN: 0}
    for li, mixer in enumerate(cfg.mixers):
        ffn = DENSE if li < cfg.n_dense_layers else MOE
        if out and out[-1][:2] == [ffn, mixer]:
            out[-1][4] += 1
        else:
            out.append([ffn, mixer, seen[ffn], seen[mixer], 1])
        seen[ffn] += 1
        seen[mixer] += 1
    return [tuple(r) for r in out]


def init_cache(cfg: ModelConfig, dtype=jnp.bfloat16) -> dict:
    if cfg.kv_dtype not in ("bf16", "bfloat16"):
        raise ValueError(
            f"kv_dtype={cfg.kv_dtype!r} cannot hold architecture "
            "'lfm2moe': its conv-state + ring cache is bf16 only")
    g = ring_view(cfg)
    ring = (g.n_layers, g.n_kv_heads, cfg.n_ctx, g.head_dim)
    conv = (cfg.n_layers_of(CONV), cfg.conv_l_cache - 1, cfg.dim)
    return {"conv": jnp.zeros(conv, dtype),
            "k": jnp.zeros(ring, dtype), "v": jnp.zeros(ring, dtype)}


def conv_nbytes(cfg: ModelConfig) -> int:
    return cfg.n_layers_of(CONV) * (cfg.conv_l_cache - 1) * cfg.dim * 2


def cache_nbytes(cfg: ModelConfig) -> int:
    row = 2 * cfg.n_kv_heads * cfg.head_dim * 2            # K and V, bf16
    return row * cfg.n_layers_of(ATTN) * cfg.n_ctx + conv_nbytes(cfg)


# ---------------------------------------------------------------------------
# the two mixers
# ---------------------------------------------------------------------------

def conv_mix(u, taps, carried, n_valid):
    """The taps over a pass's inputs.  ``u`` (S, dim) the pass's own,
    ``taps`` (dim, L) f32 oldest first, ``carried`` (L - 1, dim) the inputs
    of the L - 1 positions before the pass (zeros where there are none),
    ``n_valid`` how many of the S rows are real.  Returns (v (S, dim) f32,
    the L - 1 inputs to carry on: those of the last REAL positions)."""
    S, L = u.shape[0], taps.shape[1]
    run = jnp.concatenate([carried.astype(u.dtype), u])       # (L - 1 + S, dim)
    v = sum(taps[:, j][None, :] * run[j:j + S].astype(jnp.float32)
            for j in range(L))
    # row t of the pass is row t + L - 1 of the run
    return v, jax.lax.dynamic_slice_in_dim(run, n_valid, L - 1, axis=0)


def _conv(h, w, i, cache, pos_offset, n_valid, cfg: ModelConfig, live):
    """One conv layer's mixer branch: a prefill slice and a decode step
    alike (a step is a pass of one real row).  ``i``: the layer within the
    conv layers' weights and cache leaf.  Returns (h + branch, cache)."""
    D = cfg.dim

    def lin(x, name):
        with jax.named_scope(name):
            return linear_at(x, w[name], i)

    hn = rms_norm(h, w["attn_norm"][i], cfg.rms_eps)
    with jax.named_scope("shortconv"):
        bcx = lin(hn, "in_proj")
        b, c, x = bcx[:, :D], bcx[:, D:2 * D], bcx[:, 2 * D:]
        with jax.named_scope("conv"):
            held = jax.lax.dynamic_index_in_dim(cache["conv"], i, 0,
                                                keepdims=False)
            # the pass that starts its sequence starts from zero rows
            carried = jnp.where(pos_offset == 0, jnp.zeros((), held.dtype),
                                held)
            v, carry_on = conv_mix(b * x, w["conv"][i], carried, n_valid)
            if live is not None:     # a lane that holds no request
                carry_on = jnp.where(live, carry_on, held)
            cache = dict(cache, conv=jax.lax.dynamic_update_slice(
                cache["conv"], carry_on[None].astype(held.dtype), (i, 0, 0)))
            gated = (c.astype(jnp.float32) * v).astype(h.dtype)
        out = lin(gated, "out_proj")
    return h + out, cache


def _attention(h, w, i, cache, positions, pos_offset, cfg: ModelConfig, live,
               kv_bound):
    """One attention layer's mixer branch, on the packed ring.  ``i``: the
    layer within the attention layers' weights and rings.  Returns (h +
    branch, cache)."""
    S = h.shape[0]
    n_kv, hd = cfg.n_kv_heads, cfg.head_dim
    g = ring_view(cfg)

    def lin(x, name):
        with jax.named_scope(name):
            return linear_at(x, w[name], i)

    hn = rms_norm(h, w["attn_norm"][i], cfg.rms_eps)
    q = lin(hn, "wq").reshape(S, cfg.n_heads, hd)
    k = lin(hn, "wk").reshape(S, n_kv, hd)
    v = lin(hn, "wv").reshape(S, n_kv, hd)
    q = rms_norm(q, w["attn_q_norm"][i], cfg.rms_eps)         # over a head
    k = rms_norm(k, w["attn_k_norm"][i], cfg.rms_eps)
    q, k = rope(q, positions, cfg), rope(k, positions, cfg)
    dtype = cache["k"].dtype
    # neighbouring KV heads side by side: (n_kv / pack, S, hd x pack)
    kh = k.astype(dtype).reshape(S, g.n_kv_heads, g.head_dim).transpose(1, 0, 2)
    vh = v.astype(dtype).reshape(S, g.n_kv_heads, g.head_dim).transpose(1, 0, 2)
    qp = pack_queries(q, cfg)
    ring = {"k": cache["k"], "v": cache["v"]}
    if S == 1 and ring_kernel_block(g):
        ctx, ring = _kernel_decode(qp, ring, i, pos_offset, live, g, h.dtype,
                                   kh[:, 0], vh[:, 0])
    else:
        with jax.named_scope("kv_write"):
            ring = {"k": jax.lax.dynamic_update_slice(
                        ring["k"], kh[None], (i, 0, pos_offset, 0)),
                    "v": jax.lax.dynamic_update_slice(
                        ring["v"], vh[None], (i, 0, pos_offset, 0))}
        ck = jax.lax.dynamic_index_in_dim(ring["k"], i, 0, keepdims=False)
        cv = jax.lax.dynamic_index_in_dim(ring["v"], i, 0, keepdims=False)
        ctx = _ring_attention(qp, ck, cv, None, None, ring, i, positions,
                              pos_offset, kv_bound, live, g, h.dtype)
    return h + lin(unpack_context(ctx, cfg), "wo"), dict(cache, **ring)


def layer(h, params, fi, mi, ffn: str, mixer: str, cache, positions,
          pos_offset, n_valid, cfg: ModelConfig, live=None, kv_bound=None):
    """One block: ``fi`` the layer within its feed-forward kind's stack of
    weights, ``mi`` within its mixer kind's weights and cache leaves.
    Returns (h, cache, None | the routed layer's counters: models/routed.py
    ``expert_branch``)."""
    if mixer == CONV:
        h, cache = _conv(h, params[CONV], mi, cache, pos_offset, n_valid,
                         cfg, live)
    else:
        h, cache = _attention(h, params[ATTN], mi, cache, positions,
                              pos_offset, cfg, live, kv_bound)
    w = params[ffn]
    hn = rms_norm(h, w["ffn_norm"][fi], cfg.rms_eps)
    if ffn == DENSE:
        return h + swiglu(hn, w, fi, "w_gate", "w_up", "w_down"), cache, None
    out, routed = expert_branch(hn, w, fi, cfg, live)
    return h + out, cache, routed


def forward(params: dict, cfg: ModelConfig, tokens, pos_offset, cache: dict,
            last_idx=None, return_all: bool = False, live=None,
            with_stats: bool = False, with_picks: bool = False,
            kv_bound=None):
    """``models/llama.py forward`` for a file whose mixer kind is the
    layer's: the runs of :func:`runs` in order, each a ``fori_loop`` over
    its kinds' stacked weights and its mixer kind's cache leaves.
    ``with_stats`` / ``with_picks`` as models/mla.py has them; ``kv_bound``:
    a lane step's ``live_bound`` (the attention layers' XLA loop)."""
    S = tokens.shape[0]
    check_stacks(params, cfg, tuple(
        (kind, cfg.n_layers_of(kind)) for kind in (CONV, ATTN)))
    n_moe = n_moe_layers(cfg)
    h = jnp.take(params["tok_emb"], tokens, axis=0).astype(jnp.bfloat16)
    positions = pos_offset + jnp.arange(S, dtype=jnp.int32)
    n_valid = jnp.int32(S) if last_idx is None else last_idx + 1

    def body_of(ffn, mixer, ffirst, mfirst):
        def body(t, carry):
            fi, mi = jnp.int32(ffirst + t), jnp.int32(mfirst + t)
            h, cache, routed = layer(
                carry[0], params["layers"], fi, mi, ffn, mixer, carry[1],
                positions, pos_offset, n_valid, cfg, live, kv_bound)
            if routed is None:
                return (h, cache, *carry[2:])
            return (h, cache, *moe_stats(carry[2], carry[3], fi, routed))

        return body

    carry = (h, cache, jnp.zeros(expert_stats_len(cfg), jnp.int32),
             jnp.zeros((n_moe, S, cfg.n_experts_used), jnp.int32))
    for ffn, mixer, ffirst, mfirst, count in runs(cfg):
        carry = jax.lax.fori_loop(
            0, count, body_of(ffn, mixer, ffirst, mfirst), carry)
    h, new_cache, *routed = carry
    tail = tuple(r for r, want in zip(routed, (with_stats, with_picks))
                 if want)

    def head(x):
        hn = rms_norm(x, params["out_norm"], cfg.rms_eps)
        with jax.named_scope("head"):
            return linear(hn.astype(jnp.bfloat16), params["output"]
                          ).astype(jnp.float32)

    if return_all:
        return (head(h), new_cache, *tail)
    if last_idx is None:
        last_idx = jnp.int32(S - 1)
    h_last = jax.lax.dynamic_slice_in_dim(h, last_idx, 1, axis=0)
    return (head(h_last)[0], new_cache, *tail)


# ---------------------------------------------------------------------------
# the kind's object
# ---------------------------------------------------------------------------

def _health(cfg: ModelConfig, engine) -> dict:
    return {
        "kind": CONV_RING,
        "l_cache": cfg.conv_l_cache,
        "conv_layers": cfg.n_layers_of(CONV),
        "ring_layers": cfg.n_layers_of(ATTN),
        "heads_per_ring_row": ring_pack(cfg),
        "bytes_per_lane": cache_nbytes(cfg),
        "conv_state_bytes": conv_nbytes(cfg),
        "dense_layers": cfg.n_dense_layers,
        "routed_layers": n_moe_layers(cfg),
        "experts_held": [cfg.experts_first, cfg.n_held],
        "experts_routed": cfg.n_experts,
        "prefix_reuse": "off: a convolution's carried inputs cannot be "
                        "rolled back to a prefix's end",
        "kv_paged": "refused at start"}


def _note_decode(counts: dict, cfg: ModelConfig, wanted: list, n_steps: int,
                 live: list | None = None) -> None:
    counts["state_updates"] += len(wanted) * n_steps * cfg.n_layers_of(CONV)
    # the ring's three, summed over the attention layers and no others
    g = ring_view(cfg)
    one = dict.fromkeys(("read", "live", "rows_written"), 0)
    note_ring_decode(one, g, wanted, n_steps, live)
    counts["rows_written"] += one["rows_written"]       # a row a ring layer
    counts["read"] += one["read"] * g.n_layers
    counts["live"] += one["live"] * g.n_layers


def _note_prefill(counts, cfg: ModelConfig, n_prompt: int, slices) -> dict:
    # nothing is reused, so every prompt's first pass is at position 0
    counts["state_starts"] += 1
    if slices is None:
        return {}
    return {"conv_layers": cfg.n_layers_of(CONV),
            "ring_layers": cfg.n_layers_of(ATTN), "slices": len(slices)}


def _attn_impl(cfg: ModelConfig, asked: str) -> str:
    """``auto`` by the width of a row of the RING, which is what the
    kernels read, not by a head's."""
    if asked == "auto" and jax.default_backend() == "tpu" \
            and ring_view(cfg).head_dim % _LANES == 0:
        return "pallas"
    return asked


CACHE = CacheKind(
    name=CONV_RING, arch="lfm2moe",
    init=init_cache, nbytes=cache_nbytes, forward=forward,
    step_bound=ring_step_bound,      # the attention layers' XLA loop
    supports={
        "int8": "its conv-state + ring cache is bf16 only",
        "paged": "a pool page is a run of ring slots by token position, and "
                 "its conv layers carry inputs that cannot be rolled back "
                 "to a shared prefix"},
    attn_impl=_attn_impl,
    # a slice's XLA attention holds (heads, rows, n_ctx) float32 scores
    widest_slice=lambda cfg: 0 if cfg.attn_impl == "pallas" else 256,
    decode_kernel_block=lambda cfg: ring_kernel_block(ring_view(cfg)),
    health=_health,
    own_gauges={"conv_state_updates_total": "state_updates",
                "conv_state_starts_total": "state_starts"},
    note_decode=_note_decode, note_prefill=_note_prefill)
