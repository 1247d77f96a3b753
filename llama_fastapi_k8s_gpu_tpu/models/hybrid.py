"""The fifth cache KIND: an attention kind per LAYER, window or global, each
on a cache leaf of its own size (``general.architecture = "exaone-moe"``;
``cfg.attn_kinds``; ``cache_kind`` ``window+global-ring``).

- A layer is ``"window"`` (causal over its last ``cfg.sliding_window``
  positions) or ``"global"`` (causal over all), in the file's order
  (``cfg.attn_kinds``).  The kinds whose layers rotate Q and K are
  ``cfg.rope_kinds`` (here the window layers: the global ones attend
  unrotated).  Q and K are RMS-normed over EACH head's width before that.
- The cache has a leaf pair per KIND, as deep as the kind has layers:
  ``k`` / ``v`` (n_global, n_kv, n_ctx, hd), a ring where slot = position,
  and ``kw`` / ``vw`` (n_window, n_kv, ``cfg.window_slots``, hd), where
  position p lives in slot ``p % window_slots`` and a new row overwrites the
  one the window has just let go.  A window layer never holds ``n_ctx``
  slots: at 9 window + 3 global layers and 16384 positions a sequence is
  0.21 GB, not 0.81.
- A decode step reads a window leaf WHOLE (it is the window), with the mask
  on the position each slot holds; on a TPU that is the decode kernel in
  its ``wrap`` form (ops/pallas/attention.py: it stores the step's row too),
  else the XLA form below, which tier-1 holds the kernel to.  A global
  layer's step is the ring's (models/llama.py), bounded per lane.
- A prefill slice in a window layer attends to the leaf's rows in position
  order followed by its own rows (``window_slots + S`` keys, whatever the
  slice's width) and then leaves its last REAL rows in the leaf: rows of
  padding past the prompt's end are never stored (on a ring they land
  beyond the sequence; here they would land on the window).
- A wrapped window cannot be rolled back to an earlier position, so prefix
  reuse and lane claims are off for this kind (engine/engine.py).
- The feed-forward kind is the layer's too (models/routed.py): leading
  dense layers, then a grouped float32 router over the experts HELD here
  plus a shared expert.  Weights are two stacks by feed-forward kind; the
  stack is walked as runs of one (feed-forward, attention) kind, each a
  ``fori_loop`` (:func:`runs`).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ..ops.linear import linear, linear_at
from .cache import CacheKind
from .config import GLOBAL, WINDOW, WINDOW_GLOBAL_RING, ModelConfig
from .llama import (
    _kernel_decode, _ring_attention, decode_read_slots, expert_stats_len,
    ring_kernel_block, ring_step_bound, rms_norm, rope)
from .routed import (
    DENSE, MOE, check_stacks, expert_branch, moe_stats, n_moe_layers, swiglu)


def ring_view(cfg: ModelConfig) -> ModelConfig:
    """The configuration as a GLOBAL layer's ring sees it: no window, one
    kind (what models/llama.py's ring functions take)."""
    return dataclasses.replace(cfg, sliding_window=0, attn_kinds=())


def window_block(cfg: ModelConfig) -> int:
    """The decode kernel's block on a WINDOW leaf where the kernel serves
    this file's decode steps (``ring_kernel_block``), else 0: the same
    rule over the leaf's slots."""
    return ring_kernel_block(cfg) and ring_kernel_block(
        dataclasses.replace(cfg, n_ctx=cfg.window_slots))


def runs(cfg: ModelConfig) -> list[tuple[str, str, int, int, int]]:
    """The stack as runs of one (feed-forward kind, attention kind), in
    order: (ffn kind, attention kind, the run's first layer counted within
    its feed-forward stack of weights, within its attention kind's cache
    leaves, layers in the run)."""
    out = []
    seen = {DENSE: 0, MOE: 0, WINDOW: 0, GLOBAL: 0}
    for li, attn in enumerate(cfg.attn_kinds):
        ffn = DENSE if li < cfg.n_dense_layers else MOE
        if out and out[-1][:2] == [ffn, attn]:
            out[-1][4] += 1
        else:
            out.append([ffn, attn, seen[ffn], seen[attn], 1])
        seen[ffn] += 1
        seen[attn] += 1
    return [tuple(r) for r in out]


def init_cache(cfg: ModelConfig, dtype=jnp.bfloat16) -> dict:
    if cfg.kv_dtype not in ("bf16", "bfloat16"):
        raise ValueError(
            f"kv_dtype={cfg.kv_dtype!r} cannot hold architecture "
            "'exaone-moe': its window + global cache is bf16 only")
    hd, n_kv = cfg.head_dim, cfg.n_kv_heads
    ring = (cfg.n_attn_layers(GLOBAL), n_kv, cfg.n_ctx, hd)
    win = (cfg.n_attn_layers(WINDOW), n_kv, cfg.window_slots, hd)
    return {"k": jnp.zeros(ring, dtype), "v": jnp.zeros(ring, dtype),
            "kw": jnp.zeros(win, dtype), "vw": jnp.zeros(win, dtype)}


def cache_nbytes(cfg: ModelConfig) -> int:
    row = 2 * cfg.n_kv_heads * cfg.head_dim * 2          # K and V, bf16
    return row * (cfg.n_attn_layers(GLOBAL) * cfg.n_ctx
                  + cfg.n_attn_layers(WINDOW) * cfg.window_slots)


def chunk_counts(positions: list[int], n_steps: int, cfg: ModelConfig,
                 bound: int | None = None) -> dict:
    """Slots the layers of each kind read and need over ``n_steps`` decode
    steps of the sequences at ``positions``, summed over the kind's LAYERS
    (the kinds' leaves differ in size, so a sum over kinds is in
    layer-slots): a global layer as the ring does (whole blocks up to the
    position, or up to ``bound`` under the XLA loop's one bound for all
    lanes), a window layer its whole leaf against the window's live
    positions.  Host arithmetic for the engines' ``{window,global}_slots_*``
    counters."""
    block = ring_kernel_block(cfg)
    n_w, n_g = cfg.n_attn_layers(WINDOW), cfg.n_attn_layers(GLOBAL)
    out = {"window_read": 0, "window_live": 0,
           "global_read": 0, "global_live": 0}
    for p in positions:
        for t in range(n_steps):
            at = (p if bound is None or block else bound) + t
            out["global_read"] += \
                n_g * decode_read_slots(at, cfg.n_ctx, block)[1]
            out["global_live"] += n_g * min(p + t + 1, cfg.n_ctx)
            out["window_read"] += n_w * cfg.window_slots
            out["window_live"] += n_w * min(p + t + 1, cfg.sliding_window)
    return out


# ---------------------------------------------------------------------------
# a window layer's attention
# ---------------------------------------------------------------------------

def slot_positions(pos, slots: int):
    """(slots,) the newest position <= ``pos`` that lives in each slot of a
    leaf that wraps (below 0: the slot holds none of this sequence)."""
    return pos - jnp.mod(pos - jnp.arange(slots, dtype=jnp.int32), slots)


def window_decode_attention(q, kw_l, vw_l, pos, cfg: ModelConfig, out_dtype):
    """A decode step's attention (S = 1) over a window layer's leaf, which
    holds the step's own row: plain XLA, the path where the decode kernel
    does not run and what tier-1 holds its ``wrap`` form to.  The flash
    recurrence's precision in one block: f32 scores, max and sum, bf16
    probabilities into an f32 weighted sum, the division last.  ``q`` (1,
    n_heads, hd); ``kw_l`` / ``vw_l`` (n_kv, slots, hd)."""
    n_kv, hd = cfg.n_kv_heads, cfg.head_dim
    qg = q.reshape(n_kv, cfg.n_heads // n_kv, hd)
    with jax.named_scope("attn_scores"):
        s = jnp.einsum("ngh,nth->ngt", qg, kw_l.astype(qg.dtype),
                       preferred_element_type=jnp.float32) * cfg.sm_scale
    key_pos = slot_positions(jnp.asarray(pos, jnp.int32), kw_l.shape[1])
    mask = (key_pos >= 0) & (key_pos > pos - cfg.sliding_window)
    s = jnp.where(mask[None, None, :], s, -jnp.inf)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    with jax.named_scope("attn_pv"):
        acc = jnp.einsum("ngt,nth->ngh", p.astype(qg.dtype),
                         vw_l.astype(qg.dtype),
                         preferred_element_type=jnp.float32)
    ctx = acc / jnp.sum(p, axis=-1, keepdims=True)
    return ctx.reshape(1, cfg.n_heads * hd).astype(out_dtype)


def _run_attention_xla(q, kt, vt, q0: int, first_key, window: int, out_dtype,
                       sm_scale: float):
    """S queries over a run of keys in position order: query s is row
    ``q0 + s`` of the run, rows below ``first_key`` hold no position."""
    S, n_heads, hd = q.shape
    n_kv, T, _ = kt.shape
    qg = q.reshape(S, n_kv, n_heads // n_kv, hd).transpose(1, 2, 0, 3)
    with jax.named_scope("attn_scores"):
        s = jnp.einsum("ngsh,nch->ngsc", qg, kt,
                       preferred_element_type=jnp.float32) * sm_scale
    c = jnp.arange(T)[None, :]
    row = (q0 + jnp.arange(S))[:, None]
    mask = (c <= row) & (c > row - window) & (c >= first_key)
    s = jnp.where(mask[None, None], s, -jnp.inf)
    probs = jax.nn.softmax(s, axis=-1).astype(vt.dtype)
    with jax.named_scope("attn_pv"):
        ctx = jnp.einsum("ngsc,nch->ngsh", probs, vt)
    return ctx.transpose(2, 0, 1, 3).reshape(S, n_heads * hd).astype(out_dtype)


def window_slice(q, kh, vh, cache, ci, pos_offset, n_valid, cfg: ModelConfig,
                 out_dtype):
    """A prefill slice's attention in a window layer, and its write.  ``q``
    (S, n_heads, hd); ``kh`` / ``vh`` (n_kv, S, hd) the slice's rows;
    ``ci`` the layer within the window leaves; ``n_valid`` the slice's
    real rows (the others are padding past the prompt's end).  The keys
    are the leaf's rows in position order (positions ``pos_offset -
    slots`` .. ``pos_offset - 1``) followed by the slice's; afterwards the
    leaf holds the last ``slots`` REAL positions.  Returns (ctx, cache)."""
    S = q.shape[0]
    slots, W = cfg.window_slots, cfg.sliding_window
    kw_l = jax.lax.dynamic_index_in_dim(cache["kw"], ci, 0, keepdims=False)
    vw_l = jax.lax.dynamic_index_in_dim(cache["vw"], ci, 0, keepdims=False)
    at = jnp.arange(slots, dtype=jnp.int32)
    order = jnp.mod(pos_offset + at, slots)
    kt = jnp.concatenate([jnp.take(kw_l, order, axis=1), kh], axis=1)
    vt = jnp.concatenate([jnp.take(vw_l, order, axis=1), vh], axis=1)
    first_key = jnp.maximum(slots - pos_offset, 0)
    if cfg.attn_impl == "pallas":
        from ..ops.pallas import flash_attention, use_interpret

        ctx = flash_attention(
            q, kt, vt, jnp.int32(slots), sm_scale=cfg.sm_scale,
            sliding_window=W, interpret=use_interpret(), first_key=first_key,
        ).reshape(S, -1).astype(out_dtype)
    else:
        ctx = _run_attention_xla(q, kt, vt, slots, first_key, W, out_dtype,
                                 cfg.sm_scale)
    # the leaf afterwards: slot s holds the newest real position that
    # lives there, from this slice where it has one, else what it held
    last = n_valid - 1
    src = last - jnp.mod(pos_offset + last - at, slots)
    take = (src >= 0)[None, :, None]
    src = jnp.maximum(src, 0)
    with jax.named_scope("kv_write"):
        cache = dict(
            cache,
            kw=jax.lax.dynamic_update_slice(
                cache["kw"], jnp.where(take, jnp.take(kh, src, axis=1),
                                       kw_l)[None], (ci, 0, 0, 0)),
            vw=jax.lax.dynamic_update_slice(
                cache["vw"], jnp.where(take, jnp.take(vh, src, axis=1),
                                       vw_l)[None], (ci, 0, 0, 0)))
    return ctx, cache


def window_step(q, kh, vh, cache, ci, pos, live, cfg: ModelConfig, out_dtype):
    """A decode step in a window layer: the row to slot ``pos % slots``,
    then the leaf whole.  ``kh`` / ``vh`` (n_kv, 1, hd)."""
    slots = cfg.window_slots
    block = window_block(cfg)
    if block:
        from ..ops.pallas import flash_attention_decode, use_interpret

        ctx, kw, vw = flash_attention_decode(
            q[0], cache["kw"], cache["vw"], ci, pos,
            True if live is None else live,
            sm_scale=cfg.sm_scale, block_k=block,
            sliding_window=cfg.sliding_window, interpret=use_interpret(),
            k_new=kh[:, 0], v_new=vh[:, 0], wrap=True)
        return ctx[None].astype(out_dtype), dict(cache, kw=kw, vw=vw)
    slot = jnp.mod(pos, slots)
    with jax.named_scope("kv_write"):
        cache = dict(
            cache,
            kw=jax.lax.dynamic_update_slice(
                cache["kw"], kh[None], (ci, 0, slot, 0)),
            vw=jax.lax.dynamic_update_slice(
                cache["vw"], vh[None], (ci, 0, slot, 0)))
    kw_l = jax.lax.dynamic_index_in_dim(cache["kw"], ci, 0, keepdims=False)
    vw_l = jax.lax.dynamic_index_in_dim(cache["vw"], ci, 0, keepdims=False)
    return window_decode_attention(q, kw_l, vw_l, pos, cfg, out_dtype), cache


# ---------------------------------------------------------------------------
# the layers
# ---------------------------------------------------------------------------

def _attention(h, layers, wi, ci, kind: str, cache, positions, pos_offset,
               n_valid, cfg: ModelConfig, live, kv_bound):
    """One layer's attention branch.  ``wi``: the layer within its stack of
    weights, ``ci``: within its attention kind's cache leaves.  Returns
    (h + branch, cache)."""
    S = h.shape[0]
    n_kv, hd = cfg.n_kv_heads, cfg.head_dim

    def lin(x, name):
        with jax.named_scope(name):
            return linear_at(x, layers[name], wi)

    hn = rms_norm(h, layers["attn_norm"][wi], cfg.rms_eps)
    q = lin(hn, "wq").reshape(S, cfg.n_heads, hd)
    k = lin(hn, "wk").reshape(S, n_kv, hd)
    v = lin(hn, "wv").reshape(S, n_kv, hd)
    q = rms_norm(q, layers["attn_q_norm"][wi], cfg.rms_eps)   # over a head
    k = rms_norm(k, layers["attn_k_norm"][wi], cfg.rms_eps)
    if kind in cfg.rope_kinds:
        q, k = rope(q, positions, cfg), rope(k, positions, cfg)
    dtype = cache["k"].dtype
    kh = k.astype(dtype).transpose(1, 0, 2)                   # (n_kv, S, hd)
    vh = v.astype(dtype).transpose(1, 0, 2)
    if kind == WINDOW:
        with jax.named_scope("window_attn"):
            if S == 1:
                ctx, cache = window_step(q, kh, vh, cache, ci, pos_offset,
                                         live, cfg, h.dtype)
            else:
                ctx, cache = window_slice(q, kh, vh, cache, ci, pos_offset,
                                          n_valid, cfg, h.dtype)
        return h + lin(ctx, "wo"), cache
    # a global layer: models/llama.py's ring, on this kind's leaves
    gcfg = ring_view(cfg)
    ring = {"k": cache["k"], "v": cache["v"]}
    if S == 1 and ring_kernel_block(gcfg):
        ctx, ring = _kernel_decode(q, ring, ci, pos_offset, live, gcfg,
                                   h.dtype, kh[:, 0], vh[:, 0])
    else:
        with jax.named_scope("kv_write"):
            ring = {"k": jax.lax.dynamic_update_slice(
                        ring["k"], kh[None], (ci, 0, pos_offset, 0)),
                    "v": jax.lax.dynamic_update_slice(
                        ring["v"], vh[None], (ci, 0, pos_offset, 0))}
        ck = jax.lax.dynamic_index_in_dim(ring["k"], ci, 0, keepdims=False)
        cv = jax.lax.dynamic_index_in_dim(ring["v"], ci, 0, keepdims=False)
        ctx = _ring_attention(q, ck, cv, None, None, ring, ci, positions,
                              pos_offset, kv_bound, live, gcfg, h.dtype)
    return h + lin(ctx, "wo"), dict(cache, **ring)


def layer(h, w, wi, ci, ffn: str, attn: str, cache, positions, pos_offset,
          n_valid, cfg: ModelConfig, live=None, kv_bound=None):
    """One block: ``w`` its feed-forward kind's stack of weights, ``wi`` the
    layer within it, ``ci`` the layer within its attention kind's cache
    leaves.  Returns (h, cache, None | the routed layer's counters:
    models/routed.py ``expert_branch``)."""
    h, cache = _attention(h, w, wi, ci, attn, cache, positions, pos_offset,
                          n_valid, cfg, live, kv_bound)
    hn = rms_norm(h, w["ffn_norm"][wi], cfg.rms_eps)
    if ffn == DENSE:
        return h + swiglu(hn, w, wi, "w_gate", "w_up", "w_down"), cache, None
    out, routed = expert_branch(hn, w, wi, cfg, live)
    return h + out, cache, routed


def forward(params: dict, cfg: ModelConfig, tokens, pos_offset, cache: dict,
            last_idx=None, return_all: bool = False, live=None,
            with_stats: bool = False, with_picks: bool = False,
            kv_bound=None):
    """``models/llama.py forward`` for a file whose attention kind is the
    layer's: the runs of :func:`runs` in order, each a ``fori_loop`` over
    its feed-forward kind's stacked weights and its attention kind's cache
    leaves.  ``with_stats`` / ``with_picks`` as models/mla.py has them;
    ``kv_bound``: a lane step's ``live_bound`` (the global layers' XLA
    loop; the window layers read their whole leaf)."""
    S = tokens.shape[0]
    check_stacks(params, cfg)
    n_moe = n_moe_layers(cfg)
    h = jnp.take(params["tok_emb"], tokens, axis=0).astype(jnp.bfloat16)
    positions = pos_offset + jnp.arange(S, dtype=jnp.int32)
    n_valid = jnp.int32(S) if last_idx is None else last_idx + 1

    def body_of(ffn, attn, wfirst, cfirst):
        def body(t, carry):
            wi, ci = jnp.int32(wfirst + t), jnp.int32(cfirst + t)
            h, cache, routed = layer(
                carry[0], params["layers"][ffn], wi, ci, ffn, attn, carry[1],
                positions, pos_offset, n_valid, cfg, live, kv_bound)
            if routed is None:
                return (h, cache, *carry[2:])
            return (h, cache, *moe_stats(carry[2], carry[3], wi, routed))

        return body

    carry = (h, cache, jnp.zeros(expert_stats_len(cfg), jnp.int32),
             jnp.zeros((n_moe, S, cfg.n_experts_used), jnp.int32))
    for ffn, attn, wfirst, cfirst, count in runs(cfg):
        carry = jax.lax.fori_loop(
            0, count, body_of(ffn, attn, wfirst, cfirst), carry)
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


def _health(cfg: ModelConfig, engine) -> dict:
    return {
        "kind": WINDOW_GLOBAL_RING,
        "window": cfg.sliding_window,
        "window_slots": cfg.window_slots,
        "window_layers": cfg.n_attn_layers(WINDOW),
        "global_layers": cfg.n_attn_layers(GLOBAL),
        "rotated": list(cfg.rope_kinds),
        "bytes_per_lane": cache_nbytes(cfg),
        "dense_layers": cfg.n_dense_layers,
        "routed_layers": n_moe_layers(cfg),
        "experts_held": [cfg.experts_first, cfg.n_held],
        "experts_routed": cfg.n_experts,
        "prefix_reuse": "off: a wrapped window cannot be rolled "
                        "back to a prefix's end",
        "kv_paged": "refused at start"}


def _note_decode(counts: dict, cfg: ModelConfig, wanted: list, n_steps: int,
                 live: list | None = None) -> None:
    dispatched = wanted if live is None else live
    if ring_kernel_block(cfg):   # the kernels store the step's rows
        counts["rows_written"] += len(dispatched) * n_steps * cfg.n_layers
    c = chunk_counts(wanted, n_steps, cfg, max(dispatched, default=0))
    counts.update(c)
    # the ring totals keep their meaning: the sum over kinds
    counts["read"] += c["window_read"] + c["global_read"]
    counts["live"] += c["window_live"] + c["global_live"]


CACHE = CacheKind(
    name=WINDOW_GLOBAL_RING, arch="exaone-moe",
    init=init_cache, nbytes=cache_nbytes, forward=forward,
    # the global layers' XLA loop; the window layers read their whole leaf
    step_bound=ring_step_bound,
    supports={
        "int8": "its window + global cache is bf16 only",
        "paged": "a pool page is a run of ring slots by token position, and "
                 "its window layers keep window slots that wrap"},
    decode_kernel_block=ring_kernel_block,   # both leaf kinds
    health=_health,
    own_gauges={"window_slots_read_total": "window_read",
                "window_slots_live_total": "window_live",
                "global_slots_read_total": "global_read",
                "global_slots_live_total": "global_live"},
    note_decode=_note_decode)
