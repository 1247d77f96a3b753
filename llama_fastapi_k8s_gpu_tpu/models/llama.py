"""Llama-family transformer as pure JAX functions.

This is the in-tree replacement for the model graph the reference runs inside
llama.cpp (``create_chat_completion``'s prefill/decode, reference
api.py:55-63): RMSNorm → GQA attention with interleaved RoPE → SwiGLU, over a
preallocated, donated KV cache.  Design choices are TPU-first:

- layers are *stacked* and iterated with ``lax.scan`` so XLA compiles one
  layer body regardless of depth (compile time ∝ 1, not n_layers);
- K/V are written with ``dynamic_update_slice`` into a ring of static
  shape; a prefill masks the full ``n_ctx`` ring (prompt lengths are
  bucketed by the engine to bound recompiles), a decode step reads it in
  blocks up to the newest live slot, under a TRACED bound: every position
  runs the one compiled decode program (a kernel whose bound is each
  lane's own, ops/pallas/attention.py ``flash_attention_decode``, or the
  XLA loop of :func:`decode_attention` under one bound for all lanes:
  :func:`decode_kernel_block` says which);
- sliding-window masking (Mistral) is the same mask with one extra term
  (a file whose window is a LAYER kind's keeps window slots for those
  layers instead: models/hybrid.py);
- matmuls go through ``ops.linear`` so bf16 / int8 / (later) fused-Q4_K
  weights are interchangeable without touching the graph.
- the feed-forward kind is the configuration's: dense SwiGLU, or
  (``cfg.n_experts``) a float32 router over SwiGLU experts whose products
  are computed for the picked experts only (ops/pallas/experts.py); so is
  the RMSNorm of Q and K (``cfg.qk_norm``) and the float32 residual
  stream (``cfg.fp32_residual``).  One layer body, not a copy.
- the cache KIND is one object (models/cache.py ``cache_of``) that
  :func:`init_cache`, :func:`forward` and the rest ask; the ring's: ``CACHE``.

RoPE is the *interleaved* (ggml "NORM") variant: GGUF conversion permutes
Q/K weights to this convention, so parity with llama.cpp requires it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..ops import linear
from ..ops.linear import linear_at
from . import eva
from .cache import CacheKind, cache_of
from .config import RING, ModelConfig


def rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    x32 = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return ((x32 * inv) * w.astype(jnp.float32)).astype(x.dtype)


def embed(params: dict, tokens, dim: int):
    """The tokens' rows of the embedding, bf16: of a float table its rows, of
    the tied Q6_K head's planes (models/params.py: ONE stored tensor, a
    ``phi4flash`` or ``jamba`` file's) the gathered rows dequantized
    (ops/pallas/q6matmul.py ``dequant_rows6``)."""
    emb = params["tok_emb"]
    if isinstance(emb, dict):
        from ..ops.pallas.q6matmul import dequant_rows6

        with jax.named_scope("embed_rows"):
            return dequant_rows6(emb, tokens, dim).astype(jnp.bfloat16)
    return jnp.take(emb, tokens, axis=0).astype(jnp.bfloat16)


def rope_interleaved(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (S, H, hd); rotate pairs (2i, 2i+1) by pos * theta^(-2i/hd)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[:, None].astype(jnp.float32) * freqs[None, :]  # (S, half)
    cos = jnp.cos(ang)[:, None, :]  # (S, 1, half)
    sin = jnp.sin(ang)[:, None, :]
    xf = x.astype(jnp.float32)
    x1 = xf[..., 0::2]
    x2 = xf[..., 1::2]
    o1 = x1 * cos - x2 * sin
    o2 = x1 * sin + x2 * cos
    return jnp.stack([o1, o2], axis=-1).reshape(x.shape).astype(x.dtype)


def rope_half(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (S, H, hd); rotate pairs (i, i + hd/2) by pos * theta^(-2i/hd):
    "rotate-half", ggml's NEOX mode, what Hugging Face computes on
    unpermuted Q/K."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[:, None].astype(jnp.float32) * freqs[None, :]  # (S, half)
    cos = jnp.cos(ang)[:, None, :]  # (S, 1, half)
    sin = jnp.sin(ang)[:, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1).astype(x.dtype)


def rope(x: jax.Array, positions: jax.Array, cfg: ModelConfig) -> jax.Array:
    """The rotary embedding in the pairing the file's architecture stores
    its Q/K for (``cfg.rope_neox``, from ``gguf.constants
    NEOX_ROPE_ARCHITECTURES``)."""
    fn = rope_half if cfg.rope_neox else rope_interleaved
    return fn(x, positions, cfg.rope_theta)


def init_cache(cfg: ModelConfig, dtype=jnp.bfloat16) -> dict:
    """The cache leaves of ONE sequence, of the configuration's kind
    (models/cache.py; docs/KV_CACHE.md "Cache kinds")."""
    return cache_of(cfg).init(cfg, dtype)


def cache_nbytes(cfg: ModelConfig) -> int:
    """HBM bytes of ONE sequence's cache (a lane engine holds one a lane):
    /health ``kv_cache_bytes`` and docs/KV_CACHE.md's lane-headroom math,
    from shapes, so callers never need a live cache."""
    return cache_of(cfg).nbytes(cfg)


def _init_ring(cfg: ModelConfig, dtype=jnp.bfloat16) -> dict:
    """KV ring, HEAD-MAJOR: (L, n_kv, n_ctx, hd), L = ``cfg.cache_leaves``
    (a leaf a layer, and a layer AND pass where layers run several times:
    ``cfg.ut_steps``).  Head-major is the layout
    every attention consumer reads (XLA decode scores, the flash kernel's
    per-head blocks, ring chunks), so readers slice it directly; the
    sequence-major alternative forced a full-ring transpose per layer per
    decode step and per flash prefill call (VERDICT r3 #9, ≤ ~1 ms/token
    at 8k).  Writers pay instead: the S NEW tokens' (S, n_kv, hd) slab is
    transposed before its dynamic_update_slice — S ≤ bucket-size, not
    n_ctx.

    ``cfg.kv_dtype == "int8"``: the quantized layout of docs/KV_CACHE.md,
    int8 ``k_q``/``v_q`` plus f32 scales ``k_s``/``v_s`` (L, n_kv, n_ctx)."""
    shape = (cfg.cache_leaves, cfg.n_kv_heads, cfg.n_ctx, cfg.head_dim)
    if cfg.kv_dtype == "int8":
        sshape = shape[:-1]
        return {
            "k_q": jnp.zeros(shape, jnp.int8),
            "v_q": jnp.zeros(shape, jnp.int8),
            "k_s": jnp.zeros(sshape, jnp.float32),
            "v_s": jnp.zeros(sshape, jnp.float32),
        }
    if cfg.kv_dtype not in ("bf16", "bfloat16"):
        raise ValueError(f"kv_dtype must be bf16|int8, got {cfg.kv_dtype!r}")
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _ring_nbytes(cfg: ModelConfig) -> int:
    per_tok_head = cfg.head_dim * (1 if cfg.kv_dtype == "int8" else 2) \
        + (4 if cfg.kv_dtype == "int8" else 0)
    return 2 * cfg.cache_leaves * cfg.n_kv_heads * cfg.n_ctx * per_tok_head


def xla_attention(q, kk, vv, cks, cvs, positions, cfg: ModelConfig,
                  out_dtype):
    """The XLA score-matrix attention over a full head-major ring: the
    small-prompt prefill path (S > 1; a decode step reads the live part
    only: :func:`decode_attention`).
    ``cks``/``cvs`` are the int8 cache's
    per-head per-token scales (None for bf16): scores are linear in K and
    probs·V is linear in V, so both scale sets fold OUTSIDE the int8
    contractions and no dequantized ring is ever materialized."""
    S = q.shape[0]
    n_kv, group, hd = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads, cfg.head_dim
    quant = cks is not None
    # (S, n_kv, group, hd) → (n_kv, group, S, hd)
    qg = q.reshape(S, n_kv, group, hd).transpose(1, 2, 0, 3)
    if quant:
        # scores are linear in K, so the per-token scale factors out of
        # the contraction: einsum over the RAW int8 ring (the int8→bf16
        # convert fuses into the dot's operand read — HBM moves int8),
        # then scale each key column once.  No dequantized ring is ever
        # materialized.
        with jax.named_scope("attn_scores"):
            scores = jnp.einsum(
                "ngsh,nch->ngsc", qg, kk.astype(qg.dtype),
                preferred_element_type=jnp.float32,
            ) * cfg.sm_scale * cks[:, None, None, :]
    else:
        with jax.named_scope("attn_scores"):
            scores = jnp.einsum(
                "ngsh,nch->ngsc", qg, kk, preferred_element_type=jnp.float32
            ) * cfg.sm_scale  # (n_kv, group, S, n_ctx)

    key_pos = jnp.arange(cfg.n_ctx)
    q_pos = positions  # (S,)
    # causal over the whole ring (S > 1 only: a decode step is decode_attention)
    mask = key_pos[None, :] <= q_pos[:, None]
    if cfg.sliding_window:
        mask &= key_pos[None, :] > q_pos[:, None] - cfg.sliding_window
    scores = jnp.where(mask[None, None, :, :], scores, -jnp.inf)
    if quant:
        # same trick on V: probs·(q·s) == (probs·s)·q — fold the value
        # scales into the (tiny) probability matrix, contract int8
        probs = (jax.nn.softmax(scores, axis=-1)
                 * cvs[:, None, None, :]).astype(qg.dtype)
        with jax.named_scope("attn_pv"):
            ctx = jnp.einsum("ngsc,nch->ngsh", probs, vv.astype(qg.dtype))
    else:
        probs = jax.nn.softmax(scores, axis=-1).astype(vv.dtype)
        with jax.named_scope("attn_pv"):
            ctx = jnp.einsum("ngsc,nch->ngsh", probs, vv)  # (n_kv, group, S, hd)
    return ctx.transpose(2, 0, 1, 3).reshape(S, cfg.n_heads * hd).astype(out_dtype)


#: ring slots the XLA loop of :func:`decode_attention` reads at a time
#: (the path of int8 rings and the CPU;
#: the kernel's block is ``DECODE_KERNEL_BLOCK``).  Measured on the
#: chip (PERF.md section 6, PR 31; 8 lanes x 32 layers, the read alone): at chat
#: lengths 128, 256 and 512 take the same time (an iteration costs 3-4 us
#: beside its bytes, which is what finer blocks would save in slots read
#: past the bound), 1024 reads too far past it (+14 %); with the ring full
#: 512 costs 12 % over one pass over the whole ring, 256 26 %, 128 41 %.
DECODE_KV_BLOCK = 512

#: ring slots the decode KERNEL copies at a time
#: (ops/pallas/attention.py ``flash_attention_decode``): (least, most, slots
#: x KV heads a copy).  There a block's fixed cost is a copy's issue and a
#: wait, not a loop iteration of two fusions, so finer blocks than the
#: loop's pay, down to about 512 KB a copy (2048 head-slots of 128 bf16):
#: 8 KV heads read fastest in blocks of 256, 16 in blocks of 128 (PERF.md
#: section 6, PR 37, has what was tried).
DECODE_KERNEL_BLOCK = (128, 512, 2048)


def decode_kernel_block(cfg: ModelConfig) -> int:
    """The block of the decode kernel where a decode step's attention runs
    it, else 0, by the cache kind's rule (``CacheKind.decode_kernel_block``:
    the ring's is :func:`ring_kernel_block`)."""
    return cache_of(cfg).decode_kernel_block(cfg)


def ring_kernel_block(cfg: ModelConfig) -> int:
    """The block of the decode kernel where a decode step's attention on a
    RING runs it, else 0 (the XLA loop of :func:`decode_attention`).
    Decided by what the configuration shows, no setting: the kernel serves
    a bf16 ring under ``attn_impl == "pallas"`` (a TPU, ``head_dim % 128 ==
    0``, the flash probe passed: engine/engine.py) whose slots its block
    divides; the block is a power of two by the KV heads a copy spans
    (``DECODE_KERNEL_BLOCK``)."""
    if cfg.attn_impl != "pallas" or cfg.kv_dtype == "int8":
        return 0
    least, most, head_slots = DECODE_KERNEL_BLOCK
    per_head = max(head_slots // cfg.n_kv_heads, 1)
    block = min(max(1 << (per_head.bit_length() - 1), least), most,
                cfg.n_ctx)
    return block if cfg.n_ctx % block == 0 and block % 16 == 0 else 0


def ring_write_impl(cfg: ModelConfig) -> str | None:
    """Who stores a decode step's K and V row in a ring: ``kernel`` where
    the layer hands the row to the decode kernel (its block is not 0; a
    lane that holds no request then stores nothing), else ``xla``
    (``dynamic_update_slice``: int8 rings, the CPU, the ring layers
    of models/sala.py; prefill slices on every path).  None on a cache
    that has no ring."""
    kind = cache_of(cfg)
    if kind.kernel_writes is None:
        return None
    return "kernel" if kind.kernel_writes \
        and kind.decode_kernel_block(cfg) else "xla"


def decode_read_slots(bound, n_ctx: int, block: int = 0):
    """(blocks, ring slots) a decode step's attention covers when the
    newest position it reads up to is ``bound``, in blocks of ``block``
    (0: the XLA loop's ``DECODE_KV_BLOCK``).  Integer arithmetic on a
    host int (the engines' ``ring_slots_*`` counters) or on a traced scalar
    (the loop's trip count) alike."""
    block = min(block or DECODE_KV_BLOCK, n_ctx)
    least = min if isinstance(bound, int) else jnp.minimum
    # ceil((bound + 1) / block), and no more blocks than the ring holds
    n_blocks = least((bound + block) // block, -(-n_ctx // block))
    return n_blocks, least(n_blocks * block, n_ctx)


def decode_chunk_slots(pos: int, n_steps: int, n_ctx: int,
                       bound: int | None = None,
                       block: int = 0) -> tuple[int, int]:
    """(ring slots read, ring slots live) of ONE sequence over ``n_steps``
    decode steps from position ``pos``: a step at position p has p + 1
    live slots (at or below it) and reads ``decode_read_slots`` of the
    step's bound, which starts at ``bound`` and walks with the steps.  The
    XLA loop's bound is the largest live lane's position and its block
    ``DECODE_KV_BLOCK``; the kernel's is the sequence's own position (the
    default) and ``block`` its ``decode_kernel_block``: ``ceil((pos + t +
    1) / block) * block`` slots a step (a sliding window's skipped blocks
    are not taken off, on either side).  Host arithmetic for the engines'
    ``ring_slots_*`` counters: no device fetch."""
    bound = pos if bound is None else bound
    read = live = 0
    for t in range(n_steps):
        read += decode_read_slots(bound + t, n_ctx, block)[1]
        live += min(pos + t + 1, n_ctx)
    return read, live


def decode_attention(q, cache, i, pos, bound, cfg: ModelConfig, out_dtype):
    """A decode step's attention (S = 1) over the LIVE part of layer
    ``i``'s ring, as a loop in plain XLA: the path where the decode kernel
    does not run (:func:`decode_kernel_block`: int8 rings, the CPU) and the reference tier-1 holds that kernel to.  K/V are read
    in blocks up to slot ``bound`` and no
    further, with a running max and sum (the flash recurrence in plain
    XLA), instead of all ``n_ctx`` slots behind a mask.  Slots past the
    position have probability exactly 0 either way, so this is
    :func:`xla_attention`'s mathematics at its precision (bf16 K/V, f32
    scores, max and sum, bf16 probabilities into a f32-accumulated PV):
    what differs is that a probability is rounded to bf16 BEFORE the
    division by the sum and not after, and the order of the f32 sums.

    ``q`` (1, n_heads, hd).  ``cache``: the STACKED leaves (L, n_kv, n_ctx,
    hd) (+ int8 scales), sliced in place at (i, head, block): handing
    ``at_layer(cache)`` into a loop can materialise the layer's ring.
    ``pos``: this sequence's position, the causal bound of its mask.
    ``bound``: the position the READ goes up to, >= ``pos`` of every
    sequence whose output is used.  Under ``vmap`` over lanes ``pos`` is
    per lane and ``bound`` must be unbatched (the largest live lane's
    position, parallel/batched.py): the trip count then stays a scalar and
    the loop a real loop.  A lane beyond ``bound`` (one that holds no
    request) reads too little; its output is never used.

    A LANE'S RESULT DOES NOT DEPEND ON ``bound``, so not on what the other
    lanes hold: a block that lies wholly beyond ``pos`` has every score at
    -inf, leaves the running max as it was, and adds probabilities of
    exactly 0.0 under a rescale of exactly exp(0) = 1.0, so max, sum and
    accumulator come out bit for bit as they went in; the block that holds
    ``pos`` is masked by ``pos`` alone.  The same lane with the same ring
    gives bitwise the same output under any bound >= its position
    (tests/test_decode_lanes.py), which is what lets a greedy probe repeat
    its text whatever its neighbours served in between.

    The read covers ``decode_read_slots(bound, n_ctx)`` slots; a last
    block that would overhang the ring is read shifted back, the overlap
    masked.  The sliding window is the same mask as in
    :func:`xla_attention`; int8 rings fold their scales as it does."""
    n_kv, group, hd = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads, cfg.head_dim
    n_ctx = cfg.n_ctx
    T = min(DECODE_KV_BLOCK, n_ctx)
    quant = cfg.kv_dtype == "int8"
    kname, vname = ("k_q", "v_q") if quant else ("k", "v")
    qg = q.reshape(n_kv, group, 1, hd)
    i = jnp.asarray(i, jnp.int32)
    pos = jnp.asarray(pos, jnp.int32)
    n_blocks, _ = decode_read_slots(jnp.asarray(bound, jnp.int32), n_ctx)

    def block(j, carry):
        m, l, acc = carry
        lo = j * T
        at = jnp.minimum(lo, n_ctx - T)
        kb = jax.lax.dynamic_slice(
            cache[kname], (i, 0, at, 0), (1, n_kv, T, hd))[0]
        vb = jax.lax.dynamic_slice(
            cache[vname], (i, 0, at, 0), (1, n_kv, T, hd))[0]
        with jax.named_scope("attn_scores"):
            s = jnp.einsum("ngsh,nch->ngsc", qg, kb.astype(qg.dtype),
                           preferred_element_type=jnp.float32
                           ) * cfg.sm_scale           # (n_kv, group, 1, T)
        if quant:
            ksb = jax.lax.dynamic_slice(
                cache["k_s"], (i, 0, at), (1, n_kv, T))[0]
            vsb = jax.lax.dynamic_slice(
                cache["v_s"], (i, 0, at), (1, n_kv, T))[0]
            s = s * ksb[:, None, None, :]
        key_pos = at + jnp.arange(T)
        mask = (key_pos >= lo) & (key_pos <= pos)
        if cfg.sliding_window:
            mask &= key_pos > pos - cfg.sliding_window
        s = jnp.where(mask[None, None, None, :], s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1)
        if quant:
            p = p * vsb[:, None, None, :]
        with jax.named_scope("attn_pv"):
            acc = acc * alpha[..., None] + jnp.einsum(
                "ngsc,nch->ngsh", p.astype(qg.dtype), vb.astype(qg.dtype),
                preferred_element_type=jnp.float32)
        return m_new, l, acc

    # a finite floor, not -inf: a block that holds no slot of this sequence
    # (all masked) must leave exp(m - m_new) = 1, not exp(-inf + inf)
    _, l, acc = jax.lax.fori_loop(0, n_blocks, block, (
        jnp.full((n_kv, group, 1), -1e30, jnp.float32),
        jnp.zeros((n_kv, group, 1), jnp.float32),
        jnp.zeros((n_kv, group, 1, hd), jnp.float32)))
    ctx = acc / jnp.where(l > 0, l, 1.0)[..., None]   # (n_kv, group, 1, hd)
    return ctx.transpose(2, 0, 1, 3).reshape(
        1, cfg.n_heads * hd).astype(out_dtype)


def route(hn, w_router, cfg: ModelConfig):
    """The router of a routed feed-forward: ``softmax(W_r · hn)`` over ALL
    experts in float32, the ``n_experts_used`` largest picked, their
    probabilities as they are unless ``cfg.norm_topk_prob``.  hn (S, dim),
    w_router (E, dim) f32 -> (picks (S, k) int32, weights (S, k) f32)."""
    logits = jnp.einsum("sd,ed->se", hn.astype(jnp.float32), w_router,
                        precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, picks = jax.lax.top_k(probs, cfg.n_experts_used)
    if cfg.norm_topk_prob:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return picks.astype(jnp.int32), weights


def expert_stats_len(cfg: ModelConfig) -> int:
    """Length of the routed layers' counter vector (:func:`forward`
    ``with_stats``): [(layer, step) pairs, distinct experts read summed
    over them, rows each HELD expert took..., the picks the live rows'
    routers made over ALL experts (what was not held is the difference:
    models/mla.py)[, of those the picks of a zero expert, where the router
    has any: models/routed.py]]."""
    return 3 + cfg.n_held + bool(cfg.n_zero_experts)


def layer_passes(cfg: ModelConfig) -> int:
    """Layer applications a token takes: the bodies :func:`forward`'s one
    loop runs (its trip count where layers run several times) and what the
    engines' ``layer_passes_total`` counts by."""
    return cfg.n_layers * cfg.ut_steps


def has_step_stats(cfg: ModelConfig) -> bool:
    """Whether a decode step's ``forward`` is asked ``with_stats`` (the
    decode programs of models/generate.py and parallel/batched.py): a
    routed block's counters, or the exit masses of a looped stack."""
    return bool(cfg.n_experts) or cfg.ut_steps > 1


def step_stats_zeros(cfg: ModelConfig, n_steps: int) -> jax.Array:
    """A chunk's rows of step statistics at 0: ``expert_stats_len`` int32
    counters a step, or ``ut_steps`` float32 exit masses."""
    if cfg.ut_steps > 1:
        return jnp.zeros((n_steps, cfg.ut_steps), jnp.float32)
    return jnp.zeros((n_steps, expert_stats_len(cfg)), jnp.int32)


def lanes_step_stats(cfg: ModelConfig, stats: jax.Array, alive) -> jax.Array:
    """One step's statistics of B lanes ``vmap``ped side by side, as one
    row: a routed block's counters are of the step and the same in every
    lane; exit masses are a lane's own and summed over the lanes ``alive``
    ((B,) bool or None: all), whose tokens are the ones decoded."""
    if cfg.ut_steps == 1:
        return stats[0]
    if alive is not None:
        stats = jnp.where(alive[:, None], stats, 0.0)
    return jnp.sum(stats, axis=0)


def exit_mass(lams: jax.Array) -> jax.Array:
    """The exit rule's mass a pass from the gate's outputs ``lams`` (T,):
    ``p_t = lam_t prod_{j<t} (1 - lam_j)``, the last pass takes what is
    left.  Sums to 1; a token leaves at the first pass whose cumulative
    mass reaches ``cfg.exit_threshold`` (at 1.0: the last)."""
    stay = jnp.concatenate([jnp.ones(1, lams.dtype),
                            jnp.cumprod(1.0 - lams[:-1])])
    return stay * jnp.concatenate([lams[:-1], jnp.ones(1, lams.dtype)])


def _kernel_decode(q, cache, i, pos, live, cfg: ModelConfig, dtype,
                   k_new=None, v_new=None):
    """A decode step's attention through the decode kernel
    (:func:`decode_kernel_block` is not 0): bounded by this sequence's own
    position, and nothing at all for a lane that holds no request.  (1,
    n_heads * head_dim) in ``dtype`` over a ring that holds the step's row;
    given the row (``k_new``, ``v_new`` (n_kv, hd)) the kernel stores it
    itself: (ctx, cache)."""
    from ..ops.pallas import flash_attention_decode, use_interpret

    out = flash_attention_decode(
        q[0], cache["k"], cache["v"], i, pos,
        True if live is None else live,
        sm_scale=cfg.sm_scale,
        block_k=ring_kernel_block(cfg),
        sliding_window=cfg.sliding_window,
        interpret=use_interpret(),
        k_new=k_new, v_new=v_new,
    )
    if k_new is None:
        return out[None].astype(dtype)
    ctx, k, v = out
    return ctx[None].astype(dtype), {"k": k, "v": v}


def _ring_attention(q, ck, cv, cks, cvs, cache, i, positions, pos_offset,
                    kv_bound, live, cfg: ModelConfig, dtype):
    """One layer's attention over a RING cache, after the write: ``ck`` /
    ``cv`` the layer's ring (``cks`` / ``cvs`` its int8 scales or None),
    by ``cfg.attn_impl`` and the pass's length.  (S, n_heads * head_dim)
    in ``dtype``."""
    S, hd, quant = q.shape[0], cfg.head_dim, cks is not None
    if cfg.attn_impl == "pallas" and S > 1:
        # blockwise flash kernel: streams K/V, never materializes scores;
        # int8 caches ride the fused-dequant path (scales folded in-kernel)
        from ..ops.pallas import flash_attention, use_interpret

        ctx = flash_attention(
            q, ck, cv, pos_offset,
            sm_scale=cfg.sm_scale,
            sliding_window=cfg.sliding_window,
            k_scale=cks,
            v_scale=cvs,
            interpret=use_interpret(),
        ).reshape(S, cfg.n_heads * hd).astype(dtype)
    elif S == 1 and ring_kernel_block(cfg):
        # a decode step reads the live part of the ring, not n_ctx slots
        # (models/sala.py's ring layers, which write before they call;
        # ``_layer`` hands the kernel the row to store)
        ctx = _kernel_decode(q, cache, i, pos_offset, live, cfg, dtype)
    elif S == 1:
        # the same read as a loop in plain XLA, under one bound for all lanes
        ctx = decode_attention(
            q, cache, i, pos_offset,
            pos_offset if kv_bound is None else kv_bound, cfg, dtype)
    else:
        ctx = xla_attention(q, ck, cv, cks, cvs, positions, cfg, dtype)
    return ctx


def _layer(h, layers, w, c, cache, positions, pos_offset,
           cfg: ModelConfig, live=None, kv_bound=None):
    """One transformer block over S tokens against row ``w`` of the
    stacked weights and leaf ``c`` of the cache: the same index wherever a
    layer of the file is a layer of the step, ``c = pass * n_layers + w``
    where layers run several times (``cfg.ut_steps``: a pass has its own
    keys and values, the weights have not).  ``cfg.sandwich_norm``: each
    sub-block's output passes a second RMSNorm before it joins the stream.
    ``cache``: the FULL stacked cache pytree, head-major
    (L, n_kv, n_ctx, hd) value leaves (+ (L, n_kv, n_ctx) scale leaves
    under ``kv_dtype=int8``).  Returns (h, cache, routed): None for the
    dense feed-forward, else (rows each expert took (E,) int32, the
    router's picks (S, k) int32).  ``live`` (scalar bool
    or None): False marks a lane that holds no request, whose rows then
    reach no expert and, where the decode kernel serves the ring, read no
    slot of it (its output is not read).  ``kv_bound``: see
    :func:`forward`.  On a window + summary cache the leaves are the
    window and summary leaves of models/eva.py, the S tokens lie inside ONE
    window, and ``kv_bound`` is the triple of ``eva.live_bounds``.

    The weights stay STACKED (L, ...) and are addressed per layer with
    :func:`ops.linear.linear_at` — scanning them as xs would materialize a
    per-layer copy of every fused quantized plane before its pallas_call
    (+6.3 ms/token measured on 8B v5e decode, tools/decode_breakdown.py).
    The cache is updated the same way: only the S new token slots of layer
    ``c`` are written (``dynamic_update_slice`` at (c, pos, 0, 0)); carrying
    per-layer caches through ``lax.scan`` xs/ys instead restacks the whole
    ring every step — ~256 MB/token at n_ctx 1024, ~2 GB at 8192."""
    S = h.shape[0]
    n_kv, group, hd = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads, cfg.head_dim
    quant = cfg.kv_dtype == "int8"

    def lin(x, name):
        # the projection's own name rides in the HLO's op_name metadata
        with jax.named_scope(name):
            return linear_at(x, layers[name], w)

    def at_layer(leaf):
        return jax.lax.dynamic_index_in_dim(leaf, c, axis=0, keepdims=False)

    def ring_write(leaf, new, at):
        with jax.named_scope("kv_write"):
            return jax.lax.dynamic_update_slice(leaf, new[None], at)

    def normed(x, name):
        # a float32 residual stream feeds the matmuls bf16 all the same
        xn = rms_norm(x, layers[name][w], cfg.rms_eps)
        return xn.astype(jnp.bfloat16) if cfg.fp32_residual else xn

    def joins(x, name):
        # what a sub-block adds to the stream: itself, or (sandwich norm)
        # its RMSNorm under the scope of the norm's name
        if not cfg.sandwich_norm:
            return x
        with jax.named_scope(name):
            return rms_norm(x, layers[name][w], cfg.rms_eps)

    hn = normed(h, "attn_norm")
    q, k = lin(hn, "wq"), lin(hn, "wk")
    if cfg.qk_norm:   # over the whole projection, before heads and RoPE
        q = rms_norm(q, layers["attn_q_norm"][w], cfg.rms_eps)
        k = rms_norm(k, layers["attn_k_norm"][w], cfg.rms_eps)
    q = q.reshape(S, cfg.n_heads, hd)
    k = k.reshape(S, n_kv, hd)
    v = lin(hn, "wv").reshape(S, n_kv, hd)
    q = rope(q, positions, cfg)
    k = rope(k, positions, cfg)

    ctx = None
    if cfg.eva_window:
        # the other cache kind: its write, its attention and its window
        # close are one step (models/eva.py)
        ctx, cache = eva.attend(
            q, k, v, cache, c, positions, kv_bound,
            layers["eva_phi"][w], layers["eva_mu"][w], cfg, hn.dtype)
    elif quant:
        # quantize ONLY the S new tokens' head-major slab (kvquant.py: int8
        # values + per-head per-token f32 scales), then write both planes
        from ..ops.pallas.kvquant import quantize_kv

        kq, ks = quantize_kv(k.transpose(1, 0, 2))     # (n_kv, S, hd)
        vq, vs = quantize_kv(v.transpose(1, 0, 2))
        cache = {
            "k_q": ring_write(cache["k_q"], kq, (c, 0, pos_offset, 0)),
            "v_q": ring_write(cache["v_q"], vq, (c, 0, pos_offset, 0)),
            "k_s": ring_write(cache["k_s"], ks, (c, 0, pos_offset)),
            "v_s": ring_write(cache["v_s"], vs, (c, 0, pos_offset)),
        }
        ck, cv = at_layer(cache["k_q"]), at_layer(cache["v_q"])
        cks, cvs = at_layer(cache["k_s"]), at_layer(cache["v_s"])
    else:
        # head-major write: transpose only the S new tokens, not the ring
        kh = k.astype(cache["k"].dtype).transpose(1, 0, 2)   # (n_kv, S, hd)
        vh = v.astype(cache["v"].dtype).transpose(1, 0, 2)
        cks = cvs = None
        if S == 1 and ring_kernel_block(cfg):
            # the decode kernel stores the step's row itself, into the
            # block it reads anyway, and nothing for a lane that holds no
            # request: no update of the lanes' stacked leaf beside it
            ctx, cache = _kernel_decode(q, cache, c, pos_offset, live, cfg,
                                        h.dtype, kh[:, 0], vh[:, 0])
        else:
            cache = {
                "k": ring_write(cache["k"], kh, (c, 0, pos_offset, 0)),
                "v": ring_write(cache["v"], vh, (c, 0, pos_offset, 0)),
            }
            ck, cv = at_layer(cache["k"]), at_layer(cache["v"])

    if ctx is None:     # a ring, written above: attend by impl and length
        ctx = _ring_attention(q, ck, cv, cks, cvs, cache, c, positions,
                              pos_offset, kv_bound, live, cfg, h.dtype)
    h = h + joins(lin(ctx, "wo"), "post_attn_norm")

    hn = normed(h, "ffn_norm")
    if cfg.n_experts:
        from ..ops.pallas.experts import routed_experts

        with jax.named_scope("router"):
            picks, weights = route(hn, layers["w_router"][w], cfg)
        if live is not None:
            picks = jnp.where(live, picks, cfg.n_experts)
        with jax.named_scope("experts"):
            out, count = routed_experts(
                hn, picks, weights, layers["w_gate_exps"],
                layers["w_up_exps"], layers["w_down_exps"], w)
        return h + out, cache, (count, picks)
    gated = jax.nn.silu(lin(hn, "w_gate").astype(jnp.float32)).astype(hn.dtype)
    h = h + joins(lin(gated * lin(hn, "w_up"), "w_down"), "post_ffn_norm")
    return h, cache, None


def forward(
    params: dict,
    cfg: ModelConfig,
    tokens: jax.Array,      # (S,) int32, padded to a static bucket
    pos_offset: jax.Array,  # scalar int32: cache position of tokens[0]
    cache: dict,
    last_idx: jax.Array | None = None,  # scalar int32: position of last real token
    return_all: bool = False,
    live: jax.Array | None = None,
    with_stats: bool = False,
    with_picks: bool = False,
    kv_bound: jax.Array | None = None,
    all_heads: bool = False,
):
    """Run S tokens through the stack. Returns (logits, new_cache):
    logits (vocab,) at ``last_idx`` (default S-1), or (S, vocab) if
    ``return_all``.  ``live``: see :func:`_layer`.  Of a routed block,
    ``with_stats`` appends the counter vector of :func:`expert_stats_len`
    and ``with_picks`` the routers' picks (L, S, k) int32 (what the
    comparison with the reference counts mismatches on); of a stack whose
    layers run several times (``cfg.ut_steps`` > 1) ``with_stats`` appends
    the exit gate's mass a pass (:func:`exit_mass`, (ut_steps,) float32) of
    the row at ``last_idx``, zeros where ``live`` is False.  ``kv_bound``
    (scalar int32, a decode step only): the ring slot the XLA loop of a
    decode step's attention reads up to (:func:`decode_attention`),
    default this sequence's own position; lanes ``vmap``ped over one step
    share the largest live lane's, as an UNBATCHED value (the decode
    kernel takes none: it reads up to this sequence's own position, and
    nothing where ``live`` is False); on a window + summary cache
    the triple of ``eva.live_bounds``.  ``all_heads``: the logits of every
    prediction head (``vocab_size * n_pred_heads`` rows) and not head 0's
    alone."""
    own = cache_of(cfg).forward
    if own is not None:   # a stack of the kind's own: its loops and head
        return own(params, cfg, tokens, pos_offset, cache, last_idx,
                   return_all, live, with_stats=with_stats,
                   with_picks=with_picks, kv_bound=kv_bound)
    S = tokens.shape[0]
    h = jnp.take(params["tok_emb"], tokens, axis=0).astype(
        jnp.float32 if cfg.fp32_residual else jnp.bfloat16)
    positions = pos_offset + jnp.arange(S, dtype=jnp.int32)

    # trace-time layer-count check over EVERY stacked leaf: looping over ids
    # (not weight xs) would otherwise let a config/checkpoint depth mismatch
    # silently clamp the per-layer gathers to the last real layer (scan over
    # xs used to enforce this shape agreement implicitly)
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            params["layers"])[0]:
        if leaf.shape[0] != cfg.n_layers:
            name = jax.tree_util.keystr(path)
            raise ValueError(
                f"stacked leaf {name} has {leaf.shape[0]} layers but "
                f"cfg.n_layers={cfg.n_layers}")

    routed = [jnp.zeros(expert_stats_len(cfg), jnp.int32),
              jnp.zeros((cfg.n_layers, S, cfg.n_experts_used), jnp.int32)] \
        if cfg.n_experts else []
    # fori_loop (not scan with cache xs/ys): the stacked cache rides the
    # carry and each layer writes only its S new token slots in place —
    # scan's ys-restack rewrites the entire ring every call (~256
    # MB/token at n_ctx 1024, ~2 GB at 8192 — measured as most of the
    # 8k decode gap)
    loop = cfg.ut_steps > 1
    gate = loop and with_stats    # the exit gate is computed where it is read

    def pass_end(h, t, lams=None):
        """What ends pass ``t``: the FINAL norm (its output feeds the next
        pass, and the head after the last), then the exit gate on the
        normed rows into ``lams`` (T, S), where that is carried."""
        with jax.named_scope("pass_norm"):
            h = rms_norm(h, params["out_norm"], cfg.rms_eps)
        if lams is None:
            return (h,)
        with jax.named_scope("exit_gate"):
            lam = jax.nn.sigmoid(jnp.einsum(
                "sd,d->s", h.astype(jnp.float32), params["exit_gate"]["w"],
                precision=jax.lax.Precision.HIGHEST)
                + params["exit_gate"]["b"])
        return h, jax.lax.dynamic_update_slice(lams, lam[None], (t, 0))

    def loop_body(i, carry):
        # ONE body for every (pass, layer) pair: weights at i % n_layers,
        # the cache leaf at i; the pass's end behind a conditional, so that
        # the other n_layers - 1 bodies of a pass pay nothing for it
        c = jnp.int32(i)
        w = c % cfg.n_layers
        with jax.named_scope("ut_pass"):
            h, cache, _ = _layer(
                carry[0], params["layers"], w, c, carry[1],
                positions, pos_offset, cfg, live, kv_bound)
        h, *lams = jax.lax.cond(
            w == cfg.n_layers - 1,
            lambda h, *lams: pass_end(h, c // cfg.n_layers, *lams),
            lambda h, *lams: (h, *lams), h, *carry[2:])
        return (h, cache, *lams)

    def body(i, carry):
        at = jnp.int32(i)       # a layer of the file is a leaf of the cache
        h, cache, out = _layer(
            carry[0], params["layers"], at, at, carry[1],
            positions, pos_offset, cfg, live, kv_bound)
        if out is None:
            return h, cache
        count, picks = out
        read = jnp.sum(count > 0, dtype=jnp.int32)
        stats = carry[2] + jnp.concatenate(
            [jnp.stack([jnp.int32(1), read]), count,
             jnp.sum(count, dtype=jnp.int32)[None]])   # every pick is held
        return h, cache, stats, jax.lax.dynamic_update_slice(
            carry[3], picks[None], (i, 0, 0))

    if loop:
        if cfg.n_experts:
            raise ValueError("layers that run several times (ut_steps "
                             f"{cfg.ut_steps}) have the dense feed-forward")
        lams = [jnp.zeros((cfg.ut_steps, S), jnp.float32)] if gate else []
        h, new_cache, *lams = jax.lax.fori_loop(
            0, layer_passes(cfg), loop_body, (h, cache, *lams))
        if gate:
            at = jnp.int32(S - 1) if last_idx is None else last_idx
            mass = exit_mass(jax.lax.dynamic_slice_in_dim(
                lams[0], at, 1, axis=1)[:, 0])
            routed = [mass if live is None else jnp.where(live, mass, 0.0)]
    else:
        h, new_cache, *routed = jax.lax.fori_loop(
            0, cfg.n_layers, body, (h, cache, *routed))

    out_w = params["output"]
    tail = tuple(r for r, want in zip(routed, (with_stats, with_picks))
                 if want)

    def head(x):
        # (a looped stack's rows left their last pass through the final norm)
        hn = x if loop else rms_norm(x, params["out_norm"], cfg.rms_eps)
        with jax.named_scope("head"):
            if cfg.fp32_residual and "w" in out_w:
                # float32 logits: bf16 inputs, the sums and the result f32
                logits = jax.lax.dot_general(
                    hn.astype(jnp.bfloat16), out_w["w"],
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
            else:
                logits = linear(hn.astype(jnp.bfloat16), out_w
                                ).astype(jnp.float32)
        if cfg.n_pred_heads > 1 and not all_heads:
            logits = logits[:, :cfg.vocab_size]   # head 0: the next token
        return logits

    if return_all:
        return (head(h), new_cache, *tail)
    if last_idx is None:
        last_idx = jnp.int32(S - 1)
    h_last = jax.lax.dynamic_slice_in_dim(h, last_idx, 1, axis=0)
    return (head(h_last)[0], new_cache, *tail)


def prefill(params, cfg: ModelConfig, tokens, length, cache):
    """Prompt pass: tokens padded to a bucket, ``length`` = real token count.
    Returns logits at the last real token."""
    return forward(params, cfg, tokens, jnp.int32(0), cache, last_idx=length - 1)


def decode_step(params, cfg: ModelConfig, token, pos, cache):
    """One autoregressive step: ``token`` at cache position ``pos``."""
    return forward(params, cfg, token[None], pos, cache)


def live_bound(pos: jax.Array, live: jax.Array | None = None) -> jax.Array:
    """The ring slot the XLA loop of a decode step's attention reads up
    to, ONE scalar for all lanes (:func:`decode_attention`): the largest
    position among the lanes that hold a request (``live`` (B,) bool; None:
    all).  A freed lane's position walks on and must not drag the read."""
    return jnp.max(pos if live is None else jnp.where(live, pos, 0))


def ring_step_bound(cfg: ModelConfig, pos: jax.Array, live=None):
    """A lane step's ``kv_bound`` on a ring.  Read by the decode kernel it
    needs none: the kernel's reach is PER LANE, the lane's own ``pos`` where
    it is ``live`` and nothing where not, and ``forward`` has both already.
    Read by the XLA loop it takes :func:`live_bound` (the trip count)."""
    return None if cache_of(cfg).decode_kernel_block(cfg) \
        else live_bound(pos, live)


def note_ring_decode(counts: dict, cfg: ModelConfig, wanted: list,
                     n_steps: int, live: list | None = None, *,
                     until: int | None = None) -> None:
    """Count one decode chunk's read of a ring against what it needed, per
    step and summed over the sequences at positions ``wanted`` (their
    first step's): :func:`decode_chunk_slots`.  Under the XLA loop every
    one reads up to the bound of the positions ``live`` (the lanes the
    chunk was dispatched as live; default ``wanted``); under the decode
    kernel each reads its OWN blocks and a lane not wanted reads nothing.
    Where the kernel stores the step's row too, every lane dispatched live
    stored its row in every layer of every step.  ``until``: the first
    position whose step no longer reads the ring so (models/sala.py)."""
    dispatched = wanted if live is None else live
    if until is not None:
        dispatched = [p for p in dispatched if p < until]
    kind = cache_of(cfg)
    block = kind.decode_kernel_block(cfg)
    bound = None if block else max(dispatched, default=0)
    if block and kind.kernel_writes:
        counts["rows_written"] += len(dispatched) * n_steps \
            * cfg.cache_leaves      # a row a leaf: a layer, sub-layer, pass
    for p in wanted:
        steps = n_steps if until is None else min(n_steps, max(until - p, 0))
        read, lv = decode_chunk_slots(p, steps, cfg.n_ctx, bound, block)
        counts["read"] += read
        counts["live"] += lv


def loop_attrs(cfg: ModelConfig) -> dict:
    """What a looped stack (``cfg.ut_steps`` > 1) puts on its traced
    ``prefill`` and ``decode_chunk`` spans: the passes, and the layer
    applications a token takes; nothing on any other."""
    if cfg.ut_steps == 1:
        return {}
    return {"ut_steps": cfg.ut_steps, "layer_passes": layer_passes(cfg)}


def _engine_health(cfg: ModelConfig) -> dict:
    """/health ``engine.loop`` of a looped stack; no key on any other."""
    if cfg.ut_steps == 1:
        return {}
    return {"loop": {"ut_steps": cfg.ut_steps, "layers": cfg.n_layers,
                     "cache_leaves": cfg.cache_leaves,
                     "exit_threshold": cfg.exit_threshold}}


CACHE = CacheKind(
    name=RING, arch="llama",
    init=_init_ring, nbytes=_ring_nbytes,
    step_bound=ring_step_bound,
    # the ring is what every feature was built on: it refuses nothing
    supports=dict.fromkeys(("int8", "paged"), True),
    rolls_back=True, always_slices=False,
    decode_kernel_block=ring_kernel_block, note_decode=note_ring_decode,
    span_attrs=loop_attrs, engine_health=_engine_health)
