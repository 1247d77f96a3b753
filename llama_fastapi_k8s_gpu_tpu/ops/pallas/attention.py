"""Blockwise flash attention for TPU (Pallas).

The reference's attention runs inside llama.cpp's CUDA kernels (reference
docker/Dockerfile.base:30-32); the XLA fallback in ``models/llama.py``
materializes the full (S, n_ctx) score matrix.  This kernel streams K/V
HBM→VMEM in blocks with an online softmax, so VMEM usage is O(block) and
``n_ctx`` can grow past 1024 (SURVEY.md §5 "Long-context") without the
scores ever hitting HBM.

Layout: GQA folds the ``group = n_heads // n_kv_heads`` query heads that
share one KV head into the row dimension, so each grid step is a dense
(BQ, hd) × (hd, BK) MXU matmul.  The kv-block index is the *last* grid
dimension — TPU grids execute sequentially, so the running max / sum /
accumulator live in VMEM scratch across kv steps and the output is written
once on the final step.

Multi-KV-block inner loop (``kv_unroll``): each grid step fetches a FUSED
K/V block of ``kv_unroll * block_k`` tokens and iterates the online-softmax
update over the ``block_k``-sized sub-blocks in-kernel (a trace-time Python
loop, so the math per sub-block — and therefore the result — is identical
to the unrolled grid).  Fewer grid launches amortize the per-step block-DMA
setup that dominates long-context prefill on this platform (docs/PERF.md
"Roofline, revised": the 8k+ TTFT floor was per-grid-step overhead, not
FLOPs), at the cost of ``kv_unroll``× the K/V VMEM residency per step.
``LFKT_FLASH_KV_UNROLL`` sets the default; the causal classifier still
skips/interior-specializes per sub-block, so a fused block pays VPU mask
work only for the sub-blocks that need it.

Paged-KV contract (``LFKT_KV_PAGED``, parallel/kvpool.py): the pool is
**page-contiguous**, not gathered — a radix-cache hit copies its pages
into the FRONT of an ordinary dense ring before prefill, so this kernel
always sees the same head-major ``(n_kv, n_ctx, hd)`` ring it was probed
and tuned for, with no page-table indirection in the block index maps
(the KER001-003 contract is unchanged, and paged greedy decode stays
bit-identical to dense).  A gathered variant — per-block page-id
prefetch feeding the K/V index maps — only pays once pages stop being
materialized locally, i.e. the disaggregated-prefill step (ROADMAP item
6) where the page pytree becomes the wire format; grow it from the
``kv_unroll`` block loop here when that lands.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...obs.devtime import register_program

# Large-but-finite mask value: keeps exp() well-defined when an entire block
# (or an entire padded row) is masked, unlike -inf.
DEFAULT_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)


def _attn_kernel(
    # scalar prefetch
    pos_ref,            # (1,) int32 — cache position of query token 0
    # inputs
    q_ref,              # (1, BQ, hd)
    k_ref,              # (1, U*BK, hd) — bf16, or int8 when quantized
    v_ref,              # (1, U*BK, hd)
    # quantized only (absent otherwise): per-token f32 scale blocks
    #   ks_ref          # (1, 1, U*BK)
    #   vs_ref          # (1, 1, U*BK)
    # outputs
    *rest,              # o_ref (1, BQ, hd), then scratch:
    # m_ref,            # (BQ, 128) f32  running max (lane-replicated)
    # l_ref,            # (BQ, 128) f32  running sum (lane-replicated)
    # acc_ref,          # (BQ, hd)  f32  running weighted sum
    seq_len: int,       # S — real (bucketed) query length
    block_q: int,
    block_k: int,
    kv_unroll: int,     # U — block_k-sized sub-blocks fused per grid step
    sm_scale: float,
    sliding_window: int,
    quantized: bool = False,
    key_floor: bool = False,   # pos_ref is (2,): [position, first real key]
    bounded: bool = False,     # pos_ref's LAST: the key steps the grid walks
):
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        ks_ref = vs_ref = None
        o_ref, m_ref, l_ref, acc_ref = rest
    qb = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Block-level causal classification (the VPU fix: the kernel was
    # mask/softmax-bound, spending identical VPU work on fully-masked
    # future blocks and on interior blocks that need no masking at all).
    # Query tokens of this tile: rows are (group, S)-flattened, so token =
    # row % S.  The tight span bound needs the tile to cover one contiguous
    # token range, which holds iff S % BQ == 0; any other shape (tile
    # wrapping mid-span, or spanning whole copies) falls back to the
    # conservative full range [0, S-1] — always correct, just fewer
    # skip/interior blocks.
    if block_q < seq_len and seq_len % block_q == 0:
        t_min = jax.lax.rem(qb * block_q, seq_len)
        t_max = t_min + block_q - 1
    else:
        t_min = 0
        t_max = seq_len - 1
    q_min = pos_ref[0] + t_min
    q_max = pos_ref[0] + t_max

    # The inner loop over the fused block's sub-blocks is a trace-time
    # Python loop (``u`` is static), so every sub-block runs the SAME
    # online-softmax update, in the same order, as the kv_unroll=1 grid —
    # the result is bit-identical; only the launch count changes.
    def _sub_block(u: int):
        kmin = (kb * kv_unroll + u) * block_k
        kmax = kmin + block_k - 1

        skip = kmin > q_max                        # fully in the masked future
        if sliding_window:
            skip |= kmax <= q_min - sliding_window  # fully behind the window
            interior = jnp.bool_(False)            # window edge → always mask
        else:
            interior = kmax <= q_min               # fully unmasked block
        if key_floor:
            # keys below the floor hold no position of this sequence
            skip |= kmax < pos_ref[1]
            interior &= kmin >= pos_ref[1]

        lo = u * block_k

        def _body(masked: bool):
            q = q_ref[0]                           # (BQ, hd)
            k = k_ref[0, lo:lo + block_k, :]       # (BK, hd)
            if quantized:
                # fused dequant, scale-last: scores are linear in K, so the
                # per-token scale factors out of the contraction — dot the
                # RAW int8 block (cast in-register; [-127,127] is exact in
                # any float), then scale each key column once.  HBM moved
                # int8.
                k = k.astype(q.dtype)
            scores = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * sm_scale                           # (BQ, BK)
            if quantized:
                scores = scores * ks_ref[0, :, lo:lo + block_k]  # (1, BK) bcast

            if masked:
                row = qb * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0)
                q_pos = pos_ref[0] + jax.lax.rem(row, seq_len)
                key_pos = kmin + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 1)
                mask = key_pos <= q_pos
                if sliding_window:
                    mask &= key_pos > q_pos - sliding_window
                if key_floor:
                    mask &= key_pos >= pos_ref[1]
                scores = jnp.where(mask, scores, DEFAULT_MASK_VALUE)

            m_prev = m_ref[:, :1]                  # (BQ, 1)
            l_prev = l_ref[:, :1]
            m_cur = jnp.max(scores, axis=-1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            alpha = jnp.exp(m_prev - m_new)        # rescale of old state
            p = jnp.exp(scores - m_new)            # (BQ, BK)
            l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)

            v = v_ref[0, lo:lo + block_k, :]       # (BK, hd)
            if quantized:
                # same trick on V: p·(q·s) == (p·s)·q — fold the value
                # scales into the (BQ, BK) probability tile, contract the
                # raw int8
                p = p * vs_ref[0, :, lo:lo + block_k]
                v = v.astype(q_ref.dtype)
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            acc_ref[...] = acc_ref[...] * alpha + pv
            m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
            l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

        @pl.when(jnp.logical_and(jnp.logical_not(skip), interior))
        def _interior():
            _body(masked=False)

        @pl.when(jnp.logical_and(jnp.logical_not(skip),
                                 jnp.logical_not(interior)))
        def _edge():
            _body(masked=True)

    for u in range(kv_unroll):
        _sub_block(u)

    # (a bounded walk reads its last step from the scalar the grid's extent
    # was set from, not from the grid)
    last = (pos_ref[2 if key_floor else 1] if bounded
            else pl.num_programs(2)) - 1

    @pl.when(kb == last)
    def _finish():
        l = l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)            # fully-masked (padded) rows
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _pick_block(n: int, preferred: int) -> int:
    for b in (preferred, 512, 256, 128, 64, 32, 16, 8):
        if b <= preferred and n % b == 0:
            return b
    return n


def _env_kv_unroll() -> int:
    """The ``LFKT_FLASH_KV_UNROLL`` default, read through the knob registry
    (lfkt-lint CFG001) at trace time — the qmatmul ``_env_variant``
    convention: env knobs for kernel geometry are process-lifetime choices
    baked into the compiled programs at first trace."""
    from ...utils.config import knob

    u = int(knob("LFKT_FLASH_KV_UNROLL"))
    if u < 1:
        raise ValueError(f"LFKT_FLASH_KV_UNROLL must be >= 1, got {u}")
    return u


#: fused key blocks a ring holds from which the prefill kernel's walk ENDS
#: at the slice's own end: a ring of up to this many is walked whole, every
#: step past the slice classified and skipped in the kernel (its K/V blocks
#: are fetched all the same); a longer one (131072 slots and more at the
#: default blocks) would spend a slice's time on steps that compute nothing,
#: so the key axis of its grid is a TRACED extent, ``ceil((pos + S) /
#: fused block)``.  Every ring up to 32768 slots keeps the program it had.
WALK_WHOLE_STEPS = 8


def flash_plan(S: int, n_heads: int, n_kv: int, n_ctx: int,
               block_q: int = 512, block_k: int = 1024,
               kv_unroll: int | None = None) -> dict:
    """The static plan of :func:`flash_attention` for these shapes: ``bq``
    rows a row tile, ``bk`` keys a sub-block, ``unroll`` sub-blocks and
    ``bkf`` keys a grid step, ``row_tiles`` and ``key_steps`` (the whole
    ring's), and ``bounded``: whether the key axis ends at the slice's end
    (:data:`WALK_WHOLE_STEPS`).  The kernel's builder and the counters of a
    cache kind (models/jamba.py ``prefill_walk``) read the same numbers."""
    gs = (n_heads // n_kv) * S
    bq = _pick_block(gs, block_q)
    bk = _pick_block(n_ctx, block_k)
    if kv_unroll is None:
        kv_unroll = _env_kv_unroll()
    # largest unroll <= requested whose fused block divides the ring
    u = max(1, min(int(kv_unroll), n_ctx // bk))
    while u > 1 and n_ctx % (bk * u):
        u -= 1
    bkf = bk * u                                   # fused K/V block
    steps = n_ctx // bkf
    return {"bq": bq, "bk": bk, "unroll": u, "bkf": bkf,
            "row_tiles": gs // bq, "key_steps": steps,
            "bounded": steps > WALK_WHOLE_STEPS}


def flash_steps_walked(plan: dict, end: int) -> int:
    """Key steps a call whose last query sits at position ``end - 1`` walks
    under ``plan``: up to the slice's end where the walk is bounded, else the
    ring's.  (Host arithmetic and traced alike.)"""
    if not plan["bounded"]:
        return plan["key_steps"]
    least = min if isinstance(end, int) else jnp.minimum
    return least((end + plan["bkf"] - 1) // plan["bkf"], plan["key_steps"])


@functools.partial(
    jax.jit,
    static_argnames=("sm_scale", "sliding_window", "block_q", "block_k",
                     "kv_unroll", "interpret"),
)
def flash_attention(
    q: jax.Array,          # (S, n_heads, hd)
    k: jax.Array,          # (n_kv_heads, n_ctx, hd) — full ring cache,
    v: jax.Array,          #   HEAD-MAJOR (models/llama.py init_cache)
    pos_offset: jax.Array, # scalar int32: cache position of q[0]
    sm_scale: float,
    sliding_window: int = 0,
    block_q: int = 512,
    block_k: int = 1024,
    kv_unroll: int | None = None,  # block_k sub-blocks fused per grid step
    #                                (None: LFKT_FLASH_KV_UNROLL)
    k_scale: jax.Array | None = None,  # (n_kv, n_ctx) f32 — int8 cache only
    v_scale: jax.Array | None = None,
    interpret: bool = False,
    first_key: jax.Array | None = None,  # scalar int32: keys below it are
    #                                      masked (a window layer's slice)
) -> jax.Array:
    """Causal (+ sliding-window) attention of S queries over the KV ring.

    Returns (S, n_heads, hd) in q.dtype.  The causal mask ``key_pos <=
    q_pos`` makes unwritten cache slots invisible, exactly like the XLA
    path in ``models/llama.py``.  K/V arrive head-major, which is the
    kernel's own block layout — no ring-sized transpose on the way in.

    With ``k_scale``/``v_scale`` (the int8 cache's per-head per-token
    scales, docs/KV_CACHE.md), K/V are int8 and the kernel dequantizes
    in-register — the ring's HBM traffic roughly halves, which is the
    whole point of ``kv_dtype=int8`` on a bandwidth-bound decode chip.

    ``kv_unroll`` fuses that many ``block_k`` sub-blocks into one grid
    step's K/V fetch and runs the online softmax over them in-kernel —
    numerically identical to the unrolled grid (same sub-block math, same
    order), but with ``kv_unroll``× fewer grid launches to pay per-step
    block-DMA setup for.  Clamped so the fused block still divides
    ``n_ctx`` (tiny rings degrade gracefully to the plain grid).

    A ring of more than :data:`WALK_WHOLE_STEPS` fused blocks is walked up
    to the block that holds the slice's LAST query and no further: the key
    axis of the grid has a traced extent (:func:`flash_steps_walked`), so no
    step, and no K/V fetch, is spent past the slice.  Causality makes the
    result the same to the bit: every key past the slice is masked for
    every query of it.

    ``first_key``: the keys are no ring of ``n_ctx`` positions but a run
    that starts before the sequence does (models/hybrid.py: a window
    layer's last window of cached rows, in position order, then the slice's
    own; ``pos_offset`` is then q[0]'s row in that run): rows below
    ``first_key`` hold no position and are masked.  The kernel is then
    named ``flash_attention_window``.
    """
    S, n_heads, hd = q.shape
    n_kv, n_ctx, _ = k.shape
    group = n_heads // n_kv
    gs = group * S
    quantized = k_scale is not None

    plan = flash_plan(S, n_heads, n_kv, n_ctx, block_q, block_k, kv_unroll)
    bq, bk, u, bkf = (plan[key] for key in ("bq", "bk", "unroll", "bkf"))
    # (a window layer's run of keys is short, and starts before position 0)
    bounded = plan["bounded"] and first_key is None

    # (S, n_kv, group, hd) → (n_kv, group*S, hd): row = g*S + s
    qg = q.reshape(S, n_kv, group, hd).transpose(1, 2, 0, 3).reshape(n_kv, gs, hd)
    kk = k                                         # (n_kv, n_ctx, hd)
    vv = v

    scalars = jnp.atleast_1d(pos_offset.astype(jnp.int32))
    key_steps = n_ctx // bkf
    if bounded:
        key_steps = flash_steps_walked(plan, scalars[0] + S)
    grid = (n_kv, gs // bq, key_steps)
    kernel = functools.partial(
        _attn_kernel,
        seq_len=S,
        block_q=bq,
        block_k=bk,
        kv_unroll=u,
        sm_scale=sm_scale,
        sliding_window=sliding_window,
        quantized=quantized,
        key_floor=first_key is not None,
        bounded=bounded,
    )
    if first_key is not None:
        scalars = jnp.concatenate(
            [scalars, jnp.atleast_1d(jnp.asarray(first_key, jnp.int32))])
    if bounded:
        scalars = jnp.concatenate([scalars, jnp.atleast_1d(key_steps)])
    in_specs = [
        pl.BlockSpec((1, bq, hd), lambda h, qb, kb, *_: (h, qb, 0)),
        pl.BlockSpec((1, bkf, hd), lambda h, qb, kb, *_: (h, kb, 0)),
        pl.BlockSpec((1, bkf, hd), lambda h, qb, kb, *_: (h, kb, 0)),
    ]
    operands = [qg, kk, vv]
    if quantized:
        # scales ride as (n_kv, 1, n_ctx): Mosaic wants a block's last two
        # dims (8, 128)-aligned or equal to the array's, and a (1, bkf)
        # block of a 2-D (n_kv, n_ctx) array is neither
        in_specs += [
            pl.BlockSpec((1, 1, bkf), lambda h, qb, kb, *_: (h, 0, kb)),
            pl.BlockSpec((1, 1, bkf), lambda h, qb, kb, *_: (h, 0, kb)),
        ]
        operands += [s.astype(jnp.float32).reshape(n_kv, 1, n_ctx)
                     for s in (k_scale, v_scale)]
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, bq, hd), lambda h, qb, kb, *_: (h, qb, 0)),
            scratch_shapes=[
                pltpu.VMEM((bq, 128), jnp.float32),
                pltpu.VMEM((bq, 128), jnp.float32),
                pltpu.VMEM((bq, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((n_kv, gs, hd), q.dtype),
        interpret=interpret,
        **({} if first_key is None else {"name": "flash_attention_window"}),
    )(scalars, *operands)

    # (n_kv, group, S, hd) → (S, n_heads, hd)
    return out.reshape(n_kv, group, S, hd).transpose(2, 0, 1, 3).reshape(S, n_heads, hd)


# ---------------------------------------------------------------------------
# the decode step (S = 1): every lane walks its own blocks of the ring
# ---------------------------------------------------------------------------

#: rows a lane's queries of one KV head are padded to a multiple of: the
#: bf16 tile's sublanes, so the (rows, hd) x (hd, T) score product is an
#: aligned MXU pass whatever the group (4 in GQA 32/8, 1 in MHA: 16 rows;
#: 20 heads on one KV head: 32)
_DECODE_ROWS = 16

#: rows of the ring the kernel copies back around the row it stores: one
#: bf16 tile's sublanes, what a block's slots are a multiple of
#: (models/llama.py ``decode_kernel_block``)
_ROW_TILE = 16


def _decode_kernel(
    # scalar prefetch
    i_ref,              # (1,) int32: the layer
    pos_ref,            # (B,) int32: each lane's position
    live_ref,           # (B,) int32: 0 = the lane holds no request
    # inputs
    q_ref,              # (1, n_kv, ROWS, hd): this lane's queries
    *rest,
    # store only:         kn_ref, vn_ref (1, n_kv, 1, hd): the step's rows
    # k_hbm, v_hbm        (B, L, n_kv, n_ctx, hd) in HBM, read in place
    # outputs
    # o_ref               (1, n_kv, ROWS, hd)
    # store only:         the rings again (aliased: the one buffer each)
    # scratch
    # kbuf, vbuf          (2, n_kv, T, hd): two slots, copy against compute
    # sem                 DMA semaphores (2 = k|v, 2 slots)
    # m_ref, l_ref        (n_kv, ROWS, 128) f32 running max and sum
    #                     (lane-replicated)
    # acc_ref             (n_kv, ROWS, hd) f32 running weighted sum
    # slot_ref            SMEM (1,): the slot the next block to consume is in
    # nxt_ref             SMEM (B + 1,): first lane >= c that reads anything
    # store only:         wsem, DMA semaphores (2 = k|v) of the rows' tiles
    # (``v_width``: ONE ring, one row, one buffer, one semaphore each; o_ref
    # and acc_ref ``v_width`` wide)
    block_k: int,
    n_ctx: int,
    sliding_window: int,
    sm_scale: float,
    store: bool = False,
    wrap: bool = False,
    v_width: int = 0,
    select: bool = False,
):
    """One grid step is one LANE: a loop over that lane's own blocks, from
    the sliding window's first to the one that holds its position, with a
    DYNAMIC trip count; a lane that holds no request runs no iteration and
    starts no copy.  The copy of a block's K and V (all KV heads at once)
    is started one block ahead, across the lanes too: a lane's last
    iteration starts the next reading lane's first block, so only the very
    first copy of a call is waited for in full.

    ``store``: the step's own K and V row (``kn_ref``, ``vn_ref``) is not
    in the ring yet and the kernel puts it there.  The block that holds
    the position is the last one a lane reads: once its copy has arrived
    the row is set into it IN VMEM (a select over the bf16 tile of
    ``_ROW_TILE`` rows that holds it: a single bf16 row is half a packed
    sublane and no copy's unit), attention reads the block as it then
    stands (the very values a write before the call would have left
    there, in the same order), and the tile is copied back to the ring
    while the block is computed on.  No read has to be ordered against
    the write: the one block that holds the row is in VMEM before the row
    is set.  A lane that holds no request stores nothing.

    ``wrap``: the leaf is a WINDOW layer's (models/hybrid.py): ``n_ctx`` is
    its slots, fewer than the positions a sequence walks; position p lives
    in slot ``p % n_ctx``, the step's row is stored there (over the row of
    position ``p - n_ctx``, which the window no longer holds), a lane reads
    the blocks that hold a live position (all of them once it has wrapped)
    and the mask is on the POSITION a slot holds, not on the slot.

    ``v_width``: the leaf is a LATENT ring's (models/mla.py: one row a
    position for all heads, ``n_kv`` 1): there is ONE ring, the keys are
    its whole rows and the values the same rows' first ``v_width`` columns,
    so a block is copied once and serves both products; the step's row is
    one row.

    ``select``: the first of ``rest`` is this lane's (1, 1, n_ctx) float32
    BIAS on the scores, 0 at a position the query may attend and ``-inf``
    elsewhere (models/mla.py ``select_topk``: a ``deepseek32`` file's
    selection, a mask on the blocks read)."""
    bias_ref = None
    if select:
        bias_ref, rest = rest[0], rest[1:]
    nr = 1 if v_width else 2          # rings, and all that is one a ring
    if store:
        new_refs, o_ref, rest = rest[:nr], rest[2 * nr], rest[2 * nr + 1:]
    else:
        new_refs, o_ref, rest = (), rest[nr], rest[:nr] + rest[nr + 1:]
    pairs = tuple(zip(rest[:nr], rest[nr:2 * nr]))  # (ring in HBM, buffer)
    # (``wsem``, the last: store only)
    sem, m_ref, l_ref, acc_ref, slot_ref, nxt_ref = rest[2 * nr:2 * nr + 6]
    wsem = rest[-1]
    kbuf, vbuf = pairs[0][1], pairs[-1][1]
    T = block_k
    b = pl.program_id(0)
    B = pl.num_programs(0)
    layer = i_ref[0]

    def span(lane):
        """[lo, hi): the blocks ``lane`` reads."""
        # (a leaf that wraps: every block once the position passed its end)
        p = jnp.minimum(pos_ref[lane], n_ctx - 1)
        hi = jnp.where(live_ref[lane] != 0, p // T + 1, 0)
        if not sliding_window or wrap:
            return 0, hi
        return jnp.minimum(jnp.maximum(p - sliding_window + 1, 0) // T, hi), hi

    def copies(lane, j, slot):
        at = pl.multiple_of(j * T, T)
        return [pltpu.make_async_copy(
            ring.at[lane, layer, :, pl.ds(at, T), :], buf.at[slot],
            sem.at[n, slot])
            for n, (ring, buf) in enumerate(pairs)]

    def start(lane, j, slot):
        for c in copies(lane, j, slot):
            c.start()

    def start_first(lane, slot):
        start(lane, span(lane)[0], slot)

    def tile_copies(slot, r, at):
        """The tile of rows [r, r + _ROW_TILE) of this lane's block in
        ``slot``, to the ring's slots from ``at`` on."""
        return [pltpu.make_async_copy(
            buf.at[slot, :, pl.ds(r, _ROW_TILE), :],
            ring.at[b, layer, :, pl.ds(at, _ROW_TILE), :], wsem.at[n])
            for n, (ring, buf) in enumerate(pairs)]

    @pl.when(b == 0)
    def _first():
        nxt_ref[B] = B

        def scan(t, _):
            c = B - 1 - t
            lo, hi = span(c)
            nxt_ref[c] = jnp.where(hi > lo, c, nxt_ref[c + 1])
            return 0

        jax.lax.fori_loop(0, B, scan, 0)
        slot_ref[0] = 0

        @pl.when(nxt_ref[0] < B)
        def _():
            start_first(nxt_ref[0], 0)

    # a finite floor, not -inf (models/llama.py decode_attention): a block
    # of a window's first, wholly masked slots must leave exp(m - m_new) = 1
    m_ref[...] = jnp.full_like(m_ref, -1e30)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    lo, hi = span(b)
    pos = pos_ref[b]
    # the block that holds the step's row: the last one read, or on a leaf
    # that wraps the one of slot ``pos % n_ctx``
    row_block = jax.lax.rem(pos, n_ctx) // T if wrap else hi - 1

    def block(j, _):
        slot = slot_ref[0]

        @pl.when(j + 1 < hi)
        def _():
            start(b, j + 1, 1 - slot)

        @pl.when(jnp.logical_and(j + 1 >= hi, nxt_ref[b + 1] < B))
        def _():
            start_first(nxt_ref[b + 1], 1 - slot)

        for c in copies(b, j, slot):
            c.wait()
        if store:
            @pl.when(j == row_block)
            def _():
                # the slot ``dynamic_update_slice`` would write: clamped
                at = (jax.lax.rem(pos, n_ctx) if wrap
                      else jnp.minimum(pos, n_ctx - 1)) - j * T
                r = pl.multiple_of(at // _ROW_TILE * _ROW_TILE, _ROW_TILE)
                for (_, buf), new_ref in zip(pairs, new_refs):
                    tile = buf[slot, :, pl.ds(r, _ROW_TILE), :]
                    row = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1)
                    buf[slot, :, pl.ds(r, _ROW_TILE), :] = jnp.where(
                        row == at - r,
                        jnp.broadcast_to(new_ref[0], tile.shape), tile)
                for c in tile_copies(slot, r,
                                     pl.multiple_of(j * T + r, _ROW_TILE)):
                    c.start()

        q = q_ref[0]                                   # (n_kv, ROWS, hd)
        k = kbuf[slot]                                 # (n_kv, T, hd)
        v = vbuf[slot, :, :, :v_width] if v_width else vbuf[slot]
        s = jnp.einsum("ngh,nth->ngt", q, k,
                       preferred_element_type=jnp.float32) * sm_scale
        key_pos = j * T + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        if wrap:
            # the newest position <= pos that lives in the slot (below 0:
            # the slot holds none of this sequence yet)
            key_pos = pos - jax.lax.rem(pos + n_ctx - key_pos, n_ctx)
            mask = key_pos >= 0
        else:
            mask = key_pos <= pos
        if sliding_window:
            mask &= key_pos > pos - sliding_window
        s = jnp.where(mask, s, -jnp.inf)
        if select:
            s = s + bias_ref[0, :, pl.ds(pl.multiple_of(j * T, T), T)][None]
        m = m_ref[:, :, :1]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l_ref[:, :, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.einsum(
            "ngt,nth->ngh", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)
        slot_ref[0] = 1 - slot
        if store and wrap:
            # the row's block need not be the last: its tile is back in
            # the leaf before a later block's copy may land in its slot
            @pl.when(j == row_block)
            def _():
                for c in tile_copies(0, 0, 0):
                    c.wait()
        return 0

    jax.lax.fori_loop(lo, hi, block, 0)
    if store and not wrap:
        # the tile's copy ran beside the last block's arithmetic; its slot
        # is copied into again by the next lane's first iteration
        @pl.when(hi > lo)
        def _():
            for c in tile_copies(0, 0, 0):
                c.wait()

    l = l_ref[:, :, :1]
    o_ref[0] = (acc_ref[...] / jnp.where(l > 0, l, 1.0)).astype(o_ref.dtype)


def _decode_lanes(q, i, pos, live, *arrays, block_k: int, sm_scale: float,
                  sliding_window: int, interpret: bool, wrap: bool = False,
                  v_width: int = 0, select: bool = False):
    """q (B, n_heads, hd), i scalar, pos and live (B,), then the rings k / v
    (B, L, n_kv, n_ctx, hd) -> (B, n_heads * hd) in q.dtype: ONE kernel over
    the lanes.  With the step's rows ``k_new`` / ``v_new`` (B, n_kv, hd)
    after the rings the kernel stores them at (lane, i, :, pos, :) of the
    rings, which it then returns beside the context as outputs aliased onto
    their inputs.  ``wrap``: k / v are a window layer's leaves, whose
    ``n_ctx`` slots wrap; the kernel is then named
    ``flash_attention_decode_window``.  ``v_width``: ONE ring (and one row)
    in place of two, a latent leaf (B, L, 1, n_ctx, hd) whose rows are the
    keys and their first ``v_width`` columns the values: (B, n_heads *
    v_width); the kernel is then named ``flash_attention_decode_latent``.
    ``select``: the LAST array is the lanes' bias on the scores (B, n_ctx)
    float32 (0 | -inf: ``_decode_kernel``); the kernel is then named
    ``flash_attention_decode_latent_select``."""
    n = 1 if v_width else 2
    bias = ()
    if select:
        arrays, bias = arrays[:-1], (arrays[-1][:, None, :],)
    rings, rows = arrays[:n], arrays[n:]
    B, n_heads, hd = q.shape
    _, _, n_kv, n_ctx, _ = rings[0].shape
    group = n_heads // n_kv
    store = bool(rows)
    if n_ctx % block_k:
        raise ValueError(f"the ring's {n_ctx} slots are no multiple of the "
                         f"decode kernel's block of {block_k}")
    if store and block_k % _ROW_TILE:
        raise ValueError(f"the decode kernel's block of {block_k} slots is "
                         f"no multiple of the {_ROW_TILE} rows it stores by")
    n_rows = -(-group // _DECODE_ROWS) * _DECODE_ROWS
    out_w = v_width or hd
    qg = jnp.pad(q.reshape(B, n_kv, group, hd),
                 ((0, 0), (0, 0), (0, n_rows - group), (0, 0)))
    lane_block = pl.BlockSpec((1, n_kv, n_rows, hd),
                              lambda b, *_: (b, 0, 0, 0))
    out_block = pl.BlockSpec((1, n_kv, n_rows, out_w),
                             lambda b, *_: (b, 0, 0, 0))
    row_block = pl.BlockSpec((1, n_kv, 1, hd), lambda b, *_: (b, 0, 0, 0))
    in_place = pl.BlockSpec(memory_space=pl.ANY)
    ctx_shape = jax.ShapeDtypeStruct((B, n_kv, n_rows, out_w), q.dtype)
    new = [x.reshape(B, n_kv, 1, hd) for x in rows]
    out = pl.pallas_call(
        functools.partial(_decode_kernel, block_k=block_k, n_ctx=n_ctx,
                          sliding_window=sliding_window, sm_scale=sm_scale,
                          store=store, wrap=wrap, v_width=v_width,
                          select=select),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[lane_block] + [pl.BlockSpec(
                (1, 1, n_ctx), lambda b, *_: (b, 0, 0))] * len(bias)
            + [row_block] * len(new) + [in_place] * n,
            out_specs=[out_block] + [in_place] * n if store else out_block,
            scratch_shapes=[
                pltpu.VMEM((2, n_kv, block_k, hd), r.dtype) for r in rings
            ] + [
                pltpu.SemaphoreType.DMA((n, 2)),
                pltpu.VMEM((n_kv, n_rows, 128), jnp.float32),
                pltpu.VMEM((n_kv, n_rows, 128), jnp.float32),
                pltpu.VMEM((n_kv, n_rows, out_w), jnp.float32),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.SMEM((B + 1,), jnp.int32),
            ] + ([pltpu.SemaphoreType.DMA((n,))] if store else []),
        ),
        out_shape=[ctx_shape] + [jax.ShapeDtypeStruct(r.shape, r.dtype)
                                 for r in rings] if store else ctx_shape,
        # the rings (after the three prefetched scalars, the queries and
        # the rows), updated in place
        input_output_aliases={4 + len(bias) + n + r: 1 + r for r in range(n)}
        if store else {},
        # lanes in order: the slot parity and the copy started ahead are
        # carried from one lane to the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="flash_attention_decode" + (
            "_latent" if v_width else "_window" if wrap else "") + (
            "_select" if select else ""),
    )(jnp.asarray(i, jnp.int32).reshape(1), pos.astype(jnp.int32),
      live.astype(jnp.int32), qg, *bias, *new, *rings)
    ctx, *rings = out if store else (out,)
    ctx = ctx[:, :, :group, :].reshape(B, n_heads * out_w)
    return (ctx, *rings) if store else ctx


@functools.lru_cache(maxsize=8)
def _decode_vmappable(block_k: int, sm_scale: float, sliding_window: int,
                      interpret: bool, wrap: bool = False, v_width: int = 0,
                      select: bool = False):
    """The per-sequence call with its vmap rule: lanes ``vmap``ped over one
    step become ONE kernel over (B lanes), as the fused matmuls' rows do
    (qmatmul.py ``rows_vmappable``); without the rule ``vmap`` would batch
    the kernel's grid and every lane would run the longest lane's trips.
    ``rings``: the two rings or the one latent leaf; ``rows``: nothing, or
    the step's row for each (the rings are then returned beside the
    context, all batched).  ``select``: the LAST of ``rows`` is no row but
    the sequence's bias on the scores (n_ctx,) (``_decode_lanes``)."""
    from jax.custom_batching import custom_vmap

    lanes = functools.partial(
        _decode_lanes, block_k=block_k, sm_scale=sm_scale,
        sliding_window=sliding_window, interpret=interpret, wrap=wrap,
        v_width=v_width, select=select)

    @custom_vmap
    def one(q, rings, i, pos, live, rows):
        q, rings, pos, live, rows = jax.tree.map(
            lambda x: x[None], (q, rings, pos, live, rows))
        out = lanes(q, i, pos, live, *rings, *rows)
        return jax.tree.map(lambda x: x[0], out)

    @one.def_vmap
    def _rule(axis_size, in_batched, q, rings, i, pos, live, rows):  # noqa: ANN001
        if in_batched[2]:
            raise NotImplementedError(
                "decode attention vmap: the layer index is one for all lanes")

        def per_lane(x, batched):
            return x if batched else jnp.broadcast_to(
                x, (axis_size, *x.shape))

        qb, rb, _, *rest_b = in_batched
        q, rings, pos, live, rows = jax.tree.map(
            per_lane, (q, rings, pos, live, rows), (qb, rb, *rest_b))
        out = lanes(q, i, pos, live, *rings, *rows)
        return out, jax.tree.map(lambda _: True, out)

    return one


def flash_attention_decode(
    q: jax.Array,          # (n_heads, hd): ONE sequence's query
    k: jax.Array,          # (L, n_kv, n_ctx, hd): the STACKED bf16 ring,
    v: jax.Array,          #   left in HBM and read in place at layer i
    i: jax.Array,          # scalar int32: the layer
    pos: jax.Array,        # scalar int32: this sequence's position
    live: jax.Array,       # scalar bool: False = reads nothing, returns 0
    *,
    sm_scale: float,
    block_k: int,
    sliding_window: int = 0,
    interpret: bool = False,
    k_new: jax.Array | None = None,   # (n_kv, hd): this step's K row and
    v_new: jax.Array | None = None,   #   V row, not in the ring yet
    wrap: bool = False,               # the leaf's slots wrap (a window
    #                                   layer's: slot = position % slots)
):
    """A decode step's attention (S = 1) over the live part of layer
    ``i``'s ring: the flash recurrence of ``models/llama.py
    decode_attention`` (f32 scores, max and sum; bf16 probabilities into an
    f32 accumulator; the division last) as one kernel whose read is bounded
    PER LANE: ``ceil((pos + 1) / block_k)`` blocks of ``block_k`` slots,
    fewer under a sliding window, none where ``live`` is False.  Returns
    (n_heads * hd,) in q.dtype.  Under ``vmap`` over lanes (everything but
    ``i`` batched) it is still one kernel (:func:`_decode_vmappable`), and
    a lane's output does not depend on its neighbours: each grid step
    reads that lane's scalars, queries and blocks alone.

    With ``k_new`` / ``v_new`` the ring does not hold the step's own row
    yet and the kernel stores it, at ``(i, :, pos, :)`` (clamped to the
    ring's last slot, as ``dynamic_update_slice`` clamps), before it
    attends: the result is ``(ctx, k, v)``, the rings the very buffers
    that came in (aliased outputs) and, where ``live`` is False, untouched.
    Without them the call reads a ring that was written before it.

    ``wrap``: ``k`` / ``v`` hold ``sliding_window`` or a few more slots,
    not ``n_ctx``: position p lives in slot ``p % slots``, the row is
    stored there, and the mask is on positions (``_decode_kernel``)."""
    if wrap and not 0 < sliding_window <= k.shape[2]:
        raise ValueError(
            f"a leaf of {k.shape[2]} slots that wrap holds no window of "
            f"{sliding_window}")
    rows = () if k_new is None else (k_new.astype(k.dtype),
                                     v_new.astype(v.dtype))
    return _decode_vmappable(int(block_k), float(sm_scale),
                             int(sliding_window), bool(interpret), bool(wrap))(
        q, (k, v), jnp.asarray(i, jnp.int32), jnp.asarray(pos, jnp.int32),
        jnp.asarray(live, jnp.bool_), rows)


def latent_attention_decode(
    q: jax.Array,          # (n_heads, w): ONE sequence's [q_abs | q_r | 0]
    lat: jax.Array,        # (L, 1, n_ctx, w): the STACKED bf16 latent leaf,
    #                        left in HBM and read in place at layer i
    i: jax.Array,          # scalar int32: the layer (the leaf's)
    pos: jax.Array,        # scalar int32: this sequence's position
    live: jax.Array,       # scalar bool: False = reads and stores nothing
    row: jax.Array,        # (w,): this step's [c | k_r | 0], not cached yet
    *,
    sm_scale: float,
    block_k: int,
    v_width: int,          # kv_lora_rank: a row's first columns, the values
    interpret: bool = False,
    sel: jax.Array | None = None,   # (n_ctx,) bool: the positions the query
    #                                 may attend beside the causal bound
):
    """A decode step's ABSORBED latent attention (``models/mla.py
    latent_attention`` at S = 1) as :func:`flash_attention_decode`'s kernel
    on one ring in place of two: the leaf is one KV "head" for all
    ``n_heads`` query rows, a block of it is copied ONCE and is the keys
    (all ``w`` columns) and the values (its first ``v_width``), and the
    step's row is stored as that kernel stores a ring's (clamped alike).
    The same recurrence in the same order, bounded per lane, nothing for a
    lane that is not ``live``.  Returns (the weighted sum of LATENTS
    (n_heads * v_width,) in q.dtype, before ``W_uv``; the leaf, the very
    buffer that came in).  One kernel under ``vmap`` over lanes too.
    ``sel``: a selection (a ``deepseek32`` file's), applied as a mask on the
    blocks read: a position outside it has probability exactly 0."""
    rows = (row.astype(lat.dtype)[None],)
    if sel is not None:
        rows += (jnp.where(sel, 0.0, -jnp.inf).astype(jnp.float32),)
    ctx, lat = _decode_vmappable(
        int(block_k), float(sm_scale), 0, bool(interpret), False,
        int(v_width), sel is not None)(
        q, (lat,), jnp.asarray(i, jnp.int32), jnp.asarray(pos, jnp.int32),
        jnp.asarray(live, jnp.bool_), rows)
    return ctx, lat


# ---------------------------------------------------------------------------
# a prefill slice (S > 1) over a latent leaf: scores never leave VMEM
# ---------------------------------------------------------------------------

#: rows of a query tile and of a key block of :func:`latent_attention_prefill`,
#: the keys one pass of its recurrence scores, and the row groups of a tile
#: that a pass scores side by side (a tile is rows of the head-major query:
#: nothing here depends on the number of heads; the sweep was made at
#: gigachat's 64).  From the sweep on the chip (PERF.md
#: section 6, PR 50): at 64 heads x 1024 tokens against 11264 latents, ms a
#: call, (512, 1024, 512, 1) 11.28, (512, 1024, 1024, 1) 11.05, (512, 2048,
#: 512, 1) 11.38, (256, 1024, 512, 1) 12.97, (512, 1024, 256, 1) 14.37,
#: (1024, 1024, 512, 1) 10.69, (512, 1024, 512, 2) 10.54, (1024, 1024, 512,
#: 2) 10.32, (1024, 1024, 512, 4) 10.22; a key axis of the grid that ends at
#: the slice's bound (a dynamic extent) 10.79 for the last, and 6.30 for
#: 5.75 at 5632 latents: the static grid's empty steps cost less than the
#: pipeline's restart at every tile
LATENT_PREFILL_BLOCKS = (1024, 1024, 512, 4)


def latent_prefill_tile(n_heads: int, seq_len: int,
                        block_q: int = LATENT_PREFILL_BLOCKS[0]) -> int:
    """Rows of a query tile of :func:`latent_attention_prefill` for a slice
    of ``seq_len`` tokens, or 0 where no tile fits it: the largest power of
    two up to ``block_q``, at least one bf16 tile's 16 sublanes, that
    divides the ``n_heads * seq_len`` rows and either divides ``seq_len``
    (a tile is contiguous tokens of ONE head) or is a multiple of it (whole
    heads over all the slice's tokens)."""
    b = block_q
    while b >= _ROW_TILE:
        if (n_heads * seq_len) % b == 0 and (
                seq_len % b == 0 or b % seq_len == 0):
            return b
        b //= 2
    return 0


def _latent_tile_span(t, seq_len: int, block_q: int):
    """(first, last) token of the slice that query tile ``t`` holds: rows
    are h * S + s, so a tile that divides S is one head's contiguous
    tokens, and one that is a multiple of S whole heads over all of them."""
    if block_q < seq_len:
        first = jax.lax.rem(t * block_q, seq_len)
        return first, first + block_q - 1
    return 0, seq_len - 1


def _latent_prefill_kernel(
    # scalar prefetch
    i_ref,              # (1,) int32: the layer (the index maps')
    pos_ref,            # (1,) int32: cache position of the slice's token 0
    # inputs
    q_ref,              # (BQ, w): rows h * S + s of [q_abs | q_r | 0]
    lat_ref,            # (BK, w): one block of the layer's latents
    *rest,
    # ``select``:         bias_ref (BQ, BK) bf16, the tile's rows against
    #                     the block's keys: 0 | -inf on the scores
    # outputs
    # o_ref               (BQ, v_width)
    # scratch
    # m_ref               (BQ, 128) f32 running max (lane-replicated)
    # l_ref               (BQ, 128) f32 running sum
    # acc_ref             (BQ, v_width) f32 running weighted sum
    seq_len: int,
    block_q: int,
    block_k: int,
    sub_k: int,         # keys a pass of the recurrence scores
    sm_scale: float,
    v_width: int,
    chains: int,        # independent row groups of a tile, scored together
    select: bool = False,
):
    """``_attn_kernel``'s recurrence and block classification on ONE ring:
    a block of latents is the keys (all its columns) and the values (its
    first ``v_width``).  The key-block index map is clamped at the tile's
    last block, so a grid step past it fetches nothing and, its keys lying
    beyond every query of the tile, computes nothing.  ``select``: a bias
    block comes with every key block (models/mla.py ``select_topk``: a
    ``deepseek32`` file's selection as a mask), added to the scores of
    every pass, the wholly causal ones too."""
    del i_ref
    bias_ref = None
    if select:
        bias_ref, rest = rest[0], rest[1:]
    o_ref, m_ref, l_ref, acc_ref = rest
    t = pl.program_id(0)
    kb = pl.program_id(1)

    @pl.when(kb == 0)
    def _init():
        # a finite floor and -inf masks, as models/mla.py latent_attention
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    t_min, t_max = _latent_tile_span(t, seq_len, block_q)
    q_min = pos_ref[0] + t_min
    q_max = pos_ref[0] + t_max

    def _sub_block(u: int):
        kmin = kb * block_k + u * sub_k
        kmax = kmin + sub_k - 1
        skip = kmin > q_max                 # wholly in the masked future
        interior = kmax <= q_min            # wholly unmasked

        def _body(masked: bool):
            k = lat_ref[u * sub_k:(u + 1) * sub_k, :]       # (SK, w)
            rq = block_q // chains
            # every chain's scores first: chains are independent rows of
            # the tile, so one's softmax can run beside another's products
            scores = [jax.lax.dot_general(
                q_ref[c * rq:(c + 1) * rq, :], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
                for c in range(chains)]
            for c, s in enumerate(scores):
                rows = slice(c * rq, (c + 1) * rq)
                if masked:
                    row = t * block_q + c * rq + jax.lax.broadcasted_iota(
                        jnp.int32, s.shape, 0)
                    q_pos = pos_ref[0] + jax.lax.rem(row, seq_len)
                    key_pos = kmin + jax.lax.broadcasted_iota(
                        jnp.int32, s.shape, 1)
                    s = jnp.where(key_pos <= q_pos, s, -jnp.inf)
                if select:
                    s = s + bias_ref[rows, u * sub_k:(u + 1) * sub_k
                                     ].astype(jnp.float32)
                m_prev = m_ref[rows, :1]
                m_new = jnp.maximum(m_prev,
                                    jnp.max(s, axis=-1, keepdims=True))
                p = jnp.exp(s - m_new)
                alpha = jnp.exp(m_prev - m_new)
                l_new = l_ref[rows, :1] * alpha \
                    + jnp.sum(p, axis=-1, keepdims=True)
                acc_ref[rows, :] = acc_ref[rows, :] * alpha \
                    + jax.lax.dot_general(
                        p.astype(k.dtype), k[:, :v_width],
                        (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
                m_ref[rows, :] = jnp.broadcast_to(m_new, (rq, 128))
                l_ref[rows, :] = jnp.broadcast_to(l_new, (rq, 128))

        @pl.when(jnp.logical_and(jnp.logical_not(skip), interior))
        def _interior():
            _body(masked=False)

        @pl.when(jnp.logical_and(jnp.logical_not(skip),
                                 jnp.logical_not(interior)))
        def _edge():
            _body(masked=True)

    for u in range(block_k // sub_k):
        _sub_block(u)

    @pl.when(kb == pl.num_programs(1) - 1)
    def _finish():
        l = l_ref[:, :1]
        o_ref[...] = (acc_ref[...] / jnp.where(l > 0, l, 1.0)
                      ).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("sm_scale", "v_width", "block_q", "block_k", "sub_k",
                     "chains", "interpret"),
)
def latent_attention_prefill(
    q: jax.Array,          # (n_heads, S, w): HEAD-MAJOR [q_abs | q_r | 0]
    lat: jax.Array,        # (L, 1, n_ctx, w): the STACKED bf16 latent leaf,
    #                        left in HBM and read in place at layer i, the
    #                        slice's own rows already written
    i: jax.Array,          # scalar int32: the layer (the leaf's)
    pos_offset: jax.Array, # scalar int32: cache position of q[:, 0]
    *,
    sm_scale: float,
    v_width: int,          # kv_lora_rank: a row's first columns, the values
    block_q: int = LATENT_PREFILL_BLOCKS[0],
    block_k: int = LATENT_PREFILL_BLOCKS[1],
    sub_k: int = LATENT_PREFILL_BLOCKS[2],
    chains: int = LATENT_PREFILL_BLOCKS[3],
    interpret: bool = False,
    sel: jax.Array | None = None,   # (S, n_ctx) bool: the positions each
    #                                 query may attend beside the causal bound
) -> jax.Array:
    """A prefill slice's causal ABSORBED latent attention (``models/mla.py
    latent_attention`` at S > 1) as ONE kernel: grid (query tiles, key
    blocks), key blocks last, the scores, the probabilities and the
    accumulator in VMEM from a tile's first block to its last.  The loop's
    arithmetic (bf16 operands, float32 scores, max, sum and accumulator,
    the division last), only where the intermediates live differs.

    A query tile is ``block_q`` rows of the head-major query, row = h * S +
    s (:func:`latent_prefill_tile`: one head's contiguous tokens, or whole
    heads over all of them), so its causal bounds are tight.  A key block
    is ``block_k`` rows of the leaf, copied ONCE for the scores (all ``w``
    columns) and the weighted sum (its first ``v_width``) and scored
    ``sub_k`` keys a pass, each pass skipped where it lies wholly beyond
    the tile's last query and unmasked where wholly before its first; a
    pass scores ``chains`` row groups of the tile side by side, so that one
    group's softmax runs beside another's products.  The block index is
    clamped at the block that holds the tile's last
    position: the grid walks all of ``n_ctx``, but a step past that block
    names the block already in VMEM, and no copy is started for it.

    ``sel``: a selection (a ``deepseek32`` file's) applied as a MASK: it
    comes to the kernel as a bf16 bias (0 | -inf) whose rows are a TILE's
    rows (a tile of whole heads: the slice's rows again and again), one
    block a key block; the kernel is then named
    ``flash_attention_prefill_latent_select``.

    Returns the weighted sum of LATENTS (n_heads, S, v_width) in q.dtype,
    before ``W_uv``.  One sequence: no ``vmap`` rule."""
    H, S, W = q.shape
    _, _, n_ctx, _ = lat.shape
    bq = latent_prefill_tile(H, S, block_q)
    bk = min(block_k, n_ctx)
    sk = min(sub_k, bk)
    if not bq or n_ctx % bk or bk % sk:
        raise ValueError(
            f"no tile of the latent prefill kernel fits {H} heads x {S} "
            f"tokens against {n_ctx} positions in blocks of {block_k}")
    n_kb = n_ctx // bk

    def last_block(t, pos_ref):
        return jnp.minimum((pos_ref[0] + _latent_tile_span(t, S, bq)[1])
                           // bk, n_kb - 1)

    select = sel is not None
    bias, call_kw = (), {}
    if select:
        bias = jnp.where(sel, 0.0, -jnp.inf).astype(jnp.bfloat16)
        if bq > S:                  # whole heads: every head's rows
            bias = jnp.tile(bias, (bq // S, 1))
        bias = (bias,)
        # the bias blocks' two buffers beside the kernel's own 11 MB
        call_kw = {"compiler_params": pltpu.CompilerParams(
            vmem_limit_bytes=48 << 20)}
    per_head = max(S // bq, 1)      # tiles a head's tokens make
    out = pl.pallas_call(
        functools.partial(
            _latent_prefill_kernel, seq_len=S, block_q=bq, block_k=bk,
            sub_k=sk, sm_scale=sm_scale, v_width=v_width,
            chains=chains if bq % (16 * chains) == 0 else 1, select=select),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(H * S // bq, n_kb),
            in_specs=[
                pl.BlockSpec((bq, W), lambda t, kb, i, pos: (t, 0)),
                pl.BlockSpec((None, None, bk, W), lambda t, kb, i, pos: (
                    i[0], 0, jnp.minimum(kb, last_block(t, pos)), 0)),
            ] + [pl.BlockSpec((bq, bk), lambda t, kb, i, pos: (
                jax.lax.rem(t, per_head),
                jnp.minimum(kb, last_block(t, pos))))] * select,
            out_specs=pl.BlockSpec((bq, v_width),
                                   lambda t, kb, i, pos: (t, 0)),
            scratch_shapes=[
                pltpu.VMEM((bq, 128), jnp.float32),
                pltpu.VMEM((bq, 128), jnp.float32),
                pltpu.VMEM((bq, v_width), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((H * S, v_width), q.dtype),
        interpret=interpret,
        name="flash_attention_prefill_latent" + ("_select" if select else ""),
        **call_kw,
    )(jnp.asarray(i, jnp.int32).reshape(1),
      jnp.asarray(pos_offset, jnp.int32).reshape(1),
      q.reshape(H * S, W), lat, *bias)
    return out.reshape(H, S, v_width)


# devtime inventory (lfkt-lint PERF001): flash attention is a TRACE-INNER
# dispatch site — it runs inside the prefill/decode entry programs, so its
# compile wall is attributed to whichever host program traced it
# (obs/devtime.py; /debug/compiles shows it under kind="inner")
register_program("flash_attention", site="ops.pallas.attention")
register_program("_decode_lanes", site="ops.pallas.attention")
register_program("latent_attention_prefill", site="ops.pallas.attention")
