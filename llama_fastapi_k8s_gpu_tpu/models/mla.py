"""The fourth cache KIND, and a feed-forward kind per LAYER
(``general.architecture = "deepseek2"``: the DeepSeek-V2/V3 family's block;
``cfg.kv_lora_rank``), or TWO attention sub-layers a layer with a
shortcut-connected expert branch (``"longcat-flash"``), or a learned INDEXER
beside every layer's attention that picks the positions a query attends
(``"deepseek32"``: the last point).

- Latent attention (MLA).  ``c_q = RMSNorm(W_qa x)``; per head ``[q_n | q_r]
  = W_qb c_q`` (``qk_nope_dim`` + ``qk_rope_dim``); ``[c_kv | k_r] = W_kva
  x``, ``c = RMSNorm(c_kv)``; per head ``[k_n | v] = W_kvb c``; ``q_r`` and
  the ONE ``k_r`` all heads share are rotated on interleaved pairs (YaRN
  frequencies: :func:`rope_inv_freq`); scores ``(q_n . k_n + q_r . k_r) *
  scale``, ``scale = (d_n + d_r)^-1/2 * cfg.attn_mscale``; causal softmax;
  ``o = P v``; ``W_o``.  The cache keeps, per layer and position, the normed
  latent ``c`` and the rotated ``k_r`` side by side: leaf ``lat`` (L, 1,
  n_ctx, r_kv + d_r filled up to a multiple of 128: :func:`leaf_width`)
  bf16, one row for all heads (1152 B at 512 + 64, 1280 as laid out,
  against 49 152 B for 64 heads of K and V).  It is POSITIONAL, as a ring
  is: a prefix of it is a prefix of the sequence, so the engines' prefix
  reuse (the serial claim, the lanes' claims) serves it unchanged.
- Every read is in the ABSORBED form (:func:`latent_attention`): ``W_kvb``'s
  key half is folded into the query (``q_abs = q_n W_uk``, r_kv wide), the
  scores are ``[q_abs | q_r] . [c | k_r]``, the weighted sum is over the
  latents themselves and ``W_kvb``'s value half is applied after it.  A
  block of latents is read ONCE for all heads, which is the mechanism's
  point; the expanded form (:func:`expanded_attention`: K and V of every
  head for every position) is what the reference computes and what
  tests hold the absorbed form to.  WHICH READ SERVES WHICH S, decided by
  what the program observes (S, the backend, the kernels' probes) and by no
  knob: :func:`latent_attention` is a loop in plain XLA over blocks of
  ``LATENT_BLOCK`` positions up to a traced bound (flash recurrence:
  running max and sum; under the lane engine's ``vmap`` ONE bound for all
  lanes), after an XLA ``dynamic_update_slice`` of the pass's rows; it
  serves on the CPU, after a failed probe, a decode step (S = 1) wherever
  :func:`kernel_block` says 0 and a prefill slice (S > 1) wherever
  :func:`slice_tile` says 0, and it is what tests hold the kernels to.  A
  prefill slice where ``cfg.latent_slice_kernel`` is set (the engine's: a
  TPU, the probe passed) and a tile fits its rows (a tile is rows of the
  head-major query, whatever the number of heads: every slice width of
  engine/slices.py at 64 heads and at 128) is, after the same XLA
  write, ONE Pallas kernel over the scratch leaf in place (ops/pallas/
  attention.py ``latent_attention_prefill``): query tiles of 1024 rows
  (one head's contiguous tokens, whole heads of a narrow slice) against
  blocks of ``LATENT_SLICE_BLOCK`` rows, each block copied once for the
  scores and the weighted sum, no block past a tile's last position
  fetched, and the scores, probabilities and accumulator in VMEM from a
  tile's first block to its last (the loop writes them to HBM three times
  a block).  /health ``engine.
  latent_slice_read`` (``kernel`` / ``xla``), the traced ``prefill``
  span's ``latent_read`` and the gauges ``latent_slices_kernel_total`` /
  ``latent_slices_loop_total`` say which served.  A decode step where
  ``cfg.latent_kernel`` is set (the engine's: a TPU, the probe passed) is
  ONE Pallas kernel over the lanes (ops/pallas/attention.py
  ``latent_attention_decode``, the ring's decode kernel on one leaf): each
  live lane's own blocks of ``LATENT_KERNEL_BLOCK`` rows read in place, a
  block copied once for the scores (all 640 columns) and the weighted sum
  (its first ``kv_lora_rank``), the step's row set into the block that
  holds it and its 16-row tile copied back; a lane that holds no request
  reads and stores nothing.  The same recurrence in the same order: tests
  hold the kernel to the loop.
- The feed-forward kind is the LAYER's (models/routed.py, shared with
  models/hybrid.py): leading dense layers, then a float32 grouped router
  over the experts HELD here plus a shared expert.  The two kinds are two
  stacks of weights (``params["layers"]["dense" | "moe"]``), each a
  ``fori_loop``.
- A ``longcat-flash`` layer (``cfg.attn_sublayers == 2``;
  :func:`shortcut_layer`) is two sub-blocks of this attention and a dense
  SwiGLU each, and ONE expert branch that reads sub-block 0's normed rows
  and joins after sub-block 1's feed-forward (a shortcut-connected
  mixture of experts: in a deployment the branch's dispatch and combine
  hide behind sub-block 1; on one chip the late join only frees the order
  of operations).  Its router has outputs that are identity experts
  (models/routed.py), its query and latent are scaled
  (``cfg.q_latent_scale``, ``cfg.kv_latent_scale``; the latent BEFORE it is
  cached, so every read of the cache is as above), and the latent ring has
  a leaf an attention SUB-layer, ``2 l + s``.  Three stacks of weights:
  ``params["layers"]["attn" | "ffn"]`` at depth ``2 L``, ``["moe"]`` at
  depth ``L``; one ``fori_loop`` over the layers.
- A ``deepseek32`` layer (``cfg.index_topk``: DeepSeek Sparse Attention) has
  a learned INDEXER beside this attention: ``qI_h = W_Iq c_q`` (``index_
  heads`` of ``index_dim``, from the SAME normed query latent), ``kI =
  LayerNorm(W_Ik x)`` (weight and bias; ONE vector a position), the first
  ``qk_rope_dim`` columns of both rotated on HALVES with the attention's
  YaRN frequencies, ``w = W_Iw x`` times ``index_heads^-1/2 index_dim^-1/2``
  (signed, float32), ``I(t, s) = sum_h w_h(t) relu(qI_h(t) . kI(s))``; a
  query attends the ``min(index_topk, t + 1)`` positions of largest ``I``
  (of equal scores the lower position) and no other.  The ring holds a
  SECOND leaf, ``idx`` (L, 1, n_ctx, index_dim filled up to 128) bf16,
  written where ``lat`` is written: one row a position as well, so the
  cache rolls back, is claimed and is copied as before, by whoever maps
  over its leaves (:data:`INDEXED`, the kind's object for such a file).
  Per pass and layer (:func:`_indexer`): the pass's index keys are written
  by an XLA update, :func:`index_scores` scores every query against the
  leaf in blocks up to the read's bound (a loop in plain XLA: bf16
  operands, float32 products, relu, weights and sums; the per-head scores
  of a block and a group of heads at a time, never all of them),
  :func:`select_topk` finds each row's k-th largest score by a search on
  its bits (no sort) and hands out the selection as a mask, and the
  attention is the read that serves S as above WITH that mask: the XLA
  loop's ``sel``, or a bias operand of the two kernels
  (``flash_attention_decode_latent_select``, ``flash_attention_prefill_
  latent_select``).  The selection is a MASK on the blocks read, not a
  gather: a decode step fetches every live latent and scores it at all
  heads (tools/time_dsa_select.py has both on the chip; PERF.md section 6,
  PR 58); the counters ``latents_selected_total`` / ``latents_read_total``
  say how far the fetch is from the selection.  While a query has no more
  than ``index_topk`` positions the layer is the dense one exactly.
"""

from __future__ import annotations

import dataclasses
import logging
import math

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.linear import linear, linear_at
from .cache import CacheKind
from .config import LATENT_RING, ModelConfig
from .llama import (
    expert_stats_len, live_bound, note_ring_decode, ring_step_bound,
    rms_norm)
from .routed import (  # noqa: F401  (``mla.route_grouped``: the tests' name)
    DENSE, HI, MOE, check_stacks, expert_branch, held_picks, moe_stats,
    n_moe_layers, route_grouped, swiglu)

#: the two stacks at depth ``2 L`` of a ``longcat-flash`` file (a third,
#: ``MOE``, at depth ``L``): a sub-block's attention, its dense feed-forward
ATTN, FFN = "attn", "ffn"

logger = logging.getLogger(__name__)

#: latent rows a block of :func:`latent_attention`'s loop reads (the XLA
#: loop of ``models/llama.py decode_attention`` reads 512 ring slots a time)
LATENT_BLOCK = 512

#: latent rows the decode KERNEL copies at a time
#: (ops/pallas/attention.py ``latent_attention_decode``;
#: :func:`kernel_block` says where it serves): 1.3 MB a copy at 640
#: columns.  On the chip a block of 256 / 512 / 1024 rows costs 0.76 / 1.09
#: / 1.84 us (0.4 us fixed, then 0.36 us a 256 rows, which is 89 % of the
#: HBM's rate), so at 12 live lanes of context 8.7k a step's seven layers
#: take 2.23 / 1.62 / 1.42 ms, with the half block a lane reads past its
#: position counted in (PERF.md section 6, PR 47).
LATENT_KERNEL_BLOCK = 1024

#: latent rows a block of the prefill slices' KERNEL holds
#: (ops/pallas/attention.py ``latent_attention_prefill``;
#: :func:`slice_tile` says where it serves)
LATENT_SLICE_BLOCK = 1024


def kernel_block(cfg: ModelConfig) -> int:
    """The decode kernel's block on the latent leaf where
    ``cfg.latent_kernel`` says it serves (the engine's: a TPU, the kernel's
    own probe passed; ``attn_impl`` is ``xla`` for this kind), else 0: rows
    for all heads, dividing the leaf's."""
    if not cfg.latent_kernel:
        return 0
    block = min(LATENT_KERNEL_BLOCK, cfg.n_ctx)
    return block if cfg.n_ctx % block == 0 and block % 16 == 0 else 0


def slice_tile(cfg: ModelConfig, S: int) -> int:
    """Rows of a query tile of the prefill slices' kernel where it serves a
    slice of ``S`` tokens, else 0 (the XLA loop serves):
    ``cfg.latent_slice_kernel`` (the engine's: a TPU, the kernel's own
    probe passed), S > 1, whole blocks in the leaf, and a tile that fits
    the slice's ``n_heads * S`` rows."""
    if not cfg.latent_slice_kernel or S < 2 \
            or cfg.n_ctx % min(LATENT_SLICE_BLOCK, cfg.n_ctx) \
            or (cfg.index_topk and S % 16):
        # (a selection comes to the kernel as whole bf16 tiles of the
        # slice's rows)
        return 0
    from ..ops.pallas.attention import latent_prefill_tile

    return latent_prefill_tile(cfg.n_heads, S)


def lat_width(cfg: ModelConfig) -> int:
    return cfg.kv_lora_rank + cfg.qk_rope_dim


def leaf_width(cfg: ModelConfig) -> int:
    """The ``lat`` leaf's last dimension: a row's ``lat_width`` filled up
    with zeros to the tile's 128 lanes.  The chip lays a 576-wide row out
    in 640 either way; with 576 stated the compiler chose to turn the
    lanes' whole leaf (positions minor) on the way into every decode chunk
    and back out (tests/test_chip_compile.py), with 640 it leaves it be."""
    return -(-lat_width(cfg) // 128) * 128


def index_leaf_width(cfg: ModelConfig) -> int:
    """The ``idx`` leaf's last dimension (0: the file has no indexer): an
    index key filled up to the tile's 128 lanes, as :func:`leaf_width`."""
    return -(-cfg.index_dim // 128) * 128 if cfg.index_topk else 0


def init_cache(cfg: ModelConfig, dtype=jnp.bfloat16) -> dict:
    # a leaf an attention SUB-layer (one a layer but in ``longcat-flash``);
    # a ``deepseek32`` file's ring has a SECOND leaf, the index keys: one
    # row a position too, so a prefix of both is a prefix of the sequence
    # and whatever copies, rolls back or claims the cache maps over the two
    cache = {"lat": jnp.zeros(
        (cfg.cache_leaves, 1, cfg.n_ctx, leaf_width(cfg)), dtype)}
    if cfg.index_topk:
        cache["idx"] = jnp.zeros(
            (cfg.cache_leaves, 1, cfg.n_ctx, index_leaf_width(cfg)), dtype)
    return cache


def cache_nbytes(cfg: ModelConfig) -> int:
    return cfg.cache_leaves * cfg.n_ctx * (
        leaf_width(cfg) + index_leaf_width(cfg)) * 2


def prefill_positions_read(slices, cfg: ModelConfig) -> int:
    """Cached rows (a layer's) the slices of a prompt's prefill read:
    ``slices`` [(offset, positions)] (engine/slices.py ``plan_slices``),
    each reading whole blocks, of the read that serves it
    (:func:`slice_tile`), up to its own last position.  Host arithmetic for
    the ``prefill`` span."""
    def rows(off, n):
        T = min(LATENT_SLICE_BLOCK if slice_tile(cfg, n) else LATENT_BLOCK,
                cfg.n_ctx)
        return min(-(-min(off + n, cfg.n_ctx) // T) * T, cfg.n_ctx)

    return sum(rows(off, n) for off, n in slices)


def attn_scale(cfg: ModelConfig) -> float:
    return (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5 * cfg.attn_mscale


def rope_inv_freq(cfg: ModelConfig) -> np.ndarray:
    """(d_r / 2,) float32 inverse frequencies of the rotated part.  Plain:
    ``theta^(-2i/d_r)``.  YaRN as published (DeepSeek-V3's
    ``DeepseekV3YarnRotaryEmbedding``): blended with the same over
    ``factor`` by a linear ramp between the correction dims of
    ``beta_fast`` and ``beta_slow`` rotations at the original context."""
    d = cfg.qk_rope_dim
    base = cfg.rope_theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    if not cfg.rope_yarn_factor or cfg.rope_yarn_factor == 1.0:
        return base.astype(np.float32)

    def corr_dim(n_rot):
        return d * math.log(cfg.rope_yarn_orig_ctx / (n_rot * 2 * math.pi)) \
            / (2 * math.log(cfg.rope_theta))

    low = max(math.floor(corr_dim(cfg.rope_yarn_beta_fast)), 0)
    high = min(math.ceil(corr_dim(cfg.rope_yarn_beta_slow)), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp                       # 1: the plain frequency
    return (base / cfg.rope_yarn_factor * (1.0 - keep) + base * keep
            ).astype(np.float32)


def rope_pairs(x: jax.Array, positions: jax.Array, inv_freq) -> jax.Array:
    """x (S, H, d_r): rotate pairs (2i, 2i+1) by ``pos * inv_freq[i]``
    (ggml's NORM mode: the converter interleaves the rotated rows; the
    published code de-interleaves and rotates halves, the same map)."""
    ang = positions[:, None].astype(jnp.float32) * jnp.asarray(inv_freq)[None]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., 0::2], xf[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape).astype(x.dtype)


# ---------------------------------------------------------------------------
# attention over cached latents
# ---------------------------------------------------------------------------

def absorb_query(q_n, w_uk):
    """q_n (S, H, d_n) x W_uk (H, d_n, r) -> (S, H, r): the key half of
    ``W_kvb`` folded into the query.  (Head-major operands: the batch
    dimension leads, which is the form every backend's dot takes.)"""
    with jax.named_scope("mla_absorb_q"):
        return jnp.einsum("hsn,hnr->hsr", q_n.transpose(1, 0, 2), w_uk,
                          preferred_element_type=jnp.float32
                          ).astype(q_n.dtype).transpose(1, 0, 2)


def latent_attention(q_full, lat, i, positions, bound, cfg: ModelConfig,
                     sel=None):
    """Causal attention of S queries over layer ``i``'s cached latents, in
    the absorbed form.  ``q_full`` (S, H, r_kv + d_r) = [q_abs | q_r];
    ``lat`` the stacked leaf (L, 1, n_ctx, r_kv + d_r), sliced in place at
    (i, block); ``positions`` (S,) each query's own position, its causal
    bound; ``bound`` (scalar) the position the read goes up to, >= every
    position whose output is used (under ``vmap`` over lanes it must be
    unbatched: the trip count stays a scalar).  Returns the weighted sum
    of LATENTS, head-major (H, S, r_kv) float32, before ``W_uv``.  A query's result
    does not depend on ``bound``: a block wholly beyond its position adds
    probabilities of exactly 0 under a rescale of exactly 1
    (``models/llama.py decode_attention`` has the argument).  ``sel`` (S,
    n_ctx) bool or None: the positions each query may attend beside the
    causal bound (:func:`select_topk`: a ``deepseek32`` file's selection,
    applied as a MASK on the blocks read)."""
    S, H, W = q_full.shape
    r, n_ctx = cfg.kv_lora_rank, cfg.n_ctx
    T = min(LATENT_BLOCK, n_ctx)
    scale = attn_scale(cfg)
    i = jnp.asarray(i, jnp.int32)
    n_blocks = jnp.minimum((jnp.asarray(bound, jnp.int32) + T) // T,
                           -(-n_ctx // T))
    qh = q_full.transpose(1, 0, 2)                       # (H, S, W)

    def block(j, carry):
        m, l, acc = carry
        lo = j * T
        at = jnp.minimum(lo, n_ctx - T)
        lb = jax.lax.dynamic_slice(lat, (i, 0, at, 0), (1, 1, T, W))[0, 0]
        with jax.named_scope("mla_scores"):
            s = jnp.einsum("hsw,tw->hst", qh, lb,
                           preferred_element_type=jnp.float32) * scale
        key_pos = at + jnp.arange(T)
        mask = (key_pos >= lo)[None, :] \
            & (key_pos[None, :] <= positions[:, None])   # (S, T)
        if sel is not None:
            mask &= jax.lax.dynamic_slice(sel, (0, at), (S, T))
        s = jnp.where(mask[None], s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1)
        with jax.named_scope("mla_pv"):
            acc = acc * alpha[..., None] + jnp.einsum(
                "hst,tr->hsr", p.astype(lb.dtype), lb[:, :r],
                preferred_element_type=jnp.float32)
        return m_new, l, acc

    # a finite floor, not -inf: an all-masked block must leave the rescale 1
    _, l, acc = jax.lax.fori_loop(0, n_blocks, block, (
        jnp.full((H, S), -1e30, jnp.float32),
        jnp.zeros((H, S), jnp.float32),
        jnp.zeros((H, S, r), jnp.float32)))
    return acc / jnp.where(l > 0, l, 1.0)[..., None]     # (H, S, r)


def expand_values(ctx_lat, w_uv, dtype):
    """(H, S, r) weighted latents x W_uv (H, d_v, r) -> (S, H * d_v): the
    value half of ``W_kvb`` applied after the weighted sum."""
    with jax.named_scope("mla_expand_v"):
        o = jnp.einsum("hsr,hvr->hsv", ctx_lat.astype(dtype), w_uv,
                       preferred_element_type=jnp.float32)
    return o.transpose(1, 0, 2).reshape(o.shape[1], -1).astype(dtype)


def expanded_attention(q_n, q_r, rows, w_uk, w_uv, positions,
                       cfg: ModelConfig):
    """The same attention in the EXPANDED form, float32, over ``rows`` (T,
    r_kv + d_r) cached latents at positions 0..T-1: every head's keys and
    values for every position.  What the absorbed form must equal
    (tests/test_mla.py); nothing serves through it."""
    f32 = jnp.float32
    r = cfg.kv_lora_rank
    c = rows[:, :r].astype(f32)
    k_r = rows[:, r:r + cfg.qk_rope_dim].astype(f32)
    k_n = jnp.einsum("tr,hnr->thn", c, w_uk.astype(f32), precision=HI)
    v = jnp.einsum("tr,hvr->thv", c, w_uv.astype(f32), precision=HI)
    s = (jnp.einsum("shn,thn->hst", q_n.astype(f32), k_n, precision=HI)
         + jnp.einsum("shd,td->hst", q_r.astype(f32), k_r, precision=HI)
         ) * attn_scale(cfg)
    mask = jnp.arange(rows.shape[0])[None, :] <= positions[:, None]
    p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("hst,thv->shv", p, v, precision=HI)
    return o.reshape(o.shape[0], -1)


# ---------------------------------------------------------------------------
# the learned indexer of a ``deepseek32`` file (DeepSeek Sparse Attention)
# ---------------------------------------------------------------------------

#: index keys a block of :func:`index_scores`' loop scores
INDEX_BLOCK = 1024

#: (head, query) rows of per-head scores a pass of that loop holds at once:
#: the per-head scores of a slice never exist whole (64 heads x 1024 rows x
#: 16384 keys would be 4.3 GB float32), 8192 rows x 1024 keys are 32 MB
INDEX_ROWS = 8192

#: bits of the k-th largest score that one pass of :func:`select_topk`
#: settles (2^bits - 1 counts a pass, 32 / bits passes)
SELECT_BITS = 4


def rope_halves(x: jax.Array, positions: jax.Array, inv_freq) -> jax.Array:
    """x (S, H, d): the first ``2 * len(inv_freq)`` columns rotated on
    HALVES (column i with i + d_r / 2: the indexer's layout, not the main
    attention's interleaved pairs), the others left as they are."""
    n = len(inv_freq)
    ang = positions[:, None].astype(jnp.float32) * jnp.asarray(inv_freq)[None]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :n], xf[..., n:2 * n]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos, xf[..., 2 * n:]],
        axis=-1).astype(x.dtype)


def layer_norm(x, weight, bias, eps: float):
    """LayerNorm over the last axis in float32 (mean and variance), with
    weight AND bias: the index key's norm."""
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps) * weight + bias
            ).astype(x.dtype)


def index_scores(q_i, w, idx, i, bound, cfg: ModelConfig):
    """The indexer's scores of S queries against layer ``i``'s cached index
    keys: ``I(t, s) = sum_h w_h(t) relu(q_h(t) . k(s))``.  ``q_i`` (S, Hi,
    dI) bf16, ``w`` (S, Hi) float32 (the scale folded in), ``idx`` the
    stacked leaf (L, 1, n_ctx, dI filled up), sliced in place at (i, block);
    ``bound`` as :func:`latent_attention`'s.  Returns (S, n_ctx) float32,
    ``-inf`` past the last block read.  A loop in plain XLA over blocks of
    ``INDEX_BLOCK`` keys and, within a block, over groups of heads: bf16
    operands, float32 products, relu, weights and sum, and never more than
    ``INDEX_ROWS`` (head, query) rows of per-head scores at once."""
    S, Hi, dI = q_i.shape
    n_ctx = cfg.n_ctx
    T = min(INDEX_BLOCK, n_ctx)
    g = Hi
    while g > 1 and (g * S > INDEX_ROWS or Hi % g):
        g -= 1
    i = jnp.asarray(i, jnp.int32)
    n_blocks = jnp.minimum((jnp.asarray(bound, jnp.int32) + T) // T,
                           -(-n_ctx // T))
    qh = q_i.transpose(1, 0, 2)                          # (Hi, S, dI)
    wh = w.T.astype(jnp.float32)                         # (Hi, S)

    def block(j, out):
        at = jnp.minimum(j * T, n_ctx - T)
        kb = jax.lax.dynamic_slice(
            idx, (i, 0, at, 0), (1, 1, T, idx.shape[-1]))[0, 0][:, :dI]

        def heads(n, acc):
            qg = jax.lax.dynamic_slice_in_dim(qh, n * g, g, axis=0)
            wg = jax.lax.dynamic_slice_in_dim(wh, n * g, g, axis=0)
            s = jnp.einsum("hsd,td->hst", qg, kb,
                           preferred_element_type=jnp.float32)
            return acc + jnp.sum(jnp.maximum(s, 0.0) * wg[..., None], axis=0)

        with jax.named_scope("dsa_index_scores"):
            acc = jnp.zeros((S, T), jnp.float32)
            acc = heads(0, acc) if g == Hi else jax.lax.fori_loop(
                0, Hi // g, heads, acc)
        return jax.lax.dynamic_update_slice(out, acc, (0, at))

    return jax.lax.fori_loop(0, n_blocks, block,
                             jnp.full((S, n_ctx), -jnp.inf, jnp.float32))


def select_topk(scores, positions, k: int):
    """(S, n_ctx) bool: for each query the ``min(k, t + 1)`` positions ``s
    <= t`` of largest score (``positions`` (S,): each query's ``t``).  The
    tie rule: of equal scores the LOWER position is taken.  No sort: the
    k-th largest value is found by a search on the scores' bits (the
    float32 order is an integer order after a fold of the sign), ``SELECT_
    BITS`` bits a pass, each pass a handful of counts over the row."""
    S, n = scores.shape
    causal = jnp.arange(n, dtype=jnp.int32)[None, :] <= positions[:, None]
    with jax.named_scope("dsa_select"):
        # (+ 0.0: a negative zero is a zero)
        bits = jax.lax.bitcast_convert_type(scores + 0.0, jnp.int32)
        key = bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))
        u = jax.lax.bitcast_convert_type(key, jnp.uint32) \
            ^ jnp.uint32(0x80000000)
        u = jnp.where(causal, u, jnp.uint32(0))
        kk = jnp.minimum(jnp.int32(k), positions.astype(jnp.int32) + 1)
        B = SELECT_BITS
        steps = jnp.arange(1, 1 << B, dtype=jnp.uint32)

        def settle(p, thr):
            shift = (32 - B * (p + 1)).astype(jnp.uint32)
            cand = thr[:, None] | (steps[None, :] << shift)       # (S, 2^B-1)
            cnt = jnp.sum(u[:, None, :] >= cand[:, :, None], axis=-1,
                          dtype=jnp.int32)
            # the counts fall as the candidate rises: the largest that
            # still has k at or above it
            c = jnp.sum(cnt >= kk[:, None], axis=-1).astype(jnp.uint32)
            return thr | (c << shift)

        thr = jax.lax.fori_loop(0, 32 // B, settle,
                                jnp.zeros((S,), jnp.uint32))
        above = u > thr[:, None]
        tie = (u == thr[:, None]) & causal
        room = kk - jnp.sum(above, axis=-1, dtype=jnp.int32)
        return above | (tie & (jnp.cumsum(tie, axis=-1, dtype=jnp.int32)
                               <= room[:, None]))


def _indexer(hn, c_q, layers, i, li, cache, positions, pos_offset, cfg,
             kv_bound, lin):
    """A ``deepseek32`` layer's indexer for the pass's S rows: the index
    keys written to the ``idx`` leaf, then every query scored against the
    leaf and its positions chosen.  Returns (idx leaf, the scores (S, n_ctx)
    float32, the selection (S, n_ctx) bool)."""
    S = hn.shape[0]
    Hi, dI = cfg.index_heads, cfg.index_dim
    inv_freq = rope_inv_freq(cfg)
    q_i = rope_halves(lin(c_q, "idx_wq_b").reshape(S, Hi, dI), positions,
                      inv_freq)
    k_i = layer_norm(lin(hn, "idx_wk")[:, :dI], layers["idx_k_norm"][i],
                     layers["idx_k_norm_b"][i], cfg.index_norm_eps)
    k_i = rope_halves(k_i[:, None], positions, inv_freq)[:, 0]
    with jax.named_scope("dsa_index_weights"):
        w = jnp.einsum("sd,hd->sh", hn.astype(jnp.float32),
                       layers["idx_proj"][i], precision=HI
                       ) * (Hi ** -0.5 * dI ** -0.5)
    idx = cache["idx"]
    rows = jnp.pad(k_i, ((0, 0), (0, idx.shape[-1] - dI))).astype(idx.dtype)
    with jax.named_scope("kv_write"):
        idx = jax.lax.dynamic_update_slice(
            idx, rows[None, None], (li, 0, pos_offset, 0))
    bound = pos_offset + S - 1 if kv_bound is None or S > 1 else kv_bound
    scores = index_scores(q_i, w, idx, li, bound, cfg)
    return idx, scores, select_topk(scores, positions, cfg.index_topk)


# ---------------------------------------------------------------------------
# the layers
# ---------------------------------------------------------------------------

def _attention(h, layers, i, li, cache, positions, pos_offset, cfg, live,
               kv_bound, tap=None):
    """One layer's attention branch.  ``i``: the layer's number within its
    kind's stack of weights, ``li``: its number in the whole stack (the
    cache's).  ``live`` (scalar bool or None): whether this sequence holds
    a request; the decode kernel reads and stores nothing where not.
    ``tap`` (``forward(with_index=True)``): the indexer's scores and the
    selection of every leaf so far, each (leaves, S, n_ctx), handed back
    with this layer's set in.  Returns (h + branch, cache, tap)."""
    S = h.shape[0]
    H, r, d_n, d_r = (cfg.n_heads, cfg.kv_lora_rank, cfg.qk_nope_dim,
                      cfg.qk_rope_dim)

    def lin(x, name):
        with jax.named_scope(name):
            return linear_at(x, layers[name], i)

    hn = rms_norm(h, layers["attn_norm"][i], cfg.rms_eps)
    c_q = rms_norm(lin(hn, "wq_a"), layers["q_a_norm"][i], cfg.rms_eps)
    q = lin(c_q, "wq_b").reshape(S, H, d_n + d_r)
    if cfg.q_latent_scale != 1.0:       # both parts (``mla_scale_q_lora``)
        q = (q.astype(jnp.float32) * cfg.q_latent_scale).astype(q.dtype)
    inv_freq = rope_inv_freq(cfg)
    q_r = rope_pairs(q[..., d_n:], positions, inv_freq)
    # the projection's rows are filled up to a kernel's N: the first
    # r_kv + d_r are the file's
    kv = lin(hn, "wkv_a")[:, :r + d_r]
    c = rms_norm(kv[:, :r], layers["kv_a_norm"][i], cfg.rms_eps)
    if cfg.kv_latent_scale != 1.0:
        # (``mla_scale_kv_lora``) before it is cached: every read, absorbed
        # or expanded, finds the latent as ``W_kvb`` takes it; ``k_r`` is not
        c = (c.astype(jnp.float32) * cfg.kv_latent_scale).astype(c.dtype)
    k_r = rope_pairs(kv[:, None, r:], positions, inv_freq)[:, 0]
    fill = leaf_width(cfg) - r - d_r
    rows = jnp.concatenate(
        [c, k_r, jnp.zeros((S, fill), c.dtype)], axis=-1
    ).astype(cache["lat"].dtype)
    q_full = jnp.concatenate(
        [absorb_query(q[..., :d_n], layers["w_uk"]["w"][i]), q_r,
         jnp.zeros((S, H, fill), q_r.dtype)], axis=-1)
    sel = None
    if cfg.index_topk:
        # the positions each query may attend: the index keys are written
        # and scored BEFORE the read, whichever read serves
        idx, scores, sel = _indexer(hn, c_q, layers, i, li, cache, positions,
                                    pos_offset, cfg, kv_bound, lin)
        cache = {**cache, "idx": idx}
        if tap is not None:
            tap = tuple(jax.lax.dynamic_update_slice(t, x[None], (li, 0, 0))
                        for t, x in zip(tap, (scores, sel)))
    block = kernel_block(cfg) if S == 1 else 0
    if block:
        # the decode kernel: this sequence's own blocks, read in place, and
        # the step's row stored into the block it reads anyway
        from ..ops.pallas import latent_attention_decode, use_interpret

        with jax.named_scope("mla_attn"):
            ctx, lat = latent_attention_decode(
                q_full[0], cache["lat"], li, pos_offset,
                True if live is None else live, rows[0],
                sm_scale=attn_scale(cfg), block_k=block, v_width=r,
                interpret=use_interpret(),
                sel=None if sel is None else sel[0])
        cache, ctx = {**cache, "lat": lat}, ctx.reshape(H, 1, r)
    else:
        with jax.named_scope("kv_write"):
            cache = {**cache, "lat": jax.lax.dynamic_update_slice(
                cache["lat"], rows[None, None], (li, 0, pos_offset, 0))}
        with jax.named_scope("mla_attn"):
            if slice_tile(cfg, S):
                # the slice kernel: the scratch leaf in place, the scores
                # in VMEM (``positions`` are ``pos_offset`` on)
                from ..ops.pallas import (
                    latent_attention_prefill, use_interpret)

                ctx = latent_attention_prefill(
                    q_full.transpose(1, 0, 2), cache["lat"], li, pos_offset,
                    sm_scale=attn_scale(cfg), v_width=r,
                    block_k=LATENT_SLICE_BLOCK, interpret=use_interpret(),
                    sel=sel)
            else:
                bound = pos_offset + S - 1 if kv_bound is None or S > 1 \
                    else kv_bound
                ctx = latent_attention(q_full, cache["lat"], li, positions,
                                       bound, cfg, sel)
    o = expand_values(ctx, layers["w_uv"]["w"][i], h.dtype)
    return h + lin(o, "wo"), cache, tap


def _asked(tap) -> tuple:
    """What a layer returns after everything else: the tap, where one was
    handed in."""
    return () if tap is None else (tap,)


def dense_layer(h, layers, i, cache, positions, pos_offset, cfg, live,
                kv_bound, tap=None):
    """Returns (h, cache), then :func:`_attention`'s ``tap`` where asked."""
    h, cache, tap = _attention(h, layers, i, i, cache, positions, pos_offset,
                               cfg, live, kv_bound, tap)
    hn = rms_norm(h, layers["ffn_norm"][i], cfg.rms_eps)
    return (h + swiglu(hn, layers, i, "w_gate", "w_up", "w_down"), cache,
            *_asked(tap))


def moe_layer(h, layers, i, cache, positions, pos_offset, cfg, live,
              kv_bound, tap=None):
    """Returns (h, cache, (rows each HELD expert took (n_held,), the
    router's picks (S, k) over all experts, picks of live rows)), then
    :func:`_attention`'s ``tap`` where asked."""
    h, cache, tap = _attention(h, layers, i, cfg.n_dense_layers + i, cache,
                               positions, pos_offset, cfg, live, kv_bound,
                               tap)
    hn = rms_norm(h, layers["ffn_norm"][i], cfg.rms_eps)
    out, routed = expert_branch(hn, layers, i, cfg, live)
    return (h + out, cache, routed, *_asked(tap))


def shortcut_layer(h, layers, l, cache, positions, pos_offset, cfg, live,
                   kv_bound):
    """One ``longcat-flash`` layer ``l``: sub-block ``s`` is attention
    (weights and cache leaf ``2 l + s``) then a dense SwiGLU; the ONE expert
    branch reads sub-block 0's normed rows ``u`` and its result is carried
    across sub-block 1 to the layer's end.  Returns as :func:`moe_layer`."""
    def attn(h, cache, s):
        i = 2 * l + s
        with jax.named_scope(f"attn{s}"):
            h, cache, _ = _attention(h, layers[ATTN], i, i, cache, positions,
                                     pos_offset, cfg, live, kv_bound)
        return h, cache, rms_norm(h, layers[FFN]["ffn_norm"][i], cfg.rms_eps)

    def ffn(u, s):
        with jax.named_scope(f"ffn{s}"):
            return swiglu(u, layers[FFN], 2 * l + s, "w_gate", "w_up",
                          "w_down")

    a, cache, u = attn(h, cache, 0)
    m, routed = expert_branch(u, layers[MOE], l, cfg, live)
    b = a + ffn(u, 0)
    c, cache, u = attn(b, cache, 1)
    f1 = ffn(u, 1)
    with jax.named_scope("shortcut_join"):
        return c + f1 + m, cache, routed


def forward(params: dict, cfg: ModelConfig, tokens, pos_offset, cache: dict,
            last_idx=None, return_all: bool = False, live=None,
            with_stats: bool = False, with_picks: bool = False,
            kv_bound=None, with_index: bool = False):
    """``models/llama.py forward`` for a ``deepseek2`` file: the leading
    dense layers, then the routed ones, each kind a ``fori_loop`` over its
    own stack of weights; the cache is one leaf over all layers.
    ``with_stats`` / ``with_picks`` as there (the counter vector of
    ``llama.expert_stats_len`` is over the HELD experts; the picks are the
    router's, over all).  ``kv_bound``: a lane step's ``live_bound``.
    ``with_index`` (a ``deepseek32`` file; tests and benchmarks/
    compare_dsa.py): the indexer's scores (leaves, S, n_ctx) float32 and
    the selection (the same, bool) every layer's queries were served with,
    after everything else."""
    S = tokens.shape[0]
    asked = ()
    if with_index:
        if not cfg.index_topk:
            raise ValueError("with_index: the file has no indexer")
        shape = (cfg.cache_leaves, S, cfg.n_ctx)
        asked = ((jnp.zeros(shape, jnp.float32),
                  jnp.zeros(shape, jnp.bool_)),)
    n_moe = n_moe_layers(cfg)
    # a ``longcat-flash`` file: no dense layer, every layer a
    # :func:`shortcut_layer` over all three stacks
    shortcut = cfg.attn_sublayers == 2
    check_stacks(params, cfg, ((ATTN, 2 * n_moe), (FFN, 2 * n_moe))
                 if shortcut else ())
    h = jnp.take(params["tok_emb"], tokens, axis=0).astype(jnp.bfloat16)
    positions = pos_offset + jnp.arange(S, dtype=jnp.int32)
    routed_layer, routed_stacks = (shortcut_layer, params["layers"]) \
        if shortcut else (moe_layer, params["layers"][MOE])

    # a carry ends in the tap, where one was asked for
    def dense_body(i, carry):
        return dense_layer(carry[0], params["layers"][DENSE], jnp.int32(i),
                           carry[1], positions, pos_offset, cfg, live,
                           kv_bound, *carry[2:])

    def moe_body(i, carry):
        h, cache, routed, *tap = routed_layer(
            carry[0], routed_stacks, jnp.int32(i), carry[1], positions,
            pos_offset, cfg, live, kv_bound, *carry[4:])
        return (h, cache, *moe_stats(carry[2], carry[3], i, routed), *tap)

    carry = (h, cache, *asked)
    if cfg.n_dense_layers:
        carry = jax.lax.fori_loop(0, cfg.n_dense_layers, dense_body, carry)
    routed = []
    if n_moe:
        h, new_cache, *routed = jax.lax.fori_loop(0, n_moe, moe_body, (
            *carry[:2], jnp.zeros(expert_stats_len(cfg), jnp.int32),
            jnp.zeros((n_moe, S, cfg.n_experts_used), jnp.int32),
            *carry[2:]))
        routed, asked = routed[:2], routed[2:]
    else:
        h, new_cache, *asked = carry
    tail = tuple(r for r, want in zip(routed, (with_stats, with_picks))
                 if want)
    for tap in asked:
        tail += tuple(tap)

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


def _probe_kernels(cfg: ModelConfig, asked: str, attn_impl: str, probed):
    """A decode step's and a prefill slice's read of the latent leaf are
    their kernels where the chip compiles them (``auto``: a TPU), each
    behind its own probe; a Mosaic failure degrades that read to the XLA
    loop, and says so (ops/pallas/probe.py)."""
    if asked == "pallas" or (
            asked == "auto" and jax.default_backend() == "tpu"):
        from ..obs.devtime import DEVTIME
        from ..ops.pallas.probe import (
            probe_latent_decode, probe_latent_prefill)

        probes = (("latent_decode", probe_latent_decode, "latent_kernel",
                   "decode steps read"),
                  ("latent_prefill", probe_latent_prefill,
                   "latent_slice_kernel", "prefill slices read"))
        if cfg.index_topk:
            # a ``deepseek32`` file's reads take the selection: the kernels
            # WITH the bias operand are what must compile
            from ..ops.pallas.probe import (
                probe_latent_decode_select, probe_latent_prefill_select)

            probes = (
                ("latent_decode_select", probe_latent_decode_select,
                 "latent_kernel", "decode steps read"),
                ("latent_prefill_select", probe_latent_prefill_select,
                 "latent_slice_kernel", "prefill slices read"))
        for name, probe, flag, what in probes:
            probed.append(name)
            err = probe()
            if err is None:
                cfg = dataclasses.replace(cfg, **{flag: True})
                continue
            DEVTIME.record_degrade(probe.__name__, err)
            logger.error("pallas %s kernel failed its compile probe; %s "
                         "the latent ring through the XLA loop: %s",
                         name.replace("_", " "), what, err)
    return cfg, attn_impl


def _health(cfg: ModelConfig, engine) -> dict:
    reuse = getattr(engine, "_lane_prefix", engine._prefix_cache)
    return {
        "kind": LATENT_RING,
        "latent": cfg.kv_lora_rank, "rotated_key": cfg.qk_rope_dim,
        "bytes_per_position": 2 * cfg.cache_leaves * lat_width(cfg),
        "bytes_per_position_laid_out":
            2 * cfg.cache_leaves * leaf_width(cfg),
        "read": "absorbed, blocks of %d" % LATENT_BLOCK,
        "dense_layers": cfg.n_dense_layers,
        "routed_layers": n_moe_layers(cfg),
        "experts_held": [cfg.experts_first, cfg.n_held],
        "experts_routed": cfg.n_experts,
        # a ``longcat-flash`` file's: the leaves of the ring, and the
        # router's outputs that are identity experts
        **({"attn_sublayers": cfg.n_attn_sublayers,
            "experts_zero": cfg.n_zero_experts}
           if cfg.attn_sublayers > 1 else {}),
        "prefix_reuse": "on" if reuse else "off",
        "kv_paged": "refused at start"}


def slice_read(cfg: ModelConfig, S: int) -> str:
    """Which read serves a prefill slice of ``S`` tokens."""
    return "kernel" if slice_tile(cfg, S) else "loop"


def _note_prefill(counts, cfg: ModelConfig, n_prompt: int, slices) -> dict:
    # the cached rows the prompt's slices read, from the reused prefix on,
    # and which read serves them
    if slices is None:
        return {}
    return {"cache": LATENT_RING,
            "latent_read": "+".join(sorted(
                {slice_read(cfg, n) for _, n in slices})),
            "latent_positions_read": prefill_positions_read(slices, cfg)}


def _note_slice(counts, cfg: ModelConfig, tokens: int) -> None:
    counts["slices_" + slice_read(cfg, tokens)] += 1


CACHE = CacheKind(
    name=LATENT_RING, arch="deepseek2",
    arch_for=lambda cfg: "longcat-flash" if cfg.attn_sublayers == 2
    else "deepseek2",
    init=init_cache, nbytes=cache_nbytes, forward=forward,
    step_bound=ring_step_bound,
    supports={
        "int8": "its latent ring is bf16 only",
        "paged": "a pool page is a run of K and V slots per KV head, and "
                 "its cache is one latent row a position for all heads"},
    rolls_back=True,   # positional, as the ring is
    # ``attn_impl`` stays xla: a prefill slice's attention is this file's
    # own read (the loop, or the kernel of the same absorbed form on the
    # latent leaf: ``engine_health`` says which), and the ring's flash
    # kernel serves nothing
    attn_impl=lambda cfg, asked: "xla",
    probe_kernels=_probe_kernels,
    decode_kernel_block=kernel_block,
    health=_health,
    engine_health=lambda cfg: {
        "latent_slice_read": "kernel" if cfg.latent_slice_kernel else "xla"},
    # the ring's own arithmetic under the kind's names too: a latent is
    # read once for all heads; and the slices by the read that served them
    own_gauges={"latent_positions_read_total": "read",
                "latent_positions_live_total": "live",
                "latent_slices_kernel_total": "slices_kernel",
                "latent_slices_loop_total": "slices_loop"},
    note_decode=note_ring_decode, note_prefill=_note_prefill,
    note_slice=_note_slice,
    decode_span_attrs=lambda pos: {"cache": LATENT_RING,
                                   "latent_positions": pos},
    variant=lambda cfg: INDEXED if cfg.index_topk else CACHE)


# ---------------------------------------------------------------------------
# the same kind with the index-key leaf (``deepseek32``)
# ---------------------------------------------------------------------------

def _index_sums(cfg: ModelConfig, first: int, last: int) -> tuple[int, int]:
    """Over the queries at positions ``first`` .. ``last - 1`` of one leaf:
    (index keys scored: every position at or below the query; latents
    selected: ``min(index_topk, position + 1)``).  Host arithmetic."""
    def tri(n):
        return n * (n + 1) // 2

    first, last, k = min(first, cfg.n_ctx), min(last, cfg.n_ctx), \
        cfg.index_topk
    selected = tri(min(last, k)) - tri(min(first, k)) \
        + k * (max(last, k) - max(first, k))
    return tri(last) - tri(first), selected


def _note_indexed_decode(counts: dict, cfg: ModelConfig, wanted: list,
                         n_steps: int, live: list | None = None) -> None:
    """The ring's counters, and per wanted lane, step and leaf: the index
    keys its query scored, the latents the selection chose, and the latents
    the attention FETCHED (the blocks the read walked: the selection is a
    mask on them)."""
    before = counts["read"]
    note_ring_decode(counts, cfg, wanted, n_steps, live)
    L = cfg.cache_leaves
    counts["read_decode"] += (counts["read"] - before) * L
    for p in wanted:
        scored, selected = _index_sums(cfg, p, p + n_steps)
        counts["scored_decode"] += scored * L
        counts["selected_decode"] += selected * L


def _note_indexed_prefill(counts, cfg: ModelConfig, n_prompt: int,
                          slices) -> dict:
    attrs = _note_prefill(counts, cfg, n_prompt, slices)
    if slices is None:
        return attrs
    L = cfg.cache_leaves
    scored, selected = _index_sums(cfg, slices[0][0], n_prompt)
    counts["scored_prefill"] += scored * L
    counts["selected_prefill"] += selected * L
    # every row of a slice against the blocks the slice's read walks
    counts["read_prefill"] += L * sum(
        min(n, max(n_prompt - off, 0)) * prefill_positions_read([(off, n)],
                                                                cfg)
        for off, n in slices)
    return {**attrs, "index_keys_scored": scored,
            "latents_selected": selected, "select": "mask"}


def _indexed_health(cfg: ModelConfig, engine) -> dict:
    h = _health(cfg, engine)
    both = lat_width(cfg) + cfg.index_dim
    laid = leaf_width(cfg) + index_leaf_width(cfg)
    return {**h, "index_key": cfg.index_dim, "index_heads": cfg.index_heads,
            "index_topk": cfg.index_topk,
            "bytes_per_position": 2 * cfg.cache_leaves * both,
            "bytes_per_position_laid_out": 2 * cfg.cache_leaves * laid,
            "read": h["read"] + ", the selection a mask"}


def _phased(name: str, key: str) -> dict:
    return {f'{name}{{phase="{phase}"}}': f"{key}_{phase}"
            for phase in ("prefill", "decode")}


#: a ``deepseek32`` file's latent ring: TWO leaves a layer of unlike width
#: (the latents and the index keys), both one row a position, so it rolls
#: back, is claimed and is copied as the one-leaf ring is (every engine
#: maps over the leaves); its decode steps always take the lanes' bound
#: (the indexer's scores are a loop in plain XLA, whichever read serves the
#: attention), and it counts what was scored, selected and fetched
INDEXED = dataclasses.replace(
    CACHE, arch="deepseek32", arch_for=None, variant=None,
    step_bound=lambda cfg, pos, live=None: live_bound(pos, live),
    health=_indexed_health,
    own_gauges={**CACHE.own_gauges,
                **_phased("index_keys_scored_total", "scored"),
                **_phased("latents_selected_total", "selected"),
                **_phased("latents_read_total", "read")},
    note_decode=_note_indexed_decode, note_prefill=_note_indexed_prefill,
    counts_prefill=True,
    decode_span_attrs=lambda pos: {
        "cache": LATENT_RING, "latent_positions": pos, "select": "mask"},
    span_attrs=lambda cfg: {"index_topk": cfg.index_topk})
