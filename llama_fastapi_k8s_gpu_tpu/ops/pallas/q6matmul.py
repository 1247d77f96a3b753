"""Fused Q6_K dequant-matmul (Pallas): the Q4_K_M file's *other* format.

llama.cpp's Q4_K_M quantization (the reference's served artifact,
reference api.py:14, docker/Dockerfile.base:30-32) is mixed: most linears
are Q4_K but ``ffn_down``, some ``attn_v`` layers and ``output.weight`` are
**Q6_K** (~27% of the weights).  Round 2 served those from an int8 requant
(1 B/weight); this kernel keeps them at their file precision and
~0.88 B/weight in HBM — less decode traffic AND less of the 16 GB chip —
so a Q4_K_M file serves fully fused with no requantization anywhere.

Same design as the v2 Q4_K kernel (ops/pallas/qmatmul.py — float nibble
split, lane-tiled scales, corrections folded into extra K columns), adapted
to Q6_K's layout (gguf/quants.py: ``y = d·sc[j]·(q6−32)``, 16 sub-blocks of
16, int8 sub-scales, ``q6 = ql_nibble | qh_crumb<<4`` ∈ [0,64)):

- the 4 low bits of each weight ride a re-biased packed byte
  ``v4 = (hi−8)·16 + lo`` (two weights/byte), split by ``floor``;
- the 2 high bits ride a crumb byte ``v2 = ((c3·4+c2)·4+c1)·4+c0 − 128``
  (four weights/byte), split by a 3-step ``floor`` chain;
- a K-tile of 2048 = 8 super-blocks × 16 sub-blocks = exactly **128
  sub-scales**, so with element-major columns (column ``c`` → sub-block
  ``c % 128``) the effective scale ``d·sc`` lane-tiles with period 128 —
  one vreg-tiling ``pltpu.repeat``, no arithmetic;
- per weight the kernel computes ``nib·eff + crumb·(16·eff)`` (2 muls, 1
  add, 1 cast); the −32 offset and the hi-half's +8 nibble bias become 256
  correction columns dotted against per-sub-block activation sums.

Layout contract (:func:`prep_q6k`):

- ``q4`` (N, K/2) int8 — tile-local byte ``b`` ∈ [0,1024) holds the low
  nibbles of columns ``b`` and ``b+1024``; column ``c = e·128 + s``,
  sub-block ``s = c % 128`` (block-major), element ``e = c//128`` ∈ [0,16).
- ``q2`` (N, K/4) int8 — byte ``b`` ∈ [0,512) holds the crumbs of columns
  ``b``, ``b+512``, ``b+1024``, ``b+1536`` (c0..c3 low-to-high).
- ``sm6`` (K/2048, N, 128) bf16 — the 128 effective sub-scales ``d·sc`` of
  the tile, block-major.

Shape requirements: ``N % 128`` == 0 and a K of whole 2048 tiles, or of
whole tiles and one TAIL tile — same classes as the Q4_K kernel
(``qmatmul.tail_of``); ineligible tensors fall back to int8
(models/params.py).

A tail of ``T`` columns (512 or 1024; PR 63) is the same layout at its own
period ``S = T/16`` sub-blocks (32 or 64), in planes beside the whole
tiles': ``q4_t`` (N, T/2) — byte ``b`` the low nibbles of columns ``b`` and
``b + T/2``, column ``c = e·S + s``; ``q2_t`` (N, T/4) — byte ``b`` the
crumbs of columns ``b + j·T/4``; ``sm6_t`` (1, N, 128) — the S sub-scales
tiled ``128/S`` times.  The activations' tail gains its own 256 correction
columns.  The last K step of a call adds the tail's product
(:func:`_q6k_head_tail_kernel`): the integer body joins the tail's
quarters to the 512 columns a dot takes (one dot for 512, two for 1024,
where a whole tile has four); :func:`dequant_rows6` puts its columns back
in the file's order.

One body builds that plane, :func:`_q6k_tile_product`: bit for bit what the
float form above gives, from integer operations on the packed bytes.  It
serves the vocabulary head's call (:func:`_q6k_2d_raw`) and, since PR 64,
the stacked calls (a layer's ``w_down`` / ``wv``: :func:`_q6k_2d_stacked_raw`)
through one builder (:func:`_q6k_call`, tiled by the call's rows, N and K:
:func:`_q6k_tiling`), and since PR 59 the grouped expert calls
(:func:`_q6k_expert_kernel` under ops/pallas/experts.py's grid and tile).
``LFKT_Q6K_KERNEL=pre`` is another LAYOUT (one combined plane, its own
kernel), chosen at load.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ...gguf.constants import GGML_BLOCK_SIZES, GGMLType, QK_K
from ...obs.devtime import register_program
from ...gguf.quants import _garbage_tolerant
from .qmatmul import (
    _augment_tile,
    batched_rows,
    _env_variant,
    _interpret,
    _lane_repeat,
    _NIB,
    _permute_tiles,
    _pick_tn,
    kernel_name,
    MANYROW_MAX,
    MANYROW_TN,
    MANYROW_VMEM,
    plain_pallas_call,
    _q4k_accum,
    q4k_compatible,
    rows_vmappable,
    _spec_axis,
    stacked_pallas_call,
    stacked_partitioned,
    tail_of,
    TK,
    TM,
    _tn_prefs_for,
    _with_tail,
)

# ``LFKT_Q6K_KERNEL`` chooses a LAYOUT, at load (first entry = the default,
# ops/pallas/qmatmul.py::_env_variant): every call is dispatched on the
# planes it is given.  The float bodies the knob used to choose among for
# the stacked calls (`cur`, `parfloor`, `vbf32`: the 07-31 / 08-01 A/Bs)
# went in PR 64, when those calls took :func:`_q6k_tile_product`.
#
# `pre`: prep stores one pre-combined int8 plane ``q6p = q6 ∈ [0,64)``
# (N, K) at 1 B/weight instead of the packed q4+q2 split at 0.75 B/weight.
# The kernel then pays ~3 VPU ops/weight (convert, ·eff, bf16 cast): what
# the integer body pays on the split planes since, at a third more bytes.
# No cell loads it (kernel_microbench_q6kpre_2026-08-01: a per-op win over
# the float body, engine-flat).  Numerics: ``q6·eff`` is an exact f32
# product (6-bit int × bf16 ≤ 14 mantissa bits), so the bf16-cast plane
# equals the split path's plane; only the +8 hi-nibble bias moves from a
# separately bf16-rounded corr column into the exact plane — deviation vs
# the split layout is corr-rounding scale (~1e-3), gated on chip.
Q6K_LAYOUTS = ("split", "pre")

_SUBS6 = TK // 16    # 128 sub-blocks of 16 per k-tile
TKA6 = TK + 256      # + [xsum_all(128) | xsum_hi(128)] correction columns


q6k_compatible = q4k_compatible  # same divisibility classes


# ---------------------------------------------------------------------------
# host-side weight prep
# ---------------------------------------------------------------------------

def _combine_q6p(q4: np.ndarray, q2: np.ndarray, n_out: int,
                 k_in: int) -> np.ndarray:
    """Split planes → the `pre` layout's combined plane ``q6p`` (N, K) int8,
    true ``q6 = nib | crumb<<4`` ∈ [0, 64) in element-major tile-column
    order.  Tile-local column ``c``: nibble from q4 byte ``c % 1024``
    (lo if c < 1024 else hi), crumb from q2 byte ``c % 512`` (digit
    ``c // 512``).  Pure integer numpy over the native packers' output —
    the C++ layout contract is untouched."""
    kt = k_in // TK
    v4 = q4.reshape(n_out, kt, TK // 2)
    lo = (v4 & 0x0F).astype(np.int8)                  # low nibble
    hi = ((v4 >> 4) + 8).astype(np.int8)              # true high nibble
    nib = np.concatenate([lo, hi], axis=2)            # (N, kt, TK)
    u = q2.reshape(n_out, kt, TK // 4).astype(np.int16) + 128  # ∈ [0,255]
    crumb = np.concatenate(
        [u & 3, (u >> 2) & 3, (u >> 4) & 3, (u >> 6) & 3], axis=2)
    return (nib + (crumb << 4).astype(np.int8)).reshape(n_out, k_in)


def _q6k_tile_planes(blocks: np.ndarray, width: int):
    """(N, nb, 210) Q6_K super-blocks -> (q4 (N, nb * 128), q2 (N, nb * 64)
    int8, sm6 (tiles, N, 128) float32) in tiles of ``width`` columns (a
    multiple of 256 that divides 2048): the layout contract above at
    ``width`` = :data:`TK`, a tail's at its own width, where the tile's
    ``width / 16`` sub-scales are tiled up to the plane's 128 lanes."""
    n_out, nb, _ = blocks.shape
    kt = nb * QK_K // width
    subs = width // 16                                # sub-blocks a tile
    ql = blocks[..., 0:128].reshape(n_out, nb, 2, 64)
    qh = blocks[..., 128:192].reshape(n_out, nb, 2, 32)
    sc = blocks[..., 192:208].view(np.int8).astype(np.float32)  # (N, nb, 16)
    d = blocks[..., 208:210].copy().view(np.float16).astype(np.float32)[..., 0]

    low = np.empty((n_out, nb, 2, 128), dtype=np.uint8)
    low[..., 0:64] = ql & 0x0F
    low[..., 64:128] = ql >> 4
    hi = np.empty((n_out, nb, 2, 128), dtype=np.uint8)
    hi[..., 0:32] = qh & 3
    hi[..., 32:64] = (qh >> 2) & 3
    hi[..., 64:96] = (qh >> 4) & 3
    hi[..., 96:128] = (qh >> 6) & 3
    q6 = (low | (hi << 4)).reshape(n_out, nb, 256)    # elem idx = sub*16 + e

    # element-major tile columns: Q[..., e, s], s = blk*16 + sub
    Q = q6.reshape(n_out, kt, width // QK_K, 16, 16).transpose(0, 1, 4, 2, 3)
    Q = np.ascontiguousarray(Q).reshape(n_out, kt, 16, subs)
    nib = Q & 0x0F
    crumb = Q >> 4                                    # ∈ [0, 4)

    lo4 = nib[:, :, :8, :].reshape(n_out, kt, width // 2)
    hi4 = nib[:, :, 8:, :].reshape(n_out, kt, width // 2)
    v4 = ((hi4.astype(np.int16) - 8) << 4) + lo4
    q4 = v4.astype(np.int8).reshape(n_out, nb * (QK_K // 2))

    cr = crumb.reshape(n_out, kt, 4, width // 4).astype(np.int16)
    v2 = (((cr[:, :, 3] * 4 + cr[:, :, 2]) * 4 + cr[:, :, 1]) * 4
          + cr[:, :, 0]) - 128
    q2 = v2.astype(np.int8).reshape(n_out, nb * (QK_K // 4))

    eff = d[..., None] * sc                           # (N, nb, 16)
    sm6 = np.tile(eff.reshape(n_out, kt, subs), (1, 1, _SUBS6 // subs))
    return q4, q2, np.ascontiguousarray(sm6.transpose(1, 0, 2))


@_garbage_tolerant
def prep_q6k(raw: np.ndarray, n_out: int, k_in: int) -> dict:
    """Raw Q6_K block bytes (row-major, ``n_out`` rows of ``k_in`` elements)
    → the kernel layout dict: {"q4", "q2", "sm6"} (split layout), with
    {"q4_t", "q2_t", "sm6_t"} beside them where ``k_in`` ends in a tail
    (``qmatmul.tail_of``), or {"q6p", "sm6"} under ``LFKT_Q6K_KERNEL=pre``
    (see Q6K_LAYOUTS; a K with a tail keeps the split layout there).

    Dispatches to the threaded C++ packer (native/src/gguf_dequant.cpp,
    bit-identical planes — tests/test_native.py) when available; the numpy
    chain below is the reference implementation and the fallback."""
    if not q6k_compatible(n_out, k_in):
        raise ValueError(f"({n_out}, {k_in}) not fused-Q6_K compatible "
                         f"(need K%{TK}==0 or a tail, N%128==0)")
    from ...native import native_prep_q6k

    tail = tail_of(k_in)
    pre = _env_variant("LFKT_Q6K_KERNEL", Q6K_LAYOUTS) == "pre" and not tail
    nat = native_prep_q6k(raw, n_out, k_in)
    if nat is not None:
        if pre:
            return {"q6p": jnp.asarray(_combine_q6p(
                        np.asarray(nat["q4"]), np.asarray(nat["q2"]),
                        n_out, k_in)),
                    "sm6": jnp.asarray(nat["sm6"])}
        return {key: jnp.asarray(a) for key, a in nat.items()}
    bs = GGML_BLOCK_SIZES[GGMLType.Q6_K][1]           # 210
    nb = k_in // QK_K
    whole = (k_in - tail) // QK_K                     # blocks in whole tiles
    blocks = np.ascontiguousarray(raw, dtype=np.uint8)[: n_out * nb * bs]
    blocks = blocks.reshape(n_out, nb, bs)
    q4, q2, sm6 = _q6k_tile_planes(blocks[:, :whole], TK)
    sm6 = jnp.asarray(sm6, dtype=jnp.bfloat16)
    if pre:
        return {"q6p": jnp.asarray(_combine_q6p(q4, q2, n_out, k_in)),
                "sm6": sm6}
    w = {"q4": jnp.asarray(q4), "q2": jnp.asarray(q2), "sm6": sm6}
    if tail:
        q4, q2, sm6 = _q6k_tile_planes(blocks[:, whole:], tail)
        w.update(q4_t=jnp.asarray(q4), q2_t=jnp.asarray(q2),
                 sm6_t=jnp.asarray(sm6, dtype=jnp.bfloat16))
    return w


def permute_x6(x: jax.Array) -> jax.Array:
    """(..., K) → (..., K): element-major column order (column ``e·128+s`` ←
    original element ``(s//16)·256 + (s%16)·16 + e``); a tail the same way
    at its own width (column ``e·(width/16) + s``)."""
    K = x.shape[-1]
    if tail_of(K):
        return _with_tail(x, permute_x6,
                          lambda t, width: _permute_tiles(t, width, 16))
    lead = x.shape[:-1]
    nl = len(lead)
    xb = x.reshape(*lead, K // TK, 8, 16, 16)         # [blk, sub, e]
    xe = jnp.transpose(xb, (*range(nl), nl, nl + 3, nl + 1, nl + 2))
    return xe.reshape(*lead, K)


def augment_x6(xp: jax.Array) -> jax.Array:
    """Permuted activations (B, K) → (B, K/TK·TKA6): each tile gains 256
    correction columns [sum per sub-block | sum over the hi-nibble half]
    dotted against [−32·eff | 8·eff]; a tail of ``width`` columns its own
    256 (``width + 256`` in all: ``qmatmul.augment_x``)."""
    B, K = xp.shape
    if tail_of(K):
        return _with_tail(xp, augment_x6,
                          lambda t, width: _augment_tile(t, width, 16, _SUBS6))
    kt = K // TK
    xt = xp.reshape(B, kt, 16, _SUBS6)
    xsum = jnp.sum(xt, axis=2)                        # (B, kt, 128)
    xsum_hi = jnp.sum(xt[:, :, 8:, :], axis=2)
    xpa = jnp.concatenate([xt.reshape(B, kt, TK), xsum, xsum_hi], axis=-1)
    return xpa.reshape(B, kt * TKA6)


def dequant_ref6(w: dict) -> jax.Array:
    """(N, K) f32 dequantized weights in **permuted** column order."""
    def tiles(q4, q2, sm6, width):
        N, half = q4.shape
        kt = half // (width // 2)
        v4 = q4.astype(jnp.float32).reshape(N, kt, width // 2)
        h = jnp.floor(v4 / 16.0)
        nib = jnp.concatenate([v4 - 16.0 * h, h + 8.0], axis=2)
        u = q2.astype(jnp.float32).reshape(N, kt, width // 4) + 128.0
        c3 = jnp.floor(u / 64.0)
        r = u - 64.0 * c3
        c2 = jnp.floor(r / 16.0)
        r = r - 16.0 * c2
        c1 = jnp.floor(r / 4.0)
        c0 = r - 4.0 * c1
        crumb = jnp.concatenate([c0, c1, c2, c3], axis=2)     # (N, kt, width)
        q6 = nib + 16.0 * crumb
        eff = jnp.transpose(sm6, (1, 0, 2)).astype(jnp.float32)
        eff = jnp.tile(eff, (1, 1, width // _SUBS6))
        return (eff * (q6 - 32.0)).reshape(N, kt * width)

    whole = tiles(w["q4"], w["q2"], w["sm6"], TK)
    if "q4_t" not in w:
        return whole
    return jnp.concatenate(
        [whole, tiles(w["q4_t"], w["q2_t"], w["sm6_t"],
                      2 * w["q4_t"].shape[1])], axis=1)


def dequant_rows6(w: dict, rows: jax.Array, k_in: int) -> jax.Array:
    """Rows ``rows`` of a split-layout Q6_K matrix, dequantized, in the
    FILE's column order: (len(rows), ``k_in``) f32, the weights the kernels
    multiply by (:func:`dequant_ref6` on the gathered rows' planes, its
    columns put back in order, the zero fill past ``k_in`` cut).  What an
    embedding lookup of a tied Q6_K head reads (models/phi4flash.py): the
    rows it gathers, not a second table."""
    if "q4" not in w:
        raise ValueError("dequant_rows6 reads the split layout (q4, q2, sm6)")
    p = dequant_ref6({key: jnp.take(a, rows, axis=1 if key.startswith("sm6")
                                    else 0) for key, a in w.items()})
    K = p.shape[1]
    n = K // TK * TK
    # permute_x6's inverse, each tile at its own width: column e * (width /
    # 16) + s of a tile back to element s * 16 + e
    cols = [_permute_tiles(p[:, :n], TK, TK // 16)]
    if n < K:
        cols.append(_permute_tiles(p[:, n:], K - n, (K - n) // 16))
    return jnp.concatenate(cols, axis=1)[:, :k_in]


# ---------------------------------------------------------------------------
# kernel
# ---------------------------------------------------------------------------

def _q6k_pre_kernel(xpa_ref, q6p_ref, sm_ref, o_ref, *, interpret):
    """`pre` layout body: one combined int8 plane, ~3 VPU ops/weight.

    ``y = Σ x·(q6−32)·eff = dot(x, q6·eff) − 32·Σ_s eff_s·xsum_s`` — the
    hi-nibble bias lives inside the exact plane, so only the −32 offset
    rides the correction dot; the xsum_hi half of the shared augment_x6
    columns is dotted against zeros (keeping one activation layout for
    both Q6_K layouts costs 128 dead columns ≈ 6% of the corr dot, which
    is itself ~6% of the MXU work)."""
    TN = q6p_ref.shape[0]
    sm = sm_ref[...].reshape(TN, 128)
    eff = _lane_repeat(sm, TK // 128, interpret)
    a = (q6p_ref[...].astype(jnp.float32) * eff).astype(jnp.bfloat16)
    corr = jnp.concatenate(
        [sm * -32.0, jnp.zeros_like(sm)], axis=1).astype(jnp.bfloat16)
    xpa = xpa_ref[...]
    part = jax.lax.dot_general(
        xpa[:, :TK], a, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    part += jax.lax.dot_general(
        xpa[:, TK:], corr, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    _q4k_accum(o_ref, part)


def _q6k_pre_specs(B: int, TN: int):
    """(in_specs, out_spec) for the `pre` layout: one (TN, TK) int8 plane
    plus the shared sm6 scale plane."""
    return (
        [
            ((B, TKA6), lambda n, k: (0, k)),
            ((TN, TK), lambda n, k: (n, k)),
            ((1, TN, 128), lambda n, k: (k, n, 0)),
        ],
        ((B, TN), lambda n, k: (0, n)),
    )


_TN_PREFS_Q6K = (256, 128)  # the `pre` layout's calls (float32 planes)


# ---------------------------------------------------------------------------
# the split layout's calls: the vocabulary head's (the one unstacked fused
# tensor: ``ops/linear.py linear`` is called by the models' ``head``
# functions alone) and a layer's stacked ``w_down`` / ``wv``, one body
# (:func:`_q6k_tile_product`) under one builder (:func:`_q6k_call`), tiled
# by the call's shape (:func:`_q6k_tiling`)
# ---------------------------------------------------------------------------

#: the widest N tile of a few-row call, as 128-row units: the tile is the
#: largest ``128 * d`` with ``d`` up to this that divides N (128256 = 167 x
#: 768, 153600 = 150 x 1024).  On the chip at 153600 x 6144, 16 rows, all
#: of K a step: 1.17 / 1.14 / 1.12 ms at d = 4 / 8 / 16, and 1.62 at the
#: float body's (256, one K tile), whose 1800 grid steps cost 0.27 us each
HEAD_TN_UNITS = 8
#: the most weights of one grid step's block (N tile x K tiles x 2048)
HEAD_W_BLOCK = 1024 * 4 * 2048
#: scoped VMEM of a few-row call
HEAD_VMEM = 64 * 2 ** 20
#: the fewest grid steps a few-row call is tiled into where its shape allows:
#: the first block's fetch hides behind nothing, so a call of one or two
#: steps fetches, then computes.  On the chip (docs/PERF.md "Rows of a fused
#: matmul call", PR 64): ``wv`` at 1024 x 4096, 16 rows, 8.0 us at 4 steps,
#: 8.3-8.7 at 2 or 8, 10.7 at one, 10.6 at 16; from 8 steps up a ``w_down``
#: reads the same within the sweep's 2 % ((512, 7) 8 steps, (256, 7) 16,
#: (1024, 1) 28 at 4096 x 14336: 78-81 % of ``stored bytes / 819 GB/s``)
MIN_STEPS = 4
#: the most bytes of a few-row call's activation block (K tiles a step x rows
#: x 2304 bfloat16): four K tiles of a :data:`TM`-row block, what the head's
#: widest call takes.  A slice beside live lanes (256 rows) of a K of seven
#: tiles takes one a step
X_BLOCK = 4 * TM * TKA6 * 2
#: what ``/health`` ``engine.head_kernel`` calls :func:`_q6k_2d_raw` as it is
#: built here (serving/registry.py ``head_kind``): a change of the body the
#: unstacked call runs changes this name with it
HEAD_KERNEL = "q6k-head"
#: what ``/health`` ``engine.q6k_kernel`` calls the body of the STACKED
#: split-layout calls (serving/registry.py ``stacked_q6k_kind``): the integer
#: dequantization of :func:`_q6k_tile_product`, as ``q6k-int`` names it for
#: the grouped expert calls (ops/pallas/experts.py ``FAMILIES``)
STACKED_KERNEL = "q6k-int"

_CRUMB = 0x30303030              # a crumb, where ``q6 = nib | crumb << 4`` has it


def _q6k_tile_product(q4, q2, sm, xpa, interpret, width: int = TK):
    """One K tile of a Q6_K product: :func:`dequant_ref6`'s plane (the float
    ``floor`` form of the module's text) bit for bit, at half that form's
    vector operations.  The packed bytes are taken
    apart as INTEGERS, four weight rows a 32-bit word (the int8 planes
    bitcast in the kernel), and joined to ``q6 = nib | crumb << 4`` before
    the scale, so that a weight pays one conversion, one multiply by ``eff``
    (exact: 6 bits by a bfloat16) and the bfloat16 cast.  The high half's
    nibble keeps its bias of -8 (the byte holds ``hi - 8``), whose +8 stays
    in the correction columns.

    The operands are read by the caller's thunks, in this order: ``q4()``
    (TN, TK/2) and ``q2()`` (TN, TK/4) int8, ``sm()`` (TN, 128), ``xpa()``
    (B, TKA6): thunks, so that each read stays where it was among the
    operations and the head's program keeps its text
    (tools/traced_program_hashes.py).  Returns the (B, TN) float32 product.
    The bodies that run it: the head's and the stacked calls'
    (:func:`_q6k_head_kernel`) and the grouped expert calls'
    (:func:`_q6k_expert_kernel`).  ``width``: the tile's columns, a tail's
    512 or 1024 (q4 (TN, width/2), q2 (TN, width/4), xpa (B, width + 256)):
    its quarters are joined to the 512 columns a dot takes, one dot or two
    where a whole tile has four."""
    from jax.experimental.pallas import tpu as pltpu

    Q = width // 4

    def signed(t, flip):
        # a byte ``16 c + u``, u = (hi - 8) mod 16, to the int8 ``16 c + hi
        # - 8``: u ^ 8 = hi, then - 8 without a borrow between the bytes
        # (``flip``: bits to turn on the way, in the same operation)
        return ((t ^ (0x08080808 | flip)) + 0x78787878) ^ -0x7F7F7F80

    dot = functools.partial(
        jax.lax.dot_general, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    w4 = pltpu.bitcast(q4(), jnp.int32)
    w2 = pltpu.bitcast(q2(), jnp.int32)
    lo, hi = w4 & _NIB, (w4 >> 4) & _NIB          # hi: (hi - 8) mod 16
    # (the crumb byte is stored less 128: its top bit, the fourth crumb's
    # high one, arrives turned)
    q6 = (lo[:, :Q] | ((w2 << 4) & _CRUMB),       # columns [0, 512)
          lo[:, Q:] | ((w2 << 2) & _CRUMB),       # [512, 1024)
          signed(hi[:, :Q] | (w2 & _CRUMB), 0),   # [1024, 1536)
          signed(hi[:, Q:] | ((w2 >> 2) & _CRUMB), 0x20202020))
    if width < TK:
        g, Q = TK // width, TK // 4
        q6 = tuple(jnp.concatenate(q6[i:i + g], axis=1)
                   for i in range(0, 4, g))
    sm = sm()                                     # (TN, 128): eff = d·sc
    eff = _lane_repeat(sm, Q // 128, interpret)
    corr = jnp.concatenate([sm * -32.0, sm * 8.0],
                           axis=1).astype(jnp.bfloat16)
    xpa = xpa()
    # a dot a quarter: joined into one (TN, TK) plane first, the same work
    # takes 1.34 ms where this takes 1.14 (153600 x 6144, on the chip): the
    # float32 sums of a K tile are taken in another order than the stacked
    # body's one dot takes them
    p = dot(xpa[:, width:], corr)
    for c, q in enumerate(q6):
        a = (pltpu.bitcast(q, jnp.int8).astype(jnp.float32) * eff
             ).astype(jnp.bfloat16)               # (TN, 512)
        p += dot(xpa[:, c * Q:(c + 1) * Q], a)
    return p


def _q6k_head_kernel(xpa_ref, q4_ref, q2_ref, sm_ref, o_ref, *, interpret,
                     tiles, accumulate):
    """The body of the split layout's calls, the head's (whose name it keeps)
    and the stacked ones: :func:`_q6k_tile_product` over ``tiles`` K tiles a
    grid step.  xpa (B, tiles * TKA6), which is the whole of it, fetched once
    a call, where the step holds all of K; q4 (TN, tiles * TK/2), q2 (TN,
    tiles * TK/4) int8; sm (tiles, TN, 128)."""
    part = _q6k_head_tiles(xpa_ref, q4_ref, q2_ref, sm_ref, interpret, tiles)
    if accumulate:
        _q4k_accum(o_ref, part)
    else:
        o_ref[...] = part


def _q6k_head_tiles(xpa_ref, q4_ref, q2_ref, sm_ref, interpret, tiles):
    """The products of a grid step's ``tiles`` whole K tiles, summed in the
    tiles' order."""
    Q = TK // 4
    part = None
    for j in range(tiles):
        p = _q6k_tile_product(
            lambda: q4_ref[:, j * 2 * Q:(j + 1) * 2 * Q],
            lambda: q2_ref[:, j * Q:(j + 1) * Q],
            lambda: sm_ref[j],
            lambda: xpa_ref[:, j * TKA6:(j + 1) * TKA6], interpret)
        part = p if part is None else part + p
    return part


def _q6k_head_tail_kernel(xpa_ref, xt_ref, q4_ref, q2_ref, sm_ref, q4t_ref,
                          q2t_ref, smt_ref, o_ref, *, interpret, tiles,
                          steps):
    """:func:`_q6k_head_kernel` of a K that ends in a tail tile
    (``qmatmul.tail_of``): the LAST of the call's ``steps`` K steps adds the
    tail's product (xt (B, T + 256); q4t (TN, T/2), q2t (TN, T/4); smt (1,
    TN, 128)), whose blocks arrived with the N tile's first."""
    part = _q6k_head_tiles(xpa_ref, q4_ref, q2_ref, sm_ref, interpret, tiles)

    def tail():
        return _q6k_tile_product(
            lambda: q4t_ref[...], lambda: q2t_ref[...], lambda: smt_ref[0],
            lambda: xt_ref[...], interpret, 2 * q4t_ref.shape[1])

    if steps == 1:
        o_ref[...] = part + tail()
        return
    _q4k_accum(o_ref, part)

    @pl.when(pl.program_id(1) == steps - 1)
    def _():
        o_ref[...] += tail()


def _q6k_expert_kernel(xpa_ref, q4_ref, q2_ref, sm_ref, o_ref, *, interpret,
                       accum):
    """The grouped expert calls' body (ops/pallas/experts.py): one K tile a
    grid step of :func:`_q6k_tile_product`, folded into the output block by
    the grid's own ``accum``.  xpa (rows, TKA6); q4 (TN, TK/2), q2 (TN,
    TK/4) int8; sm (1, TN, 128)."""
    TN = q4_ref.shape[0]
    accum(o_ref, _q6k_tile_product(
        lambda: q4_ref[...], lambda: q2_ref[...],
        lambda: sm_ref[...].reshape(TN, 128), lambda: xpa_ref[...],
        interpret))


def _n_tiles(N: int, interpret: bool) -> list:
    """The N tiles ``128 * d``, ``d`` up to :data:`HEAD_TN_UNITS`, that
    divide N, widest first (a narrower N, in interpret mode only: what
    divides it)."""
    return [128 * d for d in range(HEAD_TN_UNITS, 0, -1)
            if N % (128 * d) == 0] or [_pick_tn(N, interpret, ())]


def wide_tn(N: int, interpret: bool) -> int:
    """The widest of :func:`_n_tiles`: the N tile of the grouped Q6_K and
    Q4_K expert calls, and a head's."""
    return _n_tiles(N, interpret)[0]


def _q6k_tiling(N: int, B: int, kt: int, interpret: bool):
    """(N tile, K tiles a grid step) of a split-layout call, from its shape
    alone: ``B`` rows against (N, ``kt`` K tiles).  The rows of a decode step
    or of a slice beside live lanes (up to :data:`TM`): of the N tiles ``128
    * d`` that divide N (:data:`HEAD_TN_UNITS`) and the K tiles a step that
    divide the call's, fit :data:`HEAD_W_BLOCK` and keep the activations'
    block inside :data:`X_BLOCK`, the pair of the FEWEST grid steps that
    still leaves :data:`MIN_STEPS` of them (the fewest of all where none
    does), and of two such pairs the one with more of K a step (where a step
    holds all of K the activations are fetched once a call).  The head's N is
    so tall that this is its widest tile and all the K tiles that fit;
    ``w_down`` at 4096 x 14336 takes (512, 7): 8 steps where the float body
    had (256, 1): 112; ``wv`` at 1024 x 4096 (256, 2): 4 for 8.  A many-row
    call keeps its own tiles, a K tile a step."""
    if B > TM:
        return _pick_tn(N, interpret, prefs=MANYROW_TN), 1
    pairs = [(tn, t) for tn in _n_tiles(N, interpret)
             for t in range(1, kt + 1)
             if kt % t == 0 and (t == 1 or (
                 tn * t * TK <= HEAD_W_BLOCK and t * B * TKA6 * 2 <= X_BLOCK))]

    def steps(pair):
        return (N // pair[0]) * (kt // pair[1])

    enough = [p for p in pairs if steps(p) >= MIN_STEPS] or pairs
    return min(enough, key=lambda p: (steps(p), -p[1], -p[0]))


def _q6k_call(xpa: jax.Array, q4: jax.Array, q2: jax.Array, sm: jax.Array,
              tail: tuple, interpret: bool, idx=None) -> jax.Array:
    """The split layout's call, the ONE builder of its ``pallas_call``:
    ``xpa`` (B, kt * TKA6, and a tail's T + 256 columns after them) against
    the planes of (N, kt whole K tiles) and ``tail`` (q4_t, q2_t, sm6_t; ()
    for none), unstacked (``idx`` None: the head's) or on layer ``idx`` of
    stacked planes."""
    B, N, kt = xpa.shape[0], q4.shape[-2], xpa.shape[1] // TKA6
    TN, tiles = _q6k_tiling(N, B, kt, interpret)
    kernel = functools.partial(_q6k_head_kernel, interpret=interpret,
                               tiles=tiles, accumulate=tiles < kt)
    acts = [((B, tiles * TKA6), lambda n, k: (0, k))]
    operands = [xpa]
    planes = [
        ((TN, tiles * TK // 2), lambda n, k: (n, k)),
        ((TN, tiles * TK // 4), lambda n, k: (n, k)),
        ((tiles, TN, 128), lambda n, k: (k, n, 0)),
    ]
    if tail:
        T = 2 * tail[0].shape[-1]
        kernel = functools.partial(
            _q6k_head_tail_kernel, interpret=interpret, tiles=tiles,
            steps=kt // tiles)
        acts.append(((B, T + 256), lambda n, k: (0, 0)))
        operands.append(xpa[:, kt * TKA6:])     # an operand of its own
        planes += [
            ((TN, T // 2), lambda n, k: (n, 0)),
            ((TN, T // 4), lambda n, k: (n, 0)),
            ((1, TN, 128), lambda n, k: (0, n, 0)),
        ]
    args = (kernel, (N // TN, kt // tiles), acts + planes,
            ((B, TN), lambda n, k: (0, n)),
            jax.ShapeDtypeStruct((B, N), jnp.float32), interpret,
            kernel_name("q6k", B))
    if idx is None:
        return plain_pallas_call(*args, few_vmem=HEAD_VMEM)(
            *operands, q4, q2, sm, *tail)
    return stacked_pallas_call(*args, n_act=len(acts), few_vmem=HEAD_VMEM)(
        idx, *operands, q4, q2, sm, *tail)


def _q6k_2d_raw(xpa: jax.Array, q4: jax.Array, q2: jax.Array, sm: jax.Array,
                interpret: bool, tail: tuple = ()) -> jax.Array:
    """``tail``: (q4_t, q2_t, sm6_t), the planes of K's tail tile."""
    return _q6k_call(xpa, q4, q2, sm, tail, interpret)


def _q6k_pre_2d_raw(xpa: jax.Array, q6p: jax.Array, sm: jax.Array,
                    interpret: bool) -> jax.Array:
    B, KA = xpa.shape
    K = (KA // TKA6) * TK
    N = q6p.shape[0]
    TN = _pick_tn(N, interpret, prefs=_tn_prefs_for(B, _TN_PREFS_Q6K))
    in_specs, out_spec = _q6k_pre_specs(B, TN)
    return plain_pallas_call(
        functools.partial(_q6k_pre_kernel, interpret=interpret),
        (N // TN, K // TK), in_specs, out_spec,
        jax.ShapeDtypeStruct((B, N), jnp.float32), interpret,
        kernel_name("q6k_pre", B),
    )(xpa, q6p, sm)


@functools.lru_cache(maxsize=4)
def _q6k_pre_2d_partitioned(interpret: bool):
    """GSPMD rule for the `pre` layout (same contract: partition N/rows,
    never K)."""
    from jax.experimental.custom_partitioning import custom_partitioning
    from jax.sharding import NamedSharding, PartitionSpec as P

    @custom_partitioning
    def fn(xpa, q6p, sm):
        return _q6k_pre_2d_raw(xpa, q6p, sm, interpret)

    def partition(mesh, arg_shapes, result_shape):
        rows = _spec_axis(arg_shapes[0].sharding, 0)
        n_ax = _spec_axis(arg_shapes[1].sharding, 0)
        arg_shardings = (
            NamedSharding(mesh, P(rows, None)),
            NamedSharding(mesh, P(n_ax, None)),
            NamedSharding(mesh, P(None, n_ax, None)),
        )

        def lower(xpa, q6p, sm):
            return _q6k_pre_2d_raw(xpa, q6p, sm, interpret)

        return (mesh, lower, NamedSharding(mesh, P(rows, n_ax)),
                arg_shardings)

    def infer(mesh, arg_shapes, result_shape):
        return NamedSharding(
            mesh, P(_spec_axis(arg_shapes[0].sharding, 0),
                    _spec_axis(arg_shapes[1].sharding, 0)))

    fn.def_partition(
        partition=partition,
        infer_sharding_from_operands=infer,
        sharding_rule="b k, n j, t n l -> b n",
    )
    return jax.jit(rows_vmappable(fn, xpa_pos=0))


def _q6k_pre_2d_stacked_raw(idx: jax.Array, xpa: jax.Array, q6p: jax.Array,
                            sm: jax.Array, interpret: bool) -> jax.Array:
    B, KA = xpa.shape
    K = (KA // TKA6) * TK
    N = q6p.shape[1]
    TN = _pick_tn(N, interpret, prefs=_tn_prefs_for(B, _TN_PREFS_Q6K))
    in_specs, out_spec = _q6k_pre_specs(B, TN)
    call = stacked_pallas_call(
        functools.partial(_q6k_pre_kernel, interpret=interpret),
        grid=(N // TN, K // TK),
        in_specs=in_specs,
        out_spec=out_spec,
        out_shape=jax.ShapeDtypeStruct((B, N), jnp.float32),
        interpret=interpret,
        name=kernel_name("q6k_pre", B),
    )
    return call(idx, xpa, q6p, sm)


@functools.lru_cache(maxsize=4)
def _q6k_pre_2d_stacked_partitioned(interpret: bool):
    return stacked_partitioned(
        _q6k_pre_2d_stacked_raw, "i, b k, l n j, l t n m -> b n", interpret)


@functools.lru_cache(maxsize=4)
def _q6k_2d_partitioned(interpret: bool, tail: bool = False):
    """GSPMD rule mirroring the Q4_K kernel's: partition over N (and rows),
    never over K; tp-sharded weights compute locally.  ``tail``: the call
    takes a tail tile's three planes after the whole tiles'."""
    from jax.experimental.custom_partitioning import custom_partitioning
    from jax.sharding import NamedSharding, PartitionSpec as P

    @custom_partitioning
    def fn(xpa, q4, q2, sm, *tail_planes):
        return _q6k_2d_raw(xpa, q4, q2, sm, interpret, tail_planes)

    def partition(mesh, arg_shapes, result_shape):
        rows = _spec_axis(arg_shapes[0].sharding, 0)
        n_ax = _spec_axis(arg_shapes[1].sharding, 0)
        planes = (NamedSharding(mesh, P(n_ax, None)),
                  NamedSharding(mesh, P(n_ax, None)),
                  NamedSharding(mesh, P(None, n_ax, None)))
        arg_shardings = (NamedSharding(mesh, P(rows, None)), *planes,
                         *(planes if tail else ()))
        result_sharding = NamedSharding(mesh, P(rows, n_ax))

        def lower(xpa, q4, q2, sm, *tail_planes):
            return _q6k_2d_raw(xpa, q4, q2, sm, interpret, tail_planes)

        return mesh, lower, result_sharding, arg_shardings

    def infer(mesh, arg_shapes, result_shape):
        return NamedSharding(
            mesh, P(_spec_axis(arg_shapes[0].sharding, 0),
                    _spec_axis(arg_shapes[1].sharding, 0)))

    fn.def_partition(
        partition=partition,
        infer_sharding_from_operands=infer,
        sharding_rule="b k, n j, n p, t n l, n q, n r, u n s -> b n" if tail
        else "b k, n j, n p, t n l -> b n",
    )
    return jax.jit(rows_vmappable(fn, xpa_pos=0, bound=MANYROW_MAX))


def _q6k_2d_stacked_raw(idx: jax.Array, xpa: jax.Array, q4: jax.Array,
                        q2: jax.Array, sm: jax.Array, *tail,
                        interpret: bool) -> jax.Array:
    """``tail``: (q4_t, q2_t, sm6_t), the stacked planes of K's tail tile."""
    return _q6k_call(xpa, q4, q2, sm, tail, interpret, idx)


@functools.lru_cache(maxsize=4)
def _q6k_2d_stacked_partitioned(interpret: bool, tail: bool = False):
    return stacked_partitioned(
        _q6k_2d_stacked_raw,
        "i, b k, l n j, l n p, l t n m, l n q, l n r, l u n s -> b n" if tail
        else "i, b k, l n j, l n p, l t n m -> b n", interpret, MANYROW_MAX)


def _q6k_planes(w: dict) -> tuple:
    """A split-layout weight dict's planes in the calls' order: the whole
    tiles', then the tail's where the K has one."""
    return (w["q4"], w["q2"], w["sm6"]) + (
        (w["q4_t"], w["q2_t"], w["sm6_t"]) if "q4_t" in w else ())


def q6k_matmul_stacked(x: jax.Array, w: dict, idx,
                       interpret: bool | None = None) -> jax.Array:
    """x (..., K) → (..., N) against layer ``idx`` of stacked Q6_K weights
    (``q4`` (L, N, K/2), ``q2`` (L, N, K/4), ``sm6`` (L, K/2048, N, 128),
    with ``q4_t`` / ``q2_t`` / ``sm6_t`` beside them where K ends in a tail;
    or ``q6p`` (L, N, K) + ``sm6`` for the `pre` layout).  The program is
    dispatched on the LAYOUT (plane presence), not the env knob, so
    weights prepped under one variant can never meet the other family's
    kernel."""
    K = x.shape[-1]
    lead = x.shape[:-1]
    xpa = augment_x6(permute_x6(x).reshape(-1, K).astype(jnp.bfloat16))
    i1 = jnp.asarray(idx, jnp.int32).reshape(1)
    if "q6p" in w:
        fn = _q6k_pre_2d_stacked_partitioned(_interpret(interpret))
        y = batched_rows(lambda xp, *ws: fn(i1, xp, *ws),
                         xpa, w["q6p"], w["sm6"])
    else:
        fn = _q6k_2d_stacked_partitioned(_interpret(interpret), "q4_t" in w)
        y = batched_rows(lambda xp, *ws: fn(i1, xp, *ws),
                         xpa, *_q6k_planes(w), bound=MANYROW_MAX)
    return y.reshape(*lead, -1).astype(x.dtype)


def q6k_matmul(x: jax.Array, w: dict, interpret: bool | None = None) -> jax.Array:
    """x (..., K) bf16/f32 → (..., N) in x.dtype, weights in Q6_K kernel
    layout.  The fused path of ``ops.linear.linear`` for Q6_K tensors.
    Layout-dispatched like :func:`q6k_matmul_stacked`."""
    K = x.shape[-1]
    lead = x.shape[:-1]
    xpa = augment_x6(permute_x6(x).reshape(-1, K).astype(jnp.bfloat16))
    if "q6p" in w:
        fn = _q6k_pre_2d_partitioned(_interpret(interpret))
        y = batched_rows(fn, xpa, w["q6p"], w["sm6"])
    else:
        fn = _q6k_2d_partitioned(_interpret(interpret), "q4_t" in w)
        y = batched_rows(fn, xpa, *_q6k_planes(w), bound=MANYROW_MAX)
    return y.reshape(*lead, -1).astype(x.dtype)


# devtime inventory (lfkt-lint PERF001): trace-inner fused-matmul builders
# (see ops/pallas/qmatmul.py for the attribution contract)
register_program("_q6k_2d_raw", site="ops.pallas.q6matmul")
register_program("_q6k_2d_partitioned", site="ops.pallas.q6matmul")
register_program("_q6k_pre_2d_partitioned", site="ops.pallas.q6matmul")
