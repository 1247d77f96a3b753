"""Fused Q4_K dequant-matmul (Pallas): decode directly from ~5-bit weights.

The decode hot loop is HBM-bandwidth-bound: every generated token reads every
weight byte once (SURVEY.md §6; the reference's llama.cpp engine solves this
on GPU with fused dequant-matmul CUDA kernels inside llama-cpp-python,
reference docker/Dockerfile.base:30-32).  The int8 path (ops/linear.py)
already halves traffic vs bf16; this kernel goes further by keeping the
weights in (almost) their GGUF Q4_K form in HBM:

- packed 4-bit nibbles (re-biased, see below)            → 4.00 bit/weight
- folded per-sub-block scale/min in bf16 (d·sc, dmin·mn) → 1.00 bit/weight
                                                      total ≈ 5 bit/weight

i.e. ~0.62× the int8 bytes/token, which on a bandwidth-bound decode is a
~1.6× throughput ceiling raise.  Tiles are dequantized into VMEM only, fed
straight to the MXU, and never written back to HBM.

Dequant cost design (v2 — the round-2 kernel lost 2× to int8 because it
expanded per-sub-block scales over lanes with 0/1 matmuls, ~128 MXU MACs per
weight; measured on v5e this kernel is ~1.2× *faster* than the int8 matvec
at ~0.5× the bytes):

1. **Float nibble split.**  Mosaic has no cheap int8 bit ops (int8
   elementwise lowering fails; int32 widening costs 4× the registers), so
   the packed byte is stored *re-biased*, ``v = (hi−8)·16 + lo`` ∈ [−128,127],
   and split in float arithmetic: ``h = floor(v/16) = hi−8``,
   ``l = v − 16h = lo``.  Both come out of 4 VPU ops on f32 vregs.
2. **Lane-tiled scales.**  Columns are laid out *element-major* inside each
   2048-wide K tile (column ``c`` belongs to sub-block ``c % 64``), so the
   per-sub-block scale vector expands over lanes by vreg tiling
   (``pltpu.repeat`` of the 128-lane [sc|sc] pair) — a register copy, not
   arithmetic.
3. **Affine corrections ride the matmul.**  The per-sub-block min and the
   +8 nibble bias never touch the per-weight path: since
   ``w = q·sc − mn`` and ``Σ_c x_c·const_s = const_s·(Σ x over sub-block)``,
   both fold into 128 extra "correction" K-columns — the activation side
   carries per-sub-block sums (``xsum``, ``xsum_hi``), the weight side
   carries ``[−mn | 8·sc]`` — handled by the same MXU dot that does the real
   work.  Per weight the kernel computes exactly one multiply (``l·sc`` /
   ``h·sc``) plus the bf16 cast.

Layout contract (produced by :func:`prep_q4k` from raw GGUF block bytes; bit
layouts follow gguf/quants.py, the numpy oracle).  The K axis is processed
in fixed tiles of ``TK = 2048`` elements = 8 Q4_K super-blocks:

- ``qs`` (N, K/2) int8 — re-biased packed bytes.  Tile-local byte ``b`` ∈
  [0,1024) holds the weights of columns ``b`` (lo) and ``b+1024`` (hi),
  where column ``c = e·64 + s``: sub-block ``s = c % 64`` (block-major:
  super-block ``s//8``, sub ``s%8``), element ``e = c // 64`` ∈ [0,32).
- ``sm`` (K/2048, N, 128) bf16 — per k-tile: 64 effective scales (d·sc)
  then 64 effective mins (dmin·mn), one per 32-element sub-block, in natural
  block-major order.  Merging them into one 128-lane array keeps every
  Pallas block shape on Mosaic's (8, 128) tiling grid.

Activations are pre-permuted to the same column order by :func:`permute_x`
(a reshape+transpose fused into the surrounding XLA graph) and augmented
with the per-sub-block sums by :func:`augment_x`.

Shape requirements: ``N % 128 == 0`` and a K of whole tiles (``K % 2048 ==
0``: all Llama-3 / Mistral linear shapes; a K that ``ops/linear.py
padded_k`` fills up to one with zero blocks: 7168 -> 8192, 11008 -> 12288),
or of whole tiles and ONE TAIL TILE (:func:`tail_of`: a K above one tile, a
multiple of 512, that filling would widen by a fifth or more: 2560 = 2048 +
512, 5120 = 2 x 2048 + 1024).  Loaders fall back to the int8 format
otherwise — see models/params.py.

The tail tile (PR 63) is the same layout at its own period, in planes of its
own beside the whole tiles', which stay what they were to the bit:

- ``qs_t`` (N, T/2) int8 for a tail of ``T`` columns (512 or 1024) — byte
  ``b`` holds the weights of columns ``b`` (lo) and ``b + T/2`` (hi), column
  ``c = e·S + s`` over the tail's OWN ``S = T/32`` sub-blocks (16 or 32),
  element ``e = c // S`` ∈ [0,32): element-major with period S, which divides
  the 64 lanes of a scale vector.
- ``sm_t`` (1, N, 128) bf16 — the tail's S scales tiled ``64/S`` times, then
  its S mins tiled alike, so that :func:`_lane_repeat` expands them exactly
  as it expands a whole tile's.
- activations: :func:`permute_x` lays the last T columns out with period S,
  :func:`augment_x` appends the tail's own 128 correction columns (its S
  sub-block sums, zeros up to 64, twice): ``K + 128 * (K // 2048 + 1)``
  columns in all, nothing padded.
- kernels: the grid is the whole tiles' (N tiles x whole K tiles); the tail
  takes no step of its own, the last K step runs the body a second time on
  the tail's blocks at width T (:func:`tail_kernel`).  A quarter or a half
  of a tile's bytes, dequantization and MXU passes where the zero fill cost
  a whole tile's.

Two bodies read these planes.  :func:`_q4k_matmul_kernel`, the float split
above with its ``LFKT_Q4K_KERNEL`` variants, is the body of the dense calls
(stacked and unstacked).  :func:`_q4k_expert_kernel` /
:func:`_q4k_tile_product`, since PR 61 the body of the grouped expert calls
(ops/pallas/experts.py), takes the same bytes apart as 32-bit INTEGERS of
four weight rows (the widening that point 1 priced at 4x the registers is a
bitcast there, and Mosaic's 32-bit bit operations are cheap): the same two
bfloat16 planes bit for bit, the same three dots, at about 7.5 vector
operations a packed byte for 9-10 and no ``floor``.  The planes in HBM and
the packers are one and the same.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ...gguf.constants import GGML_BLOCK_SIZES, GGMLType, QK_K
from ...obs.devtime import register_program
from ...gguf.quants import _garbage_tolerant, unpack_scale_min_k4

TK = 2048            # K elements per kernel step = 8 super-blocks
_SUBS = TK // 32     # 64 sub-blocks per k-tile
TKA = TK + 128       # augmented tile: + [xsum_all(64) | xsum_hi(64)] columns


def _interpret(override: bool | None) -> bool:
    if override is not None:
        return override
    from . import use_interpret

    return use_interpret()


def tail_of(k_in: int) -> int:
    """The width of the narrow LAST tile a fused Q4_K / Q6_K layout of a
    ``k_in``-wide matrix ends in, or 0 where it has none: ``k_in % TK`` of a
    K above one tile that is a multiple of 512 and that filling up to the
    next multiple of :data:`TK` would widen by a fifth or more (2560 -> 512,
    3072 and 5120 -> 1024; 7168, whose fill is 14 %, and 11008, whose rest of
    768 has no period that divides 128 lanes, have none:
    ``ops/linear.py padded_k`` fills them).  Only 512 and 1024 ever meet the
    rule: a rest of 1536 is filled by a quarter of a tile at most."""
    t = k_in % TK
    return t if k_in > TK and t % 512 == 0 and 5 * (TK - t) >= k_in else 0


def q4k_compatible(n_out: int, k_in: int, for_tpu: bool | None = None) -> bool:
    """Whether (n_out, k_in) can use the fused kernel.  On TPU, N must tile
    to 128 sublanes; interpret mode (CPU tests) accepts any multiple of 8.
    K: whole tiles, or whole tiles and a tail (:func:`tail_of`)."""
    if for_tpu is None:
        for_tpu = not _interpret(None)
    return (k_in % TK == 0 or tail_of(k_in) > 0) \
        and n_out % (128 if for_tpu else 8) == 0


# ---------------------------------------------------------------------------
# host-side weight prep
# ---------------------------------------------------------------------------

def _q4k_tile_planes(blocks: np.ndarray, width: int):
    """(N, nb, 144) Q4_K super-blocks -> (qs (N, nb * 128) int8, sm (tiles,
    N, 128) float32) in tiles of ``width`` columns (a multiple of 256 that
    divides 2048): the layout contract above at ``width`` = :data:`TK`, a
    tail's at its own width, where the tile's ``width / 32`` scales (then
    its mins) are tiled up to the 64 lanes of the plane's half."""
    n_out, nb, _ = blocks.shape
    ktiles = nb * QK_K // width
    subs = width // 32                                # sub-blocks a tile
    d = blocks[..., 0:2].copy().view(np.float16).astype(np.float32)[..., 0]
    dmin = blocks[..., 2:4].copy().view(np.float16).astype(np.float32)[..., 0]
    sc, mn = unpack_scale_min_k4(blocks[..., 4:16])   # (N, nb, 8) uint8
    eff_s = d[..., None] * sc.astype(np.float32)      # (N, nb, 8)
    eff_m = dmin[..., None] * mn.astype(np.float32)
    rep = (1, 1, _SUBS // subs)
    sm = np.concatenate([
        np.tile(eff_s.reshape(n_out, ktiles, subs), rep),   # block-major
        np.tile(eff_m.reshape(n_out, ktiles, subs), rep),
    ], axis=-1)                                       # (N, ktiles, 128)
    sm = np.ascontiguousarray(sm.transpose(1, 0, 2))  # (ktiles, N, 128)

    # unpack file nibbles: byte g*32+i of a super-block holds sub 2g elem i
    # (lo) and sub 2g+1 elem i (hi)
    fqs = blocks[..., 16:].reshape(n_out, nb, 4, 32)
    q = np.empty((n_out, nb, 8, 32), dtype=np.uint8)  # [sub, elem]
    q[:, :, 0::2, :] = fqs & 0x0F
    q[:, :, 1::2, :] = (fqs >> 4) & 0x0F
    # tile-local element-major columns: Q[..., e, s], s = sb*8 + sub
    Q = q.reshape(n_out, ktiles, width // QK_K, 8, 32).transpose(0, 1, 4, 2, 3)
    Q = np.ascontiguousarray(Q).reshape(n_out, ktiles, 32, subs)
    lo = Q[:, :, :16, :].reshape(n_out, ktiles, width // 2)
    hi = Q[:, :, 16:, :].reshape(n_out, ktiles, width // 2)
    v = ((hi.astype(np.int16) - 8) << 4) + lo         # re-biased byte
    return v.astype(np.int8).reshape(n_out, nb * (QK_K // 2)), sm


@_garbage_tolerant
def prep_q4k(raw: np.ndarray, n_out: int, k_in: int) -> dict:
    """Raw Q4_K block bytes (row-major, ``n_out`` rows of ``k_in`` elements)
    → the kernel layout dict {"qs", "sm"}, with {"qs_t", "sm_t"} beside them
    where ``k_in`` ends in a tail (:func:`tail_of`).

    Dispatches to the threaded C++ packer (native/src/gguf_dequant.cpp,
    bit-identical planes — tests/test_native.py) when available; the numpy
    chain below is the reference implementation and the fallback."""
    if not q4k_compatible(n_out, k_in):
        raise ValueError(f"({n_out}, {k_in}) not fused-Q4_K compatible "
                         f"(need K%{TK}==0 or a tail, N%128==0)")
    from ...native import native_prep_q4k

    nat = native_prep_q4k(raw, n_out, k_in)
    if nat is not None:
        return {key: jnp.asarray(a) for key, a in nat.items()}
    bs = GGML_BLOCK_SIZES[GGMLType.Q4_K][1]           # 144
    nb = k_in // QK_K
    tail = tail_of(k_in)
    whole = (k_in - tail) // QK_K                     # blocks in whole tiles
    blocks = np.ascontiguousarray(raw, dtype=np.uint8)[: n_out * nb * bs]
    blocks = blocks.reshape(n_out, nb, bs)
    qs, sm = _q4k_tile_planes(blocks[:, :whole], TK)
    w = {"qs": jnp.asarray(qs), "sm": jnp.asarray(sm, dtype=jnp.bfloat16)}
    if tail:
        qs, sm = _q4k_tile_planes(blocks[:, whole:], tail)
        w.update(qs_t=jnp.asarray(qs),
                 sm_t=jnp.asarray(sm, dtype=jnp.bfloat16))
    return w


def _permute_tiles(x: jax.Array, width: int, subs: int) -> jax.Array:
    """(..., K) → (..., K), each ``width``-wide tile reordered element-major
    over its own sub-blocks of ``subs`` elements (``subs`` = 32: column ``e
    · width/32 + s`` ← the tile's element ``s · 32 + e``)."""
    K = x.shape[-1]
    lead = x.shape[:-1]
    nl = len(lead)
    xb = x.reshape(*lead, K // width, width // subs, subs)
    return jnp.swapaxes(xb, nl + 1, nl + 2).reshape(*lead, K)


def _with_tail(x: jax.Array, whole, tail_fn) -> jax.Array:
    """``whole`` on the columns of ``x`` (..., K) in whole tiles and
    ``tail_fn(columns, width)`` on its tail's, side by side."""
    n = x.shape[-1] // TK * TK
    return jnp.concatenate(
        [whole(x[..., :n]), tail_fn(x[..., n:], x.shape[-1] - n)], axis=-1)


def permute_x(x: jax.Array) -> jax.Array:
    """(..., K) → (..., K) with each 2048-element k-tile reordered to the
    kernel's element-major column order (column ``e·64 + s`` ← original
    element ``(s//8)·256 + (s%8)·32 + e``); a tail (:func:`tail_of`) the
    same way at its own width (column ``e·(width/32) + s``)."""
    K = x.shape[-1]
    if tail_of(K):
        return _with_tail(x, permute_x,
                          lambda t, width: _permute_tiles(t, width, 32))
    lead = x.shape[:-1]
    xb = x.reshape(*lead, K // TK, 8, 8, 32)          # [sb, sub, e]
    xe = jnp.transpose(xb, (*range(len(lead)), len(lead), len(lead) + 3,
                            len(lead) + 1, len(lead) + 2))
    return xe.reshape(*lead, K)


def augment_x(xp: jax.Array) -> jax.Array:
    """Permuted activations (B, K) → (B, K/TK·TKA): each 2048 tile gains
    128 correction columns [per-sub-block sum | per-sub-block hi-half sum]
    that the kernel dots against [−mn | 8·sc]; a tail of ``width`` columns
    gains its own 128 (``width + 128`` in all: :func:`_augment_tile`)."""
    B, K = xp.shape
    if tail_of(K):
        return _with_tail(xp, augment_x,
                          lambda t, width: _augment_tile(t, width, 32, _SUBS))
    kt = K // TK
    xt = xp.reshape(B, kt, 32, _SUBS)
    xsum = jnp.sum(xt, axis=2)                        # (B, kt, 64)
    xsum_hi = jnp.sum(xt[:, :, 16:, :], axis=2)
    xpa = jnp.concatenate(
        [xt.reshape(B, kt, TK), xsum, xsum_hi], axis=-1)
    return xpa.reshape(B, kt * TKA)


def _augment_tile(xt: jax.Array, width: int, elems: int,
                  lanes: int) -> jax.Array:
    """A permuted tail (B, ``width``) and its 2 x ``lanes`` correction
    columns: [sum per sub-block | sum over its high half's elements], the
    tail's ``width / elems`` sub-blocks first and zeros up to ``lanes`` (the
    plane's scales and mins are tiled there: they meet zeros).  The sums are
    a zero-filled tile's for the same sub-blocks, to the bit."""
    r = xt.reshape(xt.shape[0], elems, width // elems)
    fill = [(0, 0), (0, lanes - width // elems)]
    return jnp.concatenate(
        [xt, jnp.pad(jnp.sum(r, axis=1), fill),
         jnp.pad(jnp.sum(r[:, elems // 2:], axis=1), fill)], axis=-1)


def dequant_ref(w: dict) -> jax.Array:
    """(N, K) f32 dequantized weights in **permuted** column order — the
    small-shape oracle the kernel is tested against."""
    def tiles(qs, sm, width):
        N, half = qs.shape
        kt = half // (width // 2)
        v = qs.astype(jnp.float32).reshape(N, kt, width // 2)
        h = jnp.floor(v / 16.0)
        lo = v - 16.0 * h                             # low nibble
        hi = h + 8.0                                  # high nibble
        q = jnp.concatenate([lo, hi], axis=2)         # (N, kt, width)
        sm = jnp.transpose(sm, (1, 0, 2)).astype(jnp.float32)  # (N, kt, 128)
        sc = jnp.tile(sm[..., :_SUBS], (1, 1, width // _SUBS))
        mn = jnp.tile(sm[..., _SUBS:], (1, 1, width // _SUBS))
        return (q * sc - mn).reshape(N, kt * width)

    whole = tiles(w["qs"], w["sm"], TK)
    if "qs_t" not in w:
        return whole
    return jnp.concatenate(
        [whole, tiles(w["qs_t"], w["sm_t"], 2 * w["qs_t"].shape[1])], axis=1)


# ---------------------------------------------------------------------------
# kernel
# ---------------------------------------------------------------------------

def _env_variant(name: str, allowed: tuple) -> str:
    """Read a kernel-variant env knob, failing loud on typos (an A/B run
    must never silently compare the default against itself).  The value is
    threaded into every jit/lru cache key, so changing the env between
    calls re-traces instead of silently reusing the old program.  Shared
    by every fused kernel's LFKT_Q*_KERNEL knob; the read routes through
    the utils/config.py registry (lfkt-lint CFG001) with each variant
    table's first entry as the default."""
    from ...utils.config import knob

    v = knob(name, default=allowed[0]).strip().lower()
    if v not in allowed:
        raise ValueError(f"{name} must be {'|'.join(allowed)}, got {v!r}")
    return v


# Default (first) = resplit: bit-identical planes to `cur` via the exact
# lsc = v*sc - 16*(h*sc) cancellation.  On-chip B=1 geomean 125.9 vs
# cur's 126.8 us (ahead at (4096,4096) and (14336,4096), behind 0.3% at
# (4096,14336) — kernel_microbench_2026-08-01) and +1.8% end-to-end
# (72.32 vs 71.02 tok/s, bench_q4km_variant_ab vs bench_q4km_headline
# 2026-08-01).  vbf32 is ~8% faster still but FAILS the on-chip numerics
# gate (Mosaic truncates its f32 dot to single-pass bf16: rel_dev ~3e-2
# — the microbench dev_fail rows); never default it.
Q4K_VARIANTS = ("resplit", "cur", "vbf32", "onedot")


def _lane_repeat(v, times: int, interpret: bool):
    """Expand a 128-lane per-sub-block vector over a k-tile by vreg tiling
    (f32): ``jnp.tile`` in interpret mode, ``pltpu.repeat`` on TPU.  Shared
    by every fused kernel's scale-plane expansion."""
    if times == 1:      # (a quarter of a 512-column tail is the 128 lanes)
        return v.astype(jnp.float32)
    if interpret:
        return jnp.tile(v, (1, times)).astype(jnp.float32)
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.repeat(v, times, axis=1).astype(jnp.float32)


def _q4k_matmul_kernel(xpa_ref, qs_ref, sm_ref, o_ref, *, interpret,
                       variant="cur", accum=None):
    # xpa (B, TKA) bf16 permuted+augmented; qs (TN, TK/2) int8;
    # sm (1, TN, 128) bf16.  ``accum(o_ref, part)`` folds a k-tile's partial
    # product into the output block: :func:`_q4k_accum` on the (n, k) grids
    # here, the grouped expert grid's own (ops/pallas/experts.py).  ``W``:
    # the tile's columns, TK or a tail's own width (xpa (B, W + 128), qs
    # (TN, W/2)), read off the block
    accum = accum or _q4k_accum
    TN, W = qs_ref.shape[0], 2 * qs_ref.shape[1]
    v = qs_ref[...].astype(jnp.float32)
    sm = sm_ref[...].reshape(TN, 128)
    sc, mn = sm[:, :_SUBS], sm[:, _SUBS:]
    sc2 = jnp.concatenate([sc, sc], axis=1)           # (TN, 128)
    sc_exp = _lane_repeat(sc2, W // 256, interpret)
    h = jnp.floor(v * 0.0625)                         # hi − 8
    corr = jnp.concatenate([-mn, sc * 8.0], axis=1).astype(jnp.bfloat16)
    xpa = xpa_ref[...]

    if variant == "vbf32":
        # Activation-side nibble recombination, f32 planes:
        #   y = x_lo·(v·sc) + (x_hi − 16·x_lo)·(h·sc)
        # Per weight only 2 multiplies + the floor — no reconstruction, no
        # bf16 casts.  The two terms carry 16× the result's magnitude and
        # cancel, so the planes stay f32 (v·sc and h·sc are EXACT in f32:
        # ≤8-bit int × bf16 scale needs ≤16 mantissa bits) and the dots take
        # f32 operands.  Mosaic rejects an explicit precision attr
        # ("Unsupported dot precision: HIGH"), so accuracy rests on how its
        # f32 dot lowers (multi-pass ⇒ fine; single-pass bf16 ⇒ the
        # rejected `vb` ablation's 3.3% rms returns) — the chip microbench
        # (tools/kernel_microbench.py `rel_dev` / `dev_fail` rows) is the
        # gate; the interpret-mode tests pin the algebra either way.
        a_v = v * sc_exp
        a_h = h * sc_exp
        x_lo = xpa[:, : W // 2].astype(jnp.float32)
        x_hi = xpa[:, W // 2: W].astype(jnp.float32)
        part = jax.lax.dot_general(
            x_lo, a_v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        part += jax.lax.dot_general(
            x_hi - 16.0 * x_lo, a_h, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        part += jax.lax.dot_general(
            xpa[:, W:], corr, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        accum(o_ref, part)
        return

    if variant == "resplit":
        # lsc = v·sc − 16·(h·sc): all three f32 quantities are exact
        # (v, h ≤ 8-bit ints × bf16 scale fits f32), so the cancellation
        # reproduces l·sc EXACTLY — bit-identical planes to the `cur`
        # branch with a different VPU dependency graph (the l = v − 16h
        # reconstruction never materializes)
        a_hi_f = h * sc_exp
        a_lo = (v * sc_exp - 16.0 * a_hi_f).astype(jnp.bfloat16)
        a_hi = a_hi_f.astype(jnp.bfloat16)
    else:                                             # cur | onedot
        l = v - h * 16.0                              # lo
        a_lo = (l * sc_exp).astype(jnp.bfloat16)      # (TN, W/2)
        a_hi = (h * sc_exp).astype(jnp.bfloat16)

    if variant == "onedot":
        # One concatenated (TN, W) plane, one MXU dot over the full tile
        # (plus the corr dot) — same planes as `cur` bit-for-bit, trading
        # a VMEM concat copy for fewer, larger matmuls.
        a = jnp.concatenate([a_lo, a_hi], axis=1)     # (TN, W)
        part = jax.lax.dot_general(
            xpa[:, :W], a, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        part += jax.lax.dot_general(
            xpa[:, W:], corr, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        accum(o_ref, part)
        return

    part = jax.lax.dot_general(
        xpa[:, : W // 2], a_lo, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    part += jax.lax.dot_general(
        xpa[:, W // 2: W], a_hi, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    part += jax.lax.dot_general(
        xpa[:, W:], corr, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    accum(o_ref, part)


def _q4k_accum(o_ref, part):
    @pl.when(pl.program_id(1) == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += part


_NIB = 0x0F0F0F0F                # a nibble of each of a word's four bytes


def _q4k_int_planes(qs, sc_exp):
    """The two bfloat16 planes of a K tile, (TN, TK/2) each (columns [0,
    TK/2) and [TK/2, TK)): :func:`_q4k_matmul_kernel`'s ``a_lo`` / ``a_hi``
    bit for bit (``cur``; ``resplit`` too, up to the sign of a zero under a
    negative scale), built from INTEGER operations on the packed bytes, four
    weight rows a 32-bit word.  The stored byte is ``16 (hi - 8) + lo`` in
    two's complement, so its low nibble is ``lo`` and its high one ``(hi -
    8) mod 16``, made the int8 ``hi - 8`` without a borrow between the
    bytes (``q6matmul._q6k_tile_product``'s ``signed``); a weight then pays
    one conversion, one multiply by ``sc_exp`` (exact: 4 bits by a
    bfloat16) and the bfloat16 cast.  No ``floor``, and 7.5 vector
    operations a packed byte where the float bodies take 9-10."""
    from jax.experimental.pallas import tpu as pltpu

    w = pltpu.bitcast(qs, jnp.int32)              # (TN/4, TK/2)
    lo = w & _NIB
    u = (w >> 4) & _NIB                           # (hi - 8) mod 16
    # u ^ 8 = hi, then - 8: + 120 cannot carry out of a byte, and turning
    # the top bit takes the 128 off again
    hi = ((u ^ 0x08080808) + 0x78787878) ^ -0x7F7F7F80
    return tuple((pltpu.bitcast(q, jnp.int8).astype(jnp.float32) * sc_exp
                  ).astype(jnp.bfloat16) for q in (lo, hi))


def _q4k_tile_product(qs, sm, xpa, interpret):
    """One K tile of a Q4_K product with the planes of
    :func:`_q4k_int_planes`: ``qs`` (TN, TK/2) int8, ``sm`` (TN, 128), ``xpa``
    (B, TKA) -> (B, TN) float32.  The ``[-mn | 8 sc]`` correction columns and
    the THREE dots in their order (low half, high half, correction) are
    :func:`_q4k_matmul_kernel`'s, so with equal planes the float32 product
    equals that body's bit for bit."""
    sc, mn = sm[:, :_SUBS], sm[:, _SUBS:]
    sc_exp = _lane_repeat(jnp.concatenate([sc, sc], axis=1), TK // 256,
                          interpret)
    corr = jnp.concatenate([-mn, sc * 8.0], axis=1).astype(jnp.bfloat16)
    a_lo, a_hi = _q4k_int_planes(qs, sc_exp)
    dot = functools.partial(
        jax.lax.dot_general, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    part = dot(xpa[:, : TK // 2], a_lo)
    part += dot(xpa[:, TK // 2: TK], a_hi)
    part += dot(xpa[:, TK:], corr)
    return part


def _q4k_expert_kernel(xpa_ref, qs_ref, sm_ref, o_ref, *, interpret, accum):
    """The grouped expert calls' body (ops/pallas/experts.py):
    :func:`_q4k_tile_product` over the K tiles of a grid step, their float32
    products summed in the tiles' order (``p0 + p1 + ...``: what the grid's
    ``accum`` makes of them a step at a time) and folded into the output
    block by ``accum``.  xpa (rows, tiles * TKA); qs (TN, tiles * TK/2)
    int8; sm (tiles, TN, 128)."""
    H = TK // 2
    part = None
    for j in range(sm_ref.shape[0]):
        p = _q4k_tile_product(
            qs_ref[:, j * H:(j + 1) * H], sm_ref[j],
            xpa_ref[:, j * TKA:(j + 1) * TKA], interpret)
        part = p if part is None else part + p
    accum(o_ref, part)


def _pick_tn(n: int, interpret: bool, prefs: tuple = (512, 256, 128)) -> int:
    """Largest N tile that divides ``n``.  512 measured fastest for the
    Q4_K kernel on an earlier machine (not re-measured); the
    Q6_K kernel passes smaller ``prefs`` because its wider f32
    intermediates would crowd the ~16 MB VMEM at TN=512."""
    for c in prefs + ((64, 32, 16, 8) if interpret else ()):
        if n % c == 0:
            return c
    raise ValueError(f"N={n} not divisible by 128")


_TN_PREFS_Q4K = (512, 256, 128)  # 512 measured fastest for decode (docs/bench)


#: rows of a call tiled as it always was: every decode step and every
#: slice beside live lanes.  A call of more rows (up to :data:`MANYROW_MAX`)
#: is still ONE row block, so each weight tile is read and dequantized once
#: a call whatever its rows, under a wider N tile and a raised VMEM limit
#: (docs/PERF.md "Rows of a fused matmul call")
TM = 256
#: the most rows of one call; a taller operand (a 2048-token bucket, lanes
#: x a bucket under ``vmap``) is cut into calls of this many.  Measured on
#: the chip: the MXU holds a 128 x 128 weight tile and streams the row
#: block past it, so a taller block hides more of each tile's load, and a
#: wider N tile re-reads the activations less often
MANYROW_MAX = 1024
#: N tiles of a call of more than :data:`TM` rows, both families
MANYROW_TN = (512, 256, 128)
#: scoped VMEM of such a call: at 1024 rows x TN 512 the activation block
#: is 4.7 MB (twice, for the pipeline), the Q6_K dequant intermediates six
#: float32 (512, 2048) planes
MANYROW_VMEM = 96 * 2 ** 20


def _tn_prefs_for(B: int, prefs: tuple) -> tuple:
    """Cap TN at 256 for the row blocks of up to :data:`TM` rows, bounding
    the (B, TKA) activation block plus dequant-intermediate VMEM footprint
    inside the 16 MB default (~4.3 MB activations + ~6 MB intermediates at
    256 rows).  Decode (B ≤ 128) keeps the measured-fastest TN=512."""
    if B > 128:
        return tuple(t for t in prefs if t <= 256) or prefs[-1:]
    return prefs


def tn_prefs(B: int, prefs: tuple) -> tuple:
    """The N tiles of a Q4_K / Q6_K call of ``B`` rows: a call of more
    than :data:`TM` rows takes :data:`MANYROW_TN` under its own VMEM limit
    (:func:`_manyrow_kw`), any other what it always took."""
    return MANYROW_TN if B > TM else _tn_prefs_for(B, prefs)


def _manyrow_kw(B: int, few_vmem: int | None = None) -> dict:
    """pallas_call keywords by the call's rows: up to :data:`TM` none (the
    call is built as it always was) or the family's own scoped VMEM
    (``few_vmem``: the Q6_K calls', whose few-row blocks are wide), else
    the raised VMEM limit."""
    if B <= TM and few_vmem is None:
        return {}
    from jax.experimental.pallas import tpu as pltpu

    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=MANYROW_VMEM if B > TM else few_vmem)}


def _q4k_specs(B: int, TN: int, tail: int = 0):
    """(in_specs, out_spec) as (block_shape, index_map) pairs — the single
    tiling definition consumed by BOTH the unstacked pallas_call (output
    head) and the stacked scalar-prefetch call (per-layer serving path),
    so the two can't drift.  ``tail``: the columns of the K's tail tile
    (:func:`tail_kernel`): its activations after the whole tiles', its planes
    after theirs, all fetched once an N tile."""
    x = ((B, TKA), lambda n, k: (0, k))
    planes = [
        ((TN, TK // 2), lambda n, k: (n, k)),
        ((1, TN, 128), lambda n, k: (k, n, 0)),
    ]
    out = ((B, TN), lambda n, k: (0, n))
    if not tail:
        return [x, *planes], out
    return [
        x, ((B, tail + 128), lambda n, k: (0, 0)), *planes,
        ((TN, tail // 2), lambda n, k: (n, 0)),
        ((1, TN, 128), lambda n, k: (0, n, 0)),
    ], out


def tail_kernel(body, kf: int):
    """The kernel of a call whose K is ``kf`` whole tiles and a tail
    (:func:`tail_of`), from the family's ``body(xpa_ref, *plane_refs, o_ref,
    accum=)``: refs (xpa, the tail's xpa, the whole tiles' planes, the
    tail's, out).  The tail takes no grid step of its own: the LAST whole
    tile's step runs the body a second time on the tail's blocks (its width
    is read off them), which arrived with the N tile's first."""
    def add(o_ref, part):
        o_ref[...] += part

    def kernel(xpa_ref, xt_ref, *refs):
        n = len(refs) // 2
        whole, tail, o_ref = refs[:n], refs[n:2 * n], refs[-1]
        if kf == 1:     # one step an N tile: nothing to accumulate into
            parts = []
            for x, planes in ((xpa_ref, whole), (xt_ref, tail)):
                body(x, *planes, o_ref, accum=lambda _, p: parts.append(p))
            o_ref[...] = parts[0] + parts[1]
            return
        body(xpa_ref, *whole, o_ref)

        @pl.when(pl.program_id(1) == kf - 1)
        def _():
            body(xt_ref, *tail, o_ref, accum=add)

    return kernel


def kernel_name(family: str, rows: int) -> str:
    """A fused matmul's name as a profile shows it (the Pallas ``name=``
    becomes the HLO instruction's name): the quant family — ``q4k``,
    ``q6k``, ``q6k_pre``, ``q5k``, ``q8_0`` — and the row regime, split
    where :func:`_tn_prefs_for` splits the tiling: ``fewrow`` for the up
    to 128 activation rows of a decode step, ``manyrow`` for a prefill
    slice.  ``benchmarks/layer_metrics/q4k_busy_share.py`` and its
    siblings find the kernels by these names."""
    return f"{family}_matmul_{'manyrow' if rows > 128 else 'fewrow'}"


def plain_pallas_call(kernel, grid, in_specs, out_spec, out_shape,
                      interpret: bool, name: str,
                      few_vmem: int | None = None):
    """pl.pallas_call from the same (block_shape, index_map) pairs
    :func:`stacked_pallas_call` consumes; ``name``: :func:`kernel_name`;
    ``few_vmem``: :func:`_manyrow_kw`'s."""
    o_block, o_map = out_spec
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec(b, m) for b, m in in_specs],
        out_specs=pl.BlockSpec(o_block, o_map),
        out_shape=out_shape,
        interpret=interpret,
        name=name,
        **_manyrow_kw(out_shape.shape[0], few_vmem),
    )


def _q4k_2d_raw(xpa: jax.Array, qs: jax.Array, sm: jax.Array,
                interpret: bool, variant: str = "cur",
                tail: tuple = ()) -> jax.Array:
    B, KA = xpa.shape
    K = (KA // TKA) * TK
    N = qs.shape[0]
    TN = _pick_tn(N, interpret, prefs=tn_prefs(B, _TN_PREFS_Q4K))
    kernel = functools.partial(_q4k_matmul_kernel, interpret=interpret,
                               variant=variant)
    if tail:    # (qs_t, sm_t): the planes of K's tail tile
        in_specs, out_spec = _q4k_specs(B, TN, 2 * tail[0].shape[-1])
        return plain_pallas_call(
            tail_kernel(kernel, K // TK), (N // TN, K // TK), in_specs,
            out_spec, jax.ShapeDtypeStruct((B, N), jnp.float32), interpret,
            kernel_name("q4k", B),
        )(xpa, xpa[:, K // TK * TKA:], qs, sm, *tail)
    in_specs, out_spec = _q4k_specs(B, TN)
    return plain_pallas_call(
        kernel,
        (N // TN, K // TK), in_specs, out_spec,
        jax.ShapeDtypeStruct((B, N), jnp.float32), interpret,
        kernel_name("q4k", B),
    )(xpa, qs, sm)


def _spec_axis(sharding, dim: int):
    spec = getattr(sharding, "spec", None)
    if spec is None:
        return None
    return spec[dim] if dim < len(spec) else None


@functools.lru_cache(maxsize=8)
def _q4k_2d_partitioned(interpret: bool, variant: str = "cur",
                        tail: bool = False):
    """The 2D fused matmul with a GSPMD partitioning rule: tp-sharded
    ``qs``/``sm`` (N dim) compute locally and the output comes back N-sharded
    — no all-gather of the quantized weights (VERDICT r1 #5; previously a
    sharded ``qs`` was gathered at the pallas_call, defeating tp's per-chip
    HBM purpose for exactly the format built to save bandwidth).

    Contract: partitioning is over the output dim N (and the row/batch dim
    of ``xpa``); the contraction dim K is never split (a caller shards fused
    weights on N for row-parallel layers too — gathering the small
    activations beats gathering weights).  ``tail``: the call takes a tail
    tile's two planes after the whole tiles' (:func:`tail_of`)."""
    from jax.experimental.custom_partitioning import custom_partitioning
    from jax.sharding import NamedSharding, PartitionSpec as P

    @custom_partitioning
    def fn(xpa, qs, sm, *tail_planes):
        return _q4k_2d_raw(xpa, qs, sm, interpret, variant, tail_planes)

    def partition(mesh, arg_shapes, result_shape):
        rows = _spec_axis(arg_shapes[0].sharding, 0)
        n_ax = _spec_axis(arg_shapes[1].sharding, 0)
        planes = (NamedSharding(mesh, P(n_ax, None)),
                  NamedSharding(mesh, P(None, n_ax, None)))
        arg_shardings = (
            NamedSharding(mesh, P(rows, None)),        # never split K
            *planes, *(planes if tail else ()))
        result_sharding = NamedSharding(mesh, P(rows, n_ax))

        def lower(xpa, qs, sm, *tail_planes):
            return _q4k_2d_raw(xpa, qs, sm, interpret, variant, tail_planes)

        return mesh, lower, result_sharding, arg_shardings

    def infer(mesh, arg_shapes, result_shape):
        return NamedSharding(
            mesh, P(_spec_axis(arg_shapes[0].sharding, 0),
                    _spec_axis(arg_shapes[1].sharding, 0)))

    fn.def_partition(
        partition=partition,
        infer_sharding_from_operands=infer,
        # shardy factor rule: rows (b) and output (n) propagate; K factors
        # (k, j, t) stay unsplit: a caller shards the planes over N alone
        sharding_rule="b k, n j, t n l, n p, u n q -> b n" if tail
        else "b k, n j, t n l -> b n",
    )
    return jax.jit(rows_vmappable(fn, xpa_pos=0, bound=MANYROW_MAX))


# ---------------------------------------------------------------------------
# stacked (per-layer) variants: scalar-prefetch layer indexing
# ---------------------------------------------------------------------------
#
# The model iterates its layers with ``lax.scan`` over weights stacked as
# (L, ...) arrays (models/llama.py).  A pallas_call operand must be a
# materialized buffer, so scanning the weights as xs makes XLA *copy* each
# layer's quantized planes (read+write of the full layer, ~137 MB for 8B
# Q4_K) before every kernel call — measured +6.3 ms/token on v5e, turning
# the fused win into a loss (tools/decode_breakdown.py).  The int8 path
# doesn't pay this because XLA fuses the dynamic-slice into the dot_general
# read.  The fix is TPU-idiomatic scalar prefetch: the layer index rides a
# prefetched scalar and the BlockSpec index_maps address layer ``idx[0]``
# of the stacked array directly, so block DMAs stream from the weights'
# home HBM with no intermediate copy — and the model keeps one compiled
# layer body (compile time ∝ 1, not n_layers).


class _NoLead:
    """Ref adapter hiding the leading length-1 layer axis of a stacked
    weight block, so the unstacked kernel bodies run unchanged (they use
    ``ref.shape``, ``ref[...]`` and, the Q6_K body of several K tiles a
    grid step, indices and slices of the block)."""

    __slots__ = ("_ref",)

    def __init__(self, ref):
        self._ref = ref

    @property
    def shape(self):
        return self._ref.shape[1:]

    def __getitem__(self, idx):
        if idx is Ellipsis:     # as it always read: those programs' text
            return self._ref[idx].reshape(self._ref.shape[1:])
        return self._ref[(0, *idx) if isinstance(idx, tuple) else (0, idx)]


def stacked_pallas_call(kernel, grid, in_specs, out_spec, out_shape,
                        interpret: bool, name: str, n_act: int = 1,
                        few_vmem: int | None = None):
    """Build ``fn(idx, xpa, *stacked_planes)`` running ``kernel`` (an
    unstacked fused kernel ``(xpa_ref, *plane_refs, o_ref)``) against layer
    ``idx[0]`` of weight planes stacked as (L, ...) arrays.

    ``in_specs`` are the UNSTACKED (block_shape, index_map) pairs — first
    the activations (``n_act`` operands: two where K ends in a tail), then
    the weight planes; weight specs get the layer dim
    prepended and their index_maps extended with the prefetched scalar.
    Interpret mode (CPU tests) runs the same code path — pallas emulates
    scalar prefetch.  ``name``: :func:`kernel_name`; ``few_vmem``:
    :func:`_manyrow_kw`'s."""
    from jax.experimental.pallas import tpu as pltpu

    def lift(block, imap):
        return pl.BlockSpec(
            (1, *block), lambda *a, _m=imap: (a[-1][0], *_m(*a[:-1])))

    specs = [pl.BlockSpec(b, lambda *a, _m=m: _m(*a[:-1]))
             for b, m in in_specs[:n_act]]
    specs += [lift(b, m) for b, m in in_specs[n_act:]]
    o_block, o_map = out_spec
    gs = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=specs,
        out_specs=pl.BlockSpec(o_block, lambda *a, _m=o_map: _m(*a[:-1])),
    )

    def wrapped(idx_ref, *refs):
        del idx_ref  # consumed by the index_maps
        kernel(*refs[:n_act], *(_NoLead(r) for r in refs[n_act:-1]),
               refs[-1])

    return pl.pallas_call(
        wrapped, grid_spec=gs, out_shape=out_shape, interpret=interpret,
        name=name, **_manyrow_kw(out_shape.shape[0], few_vmem))


def _q4k_2d_stacked_raw(idx: jax.Array, xpa: jax.Array, qs: jax.Array,
                        sm: jax.Array, *tail, interpret: bool,
                        variant: str = "cur") -> jax.Array:
    """``tail``: (qs_t, sm_t), the stacked planes of K's tail tile."""
    B, KA = xpa.shape
    K = (KA // TKA) * TK
    N = qs.shape[1]
    TN = _pick_tn(N, interpret, prefs=tn_prefs(B, _TN_PREFS_Q4K))
    in_specs, out_spec = _q4k_specs(
        B, TN, 2 * tail[0].shape[-1] if tail else 0)
    kernel = functools.partial(_q4k_matmul_kernel, interpret=interpret,
                               variant=variant)
    call = stacked_pallas_call(
        tail_kernel(kernel, K // TK) if tail else kernel,
        grid=(N // TN, K // TK),
        in_specs=in_specs,
        out_spec=out_spec,
        out_shape=jax.ShapeDtypeStruct((B, N), jnp.float32),
        interpret=interpret,
        name=kernel_name("q4k", B),
        n_act=2 if tail else 1,
    )
    if tail:
        return call(idx, xpa, xpa[:, K // TK * TKA:], qs, sm, *tail)
    return call(idx, xpa, qs, sm)


def rows_vmappable(fn, xpa_pos: int, bound: int = TM):
    """Give a fused matmul a vmap rule: batching over the activation
    operand is just more rows for the kernel (weights are shared across
    the batch).  ``custom_partitioning`` has no batching rule in JAX, so
    without this the vmapped engines (parallel/batched.py — the
    mesh-batched and continuous serving paths) raise
    ``NotImplementedError: Batching rule for 'custom_partitioning'`` the
    first time they meet fused weights.  ``bound``: the rows ``fn`` takes
    in one call (:func:`batched_rows`)."""
    from jax.custom_batching import custom_vmap

    @custom_vmap
    def wrapped(*args):
        return fn(*args)

    @wrapped.def_vmap
    def _rule(axis_size, in_batched, *args):  # noqa: ANN001
        if not in_batched[xpa_pos] or any(
                b for i, b in enumerate(in_batched) if i != xpa_pos):
            raise NotImplementedError(
                "fused matmul vmap: only the activation operand may carry "
                "the batch axis (weights are shared)")
        xpa = args[xpa_pos]
        nb, B, KA = xpa.shape
        # re-chunk the flattened rows: the caller's batched_rows bound was
        # applied to the PER-LANE shape, so nb*B can exceed it and blow
        # the kernel's activation/output VMEM blocks at large lane counts
        out = batched_rows(
            lambda xp: fn(*args[:xpa_pos], xp, *args[xpa_pos + 1:]),
            xpa.reshape(nb * B, KA), bound=bound)
        return out.reshape(nb, B, -1), True

    return wrapped


def stacked_partitioned(raw_fn, sharding_rule: str, interpret: bool,
                        bound: int = TM):
    """GSPMD rule shared by every stacked fused matmul — same contract as
    the unstacked kernels (partition over N and rows, never K) plus: the
    layer dim and the index scalar are never split.

    ``raw_fn(idx, xpa, *planes, interpret=...)`` is the stacked pallas
    call; plane shardings are derived from rank (value planes (L, N, K/x),
    scale planes (L, kt, N, 128) — N is always at ``rank - 2``);
    ``bound``: :func:`rows_vmappable`'s."""
    from jax.experimental.custom_partitioning import custom_partitioning
    from jax.sharding import NamedSharding, PartitionSpec as P

    @custom_partitioning
    def fn(idx, xpa, *planes):
        return raw_fn(idx, xpa, *planes, interpret=interpret)

    def lower(idx, xpa, *planes):
        return raw_fn(idx, xpa, *planes, interpret=interpret)

    def partition(mesh, arg_shapes, result_shape):
        rows = _spec_axis(arg_shapes[1].sharding, 0)
        n_ax = _spec_axis(arg_shapes[2].sharding, 1)
        arg_shardings = [
            NamedSharding(mesh, P(None)),
            NamedSharding(mesh, P(rows, None)),
        ] + [
            NamedSharding(
                mesh, P(*([None] * (len(a.shape) - 2)), n_ax, None))
            for a in arg_shapes[2:]
        ]
        return (mesh, lower, NamedSharding(mesh, P(rows, n_ax)),
                tuple(arg_shardings))

    def infer(mesh, arg_shapes, result_shape):
        return NamedSharding(
            mesh, P(_spec_axis(arg_shapes[1].sharding, 0),
                    _spec_axis(arg_shapes[2].sharding, 1)))

    fn.def_partition(
        partition=partition,
        infer_sharding_from_operands=infer,
        sharding_rule=sharding_rule,
    )
    return jax.jit(rows_vmappable(fn, xpa_pos=1, bound=bound))


@functools.lru_cache(maxsize=16)
def _q4k_2d_stacked_partitioned(interpret: bool, variant: str = "cur",
                                tail: bool = False):
    return stacked_partitioned(
        functools.partial(_q4k_2d_stacked_raw, variant=variant),
        "i, b k, l n j, l t n m, l n p, l u n q -> b n" if tail
        else "i, b k, l n j, l t n m -> b n", interpret, MANYROW_MAX)


def _q4k_planes(w: dict) -> tuple:
    """A Q4_K weight dict's planes in the calls' order: the whole tiles',
    then the tail's where the K has one."""
    return (w["qs"], w["sm"]) + (
        (w["qs_t"], w["sm_t"]) if "qs_t" in w else ())


def q4k_matmul_stacked(x: jax.Array, w: dict, idx,
                       interpret: bool | None = None) -> jax.Array:
    """x (..., K) → (..., N) against layer ``idx`` of stacked weights
    (``qs`` (L, N, K/2), ``sm`` (L, K/2048, N, 128); with a tail of T
    columns, ``qs_t`` (L, N, T/2) and ``sm_t`` (L, 1, N, 128) beside the
    whole tiles').  The fused path of
    ``ops.linear.linear_at`` — no per-layer weight copy under scan."""
    K = x.shape[-1]
    lead = x.shape[:-1]
    xpa = augment_x(permute_x(x).reshape(-1, K).astype(jnp.bfloat16))
    fn = _q4k_2d_stacked_partitioned(
        _interpret(interpret), _env_variant("LFKT_Q4K_KERNEL", Q4K_VARIANTS),
        "qs_t" in w)
    i1 = jnp.asarray(idx, jnp.int32).reshape(1)
    y = batched_rows(lambda xp, *ws: fn(i1, xp, *ws), xpa, *_q4k_planes(w),
                     bound=MANYROW_MAX)
    return y.reshape(*lead, -1).astype(x.dtype)


def batched_rows(fn, xpa: jax.Array, *weights, bound: int = TM) -> jax.Array:
    """Run a fused 2D matmul over ``xpa`` (B, K'): up to :data:`TM` rows as
    the one call it always was; more, filled up to a multiple of
    :data:`TM` with zero rows and cut into calls of ``bound`` rows, so the
    activation/output VMEM blocks stay bounded for large batch/sequence
    dims.  ``bound`` is :data:`MANYROW_MAX` for Q4_K and Q6_K, and one
    :data:`TM` block for the kernels no cell of the benchmark runs (Q5_K,
    Q8_0, the Q6_K ``pre`` layout: not measured at more rows), which
    therefore dequantize every weight tile again for each 256 rows."""
    B = xpa.shape[0]
    if B <= TM:
        return fn(xpa, *weights)
    pad = (-B) % TM
    if pad:
        xpa = jnp.concatenate(
            [xpa, jnp.zeros((pad, xpa.shape[1]), xpa.dtype)], axis=0)
    chunks = [
        fn(xpa[i:i + bound], *weights)
        for i in range(0, B + pad, bound)
    ]
    return jnp.concatenate(chunks, axis=0)[:B]


def q4k_matmul(x: jax.Array, w: dict, interpret: bool | None = None) -> jax.Array:
    """x (..., K) bf16/f32 → (..., N) in x.dtype, weights in Q4_K kernel
    layout (see module docstring).  The fused path of ``ops.linear.linear``."""
    K = x.shape[-1]
    lead = x.shape[:-1]
    xpa = augment_x(
        permute_x(x).reshape(-1, K).astype(jnp.bfloat16))
    fn = _q4k_2d_partitioned(
        _interpret(interpret), _env_variant("LFKT_Q4K_KERNEL", Q4K_VARIANTS),
        "qs_t" in w)
    y = batched_rows(fn, xpa, *_q4k_planes(w), bound=MANYROW_MAX)
    return y.reshape(*lead, -1).astype(x.dtype)


# devtime inventory (lfkt-lint PERF001): the fused-matmul builders mint
# trace-inner programs — every jit/pallas_call they create runs inside the
# engines' prefill/decode entry programs, so compile walls are attributed
# to those entries (obs/devtime.py; /debug/compiles kind="inner")
register_program("plain_pallas_call", site="ops.pallas.qmatmul")
register_program("stacked_pallas_call", site="ops.pallas.qmatmul")
register_program("stacked_partitioned", site="ops.pallas.qmatmul")
register_program("_q4k_2d_partitioned", site="ops.pallas.qmatmul")
