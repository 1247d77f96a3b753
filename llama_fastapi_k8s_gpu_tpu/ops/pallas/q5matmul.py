"""Fused Q5_K dequant-matmul (Pallas): completes the K-quant family.

Q5_K_M files (the other common llama.cpp artifact besides the reference's
Q4_K_M, reference api.py:14) store most linears as Q5_K.  Q5_K is Q4_K plus
one high bit per weight (gguf/quants.py: ``q5 = nibble + 16·hibit`` ∈
[0,32), same 8×32 sub-block scale/min structure, ``w = sc·q5 − mn``), so
this kernel is the v2 Q4_K design (ops/pallas/qmatmul.py — float nibble
split, lane-tiled scales, corrections folded into 128 extra K columns)
with one addition: a packed hi-bit plane, eight bits per byte, split by a
7-step ``floor`` chain (~1.9 VPU ops/weight extra) and folded into the
dequant as ``hibit·(16·sc)``.  ≈ 0.75 B/weight in HBM vs int8's 1.0.

Layout contract (:func:`prep_q5k`):

- ``q5s`` (N, K/2) int8 — re-biased nibble bytes, EXACTLY the Q4_K
  ``qs`` layout (column ``c = e·64 + s``, sub-block ``s = c % 64``).
- ``q5h`` (N, K/8) int8 — hi-bit bytes: tile-local byte ``b`` ∈ [0,256)
  holds bit ``j`` of columns ``b + 256·j``, stored biased (value − 128).
- ``sm5`` (K/2048, N, 128) bf16 — [64 scales | 64 mins], identical to the
  Q4_K ``sm``.

Activation prep (permute + xsum augmentation) is byte-for-byte the Q4_K
one, so the same prepared ``xpa`` could feed either kernel.
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
from ...gguf.quants import unpack_scale_min_k4
from .qmatmul import (
    augment_x,
    batched_rows,
    _env_variant,
    _interpret,
    _lane_repeat,
    permute_x,
    _pick_tn,
    kernel_name,
    plain_pallas_call,
    q4k_compatible,
    rows_vmappable,
    _spec_axis,
    stacked_pallas_call,
    stacked_partitioned,
    _SUBS,
    TK,
    TKA,
    _tn_prefs_for,
)

# `pre` is a LAYOUT variant in the Q6_K mold (q6matmul.py): prep stores one
# pre-combined int8 plane ``q5p = q5 ∈ [0,32)`` (N, K) at 1 B/weight
# instead of the nibble+hi-bit split at 0.625 B/weight.  The kernel then
# pays ~3 VPU ops/weight (convert, ·sc, bf16 cast) instead of the split
# path's nibble reconstruction + 8-step hi-bit extraction — attacking the
# measured ~205 vs ~145 µs per-op gap to the Q4_K kernel
# (kernel_microbench_q5k_2026-08-01; a Q5_K_M file carries ~2/3 of its
# weights in Q5_K, so unlike the q6k case the gap composes end-to-end:
# q5km 52.3 vs q4km 72.3 tok/s).  Numerics: ``q5·sc`` is an exact f32
# product (5-bit int × bf16 ≤ 13 mantissa bits) equal to the split path's
# summed exact terms; only the +8 hi-nibble bias moves from a separately
# bf16-rounded corr column into the exact plane — same deviation class as
# the gate-passing q6k `pre` (~1e-3), gated on chip.
# `pre` is the DEFAULT (tuple head): the 2026-08-01 chip A/B measured
# 63.09 vs 52.27 tok/s on the q5km grid (+21%, the per-op −15% composing
# at a Q5_K_M file's ~2/3 Q5_K weight share), and vs the f32 oracle the
# pre plane rounds strictly fewer terms than the split path (equal or
# better accuracy; dev vs `cur` ~3.5e-3 is two-roundings distance, inside
# the 5e-3 parity gate).  Cost: value planes go 0.625 → 1 B/weight (the
# sm5 scale plane, ~0.125 B/weight, is unchanged — totals 0.75 → 1.125),
# ≈ +2 GB on an 8B Q5_K_M's ~5.5G Q5_K weights — flip
# LFKT_Q5K_KERNEL=cur to trade the speed back for capacity.
Q5K_VARIANTS = ("pre", "cur", "parfloor")

q5k_compatible = q4k_compatible  # same divisibility classes


# ---------------------------------------------------------------------------
# host-side weight prep
# ---------------------------------------------------------------------------

def _combine_q5p(q5s: np.ndarray, q5h: np.ndarray, n_out: int,
                 k_in: int) -> np.ndarray:
    """Split planes → the `pre` layout's combined plane ``q5p`` (N, K) int8,
    true ``q5 = nibble + 16·hibit`` ∈ [0, 32) in the activation's permuted
    column order (lo-half columns [0, TK/2), hi-half [TK/2, TK) per tile;
    hi-bit byte ``b`` holds bit ``j`` of tile column ``b + 256·j``).  Pure
    integer numpy over the packers' output — the C++ layout contract is
    untouched."""
    kt = k_in // TK
    v4 = q5s.reshape(n_out, kt, TK // 2).astype(np.int16)
    h = v4 >> 4                                       # hi nibble − 8
    l = v4 - (h << 4)                                 # (arith shift floors)
    u = (q5h.reshape(n_out, kt, TK // 8).astype(np.int16)
         + 128)                                       # ∈ [0,256)
    # bit j of byte b belongs to tile column b + 256·j: emit the 8 bit
    # planes as contiguous 256-column slices (a single fancy-indexed
    # (N, kt, TK) gather here cost ~3 min of load time at 8B scale)
    out = np.empty((n_out, kt, TK), dtype=np.int8)
    half = TK // 2
    for j in range(8):
        hb_j = ((u >> j) & 1).astype(np.int8) << 4    # (N, kt, 256)
        lo, hi = j * 256, j * 256 + 256
        if hi <= half:                                # lo-half columns
            out[:, :, lo:hi] = (l[:, :, lo:hi] + hb_j).astype(np.int8)
        else:                                         # hi-half columns
            out[:, :, lo:hi] = ((h[:, :, lo - half:hi - half] + 8)
                                + hb_j).astype(np.int8)
    return out.reshape(n_out, k_in)


@_garbage_tolerant
def prep_q5k(raw: np.ndarray, n_out: int, k_in: int) -> dict:
    """Raw Q5_K block bytes (row-major, ``n_out`` rows of ``k_in`` elements)
    → the kernel layout dict: {"q5s", "q5h", "sm5"} (split layout) or
    {"q5p", "sm5"} under ``LFKT_Q5K_KERNEL=pre`` (see Q5K_VARIANTS)."""
    if not q5k_compatible(n_out, k_in):
        raise ValueError(f"({n_out}, {k_in}) not fused-Q5_K compatible "
                         f"(need K%{TK}==0, N%128==0)")
    from ...native import native_prep_q5k

    pre = _env_variant("LFKT_Q5K_KERNEL", Q5K_VARIANTS) == "pre"
    nat = native_prep_q5k(raw, n_out, k_in)
    if nat is not None:
        if pre:
            return {"q5p": jnp.asarray(_combine_q5p(
                        np.asarray(nat["q5s"]), np.asarray(nat["q5h"]),
                        n_out, k_in)),
                    "sm5": jnp.asarray(nat["sm5"])}
        return {"q5s": jnp.asarray(nat["q5s"]), "q5h": jnp.asarray(nat["q5h"]),
                "sm5": jnp.asarray(nat["sm5"])}
    bs = GGML_BLOCK_SIZES[GGMLType.Q5_K][1]           # 176
    nb = k_in // QK_K
    kt = k_in // TK
    blocks = np.ascontiguousarray(raw, dtype=np.uint8)[: n_out * nb * bs]
    blocks = blocks.reshape(n_out, nb, bs)
    d = blocks[..., 0:2].copy().view(np.float16).astype(np.float32)[..., 0]
    dmin = blocks[..., 2:4].copy().view(np.float16).astype(np.float32)[..., 0]
    sc, mn = unpack_scale_min_k4(blocks[..., 4:16])   # (N, nb, 8) uint8
    sm = np.concatenate([
        (d[..., None] * sc.astype(np.float32)).reshape(n_out, kt, _SUBS),
        (dmin[..., None] * mn.astype(np.float32)).reshape(n_out, kt, _SUBS),
    ], axis=-1).transpose(1, 0, 2)                    # (kt, N, 128)

    # 5-bit values per (sub-block, element): nibble file layout is Q4_K's
    # (byte g*32+i: sub 2g lo, sub 2g+1 hi); qh bit j = sub-block j's hi bit
    fqs = blocks[..., 48:].reshape(n_out, nb, 4, 32)
    q5 = np.empty((n_out, nb, 8, 32), dtype=np.uint8)
    q5[:, :, 0::2, :] = fqs & 0x0F
    q5[:, :, 1::2, :] = (fqs >> 4) & 0x0F
    qh = blocks[..., 16:48].reshape(n_out, nb, 1, 32)
    shifts = np.arange(8, dtype=np.uint8).reshape(1, 1, 8, 1)
    q5 |= (((qh >> shifts) & 1) << 4)

    # element-major tile columns (same map as Q4_K): Q[..., e, s]
    Q = q5.reshape(n_out, kt, 8, 8, 32).transpose(0, 1, 4, 2, 3)
    Q = np.ascontiguousarray(Q).reshape(n_out, kt, 32, 64)
    nib = Q & 0x0F
    hb = Q >> 4                                       # ∈ {0, 1}
    lo = nib[:, :, :16, :].reshape(n_out, kt, TK // 2)
    hi = nib[:, :, 16:, :].reshape(n_out, kt, TK // 2)
    v4 = ((hi.astype(np.int16) - 8) << 4) + lo
    q5s = v4.astype(np.int8).reshape(n_out, k_in // 2)

    hbc = hb.reshape(n_out, kt, TK)                   # column-major bits
    hbj = hbc.reshape(n_out, kt, 8, 256).astype(np.int16)  # [j, byte]
    v1 = (hbj << np.arange(8, dtype=np.int16).reshape(1, 1, 8, 1)).sum(2) - 128
    q5h = v1.astype(np.int8).reshape(n_out, k_in // 8)
    if pre:
        return {"q5p": jnp.asarray(_combine_q5p(q5s, q5h, n_out, k_in)),
                "sm5": jnp.asarray(np.ascontiguousarray(sm),
                                   dtype=jnp.bfloat16)}
    return {
        "q5s": jnp.asarray(q5s),
        "q5h": jnp.asarray(q5h),
        "sm5": jnp.asarray(np.ascontiguousarray(sm), dtype=jnp.bfloat16),
    }


def dequant_ref5(w: dict) -> jax.Array:
    """(N, K) f32 dequantized weights in **permuted** column order.
    Handles both layouts: the split {q5s, q5h} planes and the `pre`
    combined {q5p} plane."""
    sm_t = jnp.transpose(w["sm5"], (1, 0, 2)).astype(jnp.float32)
    if "q5p" in w:
        N, K = w["q5p"].shape
        kt = K // TK
        q5 = w["q5p"].astype(jnp.float32).reshape(N, kt, TK)
        sc = jnp.tile(sm_t[..., :_SUBS], (1, 1, TK // _SUBS))
        mn = jnp.tile(sm_t[..., _SUBS:], (1, 1, TK // _SUBS))
        return (q5 * sc - mn).reshape(N, kt * TK)
    N, half = w["q5s"].shape
    kt = half // (TK // 2)
    v4 = w["q5s"].astype(jnp.float32).reshape(N, kt, TK // 2)
    h = jnp.floor(v4 / 16.0)
    nib = jnp.concatenate([v4 - 16.0 * h, h + 8.0], axis=2)   # (N, kt, TK)
    u = w["q5h"].astype(jnp.float32).reshape(N, kt, 1, 256) + 128.0
    bits = []
    for j in range(7, -1, -1):
        bj = jnp.floor(u / float(1 << j))
        u = u - bj * float(1 << j)
        bits.append(bj)
    hb = jnp.concatenate(list(reversed(bits)), axis=2).reshape(N, kt, TK)
    q5 = nib + 16.0 * hb
    sc = jnp.tile(sm_t[..., :_SUBS], (1, 1, TK // _SUBS))
    mn = jnp.tile(sm_t[..., _SUBS:], (1, 1, TK // _SUBS))
    return (q5 * sc - mn).reshape(N, kt * TK)


# ---------------------------------------------------------------------------
# kernel
# ---------------------------------------------------------------------------

def _q5k_matmul_kernel(xpa_ref, q5s_ref, q5h_ref, sm_ref, o_ref, *, interpret,
                       variant="cur"):
    TN = q5s_ref.shape[0]
    v4 = q5s_ref[...].astype(jnp.float32)             # (TN, TK/2)
    h = jnp.floor(v4 * 0.0625)
    l = v4 - h * 16.0

    u = q5h_ref[...].astype(jnp.float32) + 128.0      # (TN, TK/8)
    if variant == "parfloor":
        # bit_j = floor(u/2^j) − 2·floor(u/2^(j+1)): independent floors
        # (depth-2 graph, same exact f32 integers → bit-identical) instead
        # of the serial remainder chain (depth-14).  Endpoints need no
        # floor: floor(u/1) = u and floor(u/256) = 0 for u ∈ [0,255].
        fl = [None] + [jnp.floor(u * (1.0 / (1 << j))) for j in range(1, 8)]
        bits = ([u - 2.0 * fl[1]]
                + [fl[j] - 2.0 * fl[j + 1] for j in range(1, 7)]
                + [fl[7]])
        hb = jnp.concatenate(bits, axis=1)            # (TN, TK) col-major
    else:
        bits = []
        for j in range(7, -1, -1):                    # bit7 .. bit0
            bj = jnp.floor(u * (1.0 / (1 << j)))
            u = u - bj * float(1 << j)
            bits.append(bj)
        hb = jnp.concatenate(list(reversed(bits)), axis=1)  # (TN, TK)

    sm = sm_ref[...].reshape(TN, 128)
    sc, mn = sm[:, :_SUBS], sm[:, _SUBS:]
    sc2 = jnp.concatenate([sc, sc], axis=1)           # (TN, 128)
    sc_exp = _lane_repeat(sc2, TK // 256, interpret)
    sc16 = sc_exp * 16.0
    a_lo = (l * sc_exp + hb[:, : TK // 2] * sc16).astype(jnp.bfloat16)
    a_hi = (h * sc_exp + hb[:, TK // 2:] * sc16).astype(jnp.bfloat16)
    corr = jnp.concatenate([-mn, sc * 8.0], axis=1).astype(jnp.bfloat16)

    xpa = xpa_ref[...]
    part = jax.lax.dot_general(
        xpa[:, : TK // 2], a_lo, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    part += jax.lax.dot_general(
        xpa[:, TK // 2: TK], a_hi, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    part += jax.lax.dot_general(
        xpa[:, TK:], corr, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(1) == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += part


def _q5k_pre_kernel(xpa_ref, q5p_ref, sm_ref, o_ref, *, interpret):
    """`pre` layout body: one combined int8 plane, ~3 VPU ops/weight.

    ``y = Σ x·q5·sc − Σ_s mn_s·xsum_s`` — the +8 hi-nibble bias lives
    inside the exact plane, so corr's second half (the split layout's
    ``sc·8`` against xsum_hi) is zeros; keeping the shared Q4_K-family
    activation layout costs 64 dead corr columns."""
    TN = q5p_ref.shape[0]
    sm = sm_ref[...].reshape(TN, 128)
    sc, mn = sm[:, :_SUBS], sm[:, _SUBS:]
    sc2 = jnp.concatenate([sc, sc], axis=1)           # (TN, 128)
    eff = _lane_repeat(sc2, TK // 128, interpret)     # col c → sc[c % 64]
    a = (q5p_ref[...].astype(jnp.float32) * eff).astype(jnp.bfloat16)
    corr = jnp.concatenate([-mn, jnp.zeros_like(mn)],
                           axis=1).astype(jnp.bfloat16)

    xpa = xpa_ref[...]
    part = jax.lax.dot_general(
        xpa[:, :TK], a, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    part += jax.lax.dot_general(
        xpa[:, TK:], corr, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(1) == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += part


def _q5k_pre_specs(B: int, TN: int):
    """(in_specs, out_spec) for the `pre` layout: one (TN, TK) int8 plane
    plus the shared sm5 scale plane."""
    return (
        [
            ((B, TKA), lambda n, k: (0, k)),
            ((TN, TK), lambda n, k: (n, k)),
            ((1, TN, 128), lambda n, k: (k, n, 0)),
        ],
        ((B, TN), lambda n, k: (0, n)),
    )


def _q5k_pre_2d_raw(xpa: jax.Array, q5p: jax.Array, sm: jax.Array,
                    interpret: bool) -> jax.Array:
    B, KA = xpa.shape
    K = (KA // TKA) * TK
    N = q5p.shape[0]
    TN = _pick_tn(N, interpret, prefs=_tn_prefs_for(B, _TN_PREFS_Q5K))
    in_specs, out_spec = _q5k_pre_specs(B, TN)
    return plain_pallas_call(
        functools.partial(_q5k_pre_kernel, interpret=interpret),
        (N // TN, K // TK), in_specs, out_spec,
        jax.ShapeDtypeStruct((B, N), jnp.float32), interpret,
        kernel_name("q5k_pre", B),
    )(xpa, q5p, sm)


@functools.lru_cache(maxsize=4)
def _q5k_pre_2d_partitioned(interpret: bool):
    """GSPMD rule for the `pre` layout (same contract: partition N/rows,
    never K)."""
    from jax.experimental.custom_partitioning import custom_partitioning
    from jax.sharding import NamedSharding, PartitionSpec as P

    @custom_partitioning
    def fn(xpa, q5p, sm):
        return _q5k_pre_2d_raw(xpa, q5p, sm, interpret)

    def partition(mesh, arg_shapes, result_shape):
        rows = _spec_axis(arg_shapes[0].sharding, 0)
        n_ax = _spec_axis(arg_shapes[1].sharding, 0)
        arg_shardings = (
            NamedSharding(mesh, P(rows, None)),
            NamedSharding(mesh, P(n_ax, None)),
            NamedSharding(mesh, P(None, n_ax, None)),
        )

        def lower(xpa, q5p, sm):
            return _q5k_pre_2d_raw(xpa, q5p, sm, interpret)

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


def _q5k_pre_2d_stacked_raw(idx: jax.Array, xpa: jax.Array, q5p: jax.Array,
                            sm: jax.Array, interpret: bool) -> jax.Array:
    B, KA = xpa.shape
    K = (KA // TKA) * TK
    N = q5p.shape[1]
    TN = _pick_tn(N, interpret, prefs=_tn_prefs_for(B, _TN_PREFS_Q5K))
    in_specs, out_spec = _q5k_pre_specs(B, TN)
    call = stacked_pallas_call(
        functools.partial(_q5k_pre_kernel, interpret=interpret),
        grid=(N // TN, K // TK),
        in_specs=in_specs,
        out_spec=out_spec,
        out_shape=jax.ShapeDtypeStruct((B, N), jnp.float32),
        interpret=interpret,
        name=kernel_name("q5k_pre", B),
    )
    return call(idx, xpa, q5p, sm)


@functools.lru_cache(maxsize=4)
def _q5k_pre_2d_stacked_partitioned(interpret: bool):
    return stacked_partitioned(
        _q5k_pre_2d_stacked_raw, "i, b k, l n j, l t n m -> b n", interpret)


_TN_PREFS_Q5K = (256, 128)


def _q5k_specs(B: int, TN: int):
    """Single tiling definition for both the unstacked and stacked calls
    (see qmatmul._q4k_specs)."""
    return (
        [
            ((B, TKA), lambda n, k: (0, k)),
            ((TN, TK // 2), lambda n, k: (n, k)),
            ((TN, TK // 8), lambda n, k: (n, k)),
            ((1, TN, 128), lambda n, k: (k, n, 0)),
        ],
        ((B, TN), lambda n, k: (0, n)),
    )


def _q5k_2d_raw(xpa: jax.Array, q5s: jax.Array, q5h: jax.Array,
                sm: jax.Array, interpret: bool,
                variant: str = "cur") -> jax.Array:
    B, KA = xpa.shape
    K = (KA // TKA) * TK
    N = q5s.shape[0]
    TN = _pick_tn(N, interpret, prefs=_tn_prefs_for(B, _TN_PREFS_Q5K))
    in_specs, out_spec = _q5k_specs(B, TN)
    return plain_pallas_call(
        functools.partial(_q5k_matmul_kernel, interpret=interpret,
                          variant=variant),
        (N // TN, K // TK), in_specs, out_spec,
        jax.ShapeDtypeStruct((B, N), jnp.float32), interpret,
        kernel_name("q5k", B),
    )(xpa, q5s, q5h, sm)


@functools.lru_cache(maxsize=4)
def _q5k_2d_partitioned(interpret: bool, variant: str = "cur"):
    """GSPMD rule mirroring the Q4_K kernel's: partition over N (and rows),
    never over K; tp-sharded weights compute locally."""
    from jax.experimental.custom_partitioning import custom_partitioning
    from jax.sharding import NamedSharding, PartitionSpec as P

    @custom_partitioning
    def fn(xpa, q5s, q5h, sm):
        return _q5k_2d_raw(xpa, q5s, q5h, sm, interpret, variant)

    def partition(mesh, arg_shapes, result_shape):
        xp_s, qs_s, qh_s, sm_s = (a.sharding for a in arg_shapes)
        rows = _spec_axis(xp_s, 0)
        n_ax = _spec_axis(qs_s, 0)
        arg_shardings = (
            NamedSharding(mesh, P(rows, None)),
            NamedSharding(mesh, P(n_ax, None)),
            NamedSharding(mesh, P(n_ax, None)),
            NamedSharding(mesh, P(None, n_ax, None)),
        )
        result_sharding = NamedSharding(mesh, P(rows, n_ax))

        def lower(xpa, q5s, q5h, sm):
            return _q5k_2d_raw(xpa, q5s, q5h, sm, interpret, variant)

        return mesh, lower, result_sharding, arg_shardings

    def infer(mesh, arg_shapes, result_shape):
        return NamedSharding(
            mesh, P(_spec_axis(arg_shapes[0].sharding, 0),
                    _spec_axis(arg_shapes[1].sharding, 0)))

    fn.def_partition(
        partition=partition,
        infer_sharding_from_operands=infer,
        sharding_rule="b k, n j, n p, t n l -> b n",
    )
    return jax.jit(rows_vmappable(fn, xpa_pos=0))


def _q5k_2d_stacked_raw(idx: jax.Array, xpa: jax.Array, q5s: jax.Array,
                        q5h: jax.Array, sm: jax.Array,
                        interpret: bool, variant: str = "cur") -> jax.Array:
    B, KA = xpa.shape
    K = (KA // TKA) * TK
    N = q5s.shape[1]
    TN = _pick_tn(N, interpret, prefs=_tn_prefs_for(B, _TN_PREFS_Q5K))
    in_specs, out_spec = _q5k_specs(B, TN)
    call = stacked_pallas_call(
        functools.partial(_q5k_matmul_kernel, interpret=interpret,
                          variant=variant),
        grid=(N // TN, K // TK),
        in_specs=in_specs,
        out_spec=out_spec,
        out_shape=jax.ShapeDtypeStruct((B, N), jnp.float32),
        interpret=interpret,
        name=kernel_name("q5k", B),
    )
    return call(idx, xpa, q5s, q5h, sm)


@functools.lru_cache(maxsize=4)
def _q5k_2d_stacked_partitioned(interpret: bool, variant: str = "cur"):
    return stacked_partitioned(
        functools.partial(_q5k_2d_stacked_raw, variant=variant),
        "i, b k, l n j, l n p, l t n m -> b n", interpret)


def q5k_matmul_stacked(x: jax.Array, w: dict, idx,
                       interpret: bool | None = None) -> jax.Array:
    """x (..., K) → (..., N) against layer ``idx`` of stacked Q5_K weights
    (``q5s`` (L, N, K/2), ``q5h`` (L, N, K/8), ``sm5`` (L, K/2048, N, 128);
    or ``q5p`` (L, N, K) + ``sm5`` for the `pre` layout).  Dispatched on
    the LAYOUT (plane presence), not the env knob, so weights prepped
    under one variant can never meet the other family's kernel."""
    K = x.shape[-1]
    lead = x.shape[:-1]
    xpa = augment_x(permute_x(x).reshape(-1, K).astype(jnp.bfloat16))
    i1 = jnp.asarray(idx, jnp.int32).reshape(1)
    if "q5p" in w:
        fn = _q5k_pre_2d_stacked_partitioned(_interpret(interpret))
        y = batched_rows(lambda xp, *ws: fn(i1, xp, *ws),
                         xpa, w["q5p"], w["sm5"])
    else:
        var = _env_variant("LFKT_Q5K_KERNEL", Q5K_VARIANTS)
        fn = _q5k_2d_stacked_partitioned(
            _interpret(interpret), "cur" if var == "pre" else var)
        y = batched_rows(lambda xp, *ws: fn(i1, xp, *ws),
                         xpa, w["q5s"], w["q5h"], w["sm5"])
    return y.reshape(*lead, -1).astype(x.dtype)


def q5k_matmul(x: jax.Array, w: dict, interpret: bool | None = None) -> jax.Array:
    """x (..., K) bf16/f32 → (..., N) in x.dtype, weights in Q5_K kernel
    layout.  The fused path of ``ops.linear.linear`` for Q5_K tensors.
    Layout-dispatched like :func:`q5k_matmul_stacked`."""
    K = x.shape[-1]
    lead = x.shape[:-1]
    xpa = augment_x(permute_x(x).reshape(-1, K).astype(jnp.bfloat16))
    if "q5p" in w:
        fn = _q5k_pre_2d_partitioned(_interpret(interpret))
        y = batched_rows(fn, xpa, w["q5p"], w["sm5"])
    else:
        # `pre` is a layout variant: split-layout weights (e.g. prepped
        # before the env flip) run the split default, never a silent
        # mislabel
        var = _env_variant("LFKT_Q5K_KERNEL", Q5K_VARIANTS)
        fn = _q5k_2d_partitioned(
            _interpret(interpret), "cur" if var == "pre" else var)
        y = batched_rows(fn, xpa, w["q5s"], w["q5h"], w["sm5"])
    return y.reshape(*lead, -1).astype(x.dtype)


# devtime inventory (lfkt-lint PERF001): trace-inner fused-matmul builders
# (see ops/pallas/qmatmul.py for the attribution contract)
register_program("_q5k_2d_partitioned", site="ops.pallas.q5matmul")
register_program("_q5k_pre_2d_partitioned", site="ops.pallas.q5matmul")
