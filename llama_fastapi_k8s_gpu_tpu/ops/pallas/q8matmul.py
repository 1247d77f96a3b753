"""Fused Q8_0 dequant-matmul (Pallas): serve Q8_0 files at file fidelity.

BASELINE config #3 names Q8_0 GGUF variants; round 2 served them through a
per-ROW int8 requant of the dequantized weights, compounding a second
quantization on top of the file's.  This kernel keeps the file's own
per-32-block scales (folded to bf16, ~0.4% scale rounding — the same fold
every fused kernel here applies) at ~1.13 B/weight vs the requant path's
1.0: a ~12% bandwidth premium for serving the file's actual quantization
grid, which is what llama.cpp does with these files.

Simplest member of the fused family (ops/pallas/qmatmul.py is the design
reference): values are already int8, so the kernel is load → widen →
multiply by the lane-tiled block scale → bf16 → MXU dot.  No packed
nibbles, no correction columns.

Layout contract (:func:`prep_q8_0`), K-tile = 2048 = 64 blocks of 32:

- ``q8`` (N, K) int8 — element-major tile columns: column ``c`` holds
  block ``c % 64``, element ``c // 64`` — the SAME column order as the
  Q4_K kernel (a 32-element "sub-block" there is a 32-element block
  here), so :func:`qmatmul.permute_x` is reused for activations.
- ``sm8`` (K/2048, N, 128) bf16 — the tile's 64 block scales (f16 in
  the file, folded to bf16) duplicated
  ``[d|d]``, so one ``pltpu.repeat`` expands them over lanes with
  period 128 (column ``c`` → lane ``c % 128`` → scale ``c % 64``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ...gguf.constants import GGML_BLOCK_SIZES, GGMLType
from ...obs.devtime import register_program
from ...gguf.quants import _garbage_tolerant
from .qmatmul import (
    batched_rows,
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
    TK,
    _tn_prefs_for,
)

q8_compatible = q4k_compatible  # same divisibility classes


@_garbage_tolerant
def prep_q8_0(raw: np.ndarray, n_out: int, k_in: int) -> dict:
    """Raw Q8_0 block bytes (row-major) → {"q8", "sm8"}."""
    if not q8_compatible(n_out, k_in):
        raise ValueError(f"({n_out}, {k_in}) not fused-Q8_0 compatible "
                         f"(need K%{TK}==0, N%128==0)")
    from ...native import native_prep_q8_0

    nat = native_prep_q8_0(raw, n_out, k_in)
    if nat is not None:
        return {"q8": jnp.asarray(nat["q8"]), "sm8": jnp.asarray(nat["sm8"])}
    bs = GGML_BLOCK_SIZES[GGMLType.Q8_0][1]           # 34
    nb = k_in // 32
    kt = k_in // TK
    blocks = np.ascontiguousarray(raw, dtype=np.uint8)[: n_out * nb * bs]
    blocks = blocks.reshape(n_out, nb, bs)
    d = blocks[..., 0:2].copy().view(np.float16).astype(np.float32)[..., 0]
    q = blocks[..., 2:34].view(np.int8)               # (N, nb, 32)

    Q = q.reshape(n_out, kt, 64, 32).transpose(0, 1, 3, 2)   # [e, b]
    q8 = np.ascontiguousarray(Q).reshape(n_out, k_in)
    dsc = d.reshape(n_out, kt, 64)
    sm8 = np.concatenate([dsc, dsc], axis=-1).transpose(1, 0, 2)
    return {
        "q8": jnp.asarray(q8),
        "sm8": jnp.asarray(np.ascontiguousarray(sm8), dtype=jnp.bfloat16),
    }


def dequant_ref8(w: dict) -> jax.Array:
    """(N, K) f32 dequantized weights in **permuted** column order."""
    N, K = w["q8"].shape
    kt = K // TK
    v = w["q8"].astype(jnp.float32).reshape(N, kt, TK)
    sm = jnp.transpose(w["sm8"], (1, 0, 2)).astype(jnp.float32)
    sc = jnp.tile(sm, (1, 1, TK // 128))
    return (v * sc).reshape(N, K)


def _q8_matmul_kernel(xp_ref, q8_ref, sm_ref, o_ref, *, interpret):
    TN = q8_ref.shape[0]
    v = q8_ref[...].astype(jnp.float32)               # (TN, TK)
    sm = sm_ref[...].reshape(TN, 128)
    sc_exp = _lane_repeat(sm, TK // 128, interpret)
    a = (v * sc_exp).astype(jnp.bfloat16)
    part = jax.lax.dot_general(
        xp_ref[...], a, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(1) == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += part


_TN_PREFS_Q8 = (256, 128)


def _q8_specs(B: int, TN: int):
    """Single tiling definition for both the unstacked and stacked calls
    (see qmatmul._q4k_specs)."""
    return (
        [
            ((B, TK), lambda n, k: (0, k)),
            ((TN, TK), lambda n, k: (n, k)),
            ((1, TN, 128), lambda n, k: (k, n, 0)),
        ],
        ((B, TN), lambda n, k: (0, n)),
    )


def _q8_2d_raw(xp: jax.Array, q8: jax.Array, sm: jax.Array,
               interpret: bool) -> jax.Array:
    B, K = xp.shape
    N = q8.shape[0]
    TN = _pick_tn(N, interpret, prefs=_tn_prefs_for(B, _TN_PREFS_Q8))
    in_specs, out_spec = _q8_specs(B, TN)
    return plain_pallas_call(
        functools.partial(_q8_matmul_kernel, interpret=interpret),
        (N // TN, K // TK), in_specs, out_spec,
        jax.ShapeDtypeStruct((B, N), jnp.float32), interpret,
        kernel_name("q8_0", B),
    )(xp, q8, sm)


@functools.lru_cache(maxsize=4)
def _q8_2d_partitioned(interpret: bool):
    """GSPMD rule mirroring the Q4_K kernel's: partition over N (and rows),
    never over K."""
    from jax.experimental.custom_partitioning import custom_partitioning
    from jax.sharding import NamedSharding, PartitionSpec as P

    @custom_partitioning
    def fn(xp, q8, sm):
        return _q8_2d_raw(xp, q8, sm, interpret)

    def partition(mesh, arg_shapes, result_shape):
        xp_s, q8_s, sm_s = (a.sharding for a in arg_shapes)
        rows = _spec_axis(xp_s, 0)
        n_ax = _spec_axis(q8_s, 0)
        arg_shardings = (
            NamedSharding(mesh, P(rows, None)),
            NamedSharding(mesh, P(n_ax, None)),
            NamedSharding(mesh, P(None, n_ax, None)),
        )
        result_sharding = NamedSharding(mesh, P(rows, n_ax))

        def lower(xp, q8, sm):
            return _q8_2d_raw(xp, q8, sm, interpret)

        return mesh, lower, result_sharding, arg_shardings

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


def _q8_2d_stacked_raw(idx: jax.Array, xp: jax.Array, q8: jax.Array,
                       sm: jax.Array, interpret: bool) -> jax.Array:
    B, K = xp.shape
    N = q8.shape[1]
    TN = _pick_tn(N, interpret, prefs=_tn_prefs_for(B, _TN_PREFS_Q8))
    in_specs, out_spec = _q8_specs(B, TN)
    call = stacked_pallas_call(
        functools.partial(_q8_matmul_kernel, interpret=interpret),
        grid=(N // TN, K // TK),
        in_specs=in_specs,
        out_spec=out_spec,
        out_shape=jax.ShapeDtypeStruct((B, N), jnp.float32),
        interpret=interpret,
        name=kernel_name("q8_0", B),
    )
    return call(idx, xp, q8, sm)


@functools.lru_cache(maxsize=4)
def _q8_2d_stacked_partitioned(interpret: bool):
    return stacked_partitioned(
        _q8_2d_stacked_raw, "i, b k, l n j, l t n m -> b n", interpret)


def q8_matmul_stacked(x: jax.Array, w: dict, idx,
                      interpret: bool | None = None) -> jax.Array:
    """x (..., K) → (..., N) against layer ``idx`` of stacked Q8_0 weights
    (``q8`` (L, N, K), ``sm8`` (L, K/2048, N, 128))."""
    K = x.shape[-1]
    lead = x.shape[:-1]
    xp = permute_x(x).reshape(-1, K).astype(jnp.bfloat16)
    fn = _q8_2d_stacked_partitioned(_interpret(interpret))
    i1 = jnp.asarray(idx, jnp.int32).reshape(1)
    y = batched_rows(lambda xq, *ws: fn(i1, xq, *ws), xp, w["q8"], w["sm8"])
    return y.reshape(*lead, -1).astype(x.dtype)


def q8_matmul(x: jax.Array, w: dict, interpret: bool | None = None) -> jax.Array:
    """x (..., K) bf16/f32 → (..., N) in x.dtype, weights in Q8_0 kernel
    layout.  The fused path of ``ops.linear.linear`` for Q8_0 tensors."""
    K = x.shape[-1]
    lead = x.shape[:-1]
    xp = permute_x(x).reshape(-1, K).astype(jnp.bfloat16)
    fn = _q8_2d_partitioned(_interpret(interpret))
    y = batched_rows(fn, xp, w["q8"], w["sm8"])
    return y.reshape(*lead, -1).astype(x.dtype)


# devtime inventory (lfkt-lint PERF001): trace-inner fused-matmul builder
# (see ops/pallas/qmatmul.py for the attribution contract)
register_program("_q8_2d_partitioned", site="ops.pallas.q8matmul")
