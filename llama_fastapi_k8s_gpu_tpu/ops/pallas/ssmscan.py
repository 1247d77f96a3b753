"""The selective scan of a state-space layer (Mamba-1) over a prefill slice
as one kernel (``models/phi4flash.py selective_scan`` is the same
recurrence as a plain ``lax.scan`` and the reference tier-1 holds this to).

Per channel ``c`` and state ``n``, in float32:

    s_t[n, c] = exp(dt_t[c] A[n, c]) s_(t-1)[n, c] + dt_t[c] x_t[c] B_t[n]
    y_t[c]    = sum_n C_t[n] s_t[n, c] + D[c] x_t[c]

The decay depends on the INPUT (``dt_t``), so nothing of the recurrence is
a matrix product: it is ``d_state`` multiply-adds and one ``exp`` a channel,
state and position, on the vector unit, one position after another.  The
layout puts the CHANNELS on a tile's (8 sublanes x 128 lanes) and the state
index on a leading axis, so that a channel tile of 1024 holds its
``d_state`` states in as many registers, ``B_t[n]`` and ``C_t[n]`` are
SCALARS (read from SMEM, nothing is broadcast along lanes or summed across
them) and a position costs seven vector operations a state and register.

The grid is (channel tiles, time chunks): a channel tile's states stay in
VMEM over all its time chunks; they are read from the STACKED leaf ``(L,
d_state, C / 128, 128)`` at layer ``i`` before the first chunk (zeros where
the pass starts its sequence: ``fresh``) and written back there after the
last (an aliased output: the other layers' states are not touched).  Rows
past the prompt's end must arrive with ``dt = 0`` (``exp(0) s + 0``: the
state is kept to the bit), which is how ``n_valid`` is honoured.

What a slice must do is ``blocks/phi4flash.py``'s and
``benchmarks/ssm_roofline.py``'s arithmetic; the kernel's HLO instruction is
named ``ssm_scan`` and its share of that roofline is ``ssm_scan_roofline``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...obs.devtime import register_program

#: channels of a grid step: one (8, 128) float32 register a state
CHANNEL_TILE = 1024
#: positions of a grid step (B and C of a chunk: 2 x 16 x 256 words of SMEM)
TIME_CHUNK = 256
_LANES = 128


def time_chunk(rows: int) -> int:
    """Positions a grid step of a ``rows``-row slice walks: the largest
    power of two up to :data:`TIME_CHUNK` that divides the slice."""
    t = TIME_CHUNK
    while rows % t:
        t //= 2
    return t


def channel_tile(d_inner: int) -> int:
    """Channels of a grid step: :data:`CHANNEL_TILE`, or all of a narrower
    layer's (tier-1's tiny files)."""
    return min(CHANNEL_TILE, d_inner)


def scan_compatible(d_inner: int) -> bool:
    """Whether the kernel takes a layer of ``d_inner`` channels: whole
    channel tiles of whole lanes (a slice's rows are the leading axis of
    every block: any count)."""
    return d_inner % _LANES == 0 and d_inner % channel_tile(d_inner) == 0


def _scan_kernel(
    # scalar prefetch
    i_ref,          # (1,) int32: the layer within the state leaf
    fresh_ref,      # (1,) int32: 1 = the pass starts its sequence
    # inputs
    b_ref,          # SMEM (Tc * N,) f32: B_t[n] at t * N + n
    c_ref,          # SMEM (Tc * N,) f32
    x_ref,          # (Tc, R, 128) f32
    dt_ref,         # (Tc, R, 128) f32, 0 in a row past the prompt's end
    a_ref,          # (N, R, 128) f32
    d_ref,          # (R, 128) f32
    s_in,           # (1, N, R, 128) f32: the leaf's block (aliased to out 0)
    # outputs
    s_out,          # the same block
    y_ref,          # (Tc, R, 128) f32
    # scratch
    s_buf,          # (N, R, 128) f32: the tile's states over its chunks
):
    del i_ref
    t_chunk = pl.program_id(1)
    Tc, N = x_ref.shape[0], a_ref.shape[0]

    @pl.when(t_chunk == 0)
    def _():
        held = s_in[0]
        s_buf[...] = jnp.where(fresh_ref[0] != 0, jnp.zeros_like(held), held)

    a = [a_ref[n] for n in range(N)]
    d = d_ref[...]

    def step(t, s):
        x, dt = x_ref[t], dt_ref[t]
        dtx = dt * x
        y = d * x
        out = []
        for n in range(N):
            new = jnp.exp(dt * a[n]) * s[n] + b_ref[t * N + n] * dtx
            y = y + c_ref[t * N + n] * new
            out.append(new)
        y_ref[t] = y
        return tuple(out)

    s = jax.lax.fori_loop(0, Tc, step, tuple(s_buf[n] for n in range(N)))
    for n in range(N):
        s_buf[n] = s[n]

    @pl.when(t_chunk == pl.num_programs(1) - 1)
    def _():
        s_out[0] = s_buf[...]


def ssm_scan(
    x: jax.Array,       # (S, C) f32: the scan's input (after conv and silu)
    dt: jax.Array,      # (S, C) f32: step sizes, 0 in rows past the prompt
    b: jax.Array,       # (S, N) f32
    c: jax.Array,       # (S, N) f32
    a: jax.Array,       # (N, C) f32: A of layer i, negative
    d: jax.Array,       # (C,) f32
    state: jax.Array,   # (L, N, C / 128, 128) f32: the STACKED leaf
    i: jax.Array,       # scalar int32: the layer within the leaf
    fresh: jax.Array,   # scalar bool: the pass starts from zero states
    *,
    interpret: bool = False,
):
    """One slice of layer ``i``'s scan, the leaf updated in place.  Returns
    (y (S, C) f32, the state leaf)."""
    S, C = x.shape
    N = a.shape[0]
    CT = channel_tile(C)
    R, Tc = CT // _LANES, time_chunk(S)
    f32 = jnp.float32

    def tiles(v):          # (..., C) -> (..., C / 128, 128)
        return v.astype(f32).reshape(*v.shape[:-1], C // _LANES, _LANES)

    rows = pl.BlockSpec((Tc, R, _LANES), lambda ct, tc, *_: (tc, ct, 0))
    smem = pl.BlockSpec((Tc * N,), lambda ct, tc, *_: (tc,),
                        memory_space=pltpu.SMEM)
    leaf = pl.BlockSpec((1, N, R, _LANES),
                        lambda ct, tc, i_ref, _: (i_ref[0], 0, ct, 0))
    state, y = pl.pallas_call(
        _scan_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(C // CT, S // Tc),
            in_specs=[smem, smem, rows, rows,
                      pl.BlockSpec((N, R, _LANES),
                                   lambda ct, tc, *_: (0, ct, 0)),
                      pl.BlockSpec((R, _LANES), lambda ct, tc, *_: (ct, 0)),
                      leaf],
            out_specs=[leaf, rows],
            scratch_shapes=[pltpu.VMEM((N, R, _LANES), f32)],
        ),
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((S, C // _LANES, _LANES), f32)],
        # operand 8 (after the two prefetched scalars): the state leaf
        input_output_aliases={8: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=bool(interpret),
        name="ssm_scan",
    )(jnp.asarray(i, jnp.int32).reshape(1),
      jnp.asarray(fresh, jnp.int32).reshape(1),
      b.astype(f32).reshape(-1), c.astype(f32).reshape(-1),
      tiles(x), tiles(dt), tiles(a), tiles(d), state)
    return y.reshape(S, C), state


# devtime inventory (lfkt-lint PERF001): a TRACE-INNER dispatch site
register_program("ssm_scan", site="ops.pallas.ssmscan")
