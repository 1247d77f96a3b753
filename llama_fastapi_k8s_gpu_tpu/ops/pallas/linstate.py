"""The decode step of a linear-attention layer's state as one kernel
(``models/sala.py lin_step`` is the same arithmetic in plain XLA and the
reference tier-1 holds this to).

Per head a float32 state ``S`` of hd x hd: ``S <- lambda S + k v^T``, ``o =
hd^-1/2 q^T S``.  The state leaf stays STACKED in HBM, ``(lanes, L_lin,
heads, hd, hd)``, and is updated IN PLACE at layer ``i`` (an aliased
output): per lane one copy in (all heads, 2 MB at 32 x 128 x 128), the
update in VMEM, one copy out.  The copy of the next lane's state is started
while this lane's is computed, and this lane's copy out runs while the next
is computed (two slots).  **A lane that holds no request copies nothing and
computes nothing** (``live``): under ``vmap`` plain XLA steps every lane's
state, in two fusions a layer (a slice, then the update), and transposes
the lanes' leaf once into and once out of a chunk.

What the step must move is the live lanes' states, read and written once:
``blocks/sala.py lin_state_bytes_per_step`` in the benchmark; the kernel's
HLO instruction is named ``lin_state`` and its share of that roofline is
``lin_state_roofline``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...obs.devtime import register_program


def _step_kernel(
    # scalar prefetch
    i_ref,              # (1,) int32: the layer (within the linear layers)
    live_ref,           # (B,) int32: 0 = the lane holds no request
    # inputs
    qt_ref,             # (1, hd, H) f32: this lane's queries, head-minor
    kt_ref,             # (1, hd, H) f32: its keys, head-minor
    v_ref,              # (1, H, hd) f32: its values
    decay_ref,          # (H, hd) f32: lambda_h along the lanes
    s_hbm,              # (B, L, H, hd, hd) f32 in HBM (aliased to out 0)
    # outputs
    s_out,              # the same buffer
    o_ref,              # (1, H, hd) f32
    # scratch
    buf,                # (2, H, hd, hd) f32: two slots
    sem_in,             # DMA semaphores (2 slots)
    sem_out,
    pend_ref,           # SMEM (2,): lane + 1 whose copy out is in flight
):
    del s_hbm           # the aliased output is the one buffer
    b = pl.program_id(0)
    B = pl.num_programs(0)
    layer = i_ref[0]
    H, hd = v_ref.shape[1], v_ref.shape[2]
    slot = b % 2

    def copy_in(lane, s):
        return pltpu.make_async_copy(s_out.at[lane, layer], buf.at[s],
                                     sem_in.at[s])

    def copy_out(lane, s):
        return pltpu.make_async_copy(buf.at[s], s_out.at[lane, layer],
                                     sem_out.at[s])

    def drain(s):
        """Wait for the copy out that still reads slot ``s``, if any."""
        @pl.when(pend_ref[s] != 0)
        def _():
            copy_out(pend_ref[s] - 1, s).wait()
            pend_ref[s] = 0

    @pl.when(b == 0)
    def _():
        pend_ref[0] = 0
        pend_ref[1] = 0

    alive = live_ref[b] != 0
    # the lane before started this lane's copy, if both hold a request
    started = jnp.logical_and(b > 0, live_ref[jnp.maximum(b - 1, 0)] != 0)

    @pl.when(jnp.logical_and(alive, jnp.logical_not(started)))
    def _():
        drain(slot)
        copy_in(b, slot).start()

    @pl.when(alive)
    def _():
        nxt = jnp.minimum(b + 1, B - 1)

        @pl.when(jnp.logical_and(b + 1 < B, live_ref[nxt] != 0))
        def _():
            drain(1 - slot)
            copy_in(nxt, 1 - slot).start()

        copy_in(b, slot).wait()
        scale = hd ** -0.5
        for h in range(H):
            kcol = kt_ref[0, :, h:h + 1]                  # (hd, 1)
            qcol = qt_ref[0, :, h:h + 1]
            new = decay_ref[h:h + 1, :] * buf[slot, h] \
                + kcol * v_ref[0, h:h + 1, :]             # (hd, hd)
            buf[slot, h] = new
            o_ref[0, h:h + 1, :] = jnp.sum(qcol * new, axis=0,
                                           keepdims=True) * scale
        copy_out(b, slot).start()
        pend_ref[slot] = b + 1

    @pl.when(jnp.logical_not(alive))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(b == B - 1)
    def _():
        drain(0)
        drain(1)


def _step_lanes(q, k, v, state, i, live, decay, *, interpret: bool):
    """q / k / v (B, H, hd), state (B, L, H, hd, hd) f32, i scalar, live
    (B,), decay (H,) f32 -> (o (B, H, hd) f32, state): ONE kernel over the
    lanes, the state updated in place."""
    B, H, hd = q.shape
    f32 = jnp.float32
    qt = q.astype(f32).transpose(0, 2, 1)
    kt = k.astype(f32).transpose(0, 2, 1)
    lane_t = pl.BlockSpec((1, hd, H), lambda b, *_: (b, 0, 0))
    lane_v = pl.BlockSpec((1, H, hd), lambda b, *_: (b, 0, 0))
    state, o = pl.pallas_call(
        _step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[lane_t, lane_t, lane_v,
                      pl.BlockSpec((H, hd), lambda b, *_: (0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[pl.BlockSpec(memory_space=pl.ANY), lane_v],
            scratch_shapes=[
                pltpu.VMEM((2, H, hd, hd), f32),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((2,), jnp.int32),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((B, H, hd), f32)],
        # operand 6 (after the two prefetched scalars): the state leaf
        input_output_aliases={6: 0},
        # lanes in order: a lane's copies are started by the lane before
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="lin_state",
    )(jnp.asarray(i, jnp.int32).reshape(1), live.astype(jnp.int32),
      qt, kt, v.astype(f32),
      jnp.broadcast_to(decay.astype(f32)[:, None], (H, hd)), state)
    return o, state


@functools.lru_cache(maxsize=2)
def _step_vmappable(interpret: bool):
    """The per-sequence call with its vmap rule: lanes ``vmap``ped over one
    step become ONE kernel over (B lanes), as ``attention.py
    _decode_vmappable``'s do."""
    from jax.custom_batching import custom_vmap

    lanes = functools.partial(_step_lanes, interpret=interpret)

    @custom_vmap
    def one(q, k, v, state, i, live, decay):
        o, state = lanes(q[None], k[None], v[None], state[None], i,
                         live[None], decay)
        return o[0], state[0]

    @one.def_vmap
    def _rule(axis_size, in_batched, q, k, v, state, i, live, decay):
        qb, kb, vb, sb, ib, lb, db = in_batched
        if ib or db:
            raise NotImplementedError(
                "linear state step vmap: the layer and its decay are one "
                "for all lanes")

        def per_lane(x, batched):
            return x if batched else jnp.broadcast_to(
                x, (axis_size, *x.shape))

        return lanes(per_lane(q, qb), per_lane(k, kb), per_lane(v, vb),
                     per_lane(state, sb), i, per_lane(live, lb), decay), \
            (True, True)

    return one


def lin_state_step(
    q: jax.Array,          # (H, hd): ONE sequence's (normed, rotated) query
    k: jax.Array,          # (H, hd)
    v: jax.Array,          # (H, hd)
    state: jax.Array,      # (L_lin, H, hd, hd) f32: the STACKED leaf
    i: jax.Array,          # scalar int32: the layer within the linear ones
    live: jax.Array,       # scalar bool: False = touches nothing, o = 0
    decay: jax.Array,      # (H,) f32: lambda_h of layer i
    *,
    interpret: bool = False,
):
    """One decode step of layer ``i``'s state, in place.  Returns (o (H,
    hd) f32, the state leaf).  Under ``vmap`` over lanes (everything but
    ``i`` and ``decay`` batched) it is still one kernel, and a lane's
    result does not depend on its neighbours."""
    return _step_vmappable(bool(interpret))(
        q, k, v, state, jnp.asarray(i, jnp.int32),
        jnp.asarray(live, jnp.bool_), decay)


# devtime inventory (lfkt-lint PERF001): a TRACE-INNER dispatch site
register_program("_step_lanes", site="ops.pallas.linstate")
