"""The Mamba-1 mixer, ONE source for the blocks that have it
(models/phi4flash.py, models/jamba.py): the selective scan, its two cache
leaves, and the mixer branch of a prefill slice and a decode step alike.

``[x, z] = W_in hn``; ``x = silu(conv(x) + b)`` over ``cfg.ssm_d_conv`` causal
depthwise taps; ``[dt, B, C] = W_x x``; (a ``jamba`` file: an RMSNorm on each
of the three, ``inner_norm``); ``dt = softplus(W_dt dt + b_dt)``; per channel
and state ``s_t = exp(dt_t A) s_(t-1) + dt_t B_t x_t``, ``y_t = C_t . s_t + D
x_t``; the branch is ``W_out (y * silu(z))``.

A sequence carries the float32 states, leaf ``state`` (ssm layers, d_state,
d_inner / 128, 128: the channels on a tile's lanes, the slice kernel's
layout), and the last ``d_conv - 1`` inputs of the taps, leaf ``conv`` (as
models/lfm2.py's).  The state INTEGRATES what it is fed: a row of padding
past the prompt's end reaches neither (``dt = 0`` there keeps the state to
the bit), **the pass that starts at position 0 starts from zero**, a lane
that holds no request keeps both as they were, and neither can be rolled
back to an earlier position.  A prefill slice's scan is
ops/pallas/ssmscan.py where the engine's probe passed
(``cfg.ssm_scan_kernel``), else :func:`selective_scan`, the plain
``lax.scan`` tier-1 holds the kernel to; a decode step is one step of the
recurrence in XLA over the lanes.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ..ops.linear import linear_at
from .config import SSM, ModelConfig
from .lfm2 import conv_mix

_LANES = 128
#: the stack of every layer's feed-forward in a block that stacks its
#: weights by mixer kind
FFN = "ffn"


def state_shape(cfg: ModelConfig) -> tuple:
    return (cfg.n_layers_of(SSM), cfg.ssm_d_state,
            cfg.ssm_d_inner // _LANES, _LANES)


def init_leaves(cfg: ModelConfig, dtype=jnp.bfloat16) -> dict:
    """The two leaves of the ssm layers of one sequence, zeros."""
    return {
        "state": jnp.zeros(state_shape(cfg), jnp.float32),
        "conv": jnp.zeros((cfg.n_layers_of(SSM), cfg.ssm_d_conv - 1,
                           cfg.ssm_d_inner), dtype)}


def state_nbytes(cfg: ModelConfig) -> int:
    """The float32 states and the carried conv rows of one sequence."""
    n = cfg.n_layers_of(SSM)
    return n * cfg.ssm_d_inner * (cfg.ssm_d_state * 4
                                  + (cfg.ssm_d_conv - 1) * 2)


def selective_scan(x, dt, b, c, a, d, s0):
    """The recurrence as a plain ``lax.scan``: what the slice kernel
    (ops/pallas/ssmscan.py) computes, and the form of the CPU.  ``x`` / ``dt``
    (S, C) f32 (``dt`` 0 in a row past the prompt's end), ``b`` / ``c`` (S, N),
    ``a`` (N, C), ``d`` (C,), ``s0`` (N, C).  Returns (y (S, C), the state
    after the last row)."""
    def step(s, row):
        xt, dtt, bt, ct = row
        s = jnp.exp(dtt[None, :] * a) * s + bt[:, None] * (dtt * xt)[None, :]
        return s, jnp.sum(ct[:, None] * s, axis=0) + d * xt

    s, y = jax.lax.scan(step, s0, (x, dt, b, c))
    return y, s


def ssm_mixer(hn, w, mi, cache, pos_offset, n_valid, cfg: ModelConfig, live,
              inner_norm=None):
    """One ssm layer's mixer branch on the NORMED rows ``hn``: a prefill
    slice and a decode step alike.  ``mi``: the layer within the ssm layers'
    weights and leaves.  ``inner_norm``: None, or (x, name) -> x normed, the
    norm the block puts on ``dt`` / ``B`` / ``C`` between ``x_proj`` and
    ``dt_proj`` / the scan (names ``dt_norm`` / ``b_norm`` / ``c_norm``).
    Returns (the branch (S, dim), cache, y (S, d_inner) f32: the scan's
    output before the gate)."""
    S = hn.shape[0]
    C, N, R = cfg.ssm_d_inner, cfg.ssm_d_state, cfg.ssm_dt_rank
    f32 = jnp.float32

    def lin(x, name):
        with jax.named_scope(name):
            return linear_at(x, w[name], mi)

    with jax.named_scope("ssm"):
        xz = lin(hn, "in_proj")
        x, z = xz[:, :C], xz[:, C:]
        fresh = pos_offset == 0     # the pass that starts its sequence
        with jax.named_scope("conv"):
            held = jax.lax.dynamic_index_in_dim(cache["conv"], mi, 0,
                                                keepdims=False)
            carried = jnp.where(fresh, jnp.zeros((), held.dtype), held)
            v, carry_on = conv_mix(x, w["conv"][mi], carried, n_valid)
            if live is not None:     # a lane that holds no request
                carry_on = jnp.where(live, carry_on, held)
            cache = dict(cache, conv=jax.lax.dynamic_update_slice(
                cache["conv"], carry_on[None].astype(held.dtype), (mi, 0, 0)))
            xc = jax.nn.silu(v + w["conv_b"][mi])                  # f32
        with jax.named_scope("x_proj"):
            # (never a fused layout: its rows are no multiple of a tile)
            dbc = jax.lax.dot_general(
                xc.astype(jnp.bfloat16), w["x_proj"]["w"][mi],
                (((1,), (1,)), ((), ())), preferred_element_type=f32)
        dt_in = dbc[:, :R]
        if inner_norm is not None:
            dt_in = inner_norm(dt_in, "dt_norm")
        with jax.named_scope("dt_proj"):
            # float32 at full precision: a step size, not an activation
            dt = jax.nn.softplus(jnp.dot(
                dt_in, w["dt_proj"][mi].T,
                precision=jax.lax.Precision.HIGHEST) + w["dt_b"][mi])
        # a row of padding past the prompt's end leaves the state as it is
        dt = jnp.where((jnp.arange(S) < n_valid)[:, None], dt, 0.0)
        b, c = dbc[:, R:R + N], dbc[:, R + N:]
        if inner_norm is not None:
            b, c = inner_norm(b, "b_norm"), inner_norm(c, "c_norm")
        with jax.named_scope("scan"):
            if S > 1 and cfg.ssm_scan_kernel:
                from ..ops.pallas import use_interpret
                from ..ops.pallas.ssmscan import ssm_scan

                y, state = ssm_scan(xc, dt, b, c, w["a"][mi], w["d"][mi],
                                    cache["state"], mi, fresh,
                                    interpret=use_interpret())
            else:
                kept = jax.lax.dynamic_index_in_dim(cache["state"], mi, 0,
                                                    keepdims=False)
                s0 = jnp.where(fresh, 0.0, kept).reshape(N, C)
                y, s = selective_scan(xc, dt, b, c, w["a"][mi], w["d"][mi],
                                      s0)
                s = s.reshape(kept.shape)
                if live is not None:
                    s = jnp.where(live, s, kept)
                state = jax.lax.dynamic_update_slice(
                    cache["state"], s[None], (mi, 0, 0, 0))
            cache = dict(cache, state=state)
        gated = (y * jax.nn.silu(z.astype(f32))).astype(hn.dtype)
        out = lin(gated, "out_proj")
    return out, cache, y


def probe_scan_kernel(cfg: ModelConfig, attn_impl: str, probed: list):
    """``cfg`` with ``ssm_scan_kernel`` where the slice kernel
    (ops/pallas/ssmscan.py) takes the layer's channels and its compile probe
    passes; where it fails, the plain ``lax.scan`` (the ring's kernels
    stay).  The probe's name is appended to ``probed``."""
    import logging

    from ..ops.pallas.ssmscan import scan_compatible

    if attn_impl == "pallas" and scan_compatible(cfg.ssm_d_inner):
        from ..ops.pallas.probe import probe_ssm_scan

        probed.append("ssm_scan")
        err = probe_ssm_scan()
        if err is None:
            cfg = dataclasses.replace(cfg, ssm_scan_kernel=True)
        else:
            logging.getLogger(__name__).error(
                "pallas selective scan failed its compile probe; the "
                "slices' scans run as lax.scan: %s", err)
    return cfg


def engine_health(cfg: ModelConfig) -> dict:
    return {"ssm_scan": "pallas" if cfg.ssm_scan_kernel else "xla"}
