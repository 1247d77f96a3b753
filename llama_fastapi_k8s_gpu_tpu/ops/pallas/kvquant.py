"""Int8 KV-cache write quantization (Pallas) + the XLA reference path.

The int8 KV cache (``ModelConfig.kv_dtype == "int8"``, docs/KV_CACHE.md)
stores each layer's ring as int8 values plus per-head, per-token symmetric
f32 scales: ``x ≈ q * s`` with ``s = max|x| / 127`` taken over the head_dim
axis of one token's head vector.  Per-token granularity (a token-block of
one) is deliberate: decode writes land one token at a time at arbitrary ring
positions, so any multi-token scale block would need a read-requantize-write
of its previously written tokens on every decode step.

Writers quantize only the S NEW token slots per layer step (S ≤ bucket
size, not n_ctx), so the quantize cost is O(new tokens) while every ring
READ — the decode-bandwidth bottleneck — moves int8 instead of bf16.

Two implementations with identical semantics:

- :func:`quantize_kv_xla` — plain jnp, the reference used on CPU
  (``JAX_PLATFORMS=cpu`` parity tests) and as the Mosaic-failure fallback;
- :func:`quantize_kv_pallas` — a small Pallas kernel (one grid step per kv
  head) used on TPU so the quantize fuses into one VMEM pass over the new
  tokens' slab.

:func:`quantize_kv` dispatches between them; :func:`force_xla_quant` pins
the XLA path (the engine's startup probe flips it when the kernel fails to
lower — ops/pallas/probe.py pattern).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...obs.devtime import register_program

_FORCE_XLA: bool = False


def force_xla_quant(value: bool) -> None:
    """Pin the XLA quantize path (set by the engine when the Pallas kernel
    fails its startup compile probe on TPU)."""
    global _FORCE_XLA
    _FORCE_XLA = value


def _scale_and_q(x32: jax.Array):
    """x32 (..., hd) f32 → (q int8 (..., hd), s f32 (...,)): symmetric
    per-vector max-abs fit onto [-127, 127]; all-zero vectors store s=0
    (and q=0), so dequant q*s is exact there too."""
    amax = jnp.max(jnp.abs(x32), axis=-1)
    s = amax / 127.0
    inv = jnp.where(s > 0, 1.0 / s, 0.0)
    q = jnp.clip(jnp.round(x32 * inv[..., None]), -127, 127).astype(jnp.int8)
    return q, s


def quantize_kv_xla(x: jax.Array):
    """x (n_kv, S, hd) → (q int8 (n_kv, S, hd), s f32 (n_kv, S))."""
    return _scale_and_q(x.astype(jnp.float32))


def _kvq_kernel(x_ref, q_ref, s_ref):
    x = x_ref[0].astype(jnp.float32)                  # (S, hd)
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    s = amax / 127.0
    inv = jnp.where(s > 0, 1.0 / s, 0.0)
    q_ref[0] = jnp.clip(jnp.round(x * inv), -127, 127).astype(jnp.int8)
    s_ref[...] = s.reshape(s_ref.shape)


@functools.partial(jax.jit, static_argnames=("interpret",))
def quantize_kv_pallas(x: jax.Array, interpret: bool = False):
    """Pallas twin of :func:`quantize_kv_xla`: one grid step per kv head
    quantizes that head's (S, hd) slab of new tokens in a single VMEM pass."""
    n_kv, S, hd = x.shape
    q, s = pl.pallas_call(
        _kvq_kernel,
        grid=(n_kv,),
        in_specs=[pl.BlockSpec((1, S, hd), lambda h: (h, 0, 0))],
        out_specs=[
            pl.BlockSpec((1, S, hd), lambda h: (h, 0, 0)),
            # (n_kv, 1, S): a (1, S) block of a 2-D (n_kv, S) array is
            # neither (8, 128)-aligned nor the array's own last two dims
            pl.BlockSpec((1, 1, S), lambda h: (h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_kv, S, hd), jnp.int8),
            jax.ShapeDtypeStruct((n_kv, 1, S), jnp.float32),
        ],
        interpret=interpret,
    )(x)
    return q, s.reshape(n_kv, S)


def quantize_kv(x: jax.Array):
    """Quantize the S new token slots of one layer's K or V write slab.

    x (n_kv, S, hd) head-major (the layout ``models/llama.py`` writes) →
    (q int8 (n_kv, S, hd), s f32 (n_kv, S)).  TPU runs the Pallas kernel;
    everything else (CPU tests, probe-degraded pods) runs the identical
    XLA formulation."""
    if _FORCE_XLA or jax.default_backend() != "tpu":
        return quantize_kv_xla(x)
    return quantize_kv_pallas(x)


def dequantize_kv(q: jax.Array, s: jax.Array, dtype=jnp.bfloat16) -> jax.Array:
    """Reference dequant: q (..., C, hd) int8 × s (..., C) f32 → dtype.
    Used by the ring-attention path (which needs materialized bf16 K/V for
    its collectives) and by tests; the XLA/Pallas attention consumers fold
    the scales into their score/value matmuls instead and never call this."""
    return (q.astype(jnp.float32) * s[..., None].astype(jnp.float32)).astype(dtype)


# devtime inventory (lfkt-lint PERF001): the KV write-quantize kernel is
# trace-inner — it compiles as part of the prefill/decode programs that
# call it from the cache-write path (obs/devtime.py)
register_program("quantize_kv_pallas", site="ops.pallas.kvquant")
