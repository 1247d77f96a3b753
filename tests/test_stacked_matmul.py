"""Stacked (scalar-prefetch) fused matmuls vs their unstacked oracles.

The model addresses layer ``i`` of stacked (L, ...) fused weights with
``ops.linear.linear_at`` → ``*_matmul_stacked`` (scalar-prefetch BlockSpec
indexing) instead of slicing per layer — slicing would materialize a copy
of every layer's quantized planes before each pallas_call (measured
+6.3 ms/token on 8B v5e decode, tools/decode_breakdown.py).  These tests
pin: (a) stacked == unstacked for every layer and every fused format,
(b) the decode-loop shape (jit + lax.scan over layer ids), and (c) the
GSPMD rule — tp-sharded stacked weights compute locally and match.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from llama_fastapi_k8s_gpu_tpu.ops.linear import (
    linear,
    linear_at,
    make_linear_int8,
    make_linear_q4k,
    make_linear_q5k,
    make_linear_q6k,
    make_linear_q8,
)

MAKERS = {
    "q4k": make_linear_q4k,
    "q5k": make_linear_q5k,
    "q6k": make_linear_q6k,
    "q8": make_linear_q8,
    "int8": make_linear_int8,
}


def _stack(ws):
    return jax.tree_util.tree_map(lambda *a: jnp.stack(a), *ws)


@pytest.mark.parametrize("fmt", list(MAKERS))
def test_stacked_matches_unstacked_per_layer(fmt):
    rng = np.random.default_rng(7)
    L, n, k = 3, 16, 2048
    ws = [MAKERS[fmt](rng.standard_normal((n, k)).astype(np.float32) * 0.02)
          for _ in range(L)]
    stacked = _stack(ws)
    x = jnp.asarray(rng.standard_normal((2, k)), jnp.bfloat16)
    for i in range(L):
        ref = np.asarray(linear(x, ws[i]).astype(jnp.float32))
        got = np.asarray(
            linear_at(x, stacked, jnp.int32(i)).astype(jnp.float32))
        np.testing.assert_allclose(got, ref, rtol=1e-3,
                                   atol=1e-3 * (np.abs(ref).max() + 1e-6))


def test_stacked_under_jit_scan_layer_ids():
    """The model's decode-loop shape: scan over layer ids, weights closed
    over (models/llama.py forward)."""
    rng = np.random.default_rng(8)
    L, n, k = 4, 8, 2048
    ws = [make_linear_q4k(
        rng.standard_normal((n, k)).astype(np.float32) * 0.02)
        for _ in range(L)]
    stacked = _stack(ws)
    x = jnp.asarray(rng.standard_normal((1, k)), jnp.bfloat16)

    @jax.jit
    def f(stacked, x):
        def step(carry, i):
            return carry, linear_at(carry, stacked, i)

        _, ys = jax.lax.scan(step, x, jnp.arange(L, dtype=jnp.int32))
        return ys

    ys = f(stacked, x)
    assert ys.shape == (L, 1, n)
    for i in range(L):
        ref = np.asarray(linear(x, ws[i]).astype(jnp.float32))
        np.testing.assert_allclose(
            np.asarray(ys[i].astype(jnp.float32)), ref, rtol=1e-3,
            atol=1e-3 * (np.abs(ref).max() + 1e-6))


_PLANE_SPEC = {
    # quantized value planes (L, N, K/x) → N on tp
    "qs": P(None, "tp", None), "q5s": P(None, "tp", None),
    "q5h": P(None, "tp", None), "q5p": P(None, "tp", None),
    "q4": P(None, "tp", None), "q6p": P(None, "tp", None),
    "q2": P(None, "tp", None), "q8": P(None, "tp", None),
    # scale planes (L, kt, N, 128) → N on tp
    "sm": P(None, None, "tp", None), "sm5": P(None, None, "tp", None),
    "sm6": P(None, None, "tp", None), "sm8": P(None, None, "tp", None),
}


@pytest.mark.parametrize("fmt", ["q4k", "q5k", "q6k", "q8"])
def test_vmapped_fused_matmul(fmt):
    """The mesh-batched/continuous engines vmap the model over lanes with
    SHARED fused weights (parallel/batched.py).  custom_partitioning has no
    batching rule in JAX, so without the rows_vmappable custom_vmap rule
    this raised ``NotImplementedError: Batching rule for
    'custom_partitioning' not implemented`` — first seen on hardware,
    because CPU tests' tiny dims always fell back to int8."""
    rng = np.random.default_rng(11)
    L, n, k, lanes = 2, 16, 2048, 3
    ws = [MAKERS[fmt](rng.standard_normal((n, k)).astype(np.float32) * 0.02)
          for _ in range(L)]
    xs = jnp.asarray(rng.standard_normal((lanes, 2, k)), jnp.bfloat16)

    got = jax.vmap(lambda x: linear(x, ws[0]))(xs)
    for b in range(lanes):
        ref = np.asarray(linear(xs[b], ws[0]).astype(jnp.float32))
        np.testing.assert_allclose(
            np.asarray(got[b].astype(jnp.float32)), ref, rtol=1e-3,
            atol=1e-3 * (np.abs(ref).max() + 1e-6))

    stacked = _stack(ws)
    got = jax.vmap(lambda x: linear_at(x, stacked, jnp.int32(1)))(xs)
    for b in range(lanes):
        ref = np.asarray(linear(xs[b], ws[1]).astype(jnp.float32))
        np.testing.assert_allclose(
            np.asarray(got[b].astype(jnp.float32)), ref, rtol=1e-3,
            atol=1e-3 * (np.abs(ref).max() + 1e-6))


def test_vmapped_fused_matmul_rejects_batched_weights():
    """The rows_vmappable rule only supports a batched activation operand;
    batching the weights (no engine does this) must raise loudly rather
    than silently compute against the wrong layout."""
    rng = np.random.default_rng(12)
    n, k, lanes = 16, 2048, 2
    ws = [make_linear_q4k(
        rng.standard_normal((n, k)).astype(np.float32) * 0.02)
        for _ in range(lanes)]
    wb = _stack(ws)   # leading dim = lanes, used as a vmap axis below
    x = jnp.asarray(rng.standard_normal((1, k)), jnp.bfloat16)
    with pytest.raises(Exception, match="activation operand|batch"):
        jax.vmap(lambda w: linear(x, w))(wb)


@pytest.mark.parametrize("fmt", ["q4k", "q5k", "q6k", "q8"])
def test_stacked_partitioned_matches_unsharded(fmt):
    rng = np.random.default_rng(9)
    L, n, k = 2, 256, 2048
    ws = [MAKERS[fmt](rng.standard_normal((n, k)).astype(np.float32)
                      * k ** -0.5) for _ in range(L)]
    stacked = _stack(ws)
    x = jnp.asarray(rng.standard_normal((3, k)), jnp.bfloat16)
    ref = np.asarray(linear(x, ws[1]).astype(jnp.float32))

    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    sharded = {
        key: jax.device_put(v, NamedSharding(mesh, _PLANE_SPEC[key]))
        for key, v in stacked.items()
    }
    got = jax.jit(linear_at)(x, sharded, jnp.int32(1))
    np.testing.assert_allclose(np.asarray(got.astype(jnp.float32)), ref,
                               rtol=2e-2, atol=2e-2 * np.abs(ref).max())
