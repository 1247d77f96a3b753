"""The many-row fused matmul call (ops/pallas/qmatmul.py, q6matmul.py): a
call of more than 256 rows (up to ``MANYROW_MAX``) is ONE kernel call with
ONE row block, so each weight tile is dequantized once a call, and per row
it gives bit for bit what the 256-row calls gave; a call of up to 256 rows
is built exactly as before (its lowered text for the chip hashes as the
parent's did)."""

from __future__ import annotations

import hashlib
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llama_fastapi_k8s_gpu_tpu.ops.pallas import q6matmul as Q6
from llama_fastapi_k8s_gpu_tpu.ops.pallas import qmatmul as Q4

L = importlib.import_module("llama_fastapi_k8s_gpu_tpu.ops.linear")

N, K = 64, 4096


@pytest.fixture(scope="module")
def weights():
    rng = np.random.default_rng(44)
    wf = rng.standard_normal((N, K)).astype(np.float32) * K ** -0.5
    out = {}
    for fmt, mk in (("q4k", L.make_linear_q4k), ("q6k", L.make_linear_q6k)):
        w = {k: v for k, v in mk(wf).items() if hasattr(v, "shape")}
        out[fmt] = (w, {k: jnp.stack([jnp.zeros_like(v), v])
                        for k, v in w.items()})
    return out


def _raw(fmt, stacked, xpa, w):
    """The kernel call's float32 result, no cast: plain, or stacked at
    layer 1."""
    planes = [w[k] for k in (("qs", "sm") if fmt == "q4k"
                             else ("q4", "q2", "sm6"))]
    idx = jnp.ones((1,), jnp.int32)
    if fmt == "q6k":                # one body, the head's and the stacked
        if stacked:
            return Q6._q6k_2d_stacked_raw(idx, xpa, *planes, interpret=True)
        return Q6._q6k_2d_raw(xpa, *planes, True)
    if stacked:
        return Q4._q4k_2d_stacked_raw(idx, xpa, *planes, interpret=True,
                                      variant="resplit")
    return Q4._q4k_2d_raw(xpa, *planes, True, "resplit")


def _xpa(fmt, x):
    if fmt == "q4k":
        return Q4.augment_x(Q4.permute_x(x).astype(jnp.bfloat16))
    return Q6.augment_x6(Q6.permute_x6(x).astype(jnp.bfloat16))


@pytest.mark.parametrize("rows", [257, 512, 1000, 1024])
@pytest.mark.parametrize("stacked", [False, True], ids=["plain", "stacked"])
@pytest.mark.parametrize("fmt", ["q4k", "q6k"])
def test_many_row_call_equals_the_256_row_calls_bit_for_bit(weights, fmt,
                                                            stacked, rows):
    w = weights[fmt][int(stacked)]
    rng = np.random.default_rng(rows)
    x = jnp.asarray(rng.standard_normal((rows, K)), jnp.bfloat16)
    xpa = _xpa(fmt, x)
    pad = -rows % Q4.TM
    xpa = jnp.concatenate([xpa, jnp.zeros((pad, xpa.shape[1]), xpa.dtype)])
    assert xpa.shape[0] > Q4.TM
    many = np.asarray(_raw(fmt, stacked, xpa, w))
    cut = np.concatenate([
        np.asarray(_raw(fmt, stacked, xpa[i:i + Q4.TM], w))
        for i in range(0, rows + pad, Q4.TM)])
    assert many.dtype == np.float32 and many.shape == (rows + pad, N)
    assert np.array_equal(many.view(np.uint32), cut.view(np.uint32))
    # and through the public entry, which fills the rows up and cuts them off
    mm = {("q4k", False): Q4.q4k_matmul, ("q6k", False): Q6.q6k_matmul,
          ("q4k", True): lambda x, w, interpret: Q4.q4k_matmul_stacked(
              x, w, 1, interpret=interpret),
          ("q6k", True): lambda x, w, interpret: Q6.q6k_matmul_stacked(
              x, w, 1, interpret=interpret)}[fmt, stacked]
    y = np.asarray(mm(x, w, interpret=True).astype(jnp.float32))
    assert np.array_equal(y, np.asarray(
        jnp.asarray(cut[:rows]).astype(jnp.bfloat16).astype(jnp.float32)))


def test_a_taller_operand_is_cut_into_many_row_calls(weights):
    """A 2048-token bucket, or lanes x a bucket under ``vmap``, passes
    ``MANYROW_MAX``: the rows are then cut into calls of that many."""
    w = weights["q4k"][0]
    x = jnp.asarray(np.random.default_rng(1).standard_normal((1280, K)),
                    jnp.bfloat16)
    calls = []

    def fn(xp, *ws):
        calls.append(xp.shape[0])
        return Q4._q4k_2d_raw(xp, *ws, True, "resplit")

    y = Q4.batched_rows(fn, _xpa("q4k", x), w["qs"], w["sm"], bound=512)
    assert calls == [512, 512, 256] and y.shape == (1280, N)
    one = Q4.batched_rows(fn, _xpa("q4k", x), w["qs"], w["sm"])
    assert np.array_equal(np.asarray(y), np.asarray(one))


# sha256[:16] of the lowered text (the Mosaic module inside, no source
# locations) of a call of up to 256 rows at (N 512, K 2048), for the chip.
# These are the programs every decode step and every slice beside live lanes
# runs.  The Q4_K eight were taken on the parent (bb5116b) before PR 44's
# change and stand.  The Q6_K eight are PR 64's own: the split layout's calls,
# the head's and the stacked ones, are built by one builder around one body
# since (the stacked call held the float body until then; the head's text
# moved by its activations' operand form, its results did not:
# tests/test_q6matmul.py).
PARENT_HASHES = {
    ("q4k", False, 1): "e2ad44577405e3ba",
    ("q4k", False, 8): "9f7506a561ebb922",
    ("q4k", False, 128): "c8e7a06a79a311e1",
    ("q4k", False, 256): "7c68fb8d3f51f817",
    ("q4k", True, 1): "283e705e657d2007",
    ("q4k", True, 8): "4226b3421b232848",
    ("q4k", True, 128): "f92acc431fcd0825",
    ("q4k", True, 256): "0b7afd2edcefc6a2",
    ("q6k", False, 1): "11b44092d44d1049",
    ("q6k", False, 8): "de0d5cd440afd154",
    ("q6k", False, 128): "cad0630e8fc3e86f",
    ("q6k", False, 256): "ee9b4aaa7972480c",
    ("q6k", True, 1): "8b8be5fbfe9c9d9c",
    ("q6k", True, 8): "219e89a79990000b",
    ("q6k", True, 128): "a293d749e8b91e61",
    ("q6k", True, 256): "c07f8ede901c5fe6",
}


def _lowered_hash(fmt, stacked, rows):
    def S(*s, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(s, dtype)

    n, k, lead = 512, 2048, ((3,) if stacked else ())
    w = {"sm" if fmt == "q4k" else "sm6": S(*lead, k // 2048, n, 128)}
    if fmt == "q4k":
        w["qs"] = S(*lead, n, k // 2, dtype=jnp.int8)
        fn = Q4.q4k_matmul_stacked if stacked else Q4.q4k_matmul
    else:
        w["q4"] = S(*lead, n, k // 2, dtype=jnp.int8)
        w["q2"] = S(*lead, n, k // 4, dtype=jnp.int8)
        fn = Q6.q6k_matmul_stacked if stacked else Q6.q6k_matmul
    was = jax.config.jax_traceback_in_locations_limit
    jax.config.update("jax_traceback_in_locations_limit", 0)
    try:
        if stacked:
            traced = jax.jit(lambda x, w, i: fn(x, w, i, interpret=False)
                             ).trace(S(rows, k), w, S(dtype=jnp.int32))
        else:
            traced = jax.jit(lambda x, w: fn(x, w, interpret=False)
                             ).trace(S(rows, k), w)
        text = traced.lower(lowering_platforms=("tpu",)).as_text()
    finally:
        jax.config.update("jax_traceback_in_locations_limit", was)
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("rows", [1, 8, 128, 256])
@pytest.mark.parametrize("stacked", [False, True], ids=["plain", "stacked"])
@pytest.mark.parametrize("fmt", ["q4k", "q6k"])
def test_a_call_of_up_to_256_rows_lowers_to_the_parents_text(fmt, stacked,
                                                             rows):
    assert _lowered_hash(fmt, stacked, rows) == \
        PARENT_HASHES[fmt, stacked, rows]


def test_more_than_256_rows_is_one_kernel_call():
    """512 and 1024 rows lower to ONE kernel call, as 256 do; more than
    ``MANYROW_MAX`` to calls of ``MANYROW_MAX`` rows and the rest (2304
    rows: the 1024-row program, called twice, and a 256-row one)."""
    assert Q4.MANYROW_MAX == 1024 and Q4.TM == 256
    def S(*s, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(s, dtype)

    w = {"qs": S(512, 1024, dtype=jnp.int8), "sm": S(1, 512, 128)}
    for rows, calls in ((256, 1), (512, 1), (1024, 1), (2048 + 256, 2)):
        text = jax.jit(lambda x, w: Q4.q4k_matmul(x, w, interpret=False)
                       ).trace(S(rows, 2048), w).lower(
                           lowering_platforms=("tpu",)).as_text()
        assert text.count("tpu_custom_call") == calls, rows
