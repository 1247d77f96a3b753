"""Fused Q6_K dequant-matmul kernel vs the dequant-then-matmul oracle.

Same contract as tests/test_qmatmul.py: the kernel must agree with an XLA
matmul against ``dequant_ref6`` (bf16-folded scales) and, end to end, with
the numpy Q6_K codec within quantization-noise tolerance.  Q6_K is what
Q4_K_M files use for ffn_down / attn_v / output (the reference's served
artifact mixes both types), so this is the second half of "serve Q4_K_M
fully fused"."""

from __future__ import annotations

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from llama_fastapi_k8s_gpu_tpu.gguf.quants import dequant_q6_k, quant_q6_k
from llama_fastapi_k8s_gpu_tpu.ops.linear import linear, make_linear_q6k
from llama_fastapi_k8s_gpu_tpu.ops.pallas.q6matmul import (
    dequant_ref6,
    permute_x6,
    prep_q6k,
    q6k_matmul,
)


def _rand_weights(rng, n, k):
    return (rng.standard_normal((n, k)).astype(np.float32) * (k ** -0.5))


@pytest.mark.parametrize("n,k,b", [
    (8, 2048, 1),       # minimum interpret-mode N tile, decode matvec
    (128, 2048, 4),     # TPU-shaped single k-tile
    (256, 4096, 2),     # full-size tiles, 2 k-steps
    (24, 6144, 3),      # non-power-of-two N (TN=8), 3 k-tiles
])
def test_kernel_matches_dequant_ref6(n, k, b):
    rng = np.random.default_rng(n + k)
    w = make_linear_q6k(_rand_weights(rng, n, k))
    x = jnp.asarray(rng.standard_normal((b, k)), jnp.float32)

    ref = permute_x6(x).astype(jnp.bfloat16).astype(jnp.float32) @ dequant_ref6(w).T
    got = q6k_matmul(x, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-2, atol=2e-2 * float(jnp.abs(ref).max()))


def test_end_to_end_vs_numpy_codec():
    rng = np.random.default_rng(0)
    n, k = 64, 2048
    wf = _rand_weights(rng, n, k)
    raw = quant_q6_k(wf.reshape(-1))
    w = prep_q6k(raw, n, k)
    w_deq = dequant_q6_k(raw, n * k).reshape(n, k)

    x = rng.standard_normal((2, k)).astype(np.float32)
    ref = x @ w_deq.T
    got = np.asarray(q6k_matmul(jnp.asarray(x), w))
    np.testing.assert_allclose(got, ref, rtol=3e-2,
                               atol=3e-2 * float(np.abs(ref).max()))


def test_prep_roundtrips_exact_values():
    """prep_q6k's repack must preserve every 6-bit value and scale exactly:
    dequant_ref6 (over the packed layout) == numpy codec dequant up to the
    bf16 scale fold, in the permuted column order."""
    rng = np.random.default_rng(1)
    n, k = 16, 2048
    raw = quant_q6_k(_rand_weights(rng, n, k).reshape(-1))
    w = prep_q6k(raw, n, k)
    ref = dequant_q6_k(raw, n * k).reshape(n, k)
    ref_p = np.asarray(permute_x6(jnp.asarray(ref)))
    got = np.asarray(dequant_ref6(w))
    np.testing.assert_allclose(got, ref_p, rtol=8e-3,
                               atol=8e-3 * float(np.abs(ref).max()))


def test_linear_dispatch_routes_q6k():
    rng = np.random.default_rng(2)
    w = make_linear_q6k(_rand_weights(rng, 16, 2048))
    x = jnp.asarray(rng.standard_normal((3, 2048)), jnp.bfloat16)
    y = linear(x, w)
    assert y.shape == (3, 16) and y.dtype == jnp.bfloat16


def test_permute_x6_is_a_permutation():
    x = jnp.arange(2048, dtype=jnp.float32)
    p = np.asarray(permute_x6(x))
    assert sorted(p.tolist()) == list(range(2048))
    # column c = e*128 + s holds original element (s//16)*256 + (s%16)*16 + e
    for c in (0, 1, 15, 16, 17, 127, 128, 129, 2047):
        s, e = c % 128, c // 128
        assert p[c] == (s // 16) * 256 + (s % 16) * 16 + e, c


def test_under_jit_and_scan():
    rng = np.random.default_rng(3)
    L, n, kdim = 3, 16, 2048
    ws = [make_linear_q6k(_rand_weights(rng, n, kdim)) for _ in range(L)]
    stacked = {key: jnp.stack([w[key] for w in ws]) for key in ws[0]}
    x = jnp.asarray(rng.standard_normal((1, kdim)), jnp.bfloat16)

    @jax.jit
    def f(stacked, x):
        def step(carry, wl):
            return carry, linear(carry, wl)

        _, ys = jax.lax.scan(step, x, stacked)
        return ys

    ys = f(stacked, x)
    assert ys.shape == (L, 1, n)
    np.testing.assert_allclose(np.asarray(ys[0]), np.asarray(linear(x, ws[0])),
                               rtol=1e-2, atol=1e-2)


def test_load_params_q4km_fuses_both_types(tmp_path):
    """A Q4_K_M-style file (attn Q4_K, ffn Q6_K): Q4_K names load the fused
    Q4_K layout, Q6_K names load the fused **Q6_K** layout (round 2 sent
    them to int8), and forward logits agree with a bf16 load."""
    from llama_fastapi_k8s_gpu_tpu.gguf import GGMLType, GGUFFile
    from llama_fastapi_k8s_gpu_tpu.models.config import ModelConfig
    from llama_fastapi_k8s_gpu_tpu.models.llama import init_cache, prefill
    from llama_fastapi_k8s_gpu_tpu.models.params import load_params
    from llama_fastapi_k8s_gpu_tpu.testing import write_tiny_llama_gguf

    cfg = ModelConfig(vocab_size=263, dim=2048, n_layers=1, n_heads=16,
                      n_kv_heads=8, ffn_dim=2048, n_ctx=32)
    path = str(tmp_path / "q4km.gguf")
    cfg = write_tiny_llama_gguf(path, cfg=cfg, quant=GGMLType.Q4_K,
                                ffn_quant=GGMLType.Q6_K)
    gf = GGUFFile(path)
    params = load_params(gf, cfg, fmt="q4k", on_device=False)
    assert "qs" in params["layers"]["wq"]
    assert "q4" in params["layers"]["w_gate"]          # fused Q6_K now

    ref = load_params(gf, cfg, fmt="bf16", on_device=False)
    toks = jnp.arange(1, 9, dtype=jnp.int32)
    lg_q, _ = prefill(params, cfg, toks, jnp.int32(8), init_cache(cfg))
    lg_r, _ = prefill(ref, cfg, toks, jnp.int32(8), init_cache(cfg))
    a, b = np.asarray(lg_q), np.asarray(lg_r)
    denom = np.abs(b).max() + 1e-6
    assert np.abs(a - b).max() / denom < 0.08, np.abs(a - b).max() / denom


def test_pre_layout_matches_oracle_and_split(monkeypatch):
    """LFKT_Q6K_KERNEL=pre (pre-combined int8 q6 plane, ~3 VPU ops/weight)
    must agree with the f32 dequant oracle at least as tightly as the
    split layout's call: its plane q6*eff is the exact f32 value the split
    path reaches via nib*eff + crumb*(16 eff) before the same bf16 cast,
    and it ROUNDS ONE FEWER corr term (the +8 hi-nibble bias rides the
    exact plane instead of a bf16 corr column)."""
    from llama_fastapi_k8s_gpu_tpu.ops.pallas import q6matmul as qm

    rng = np.random.default_rng(11)
    n, k = 64, 4096
    raw = quant_q6_k(_rand_weights(rng, n, k).reshape(-1))
    monkeypatch.setenv("LFKT_Q6K_KERNEL", "split")
    w_split = prep_q6k(raw, n, k)
    monkeypatch.setenv("LFKT_Q6K_KERNEL", "pre")
    w_pre = prep_q6k(raw, n, k)
    assert set(w_pre) == {"q6p", "sm6"}
    assert w_pre["q6p"].dtype == jnp.int8
    q6p = np.asarray(w_pre["q6p"])
    assert q6p.min() >= 0 and q6p.max() < 64

    x = jnp.asarray(rng.standard_normal((4, k)), jnp.float32)
    ref = np.asarray(
        permute_x6(x).astype(jnp.bfloat16).astype(jnp.float32)
        @ dequant_ref6(w_split).T)
    got_pre = np.asarray(q6k_matmul(x, w_pre, interpret=True))
    monkeypatch.setenv("LFKT_Q6K_KERNEL", "split")
    got_cur = np.asarray(q6k_matmul(x, w_split, interpret=True))

    scale = np.abs(ref).max()
    err_pre = np.abs(got_pre - ref).max()
    err_cur = np.abs(got_cur - ref).max()
    # pre rounds a strict subset of cur's terms; allow bf16-noise slack
    assert err_pre <= err_cur + 2e-3 * scale, (err_pre, err_cur, scale)
    np.testing.assert_allclose(got_pre, got_cur, atol=4e-3 * scale)


def test_pre_layout_stacked_matches_plain(monkeypatch):
    """Stacked scalar-prefetch path == plain path for the pre layout."""
    from llama_fastapi_k8s_gpu_tpu.ops.pallas.q6matmul import (
        q6k_matmul_stacked,
    )

    rng = np.random.default_rng(12)
    n, k = 32, 2048
    monkeypatch.setenv("LFKT_Q6K_KERNEL", "pre")
    w0 = prep_q6k(quant_q6_k(_rand_weights(rng, n, k).reshape(-1)), n, k)
    w1 = prep_q6k(quant_q6_k(_rand_weights(rng, n, k).reshape(-1)), n, k)
    ws = {key: jnp.stack([w0[key], w1[key]]) for key in w0}
    x = jnp.asarray(rng.standard_normal((2, k)), jnp.bfloat16)
    for i, w in enumerate((w0, w1)):
        plain = np.asarray(q6k_matmul(x, w, interpret=True))
        stacked = np.asarray(q6k_matmul_stacked(x, ws, i, interpret=True))
        np.testing.assert_array_equal(plain, stacked)


# ---------------------------------------------------------------------------
# the head's call (PR 57): the unstacked split-layout matmul has a body and
# a tiling of its own
# ---------------------------------------------------------------------------

def _random_planes(rng, n, k, lead=()):
    """Kernel-layout planes over every byte value (the kernels' arithmetic
    is total: any int8 pair is some Q6_K weight, any bf16 a scale); ``lead``:
    a stack's leading axes.  (Drawn and cast in numpy: an eager jnp operation
    is an XLA compile a shape.)"""
    def int8(*shape):
        return jnp.asarray(rng.integers(-128, 128, lead + shape, np.int8))

    return {
        "q4": int8(n, k // 2), "q2": int8(n, k // 4),
        "sm6": jnp.asarray((rng.standard_normal(
            lead + (k // 2048, n, 128), np.float32) * 1e-2
        ).astype(jnp.bfloat16))}


def _f32(a):
    return np.asarray(a).astype(np.float32)


def _plane_columns(w, cols, layer=None):
    """What a one-hot activation row at permuted column ``cols[r]`` (no
    correction columns) reads out of a split-layout call, exactly: the
    bfloat16 plane the body multiplies, from ``dequant_ref6``.  The plane
    holds ``q6 - 0`` in the low half of a tile and ``q6 - 8`` in the high one
    (the -32 and the +8 ride the correction columns, zero here); ``eff * (q6
    - 32) + 32 eff`` is exact in float32 (14 bits), so the bfloat16 cast is
    the kernel's own."""
    lead = () if layer is None else (layer,)
    kf = w["q4"].shape[-1] * 2                  # columns in whole tiles
    width = 2 * w["q4_t"].shape[-1] if "q4_t" in w else 2048
    tail = cols >= kf
    half = np.where(tail, (cols - kf) >= width // 2, cols % 2048 >= 1024)
    eff = np.where(
        tail[:, None],
        _f32(w.get("sm6_t", w["sm6"]))[lead][0][:, (cols - kf) % 128].T,
        _f32(w["sm6"])[lead][
            np.minimum(cols // 2048, kf // 2048 - 1), :, cols % 128])
    plane = np.asarray(_ref_columns(w, jnp.asarray(cols), layer)).T
    want = plane + eff * np.where(half, 24.0, 32.0)[:, None].astype(np.float32)
    return want.astype(jnp.bfloat16).astype(np.float32)


def _layer(w, layer):
    return w if layer is None else {key: a[layer] for key, a in w.items()}


# (one program a shape, not one an operation of ``dequant_ref6``; ``layer``:
# of a stack, None for unstacked planes)
_ref_columns = jax.jit(
    lambda w, cols, layer: dequant_ref6(_layer(w, layer))[:, cols])
_ref_product = jax.jit(
    lambda xp, w, layer=None: xp.astype(jnp.float32)
    @ dequant_ref6(_layer(w, layer)).T)
_activations = jax.jit(lambda x: (
    permute_x6(x).astype(jnp.bfloat16),
    _augment(permute_x6(x).astype(jnp.bfloat16))))


def _augment(xp):
    from llama_fastapi_k8s_gpu_tpu.ops.pallas.q6matmul import augment_x6

    return augment_x6(xp)


def _one_hot_xpa(rows, cols, kt, tail=0):
    """(rows, kt * TKA6 [+ tail + 256]) activations as the kernels take them,
    row ``r`` a one at permuted column ``cols[r]``, its correction columns
    zero."""
    from llama_fastapi_k8s_gpu_tpu.ops.pallas import q6matmul as Q6

    x = np.zeros((rows, kt * Q6.TKA6 + (tail + 256 if tail else 0)),
                 jnp.bfloat16)
    x[np.arange(rows), cols // 2048 * Q6.TKA6 + cols % 2048] = 1.0
    return jnp.asarray(x)


@pytest.mark.parametrize("n", [256, 1280])    # 1280: 512 does not divide it
@pytest.mark.parametrize("k", [2048, 6144, 8192])
@pytest.mark.parametrize("rows", [1, 4, 16])
def test_head_call_matches_oracle_and_the_stacked_bodys_plane(rows, k, n):
    """The head's call against ``dequant_ref6`` at today's tolerance, and its
    dequantized plane against ``dequant_ref6``'s BIT FOR BIT (the float form
    the stacked calls ran until PR 64): a one-hot activation row (no
    correction columns) reads one plane column out of the kernel exactly,
    whatever order the float32 sums are taken in."""
    from llama_fastapi_k8s_gpu_tpu.ops.pallas import q6matmul as Q6

    rng = np.random.default_rng(rows * 7 + k + n)
    w = _random_planes(rng, n, k)
    x = jnp.asarray(rng.standard_normal((rows, k)), jnp.float32)
    ref = _ref_product(_activations(x)[0], w)
    got = q6k_matmul(x, w, interpret=True)
    assert got.shape == (rows, n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-2,
                               atol=2e-2 * float(jnp.abs(ref).max()))

    # 256 columns of every K tile, 64 of each quarter (the four quarters of
    # a tile are taken apart by four different integer forms)
    # (one program a case; the planes are ARGUMENTS: as constants of a jit,
    # XLA:CPU folds the interpreted kernel's int8 -> int32 bitcast otherwise)
    call = jax.jit(lambda xpa, *planes: Q6._q6k_2d_raw(xpa, *planes, True))
    for t in range(k // 2048):
        cols = t * 2048 + np.concatenate(
            [q * 512 + rng.permutation(512)[:64] for q in range(4)])
        new = np.asarray(call(_one_hot_xpa(256, cols, k // 2048),
                              w["q4"], w["q2"], w["sm6"]))
        assert np.array_equal(new, _plane_columns(w, cols)), t


def test_head_call_ignores_the_layout_knob(monkeypatch):
    """The split layout's call is one program whatever ``LFKT_Q6K_KERNEL``
    says: the knob names the LAYOUT a load writes, a call is dispatched on
    the planes it is given."""
    from llama_fastapi_k8s_gpu_tpu.ops.pallas import q6matmul as Q6

    w = _random_planes(np.random.default_rng(5), 64, 2048)
    x = jnp.asarray(np.random.default_rng(6).standard_normal((2, 2048)),
                    jnp.bfloat16)
    outs = []
    for var in Q6.Q6K_LAYOUTS:
        monkeypatch.setenv("LFKT_Q6K_KERNEL", var)
        outs.append(np.asarray(q6k_matmul(x, w, interpret=True)))
    assert all(np.array_equal(o, outs[0]) for o in outs)
    assert Q6.Q6K_LAYOUTS == ("split", "pre")
    monkeypatch.setenv("LFKT_Q6K_KERNEL", "cur")    # a float body: gone
    with pytest.raises(ValueError, match="split|pre"):
        prep_q6k(np.zeros(210 * 8 * 128, np.uint8), 128, 2048)


# ---------------------------------------------------------------------------
# the stacked call (PR 64): the head's body under a tiling of the call's shape
# ---------------------------------------------------------------------------

_STACKED_SHAPES = [(1024, 4096), (4096, 14336), (4096, 16384), (2048, 6144),
                   (2560, 10240), (4096, 2560), (1280, 5120)]


@pytest.mark.parametrize("n,k", _STACKED_SHAPES)
@pytest.mark.parametrize("rows", [1, 4, 8, 16, 128, 256, 1024])
def test_stacked_call_matches_oracle_plane_and_tiling(monkeypatch, rows, n, k):
    """The stacked call at the dense configurations' widths (``wv``;
    ``w_down`` of mistral / solar, sala, ouro and evabyte filled up,
    phi4flash; a K that ends in a 512 tail and one in a 1024 tail), at the
    rows of a decode step, a slice beside live lanes and a wide slice:

    - its tiling is a function of (rows, N, K) alone, divides the shape, fits
      the weight-block and activation-block budgets, and is the grid of the
      program it builds;
    - on layers 0 and 1 of a stack of two it equals ``dequant_ref6``'s
      product at the head's tolerance;
    - the plane it multiplies is ``dequant_ref6``'s bit for bit (a one-hot
      row a column, the tail's columns among them).

    The N tiles of a call are alike, so the numbers are taken on two of them
    (N cut to twice the tile, under the tiling of the whole N), on one where
    the rows are many."""
    from llama_fastapi_k8s_gpu_tpu.ops.pallas import q6matmul as Q6
    from llama_fastapi_k8s_gpu_tpu.ops.pallas.qmatmul import (
        MANYROW_TN, TM, tail_of)

    tail = tail_of(k)
    kt = (k - tail) // 2048
    tn, tiles = Q6._q6k_tiling(n, rows, kt, False)
    assert (tn, tiles) == Q6._q6k_tiling(n, rows, kt, True)
    assert n % tn == 0 and tn % 128 == 0 and kt % tiles == 0
    if rows > TM:
        assert (tn, tiles) == (next(t for t in MANYROW_TN if n % t == 0), 1)
    else:
        assert tn <= 128 * Q6.HEAD_TN_UNITS
        assert tiles == 1 or (
            tn * tiles * 2048 <= Q6.HEAD_W_BLOCK
            and tiles * rows * Q6.TKA6 * 2 <= Q6.X_BLOCK)
        # of the pairs the budgets admit, none of fewer grid steps leaves
        # MIN_STEPS of them
        pairs = [(a, b) for a in range(128, 1025, 128) if n % a == 0
                 for b in range(1, kt + 1) if kt % b == 0 and (b == 1 or (
                     a * b * 2048 <= Q6.HEAD_W_BLOCK
                     and b * rows * Q6.TKA6 * 2 <= Q6.X_BLOCK))]

        def steps(pair):
            return (n // pair[0]) * (kt // pair[1])

        enough = [p for p in pairs if steps(p) >= Q6.MIN_STEPS] or pairs
        assert steps((tn, tiles)) == min(map(steps, enough))
    S = jax.ShapeDtypeStruct
    planes = [S((2, n, (k - tail) // 2), jnp.int8),
              S((2, n, (k - tail) // 4), jnp.int8),
              S((2, kt, n, 128), jnp.bfloat16)]
    if tail:
        planes += [S((2, n, tail // 2), jnp.int8),
                   S((2, n, tail // 4), jnp.int8),
                   S((2, 1, n, 128), jnp.bfloat16)]
    ka = kt * Q6.TKA6 + (tail + 256 if tail else 0)
    text = str(jax.make_jaxpr(functools.partial(
        Q6._q6k_2d_stacked_raw, interpret=False))(
            S((1,), jnp.int32), S((rows, ka), jnp.bfloat16), *planes))
    assert f"grid=({n // tn}, {kt // tiles})" in text, (tn, tiles)

    rng = np.random.default_rng(rows + n + k)
    n2 = (2 if rows <= 16 else 1) * tn
    monkeypatch.setattr(Q6, "_q6k_tiling", lambda *a: (tn, tiles))
    w = _random_planes(rng, n2, k - tail, (2,))
    if tail:    # its sub-scales tiled up to the plane's 128 lanes
        t = _random_planes(rng, n2, 2048, (2,))
        w.update(q4_t=t["q4"][..., :tail // 2], q2_t=t["q2"][..., :tail // 4],
                 sm6_t=jnp.asarray(np.tile(
                     np.asarray(t["sm6"])[..., :tail // 16],
                     (1, 1, 1, 2048 // tail))))
    xp, xpa = _activations(rng.standard_normal((rows, k), np.float32))
    cols = rng.permutation(k)[np.arange(rows) % k]
    hot = _one_hot_xpa(rows, cols, kt, tail)
    call = jax.jit(lambda idx, xpa, *planes: Q6._q6k_2d_stacked_raw(
        idx, xpa, *planes, interpret=True))
    planes = Q6._q6k_planes(w)
    for layer in (0, 1):
        idx = np.full((1,), layer, np.int32)
        ref = np.asarray(_ref_product(xp, w, layer))
        got = call(idx, xpa, *planes)
        assert got.shape == (rows, n2)
        np.testing.assert_allclose(np.asarray(got), ref, rtol=2e-2,
                                   atol=2e-2 * float(np.abs(ref).max()))
        assert np.array_equal(np.asarray(call(idx, hot, *planes)),
                              _plane_columns(w, cols, layer)), layer


# jax.make_jaxpr's text (kernel bodies in full), hashed with
# tools/traced_program_hashes.py.  What PR 64 moved, re-recorded on its tree:
# the head's call at the widths heads have (a decode row, a lane step's rows,
# a slice; a K that ends in a tail), whose text differs from the parent's
# (ea43b71) by the activations' operand alone (the (B, K tiles x 2304) block
# of the array as it is, where the parent took a K tile a leading index of a
# transposed copy: equal results, see the two tests after these), ...
HEAD_TRACED = {
    "dense.q6k.4096x32000.r1.tpu": "63940e169942696b",
    "dense.q6k.4096x32000.r8.tpu": "7427f260a932f7e5",
    "dense.q6k.4096x4096.r512.tpu": "799c845420dc2e07",
    "tail.dense.q6k.2560x200064.r16.tpu": "9968ab793a78bff1",
    "dense.q6k.4096x4096.r1.interp": "7b644c979c7cd030",
}
# ... and the stacked calls: the head's body under the tiling of their shape,
# where they held the float body
STACKED_TRACED = {
    "stacked.q6k.4096x4096.r1.tpu": "b544395d6d9a916a",
    "stacked.q6k.14336x4096.r8.tpu": "94767904e08f6201",
    "stacked.q6k.4096x4096.r512.tpu": "8439760a814b5601",
    "tail.stacked.q6k.2560x10240.r16.tpu": "97c2be5df7209aed",
}
# every other family through the builders PR 64 touched (``_NoLead``,
# ``plain_pallas_call``, ``stacked_pallas_call``): the parent's text
OTHER_TRACED = {
    "q4k": {
        "dense.q4k.4096x14336.r1.tpu": "f895ab9873ca09dc",
        "stacked.q4k.4096x14336.r8.tpu": "e6d8d73c15097442",
        "stacked.q4k.14336x4096.r512.tpu": "388f9ab71a8970ce",
        "stacked.q4k.4096x4096.r1.interp": "0b8b1411022c39fc",
        "tail.stacked.q4k.2560x10240.r16.tpu": "5e21eaef99869a50",
    },
    "q5k-q8_0-q6k_pre": {
        "other.stacked.q5k.4096x4096.r16.tpu": "c1c74a57dca7fdce",
        "other.stacked.q5k_pre.4096x4096.r1024.tpu": "14ca6cc89de0de4f",
        "other.dense.q5k.4096x4096.r1.interp": "b95b52677f452cd9",
        "other.stacked.q8_0.4096x4096.r16.tpu": "b2ef8eef1f938e8a",
        "other.dense.q8_0.4096x4096.r256.tpu": "7c2447b6cdaab16c",
        "other.stacked.q6k_pre.4096x4096.r1.tpu": "ed2441e32368ee73",
    },
    "grouped": {
        "grouped.q4k.gigachat.few": "a06e6583a621bfec",
        "grouped.q4k.olmoe.many": "c03002a39f8f1ceb",
        "grouped.q6k.kexaone.few": "42618ad5e0263dc3",
        "grouped.q6k.lfm2.many": "ec492c03f20af5b3",
        "routed.longcat.t16.tpu": "bf1950f1be1076af",
    },
}
# the routed layers as the parent traced them (few rows, the lanes' vmap, a
# compacted call, many rows, interpret mode): their Q6_K down calls held the
# stacked calls' float body, and hold the head's since PR 59
PARENT_ROUTED = {
    "routed.olmoe.t8.tpu": "681794971179b5d7",
    "routed.lfm2.t16.tpu.vmap": "47d36bafe2293224",
    "routed.longcat.t16.tpu": "572eab122c93cff3",
    "routed.gigachat.t256.tpu": "e74f9f118bd93412",
    "routed.kexaone.t1.interp": "b0eef6d7a0beffe8",
}


def _traced_hashes(keys):
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "traced_program_hashes", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools", "traced_program_hashes.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    return tool.hashes(only=keys.__contains__)


@pytest.mark.parametrize("want", [HEAD_TRACED, STACKED_TRACED],
                         ids=["head", "stacked"])
def test_stacked_and_grouped_q6k_calls_trace_to_the_text_they_had(want):
    """The split layout's programs, the head's and the stacked calls', are
    the ones recorded when one builder came to make both (PR 64)."""
    assert _traced_hashes(want) == want


@pytest.mark.parametrize("family", sorted(OTHER_TRACED))
def test_other_families_trace_to_the_text_they_had(family):
    """PR 64 moved no other kernel: the Q4_K, Q5_K, Q8_0 and `pre` calls and
    the grouped expert calls trace to the parent's text."""
    assert _traced_hashes(OTHER_TRACED[family]) == OTHER_TRACED[family]


def _parents_head_call(xpa, w, tn, tiles):
    """The head's call as the parent (ea43b71) built it, from this tree's
    body: the activations transposed to (K tiles, B, 2304), a K tile a
    leading index of the block (the body read ``xpa_ref[j]``)."""
    from jax.experimental import pallas as pl

    from llama_fastapi_k8s_gpu_tpu.ops.pallas import q6matmul as Q6

    (B, ka), n = xpa.shape, w["q4"].shape[0]
    kt = ka // Q6.TKA6

    class ATile:
        def __init__(self, ref):
            self.ref = ref

        def __getitem__(self, idx):     # [:, j * 2304:(j + 1) * 2304]
            return self.ref[idx[1].start // Q6.TKA6]

    def kernel(x_ref, *refs):
        Q6._q6k_head_kernel(ATile(x_ref), *refs, interpret=True, tiles=tiles,
                            accumulate=tiles < kt)

    return pl.pallas_call(
        kernel, grid=(n // tn, kt // tiles),
        in_specs=[
            pl.BlockSpec((tiles, B, Q6.TKA6), lambda n, k: (k, 0, 0)),
            pl.BlockSpec((tn, tiles * 1024), lambda n, k: (n, k)),
            pl.BlockSpec((tn, tiles * 512), lambda n, k: (n, k)),
            pl.BlockSpec((tiles, tn, 128), lambda n, k: (k, n, 0))],
        out_specs=pl.BlockSpec((B, tn), lambda n, k: (0, n)),
        out_shape=jax.ShapeDtypeStruct((B, n), jnp.float32), interpret=True,
    )(jnp.transpose(xpa.reshape(B, kt, Q6.TKA6), (1, 0, 2)),
      w["q4"], w["q2"], w["sm6"])


@pytest.mark.parametrize("rows,n,k,parent", [
    (8, 2560, 8192, (640, 4)),      # a head's tiling: the rule's, the parent's
    (256, 4096, 6144, (1024, 3)),   # a slice beside live lanes
    (8, 1024, 4096, (1024, 2)),     # an N no head has: (256, 2) since PR 64
])
def test_unstacked_call_equals_the_parents_form_bit_for_bit(rows, n, k,
                                                            parent):
    """Where the shared builder changed the unstacked call's program, its
    results are the parent's to the bit: the activations' operand form
    (every shape), and the N tile of an N so narrow that the head's widest
    tile leaves under ``MIN_STEPS`` grid steps (no head: 1024 x 4096 takes
    (256, 2) for (1024, 2); a step holds the same K tiles, so the float32
    sums are taken in the same order)."""
    from llama_fastapi_k8s_gpu_tpu.ops.pallas import q6matmul as Q6

    rng = np.random.default_rng(64 + rows)
    w = _random_planes(rng, n, k)
    xpa = Q6.augment_x6(permute_x6(jnp.asarray(
        rng.standard_normal((rows, k)), jnp.bfloat16)))
    assert (Q6._q6k_tiling(n, rows, k // 2048, True) == parent) == (n != 1024)
    new = np.asarray(Q6._q6k_2d_raw(xpa, w["q4"], w["q2"], w["sm6"], True))
    old = np.asarray(_parents_head_call(xpa, w, *parent))
    assert np.array_equal(new.view(np.uint32), old.view(np.uint32))
    assert np.abs(new).sum() > 0


def test_routed_layers_no_longer_trace_to_the_stacked_body():
    got = _traced_hashes(PARENT_ROUTED)
    assert got.keys() == PARENT_ROUTED.keys()
    assert all(got[key] != PARENT_ROUTED[key] for key in got), got


@pytest.mark.parametrize("leaf,name,stacked", [
    ("q6k", "q6k-head", "q6k-int"), ("tied", "bf16", None),
    ("int8", "int8", None), ("q4k", "q4k-fused", None), (None, None, None),
    ("q6k-pre", "q6k-fused-pre", "q6k-fused-pre")])
def test_health_names_the_heads_kernel(monkeypatch, leaf, name, stacked):
    """``/health`` ``engine.head_kernel``: ``q6k-head`` for a Q6_K head in
    the split layout, else what ``weight_formats`` would say of the head
    (a tied embedding: ``bf16``); ``engine.q6k_kernel``: the body the layers'
    stacked Q6_K linears run (``q6k-int``: the head's integer dequantization,
    since PR 64), None where no layer keeps such a tensor."""
    from llama_fastapi_k8s_gpu_tpu.ops.linear import (
        make_linear_int8, make_linear_q4k)
    from llama_fastapi_k8s_gpu_tpu.server.app import (
        _head_kernel, _q6k_kernel)

    if leaf == "q6k-pre":
        monkeypatch.setenv("LFKT_Q6K_KERNEL", "pre")
    wf = _rand_weights(np.random.default_rng(9), 16, 2048)
    emb = jnp.asarray(wf, jnp.bfloat16)
    out = {"q6k": lambda: make_linear_q6k(wf), "tied": lambda: {"w": emb},
           "q6k-pre": lambda: make_linear_q6k(wf),
           "int8": lambda: make_linear_int8(wf),
           "q4k": lambda: make_linear_q4k(wf), None: lambda: None}[leaf]()
    layers = {"wq": {"w": emb[None]}}
    if out is not None:
        layers["w_down"] = {k: v[None] for k, v in out.items()
                            if hasattr(v, "shape")}
    params = {"tok_emb": emb, "layers": layers, "output": out}
    assert _head_kernel(params) == name
    assert _q6k_kernel(params) == stacked
    assert _head_kernel(None) is None and _q6k_kernel(None) is None


@pytest.mark.anyio
async def test_health_serves_head_kernel_beside_attn_impl():
    """Through the served ``/health``: two keys in ``engine``, none in
    ``weight_formats`` (the configurations' ``expect_health`` holds that
    dict letter for letter)."""
    import httpx

    from llama_fastapi_k8s_gpu_tpu.engine import FakeEngine
    from llama_fastapi_k8s_gpu_tpu.server.app import create_app
    from llama_fastapi_k8s_gpu_tpu.utils.config import Settings

    wf = _rand_weights(np.random.default_rng(10), 16, 2048)
    stacked = {k: v[None] for k, v in make_linear_q6k(wf).items()}
    eng = FakeEngine(reply="x")
    eng.params = {"layers": {"w_down": stacked},
                  "output": make_linear_q6k(wf)}
    app = create_app(engine=eng, settings=Settings())
    transport = httpx.ASGITransport(app=app)
    async with transport:
        await app.router.startup()
        async with httpx.AsyncClient(transport=transport,
                                     base_url="http://test") as client:
            got = (await client.get("/health")).json()["engine"]
        await app.router.shutdown()
    assert got["head_kernel"] == "q6k-head"
    assert got["q6k_kernel"] == "q6k-int"
    assert got["weight_formats"] == {"w_down": "q6k-fused"}
    assert list(got)[:5] == ["model", "n_ctx", "attn_impl", "head_kernel",
                             "q6k_kernel"]
