"""Fused Q6_K dequant-matmul kernel vs the dequant-then-matmul oracle.

Same contract as tests/test_qmatmul.py: the kernel must agree with an XLA
matmul against ``dequant_ref6`` (bf16-folded scales) and, end to end, with
the numpy Q6_K codec within quantization-noise tolerance.  Q6_K is what
Q4_K_M files use for ffn_down / attn_v / output (the reference's served
artifact mixes both types), so this is the second half of "serve Q4_K_M
fully fused"."""

from __future__ import annotations

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


def _stack_of_one(wd):
    """The stacked call on a stack of one: the ``LFKT_Q6K_KERNEL`` variants
    are bodies of the stacked calls (the unstacked call is the head's, one
    body whatever the knob says)."""
    from llama_fastapi_k8s_gpu_tpu.ops.pallas.q6matmul import q6k_matmul_stacked

    ws = {key: v[None] for key, v in wd.items()}
    return lambda x, interpret=True: q6k_matmul_stacked(
        x, ws, 0, interpret=interpret)


def test_parfloor_variant_bit_identical(monkeypatch):
    """LFKT_Q6K_KERNEL=parfloor must produce BIT-identical output: its
    independent floors compute the same exact f32 integers as the serial
    remainder chain."""
    import numpy as np

    from llama_fastapi_k8s_gpu_tpu.gguf.quants import quant_q6_k
    from llama_fastapi_k8s_gpu_tpu.ops.pallas.q6matmul import prep_q6k

    rng = np.random.default_rng(1)
    n, k = 64, 2048
    w = (rng.standard_normal((n, k)) * 0.05).astype(np.float32)
    wd = prep_q6k(quant_q6_k(w.reshape(-1)), n, k)
    x = jnp.asarray(rng.standard_normal((4, k)), jnp.bfloat16)
    # the variant is part of the builder cache key, so flipping the env
    # between calls re-traces without any cache_clear choreography.
    # Compare cur vs parfloor EXPLICITLY so the assertion is immune to
    # which of the two bit-identical variants leads the tuple default.
    mm = _stack_of_one(wd)
    monkeypatch.setenv("LFKT_Q6K_KERNEL", "cur")
    a = np.asarray(mm(x))
    monkeypatch.setenv("LFKT_Q6K_KERNEL", "parfloor")
    b = np.asarray(mm(x))
    assert np.array_equal(a, b)


def test_vbf32_variant_beats_default_accuracy(monkeypatch):
    """LFKT_Q6K_KERNEL=vbf32 (activation-side recombination, f32 planes,
    telescoped crumb digits) must show no cancellation blowup: at least as
    close to the f32 dequant_ref6 oracle as the bf16-plane default, and
    inside the default's own quantization tolerance."""
    from llama_fastapi_k8s_gpu_tpu.gguf.quants import quant_q6_k
    from llama_fastapi_k8s_gpu_tpu.ops.pallas.q6matmul import prep_q6k

    rng = np.random.default_rng(7)
    n, k = 64, 4096
    w = (rng.standard_normal((n, k)) * 0.05).astype(np.float32)
    wd = prep_q6k(quant_q6_k(w.reshape(-1)), n, k)
    x = jnp.asarray(rng.standard_normal((4, k)), jnp.float32)
    ref = np.asarray(
        permute_x6(x).astype(jnp.bfloat16).astype(jnp.float32) @ dequant_ref6(wd).T)
    mm = _stack_of_one(wd)
    monkeypatch.delenv("LFKT_Q6K_KERNEL", raising=False)
    cur = np.asarray(mm(x))
    monkeypatch.setenv("LFKT_Q6K_KERNEL", "vbf32")
    got = np.asarray(mm(x))
    err_cur = np.abs(cur - ref).max()
    err_vb = np.abs(got - ref).max()
    assert err_vb <= err_cur * 1.05, (err_vb, err_cur)
    np.testing.assert_allclose(got, ref, rtol=2e-2,
                               atol=2e-2 * float(np.abs(ref).max()))


def test_pre_layout_matches_oracle_and_split(monkeypatch):
    """LFKT_Q6K_KERNEL=pre (pre-combined int8 q6 plane, ~3 VPU ops/weight)
    must agree with the f32 dequant oracle at least as tightly as the
    split `cur` path: its plane q6*eff is the exact f32 value the split
    path reaches via nib*eff + crumb*(16 eff) before the same bf16 cast,
    and it ROUNDS ONE FEWER corr term (the +8 hi-nibble bias rides the
    exact plane instead of a bf16 corr column)."""
    from llama_fastapi_k8s_gpu_tpu.ops.pallas import q6matmul as qm

    rng = np.random.default_rng(11)
    n, k = 64, 4096
    raw = quant_q6_k(_rand_weights(rng, n, k).reshape(-1))
    monkeypatch.setenv("LFKT_Q6K_KERNEL", "cur")
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
    monkeypatch.setenv("LFKT_Q6K_KERNEL", "cur")
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

def _random_planes(rng, n, k):
    """Kernel-layout planes over every byte value (the kernels' arithmetic
    is total: any int8 pair is some Q6_K weight, any bf16 a scale)."""
    return {
        "q4": jnp.asarray(rng.integers(-128, 128, (n, k // 2)), jnp.int8),
        "q2": jnp.asarray(rng.integers(-128, 128, (n, k // 4)), jnp.int8),
        "sm6": jnp.asarray(rng.standard_normal((k // 2048, n, 128)) * 1e-2,
                           jnp.bfloat16)}


def _stacked_body_call(xpa, w):
    """The unstacked call as it was before PR 57: the stacked calls' body
    (``_q6k_matmul_kernel``) under their tiling, on a stack of one."""
    from llama_fastapi_k8s_gpu_tpu.ops.pallas import q6matmul as Q6

    return Q6._q6k_2d_stacked_raw(
        jnp.zeros(1, jnp.int32), xpa, w["q4"][None], w["q2"][None],
        w["sm6"][None], interpret=True)


@pytest.mark.parametrize("n", [256, 1280])    # 1280: 512 does not divide it
@pytest.mark.parametrize("k", [2048, 6144, 8192])
@pytest.mark.parametrize("rows", [1, 4, 16])
def test_head_call_matches_oracle_and_the_stacked_bodys_plane(rows, k, n):
    """The head's call against ``dequant_ref6`` at today's tolerance, and its
    dequantized plane against the stacked body's BIT FOR BIT: a one-hot
    activation row (no correction columns) reads one plane column out of
    either kernel exactly, whatever order the float32 sums are taken in."""
    from llama_fastapi_k8s_gpu_tpu.ops.pallas import q6matmul as Q6

    rng = np.random.default_rng(rows * 7 + k + n)
    w = _random_planes(rng, n, k)
    x = jnp.asarray(rng.standard_normal((rows, k)), jnp.float32)
    ref = (permute_x6(x).astype(jnp.bfloat16).astype(jnp.float32)
           @ dequant_ref6(w).T)
    got = q6k_matmul(x, w, interpret=True)
    assert got.shape == (rows, n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-2,
                               atol=2e-2 * float(jnp.abs(ref).max()))

    # 256 columns of every K tile, 64 of each quarter (the four quarters of
    # a tile are taken apart by four different integer forms)
    assert Q6._head_tiling(n, 256, k // 2048, True)[0] == {256: 256,
                                                           1280: 640}[n]
    for t in range(k // 2048):
        cols = np.concatenate([q * 512 + rng.permutation(512)[:64]
                               for q in range(4)])
        onehot = np.zeros((256, k // 2048, Q6.TKA6), np.float32)
        onehot[np.arange(256), t, cols] = 1.0
        xpa = jnp.asarray(onehot.reshape(256, -1), jnp.bfloat16)
        new = np.asarray(Q6._q6k_2d_raw(xpa, w["q4"], w["q2"], w["sm6"], True))
        old = np.asarray(_stacked_body_call(xpa, w))
        assert np.array_equal(new.view(np.uint32), old.view(np.uint32)), t
        plane = np.asarray(dequant_ref6(w))[:, t * 2048 + cols].T
        bias = np.asarray(w["sm6"][t].astype(jnp.float32))[
            :, cols % 128].T * np.where(cols < 1024, 32.0, 24.0)[:, None]
        # (the plane holds q6 - 0 in the low half and q6 - 8 in the high
        # one; the -32 and +8 ride the correction columns, zero here)
        np.testing.assert_allclose(new, plane + bias, rtol=1e-2, atol=1e-6)


def test_head_call_ignores_the_variant_knob(monkeypatch):
    """The unstacked split-layout call is one program whatever
    ``LFKT_Q6K_KERNEL`` says of the stacked bodies."""
    from llama_fastapi_k8s_gpu_tpu.ops.pallas import q6matmul as Q6

    w = _random_planes(np.random.default_rng(5), 64, 2048)
    x = jnp.asarray(np.random.default_rng(6).standard_normal((2, 2048)),
                    jnp.bfloat16)
    outs = []
    for var in Q6.Q6K_VARIANTS:
        monkeypatch.setenv("LFKT_Q6K_KERNEL", var)
        outs.append(np.asarray(q6k_matmul(x, w, interpret=True)))
    assert all(np.array_equal(o, outs[0]) for o in outs)
    assert Q6.Q6K_VARIANTS == ("cur", "parfloor", "vbf32", "pre")


# jax.make_jaxpr's text (kernel bodies in full) of the DENSE Q6_K calls,
# hashed on the parent (5c73b4e) with tools/traced_program_hashes.py: the
# stacked call, which keeps the body ``_q6k_matmul_kernel`` (a decode row, a
# lane step's rows, a slice), and the head's call (a decode row, a lane
# step's rows, a slice, interpret mode), whose body PR 59 took apart into
# ``_q6k_tile_product`` for the grouped expert calls to share
PARENT_TRACED = {
    "stacked.q6k.4096x4096.r1.tpu": "fb7d69116df06667",
    "stacked.q6k.14336x4096.r8.tpu": "b49126df7b5baa31",
    "stacked.q6k.4096x4096.r512.tpu": "6d535e3614120b0a",
    "dense.q6k.4096x32000.r1.tpu": "97ef5a2f1f5029d3",
    "dense.q6k.4096x32000.r8.tpu": "3cb7b282a86a227c",
    "dense.q6k.4096x4096.r512.tpu": "6e6453ee43abfd43",
    "dense.q6k.4096x4096.r1.interp": "4872229a7f481e76",
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


def test_stacked_and_grouped_q6k_calls_trace_to_the_text_they_had():
    """The dense programs are the parent's: sharing the head's
    dequantization with the grouped calls changed no dense call's text."""
    assert _traced_hashes(PARENT_TRACED) == PARENT_TRACED


def test_routed_layers_no_longer_trace_to_the_stacked_body():
    got = _traced_hashes(PARENT_ROUTED)
    assert got.keys() == PARENT_ROUTED.keys()
    assert all(got[key] != PARENT_ROUTED[key] for key in got), got


@pytest.mark.parametrize("leaf,name", [
    ("q6k", "q6k-head"), ("tied", "bf16"), ("int8", "int8"),
    ("q4k", "q4k-fused"), (None, None)])
def test_health_names_the_heads_kernel(leaf, name):
    """``/health`` ``engine.head_kernel``: ``q6k-head`` for a Q6_K head in
    the split layout, else what ``weight_formats`` would say of the head
    (a tied embedding: ``bf16``)."""
    from llama_fastapi_k8s_gpu_tpu.ops.linear import (
        make_linear_int8, make_linear_q4k)
    from llama_fastapi_k8s_gpu_tpu.server.app import _head_kernel

    wf = _rand_weights(np.random.default_rng(9), 16, 2048)
    emb = jnp.asarray(wf, jnp.bfloat16)
    out = {"q6k": lambda: make_linear_q6k(wf), "tied": lambda: {"w": emb},
           "int8": lambda: make_linear_int8(wf),
           "q4k": lambda: make_linear_q4k(wf), None: lambda: None}[leaf]()
    params = {"tok_emb": emb, "layers": {}, "output": out}
    assert _head_kernel(params) == name
    assert _head_kernel(None) is None


@pytest.mark.anyio
async def test_health_serves_head_kernel_beside_attn_impl():
    """Through the served ``/health``: one new key in ``engine``, none in
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
    assert got["weight_formats"] == {"w_down": "q6k-fused"}
    assert list(got)[:4] == ["model", "n_ctx", "attn_impl", "head_kernel"]
