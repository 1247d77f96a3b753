"""Fused Q5_K dequant-matmul kernel vs the dequant-then-matmul oracle.

Same contract as tests/test_qmatmul.py / test_q6matmul.py; Q5_K completes
the K-quant family (Q5_K_M files are the other common llama.cpp artifact
besides the reference's Q4_K_M, reference api.py:14)."""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from llama_fastapi_k8s_gpu_tpu.gguf.quants import dequant_q5_k, quant_q5_k
from llama_fastapi_k8s_gpu_tpu.ops.linear import linear, make_linear_q5k
from llama_fastapi_k8s_gpu_tpu.ops.pallas.q5matmul import (
    dequant_ref5,
    prep_q5k,
    q5k_matmul,
)
from llama_fastapi_k8s_gpu_tpu.ops.pallas.qmatmul import permute_x


def _rand_weights(rng, n, k):
    return (rng.standard_normal((n, k)).astype(np.float32) * (k ** -0.5))


@pytest.mark.parametrize("n,k,b", [
    (8, 2048, 1),
    (128, 2048, 4),
    (256, 4096, 2),
])
def test_kernel_matches_dequant_ref5(n, k, b):
    rng = np.random.default_rng(n + k)
    w = make_linear_q5k(_rand_weights(rng, n, k))
    x = jnp.asarray(rng.standard_normal((b, k)), jnp.float32)

    ref = permute_x(x).astype(jnp.bfloat16).astype(jnp.float32) @ dequant_ref5(w).T
    got = q5k_matmul(x, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-2, atol=2e-2 * float(jnp.abs(ref).max()))


def test_end_to_end_vs_numpy_codec():
    rng = np.random.default_rng(0)
    n, k = 64, 2048
    raw = quant_q5_k(_rand_weights(rng, n, k).reshape(-1))
    w = prep_q5k(raw, n, k)
    w_deq = dequant_q5_k(raw, n * k).reshape(n, k)

    x = rng.standard_normal((2, k)).astype(np.float32)
    ref = x @ w_deq.T
    got = np.asarray(q5k_matmul(jnp.asarray(x), w))
    np.testing.assert_allclose(got, ref, rtol=3e-2,
                               atol=3e-2 * float(np.abs(ref).max()))


def test_prep_roundtrips_exact_values():
    """dequant_ref5 over the packed layout == numpy codec dequant (up to
    the bf16 scale fold), in the Q4_K-shared permuted column order."""
    rng = np.random.default_rng(1)
    n, k = 16, 2048
    raw = quant_q5_k(_rand_weights(rng, n, k).reshape(-1))
    w = prep_q5k(raw, n, k)
    ref = dequant_q5_k(raw, n * k).reshape(n, k)
    ref_p = np.asarray(permute_x(jnp.asarray(ref)))
    got = np.asarray(dequant_ref5(w))
    np.testing.assert_allclose(got, ref_p, rtol=8e-3,
                               atol=8e-3 * float(np.abs(ref).max()))


def test_linear_dispatch_routes_q5k():
    rng = np.random.default_rng(2)
    w = make_linear_q5k(_rand_weights(rng, 16, 2048))
    x = jnp.asarray(rng.standard_normal((3, 2048)), jnp.bfloat16)
    y = linear(x, w)
    assert y.shape == (3, 16) and y.dtype == jnp.bfloat16


def test_load_params_q5km_fuses(tmp_path):
    """A Q5_K_M-style file (attn Q5_K, ffn Q6_K) loads both fused layouts
    and its logits agree with a bf16 load."""
    from llama_fastapi_k8s_gpu_tpu.gguf import GGMLType, GGUFFile
    from llama_fastapi_k8s_gpu_tpu.models.config import ModelConfig
    from llama_fastapi_k8s_gpu_tpu.models.llama import init_cache, prefill
    from llama_fastapi_k8s_gpu_tpu.models.params import load_params
    from llama_fastapi_k8s_gpu_tpu.testing import write_tiny_llama_gguf

    cfg = ModelConfig(vocab_size=263, dim=2048, n_layers=1, n_heads=16,
                      n_kv_heads=8, ffn_dim=2048, n_ctx=32)
    path = str(tmp_path / "q5km.gguf")
    cfg = write_tiny_llama_gguf(path, cfg=cfg, quant=GGMLType.Q5_K,
                                ffn_quant=GGMLType.Q6_K)
    gf = GGUFFile(path)
    params = load_params(gf, cfg, fmt="q4k", on_device=False)
    # the shipped Q5_K default is the `pre` LAYOUT (2026-08-01 A/B)
    assert "q5p" in params["layers"]["wq"]
    assert "q4" in params["layers"]["w_gate"]

    ref = load_params(gf, cfg, fmt="bf16", on_device=False)
    toks = jnp.arange(1, 9, dtype=jnp.int32)
    lg_q, _ = prefill(params, cfg, toks, jnp.int32(8), init_cache(cfg))
    lg_r, _ = prefill(ref, cfg, toks, jnp.int32(8), init_cache(cfg))
    a, b = np.asarray(lg_q), np.asarray(lg_r)
    denom = np.abs(b).max() + 1e-6
    assert np.abs(a - b).max() / denom < 0.08, np.abs(a - b).max() / denom


def test_q5k_probe_passes():
    from llama_fastapi_k8s_gpu_tpu.ops.pallas.probe import probe_fused_q5k

    assert probe_fused_q5k() is None


def test_parfloor_variant_bit_identical(monkeypatch):
    """LFKT_Q5K_KERNEL=parfloor must produce BIT-identical output: its
    independent hi-bit floors compute the same exact f32 integers as the
    serial remainder chain."""
    import numpy as np

    from llama_fastapi_k8s_gpu_tpu.gguf.quants import quant_q5_k
    from llama_fastapi_k8s_gpu_tpu.ops.pallas.q5matmul import prep_q5k, q5k_matmul

    rng = np.random.default_rng(2)
    n, k = 64, 2048
    w = (rng.standard_normal((n, k)) * 0.05).astype(np.float32)
    # pin the SPLIT layout explicitly: the shipped default is the `pre`
    # LAYOUT since the 2026-08-01 A/B, and a default-prepped q5p plane
    # would make this split-kernel body comparison vacuous
    monkeypatch.setenv("LFKT_Q5K_KERNEL", "cur")
    wd = prep_q5k(quant_q5_k(w.reshape(-1)), n, k)
    x = jnp.asarray(rng.standard_normal((4, k)), jnp.bfloat16)
    a = np.asarray(q5k_matmul(x, wd, interpret=True))
    monkeypatch.setenv("LFKT_Q5K_KERNEL", "parfloor")
    b = np.asarray(q5k_matmul(x, wd, interpret=True))
    assert np.array_equal(a, b)


def test_pre_layout_matches_oracle_and_split(monkeypatch):
    """LFKT_Q5K_KERNEL=pre (pre-combined int8 q5 plane, ~3 VPU ops/weight)
    must agree with the f32 dequant oracle at least as tightly as the
    split `cur` path: its plane q5*sc is the exact f32 value the split
    path reaches via l*sc + hb*(16 sc) before the same bf16 cast, and it
    ROUNDS ONE FEWER corr term (the +8 hi-nibble bias rides the exact
    plane instead of a bf16 corr column)."""
    from llama_fastapi_k8s_gpu_tpu.ops.pallas import q5matmul as qm

    rng = np.random.default_rng(21)
    n, k = 64, 4096
    raw = quant_q5_k(_rand_weights(rng, n, k).reshape(-1))
    monkeypatch.setenv("LFKT_Q5K_KERNEL", "cur")
    w_split = prep_q5k(raw, n, k)
    monkeypatch.setenv("LFKT_Q5K_KERNEL", "pre")
    w_pre = prep_q5k(raw, n, k)
    assert set(w_pre) == {"q5p", "sm5"}
    assert w_pre["q5p"].dtype == jnp.int8
    q5p = np.asarray(w_pre["q5p"])
    assert q5p.min() >= 0 and q5p.max() < 32

    x = jnp.asarray(rng.standard_normal((4, k)), jnp.float32)
    ref = np.asarray(
        permute_x(x).astype(jnp.bfloat16).astype(jnp.float32)
        @ dequant_ref5(w_split).T)
    got_pre = np.asarray(q5k_matmul(x, w_pre, interpret=True))
    monkeypatch.setenv("LFKT_Q5K_KERNEL", "cur")
    got_cur = np.asarray(q5k_matmul(x, w_split, interpret=True))

    scale = np.abs(ref).max()
    err_pre = np.abs(got_pre - ref).max()
    err_cur = np.abs(got_cur - ref).max()
    # pre rounds a strict subset of cur's terms; allow bf16-noise slack
    assert err_pre <= err_cur + 2e-3 * scale, (err_pre, err_cur, scale)
    np.testing.assert_allclose(got_pre, got_cur, atol=4e-3 * scale)


def test_pre_layout_stacked_matches_plain(monkeypatch):
    """Stacked scalar-prefetch path == plain path for the pre layout."""
    from llama_fastapi_k8s_gpu_tpu.ops.pallas.q5matmul import (
        q5k_matmul_stacked,
    )

    rng = np.random.default_rng(22)
    n, k = 32, 2048
    monkeypatch.setenv("LFKT_Q5K_KERNEL", "pre")
    w0 = prep_q5k(quant_q5_k(_rand_weights(rng, n, k).reshape(-1)), n, k)
    w1 = prep_q5k(quant_q5_k(_rand_weights(rng, n, k).reshape(-1)), n, k)
    ws = {key: jnp.stack([w0[key], w1[key]]) for key in w0}
    x = jnp.asarray(rng.standard_normal((2, k)), jnp.bfloat16)
    for i, w in enumerate((w0, w1)):
        plain = np.asarray(q5k_matmul(x, w, interpret=True))
        stacked = np.asarray(q5k_matmul_stacked(x, ws, i, interpret=True))
        np.testing.assert_array_equal(plain, stacked)
