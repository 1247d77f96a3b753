"""Pallas kernels vs their oracles (interpret mode on the CPU backend).

- dequant kernels vs the numpy codecs in gguf/quants.py — bit-exact, since
  both sides run the identical f32 arithmetic (SURVEY.md §4 "Unit").
- flash attention vs the XLA score-matrix path in models/llama.py.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llama_fastapi_k8s_gpu_tpu.gguf.constants import GGMLType

# jax-version compat: jax.tree.flatten_with_path landed after 0.4.37; the
# tree_util spelling exists on every version this repo supports
_flatten_with_path = getattr(
    jax.tree, "flatten_with_path", None) or jax.tree_util.tree_flatten_with_path
from llama_fastapi_k8s_gpu_tpu.gguf.quants import dequantize, quantize
from llama_fastapi_k8s_gpu_tpu.models.config import ModelConfig
from llama_fastapi_k8s_gpu_tpu.models.generate import init_state, prefill_jit
from llama_fastapi_k8s_gpu_tpu.models.params import synth_params
from llama_fastapi_k8s_gpu_tpu.ops.pallas import device_dequant, flash_attention

# ---------------------------------------------------------------------------
# dequant
# ---------------------------------------------------------------------------

# counts chosen to exercise (kernel-only), (kernel+tail), and (tail-only)
_COUNTS = {
    GGMLType.Q8_0: [32 * 4 * 256 * 2, 32 * 4 * 256 + 32 * 20, 32 * 3],
    GGMLType.Q4_K: [256 * 256 * 2, 256 * 256 + 256 * 7, 256 * 5],
    GGMLType.Q5_K: [256 * 256 * 2, 256 * 256 + 256 * 7, 256 * 5],
    GGMLType.Q6_K: [256 * 128 * 2, 256 * 128 + 256 * 7, 256 * 5],
}


@pytest.mark.parametrize("ggml_type", list(_COUNTS))
def test_device_dequant_bit_exact(ggml_type):
    rng = np.random.default_rng(int(ggml_type))
    for n in _COUNTS[ggml_type]:
        x = rng.standard_normal(n, dtype=np.float32)
        buf = quantize(x, ggml_type)
        want = dequantize(buf, ggml_type, n)
        got = np.asarray(device_dequant(buf, ggml_type, n))
        np.testing.assert_array_equal(got, want, err_msg=f"{ggml_type} n={n}")


def test_device_dequant_fallback_formats():
    rng = np.random.default_rng(0)
    x = rng.standard_normal(64 * 32, dtype=np.float32)
    for t in (GGMLType.F16, GGMLType.F32, GGMLType.Q4_0):
        buf = quantize(x, t)
        want = dequantize(buf, t, x.size)
        got = np.asarray(device_dequant(buf, t, x.size))
        np.testing.assert_array_equal(got, want)


def test_device_dequant_bf16_output():
    rng = np.random.default_rng(1)
    n = 256 * 512
    x = rng.standard_normal(n, dtype=np.float32)
    buf = quantize(x, GGMLType.Q4_K)
    got = device_dequant(buf, GGMLType.Q4_K, n, dtype=jnp.bfloat16)
    assert got.dtype == jnp.bfloat16
    want = dequantize(buf, GGMLType.Q4_K, n)
    np.testing.assert_allclose(
        np.asarray(got, dtype=np.float32), want, rtol=1e-2, atol=1e-2
    )


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

def _ref_attention(q, k, v, pos_offset, sm_scale, sliding_window=0):
    """The XLA path from models/llama.py, as a standalone oracle.
    k/v head-major (n_kv, n_ctx, hd), matching init_cache."""
    S, H, hd = q.shape
    n_kv, n_ctx, _ = k.shape
    group = H // n_kv
    qg = q.reshape(S, n_kv, group, hd).transpose(1, 2, 0, 3)
    kk = k
    vv = v
    scores = jnp.einsum(
        "ngsh,nch->ngsc", qg, kk, preferred_element_type=jnp.float32
    ) * sm_scale
    key_pos = jnp.arange(n_ctx)
    q_pos = pos_offset + jnp.arange(S)
    mask = key_pos[None, :] <= q_pos[:, None]
    if sliding_window:
        mask &= key_pos[None, :] > q_pos[:, None] - sliding_window
    scores = jnp.where(mask[None, None, :, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(vv.dtype)
    ctx = jnp.einsum("ngsc,nch->ngsh", probs, vv)
    return ctx.transpose(2, 0, 1, 3).reshape(S, H, hd)


@pytest.mark.parametrize(
    "S,n_ctx,H,n_kv,hd,offset,window",
    [
        (16, 64, 4, 2, 32, 0, 0),       # prefill from empty cache
        (16, 64, 4, 2, 32, 13, 0),      # continuation at an offset
        (32, 128, 8, 8, 16, 0, 0),      # MHA (group=1)
        (16, 64, 4, 1, 32, 7, 0),       # maximal grouping
        (16, 64, 4, 2, 32, 9, 24),      # sliding window (Mistral path)
        (128, 256, 4, 2, 128, 0, 0),    # full-lane head_dim, multi-kv-block
    ],
)
def test_flash_attention_matches_xla(S, n_ctx, H, n_kv, hd, offset, window):
    keys = jax.random.split(jax.random.PRNGKey(S + n_ctx + H), 3)
    q = jax.random.normal(keys[0], (S, H, hd), jnp.float32)
    k = jax.random.normal(keys[1], (n_kv, n_ctx, hd), jnp.float32)
    v = jax.random.normal(keys[2], (n_kv, n_ctx, hd), jnp.float32)
    # k/v carry garbage in unwritten ring slots on purpose: the causal mask
    # must hide them, which is exactly what a real cache relies on
    sm = hd ** -0.5
    got = flash_attention(
        q, k, v, jnp.int32(offset), sm_scale=sm, sliding_window=window,
        interpret=True,
    )
    want = _ref_attention(q, k, v, jnp.int32(offset), sm, window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize(
    "S,n_ctx,H,n_kv,hd,offset,window,bq,bk",
    [
        # multi-block grids so the causal block classifier's THREE branches
        # all execute (attention.py: skip / interior-unmasked / edge-masked).
        # Default-shaped CI cases compile to a single kv block with
        # bq >= gs, where skip and interior are unreachable — a sign error
        # in the block bounds would pass every other test and silently
        # attend to future tokens at long context on hardware.
        (64, 256, 4, 2, 32, 0, 0, 16, 32),     # tight span: S % bq == 0
        (64, 256, 4, 2, 32, 100, 0, 16, 32),   # offset: fewer skips, interior
        (64, 256, 4, 2, 32, 192, 0, 16, 32),   # queries at the ring's end
        (64, 256, 4, 2, 32, 100, 48, 16, 32),  # sliding window: edge + skip
        (24, 96, 4, 2, 32, 0, 0, 16, 32),      # S % bq != 0: tile wraps →
                                               # conservative full-range path
        (64, 256, 4, 2, 32, 64, 0, 128, 32),   # bq > S, bq % S == 0
    ],
)
def test_flash_attention_block_branches(S, n_ctx, H, n_kv, hd, offset,
                                        window, bq, bk):
    keys = jax.random.split(jax.random.PRNGKey(7 * S + offset + bq), 3)
    q = jax.random.normal(keys[0], (S, H, hd), jnp.float32)
    k = jax.random.normal(keys[1], (n_kv, n_ctx, hd), jnp.float32)
    v = jax.random.normal(keys[2], (n_kv, n_ctx, hd), jnp.float32)
    sm = hd ** -0.5
    got = flash_attention(
        q, k, v, jnp.int32(offset), sm_scale=sm, sliding_window=window,
        block_q=bq, block_k=bk, interpret=True,
    )
    want = _ref_attention(q, k, v, jnp.int32(offset), sm, window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize(
    "S,n_ctx,H,n_kv,hd,offset,window,unroll",
    [
        # the multi-KV-block inner loop (LFKT_FLASH_KV_UNROLL): fused K/V
        # blocks with in-kernel sub-block iteration must match the oracle
        # across the same branch zoo as the plain grid
        (64, 256, 4, 2, 32, 0, 0, 2),      # causal from empty cache
        (64, 256, 4, 2, 32, 100, 0, 4),    # offset continuation
        (64, 256, 4, 2, 32, 100, 48, 2),   # sliding window edges
        (64, 256, 4, 2, 32, 0, 0, 8),      # whole ring in ONE grid step
        (24, 96, 4, 2, 32, 5, 0, 3),       # conservative-span path, odd U
    ],
)
def test_flash_attention_kv_unroll_matches_xla(S, n_ctx, H, n_kv, hd,
                                               offset, window, unroll):
    keys = jax.random.split(jax.random.PRNGKey(11 * S + offset + unroll), 3)
    q = jax.random.normal(keys[0], (S, H, hd), jnp.float32)
    k = jax.random.normal(keys[1], (n_kv, n_ctx, hd), jnp.float32)
    v = jax.random.normal(keys[2], (n_kv, n_ctx, hd), jnp.float32)
    sm = hd ** -0.5
    got = flash_attention(
        q, k, v, jnp.int32(offset), sm_scale=sm, sliding_window=window,
        block_q=16, block_k=32, kv_unroll=unroll, interpret=True,
    )
    want = _ref_attention(q, k, v, jnp.int32(offset), sm, window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_kv_unroll_bit_identical_to_plain_grid():
    """The fused block runs the SAME online-softmax updates in the same
    order as the unrolled grid — the outputs must be bit-identical, not
    just close (the greedy-parity contract of the prefill pipeline rests
    on this)."""
    keys = jax.random.split(jax.random.PRNGKey(99), 3)
    q = jax.random.normal(keys[0], (32, 4, 32), jnp.float32)
    k = jax.random.normal(keys[1], (2, 128, 32), jnp.float32)
    v = jax.random.normal(keys[2], (2, 128, 32), jnp.float32)
    kw = dict(sm_scale=32 ** -0.5, block_q=16, block_k=32, interpret=True)
    base = flash_attention(q, k, v, jnp.int32(17), kv_unroll=1, **kw)
    for u in (2, 4):
        fused = flash_attention(q, k, v, jnp.int32(17), kv_unroll=u, **kw)
        assert (np.asarray(base) == np.asarray(fused)).all(), u


def test_flash_attention_kv_unroll_clamps_to_ring():
    """A tiny ring (one kv block) silently degrades to the plain grid —
    an oversized LFKT_FLASH_KV_UNROLL must never be a crash."""
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(keys[0], (8, 2, 32), jnp.float32)
    k = jax.random.normal(keys[1], (2, 32, 32), jnp.float32)
    v = jax.random.normal(keys[2], (2, 32, 32), jnp.float32)
    got = flash_attention(q, k, v, jnp.int32(0), sm_scale=32 ** -0.5,
                          kv_unroll=64, interpret=True)
    want = _ref_attention(q, k, v, jnp.int32(0), 32 ** -0.5, 0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_prefill_pallas_matches_xla_end_to_end():
    """Full model forward: logits with attn_impl=pallas ≈ attn_impl=xla."""
    cfg = ModelConfig(vocab_size=256, dim=64, n_layers=2, n_heads=4,
                      n_kv_heads=2, ffn_dim=128, n_ctx=64)
    params = synth_params(cfg, fmt="bf16", seed=3)
    tokens = jnp.arange(1, 33, dtype=jnp.int32)
    length = jnp.int32(32)

    logits_xla, _ = prefill_jit(params, cfg, tokens, length,
                                init_state(cfg)["cache"])
    cfg_p = dataclasses.replace(cfg, attn_impl="pallas")
    logits_pl, _ = prefill_jit(params, cfg_p, tokens, length,
                               init_state(cfg_p)["cache"])
    # bf16 weights: tolerance covers softmax-accumulation-order noise
    np.testing.assert_allclose(
        np.asarray(logits_pl), np.asarray(logits_xla), rtol=5e-2, atol=5e-2
    )


# ---------------------------------------------------------------------------
# load path: Pallas dequant + device requant == numpy reference codecs
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fmt", ["bf16", "int8"])
def test_load_params_on_device_matches_host(tmp_path, fmt):
    from llama_fastapi_k8s_gpu_tpu.gguf import GGUFFile
    from llama_fastapi_k8s_gpu_tpu.models.params import load_params
    from llama_fastapi_k8s_gpu_tpu.testing import write_tiny_llama_gguf

    path = str(tmp_path / "tiny.gguf")
    cfg = write_tiny_llama_gguf(path, quant=GGMLType.Q4_K,
                                ffn_quant=GGMLType.Q6_K)
    gf = GGUFFile(path)
    host = load_params(gf, cfg, fmt=fmt, on_device=False)
    dev = load_params(gf, cfg, fmt=fmt, on_device=True)
    flat_h, tree_h = _flatten_with_path(host)
    flat_d, tree_d = _flatten_with_path(dev)
    assert tree_h == tree_d
    for (path_h, h), (_, d) in zip(flat_h, flat_d):
        assert h.dtype == d.dtype and h.shape == d.shape
        h32 = np.asarray(h, np.float32)
        d32 = np.asarray(d, np.float32)
        # XLA folds /127.0 into a reciprocal multiply → int8 scales can be
        # 1 ulp off the numpy codec, and quantized values ±1 on ties.
        if fmt == "int8" and h.dtype == jnp.int8:
            np.testing.assert_allclose(d32, h32, atol=1.0, err_msg=str(path_h))
        elif fmt == "int8" and h.dtype == jnp.float32:
            np.testing.assert_allclose(d32, h32, rtol=1e-6, err_msg=str(path_h))
        else:
            np.testing.assert_array_equal(d32, h32, err_msg=str(path_h))


@pytest.mark.parametrize("fmt", ["bf16", "int8"])
def test_load_params_overlap_matches_default(tmp_path, fmt, monkeypatch):
    """LFKT_LOAD_OVERLAP=1 (per-layer async device_put + device-side stack,
    progressive freeing; the default since the 2026-08-01 coldstart A/B)
    must produce a bitwise-identical pytree to the serial host-side stack
    order (LFKT_LOAD_OVERLAP=0 — pinned explicitly so the serial path
    keeps its only identity coverage whatever the shipped default)."""
    from llama_fastapi_k8s_gpu_tpu.gguf import GGUFFile
    from llama_fastapi_k8s_gpu_tpu.models.params import load_params
    from llama_fastapi_k8s_gpu_tpu.testing import write_tiny_llama_gguf

    path = str(tmp_path / "tiny-ov.gguf")
    cfg = write_tiny_llama_gguf(path, quant=GGMLType.Q4_K,
                                ffn_quant=GGMLType.Q6_K)
    gf = GGUFFile(path)
    monkeypatch.setenv("LFKT_LOAD_OVERLAP", "0")
    base = load_params(gf, cfg, fmt=fmt, on_device=False)
    monkeypatch.setenv("LFKT_LOAD_OVERLAP", "1")
    over = load_params(gf, cfg, fmt=fmt, on_device=False)
    flat_b, tree_b = _flatten_with_path(base)
    flat_o, tree_o = _flatten_with_path(over)
    assert tree_b == tree_o
    for (p, b), (_, o) in zip(flat_b, flat_o):
        assert b.dtype == o.dtype and b.shape == o.shape, p
        np.testing.assert_array_equal(np.asarray(b), np.asarray(o), err_msg=str(p))
