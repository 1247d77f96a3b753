"""Int8 KV cache (kv_dtype=int8, docs/KV_CACHE.md) vs the bf16 default.

Tier-1, CPU-only: every path here runs under JAX_PLATFORMS=cpu — the write
quantize uses the XLA reference formulation (ops/pallas/kvquant.py
dispatches off-TPU), the flash kernel's fused-dequant path runs in Pallas
interpret mode, and the engine smoke tests resolve attn_impl=xla.  No
Pallas compile is required anywhere.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llama_fastapi_k8s_gpu_tpu.models import ModelConfig, init_cache
from llama_fastapi_k8s_gpu_tpu.models.llama import cache_nbytes, forward
from llama_fastapi_k8s_gpu_tpu.models.params import synth_params
from llama_fastapi_k8s_gpu_tpu.ops.pallas import flash_attention
from llama_fastapi_k8s_gpu_tpu.ops.pallas.kvquant import (
    dequantize_kv,
    quantize_kv_pallas,
    quantize_kv_xla,
)

# head_dim 32: the int8 layout's bytes per token-head are hd + 4 vs bf16's
# 2*hd, so hd=32 gives the 0.5625x ratio the ≤0.6x capacity claim pins
CFG = ModelConfig(vocab_size=64, dim=128, n_layers=2, n_heads=4,
                  n_kv_heads=2, ffn_dim=128, n_ctx=160)
CFG8 = dataclasses.replace(CFG, kv_dtype="int8")


# ---------------------------------------------------------------------------
# quantize kernel
# ---------------------------------------------------------------------------

def test_quantize_kv_roundtrip_error_bound():
    """Symmetric per-head per-token int8: worst-case element error is half
    a quantization step = max|x| / 254 per token vector."""
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 9, 64), jnp.float32)
    q, s = quantize_kv_xla(x)
    assert q.dtype == jnp.int8 and s.dtype == jnp.float32
    assert q.shape == x.shape and s.shape == x.shape[:-1]
    y = dequantize_kv(q, s, jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    bound = amax / 254.0 + 1e-7
    assert bool(jnp.all(jnp.abs(y - x) <= bound))


def test_quantize_kv_pallas_matches_xla():
    """The Pallas write kernel and the XLA reference are the same f32 math;
    XLA may fold the /127.0 into a reciprocal multiply (exactly as in
    test_pallas.py's int8 load-path note), so scales can sit 1 ulp apart
    and a quantized value can flip ±1 on a rounding tie — nothing more."""
    for shape in [(2, 1, 32), (2, 8, 64), (4, 16, 128)]:
        x = jax.random.normal(jax.random.PRNGKey(sum(shape)), shape,
                              jnp.float32)
        q0, s0 = quantize_kv_xla(x)
        q1, s1 = quantize_kv_pallas(x, interpret=True)
        assert int(jnp.max(jnp.abs(
            q0.astype(jnp.int32) - q1.astype(jnp.int32)))) <= 1
        np.testing.assert_allclose(np.asarray(s0), np.asarray(s1), rtol=1e-6)


def test_quantize_kv_zero_vector_is_exact():
    x = jnp.zeros((2, 3, 16), jnp.float32)
    q, s = quantize_kv_xla(x)
    assert not np.any(np.asarray(q)) and not np.any(np.asarray(s))
    assert not np.any(np.asarray(dequantize_kv(q, s, jnp.float32)))


# ---------------------------------------------------------------------------
# fused-dequant flash attention vs the XLA reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("S,n_ctx,H,n_kv,hd,offset,window",
                         [(16, 64, 4, 2, 32, 0, 0),
                          (16, 64, 4, 2, 32, 13, 0),
                          (16, 64, 4, 2, 32, 9, 24)])
def test_flash_attention_fused_dequant_matches_dequantized(S, n_ctx, H, n_kv,
                                                           hd, offset, window):
    """The kernel's in-register scale folding must equal attention over the
    explicitly dequantized ring (same quantized inputs, so the only
    difference is where the scales multiply — tolerances cover f32/bf16
    accumulation-order noise only, not quantization error)."""
    keys = jax.random.split(jax.random.PRNGKey(S + offset + window), 3)
    q = jax.random.normal(keys[0], (S, H, hd), jnp.float32)
    kq, ks = quantize_kv_xla(
        jax.random.normal(keys[1], (n_kv, n_ctx, hd), jnp.float32))
    vq, vs = quantize_kv_xla(
        jax.random.normal(keys[2], (n_kv, n_ctx, hd), jnp.float32))
    sm = hd ** -0.5
    got = flash_attention(q, kq, vq, jnp.int32(offset), sm_scale=sm,
                          sliding_window=window, k_scale=ks, v_scale=vs,
                          interpret=True)
    want = flash_attention(q, dequantize_kv(kq, ks, jnp.float32),
                           dequantize_kv(vq, vs, jnp.float32),
                           jnp.int32(offset), sm_scale=sm,
                           sliding_window=window, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_model_pallas_prefill_matches_xla_with_int8_cache():
    """Full forward, int8 cache: the flash fused-dequant prefill path and
    the XLA score-matrix path read the same quantized ring."""
    cfg = dataclasses.replace(CFG8, n_ctx=64)
    params = synth_params(cfg, fmt="bf16", seed=3)
    tokens = jnp.arange(1, 33, dtype=jnp.int32) % cfg.vocab_size
    lx, _ = forward(params, cfg, tokens, jnp.int32(0), init_cache(cfg),
                    return_all=True)
    cfg_p = dataclasses.replace(cfg, attn_impl="pallas")
    lp, _ = forward(params, cfg_p, tokens, jnp.int32(0), init_cache(cfg_p),
                    return_all=True)
    np.testing.assert_allclose(np.asarray(lp), np.asarray(lx),
                               rtol=0.1, atol=0.1)


# ---------------------------------------------------------------------------
# cache layout + capacity
# ---------------------------------------------------------------------------

def test_int8_cache_layout_and_bytes():
    cache = init_cache(CFG8)
    shape = (CFG.n_layers, CFG.n_kv_heads, CFG.n_ctx, CFG.head_dim)
    assert set(cache) == {"k_q", "v_q", "k_s", "v_s"}
    assert cache["k_q"].shape == shape and cache["k_q"].dtype == jnp.int8
    assert cache["k_s"].shape == shape[:-1]
    assert cache["k_s"].dtype == jnp.float32
    # cache_nbytes (the /health figure) equals the live pytree's bytes
    for cfg in (CFG, CFG8):
        live = sum(leaf.nbytes for leaf in jax.tree.leaves(init_cache(cfg)))
        assert cache_nbytes(cfg) == live, cfg.kv_dtype


def test_int8_cache_bytes_at_most_60_percent_of_bf16():
    """THE capacity claim (ISSUE acceptance): same n_ctx, ≤ 0.6x the HBM."""
    ratio = cache_nbytes(CFG8) / cache_nbytes(CFG)
    assert ratio <= 0.6, ratio


def test_bf16_cache_layout_unchanged():
    """Default-path guard: kv_dtype=bf16 keeps the exact two-leaf layout
    (every existing cache consumer — donation, lane writes, sharding specs
    — pattern-matched on it at some point)."""
    cache = init_cache(CFG)
    assert set(cache) == {"k", "v"}
    assert cache["k"].dtype == jnp.bfloat16


# ---------------------------------------------------------------------------
# parity: int8 vs bf16 cache through the model
# ---------------------------------------------------------------------------

def test_int8_logits_close_to_bf16():
    """Prefill logits under the int8 cache stay within a small max-abs
    tolerance of the bf16 cache (per-token symmetric int8 keeps relative
    KV error ≤ 1/254; through 2 layers of this model that stays ~1e-1 on
    O(1)-magnitude logits)."""
    params = synth_params(CFG, fmt="bf16", seed=0)
    tokens = jnp.arange(1, 33, dtype=jnp.int32) % CFG.vocab_size
    lb, _ = forward(params, CFG, tokens, jnp.int32(0), init_cache(CFG),
                    return_all=True)
    l8, _ = forward(params, CFG8, tokens, jnp.int32(0), init_cache(CFG8),
                    return_all=True)
    err = float(jnp.max(jnp.abs(l8 - lb)))
    assert err < 0.15, err


# ---------------------------------------------------------------------------
# engines
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_gguf(tmp_path_factory):
    from llama_fastapi_k8s_gpu_tpu.testing import write_tiny_llama_gguf

    path = str(tmp_path_factory.mktemp("model") / "tiny.gguf")
    write_tiny_llama_gguf(path)
    return path


MSGS = [{"role": "user", "content": "Say something."}]


def test_engine_int8_serves_and_reports_bytes(tiny_gguf):
    from llama_fastapi_k8s_gpu_tpu.engine import Engine

    kw = dict(n_ctx=128, decode_chunk=4, max_gen_tokens=16,
              prefill_buckets=(32, 64, 128))
    eng_b = Engine(tiny_gguf, **kw)
    eng_8 = Engine(tiny_gguf, kv_dtype="int8", **kw)
    assert eng_8.cfg.kv_dtype == "int8"
    assert eng_8.kv_cache_bytes < eng_b.kv_cache_bytes
    out = eng_8.create_chat_completion(MSGS, max_tokens=8, seed=0)
    assert out["usage"]["completion_tokens"] > 0
    # serial prompt-prefix KV reuse (prefill_chunk_jit against the int8
    # cache): a second request sharing the prompt prefix must still serve
    eng_8._prefix_min = 1
    out2 = eng_8.create_chat_completion(MSGS, max_tokens=8)
    assert out2["usage"]["completion_tokens"] > 0


def test_engine_rejects_unknown_kv_dtype(tiny_gguf):
    from llama_fastapi_k8s_gpu_tpu.engine import Engine

    with pytest.raises(ValueError, match="kv_dtype"):
        Engine(tiny_gguf, n_ctx=128, kv_dtype="fp8")


def test_continuous_engine_int8_smoke(tiny_gguf):
    """ContinuousEngine with LFKT_KV_DTYPE=int8: multi-leaf lane writes
    (_write_lane), lane reuse across finished requests, and the lane-prefix
    snapshot path (_lane_cache_copy_jit) all generic over the cache pytree."""
    from llama_fastapi_k8s_gpu_tpu.engine import ContinuousEngine

    eng = ContinuousEngine(
        tiny_gguf, n_ctx=128, decode_chunk=4, max_gen_tokens=16,
        prefill_buckets=(32, 64, 128), batch_size=2, kv_dtype="int8",
        lane_prefix_cache=True, prefill_chunk=16)
    try:
        assert eng.cfg.kv_dtype == "int8"
        # more requests than lanes: finished lanes must be reused
        futs = [eng.submit(MSGS, max_tokens=6, temperature=0.0)
                for _ in range(4)]
        for f in futs:
            out = f.result(timeout=180)
            assert out["usage"]["completion_tokens"] > 0
        # identical prompts + lane_prefix_cache: the snapshot/reuse path
        # (chunk-aligned claims over the int8 pytree) serves another wave
        futs = [eng.submit(MSGS, max_tokens=6, temperature=0.0)
                for _ in range(3)]
        for f in futs:
            assert f.result(timeout=180)["usage"]["completion_tokens"] > 0
    finally:
        eng.shutdown()
