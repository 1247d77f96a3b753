"""Native C++ dequant library vs the numpy reference codecs — bit-exact.

The numpy implementations in gguf/quants.py are the oracle (they in turn are
validated against hand-built GGUF fixtures in test_gguf_quants.py); the C++
library (native/src/gguf_dequant.cpp) must reproduce them to the last bit,
including f16 subnormals/inf/nan and multi-threaded block splits.
"""

from __future__ import annotations

import numpy as np
import pytest

from llama_fastapi_k8s_gpu_tpu.gguf import quants
from llama_fastapi_k8s_gpu_tpu.gguf.constants import GGML_BLOCK_SIZES, GGMLType
from llama_fastapi_k8s_gpu_tpu.native import get_lib, native_dequantize

pytestmark = pytest.mark.skipif(
    get_lib() is None, reason="native library unavailable (no C++ toolchain)"
)

QUANT_TYPES = [
    GGMLType.Q8_0,
    GGMLType.Q4_0,
    GGMLType.Q4_K,
    GGMLType.Q5_K,
    GGMLType.Q6_K,
]


def _random_blocks(rng, ggml_type, n_blocks):
    _, block_bytes = GGML_BLOCK_SIZES[ggml_type]
    return rng.integers(0, 256, size=n_blocks * block_bytes, dtype=np.uint8)


@pytest.mark.parametrize("ggml_type", QUANT_TYPES)
@pytest.mark.parametrize("n_blocks", [1, 3, 64, 1024])
def test_quant_bit_exact_random_bytes(ggml_type, n_blocks):
    """Random raw bytes (arbitrary f16 scales incl. inf/nan patterns)."""
    rng = np.random.default_rng(int(ggml_type) * 1000 + n_blocks)
    block_elems, _ = GGML_BLOCK_SIZES[ggml_type]
    buf = _random_blocks(rng, ggml_type, n_blocks)
    n = n_blocks * block_elems
    ref = quants.DEQUANT[ggml_type](buf, n)
    got = native_dequantize(buf, int(ggml_type), n)
    assert got is not None
    assert got.dtype == np.float32
    np.testing.assert_array_equal(
        got.view(np.uint32), ref.astype(np.float32).view(np.uint32)
    )


@pytest.mark.parametrize("ggml_type", QUANT_TYPES)
def test_quant_roundtrip_bit_exact(ggml_type):
    """Realistic buffers produced by the in-tree quantizers."""
    rng = np.random.default_rng(7)
    block_elems, _ = GGML_BLOCK_SIZES[ggml_type]
    x = rng.standard_normal(block_elems * 37).astype(np.float32)
    buf = quants.QUANT[ggml_type](x)
    ref = quants.DEQUANT[ggml_type](buf, x.size)
    got = native_dequantize(buf, int(ggml_type), x.size)
    np.testing.assert_array_equal(got.view(np.uint32), ref.view(np.uint32))


@pytest.mark.parametrize(
    "ggml_type,width",
    [(GGMLType.F32, 4), (GGMLType.F16, 2), (GGMLType.BF16, 2)],
)
def test_float_formats_bit_exact(ggml_type, width):
    rng = np.random.default_rng(int(ggml_type))
    n = 100_003  # odd size exercises thread-split remainders
    buf = rng.integers(0, 256, size=n * width, dtype=np.uint8)
    ref = quants.DEQUANT[ggml_type](buf, n)
    got = native_dequantize(buf, int(ggml_type), n)
    np.testing.assert_array_equal(got.view(np.uint32), ref.astype(np.float32).view(np.uint32))


def test_f16_all_values_exact():
    """Every one of the 65536 f16 bit patterns converts exactly like numpy."""
    all_bits = np.arange(65536, dtype=np.uint16)
    buf = all_bits.view(np.uint8)
    ref = all_bits.view(np.float16).astype(np.float32)
    got = native_dequantize(buf, int(GGMLType.F16), 65536)
    np.testing.assert_array_equal(got.view(np.uint32), ref.view(np.uint32))


def test_single_thread_matches_multi_thread():
    rng = np.random.default_rng(0)
    buf = _random_blocks(rng, GGMLType.Q4_K, 512)
    n = 512 * 256
    a = native_dequantize(buf, int(GGMLType.Q4_K), n, n_threads=1)
    b = native_dequantize(buf, int(GGMLType.Q4_K), n, n_threads=8)
    np.testing.assert_array_equal(a.view(np.uint32), b.view(np.uint32))


def test_unsupported_type_falls_back():
    assert native_dequantize(np.zeros(8, np.uint8), int(GGMLType.Q2_K), 256) is None


def test_short_buffer_falls_back_not_oob():
    """A truncated buffer must refuse the native path (numpy raises cleanly)."""
    buf = np.zeros(143, np.uint8)  # one Q4_K block needs 144 bytes
    assert native_dequantize(buf, int(GGMLType.Q4_K), 256) is None
    with pytest.raises(ValueError):
        quants.dequantize(buf, GGMLType.Q4_K, 256)


def test_dispatch_uses_native(monkeypatch):
    """quants.dequantize routes through the native path when enabled."""
    calls = {}
    import llama_fastapi_k8s_gpu_tpu.native as native_mod

    real = native_mod.native_dequantize

    def spy(buf, t, n, n_threads=0):
        calls["hit"] = True
        return real(buf, t, n, n_threads)

    monkeypatch.setattr(native_mod, "native_dequantize", spy)
    x = np.random.default_rng(1).standard_normal(256).astype(np.float32)
    buf = quants.QUANT[GGMLType.Q4_K](x)
    out = quants.dequantize(buf, GGMLType.Q4_K, 256)
    assert calls.get("hit") and out.shape == (256,)


# ---------------------------------------------------------------------------
# fused-layout packers (prep_q4k/q5k/q6k/q8_0): C++ vs the numpy reference
# ---------------------------------------------------------------------------

def _packer_case(kind):
    """(pallas module, numpy-ref fn name, native fn name, quant codec,
    GGMLType) for each fused format."""
    from llama_fastapi_k8s_gpu_tpu.ops.pallas import (
        q5matmul, q6matmul, q8matmul, qmatmul,
    )

    return {
        "q4k": (qmatmul, "prep_q4k", "native_prep_q4k",
                quants.quant_q4_k, GGMLType.Q4_K),
        "q5k": (q5matmul, "prep_q5k", "native_prep_q5k",
                quants.quant_q5_k, GGMLType.Q5_K),
        "q6k": (q6matmul, "prep_q6k", "native_prep_q6k",
                quants.quant_q6_k, GGMLType.Q6_K),
        "q8_0": (q8matmul, "prep_q8_0", "native_prep_q8_0",
                 quants.quant_q8_0, GGMLType.Q8_0),
    }[kind]


@pytest.mark.parametrize("raw_kind", ["codec", "random_bytes"])
@pytest.mark.parametrize("kind", ["q4k", "q5k", "q6k", "q8_0"])
@pytest.mark.parametrize("n,k", [(128, 2048), (8, 4096)])
def test_prep_bit_exact(monkeypatch, kind, raw_kind, n, k):
    """The threaded C++ packers must reproduce the numpy reference chains
    bit-for-bit: int planes exactly, bf16 scale planes including the
    NaN/inf f16 scale patterns random raw bytes produce (pins bf16_rne's
    sign-preserving quiet-NaN canonicalization against XLA's cast)."""
    import llama_fastapi_k8s_gpu_tpu.native as native_mod

    # the C++ packers' contract is the SPLIT planes; prep_* may layer a
    # `pre` combined-plane layout on top under its env default (Q5_K since
    # the 2026-08-01 A/B), so pin the split layout for the comparison
    monkeypatch.setenv("LFKT_Q5K_KERNEL", "cur")
    monkeypatch.setenv("LFKT_Q6K_KERNEL", "split")
    module, ref_name, nat_name, codec, gtype = _packer_case(kind)
    rng = np.random.default_rng(hash((kind, raw_kind, n, k)) % 2**32)
    if raw_kind == "codec":
        raw = codec((rng.standard_normal(n * k) * 0.05).astype(np.float32))
    else:
        _, block_bytes = GGML_BLOCK_SIZES[gtype]
        block_elems = GGML_BLOCK_SIZES[gtype][0]
        raw = rng.integers(0, 256, size=(n * k // block_elems) * block_bytes,
                           dtype=np.uint8)
    nat = getattr(native_mod, nat_name)(raw, n, k)
    assert nat is not None
    monkeypatch.setattr(native_mod, nat_name, lambda *a, **kw: None)
    ref = getattr(module, ref_name)(raw, n, k)
    assert sorted(nat) == sorted(ref)
    for key in nat:
        a, b = nat[key], np.asarray(ref[key])
        if a.dtype == np.int8:
            assert np.array_equal(a, b), (kind, key)
        else:
            assert np.array_equal(a.view(np.uint16), b.view(np.uint16)), \
                (kind, key)


@pytest.mark.parametrize("raw_kind", ["codec", "random_bytes"])
@pytest.mark.parametrize("kind", ["q4k", "q6k"])
@pytest.mark.parametrize("n,k", [(128, 2560), (8, 5120)])
def test_prep_tail_bit_exact(monkeypatch, kind, raw_kind, n, k):
    """A K that ends in a TAIL tile (ops/pallas/qmatmul.py ``tail_of``: 2560
    = 2048 + 512, 5120 = 2 x 2048 + 1024): the C++ packers lay the whole
    tiles' planes and the tail's out as the numpy chains do, byte for byte,
    the tiled scales of the tail included."""
    import llama_fastapi_k8s_gpu_tpu.native as native_mod

    monkeypatch.setenv("LFKT_Q6K_KERNEL", "split")
    module, ref_name, nat_name, codec, gtype = _packer_case(kind)
    rng = np.random.default_rng(hash((kind, raw_kind, n, k)) % 2**32)
    if raw_kind == "codec":
        raw = codec((rng.standard_normal(n * k) * 0.05).astype(np.float32))
    else:
        block_elems, block_bytes = GGML_BLOCK_SIZES[gtype]
        raw = rng.integers(0, 256, size=(n * k // block_elems) * block_bytes,
                           dtype=np.uint8)
    nat = getattr(native_mod, nat_name)(raw, n, k)
    assert nat is not None
    monkeypatch.setattr(native_mod, nat_name, lambda *a, **kw: None)
    ref = getattr(module, ref_name)(raw, n, k)
    tail = {"q4k": {"qs_t", "sm_t"}, "q6k": {"q4_t", "q2_t", "sm6_t"}}[kind]
    assert sorted(nat) == sorted(ref) and tail <= set(nat)
    assert nat[{"q4k": "qs_t", "q6k": "q4_t"}[kind]].shape == (
        n, (k % 2048) // 2)
    for key in nat:
        a, b = nat[key], np.asarray(ref[key])
        assert a.shape == b.shape, (kind, key)
        if a.dtype == np.int8:
            assert np.array_equal(a, b), (kind, key)
        else:
            assert np.array_equal(a.view(np.uint16), b.view(np.uint16)), \
                (kind, key)
