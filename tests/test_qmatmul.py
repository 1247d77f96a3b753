"""Fused Q4_K dequant-matmul kernel vs the dequant-then-matmul oracle.

The kernel must agree with an XLA matmul against ``dequant_ref`` (the same
bf16-folded scales the kernel reads, so tolerances cover only bf16
materialization + f32 accumulation order) and, end to end, with the numpy
Q4_K codec within quantization-noise tolerance.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from llama_fastapi_k8s_gpu_tpu.gguf.quants import dequant_q4_k, quant_q4_k
from llama_fastapi_k8s_gpu_tpu.ops.linear import linear, make_linear_q4k
from llama_fastapi_k8s_gpu_tpu.ops.pallas.qmatmul import (
    dequant_ref,
    permute_x,
    prep_q4k,
    q4k_matmul,
)


def _rand_weights(rng, n, k):
    return (rng.standard_normal((n, k)).astype(np.float32) * (k ** -0.5))


@pytest.mark.parametrize("n,k,b", [
    (8, 2048, 1),       # minimum interpret-mode N tile, decode matvec
    (128, 2048, 4),     # TPU-shaped single k-tile
    (256, 4096, 2),     # full-size tiles, 2 k-steps
    (24, 6144, 3),      # non-power-of-two N (TN=8), 3 k-tiles
])
def test_kernel_matches_dequant_ref(n, k, b):
    rng = np.random.default_rng(n + k)
    w = make_linear_q4k(_rand_weights(rng, n, k))
    x = jnp.asarray(rng.standard_normal((b, k)), jnp.float32)

    ref = permute_x(x).astype(jnp.bfloat16).astype(jnp.float32) @ dequant_ref(w).T
    got = q4k_matmul(x, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-2, atol=2e-2 * float(jnp.abs(ref).max()))


def test_end_to_end_vs_numpy_codec():
    """Against full-precision scales (f16·uint8 exactly, no bf16 folding):
    bf16 scale rounding contributes ~0.4% relative error."""
    rng = np.random.default_rng(0)
    n, k = 64, 2048
    wf = _rand_weights(rng, n, k)
    raw = quant_q4_k(wf.reshape(-1))
    w = prep_q4k(raw, n, k)
    w_deq = dequant_q4_k(raw, n * k).reshape(n, k)

    x = rng.standard_normal((2, k)).astype(np.float32)
    ref = x @ w_deq.T
    got = np.asarray(q4k_matmul(jnp.asarray(x), w))
    np.testing.assert_allclose(got, ref, rtol=3e-2,
                               atol=3e-2 * float(np.abs(ref).max()))


def test_linear_dispatch_routes_q4k():
    rng = np.random.default_rng(1)
    w = make_linear_q4k(_rand_weights(rng, 16, 2048))
    x = jnp.asarray(rng.standard_normal((3, 2048)), jnp.bfloat16)
    y = linear(x, w)
    assert y.shape == (3, 16) and y.dtype == jnp.bfloat16


def test_permute_x_is_a_permutation():
    x = jnp.arange(2048, dtype=jnp.float32)
    p = np.asarray(permute_x(x))
    assert sorted(p.tolist()) == list(range(2048))
    # element-major: column c = e*64 + s holds original element
    # (s//8)*256 + (s%8)*32 + e
    for c in (0, 1, 8, 63, 64, 65, 1024, 2047):
        s, e = c % 64, c // 64
        assert p[c] == (s // 8) * 256 + (s % 8) * 32 + e, c


def test_under_jit_and_scan():
    """The kernel must trace inside jit + lax.scan (the decode loop shape)."""
    rng = np.random.default_rng(2)
    L, n, kdim = 3, 16, 2048
    ws = [make_linear_q4k(_rand_weights(rng, n, kdim)) for _ in range(L)]
    stacked = {key: jnp.stack([w[key] for w in ws]) for key in ws[0]}
    x = jnp.asarray(rng.standard_normal((1, kdim)), jnp.bfloat16)

    @jax.jit
    def f(stacked, x):
        def step(carry, wl):
            y = linear(carry, wl)
            return carry, y

        _, ys = jax.lax.scan(step, x, stacked)
        return ys

    ys = f(stacked, x)
    assert ys.shape == (L, 1, n)
    ref0 = linear(x, ws[0])
    np.testing.assert_allclose(np.asarray(ys[0]), np.asarray(ref0),
                               rtol=1e-2, atol=1e-2)


# ---------------------------------------------------------------------------
# load path: GGUF → fused-layout params (models/params.py fmt="q4k")
# ---------------------------------------------------------------------------

def test_load_params_q4k_mixed_formats(tmp_path):
    """A Q4_K_M-style file (attn Q4_K, ffn Q6_K): Q4_K names load in the
    fused Q4_K layout straight from raw bytes, Q6_K names in the fused Q6_K
    layout (tests/test_q6matmul.py covers that kernel), and the forward
    logits agree with a bf16 load within quantization noise."""
    from llama_fastapi_k8s_gpu_tpu.gguf import GGMLType, GGUFFile
    from llama_fastapi_k8s_gpu_tpu.models.config import ModelConfig
    from llama_fastapi_k8s_gpu_tpu.models.llama import init_cache, prefill
    from llama_fastapi_k8s_gpu_tpu.models.params import load_params
    from llama_fastapi_k8s_gpu_tpu.testing import write_tiny_llama_gguf

    cfg = ModelConfig(vocab_size=263, dim=2048, n_layers=1, n_heads=16,
                      n_kv_heads=8, ffn_dim=2048, n_ctx=32)
    path = str(tmp_path / "q4k.gguf")
    cfg = write_tiny_llama_gguf(path, cfg=cfg, quant=GGMLType.Q4_K,
                                ffn_quant=GGMLType.Q6_K)
    gf = GGUFFile(path)
    params = load_params(gf, cfg, fmt="q4k", on_device=False)
    # attn linears fused Q4_K, ffn fused Q6_K
    assert "qs" in params["layers"]["wq"] and "sm" in params["layers"]["wq"]
    assert "q4" in params["layers"]["w_gate"]

    ref = load_params(gf, cfg, fmt="bf16", on_device=False)
    toks = jnp.arange(1, 9, dtype=jnp.int32)
    lg_q, _ = prefill(params, cfg, toks, jnp.int32(8), init_cache(cfg))
    lg_r, _ = prefill(ref, cfg, toks, jnp.int32(8), init_cache(cfg))
    a, b = np.asarray(lg_q), np.asarray(lg_r)
    denom = np.abs(b).max() + 1e-6
    assert np.abs(a - b).max() / denom < 0.08, np.abs(a - b).max() / denom


# fused-kernel GSPMD rules (custom_partitioning in ops/pallas/q*matmul.py):
# planes sharded over N on a plain ``jax.sharding.Mesh`` must compute
# locally and match the unsharded result.  The package has no mesh of its
# own; the wrappers stay because they are in every cell's traced program
# (ROADMAP C3).

@pytest.mark.parametrize("maker_name", ["q4k", "q5k", "q6k", "q8", "q6k-pre"])
def test_fused_matmul_partitioned_matches_unsharded(maker_name, monkeypatch):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from llama_fastapi_k8s_gpu_tpu.ops import (
        make_linear_q5k,
        make_linear_q6k,
        make_linear_q8,
    )

    if maker_name == "q6k-pre":
        monkeypatch.setenv("LFKT_Q6K_KERNEL", "pre")
    maker = {"q4k": make_linear_q4k, "q5k": make_linear_q5k,
             "q6k": make_linear_q6k, "q6k-pre": make_linear_q6k,
             "q8": make_linear_q8}[maker_name]
    rng = np.random.default_rng(5)
    wf = rng.standard_normal((256, 2048)).astype(np.float32) * 2048 ** -0.5
    w = maker(wf)
    x = jnp.asarray(rng.standard_normal((3, 2048)), jnp.bfloat16)
    ref = np.asarray(linear(x, w).astype(jnp.float32))

    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    # quantized planes (N, K/x) shard their output dim N; scale tables
    # (kt, N, 128) shard N in the middle
    ws = jax.device_put(w, {
        k: NamedSharding(mesh, P("tp", None) if w[k].ndim == 2
                         else P(None, "tp", None)) for k in w})
    assert any(len(v.sharding.device_set) == 2 for v in ws.values())
    got = jax.jit(linear)(x, ws)
    np.testing.assert_allclose(np.asarray(got.astype(jnp.float32)), ref,
                               rtol=2e-2, atol=2e-2 * np.abs(ref).max())


def test_shipped_kernel_defaults_are_the_measured_configuration():
    """The tuple heads are a MEASURED decision, not style: the 2026-08-01
    chip A/B banked 72.32 tok/s with exactly q4k=resplit (the Q6_K float
    bodies it ran beside went in PR 64: ``LFKT_Q6K_KERNEL`` names a layout)
    (docs/bench/bench_q4km_variant_ab_2026-08-01.json, confirmed bare-env
    by bench_q4km_postflip_2026-08-01.json).  A reorder silently changes
    the shipped default (_env_variant takes allowed[0]) and detaches the
    headline claim from its artifact — flip only with a new banked A/B."""
    from llama_fastapi_k8s_gpu_tpu.ops.pallas.q5matmul import Q5K_VARIANTS
    from llama_fastapi_k8s_gpu_tpu.ops.pallas.q6matmul import Q6K_LAYOUTS
    from llama_fastapi_k8s_gpu_tpu.ops.pallas.qmatmul import Q4K_VARIANTS

    assert Q4K_VARIANTS[0] == "resplit"
    assert Q6K_LAYOUTS == ("split", "pre")   # the packed planes the cells load
    # q5k=pre since the 2026-08-01 q5km A/B: 63.09 vs 52.27 tok/s
    # (bench_q5km_pre_2026-08-01.json vs bench_q5km_2026-08-01.json,
    # kernel_microbench_q5kpre_2026-08-01.json)
    assert Q5K_VARIANTS[0] == "pre"


def test_resplit_variant_bit_identical(monkeypatch):
    """LFKT_Q4K_KERNEL=resplit (the shipped default since the 2026-08-01
    chip A/B) must produce BIT-identical output to `cur`: its
    lsc = v*sc - 16*(h*sc) cancellation is exact in f32.  Both sides pin
    the variant explicitly so the assertion stays cur-vs-resplit whatever
    the default ordering of Q4K_VARIANTS."""
    import numpy as np

    from llama_fastapi_k8s_gpu_tpu.gguf.quants import quant_q4_k
    from llama_fastapi_k8s_gpu_tpu.ops.pallas.qmatmul import prep_q4k, q4k_matmul

    from llama_fastapi_k8s_gpu_tpu.ops.pallas import qmatmul as qm

    rng = np.random.default_rng(0)
    n, k = 64, 2048
    w = (rng.standard_normal((n, k)) * 0.05).astype(np.float32)
    wd = prep_q4k(quant_q4_k(w.reshape(-1)), n, k)
    x = jnp.asarray(rng.standard_normal((4, k)), jnp.bfloat16)
    # the variant is part of the builder cache key, so flipping the env
    # between calls re-traces without any cache_clear choreography
    monkeypatch.setenv("LFKT_Q4K_KERNEL", "cur")
    a = np.asarray(q4k_matmul(x, wd, interpret=True))
    monkeypatch.setenv("LFKT_Q4K_KERNEL", "resplit")
    b = np.asarray(q4k_matmul(x, wd, interpret=True))
    assert np.array_equal(a, b)


def test_onedot_variant_matches_default(monkeypatch):
    """LFKT_Q4K_KERNEL=onedot computes the same bf16 planes as the default
    but sums one 2048-length dot where the default sums two 1024-length
    dots, so f32 accumulation ORDER differs — same products, near-equal
    sums (1e-6, vs the 2e-2 quantization tolerance), not bit-identity."""
    from llama_fastapi_k8s_gpu_tpu.gguf.quants import quant_q4_k
    from llama_fastapi_k8s_gpu_tpu.ops.pallas.qmatmul import prep_q4k, q4k_matmul

    rng = np.random.default_rng(3)
    n, k = 64, 2048
    w = (rng.standard_normal((n, k)) * 0.05).astype(np.float32)
    wd = prep_q4k(quant_q4_k(w.reshape(-1)), n, k)
    x = jnp.asarray(rng.standard_normal((4, k)), jnp.bfloat16)
    monkeypatch.setenv("LFKT_Q4K_KERNEL", "cur")
    a = np.asarray(q4k_matmul(x, wd, interpret=True))
    monkeypatch.setenv("LFKT_Q4K_KERNEL", "onedot")
    b = np.asarray(q4k_matmul(x, wd, interpret=True))
    np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)


def test_vbf32_variant_beats_default_accuracy(monkeypatch):
    """LFKT_Q4K_KERNEL=vbf32 recombines nibbles on the activation side with
    f32 planes.  The rejected bf16-plane `vb` ablation blew up to 3.3% rms
    (16×-magnitude bf16 terms cancelling); the f32-plane variant must show
    NO such blowup: at least as close to the f32 dequant_ref oracle as the
    bf16-plane default (whose plane rounding it eliminates — the residual
    both share is the bf16 corr/xsum path)."""
    from llama_fastapi_k8s_gpu_tpu.gguf.quants import quant_q4_k
    from llama_fastapi_k8s_gpu_tpu.ops.pallas.qmatmul import prep_q4k, q4k_matmul

    rng = np.random.default_rng(5)
    n, k = 64, 4096
    w = (rng.standard_normal((n, k)) * 0.05).astype(np.float32)
    wd = prep_q4k(quant_q4_k(w.reshape(-1)), n, k)
    x = jnp.asarray(rng.standard_normal((4, k)), jnp.float32)
    ref = np.asarray(
        permute_x(x).astype(jnp.bfloat16).astype(jnp.float32) @ dequant_ref(wd).T)
    monkeypatch.setenv("LFKT_Q4K_KERNEL", "cur")
    cur = np.asarray(q4k_matmul(x, wd, interpret=True))
    monkeypatch.setenv("LFKT_Q4K_KERNEL", "vbf32")
    got = np.asarray(q4k_matmul(x, wd, interpret=True))
    err_cur = np.abs(cur - ref).max()
    err_vb = np.abs(got - ref).max()
    assert err_vb <= err_cur * 1.05, (err_vb, err_cur)
    np.testing.assert_allclose(got, ref, rtol=2e-2,
                               atol=2e-2 * float(np.abs(ref).max()))


# ---------------------------------------------------------------------------
# the grouped expert calls' integer body (PR 61): the float bodies' planes
# ---------------------------------------------------------------------------

#: effective scales (d·sc as bfloat16) the planes are compared under: zero,
#: the smallest subnormal, one that makes subnormal products, ordinary ones,
#: 2**120 (the largest whose 128-fold is finite in float32, up to which
#: ``resplit``'s cancellation is exact) and the largest bfloat16 (whose
#: products overflow to an infinity in ``cur`` and in the integer body alike,
#: and to a NaN in ``resplit``); a negative one, which no file holds
_SCALES = {"zero": 0.0, "subnormal": 9.1835e-41, "tiny": 2.0 ** -130,
           "small": 0.0123, "one": 1.0, "odd": 0.037109375,
           "resplit_max": 2.0 ** 120, "largest": 3.3895e38, "negative": -0.0123}


class _Block:
    """What a kernel body reads of a ref (``.shape``, ``[...]``), over an
    array: the body then runs op by op outside a ``pallas_call``."""

    def __init__(self, a):
        self._a = a
        self.shape = a.shape

    def __getitem__(self, idx):
        return self._a[idx]


def _every_byte_under_every_scale():
    """(qs (16, 1024) int8, sm (1, 16, 128) bfloat16, the scale's name by
    packed column): row ``n``, byte column ``e * 64 + s`` holds the byte
    ``16 n + e - 128`` under sub-block ``s``'s scale, the ``s % 9``-th of
    :data:`_SCALES`, so every byte value meets every scale in both halves."""
    from llama_fastapi_k8s_gpu_tpu.ops.pallas import qmatmul as qm

    n, b = np.arange(16)[:, None], np.arange(qm.TK // 2)[None, :]
    qs = (16 * n + b // 64 - 128).astype(np.int8)
    names = [list(_SCALES)[s % len(_SCALES)] for s in range(64)]
    sc = np.asarray([_SCALES[name] for name in names], np.float32)
    mn = np.random.default_rng(0).random(64).astype(np.float32)
    sm = jnp.asarray(np.tile(np.concatenate([sc, mn]), (1, 16, 1)),
                     jnp.bfloat16)
    assert sorted(set(qs.reshape(-1).tolist())) == list(range(-128, 128))
    return jnp.asarray(qs), sm, np.asarray(names)[np.arange(1024) % 64]


def _float_body_planes(qs, sm, variant, monkeypatch):
    """``_q4k_matmul_kernel``'s ``a_lo`` / ``a_hi`` under ``variant``: the
    weight operands of its first two dots, the body run op by op."""
    from llama_fastapi_k8s_gpu_tpu.ops.pallas import qmatmul as qm

    seen, dot = [], jax.lax.dot_general
    with monkeypatch.context() as m:
        m.setattr(jax.lax, "dot_general",
                  lambda a, b, *r, **kw: (seen.append(b), dot(a, b, *r, **kw)
                                          )[1])
        qm._q4k_matmul_kernel(
            _Block(jnp.zeros((16, qm.TKA), jnp.bfloat16)), _Block(qs),
            _Block(sm), None, interpret=True, variant=variant,
            accum=lambda o_ref, part: None)
    assert len(seen) == 3 and seen[2].shape == (16, 128)
    return [np.asarray(a) for a in seen[:2]]


def _int_body_planes(qs, sm):
    """``_q4k_int_planes`` in a ``pallas_call`` of its own (the bitcasts are
    Mosaic's), under the scales as ``_q4k_tile_product`` spreads them."""
    from jax.experimental import pallas as pl

    from llama_fastapi_k8s_gpu_tpu.ops.pallas import qmatmul as qm

    def kernel(qs_ref, sm_ref, lo_ref, hi_ref):
        sc = sm_ref[0][:, :64]
        lo_ref[...], hi_ref[...] = qm._q4k_int_planes(
            qs_ref[...], qm._lane_repeat(jnp.concatenate([sc, sc], axis=1),
                                         qm.TK // 256, True))

    plane = jax.ShapeDtypeStruct(qs.shape, jnp.bfloat16)
    return [np.asarray(a) for a in pl.pallas_call(
        kernel, out_shape=[plane, plane], interpret=True)(qs, sm)]


@pytest.mark.parametrize("variant", ["cur", "resplit"])
@pytest.mark.parametrize("half", ["lo", "hi"])
@pytest.mark.parametrize("scale", list(_SCALES))
def test_the_integer_bodys_planes_are_the_float_bodys_bit_for_bit(
        monkeypatch, variant, half, scale):
    """Every byte value -128..127 under every scale of :data:`_SCALES`: the
    bfloat16 plane ``_q4k_int_planes`` builds from integer operations on the
    packed bytes is ``_q4k_matmul_kernel``'s, BIT for bit (``cur`` at every
    scale; ``resplit`` wherever its own cancellation is exact: not past
    2**120, where it makes a NaN of an overflow, and up to the sign of a
    zero under a negative scale, which no file holds)."""
    qs, sm, names = _every_byte_under_every_scale()
    i = ["lo", "hi"].index(half)
    want = _float_body_planes(qs, sm, variant, monkeypatch)[i][:, names == scale]
    got = _int_body_planes(qs, sm)[i][:, names == scale]
    assert got.shape == (16, 16 * list(names[:64]).count(scale))
    digits = np.asarray(qs)[:, names == scale].astype(np.int32)
    digits = digits & 15 if half == "lo" else digits >> 4
    if scale not in ("subnormal", "tiny"):  # (a backend may flush those)
        with np.errstate(over="ignore"):
            exact = np.float32(jnp.bfloat16(_SCALES[scale])) * digits.astype(
                np.float32)
            assert np.array_equal(got.astype(np.float32), np.asarray(
                jnp.asarray(exact, jnp.bfloat16), np.float32))
    if variant == "resplit" and scale == "largest" and half == "lo":
        assert np.isnan(want.astype(np.float32)).any()  # inf - inf
        return
    if variant == "resplit" and scale == "negative":
        assert np.array_equal(got, want)                # -0.0 == 0.0
        return
    assert np.array_equal(got.view(np.uint16), want.view(np.uint16))


# jax.make_jaxpr's text (kernel bodies in full), hashed on the parent
# (4165a65, PR 60) with tools/traced_program_hashes.py: the dense Q4_K calls
# (stacked and plain: a decode row, a lane step's rows, a slice, interpret
# mode), which keep ``_q4k_matmul_kernel``, and the grouped Q6_K calls, which
# share ``experts._grouped_call`` with the Q4_K ones and keep their text ...
PARENT_KEPT = {
    "stacked.q4k.4096x4096.r1.tpu": "757be23bc0c08802",
    "stacked.q4k.4096x14336.r8.tpu": "e6d8d73c15097442",
    "stacked.q4k.14336x4096.r512.tpu": "388f9ab71a8970ce",
    "dense.q4k.4096x1024.r1.tpu": "14c905113773f740",
    "stacked.q4k.4096x4096.r128.interp": "bcfef6776fda52df",
    "grouped.q6k.lfm2.few": "fcaee95a3fa2677d",
    "grouped.q6k.gigachat.few": "cfffd7c027efa916",
    "grouped.q6k.olmoe.many": "7cf0bfd85f58cdb2",
    "grouped.q6k.longcat.many": "fb783bd9fec9941f",
}
# ... and the grouped Q4_K calls, which held that float body and hold the
# integer one under a tile of their own since PR 61
PARENT_MOVED = {
    "grouped.q4k.lfm2.few": "ac531b3e69997cfa",
    "grouped.q4k.olmoe.few": "e258b6ed94a83a3e",
    "grouped.q4k.gigachat.few": "b95faa0781ed9d3c",
    "grouped.q4k.gigachat.many": "eaf88a777236eb0c",
    "grouped.q4k.kexaone.many": "4eb31323dbcaba60",
}


@pytest.fixture(scope="module")
def traced_hashes():
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "traced_program_hashes", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools", "traced_program_hashes.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    return tool.hashes(only={**PARENT_KEPT, **PARENT_MOVED}.__contains__)


@pytest.mark.parametrize("key", list(PARENT_KEPT))
def test_dense_q4k_and_grouped_q6k_calls_trace_to_the_text_they_had(
        traced_hashes, key):
    assert traced_hashes[key] == PARENT_KEPT[key]


@pytest.mark.parametrize("key", list(PARENT_MOVED))
def test_grouped_q4k_calls_no_longer_trace_to_the_float_body(traced_hashes,
                                                             key):
    assert traced_hashes[key] != PARENT_MOVED[key]
