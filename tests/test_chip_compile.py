"""Ask the TPU's compiler, without a chip: every Pallas kernel of the
serving path is COMPILED (``interpret=False`` passed explicitly) for a
described ``v5e:2x2`` device at the Llama-3-8B shapes.  Interpret mode — all
the rest of tier-1 — cannot see a slice that is not aligned to the tiling or
a kernel that wants more fast memory than it may use; this file can
(/opt/skills/guides/on-chip-measurement §2, rehearsal 3).

Rules of this file (they are what keeps the suite countable under
``pytest -n 6 --dist loadfile``): the topology is described inside a
module-scoped fixture, never while a module is imported; parametrize takes
literal tuples only; every compile runs in the test's own process; all of
it lives in this ONE file.  A compile that passes is not a chip run."""

from __future__ import annotations

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

bf16, i8, f32, i32 = jnp.bfloat16, jnp.int8, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever stops the description
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """A compile for a described device is written to the persistent cache
    but cannot be read back without a chip; keep it out of the way."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(one_chip, fn, *shapes):
    """jit → lower → compile ``fn`` on the described chip; returns the
    compiled program's text."""
    def place(s):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)

    args = jax.tree.map(place, shapes)
    return jax.jit(fn, keep_unused=True).lower(*args).compile().as_text()


def S(*shape, dtype=bf16):
    return jax.ShapeDtypeStruct(shape, dtype)


def _planes(fmt, n, k, layers=None):
    """A fused layout's planes at K ``k`` as stored: whole 2048 tiles, and
    for Q4_K / Q6_K the tail tile's planes beside them where ``k`` ends in
    one (ops/pallas/qmatmul.py ``tail_of``: 2560, 5120)."""
    from llama_fastapi_k8s_gpu_tpu.ops.pallas.qmatmul import tail_of

    tail = tail_of(k) if fmt in ("q4k", "q6k") else 0
    k -= tail
    kt = k // 2048
    lead = () if layers is None else (layers,)
    sm = S(*lead, kt, n, 128)
    sm_t = S(*lead, 1, n, 128)
    if fmt == "q4k":
        return {"qs": S(*lead, n, k // 2, dtype=i8), "sm": sm,
                **({"qs_t": S(*lead, n, tail // 2, dtype=i8), "sm_t": sm_t}
                   if tail else {})}
    if fmt == "q6k":
        return {"q4": S(*lead, n, k // 2, dtype=i8),
                "q2": S(*lead, n, k // 4, dtype=i8), "sm6": sm,
                **({"q4_t": S(*lead, n, tail // 2, dtype=i8),
                    "q2_t": S(*lead, n, tail // 4, dtype=i8),
                    "sm6_t": sm_t} if tail else {})}
    if fmt == "q5k":
        return {"q5s": S(*lead, n, k // 2, dtype=i8),
                "q5h": S(*lead, n, k // 8, dtype=i8), "sm5": sm}
    return {"q8": S(*lead, n, k, dtype=i8), "sm8": sm}


def _slice_widths(cfg):
    """The prefill slice widths an engine at the default narrow width cuts
    this block's prompts into: narrow, and the wide one where the block
    takes it (engine/slices.py)."""
    from llama_fastapi_k8s_gpu_tpu.engine.slices import wide_width
    from llama_fastapi_k8s_gpu_tpu.models.cache import cache_of

    return sorted({256, wide_width(256, cache_of(cfg).widest_slice(cfg))})


def _matmuls(fmt):
    from llama_fastapi_k8s_gpu_tpu.ops import pallas as P

    return {"q4k": (P.q4k_matmul, P.q4k_matmul_stacked),
            "q5k": (P.q5k_matmul, P.q5k_matmul_stacked),
            "q6k": (P.q6k_matmul, P.q6k_matmul_stacked),
            "q8": (P.q8_matmul, P.q8_matmul_stacked)}[fmt]


# the Q4_K_M tensor mix of Llama-3-8B (Q4_K and Q6_K) at every linear shape
# of a layer, the Q6_K output head, and the Q5_K / Q8_0 kernels of BASELINE
# config #3 — (format, k_in, n_out)
@pytest.mark.parametrize("fmt,k,n", [
    ("q4k", 4096, 4096), ("q4k", 4096, 1024), ("q4k", 4096, 14336),
    ("q4k", 14336, 4096),
    ("q6k", 4096, 4096), ("q6k", 4096, 1024), ("q6k", 4096, 14336),
    ("q6k", 14336, 4096), ("q6k", 4096, 128256),
    ("q5k", 4096, 4096), ("q5k", 4096, 14336),
    ("q8", 4096, 4096), ("q8", 4096, 14336),
    # EvaByte's feed-forward width: gate and up, and ffn_down's K = 11008
    # as it is stored, its last tile filled up to 12288 (ops/linear.py
    # padded_k)
    ("q4k", 4096, 11008), ("q6k", 12288, 4096),
    # the widest heads served (K-EXAONE's; GigaChat's, K 7168 filled up to
    # 8192): the head's own call at three and four K tiles a grid step
    ("q6k", 6144, 153600), ("q6k", 8192, 128256),
])
def test_fused_matmul_compiles(one_chip, fmt, k, n):
    """Unstacked and stacked, one decode row, a 512-row prefill bucket and
    a 1024-row wide slice (more than 256 rows: ONE call of one row block
    under the N tile and the VMEM limit of its own).  The compiled
    program must still hold the kernel — this is also where
    ``_lane_repeat``'s ``pltpu.repeat`` branch meets the compiler."""
    from llama_fastapi_k8s_gpu_tpu.ops.pallas.qmatmul import q4k_compatible

    assert q4k_compatible(n, k, for_tpu=True)
    plain, stacked = _matmuls(fmt)
    for rows in (1, 512, 1024):
        txt = _compile(one_chip, lambda x, w: plain(x, w, interpret=False),
                       S(rows, k), _planes(fmt, n, k))
        assert "tpu_custom_call" in txt
        if n >= 128256:          # the head is never stacked
            continue
        txt = _compile(
            one_chip, lambda x, w, i: stacked(x, w, i, interpret=False),
            S(rows, k), _planes(fmt, n, k, layers=2), S(dtype=i32))
        assert "tpu_custom_call" in txt


# the fused Q6_K heads of the benchmark's configurations (k_in, n_out):
# K-EXAONE, GigaChat (K 7168 filled up to 8192), LongCat, Mistral / SOLAR,
# OLMoE (50304 = 131 x 384: an N tile of 384) and Ouro (one K tile)
@pytest.mark.parametrize("k,n", [
    (6144, 153600), (8192, 128256), (6144, 131072), (4096, 32000),
    (2048, 50304), (2048, 49152)])
def test_head_call_compiles_at_the_rows_of_a_lane_step(one_chip, k, n):
    """The head's own call (ops/pallas/q6matmul.py ``_q6k_2d_raw``) under
    its wide tiling, all of K a grid step, at the rows of a decode step of
    16, 128 and 256 lanes: the tallest call that takes that tiling."""
    from llama_fastapi_k8s_gpu_tpu.ops.pallas import q6k_matmul

    for rows in (16, 128, 256):
        txt = _compile(one_chip, lambda x, w: q6k_matmul(x, w, interpret=False),
                       S(rows, k), _planes("q6k", n, k))
        assert "tpu_custom_call" in txt


# the dense block at the published widths of the two configurations with
# long-prompt cells, in the Q4_K_M mix as the chip serves it (fused planes):
# (name, layers, ffn width, vocabulary, n_ctx)
@pytest.mark.parametrize("name,L,F,V,n_ctx", [
    ("solar-serial", 48, 14336, 32000, 4096),
    ("mistral-8lane", 32, 14336, 32000, 4096),
])
def test_wide_prefill_slice_of_the_dense_block_compiles(one_chip, monkeypatch,
                                                        name, L, F, V, n_ctx):
    """The prefill slice program of the dense block over FUSED Q4_K / Q6_K
    planes compiles for the chip at the narrow width and at the wide one
    (engine/slices.py): every fused matmul of the wide slice is ONE many-row
    call of 1024 rows, none is cut into 256-row calls."""
    import re

    from llama_fastapi_k8s_gpu_tpu.models.config import ModelConfig
    from llama_fastapi_k8s_gpu_tpu.models.generate import prefill_chunk_jit
    from llama_fastapi_k8s_gpu_tpu.models.llama import init_cache
    from llama_fastapi_k8s_gpu_tpu.ops import pallas as pallas_ops

    # a whole-program compile on a CPU host would lower every kernel in
    # interpret form: the chip's form is what is asked about
    monkeypatch.setattr(pallas_ops, "use_interpret", lambda: False)
    D, H, KV = 4096, 32, 8
    cfg = ModelConfig(vocab_size=V, dim=D, n_layers=L, n_heads=H,
                      n_kv_heads=KV, ffn_dim=F, n_ctx=n_ctx, rope_theta=1e4,
                      attn_impl="pallas")

    def place(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    kv = KV * (D // H)
    params = place({
        "tok_emb": S(V, D), "out_norm": S(D, dtype=f32),
        "output": _planes("q6k", V, D),
        "layers": {
            "attn_norm": S(L, D, dtype=f32), "ffn_norm": S(L, D, dtype=f32),
            "wq": _planes("q4k", D, D, L), "wk": _planes("q4k", kv, D, L),
            "wv": _planes("q6k", kv, D, L), "wo": _planes("q4k", D, D, L),
            "w_gate": _planes("q4k", F, D, L), "w_up": _planes("q4k", F, D, L),
            "w_down": _planes("q6k", D, F, L)}})
    cache = place(jax.eval_shape(lambda: init_cache(cfg)))
    assert _slice_widths(cfg) == [256, 1024]
    for rows in _slice_widths(cfg):
        text = prefill_chunk_jit.__wrapped__.lower(
            params, cfg, place(S(rows, dtype=i32)), place(S(dtype=i32)),
            place(S(dtype=i32)), cache).compile().as_text()
        # the layer's seven matmuls, each one call whose row block is the
        # whole slice (the head has one row)
        calls = re.findall(r"%q[46]k_matmul_manyrow\S* = f32\[(\d+),\d+\]"
                           r"\S* custom-call\(", text)
        assert len(calls) >= 7 and set(calls) == {str(rows)}, (rows, calls)
        assert "flash_attention" in text


# the routed layer of OLMoE-1B-7B at its published widths (64 experts of
# width 1024 on a hidden size of 2048, 8 per token, 16 layers): Q4_K gate
# and up, Q6_K down (K = 1024: folded) — (tokens, regime of the row tiles)
@pytest.mark.parametrize("tokens,regime", [
    (1, "fewrow"),       # one stream's decode step: 8 rows
    (8, "fewrow"),       # the 8-lane decode step: 64 rows, one tile an expert
    (512, "manyrow"),    # a prefill slice: 4096 rows
])
def test_routed_experts_compile_at_olmoe_widths(one_chip, tokens, regime):
    """Router picks in, the layer's output and the experts' row counts out;
    both grouped kernels are in the program under their own names, which
    ``benchmarks/kernels/expert_matmul.json`` reads a profile by."""
    from llama_fastapi_k8s_gpu_tpu.ops.pallas.experts import (
        experts_compatible, routed_experts)

    L, E, D, F, k = 16, 64, 2048, 1024, 8
    assert experts_compatible(F, D, for_tpu=True)
    assert experts_compatible(D, F, for_tpu=True)
    gate = {"qs": S(L, E, F, D // 2, dtype=i8), "sm": S(L, E, 1, F, 128)}
    down = {"q4": S(L, E, D // 2, F, dtype=i8),        # 2 rows read as one
            "q2": S(L, E, D // 2, F // 2, dtype=i8),
            "sm6": S(L, E, 1, D // 2, 128)}
    txt = _compile(
        one_chip,
        lambda x, p, w, g, u, d, i: routed_experts(x, p, w, g, u, d, i,
                                                   interpret=False),
        S(tokens, D), S(tokens, k, dtype=i32), S(tokens, k, dtype=f32),
        gate, dict(gate), down, S(dtype=i32))
    assert f"q4k_expert_matmul_{regime}" in txt
    assert f"q6k_expert_matmul_{regime}" in txt


# a 16-lane decode step's routed layer where a share of the experts is held
# (name, experts held, hidden size, expert width, picks a token): 128 and
# 192 rows, of which a step sends the few that reach a held expert through
# calls of 64 rows
@pytest.mark.parametrize("name,E,D,F,k", [
    ("gigachat", 32, 7168, 2048, 8), ("kexaone", 16, 6144, 2048, 8),
    ("longcat", 64, 6144, 2048, 12)])
def test_compacted_few_row_experts_compile_at_the_held_share_widths(
        one_chip, name, E, D, F, k):
    """Both forms of the layer are in the program under one conditional:
    ONE Mosaic call a matrix on 64 rows and one on all the step's rows,
    under the names the benchmark's readers find."""
    import re

    from llama_fastapi_k8s_gpu_tpu.ops.pallas.experts import (
        ROW_GROUP, compacted_rows, padded_k, routed_experts)

    L, lanes = 2, 16
    rows = compacted_rows(lanes, k)
    assert rows == lanes * k > ROW_GROUP
    Dk = padded_k(D)                     # gigachat: K 7168 held at 8192
    gate = {"qs": S(L, E, F, Dk // 2, dtype=i8),
            "sm": S(L, E, Dk // 2048, F, 128)}
    down = {"q4": S(L, E, D, F // 2, dtype=i8),
            "q2": S(L, E, D, F // 4, dtype=i8), "sm6": S(L, E, 1, D, 128)}
    txt = _compile(
        one_chip,
        lambda x, p, w, g, u, d, i: routed_experts(x, p, w, g, u, d, i,
                                                   interpret=False),
        S(lanes, D), S(lanes, k, dtype=i32), S(lanes, k, dtype=f32),
        gate, dict(gate), down, S(dtype=i32))
    calls = re.findall(r"%(q[46]k_expert_matmul_\w+?)(?:\.\d+)? = "
                       r"(f32\[\d+,\d+\])\S* custom-call\(", txt)
    assert sorted(calls) == sorted(
        [("q4k_expert_matmul_fewrow", f"f32[{r},{F}]")
         for r in (ROW_GROUP, rows)] * 2
        + [("q6k_expert_matmul_fewrow", f"f32[{r},{D}]")
           for r in (ROW_GROUP, rows)]), calls
    assert " conditional(" in txt


@pytest.mark.parametrize("seq,quantized", [
    (128, False), (256, False), (512, False), (1024, False),
    (128, True), (1024, True),
])
def test_flash_attention_compiles(one_chip, seq, quantized):
    """head_dim 128, 32/8 heads, the n_ctx 1024 ring, at the prefill
    buckets; ``quantized`` is the int8-KV variant."""
    from llama_fastapi_k8s_gpu_tpu.ops.pallas import flash_attention

    kd = i8 if quantized else bf16
    shapes = [S(seq, 32, 128), S(8, 1024, 128, dtype=kd),
              S(8, 1024, 128, dtype=kd), S(dtype=i32)]
    if quantized:
        shapes += [S(8, 1024, dtype=f32), S(8, 1024, dtype=f32)]

    def fn(q, k, v, pos, *scales):
        ks, vs = scales or (None, None)
        return flash_attention(q, k, v, pos, sm_scale=128 ** -0.5,
                               k_scale=ks, v_scale=vs, interpret=False)

    assert "tpu_custom_call" in _compile(one_chip, fn, *shapes)


@pytest.mark.parametrize("lanes,layers,heads,window", [
    (8, 32, (32, 8), 0),        # mistral-7b on 8 lanes: blocks of 256
    (8, 16, (16, 16), 0),       # olmoe-1b-7b on 8 lanes: blocks of 128
    (1, 48, (32, 8), 0),        # solar-10.7b, the serial engine
    (8, 32, (32, 8), 4096),     # a sliding window
    (16, 32, (32, 8), 0),       # 16 lanes: 8.6 GB of rings
])
def test_decode_attention_kernel_compiles(one_chip, lanes, layers, heads,
                                          window):
    """The decode step's kernel over the STACKED bf16 ring of 4096 slots,
    lanes ``vmap``ped: one Mosaic call whose ring operands are read in
    place (the program makes no copy of a ring), under the profile's name."""
    from llama_fastapi_k8s_gpu_tpu.models import llama
    from llama_fastapi_k8s_gpu_tpu.models.config import ModelConfig
    from llama_fastapi_k8s_gpu_tpu.ops.pallas import flash_attention_decode

    n_heads, n_kv = heads
    cfg = ModelConfig(vocab_size=64, dim=128 * n_heads, n_layers=layers,
                      n_heads=n_heads, n_kv_heads=n_kv, ffn_dim=64,
                      n_ctx=4096, sliding_window=window, attn_impl="pallas")
    block = llama.decode_kernel_block(cfg)
    assert block == (256 if n_kv == 8 else 128)
    ring = S(lanes, layers, n_kv, 4096, 128)

    def fn(q, k, v, i, pos, live):
        return jax.vmap(lambda q, k, v, p, lv: flash_attention_decode(
            q, k, v, i, p, lv, sm_scale=128 ** -0.5, block_k=block,
            sliding_window=window, interpret=False))(q, k, v, pos, live)

    txt = _compile(one_chip, fn, S(lanes, n_heads, 128), ring, ring,
                   S(dtype=i32), S(lanes, dtype=i32),
                   S(lanes, dtype=jnp.bool_))
    assert txt.count("tpu_custom_call") == 1
    assert "flash_attention_decode" in txt
    ring_shape = f"bf16[{lanes},{layers},{n_kv},4096,128]"
    assert not [ln for ln in txt.splitlines()
                if " copy(" in ln and ring_shape in ln.split(" copy(")[0]]


@pytest.mark.parametrize("lanes,layers,heads", [
    (8, 32, (32, 8)),           # mistral-7b on 8 lanes: blocks of 256
    (8, 16, (16, 16)),          # olmoe-1b-7b on 8 lanes: blocks of 128
    (1, 48, (32, 8)),           # solar-10.7b, the serial engine
])
def test_decode_kernel_that_stores_the_row_compiles(one_chip, lanes, layers,
                                                    heads):
    """The decode kernel handed the step's K and V row (a select over one
    bf16 tile of the block in VMEM, the tile copied back): still one Mosaic
    call, its ring operands aliased onto its ring results and, the rings
    donated, the program's ring arguments onto its ring results: no copy
    of a ring anywhere."""
    from llama_fastapi_k8s_gpu_tpu.models import llama
    from llama_fastapi_k8s_gpu_tpu.models.config import ModelConfig
    from llama_fastapi_k8s_gpu_tpu.ops.pallas import flash_attention_decode

    n_heads, n_kv = heads
    block = llama.decode_kernel_block(ModelConfig(
        vocab_size=64, dim=128 * n_heads, n_layers=layers, n_heads=n_heads,
        n_kv_heads=n_kv, ffn_dim=64, n_ctx=4096, attn_impl="pallas"))
    ring = S(lanes, layers, n_kv, 4096, 128)
    row = S(lanes, n_kv, 128)

    def fn(q, k, v, i, pos, live, kn, vn):
        return jax.vmap(lambda q, k, v, p, lv, kn, vn: flash_attention_decode(
            q, k, v, i, p, lv, sm_scale=128 ** -0.5, block_k=block,
            interpret=False, k_new=kn, v_new=vn))(q, k, v, pos, live, kn, vn)

    def place(s):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)

    txt = jax.jit(fn, donate_argnums=(1, 2)).lower(*map(place, (
        S(lanes, n_heads, 128), ring, ring, S(dtype=i32),
        S(lanes, dtype=i32), S(lanes, dtype=jnp.bool_), row, row))
    ).compile().as_text()
    assert txt.count("tpu_custom_call") == 1
    assert "flash_attention_decode" in txt
    head = txt.splitlines()[0]
    assert "{1}: (1, {}, may-alias)" in head and "{2}: (2, {}, may-alias)" in head
    call = next(ln for ln in txt.splitlines() if "tpu_custom_call" in ln)
    assert "output_to_operand_aliasing={{1}: (6, {}), {2}: (7, {})}" in call
    ring_shape = f"bf16[{lanes},{layers},{n_kv},4096,128]"
    assert not [ln for ln in txt.splitlines()
                if " copy(" in ln and ring_shape in ln.split(" copy(")[0]]


@pytest.mark.parametrize("seq", [1, 128, 1024])
def test_kv_quantize_compiles(one_chip, seq):
    from llama_fastapi_k8s_gpu_tpu.ops.pallas.kvquant import quantize_kv_pallas

    txt = _compile(one_chip, lambda x: quantize_kv_pallas(x, interpret=False),
                   S(8, seq, 128))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("gtype", ["Q4_K", "Q5_K", "Q6_K", "Q8_0"])
def test_load_dequant_kernel_compiles(one_chip, gtype):
    """The dequant kernels ``load_params`` runs on the device.  They parse
    block headers on the host, so they are compiled over real (random)
    block bytes of one 256-row tile; the placed dummy argument puts the
    program on the described chip."""
    from llama_fastapi_k8s_gpu_tpu.gguf.constants import GGML_BLOCK_SIZES, GGMLType
    from llama_fastapi_k8s_gpu_tpu.ops.pallas.dequant import _DEVICE_DEQUANT

    gt = GGMLType[gtype]
    block_elems, block_bytes = GGML_BLOCK_SIZES[gt]
    n = 256 * 1024                      # >= one (256, 128) tile per format
    buf = np.random.default_rng(0).integers(
        0, 256, n // block_elems * block_bytes, dtype=np.uint8)

    def fn(_placed):
        return _DEVICE_DEQUANT[gt](buf, n, bf16, False)

    with np.errstate(all="ignore"):     # random header bytes: inf/nan scales
        assert "tpu_custom_call" in _compile(one_chip, fn, S(1, dtype=f32))


def _int8_params(cfg):
    """Shapes of ``load_params``'s tree with int8 linears (plain XLA dots:
    the fused kernels have their own tests above; what is compiled here
    is the decode step around them at a cell's ring and lane count)."""
    L, D, F, V = cfg.n_layers, cfg.dim, cfg.ffn_dim, cfg.vocab_size
    qd, kv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim

    def lin(o, i):
        return {"q": S(L, o, i, dtype=i8), "s": S(L, o, dtype=f32)}

    layers = {"attn_norm": S(L, D, dtype=f32), "ffn_norm": S(L, D, dtype=f32),
              "wq": lin(qd, D), "wk": lin(kv, D), "wv": lin(kv, D),
              "wo": lin(D, qd), "w_gate": lin(F, D), "w_up": lin(F, D),
              "w_down": lin(D, F)}
    if cfg.qk_norm:
        layers["attn_q_norm"] = S(L, qd, dtype=f32)
        layers["attn_k_norm"] = S(L, kv, dtype=f32)
    return {"tok_emb": S(V, D), "out_norm": S(D, dtype=f32),
            "output": {"q": S(V, D, dtype=i8), "s": S(V, dtype=f32)},
            "layers": layers}


# the three configurations of BENCHMARK.json, n_ctx 4096, bf16 KV:
# (name, layers, dim, heads, kv heads, ffn, vocab, qk_norm, lanes)
@pytest.mark.parametrize("name,L,D,H,KV,F,V,qk_norm,lanes", [
    ("solar-serial", 48, 4096, 32, 8, 14336, 32000, False, 0),
    ("mistral-8lane", 32, 4096, 32, 8, 14336, 32000, False, 8),
    # OLMoE's attention (16 MHA heads, QK-norm, rotate-half) and ring; its
    # routed feed-forward has its own test above, a dense one stands in
    ("olmoe-8lane", 16, 2048, 16, 16, 1024, 50304, True, 8),
])
def test_decode_step_reads_the_ring_in_blocks(one_chip, name, L, D, H, KV, F,
                                              V, qk_norm, lanes):
    """The decode chunk program of each cell (serial ``generate_chunk_jit``,
    lane ``batched_generate_chunk_perlane_jit`` with ``live``) compiles for
    the chip with the block read of ``decode_attention`` inside, and the
    compiler has put NO ring-sized copy, slice or transpose beside it
    (the loop slices the STACKED leaf in place; a form that hands a
    layer's ring into the loop may be given a copy of it in every layer)
    and needs no ring-sized scratch."""
    import re

    from llama_fastapi_k8s_gpu_tpu.models.config import ModelConfig
    from llama_fastapi_k8s_gpu_tpu.models.generate import (
        generate_chunk_jit, init_state)
    from llama_fastapi_k8s_gpu_tpu.parallel.batched import (
        batched_generate_chunk_perlane_jit, init_batched_state,
        init_lane_left)
    from llama_fastapi_k8s_gpu_tpu.sampling.sample import (
        SamplingParams, sampling_tensors)

    cfg = ModelConfig(vocab_size=V, dim=D, n_layers=L, n_heads=H,
                      n_kv_heads=KV, ffn_dim=F, n_ctx=4096, qk_norm=qk_norm,
                      rope_neox=qk_norm)

    def place(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = place(_int8_params(cfg))
    st = sampling_tensors(SamplingParams())
    if lanes:
        state = place(jax.eval_shape(lambda: init_batched_state(cfg, lanes)))
        st = place(jax.eval_shape(lambda: jax.tree.map(
            lambda a: jnp.broadcast_to(a, (lanes,)), st)))
        left = place(jax.eval_shape(lambda: init_lane_left(lanes)))
        lowered = batched_generate_chunk_perlane_jit.__wrapped__.lower(
            params, cfg, state, st, left, n_steps=8, top_k=40,
            live=place(S(lanes, dtype=jnp.bool_)), stop_ids=(2,))
    else:
        state = place(jax.eval_shape(lambda: init_state(cfg)))
        lowered = generate_chunk_jit.__wrapped__.lower(
            params, cfg, state, place(jax.eval_shape(lambda: st)),
            n_steps=8, top_k=40)
    compiled = lowered.compile()
    ring_op = re.compile(
        r"= bf16\[(\d+,)*4096,128\]\S* (copy|dynamic-slice|transpose)\(")
    found = [ln.strip()[:160] for ln in compiled.as_text().splitlines()
             if ring_op.search(ln)]
    assert not found, found[:3]
    one_layer_ring = max(lanes, 1) * KV * 4096 * 128 * 2
    assert compiled.memory_analysis().temp_size_in_bytes < one_layer_ring


@pytest.mark.parametrize("name,L,D,H,KV,F,V,qk_norm,lanes", [
    ("solar-serial", 48, 4096, 32, 8, 14336, 32000, False, 0),
    ("mistral-8lane", 32, 4096, 32, 8, 14336, 32000, False, 8),
    ("olmoe-8lane", 16, 2048, 16, 16, 1024, 50304, True, 8),
])
def test_decode_step_leaves_the_ring_to_the_kernel(one_chip, monkeypatch,
                                                   name, L, D, H, KV, F, V,
                                                   qk_norm, lanes):
    """The same decode chunk programs as a chip serves them
    (``attn_impl="pallas"``, the decode kernel compiled by Mosaic: this
    test says so in place of the backend): the kernel stores the step's
    row itself, so the ONLY operation of the compiled program that takes
    or gives a ring is the kernel's call: no ``dynamic-update-slice``, no
    select fusion, no ``copy``, only the loops' tuples around it (the
    serial engine's added lane axis is a bitcast)."""
    import re
    from collections import Counter

    import llama_fastapi_k8s_gpu_tpu.ops.pallas as pallas_ops
    from llama_fastapi_k8s_gpu_tpu.models.config import ModelConfig
    from llama_fastapi_k8s_gpu_tpu.models.generate import (
        generate_chunk_jit, init_state)
    from llama_fastapi_k8s_gpu_tpu.parallel.batched import (
        batched_generate_chunk_perlane_jit, init_batched_state,
        init_lane_left)
    from llama_fastapi_k8s_gpu_tpu.sampling.sample import (
        SamplingParams, sampling_tensors)

    monkeypatch.setattr(pallas_ops, "use_interpret", lambda: False)
    cfg = ModelConfig(vocab_size=V, dim=D, n_layers=L, n_heads=H,
                      n_kv_heads=KV, ffn_dim=F, n_ctx=4096, qk_norm=qk_norm,
                      rope_neox=qk_norm, attn_impl="pallas")

    def place(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = place(_int8_params(cfg))
    st = sampling_tensors(SamplingParams())
    if lanes:
        state = place(jax.eval_shape(lambda: init_batched_state(cfg, lanes)))
        st = place(jax.eval_shape(lambda: jax.tree.map(
            lambda a: jnp.broadcast_to(a, (lanes,)), st)))
        left = place(jax.eval_shape(lambda: init_lane_left(lanes)))
        lowered = batched_generate_chunk_perlane_jit.__wrapped__.lower(
            params, cfg, state, st, left, n_steps=8, top_k=40,
            live=place(S(lanes, dtype=jnp.bool_)), stop_ids=(2,))
    else:
        state = place(jax.eval_shape(lambda: init_state(cfg)))
        lowered = generate_chunk_jit.__wrapped__.lower(
            params, cfg, state, place(jax.eval_shape(lambda: st)),
            n_steps=8, top_k=40)
    compiled = lowered.compile()
    text = compiled.as_text()
    ring = re.compile(r"bf16\[(\d+,)*4096,128\]")
    op = re.compile(r"^\s*(ROOT )?%\S+ = .*? ([\w-]+)\(")
    ops = Counter(op.match(ln).group(2) for ln in text.splitlines()
                  if ring.search(ln) and op.match(ln))
    assert ops["custom-call"] == 1, ops
    assert set(ops) <= {"custom-call", "parameter", "tuple",
                        "get-tuple-element", "while", "bitcast"}, ops
    assert text.count("tpu_custom_call") == 1
    assert "flash_attention_decode" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 8 * 2 ** 20


def _ouro(one_chip):
    """Ouro-2.6B at its published geometry (48 layers x 4 passes, 16 MHA
    heads of 128, SwiGLU 5632, vocabulary 49152), ``n_ctx`` 1280: (cfg,
    params with int8 linears as ``_int8_params`` has them, place)."""
    from llama_fastapi_k8s_gpu_tpu.models.config import ModelConfig

    cfg = ModelConfig(vocab_size=49152, dim=2048, n_layers=48, n_heads=16,
                      n_kv_heads=16, ffn_dim=5632, n_ctx=1280, rope_theta=1e6,
                      rms_eps=1e-6, rope_neox=True, ut_steps=4,
                      sandwich_norm=True, attn_impl="pallas")

    def place(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = _int8_params(cfg)
    params["layers"].update(post_attn_norm=S(48, 2048, dtype=f32),
                            post_ffn_norm=S(48, 2048, dtype=f32))
    params["exit_gate"] = {"w": S(2048, dtype=f32), "b": S(dtype=f32)}
    return cfg, place(params), place


@pytest.mark.parametrize("name,lanes", [("ouro-serial", 0),
                                        ("ouro-4lane", 4)])
def test_looped_stack_compiles_with_one_body_and_no_ring_sized_copy(
        one_chip, monkeypatch, name, lanes):
    """The decode chunk and the 256-row prefill slice of a stack whose 48
    layers run 4 passes (models/llama.py ``forward``'s one loop of 192
    bodies) compile for the chip as a chip serves them: ONE layer body (one
    decode-kernel call, whatever the passes) on a ring of 192 leaves, the
    pass's end behind one conditional, no operation but the kernel's call
    that takes or gives a ring, and the exit masses in the chunk's result."""
    import re
    from collections import Counter

    import llama_fastapi_k8s_gpu_tpu.ops.pallas as pallas_ops
    from llama_fastapi_k8s_gpu_tpu.models.generate import (
        generate_chunk_jit, init_state, prefill_chunk_jit)
    from llama_fastapi_k8s_gpu_tpu.models.llama import cache_nbytes, init_cache
    from llama_fastapi_k8s_gpu_tpu.parallel.batched import (
        batched_generate_chunk_perlane_jit, init_batched_state,
        init_lane_left)
    from llama_fastapi_k8s_gpu_tpu.sampling.sample import (
        SamplingParams, sampling_tensors)

    monkeypatch.setattr(pallas_ops, "use_interpret", lambda: False)
    cfg, params, place = _ouro(one_chip)
    assert cfg.cache_leaves == 192 and cache_nbytes(cfg) == 1280 * 1536 * 1024
    st = sampling_tensors(SamplingParams())
    if lanes:
        state = place(jax.eval_shape(lambda: init_batched_state(cfg, lanes)))
        st = place(jax.eval_shape(lambda: jax.tree.map(
            lambda a: jnp.broadcast_to(a, (lanes,)), st)))
        left = place(jax.eval_shape(lambda: init_lane_left(lanes)))
        lowered = batched_generate_chunk_perlane_jit.__wrapped__.lower(
            params, cfg, state, st, left, n_steps=8, top_k=40,
            live=place(S(lanes, dtype=jnp.bool_)), stop_ids=(2,))
    else:
        state = place(jax.eval_shape(lambda: init_state(cfg)))
        lowered = generate_chunk_jit.__wrapped__.lower(
            params, cfg, state, place(jax.eval_shape(lambda: st)),
            n_steps=8, top_k=40)
    # the chunk hands the host its tokens AND the exit masses of its steps
    out = jax.tree.leaves(lowered.out_info)
    assert any(o.shape == (4,) and o.dtype == f32 for o in out), out
    compiled = lowered.compile()
    text = compiled.as_text()
    ring = re.compile(r"bf16\[(\d+,)*192,16,1280,128\]")
    op = re.compile(r"^\s*(ROOT )?%\S+ = .*? ([\w-]+)\(")
    ops = Counter(op.match(ln).group(2) for ln in text.splitlines()
                  if ring.search(ln) and op.match(ln))
    assert ops["custom-call"] == 1, ops
    assert set(ops) <= {"custom-call", "parameter", "tuple",
                        "get-tuple-element", "while", "bitcast",
                        "conditional"}, ops
    assert text.count("tpu_custom_call") == 1
    assert "flash_attention_decode" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 8 * 2 ** 20
    if not lanes:       # the admission's slice into the scratch cache
        cache = place(jax.eval_shape(lambda: init_cache(cfg)))
        sliced = prefill_chunk_jit.__wrapped__.lower(
            params, cfg, place(S(256, dtype=i32)), place(S(dtype=i32)),
            place(S(dtype=i32)), cache).compile()
        assert sliced.as_text().count("tpu_custom_call") == 1  # flash, once
        assert "flash_attention" in sliced.as_text()
        assert sliced.memory_analysis().temp_size_in_bytes < 256 * 2 ** 20


def test_a_stack_whose_layers_run_once_has_nothing_of_the_loop():
    """A ``mistral``-shaped configuration (``ut_steps`` 1) lowers a decode
    step with none of the loop's scopes, no arithmetic on the layer counter
    (the weights' row and the cache's leaf are the counter itself) and no
    conditional; the same widths with ``ut_steps`` 2 have all of them.
    Lowered, not compiled: no chip is described."""
    from llama_fastapi_k8s_gpu_tpu.models.config import ModelConfig
    from llama_fastapi_k8s_gpu_tpu.models.llama import forward, init_cache
    from llama_fastapi_k8s_gpu_tpu.models.params import synth_params

    scopes = ("ut_pass", "pass_norm", "exit_gate", "post_attn_norm",
              "post_ffn_norm")
    texts = {}
    for steps in (1, 2):
        cfg = ModelConfig(vocab_size=512, dim=256, n_layers=4, n_heads=4,
                          n_kv_heads=2, ffn_dim=512, n_ctx=128,
                          rope_theta=1e6, ut_steps=steps,
                          sandwich_norm=steps > 1)
        params = jax.eval_shape(lambda cfg=cfg: synth_params(cfg))
        texts[steps] = jax.jit(
            lambda p, t, pos, c, cfg=cfg: forward(
                p, cfg, t, pos, c, with_stats=steps > 1)).lower(
            params, S(1, dtype=i32), S(dtype=i32),
            jax.eval_shape(lambda cfg=cfg: init_cache(cfg))
        ).as_text(debug_info=True)
    once, looped = texts[1], texts[2]
    for scope in scopes:
        assert scope not in once and scope in looped, scope
    # (a step's block arithmetic on the POSITION has a remainder and a
    # division of its own, the same in both: the loop adds one of each on
    # the counter, and the one conditional)
    count = {op: (once.count(op), looped.count(op)) for op in (
        "stablehlo.remainder", "stablehlo.divide", "stablehlo.case")}
    print(count)
    assert count["stablehlo.case"] == (0, 1), count
    assert count["stablehlo.remainder"][1] == \
        count["stablehlo.remainder"][0] + 1, count
    assert "stablehlo.if" not in once


# BENCHMARK.json's evabyte configuration at its published widths, n_ctx
# 16384: (name, lanes)
@pytest.mark.parametrize("name,lanes", [("evabyte-serial", 0),
                                        ("evabyte-4lane", 4)])
def test_evabyte_step_reads_window_and_summaries_in_blocks(one_chip, name,
                                                           lanes):
    """The decode chunk and the prefill slice of the ``evabyte`` block
    (models/eva.py: 32 MHA heads, window 2048, chunk 16, float32 residual,
    8 prediction heads) compile for the chip at 16384 positions.  In the
    decode chunk the compiler has put no window- or summary-leaf-sized
    copy or transpose (the block reads and the window close slice the
    STACKED leaves in place; the close's own read of one layer's window
    is a dynamic-slice inside its branch), and its scratch stays under
    three layers' windows: the close converts one window to float32."""
    import re

    from llama_fastapi_k8s_gpu_tpu.models.config import ModelConfig
    from llama_fastapi_k8s_gpu_tpu.models.generate import (
        generate_chunk_jit, init_state, prefill_chunk_jit)
    from llama_fastapi_k8s_gpu_tpu.models.llama import init_cache
    from llama_fastapi_k8s_gpu_tpu.parallel.batched import (
        batched_generate_chunk_perlane_jit, init_batched_state,
        init_lane_left)
    from llama_fastapi_k8s_gpu_tpu.sampling.sample import (
        SamplingParams, sampling_tensors)

    L, H, hd, W = 32, 32, 128, 2048
    cfg = ModelConfig(vocab_size=320, dim=4096, n_layers=L, n_heads=H,
                      n_kv_heads=H, ffn_dim=11008, n_ctx=16384,
                      rope_theta=1e5, rope_neox=True, eva_window=W,
                      eva_chunk=16, n_pred_heads=8, fp32_residual=True)

    def place(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = _int8_params(cfg)
    params["layers"]["eva_phi"] = S(L, H, hd, dtype=f32)
    params["layers"]["eva_mu"] = S(L, H, hd, dtype=f32)
    params["output"] = {"w": S(320 * 8, 4096)}
    params = place(params)
    st = sampling_tensors(SamplingParams())
    if lanes:
        state = place(jax.eval_shape(lambda: init_batched_state(cfg, lanes)))
        st = place(jax.eval_shape(lambda: jax.tree.map(
            lambda a: jnp.broadcast_to(a, (lanes,)), st)))
        left = place(jax.eval_shape(lambda: init_lane_left(lanes)))
        lowered = batched_generate_chunk_perlane_jit.__wrapped__.lower(
            params, cfg, state, st, left, n_steps=8, top_k=40,
            live=place(S(lanes, dtype=jnp.bool_)), stop_ids=(2,))
    else:
        state = place(jax.eval_shape(lambda: init_state(cfg)))
        lowered = generate_chunk_jit.__wrapped__.lower(
            params, cfg, state, place(jax.eval_shape(lambda: st)),
            n_steps=8, top_k=40)
    compiled = lowered.compile()
    assert state["cache"]["sk"].shape[-2:] == (896, hd)
    leaf_op = re.compile(
        r"^\s*(ROOT )?%\S+ = bf16\[(\d+,)*(2048|896),128\]\S* "
        r"(copy|transpose)\(")
    fused = re.compile(r"^%fused_computation")
    found, in_fusion = [], False
    for ln in compiled.as_text().splitlines():
        if ln.startswith(("%", "ENTRY")):
            in_fusion = bool(fused.match(ln))
        # a copy INSIDE a fusion is a layout of what the fusion reads
        # (the close's slice), not a buffer of its own
        if not in_fusion and leaf_op.search(ln):
            found.append(ln.strip()[:160])
    assert not found, found[:3]
    one_layer_window = max(lanes, 1) * H * W * hd * 2
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 3 * one_layer_window
    if not lanes:       # the admission slice into the scratch cache
        cache = place(jax.eval_shape(lambda: init_cache(cfg)))
        for rows in _slice_widths(cfg):     # this block keeps the narrow one
            sliced = prefill_chunk_jit.__wrapped__.lower(
                params, cfg, place(S(rows, dtype=i32)), place(S(dtype=i32)),
                place(S(dtype=i32)), cache).compile()
            assert sliced.memory_analysis().temp_size_in_bytes \
                < 512 * 2 ** 20 * rows // 256
        assert _slice_widths(cfg) == [256]


# BENCHMARK.json's minicpm-sala configuration at its published widths, n_ctx
# 16384, the attention the chip resolves to: (name, lanes)
@pytest.mark.parametrize("name,lanes", [("sala-serial", 0),
                                        ("sala-8lane", 8)])
def test_sala_stack_compiles_with_no_ring_sized_copy(one_chip, name, lanes):
    """The decode chunk and the prefill slice of the ``minicpm-sala`` stack
    (models/sala.py: 24 linear-attention layers over a float32 state, 8
    block-sparse layers on a 2-head ring of 16384 slots; nine runs of one
    kind) compile for the chip with the ring's kernels inside (the decode
    kernel at 2 KV heads and a group of 16, flash prefill at 16384 keys).
    In the decode chunk the compiler has put NO ring-sized copy or
    transpose (the compressed keys close from the small last-keys leaf: a
    window read out of the ring at a lane's own position made it lay the
    whole ring out anew in every sparse layer) and none of the state leaf
    (the state's step is a kernel that updates the stacked leaf in place,
    ops/pallas/linstate.py: the plain XLA step transposed the lanes' leaf
    into and out of every chunk), and the scratch stays under half the
    lanes' state (32 MB for one sequence): the lanes' compressed-key leaf
    is laid out anew once into and once out of a chunk."""
    import re

    from llama_fastapi_k8s_gpu_tpu.models.config import ModelConfig
    from llama_fastapi_k8s_gpu_tpu.models.generate import (
        generate_chunk_jit, init_state, prefill_chunk_jit)
    from llama_fastapi_k8s_gpu_tpu.models.llama import init_cache
    from llama_fastapi_k8s_gpu_tpu.parallel.batched import (
        batched_generate_chunk_perlane_jit, init_batched_state,
        init_lane_left)
    from llama_fastapi_k8s_gpu_tpu.sampling.sample import (
        SamplingParams, sampling_tensors)

    order = "S" + "L" * 8 + "S" + "L" * 6 + "SS" + "L" * 4 + "S" + "L" * 6 \
        + "SSS"
    D, F, V, hd = 4096, 16384, 73448, 128
    cfg = ModelConfig(
        vocab_size=V, dim=D, n_layers=32, n_heads=32, n_kv_heads=2,
        ffn_dim=F, n_ctx=16384, rope_theta=1e4, rms_eps=1e-6, rope_neox=True,
        attn_impl="pallas",
        mixers=tuple("sp" if c == "S" else "lin" for c in order),
        lin_heads=32, emb_scale=12.0, residual_scale=1.4 / 32 ** 0.5,
        logit_scale=1 / 16, fp32_logits=True, sp_kernel=32, sp_stride=16,
        sp_block=64, sp_topk=64, sp_window=2048, sp_init_blocks=1,
        sp_dense_len=8192)

    def kind(L, kv):
        def lin(o, i):
            return {"q": S(L, o, i, dtype=i8), "s": S(L, o, dtype=f32)}
        return {"attn_norm": S(L, D, dtype=f32), "ffn_norm": S(L, D, dtype=f32),
                "attn_q_norm": S(L, hd, dtype=f32),
                "attn_k_norm": S(L, hd, dtype=f32),
                "wq": lin(D, D), "wk": lin(kv, D), "wv": lin(kv, D),
                "wo": lin(D, D), "wg": lin(D, D), "w_gate": lin(F, D),
                "w_up": lin(F, D), "w_down": lin(D, F)}

    def place(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = place({
        "tok_emb": S(V, D), "out_norm": S(D, dtype=f32),
        "output": {"w": S(V, D)},
        "layers": {"lin": {**kind(24, D),
                           "attn_out_norm": S(24, hd, dtype=f32)},
                   "sp": kind(8, 2 * hd)}})
    st = sampling_tensors(SamplingParams())
    if lanes:
        state = place(jax.eval_shape(lambda: init_batched_state(cfg, lanes)))
        st = place(jax.eval_shape(lambda: jax.tree.map(
            lambda a: jnp.broadcast_to(a, (lanes,)), st)))
        left = place(jax.eval_shape(lambda: init_lane_left(lanes)))
        lowered = batched_generate_chunk_perlane_jit.__wrapped__.lower(
            params, cfg, state, st, left, n_steps=8, top_k=40,
            live=place(S(lanes, dtype=jnp.bool_)), stop_ids=(2,))
    else:
        state = place(jax.eval_shape(lambda: init_state(cfg)))
        lowered = generate_chunk_jit.__wrapped__.lower(
            params, cfg, state, place(jax.eval_shape(lambda: st)),
            n_steps=8, top_k=40)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert text.count("flash_attention_decode") >= 5    # one a sparse run
    assert text.count("lin_state") >= 4            # one a linear run
    leaf_op = re.compile(
        r"^\s*(ROOT )?%\S+ = (bf16\[(\d+,)*16384,128\]|"
        r"f32\[(\d+,)*32,128,128\])\S* (copy|transpose)\(")
    fused = re.compile(r"^%fused_computation")
    found, in_fusion = [], False
    for ln in text.splitlines():
        if ln.startswith(("%", "ENTRY")):
            in_fusion = bool(fused.match(ln))
        if not in_fusion and leaf_op.search(ln):
            found.append(ln.strip()[:120])
    assert not found, found[:4]
    state_leaf = max(lanes, 1) * 24 * 32 * hd * hd * 4
    assert compiled.memory_analysis().temp_size_in_bytes \
        < max(state_leaf // 2, 32 * 2 ** 20)
    if not lanes:       # the admission slice into the scratch cache
        cache = place(jax.eval_shape(lambda: init_cache(cfg)))
        for rows in _slice_widths(cfg):     # narrow, and the wide slice
            sliced = prefill_chunk_jit.__wrapped__.lower(
                params, cfg, place(S(rows, dtype=i32)), place(S(dtype=i32)),
                place(S(dtype=i32)), cache).compile()
            assert "flash_attention" in sliced.as_text()
            assert sliced.memory_analysis().temp_size_in_bytes \
                < 512 * 2 ** 20 * rows // 256
        assert _slice_widths(cfg) == [256, 1024]


def _gigachat(one_chip, **flags):
    """BENCHMARK.json's gigachat configuration at its published widths
    (hidden 7168 filled up to K 8192, 64 heads, latents 1536 / 512 + 64, 1
    dense + 6 routed layers holding 32 of 256 experts), n_ctx 16384: (cfg,
    the parameters' shapes on the described chip, ``place``)."""
    from llama_fastapi_k8s_gpu_tpu.models.config import ModelConfig

    D, DP, V, H = 7168, 8192, 128256, 64
    r_q, r_kv, d_n, d_r, d_v, F, Fe, E = 1536, 512, 128, 64, 192, 18432, \
        2048, 32
    cfg = ModelConfig(
        vocab_size=V, dim=D, n_layers=7, n_heads=H, n_kv_heads=H, ffn_dim=F,
        n_ctx=16384, rope_theta=1e5, rms_eps=1e-6, attn_impl="xla",
        q_lora_rank=r_q, kv_lora_rank=r_kv, qk_nope_dim=d_n, qk_rope_dim=d_r,
        v_head_dim=d_v, rope_yarn_factor=64.0, rope_yarn_orig_ctx=4096,
        attn_mscale=2.0048, n_dense_layers=1, expert_ffn_dim=Fe,
        n_shared_experts=1, n_experts=256, n_experts_used=8,
        norm_topk_prob=True, expert_gating="sigmoid", n_expert_groups=8,
        n_groups_used=4, expert_weights_scale=2.5, experts_first=0,
        experts_held=E, **flags)

    def exps(fmt, n, k, L):
        kt = k // 2048
        if fmt == "q4k":
            return {"qs": S(L, E, n, k // 2, dtype=i8),
                    "sm": S(L, E, kt, n, 128)}
        return {"q4": S(L, E, n, k // 2, dtype=i8),
                "q2": S(L, E, n, k // 4, dtype=i8),
                "sm6": S(L, E, kt, n, 128)}

    def attn(L):
        return {"attn_norm": S(L, D, dtype=f32), "ffn_norm": S(L, D, dtype=f32),
                "q_a_norm": S(L, r_q, dtype=f32),
                "kv_a_norm": S(L, r_kv, dtype=f32),
                "wq_a": _planes("q4k", r_q, DP, L),
                "wq_b": {"w": S(L, H * (d_n + d_r), r_q)},
                "wkv_a": _planes("q4k", 640, DP, L),
                "wo": _planes("q4k", D, H * d_v, L),
                "w_uk": {"w": S(L, H, d_n, r_kv)},
                "w_uv": {"w": S(L, H, d_v, r_kv)}}

    def place(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = place({
        "tok_emb": S(V, D), "out_norm": S(D, dtype=f32),
        "output": _planes("q6k", V, DP),
        "layers": {
            "dense": {**attn(1), "w_gate": _planes("q4k", F, DP, 1),
                      "w_up": _planes("q4k", F, DP, 1),
                      "w_down": _planes("q6k", D, F, 1)},
            "moe": {**attn(6), "w_router": S(6, 256, D, dtype=f32),
                    "router_bias": S(6, 256, dtype=f32),
                    "w_gate_sh": _planes("q4k", Fe, DP, 6),
                    "w_up_sh": _planes("q4k", Fe, DP, 6),
                    "w_down_sh": _planes("q6k", D, Fe, 6),
                    "w_gate_exps": exps("q4k", Fe, DP, 6),
                    "w_up_exps": exps("q4k", Fe, DP, 6),
                    "w_down_exps": exps("q6k", D, Fe, 6)}}})
    return cfg, params, place


def _leaf_copies(text):
    """The copies and transposes of a leaf of 16384 positions that stand
    outside a fusion in a compiled program's text."""
    import re

    leaf_op = re.compile(
        r"^\s*(ROOT )?%\S+ = bf16\[(\d+,)*16384,\d+\]\S* (copy|transpose)\(")
    fused = re.compile(r"^%fused_computation")
    found, in_fusion = [], False
    for ln in text.splitlines():
        if ln.startswith(("%", "ENTRY")):
            in_fusion = bool(fused.match(ln))
        if not in_fusion and leaf_op.search(ln):
            found.append(ln.strip()[:120])
    return found


# (name, lanes)
@pytest.mark.parametrize("name,lanes,read", [
    ("gigachat-serial", 0, "loop"), ("gigachat-16lane", 16, "loop"),
    ("gigachat-serial-kernel", 0, "kernel"),
    ("gigachat-16lane-kernel", 16, "kernel")])
def test_latent_stack_compiles_with_no_ring_sized_copy(one_chip, monkeypatch,
                                                       name, lanes, read):
    """The decode chunk and the prefill slice of the ``deepseek2`` stack
    (models/mla.py) compile for the chip: the fused planes at K 8192 (the
    experts' gate and up among them: the grouped kernels are in the program
    under their own names), the latent projection at 640 rows, the absorbed
    attention's loop over blocks of the lanes' stacked latent leaf
    (``loop``) or the decode kernel on that leaf as it is (``kernel``: the
    leaf in its own shape is the kernel's operand and aliased result, which
    is how benchmarks/kernels/mla_attn.json finds it; no block of the
    lanes' leaf is materialised and no XLA update writes the step's row).
    The compiler has put NO copy or transpose of the latent leaf in the
    decode chunk, and a slice's scratch stays under half a GB."""
    import re

    from llama_fastapi_k8s_gpu_tpu.models.generate import (
        generate_chunk_jit, init_state, prefill_chunk_jit)
    from llama_fastapi_k8s_gpu_tpu.models.llama import init_cache
    from llama_fastapi_k8s_gpu_tpu.parallel.batched import (
        batched_generate_chunk_perlane_jit, init_batched_state,
        init_lane_left)
    from llama_fastapi_k8s_gpu_tpu.sampling.sample import (
        SamplingParams, sampling_tensors)

    from llama_fastapi_k8s_gpu_tpu.ops import pallas as pallas_ops

    monkeypatch.setattr(pallas_ops, "use_interpret", lambda: False)
    cfg, params, place = _gigachat(one_chip, latent_kernel=read == "kernel")
    st = sampling_tensors(SamplingParams())
    if lanes:
        state = place(jax.eval_shape(lambda: init_batched_state(cfg, lanes)))
        st = place(jax.eval_shape(lambda: jax.tree.map(
            lambda a: jnp.broadcast_to(a, (lanes,)), st)))
        left = place(jax.eval_shape(lambda: init_lane_left(lanes)))
        lowered = batched_generate_chunk_perlane_jit.__wrapped__.lower(
            params, cfg, state, st, left, n_steps=8, top_k=40,
            live=place(S(lanes, dtype=jnp.bool_)), stop_ids=(2,))
    else:
        state = place(jax.eval_shape(lambda: init_state(cfg)))
        lowered = generate_chunk_jit.__wrapped__.lower(
            params, cfg, state, place(jax.eval_shape(lambda: st)),
            n_steps=8, top_k=40)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert "q4k_expert_matmul_fewrow" in text
    assert "q6k_expert_matmul_fewrow" in text
    found = _leaf_copies(text)
    assert not found, found[:4]
    assert compiled.memory_analysis().temp_size_in_bytes < 512 * 2 ** 20
    leaf = "bf16[%d,7,1,16384,640]" % (lanes or 1)
    kernel = [ln for ln in text.splitlines()
              if re.match(r"\s*(ROOT )?%flash_attention_decode_latent", ln)]
    if read == "kernel":
        from llama_fastapi_k8s_gpu_tpu.models.llama import (
            decode_kernel_block, ring_write_impl)

        assert decode_kernel_block(cfg) and ring_write_impl(cfg) == "kernel"
        # the leaf un-reshaped: the kernel's aliased result (and the lanes'
        # axis first under the lane engine)
        assert kernel and all(leaf in ln.split(" custom-call(")[0]
                              for ln in kernel), kernel[:2]
        assert "bf16[16,512,640]" not in text
        assert not re.search(
            r"= bf16\[(\d+,)*16384,640\]\S* dynamic-update-slice\(", text)
        return
    assert not kernel
    if not lanes:       # the admission slice into the scratch cache
        cache = place(jax.eval_shape(lambda: init_cache(cfg)))
        for rows in _slice_widths(cfg):     # narrow, and the wide slice
            sliced = prefill_chunk_jit.__wrapped__.lower(
                params, cfg, place(S(rows, dtype=i32)), place(S(dtype=i32)),
                place(S(dtype=i32)), cache).compile()
            assert "q4k_expert_matmul_manyrow" in sliced.as_text()
            assert sliced.memory_analysis().temp_size_in_bytes \
                < 1024 * 2 ** 20 * rows // 256
        assert _slice_widths(cfg) == [256, 1024]


def _longcat(one_chip, **flags):
    """BENCHMARK.json's longcat configuration at its published widths
    (hidden 6144, 64 heads, latents 1536 / 512 + 64, 4 double layers: 8
    attention sub-layers and 8 dense feed-forwards of 12288, 4 expert
    branches holding 64 of 512 experts beside 256 identity outputs, top-12),
    n_ctx 16384: (cfg, the parameters' shapes on the described chip,
    ``place``)."""
    from llama_fastapi_k8s_gpu_tpu.models.config import ModelConfig

    D, V, H, L = 6144, 131072, 64, 4
    r_q, r_kv, d_n, d_r, d_v, F, Fe, E = 1536, 512, 128, 64, 128, 12288, \
        2048, 64
    cfg = ModelConfig(
        vocab_size=V, dim=D, n_layers=L, n_heads=H, n_kv_heads=H, ffn_dim=F,
        n_ctx=16384, rope_theta=1e7, rms_eps=1e-5, attn_impl="xla",
        q_lora_rank=r_q, kv_lora_rank=r_kv, qk_nope_dim=d_n, qk_rope_dim=d_r,
        v_head_dim=d_v, expert_ffn_dim=Fe, n_experts=512, n_experts_used=12,
        n_zero_experts=256, expert_gating="softmax",
        expert_weights_scale=6.0, experts_first=0, experts_held=E,
        attn_sublayers=2, q_latent_scale=2.0, kv_latent_scale=12.0 ** 0.5,
        **flags)

    def exps(fmt, n, k):
        kt = k // 2048
        if fmt == "q4k":
            return {"qs": S(L, E, n, k // 2, dtype=i8),
                    "sm": S(L, E, kt, n, 128)}
        return {"q4": S(L, E, n, k // 2, dtype=i8),
                "q2": S(L, E, n, k // 4, dtype=i8),
                "sm6": S(L, E, kt, n, 128)}

    def place(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    L2 = 2 * L
    params = place({
        "tok_emb": S(V, D), "out_norm": S(D, dtype=f32),
        "output": _planes("q6k", V, D),
        "layers": {
            "attn": {"attn_norm": S(L2, D, dtype=f32),
                     "q_a_norm": S(L2, r_q, dtype=f32),
                     "kv_a_norm": S(L2, r_kv, dtype=f32),
                     "wq_a": _planes("q4k", r_q, D, L2),
                     "wq_b": {"w": S(L2, H * (d_n + d_r), r_q)},
                     "wkv_a": _planes("q4k", 640, D, L2),
                     "wo": _planes("q4k", D, H * d_v, L2),
                     "w_uk": {"w": S(L2, H, d_n, r_kv)},
                     "w_uv": {"w": S(L2, H, d_v, r_kv)}},
            "ffn": {"ffn_norm": S(L2, D, dtype=f32),
                    "w_gate": _planes("q4k", F, D, L2),
                    "w_up": _planes("q4k", F, D, L2),
                    "w_down": _planes("q6k", D, F, L2)},
            "moe": {"w_router": S(L, 768, D, dtype=f32),
                    "router_bias": S(L, 768, dtype=f32),
                    "w_gate_exps": exps("q4k", Fe, D),
                    "w_up_exps": exps("q4k", Fe, D),
                    "w_down_exps": exps("q6k", D, Fe)}}})
    return cfg, params, place


# (name, lanes)
@pytest.mark.parametrize("name,lanes", [("longcat-serial", 0),
                                        ("longcat-16lane", 16)])
def test_shortcut_stack_compiles_with_no_ring_sized_copy(one_chip,
                                                         monkeypatch, name,
                                                         lanes):
    """The decode chunk and the prefill slices of the ``longcat-flash``
    stack (models/mla.py ``shortcut_layer``) compile for the chip as a chip
    serves them (both latent kernels on): the fused planes at K 6144 / 12288
    / 8192 / 2048 with nothing filled up, the latent projection at 640 rows,
    the decode kernel on the 8-leaf latent ring as it is, and the FEW-row
    grouped expert kernels on the lane step's 16 x 12 = 192 rows (the
    many-row ones on a slice's).  The compiler has put NO copy or transpose
    of the latent leaf in the decode chunk, and a slice's scratch stays
    under a GB a 256 rows."""
    import re

    from llama_fastapi_k8s_gpu_tpu.models.generate import (
        generate_chunk_jit, init_state, prefill_chunk_jit)
    from llama_fastapi_k8s_gpu_tpu.models.llama import init_cache
    from llama_fastapi_k8s_gpu_tpu.ops import pallas as pallas_ops
    from llama_fastapi_k8s_gpu_tpu.ops.pallas.experts import decode_slots
    from llama_fastapi_k8s_gpu_tpu.parallel.batched import (
        batched_generate_chunk_perlane_jit, init_batched_state,
        init_lane_left)
    from llama_fastapi_k8s_gpu_tpu.sampling.sample import (
        SamplingParams, sampling_tensors)

    monkeypatch.setattr(pallas_ops, "use_interpret", lambda: False)
    cfg, params, place = _longcat(one_chip, latent_kernel=True,
                                  latent_slice_kernel=True)
    assert decode_slots(cfg.n_held, 16, cfg.n_experts_used) == 64
    st = sampling_tensors(SamplingParams())
    if lanes:
        state = place(jax.eval_shape(lambda: init_batched_state(cfg, lanes)))
        st = place(jax.eval_shape(lambda: jax.tree.map(
            lambda a: jnp.broadcast_to(a, (lanes,)), st)))
        left = place(jax.eval_shape(lambda: init_lane_left(lanes)))
        lowered = batched_generate_chunk_perlane_jit.__wrapped__.lower(
            params, cfg, state, st, left, n_steps=8, top_k=40,
            live=place(S(lanes, dtype=jnp.bool_)), stop_ids=(2,))
    else:
        state = place(jax.eval_shape(lambda: init_state(cfg)))
        lowered = generate_chunk_jit.__wrapped__.lower(
            params, cfg, state, place(jax.eval_shape(lambda: st)),
            n_steps=8, top_k=40)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert "q4k_expert_matmul_fewrow" in text
    assert "q6k_expert_matmul_fewrow" in text
    assert "expert_matmul_manyrow" not in text
    found = _leaf_copies(text)
    assert not found, found[:4]
    assert compiled.memory_analysis().temp_size_in_bytes < 512 * 2 ** 20
    leaf = "bf16[%d,8,1,16384,640]" % (lanes or 1)
    kernel = [ln for ln in text.splitlines()
              if re.match(r"\s*(ROOT )?%flash_attention_decode_latent", ln)]
    assert kernel and all(leaf in ln.split(" custom-call(")[0]
                          for ln in kernel), kernel[:2]
    assert not re.search(
        r"= bf16\[(\d+,)*16384,640\]\S* dynamic-update-slice\(", text)
    if not lanes:       # the admission slices into the scratch cache
        cache = place(jax.eval_shape(lambda: init_cache(cfg)))
        assert _slice_widths(cfg) == [256, 1024]
        for rows in (128, 256, 1024):
            sliced = prefill_chunk_jit.__wrapped__.lower(
                params, cfg, place(S(rows, dtype=i32)), place(S(dtype=i32)),
                place(S(dtype=i32)), cache).compile()
            assert "q4k_expert_matmul_manyrow" in sliced.as_text()
            assert "flash_attention_prefill_latent" in sliced.as_text()
            assert sliced.memory_analysis().temp_size_in_bytes \
                < 1024 * 2 ** 20 * max(rows, 256) // 256


@pytest.mark.parametrize("rows", [128, 256, 1024])
def test_latent_slice_program_holds_the_slice_kernel(one_chip, monkeypatch,
                                                     rows):
    """The admission slice into the scratch cache as a chip serves it
    (``latent_slice_kernel``: the probe passed): a bucket's remainder, the
    narrow and the wide slice of the ``gigachat`` shapes each hold the
    slice kernel, ONE custom call a layer loop whose operand is the scratch
    leaf in its own shape (which is how benchmarks/kernels/mla_attn.json
    finds it) after the XLA write of the slice's rows; the compiler has put
    no copy or transpose of the leaf around it, no block of the leaf is
    sliced out, and no score or accumulator tensor (heads x rows x a block
    of keys or the latent's 512, float32: the loop's ``mla_scores`` and
    ``mla_pv``) stands in HBM."""
    import re

    from llama_fastapi_k8s_gpu_tpu.models import mla
    from llama_fastapi_k8s_gpu_tpu.models.generate import prefill_chunk_jit
    from llama_fastapi_k8s_gpu_tpu.models.llama import init_cache
    from llama_fastapi_k8s_gpu_tpu.ops import pallas as pallas_ops

    monkeypatch.setattr(pallas_ops, "use_interpret", lambda: False)
    cfg, params, place = _gigachat(one_chip, latent_kernel=True,
                                   latent_slice_kernel=True)
    assert mla.slice_tile(cfg, rows) == 1024
    assert mla.slice_read(cfg, rows) == "kernel"
    cache = place(jax.eval_shape(lambda: init_cache(cfg)))
    compiled = prefill_chunk_jit.__wrapped__.lower(
        params, cfg, place(S(rows, dtype=i32)), place(S(dtype=i32)),
        place(S(dtype=i32)), cache).compile()
    text = compiled.as_text()
    assert "q4k_expert_matmul_manyrow" in text
    kernel = [ln for ln in text.splitlines()
              if re.match(r"\s*(ROOT )?%flash_attention_prefill_latent", ln)]
    # one call in the dense layers' loop, one in the routed layers'
    assert len(kernel) == 2, kernel
    operands = {m for ln in kernel
                for m in re.findall(r"%([\w.-]+)", ln.split("custom-call(")[1]
                                    .split(")")[0])}
    shapes = dict(re.findall(r"^\s*(?:ROOT )?%([\w.-]+) = (\S+?)[{ ]",
                             text, re.M))
    assert "bf16[7,1,16384,640]" in {shapes.get(o) for o in operands}, \
        sorted((o, shapes.get(o)) for o in operands)
    assert not _leaf_copies(text)
    assert "bf16[512,640]" not in text            # the loop's block slice
    assert re.search(
        r"= bf16\[7,1,16384,640\]\S* dynamic-update-slice\(", text)
    # (the absorbed query is heads x rows x 512 float32 on its way to
    # bf16, as it was: the one such tensor, by its scope's name)
    assert "mla_scores" not in text and "mla_pv" not in text
    wide = [ln.strip()[:200] for ln in text.splitlines() if re.match(
        r"\s*(ROOT )?%%\S+ = \(?f32\[64,%d,(512|1024)\]" % rows, ln)
        and "mla_absorb_q" not in ln]
    assert not wide, wide[:3]
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 1024 * 2 ** 20 * rows // 256


def _dsv32(one_chip, **flags):
    """BENCHMARK.json's ``deepseek-v3.2-exp`` configuration at its published
    widths: ``_gigachat``'s shapes at 128 heads (v 128), 1 dense + 5 routed
    layers, vocabulary 129280, and the indexer's tensors (64 heads of 128
    from the query latent, one index key of 128, its LayerNorm, the F32
    head weights), ``index_topk`` 2048."""
    cfg, params, place = _gigachat(one_chip, **flags)
    H, Hi, dI, r_q, D, V = 128, 64, 128, 1536, 7168, 129280
    cfg = dataclasses.replace(
        cfg, n_layers=6, n_heads=H, n_kv_heads=H, v_head_dim=128,
        vocab_size=V, rope_theta=1e4, rope_yarn_factor=40.0,
        index_heads=Hi, index_dim=dI, index_topk=2048)

    def stack(tree, L):
        t = jax.tree.map(lambda a: S(L, *a.shape[1:], dtype=a.dtype), tree)
        return {**t, "wq_b": {"w": S(L, H * 192, r_q)},
                "wo": _planes("q4k", D, H * 128, L),
                "w_uk": {"w": S(L, H, 128, 512)},
                "w_uv": {"w": S(L, H, 128, 512)},
                "idx_wq_b": {"w": S(L, Hi * dI, r_q)},
                "idx_wk": _planes("q6k", 128, 8192, L),
                "idx_k_norm": S(L, dI, dtype=f32),
                "idx_k_norm_b": S(L, dI, dtype=f32),
                "idx_proj": S(L, Hi, D, dtype=f32)}

    params = place({
        "tok_emb": S(V, D), "out_norm": S(D, dtype=f32),
        "output": _planes("q6k", V, 8192),
        "layers": {"dense": stack(params["layers"]["dense"], 1),
                   "moe": stack(params["layers"]["moe"], 5)}})
    return cfg, params, place


@pytest.mark.parametrize("name,lanes,rows", [
    ("dsv32-16lane-step", 16, 0), ("dsv32-narrow-slice", 0, 256),
    ("dsv32-wide-slice", 0, 1024)])
def test_indexed_latent_stack_compiles_with_the_selection_in_the_kernels(
        one_chip, monkeypatch, name, lanes, rows):
    """The lanes' decode chunk and an admission slice of the ``deepseek32``
    stack compile for the chip at 128 heads: the indexer's scores and the
    threshold search as plain XLA over the index-key leaf in its own shape
    (which is how benchmarks/dsa_roofline.py finds them), the attention as
    the two latent kernels WITH the selection's bias operand under names
    of their own, and the compiler has put no copy or transpose of either
    leaf around them; no per-head score tensor of the slice (64 heads x
    rows x a block of keys, float32) stands whole in HBM: a slice of any
    width is scored a group of heads at a time, ``INDEX_ROWS`` (head, query)
    rows at most."""
    import re

    from llama_fastapi_k8s_gpu_tpu.models import mla
    from llama_fastapi_k8s_gpu_tpu.models.generate import prefill_chunk_jit
    from llama_fastapi_k8s_gpu_tpu.models.llama import init_cache
    from llama_fastapi_k8s_gpu_tpu.ops import pallas as pallas_ops
    from llama_fastapi_k8s_gpu_tpu.parallel.batched import (
        batched_generate_chunk_perlane_jit, init_batched_state,
        init_lane_left)
    from llama_fastapi_k8s_gpu_tpu.sampling.sample import (
        SamplingParams, sampling_tensors)

    monkeypatch.setattr(pallas_ops, "use_interpret", lambda: False)
    cfg, params, place = _dsv32(one_chip, latent_kernel=True,
                                latent_slice_kernel=True)
    if lanes:
        st = sampling_tensors(SamplingParams())
        state = place(jax.eval_shape(lambda: init_batched_state(cfg, lanes)))
        st = place(jax.eval_shape(lambda: jax.tree.map(
            lambda a: jnp.broadcast_to(a, (lanes,)), st)))
        left = place(jax.eval_shape(lambda: init_lane_left(lanes)))
        compiled = batched_generate_chunk_perlane_jit.__wrapped__.lower(
            params, cfg, state, st, left, n_steps=8, top_k=40,
            live=place(S(lanes, dtype=jnp.bool_)), stop_ids=(2,)).compile()
        kernel, leaf = "flash_attention_decode_latent_select", \
            "bf16[16,6,1,16384,128]"
    else:
        assert mla.slice_tile(cfg, rows) == 1024
        cache = place(jax.eval_shape(lambda: init_cache(cfg)))
        assert set(cache) == {"lat", "idx"}
        compiled = prefill_chunk_jit.__wrapped__.lower(
            params, cfg, place(S(rows, dtype=i32)), place(S(dtype=i32)),
            place(S(dtype=i32)), cache).compile()
        kernel, leaf = "flash_attention_prefill_latent_select", \
            "bf16[6,1,16384,128]"
    text = compiled.as_text()
    calls = [ln for ln in text.splitlines()
             if re.match(r"\s*(ROOT )?%" + kernel, ln)]
    # one call in the dense layers' loop, one in the routed layers'
    assert len(calls) == 2, calls
    assert leaf in text and not _leaf_copies(text)
    # the per-head scores: (heads of a group, queries, a block of keys)
    # float32, at any width of the slice
    per_head = {(int(g), int(r)) for g, r in re.findall(
        r"= f32\[(?:16,)?(\d+),(\d+),%d\]\S* convolution\(" % mla.INDEX_BLOCK,
        text)}
    assert per_head and all(g * r <= mla.INDEX_ROWS for g, r in per_head), \
        per_head
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 1024 * 2 ** 20 * max(rows, 256) // 256


# ``k-exaone-236b-a23b-q4km-ep8-16lane`` (benchmarks/configs: 12 layers of
# kinds window window window global x 3, layer 0 dense + 11 routed holding 16
# of 128 experts), n_ctx 16384: (name, lanes)
@pytest.mark.parametrize("name,lanes", [("kexaone-serial", 0),
                                        ("kexaone-16lane", 16)])
def test_window_global_stack_compiles_with_no_ring_sized_copy(
        one_chip, monkeypatch, name, lanes):
    """The decode chunk and the prefill slices of the ``exaone-moe`` stack
    (models/hybrid.py) compile for the chip: the decode kernel on the
    global rings and, under its own name, on the window leaves that wrap;
    the flash kernel on a window layer's run of keys under its own name;
    the fused planes at K 6144 / 8192 / 18432 / 2048 with nothing padded;
    the grouped expert kernels.  The compiler has put NO copy or transpose
    of a global ring in the decode chunk, whose step holds no XLA update of
    the lanes' stacked leaves (the kernels store the rows)."""
    import re

    from llama_fastapi_k8s_gpu_tpu.models import hybrid
    from llama_fastapi_k8s_gpu_tpu.models.config import ModelConfig
    from llama_fastapi_k8s_gpu_tpu.models.generate import (
        generate_chunk_jit, init_state, prefill_chunk_jit)
    from llama_fastapi_k8s_gpu_tpu.models.llama import (
        cache_nbytes, init_cache, ring_write_impl)
    from llama_fastapi_k8s_gpu_tpu.ops import pallas as pallas_ops
    from llama_fastapi_k8s_gpu_tpu.parallel.batched import (
        batched_generate_chunk_perlane_jit, init_batched_state,
        init_lane_left)
    from llama_fastapi_k8s_gpu_tpu.sampling.sample import (
        SamplingParams, sampling_tensors)

    monkeypatch.setattr(pallas_ops, "use_interpret", lambda: False)
    D, V, H, KV, hd = 6144, 153600, 64, 8, 128
    F, Fe, E = 18432, 2048, 16
    cfg = ModelConfig(
        vocab_size=V, dim=D, n_layers=12, n_heads=H, n_kv_heads=KV,
        ffn_dim=F, n_ctx=16384, rope_theta=1e6, rms_eps=1e-5,
        attn_impl="pallas", sliding_window=128, head_width=hd,
        qk_norm_per_head=True, rope_neox=True,
        attn_kinds=("window", "window", "window", "global") * 3,
        rope_kinds=("window",), n_dense_layers=1, expert_ffn_dim=Fe,
        n_shared_experts=1, n_experts=128, n_experts_used=8,
        norm_topk_prob=True, expert_gating="sigmoid",
        expert_weights_scale=2.5, experts_first=0, experts_held=E)
    assert (hybrid.window_block(cfg), cfg.window_slots) == (128, 128)
    assert ring_write_impl(cfg) == "kernel"
    assert cache_nbytes(cfg) == 4096 * (3 * 16384 + 9 * 128)

    def exps(fmt, n, k, L):
        kt = k // 2048
        if fmt == "q4k":
            return {"qs": S(L, E, n, k // 2, dtype=i8),
                    "sm": S(L, E, kt, n, 128)}
        return {"q4": S(L, E, n, k // 2, dtype=i8),
                "q2": S(L, E, n, k // 4, dtype=i8),
                "sm6": S(L, E, kt, n, 128)}

    def attn(L):
        return {"attn_norm": S(L, D, dtype=f32), "ffn_norm": S(L, D, dtype=f32),
                "attn_q_norm": S(L, hd, dtype=f32),
                "attn_k_norm": S(L, hd, dtype=f32),
                "wq": _planes("q4k", H * hd, D, L),
                "wk": _planes("q4k", KV * hd, D, L),
                "wv": _planes("q6k", KV * hd, D, L),
                "wo": _planes("q4k", D, H * hd, L)}

    def place(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = place({
        "tok_emb": S(V, D), "out_norm": S(D, dtype=f32),
        "output": _planes("q6k", V, D),
        "layers": {
            "dense": {**attn(1), "w_gate": _planes("q4k", F, D, 1),
                      "w_up": _planes("q4k", F, D, 1),
                      "w_down": _planes("q6k", D, F, 1)},
            "moe": {**attn(11), "w_router": S(11, 128, D, dtype=f32),
                    "router_bias": S(11, 128, dtype=f32),
                    "w_gate_sh": _planes("q4k", Fe, D, 11),
                    "w_up_sh": _planes("q4k", Fe, D, 11),
                    "w_down_sh": _planes("q6k", D, Fe, 11),
                    "w_gate_exps": exps("q4k", Fe, D, 11),
                    "w_up_exps": exps("q4k", Fe, D, 11),
                    "w_down_exps": exps("q6k", D, Fe, 11)}}})
    st = sampling_tensors(SamplingParams())
    if lanes:
        state = place(jax.eval_shape(lambda: init_batched_state(cfg, lanes)))
        st = place(jax.eval_shape(lambda: jax.tree.map(
            lambda a: jnp.broadcast_to(a, (lanes,)), st)))
        left = place(jax.eval_shape(lambda: init_lane_left(lanes)))
        lowered = batched_generate_chunk_perlane_jit.__wrapped__.lower(
            params, cfg, state, st, left, n_steps=8, top_k=40,
            live=place(S(lanes, dtype=jnp.bool_)), stop_ids=(2,))
    else:
        state = place(jax.eval_shape(lambda: init_state(cfg)))
        lowered = generate_chunk_jit.__wrapped__.lower(
            params, cfg, state, place(jax.eval_shape(lambda: st)),
            n_steps=8, top_k=40)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert "flash_attention_decode_window" in text
    assert re.search(r"flash_attention_decode[^_]", text)
    assert "q4k_expert_matmul_fewrow" in text
    assert "q6k_expert_matmul_fewrow" in text
    leaf_op = re.compile(
        r"^\s*(ROOT )?%\S+ = bf16\[(\d+,)*16384,128\]\S* "
        r"(copy|transpose|dynamic-update-slice)\(")
    fused = re.compile(r"^%fused_computation")
    found, in_fusion = [], False
    for ln in text.splitlines():
        if ln.startswith(("%", "ENTRY")):
            in_fusion = bool(fused.match(ln))
        if not in_fusion and leaf_op.search(ln):
            found.append(ln.strip()[:120])
    assert not found, found[:4]
    assert compiled.memory_analysis().temp_size_in_bytes < 512 * 2 ** 20
    if not lanes:       # the admission slices into the scratch cache
        cache = place(jax.eval_shape(lambda: init_cache(cfg)))
        assert _slice_widths(cfg) == [256, 1024]
        for rows in _slice_widths(cfg):     # narrow, and the wide slice
            sliced = prefill_chunk_jit.__wrapped__.lower(
                params, cfg, place(S(rows, dtype=i32)), place(S(dtype=i32)),
                place(S(dtype=i32)), cache).compile()
            stext = sliced.as_text()
            assert "flash_attention_window" in stext
            assert "q4k_expert_matmul_manyrow" in stext
            assert sliced.memory_analysis().temp_size_in_bytes \
                < 1024 * 2 ** 20 * rows // 256


# LFM2-24B-A2B's first pipeline stage (benchmarks/configs/lfm2-24b-a2b-q4km-
# l20-16lane.json: 20 layers conv conv attn conv x 5, 2 dense + 18 routed of
# 64 experts all held), n_ctx 16384: (name, lanes)
@pytest.mark.parametrize("name,lanes", [("shortconv-serial", 0),
                                        ("shortconv-16lane", 16)])
def test_shortconv_stack_compiles_with_no_ring_sized_copy(
        one_chip, monkeypatch, name, lanes):
    """The decode chunk (0 and 16 lanes) and both prefill slice widths of
    the ``lfm2moe`` stack (models/lfm2.py) compile for the chip: the decode
    kernel (it stores the step's row) and the flash kernel on a ring whose
    rows hold two heads of 64 side by side; the grouped expert kernels with
    the down projection's K = 1536 filled up to 2048, few-row and many-row;
    the dense down projection's K = 11776 filled up to 12288.  The compiler has put NO copy or transpose of a ring in the
    decode chunk, whose step holds no XLA update of the lanes' stacked
    rings (the kernel stores the rows)."""
    import re

    from llama_fastapi_k8s_gpu_tpu.models import lfm2
    from llama_fastapi_k8s_gpu_tpu.models.generate import (
        generate_chunk_jit, init_state, prefill_chunk_jit)
    from llama_fastapi_k8s_gpu_tpu.models.llama import (
        init_cache, ring_write_impl)
    from llama_fastapi_k8s_gpu_tpu.ops import pallas as pallas_ops
    from llama_fastapi_k8s_gpu_tpu.parallel.batched import (
        batched_generate_chunk_perlane_jit, init_batched_state,
        init_lane_left)
    from llama_fastapi_k8s_gpu_tpu.sampling.sample import (
        SamplingParams, sampling_tensors)
    from tests.test_lfm2 import published_cfg

    monkeypatch.setattr(pallas_ops, "use_interpret", lambda: False)
    import dataclasses

    cfg = dataclasses.replace(published_cfg(), attn_impl="pallas")
    D, V, F, Fe, E, hd = 2048, 65536, 11776, 1536, 64, 64
    assert lfm2.CACHE.decode_kernel_block(cfg) == 512
    assert ring_write_impl(cfg) == "kernel"

    def exps(fmt, n, k, L):       # K as stored: experts.py ``padded_k``
        k = -(-k // 2048) * 2048
        kt = k // 2048
        if fmt == "q4k":
            return {"qs": S(L, E, n, k // 2, dtype=i8),
                    "sm": S(L, E, kt, n, 128)}
        return {"q4": S(L, E, n, k // 2, dtype=i8),
                "q2": S(L, E, n, k // 4, dtype=i8),
                "sm6": S(L, E, kt, n, 128)}

    def place(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    emb = S(V, D)
    params = place({
        "tok_emb": emb, "out_norm": S(D, dtype=f32), "output": {"w": emb},
        "layers": {
            "conv": {"attn_norm": S(15, D, dtype=f32),
                     "conv": S(15, D, 3, dtype=f32),
                     "in_proj": _planes("q4k", 3 * D, D, 15),
                     "out_proj": _planes("q4k", D, D, 15)},
            "attn": {"attn_norm": S(5, D, dtype=f32),
                     "attn_q_norm": S(5, hd, dtype=f32),
                     "attn_k_norm": S(5, hd, dtype=f32),
                     "wq": _planes("q4k", D, D, 5),
                     "wk": _planes("q4k", 8 * hd, D, 5),
                     "wv": _planes("q6k", 8 * hd, D, 5),
                     "wo": _planes("q4k", D, D, 5)},
            "dense": {"ffn_norm": S(2, D, dtype=f32),
                      "w_gate": _planes("q4k", F, D, 2),
                      "w_up": _planes("q4k", F, D, 2),
                      "w_down": _planes("q6k", D, 12288, 2)},
            "moe": {"ffn_norm": S(18, D, dtype=f32),
                    "w_router": S(18, E, D, dtype=f32),
                    "router_bias": S(18, E, dtype=f32),
                    "w_gate_exps": exps("q4k", Fe, D, 18),
                    "w_up_exps": exps("q4k", Fe, D, 18),
                    "w_down_exps": exps("q6k", D, Fe, 18)}}})
    st = sampling_tensors(SamplingParams())
    if lanes:
        state = place(jax.eval_shape(lambda: init_batched_state(cfg, lanes)))
        st = place(jax.eval_shape(lambda: jax.tree.map(
            lambda a: jnp.broadcast_to(a, (lanes,)), st)))
        left = place(jax.eval_shape(lambda: init_lane_left(lanes)))
        lowered = batched_generate_chunk_perlane_jit.__wrapped__.lower(
            params, cfg, state, st, left, n_steps=8, top_k=40,
            live=place(S(lanes, dtype=jnp.bool_)), stop_ids=(2,))
    else:
        state = place(jax.eval_shape(lambda: init_state(cfg)))
        lowered = generate_chunk_jit.__wrapped__.lower(
            params, cfg, state, place(jax.eval_shape(lambda: st)),
            n_steps=8, top_k=40)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert re.search(r"flash_attention_decode[^_]", text)
    assert "q4k_expert_matmul_fewrow" in text
    assert "q6k_expert_matmul_fewrow" in text
    leaf_op = re.compile(
        r"^\s*(ROOT )?%\S+ = bf16\[(\d+,)*16384,128\]\S* "
        r"(copy|transpose|dynamic-update-slice)\(")
    fused = re.compile(r"^%fused_computation")
    found, in_fusion = [], False
    for ln in text.splitlines():
        if ln.startswith(("%", "ENTRY")):
            in_fusion = bool(fused.match(ln))
        if not in_fusion and leaf_op.search(ln):
            found.append(ln.strip()[:120])
    assert not found, found[:4]
    assert compiled.memory_analysis().temp_size_in_bytes < 512 * 2 ** 20
    if not lanes:       # the admission slices into the scratch cache
        cache = place(jax.eval_shape(lambda: init_cache(cfg)))
        assert _slice_widths(cfg) == [256, 1024]
        for rows in _slice_widths(cfg):     # narrow, and the wide slice
            sliced = prefill_chunk_jit.__wrapped__.lower(
                params, cfg, place(S(rows, dtype=i32)), place(S(dtype=i32)),
                place(S(dtype=i32)), cache).compile()
            stext = sliced.as_text()
            assert "flash_attention" in stext
            assert "q4k_expert_matmul_manyrow" in stext
            assert "q6k_expert_matmul_manyrow" in stext
            print("slice", rows, "temporaries",
                  sliced.memory_analysis().temp_size_in_bytes)
            assert sliced.memory_analysis().temp_size_in_bytes \
                < 1024 * 2 ** 20 * rows // 256


# Phi-4-mini-flash-reasoning whole (benchmarks/configs/phi4-mini-flash-3.8b-
# q4km-16lane.json: 32 layers, hidden 2560, 16 lanes of 32768 positions):
# (name, lanes)
@pytest.mark.parametrize("name,lanes", [("phi4flash-serial", 0),
                                        ("phi4flash-16lane", 16)])
def test_phi4flash_stack_compiles_with_no_ring_sized_copy(
        one_chip, monkeypatch, name, lanes):
    """The decode chunk (0 and 16 lanes) and, for both slice widths, BOTH
    prefill programs (the whole stack, and the one that stops after the
    full-attention layer) of the ``phi4flash`` stack (models/phi4flash.py)
    compile for the chip: the scan kernel on the slices, the decode kernel
    in its ``wrap`` form on the window leaves and per lane on the ONE shared
    leaf (eight calls a step), the flash kernel on rows of two 64-wide heads
    side by side, every fused matmul at K 2560 filled up to 4096 and K 5120
    to 6144, the tied Q6_K head on 200064 rows and the embedding rows
    dequantized from its planes.  The compiler has put NO copy or transpose
    of the shared leaf in the decode chunk."""
    import dataclasses
    import re

    from llama_fastapi_k8s_gpu_tpu.models import phi4flash
    from llama_fastapi_k8s_gpu_tpu.models.generate import (
        generate_chunk_jit, init_state, prefill_chunk_jit)
    from llama_fastapi_k8s_gpu_tpu.models.llama import (
        init_cache, ring_write_impl)
    from llama_fastapi_k8s_gpu_tpu.ops import pallas as pallas_ops
    from llama_fastapi_k8s_gpu_tpu.parallel.batched import (
        batched_generate_chunk_perlane_jit, init_batched_state,
        init_lane_left)
    from llama_fastapi_k8s_gpu_tpu.sampling.sample import (
        SamplingParams, sampling_tensors)
    from tests.test_phi4flash import published_cfg

    monkeypatch.setattr(pallas_ops, "use_interpret", lambda: False)
    cfg = dataclasses.replace(published_cfg(), attn_impl="pallas",
                              ssm_scan_kernel=True)
    D, V, F, C, N, R, hd = 2560, 200064, 10240, 5120, 16, 160, 64
    Dk, Ck = D, C       # as stored (ops.linear ``padded_k``): each ends in a tail
    assert phi4flash.CACHE.decode_kernel_block(cfg) == 128
    assert ring_write_impl(cfg) == "kernel"

    def place(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    def norm(L):
        return {"attn_norm": S(L, D, dtype=f32),
                "attn_norm_b": S(L, D, dtype=f32)}

    def lams(L):
        return {**{f"lam_{k}": S(L, hd, dtype=f32)
                   for k in ("q1", "k1", "q2", "k2")},
                "sub_norm": S(L, 2 * hd, dtype=f32)}

    head = _planes("q6k", V, Dk)
    params = place({
        "tok_emb": head, "output": head, "out_norm": S(D, dtype=f32),
        "out_norm_b": S(D, dtype=f32),
        "layers": {
            "ssm": {**norm(9), "in_proj": _planes("q4k", 2 * C, Dk, 9),
                    "out_proj": _planes("q4k", D, Ck, 9),
                    "x_proj": {"w": S(9, R + 2 * N, C)},
                    "conv": S(9, C, 4, dtype=f32),
                    "conv_b": S(9, C, dtype=f32),
                    "dt_proj": S(9, C, R, dtype=f32),
                    "dt_b": S(9, C, dtype=f32), "a": S(9, N, C, dtype=f32),
                    "d": S(9, C, dtype=f32)},
            "attn": {**norm(9), **lams(9),
                     "wq": _planes("q4k", D, Dk, 9),
                     "wk": _planes("q4k", D // 2, Dk, 9),
                     "wv": _planes("q6k", D // 2, Dk, 9),
                     "wo": _planes("q4k", D, Dk, 9),
                     "bq": S(9, D, dtype=f32), "bk": S(9, D // 2, dtype=f32),
                     "bv": S(9, D // 2, dtype=f32), "bo": S(9, D, dtype=f32)},
            "gmu": {**norm(7), "in_proj": _planes("q4k", C, Dk, 7),
                    "out_proj": _planes("q4k", D, Ck, 7)},
            "cross": {**norm(7), **lams(7),
                      "wq": _planes("q4k", D, Dk, 7),
                      "wo": _planes("q4k", D, Dk, 7),
                      "bq": S(7, D, dtype=f32), "bo": S(7, D, dtype=f32)},
            "ffn": {"ffn_norm": S(32, D, dtype=f32),
                    "ffn_norm_b": S(32, D, dtype=f32),
                    "w_gate": _planes("q4k", F, Dk, 32),
                    "w_up": _planes("q4k", F, Dk, 32),
                    "w_down": _planes("q6k", D, F, 32)}}})
    st = sampling_tensors(SamplingParams())
    if lanes:
        state = place(jax.eval_shape(lambda: init_batched_state(cfg, lanes)))
        st = place(jax.eval_shape(lambda: jax.tree.map(
            lambda a: jnp.broadcast_to(a, (lanes,)), st)))
        left = place(jax.eval_shape(lambda: init_lane_left(lanes)))
        lowered = batched_generate_chunk_perlane_jit.__wrapped__.lower(
            params, cfg, state, st, left, n_steps=8, top_k=40,
            live=place(S(lanes, dtype=jnp.bool_)), stop_ids=(2,))
    else:
        state = place(jax.eval_shape(lambda: init_state(cfg)))
        lowered = generate_chunk_jit.__wrapped__.lower(
            params, cfg, state, place(jax.eval_shape(lambda: st)),
            n_steps=8, top_k=40)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert "flash_attention_decode_window" in text
    assert re.search(r"flash_attention_decode[^_]", text)
    leaf_op = re.compile(
        r"^\s*(ROOT )?%\S+ = bf16\[(\d+,)*32768,128\]\S* "
        r"(copy|transpose|dynamic-update-slice)\(")
    fused = re.compile(r"^%fused_computation")
    found, in_fusion = [], False
    for ln in text.splitlines():
        if ln.startswith(("%", "ENTRY")):
            in_fusion = bool(fused.match(ln))
        if not in_fusion and leaf_op.search(ln):
            found.append(ln.strip()[:120])
    assert not found, found[:4]
    print(name, "temporaries", compiled.memory_analysis().temp_size_in_bytes)
    assert compiled.memory_analysis().temp_size_in_bytes < 1024 * 2 ** 20
    if not lanes:       # the admission slices into the scratch cache
        cache = place(jax.eval_shape(lambda: init_cache(cfg)))
        assert _slice_widths(cfg) == [256, 1024]
        lower = phi4flash.CACHE.slice_cfg(cfg, False)
        for rows in _slice_widths(cfg):     # narrow, and the wide slice
            for scfg in (cfg, lower):       # the whole stack, and 18 of 32
                sliced = prefill_chunk_jit.__wrapped__.lower(
                    params, scfg, place(S(rows, dtype=i32)),
                    place(S(dtype=i32)), place(S(dtype=i32)), cache).compile()
                stext = sliced.as_text()
                assert "ssm_scan" in stext and "flash_attention" in stext
                print("slice", rows, "lower" if scfg.lower_only else "whole",
                      "temporaries",
                      sliced.memory_analysis().temp_size_in_bytes)
                assert sliced.memory_analysis().temp_size_in_bytes \
                    < 1536 * 2 ** 20 * rows // 256



def jamba_published_cfg():
    from llama_fastapi_k8s_gpu_tpu.models.config import ModelConfig

    return ModelConfig(
        vocab_size=65536, dim=2560, n_layers=28, n_heads=20, n_kv_heads=1,
        ffn_dim=8192, n_ctx=262144, rms_eps=1e-6, head_width=128,
        mixers=tuple("attn" if i % 14 == 7 else "ssm" for i in range(28)),
        ssm_d_inner=5120, ssm_d_state=16, ssm_d_conv=4, ssm_dt_rank=160,
        ssm_inner_norms=True, tie_embeddings=True)


# AI21-Jamba2-3B whole (benchmarks/configs/jamba2-3b-q4km-16lane.json: 28
# layers, hidden 2560, 16 lanes of 262144 positions): (name, lanes)
@pytest.mark.parametrize("name,lanes", [("jamba-serial", 0),
                                        ("jamba-16lane", 16)])
def test_jamba_stack_compiles_with_no_ring_sized_copy(
        one_chip, monkeypatch, name, lanes):
    """The decode chunk (0 and 16 lanes) and both slice widths' prefill
    program of the ``jamba`` stack (models/jamba.py) compile for the chip:
    the scan kernel on the slices of 26 layers, the decode kernel per lane
    at 20 query rows (padded to 32) on ONE K/V head, the flash kernel with
    one step on its head axis and a key axis that ENDS at the slice's end (a
    traced grid extent on the ring of 262144 slots), every fused matmul at
    K 2560 / 5120 with a tail tile and K 8192 whole, the tied Q6_K head on
    65536 rows and the embedding rows dequantized from its planes.  The
    compiler has put NO copy or transpose of a ring in the decode chunk."""
    import dataclasses
    import re

    from llama_fastapi_k8s_gpu_tpu.models import jamba
    from llama_fastapi_k8s_gpu_tpu.models.generate import (
        generate_chunk_jit, init_state, prefill_chunk_jit)
    from llama_fastapi_k8s_gpu_tpu.models.llama import (
        init_cache, ring_write_impl)
    from llama_fastapi_k8s_gpu_tpu.ops import pallas as pallas_ops
    from llama_fastapi_k8s_gpu_tpu.ops.pallas.attention import flash_plan
    from llama_fastapi_k8s_gpu_tpu.parallel.batched import (
        batched_generate_chunk_perlane_jit, init_batched_state,
        init_lane_left)
    from llama_fastapi_k8s_gpu_tpu.sampling.sample import (
        SamplingParams, sampling_tensors)

    monkeypatch.setattr(pallas_ops, "use_interpret", lambda: False)
    cfg = dataclasses.replace(jamba_published_cfg(), attn_impl="pallas",
                              ssm_scan_kernel=True)
    D, V, F, C, N, R = 2560, 65536, 8192, 5120, 16, 160
    assert jamba.CACHE.decode_kernel_block(cfg) == 512
    assert ring_write_impl(cfg) == "kernel"
    assert flash_plan(1024, 20, 1, cfg.n_ctx)["bounded"]
    assert jamba.cache_nbytes(cfg) == 277753856

    def place(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    head = _planes("q6k", V, D)
    params = place({
        "tok_emb": head, "output": head, "out_norm": S(D, dtype=f32),
        "layers": {
            "ssm": {"attn_norm": S(26, D, dtype=f32),
                    "in_proj": _planes("q4k", 2 * C, D, 26),
                    "out_proj": _planes("q4k", D, C, 26),
                    "x_proj": {"w": S(26, R + 2 * N, C)},
                    "conv": S(26, C, 4, dtype=f32),
                    "conv_b": S(26, C, dtype=f32),
                    "dt_norm": S(26, R, dtype=f32),
                    "b_norm": S(26, N, dtype=f32),
                    "c_norm": S(26, N, dtype=f32),
                    "dt_proj": S(26, C, R, dtype=f32),
                    "dt_b": S(26, C, dtype=f32), "a": S(26, N, C, dtype=f32),
                    "d": S(26, C, dtype=f32)},
            "attn": {"attn_norm": S(2, D, dtype=f32),
                     "wq": _planes("q4k", D, D, 2),
                     "wk": _planes("q4k", 128, D, 2),
                     "wv": _planes("q6k", 128, D, 2),
                     "wo": _planes("q4k", D, D, 2)},
            "ffn": {"ffn_norm": S(28, D, dtype=f32),
                    "w_gate": _planes("q4k", F, D, 28),
                    "w_up": _planes("q4k", F, D, 28),
                    "w_down": _planes("q6k", D, F, 28)}}})
    st = sampling_tensors(SamplingParams())
    if lanes:
        state = place(jax.eval_shape(lambda: init_batched_state(cfg, lanes)))
        st = place(jax.eval_shape(lambda: jax.tree.map(
            lambda a: jnp.broadcast_to(a, (lanes,)), st)))
        left = place(jax.eval_shape(lambda: init_lane_left(lanes)))
        lowered = batched_generate_chunk_perlane_jit.__wrapped__.lower(
            params, cfg, state, st, left, n_steps=8, top_k=40,
            live=place(S(lanes, dtype=jnp.bool_)), stop_ids=(2,))
    else:
        state = place(jax.eval_shape(lambda: init_state(cfg)))
        lowered = generate_chunk_jit.__wrapped__.lower(
            params, cfg, state, place(jax.eval_shape(lambda: st)),
            n_steps=8, top_k=40)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert re.search(r"flash_attention_decode[^_]", text)
    leaf_op = re.compile(
        r"^\s*(ROOT )?%\S+ = bf16\[(\d+,)*262144,128\]\S* "
        r"(copy|transpose|dynamic-update-slice)\(")
    fused = re.compile(r"^%fused_computation")
    found, in_fusion = [], False
    for ln in text.splitlines():
        if ln.startswith(("%", "ENTRY")):
            in_fusion = bool(fused.match(ln))
        if not in_fusion and leaf_op.search(ln):
            found.append(ln.strip()[:120])
    assert not found, found[:4]
    print(name, "temporaries", compiled.memory_analysis().temp_size_in_bytes)
    assert compiled.memory_analysis().temp_size_in_bytes < 1024 * 2 ** 20
    if not lanes:       # the admission slices into the scratch cache
        cache = place(jax.eval_shape(lambda: init_cache(cfg)))
        assert _slice_widths(cfg) == [256, 1024]
        for rows in _slice_widths(cfg):     # narrow, and the wide slice
            sliced = prefill_chunk_jit.__wrapped__.lower(
                params, cfg, place(S(rows, dtype=i32)), place(S(dtype=i32)),
                place(S(dtype=i32)), cache).compile()
            stext = sliced.as_text()
            assert "ssm_scan" in stext and "flash_attention" in stext
            print("slice", rows, "temporaries",
                  sliced.memory_analysis().temp_size_in_bytes)
            assert sliced.memory_analysis().temp_size_in_bytes \
                < 1536 * 2 ** 20 * rows // 256
