"""The routed block (``olmoe``: QK-norm, a float32 router, sparse SwiGLU
experts) against its plain float32 reference (``benchmarks/
reference_routed.py``), on the CPU at a tiny size: 2 layers, hidden 256, 4
heads, 8 experts of width 256, 2 per token, every tensor type of the Q4_K_M
mix, seeded random weights.  Logits, never tokens.

At this size a token gives each of its 2 experts a weight of 0.2-0.5, so a
near-tie that a bfloat16 hidden state orders the other way moves that
position's logits, and through attention every later one's, by tens of per
cent: that is the size, not the arithmetic (the reference with only its
matmul inputs rounded to bfloat16 then reads 2-11 %, by the seed).  So the
two are held apart here: the LOGITS are compared with the reference sent to
the experts the program picked (``use_picks``: the reference's own
probabilities, no pick of its own overridden unseen: the picks are compared
beside), and the PICKS with the reference's own wherever they are no
near-tie.  At the published size (8 of 64, an unnormalised last weight of
0.02) ``benchmarks/compare_routed.py`` compares every position unaided.

Tolerances, with their reasons:

- ``LOGITS["bf16"]`` 3 %: the program multiplies in bfloat16 (2^-9 relative
  rounding per operand) and keeps activations in bfloat16 between layers:
  1.4-1.6 % of the logits' norm over 40 positions on four weight seeds, up
  to 2.2 % over the ten-odd positions one lane decodes.  The interleaved
  RoPE pairing on the same weights reads 70-85 % (tested below).
- ``LOGITS["q4k"]`` 7 %: the experts are the fused K-quant planes (bf16
  products of exact integers and bf16 scales: as bf16), but the attention
  matrices, too narrow to fuse at this size (K = 256), load as int8
  per-row requants with per-row int8 activations: 4.2-4.6 % measured over
  40 positions on four weight seeds, up to 6.3 % over the six positions one
  lane decodes.  At the published size every matrix fuses and nothing is
  requantized.  The reference with its matmul inputs rounded to float8
  reads 14.8 %, one without each token's last pick over 30 %, the program
  under the interleaved RoPE pairing 70-85 % (all tested below): either
  limit fails all three.
- ``SAME``: two paths of the PROGRAM over the same weights and rows (fused
  against dequantized experts) differ by bf16 rounding of one layer's
  products alone: under 2 %.
- picks: exact where the reference's last pick leads the first one not
  picked by more than ``GAP`` (relative).
"""

from __future__ import annotations

import functools
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")

LOGITS = {"bf16": 3e-2, "q4k": 7e-2}
SAME = 2e-2
GAP = 0.3
N_CTX = 64


@pytest.fixture(scope="module")
def ref():
    """The benchmark's copy of the reference (``benchmarks/`` is not a
    package: its files import each other by bare name)."""
    sys.path.insert(0, BENCH)
    try:
        import reference_routed
        yield reference_routed
    finally:
        sys.path.remove(BENCH)


@pytest.fixture(scope="module")
def gguf_path(tmp_path_factory):
    from llama_fastapi_k8s_gpu_tpu.testing import write_tiny_olmoe_gguf

    path = str(tmp_path_factory.mktemp("olmoe") / "tiny.gguf")
    write_tiny_olmoe_gguf(path, seed=3)
    return path


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(5).integers(0, 256, size=40)


@pytest.fixture(scope="module")
def want(ref, gguf_path, tokens):
    """(logits (S, V), per layer (probabilities, picks)) of the reference's
    full forward pass."""
    hp, tensors = ref.open_model(gguf_path)
    logits, routed = ref.forward(hp, tensors, tokens)
    return np.asarray(logits), routed


@pytest.fixture(scope="module")
def loaded(gguf_path):
    """{fmt: (params, cfg)} through the program's loader."""
    from llama_fastapi_k8s_gpu_tpu.gguf import GGUFFile
    from llama_fastapi_k8s_gpu_tpu.models.config import ModelConfig
    from llama_fastapi_k8s_gpu_tpu.models.params import load_params

    gf = GGUFFile(gguf_path)
    cfg = ModelConfig.from_gguf(gf, n_ctx=N_CTX)
    return {fmt: (load_params(gf, cfg, fmt=fmt), cfg)
            for fmt in ("bf16", "q4k")}


def rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def reference_with(ref, gguf_path, tokens, picks):
    """The reference's logits with every token sent to ``picks`` (L, S, k)."""
    hp, tensors = ref.open_model(gguf_path)
    return np.asarray(ref.forward(hp, tensors, tokens,
                                  use_picks=list(np.asarray(picks)))[0])


# ---------------------------------------------------------------------------
# the file: 3-D expert tensors, the architecture by name
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("gtype,tol", [
    ("F32", 0.0), ("F16", 1e-3), ("Q8_0", 1e-2), ("Q6_K", 2e-2),
    ("Q4_K", 8e-2)])
def test_expert_tensor_of_rank_3_survives_write_and_read(tmp_path, gtype, tol):
    """(E, out, in) goes to the file as ggml shape (in, out, E) and comes
    back; ``tol`` is the type's own quantization step."""
    from llama_fastapi_k8s_gpu_tpu.gguf import GGMLType, GGUFFile, GGUFWriter

    w = np.random.default_rng(0).standard_normal((3, 8, 256)).astype(
        np.float32)
    path = str(tmp_path / "e.gguf")
    wr = GGUFWriter(path)
    wr.add_metadata("general.architecture", "olmoe")
    wr.add_tensor("blk.0.ffn_gate_exps.weight", w, GGMLType[gtype])
    wr.write()
    t = GGUFFile(path)["blk.0.ffn_gate_exps.weight"]
    assert t.shape == (256, 8, 3) and t.ggml_type == GGMLType[gtype]
    back = t.astype_f32()
    assert back.shape == w.shape
    assert rel(back, w) <= tol


@pytest.mark.parametrize("arch,served", [
    ("llama", True), ("mistral", True), ("olmoe", True),
    ("qwen2moe", False), ("mamba", False)])
def test_an_architecture_the_program_does_not_serve_is_refused_by_name(
        tmp_path, arch, served):
    from llama_fastapi_k8s_gpu_tpu.gguf import GGUFFile, GGUFWriter
    from llama_fastapi_k8s_gpu_tpu.models.config import ModelConfig

    path = str(tmp_path / "a.gguf")
    wr = GGUFWriter(path)
    wr.add_metadata("general.architecture", arch)
    for key, v in (("block_count", 1), ("embedding_length", 64),
                   ("feed_forward_length", 64), ("attention.head_count", 2),
                   ("vocab_size", 10)):
        wr.add_metadata(f"{arch}.{key}", v)
    wr.write()
    gf = GGUFFile(path)
    if served:
        assert gf.require_served() == arch
        cfg = ModelConfig.from_gguf(gf)
        assert cfg.qk_norm == (arch == "olmoe")
        # llama.cpp's converter permutes Q/K of a ``llama`` file to the
        # interleaved pairs and leaves an ``olmoe`` file's as published
        assert cfg.rope_neox == (arch == "olmoe")
    else:
        with pytest.raises(ValueError, match=f"'{arch}' is not served"):
            ModelConfig.from_gguf(gf)


def test_config_reads_the_experts_and_the_qk_norm(loaded):
    _, cfg = loaded["bf16"]
    assert (cfg.n_experts, cfg.n_experts_used, cfg.ffn_dim) == (8, 2, 256)
    assert cfg.qk_norm and cfg.rope_neox and not cfg.norm_topk_prob
    assert cfg.n_linear_weights == 2 * (4 * 256 * 256 + 3 * 256 * 256 * 8)


@pytest.mark.parametrize("fmt,gate_keys,down_keys", [
    ("bf16", {"w"}, {"w"}), ("q4k", {"qs", "sm"}, {"q4", "q2", "sm6"})])
def test_expert_tensors_load_with_a_layer_and_expert_axis(
        loaded, fmt, gate_keys, down_keys):
    """Fused planes keep the file's blocks (no dequantized copy) under
    (L, E, ...); the bf16 load is the fallback the CPU tests compare with."""
    layers = loaded[fmt][0]["layers"]
    assert set(layers["w_gate_exps"]) == gate_keys
    assert set(layers["w_down_exps"]) == down_keys
    for leaf in layers["w_gate_exps"].values():
        assert leaf.shape[:2] == (2, 8)
    assert layers["w_router"].shape == (2, 8, 256)
    assert layers["w_router"].dtype == np.float32
    assert layers["attn_q_norm"].shape == (2, 256)
    assert "w_gate" not in layers
    if fmt == "q4k":       # K = 256 folds 8 output rows into one of 2048
        assert layers["w_gate_exps"]["qs"].shape == (2, 8, 256 // 8, 1024)
        assert layers["w_gate_exps"]["qs"].dtype == np.int8


@pytest.mark.parametrize("gtype", ["Q4_K", "Q6_K"])
def test_expert_planes_are_the_dense_planes_of_each_expert(gtype):
    """``prep_experts`` reshapes what the dense packer makes of the same
    bytes: expert ``e``'s planes equal the dense prep of its own rows."""
    from llama_fastapi_k8s_gpu_tpu.gguf import GGMLType, quants
    from llama_fastapi_k8s_gpu_tpu.ops.pallas.experts import prep_experts
    from llama_fastapi_k8s_gpu_tpu.ops.pallas.q6matmul import prep_q6k
    from llama_fastapi_k8s_gpu_tpu.ops.pallas.qmatmul import prep_q4k

    E, N, K = 3, 16, 2048
    w = np.random.default_rng(1).standard_normal((E, N, K)).astype(np.float32)
    raw = np.asarray(quants.quantize(w, GGMLType[gtype]))
    planes = prep_experts(raw, E, N, K, GGMLType[gtype])
    per = raw.size // E
    dense = prep_q4k if gtype == "Q4_K" else prep_q6k
    for e in range(E):
        one = dense(raw[e * per:(e + 1) * per], N, K)
        for key, plane in one.items():
            np.testing.assert_array_equal(
                np.asarray(planes[key][e], np.float32),
                np.asarray(plane, np.float32))


def test_an_expert_type_without_a_grouped_kernel_is_not_fused():
    from llama_fastapi_k8s_gpu_tpu.gguf import GGMLType
    from llama_fastapi_k8s_gpu_tpu.ops.pallas.experts import (
        experts_compatible, fold_factor, prep_experts)

    assert prep_experts(np.zeros(1, np.uint8), 2, 16, 2048,
                        GGMLType.Q8_0) is None
    assert [fold_factor(k) for k in (256, 1024, 2048, 4096, 768)] \
        == [8, 2, 1, 1, 1]
    assert experts_compatible(2048, 1024, for_tpu=True)      # OLMoE down
    assert experts_compatible(1024, 2048, for_tpu=True)      # gate, up
    assert not experts_compatible(1024, 768, for_tpu=True)


# ---------------------------------------------------------------------------
# rows -> tiles
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_tokens,k,E,tm,dead", [
    (8, 8, 64, 16, ()), (8, 8, 64, 16, (0, 3, 7)), (1, 2, 8, 16, ()),
    (80, 2, 8, 128, (5,)), (3, 2, 4, 16, (0, 1, 2))])
def test_plan_groups_gives_every_row_a_slot_in_a_tile_of_its_expert(
        n_tokens, k, E, tm, dead):
    from llama_fastapi_k8s_gpu_tpu.ops.pallas.experts import (
        n_tiles, plan_groups)

    rng = np.random.default_rng(n_tokens * 7 + k)
    picks = np.stack([rng.permutation(E)[:k] for _ in range(n_tokens)])
    picks[list(dead)] = E
    rows = picks.reshape(-1).astype(np.int32)
    plan = {key: np.asarray(v)
            for key, v in plan_groups(rows, E, n_tokens, tm).items()}
    T = n_tiles(rows.size, E, n_tokens, tm)
    assert plan["tile_expert"].shape == (T,)
    live = rows < E
    np.testing.assert_array_equal(plan["count"],
                                  np.bincount(rows[live], minlength=E)[:E])
    assert plan["n_used"] == sum(-(-c // tm) for c in plan["count"])
    assert (plan["pos"][~live] == T * tm).all()
    slots = plan["pos"][live]
    assert len(set(slots.tolist())) == live.sum()        # no two rows share
    np.testing.assert_array_equal(plan["tile_expert"][slots // tm],
                                  rows[live])            # its expert's tile
    np.testing.assert_array_equal(plan["src"][slots], np.nonzero(live)[0])
    assert (plan["src"] == rows.size).sum() == T * tm - live.sum()
    if plan["n_used"]:       # tiles past the last one in use repeat it
        assert (plan["tile_expert"][plan["n_used"]:]
                == plan["tile_expert"][plan["n_used"] - 1]).all()


@pytest.mark.parametrize("rows,slots", [
    ([3, 3, 0, 7, 8, 8, 1], 4),        # 8 = no expert
    ([8, 8, 8], 3), ([5], 1), ([0, 1, 2, 3, 4, 5, 6, 7], 8),
    # the extent of the slot axis: none, one, a part and all of the slots
    ([8] * 12, 8), ([2] * 12, 8), ([6, 8, 1, 6, 1, 8, 4, 4, 1], 8),
    ([7, 6, 5, 4, 3, 2, 1, 0, 0, 7], 8)])
def test_experts_in_use_lists_the_distinct_experts_once(rows, slots):
    """The few-row regime's slots: one per distinct expert, rising, idle
    slots repeating the last so that they move no block; the grid's slot
    axis ends at ``n_used`` (one slot, which skips its body, when no row
    has an expert), of the ``decode_slots`` a call of these rows has."""
    from llama_fastapi_k8s_gpu_tpu.ops.pallas.experts import (
        FEW_ROWS, decode_slots, experts_in_use, slot_extent)

    E = 8
    count, experts, n_used = experts_in_use(
        np.asarray(rows, np.int32), E, slots)
    live = [r for r in rows if r < E]
    np.testing.assert_array_equal(count, np.bincount(live, minlength=E)[:E])
    distinct = sorted(set(live))
    assert int(n_used) == len(distinct)
    want = distinct + [distinct[-1] if distinct else 0] * (slots - len(distinct))
    assert np.asarray(experts).tolist() == want
    assert int(slot_extent(n_used)) == max(len(distinct), 1) <= slots
    assert decode_slots(E, len(rows), 1) == min(E, len(rows)) >= slots
    assert decode_slots(E, FEW_ROWS // 2 + 1, 2) == 0     # the many-row plan


# ---------------------------------------------------------------------------
# the grouped kernels against the dequantized experts
# ---------------------------------------------------------------------------

def _expert_weights(E, N, K, gtype, rng, L=2):
    """(fused planes, bf16 copies of their dequantized values) of L layers
    of E experts (N, K); a K the K tile does not divide (1536) has its
    last tile filled up with zero blocks, as the loader does."""
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.gguf import GGMLType, quants
    from llama_fastapi_k8s_gpu_tpu.ops.pallas.experts import (
        padded_k, prep_experts)

    fused, plain = [], []
    k_pad = padded_k(K)
    for _ in range(L):
        w = rng.standard_normal((E, N, K)).astype(np.float32) * K ** -0.5
        raw = np.asarray(quants.quantize(w, GGMLType[gtype]))
        plain.append(quants.dequantize(raw, GGMLType[gtype], w.size
                                       ).reshape(E, N, K))
        raw = raw.reshape(E * N, -1)
        raw = np.pad(raw, ((0, 0), (0, raw.shape[1] * (k_pad - K) // K)))
        fused.append(prep_experts(raw.reshape(-1), E, N, k_pad,
                                  GGMLType[gtype]))
    return ({key: jnp.stack([f[key] for f in fused]) for key in fused[0]},
            {"w": jnp.asarray(np.stack(plain), jnp.bfloat16)})


def _picks(rng, M, k, E, used):
    """(M, k) picks: ``used`` None = ``k`` distinct experts a token at
    random, token 1 without any; else exactly ``used`` distinct experts
    spread over the ``E`` (0: every pick ``E``, no expert at all)."""
    if used is None:
        picks = np.stack([rng.permutation(E)[:k] for _ in range(M)])
        picks[1] = E
    elif used == 0:
        picks = np.full((M, k), E)
    else:
        pool = (np.arange(used) * E) // used
        picks = pool[np.arange(M * k) % used].reshape(M, k)
    return picks.astype(np.int32)


@pytest.mark.parametrize("D,F,E,k,M,used", [
    (256, 256, 8, 2, 5, None),      # fold 8 both ways, few rows
    (256, 256, 8, 2, 80, None),     # many rows: 160 > 128
    (2048, 1024, 4, 2, 3, None),    # OLMoE's widths: gate unfolded, down fold 2
    (2048, 1024, 4, 3, 50, None),
    # the slot axis ends at the slots in use: none (zeros), one, a part and
    # all of T = min(E, rows); down folded (K 1024), zero-filled (K 1536:
    # LFM2's) and plain (K 2048)
    (256, 256, 8, 2, 5, 0),
    (256, 1024, 4, 1, 6, 1),
    (256, 1536, 4, 2, 3, 2),
    (256, 2048, 4, 2, 3, 4)])
def test_grouped_kernels_agree_with_the_dequantized_experts(D, F, E, k, M,
                                                            used):
    """Same rows, same weights, fused planes (Q4_K gate and up, Q6_K down)
    against bf16 copies of their dequantized values; a token without a pick
    (``E``) gets nothing; under ``vmap`` the lanes become rows of ONE call
    and the counts are the step's, not a lane's."""
    import jax
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.ops.pallas.experts import routed_experts

    rng = np.random.default_rng(D + M)
    L = 2 if used is None else 1
    g, gd = _expert_weights(E, F, D, "Q4_K", rng, L)
    u, ud = _expert_weights(E, F, D, "Q4_K", rng, L)
    d, dd = _expert_weights(E, D, F, "Q6_K", rng, L)
    x = jnp.asarray(rng.standard_normal((M, D)), jnp.bfloat16)
    picks = _picks(rng, M, k, E, used)
    live = int((picks < E).sum())
    picks = jnp.asarray(picks)
    wts = jnp.asarray(rng.random((M, k)), jnp.float32)
    y, count = routed_experts(x, picks, wts, g, u, d, L - 1)
    y2, count2 = routed_experts(x, picks, wts, gd, ud, dd, L - 1)
    if live:
        assert rel(y, y2) < SAME
    else:
        assert not np.asarray(y, np.float32).any()
    np.testing.assert_array_equal(count, count2)
    assert int(np.asarray(count).sum()) == live
    if used is None:
        assert not np.asarray(y[1], np.float32).any()
    else:
        assert int((np.asarray(count) > 0).sum()) == used
    yv, cv = jax.vmap(lambda a, p, w: routed_experts(a, p, w, g, u, d, L - 1))(
        x[:, None], picks[:, None], wts[:, None])
    np.testing.assert_allclose(np.asarray(yv[:, 0], np.float32),
                               np.asarray(y, np.float32), rtol=0, atol=0)
    np.testing.assert_array_equal(cv[0], count)


@pytest.mark.parametrize("gtype,used", [("Q4_K", 3), ("Q6_K", 1),
                                        ("Q6_K", 4)])
def test_a_few_row_call_is_its_rows_through_the_dense_kernel_bit_for_bit(
        gtype, used):
    """The grouped call changes the GRID around the dense kernels' bodies,
    not the arithmetic: each row's result is, bit for bit, that row of the
    dense fused matmul of ITS expert's planes over the same row block (one
    expert at a time), whatever the number of slots the grid walked."""
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.ops.pallas import experts as X

    E, N, K, R, layer = 4, 128, 2048, 6, 0
    rng = np.random.default_rng(used)
    w, _ = _expert_weights(E, N, K, gtype, rng, L=1)
    fam = X.FAMILIES[X.family_of(w)]
    planes = [w[key] for key in fam.planes]
    x = jnp.asarray(rng.standard_normal((R, K)), jnp.bfloat16)
    row_expert = _picks(rng, R, 1, E, used).reshape(R)
    row_expert[2] = E                                  # a row without one
    _, slots, n_used = X.experts_in_use(row_expert, E, X.decode_slots(E, R, 1))
    meta = jnp.concatenate([jnp.asarray([layer], jnp.int32), n_used[None],
                            slots])
    got = np.asarray(X.grouped_matmul_few(
        fam, meta, x, jnp.asarray(row_expert), planes, 1, True))
    xpa = X._activations(jnp.pad(x, ((0, 16 - R), (0, 0))), fam)
    dense = _dense_call(X, fam)
    for r, e in enumerate(row_expert):
        want = np.zeros(N, np.float32) if e == E else np.asarray(
            dense(xpa, *(p[layer, e] for p in planes), True, "cur"))[r]
        np.testing.assert_array_equal(got[r], want)
    assert np.abs(got).sum() > 0


def _dense_call(X, fam):
    """``fn(xpa, *planes, interpret, variant)``: the dense fused matmul whose
    body the grouped calls of ``fam`` run.  Q6_K: the UNSTACKED call, the
    head's (``q6matmul._q6k_tile_product`` since PR 59; the stacked calls'
    float body takes a K tile's float32 sums in another order), which has
    no variant."""
    if fam.name == "q4k":
        return X._q4._q4k_2d_raw
    return lambda xpa, *rest: X._q6._q6k_2d_raw(xpa, *rest[:-1])


def _rows_with_an_expert(rng, R, E, n_real, used=None):
    """(R,) row experts: ``n_real`` rows at random places reach an expert
    (of ``used`` distinct ones dealt round, else any), the rest ``E``."""
    row_expert = np.full(R, E, np.int32)
    at = np.sort(rng.permutation(R)[:n_real])
    row_expert[at] = rng.integers(0, E, n_real) if used is None else (
        (np.arange(used) * E) // used)[np.arange(n_real) % used]
    return row_expert


# (type, rows of the layer's call, rows that reach an expert, distinct
# experts (None: any), K): the rows in use up to a compacted call's 64 at
# both row counts of the served files, one slot and every slot in use, a
# folded K (f = 2)
COMPACTED = [(g, R, n, None, 2048) for g in ("Q4_K", "Q6_K")
             for R in (128, 192) for n in (0, 1, 63, 64)] + [
    ("Q4_K", 128, 40, 1, 2048), ("Q6_K", 192, 64, 1, 2048),
    ("Q4_K", 192, 64, 4, 2048), ("Q6_K", 128, 17, 4, 2048),
    ("Q4_K", 128, 1, None, 1024), ("Q6_K", 128, 64, None, 1024),
    ("Q6_K", 192, 63, 1, 1024), ("Q4_K", 192, 64, 4, 1024)]


@pytest.mark.parametrize("gtype,R,n_real,used,K", COMPACTED)
def test_a_compacted_call_is_the_call_of_all_rows_and_the_dense_kernel(
        gtype, R, n_real, used, K):
    """The layer's call of ``ROW_GROUP`` rows on the rows that reach an
    expert (:func:`compact_rows`: in their order, the zero row past them):
    put back in the rows' places it is, bit for bit, the call of all R rows
    as it was built before (every slot multiplies all of them) and each
    row's own row of the dense fused matmul of its expert's planes; the
    places past the rows in use stay zero."""
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.ops.pallas import experts as X

    E, N, layer, G = 4, 128 * (2048 // K), 0, 64
    rng = np.random.default_rng(R + n_real)
    w, _ = _expert_weights(E, N, K, gtype, rng, L=1)
    fam = X.FAMILIES[X.family_of(w)]
    f = X.fold_factor(K)
    planes = [w[key] for key in fam.planes]
    x = jnp.asarray(rng.standard_normal((R, K)), jnp.bfloat16)
    row_expert = _rows_with_an_expert(rng, R, E, n_real, used)
    assert X.ROW_GROUP == G and X.compacted_rows(R, 1) == R
    assert not X.compacted_rows(G, 1)
    assert not X.compacted_rows(X.FEW_ROWS + 1, 1)
    _, slots, n_used = X.experts_in_use(row_expert, E, X.decode_slots(E, R, 1))
    assert used is None or int(n_used) == used
    meta = jnp.concatenate([jnp.asarray([layer], jnp.int32), n_used[None],
                            slots])
    want = np.asarray(X.grouped_matmul_few(
        fam, meta, x, jnp.asarray(row_expert), planes, f, True))
    place, src, n = X.compact_rows(jnp.asarray(row_expert), E, G)
    assert int(n) == n_real
    np.testing.assert_array_equal(np.asarray(src)[:n_real],
                                  np.flatnonzero(row_expert < E))
    assert (np.asarray(src)[n_real:] == R).all()
    got = np.asarray(X.grouped_matmul_few(
        fam, meta, jnp.concatenate([x, jnp.zeros((1, K), x.dtype)])[src],
        jnp.asarray(np.append(row_expert, E))[src], planes, f, True))
    assert got.shape[0] == G and not got[n_real:].any()
    back = np.concatenate([got, np.zeros((1, got.shape[1]), got.dtype)])[
        np.asarray(place)]
    np.testing.assert_array_equal(back, want)
    assert (np.abs(back).sum() > 0) == (n_real > 0)
    if f == 1:      # the dense kernel on the same row block, an expert a time
        xpa = X._activations(x, fam)
        dense = _dense_call(X, fam)
        for e in np.unique(row_expert[row_expert < E]):
            rows = np.flatnonzero(row_expert == e)
            np.testing.assert_array_equal(back[rows], np.asarray(dense(
                xpa, *(p[layer, e] for p in planes), True, "cur"))[rows])


@pytest.mark.parametrize("N", [1024, 1280, 2048])   # 1280: the tile is 640
@pytest.mark.parametrize("f", [1, 2])
def test_grouped_q6k_calls_read_the_stacked_bodys_plane_bit_for_bit(f, N):
    """The dequantized plane of the grouped Q6_K calls (the head's integer
    body since PR 59) against the stacked dense body's, BIT FOR BIT: a
    one-hot row reads one plane column (and its two correction terms) out
    of either kernel exactly, whatever order the float32 sums are taken
    in.  256 columns of the K tile, 64 of each quarter (four integer forms
    take the quarters apart), through the few-row and the many-row call,
    every column from both experts, unfolded and folded (``f`` 2: a row of
    1024 offered twice in a row of 2048)."""
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.ops.pallas import experts as X
    from llama_fastapi_k8s_gpu_tpu.ops.pallas import q6matmul as Q6

    E, K, G = 2, 2048 // f, 128
    rng = np.random.default_rng(N + f)
    fam = X.FAMILIES["q6k"]
    planes = [
        jnp.asarray(rng.integers(-128, 128, (1, E, N, 1024)), jnp.int8),
        jnp.asarray(rng.integers(-128, 128, (1, E, N, 512)), jnp.int8),
        jnp.asarray(rng.standard_normal((1, E, 1, N, 128)) * 1e-2,
                    jnp.bfloat16)]
    assert fam.tn(N, G * f, True) == {1024: 1024, 1280: 640, 2048: 1024}[N]
    # the elements of a row of 2048 that land in the chosen tile columns;
    # folded, copy ``element // K`` of the row holds it
    cols = np.concatenate([q * 512 + rng.permutation(512)[:64]
                           for q in range(4)])
    landed = np.asarray(Q6.permute_x6(jnp.arange(2048)[None]))[0][cols]
    x = np.zeros((2 * G, K), np.float32)
    x[np.arange(2 * G), landed % K] = 1.0
    x = jnp.asarray(x, jnp.bfloat16)
    hot = np.asarray(Q6.permute_x6(X._fold_rows(x, f, 2 * G)), np.float32)
    assert set(cols) <= set(np.nonzero(hot)[1])

    def stacked(rows, e):       # (G, K) rows -> (G, N * f), expert e's plane
        out = Q6._q6k_2d_stacked_raw(
            jnp.zeros(1, jnp.int32), X._activations(
                X._fold_rows(rows, f, G), fam),
            *(p[0, e][None] for p in planes), interpret=True)
        return np.asarray(X._unfold_rows(out, f, G))

    def same(got, want):
        assert np.array_equal(np.asarray(got).view(np.uint32),
                              want.view(np.uint32))
        assert np.abs(want).sum() > 0

    halves = [x[:G], x[G:]]
    want = [[stacked(rows, e) for e in range(E)] for rows in halves]
    meta = jnp.asarray([0, E, 0, 1], jnp.int32)
    for rows, by_expert in zip(halves, want):
        for swap in range(E):
            row_expert = (np.arange(G) + swap) % E
            same(X.grouped_matmul_few(fam, meta, rows, jnp.asarray(
                row_expert, jnp.int32), planes, f, True),
                np.stack(by_expert)[row_expert, np.arange(G)])
    # many rows: four tiles, both halves through expert 0, then through 1
    tiles = jnp.asarray([0, 4, 0, 0, 1, 1], jnp.int32)
    same(X.grouped_matmul_many(fam, tiles, jnp.concatenate(halves * E),
                               planes, f, True),
         np.concatenate([want[h][e] for e in range(E) for h in range(2)]))


def _parents_q4k_family(X, variant):
    """The ``q4k`` family as the grouped calls had it up to PR 60: the
    stacked dense calls' float body (``qmatmul._q4k_matmul_kernel`` under
    ``variant``), N tiles of 512 / 256 / 128 by the rows, a K tile a step."""
    import copy

    fam = copy.copy(X.FAMILIES["q4k"])
    fam.kernel = functools.partial(X._q4._q4k_matmul_kernel, variant=variant)
    fam.tn = lambda N, rows, interpret: X._q4._pick_tn(
        N, interpret, prefs=X._q4._tn_prefs_for(rows, X._q4._TN_PREFS_Q4K))
    fam.few_k_tiles, fam.many_vmem = False, None
    return fam


# the five served gate / up shapes cut down in N (the N tile's rule still
# sees 1536 = 2 x 768 as 384 = 1 x 384), and a down projection's: (name, N,
# K of the file, K tiles a few-row step)
Q4K_SHAPES = [("lfm2", 384, 2048, 1), ("olmoe", 256, 2048, 1),
              ("gigachat-filled", 256, 7168, 4), ("kexaone", 256, 6144, 3),
              ("longcat", 384, 6144, 3), ("olmoe-down-folded", 256, 1024, 1)]


@pytest.mark.parametrize("variant", ["resplit", "cur"])
@pytest.mark.parametrize("name,N,K,tiles", Q4K_SHAPES,
                         ids=[s[0] for s in Q4K_SHAPES])
def test_grouped_q4k_calls_equal_the_float_bodys_bit_for_bit(name, N, K,
                                                             tiles, variant):
    """The grouped Q4_K calls' RESULTS (the integer body, the head's N tile
    and, few rows, all the K tiles that fit a grid step, since PR 61)
    against the same calls as PR 60 built them (:func:`_parents_q4k_family`),
    bit for bit: equal planes (tests/test_qmatmul.py), the same three dots
    a K tile, the tiles' products summed in the grid's order.  Few rows: 3
    experts held and 2 in use (a slot count under the extent), a row without
    an expert; many rows: the plan's tiles, a token without a pick."""
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.ops.pallas import experts as X

    E, R = 3, 24
    rng = np.random.default_rng(N + K)
    w, _ = _expert_weights(E, N, K, "Q4_K", rng, L=1)
    new, old = X.FAMILIES["q4k"], _parents_q4k_family(X, variant)
    planes = [w[key] for key in new.planes]
    f, kt = X.fold_factor(K), X.padded_k(K) * X.fold_factor(K) // X.TK
    assert planes[0].shape == (1, E, N // f, kt * X.TK // 2)
    assert X._few_k_tiles(kt, new.tn(N // f, R * f, True)) == tiles == kt

    def same(got, want):
        assert np.array_equal(np.asarray(got).view(np.uint32),
                              np.asarray(want).view(np.uint32))
        assert np.abs(np.asarray(want)).sum() > 0

    x = jnp.asarray(rng.standard_normal((R, K)), jnp.bfloat16)
    row_expert = np.asarray([0, 2])[rng.integers(0, 2, R)].astype(np.int32)
    row_expert[5] = E
    _, slots, n_used = X.experts_in_use(row_expert, E, X.decode_slots(E, R, 1))
    assert int(n_used) == 2 < slots.shape[0]
    meta = jnp.concatenate([jnp.zeros(1, jnp.int32), n_used[None], slots])
    got = X.grouped_matmul_few(new, meta, x, jnp.asarray(row_expert), planes,
                               f, True)
    same(got, X.grouped_matmul_few(old, meta, x, jnp.asarray(row_expert),
                                   planes, f, True))
    assert not np.asarray(got[5]).any()
    M, k = 100, 2                               # 200 rows: the many-row plan
    picks = _picks(rng, M, k, E, None).reshape(-1)
    plan = X.plan_groups(jnp.asarray(picks), E, M, X.TM_MANY)
    meta = jnp.concatenate([jnp.zeros(1, jnp.int32), plan["n_used"][None],
                            plan["tile_expert"]])
    xr = jnp.asarray(rng.standard_normal((M * k + 1, K)), jnp.bfloat16
                     ).at[M * k].set(0)[plan["src"]]
    live = int(plan["n_used"]) * X.TM_MANY      # the tiles past are not written
    same(X.grouped_matmul_many(new, meta, xr, planes, f, True)[:live],
         X.grouped_matmul_many(old, meta, xr, planes, f, True)[:live])


@pytest.mark.parametrize("N,tn", [(1024, 1024), (1536, 768), (2048, 1024),
                                  (6144, 1024), (7168, 1024), (1280, 640),
                                  (200, 8)])      # 200: interpret mode's own
@pytest.mark.parametrize("family", ["q4k", "q6k"])
def test_a_grouped_calls_n_tile_is_the_heads_whatever_its_rows(family, N, tn):
    from llama_fastapi_k8s_gpu_tpu.ops.pallas import experts as X

    fam = X.FAMILIES[family]
    assert {fam.tn(N, rows, True) for rows in (16, 64, 128, 192, 256)} == {tn}
    if N % 128 == 0:
        assert fam.tn(N, 64, False) == tn


@pytest.mark.parametrize("kt,tn,tiles", [
    (1, 1024, 1), (3, 1024, 3), (4, 1024, 4),     # 2048, 6144, 8192: all of K
    (4, 768, 4), (8, 1024, 4), (5, 1024, 1), (6, 1024, 3), (8, 256, 8)])
def test_a_few_row_q4k_step_holds_the_k_tiles_that_fit(kt, tn, tiles):
    """:func:`_few_k_tiles`: the most K tiles that divide the call's and
    fit the head's weight block; the Q4_K family's few-row calls alone (a
    many-row call and every Q6_K call take a K tile a step)."""
    from llama_fastapi_k8s_gpu_tpu.ops.pallas import experts as X

    assert X._few_k_tiles(kt, tn) == tiles
    assert X.FAMILIES["q4k"].few_k_tiles and not X.FAMILIES["q6k"].few_k_tiles
    assert X.FAMILIES["q4k"].many_vmem == X.FEW_VMEM
    assert X.FAMILIES["q6k"].many_vmem is None


def _as_it_was_built(monkeypatch, run):
    """``run()`` with every few-row call built as it was before (the
    threshold raised to all of them)."""
    from llama_fastapi_k8s_gpu_tpu.ops.pallas import experts as X

    monkeypatch.setattr(X, "ROW_GROUP", X.FEW_ROWS)
    X._routed_fn.cache_clear()           # the jitted layer read the old one
    try:
        return run()
    finally:
        monkeypatch.undo()
        X._routed_fn.cache_clear()


@pytest.mark.parametrize("k,n_real,D,F", [
    (k, n, 256, 256) for k in (8, 12) for n in (0, 1, 63, 64, 65, 16 * k)] + [
    (8, 10, 2048, 1024), (12, 65, 2048, 1024)])
def test_the_layer_of_more_than_64_rows_is_the_layer_as_it_was_built(
        monkeypatch, k, n_real, D, F):
    """16 tokens' picks (128 and 192 rows), of which ``n_real`` at random
    places reach an expert: up to 64 the layer runs its three calls on a
    block of 64 rows, with more the calls of all the rows; either way
    result and counts are bit for bit the layer as it was built before."""
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.ops.pallas.experts import routed_experts

    M, E = 16, 8
    rng = np.random.default_rng(k + n_real)
    g, _ = _expert_weights(E, F, D, "Q4_K", rng, 1)
    u, _ = _expert_weights(E, F, D, "Q4_K", rng, 1)
    d, _ = _expert_weights(E, D, F, "Q6_K", rng, 1)
    x = jnp.asarray(rng.standard_normal((M, D)), jnp.bfloat16)
    picks = jnp.asarray(_rows_with_an_expert(rng, M * k, E, n_real
                                             ).reshape(M, k))
    wts = jnp.asarray(rng.random((M, k)), jnp.float32)

    def layer():
        return routed_experts(x, picks, wts, g, u, d, 0)

    y, count = layer()
    want, want_count = _as_it_was_built(monkeypatch, layer)
    np.testing.assert_array_equal(np.asarray(y, np.float32),
                                  np.asarray(want, np.float32))
    np.testing.assert_array_equal(count, want_count)
    assert int(np.asarray(count).sum()) == n_real
    assert np.asarray(y, np.float32).any() == (n_real > 0)


@pytest.mark.parametrize("lanes,k,dead", [(16, 8, (5,)), (16, 12, (0, 9)),
                                          (8, 8, (3,))])
def test_lanes_with_a_dead_one_between_them_give_the_uncompacted_layer(
        monkeypatch, lanes, k, dead):
    """Under ``vmap`` the lanes' picks are the rows of ONE call: 128 and
    192 rows are compacted (a dead lane's rows and the picks of an expert
    held elsewhere reach none), 64 rows are not.  The layer's result and
    counts are, bit for bit, those of the layer with every call built as
    it was before; a dead lane gets nothing and a live lane what it gets
    alone."""
    import jax
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.ops.pallas import experts as X

    D, F, E = 256, 256, 8
    rng = np.random.default_rng(lanes + k)
    g, _ = _expert_weights(E, F, D, "Q4_K", rng, 1)
    u, _ = _expert_weights(E, F, D, "Q4_K", rng, 1)
    d, _ = _expert_weights(E, D, F, "Q6_K", rng, 1)
    x = jnp.asarray(rng.standard_normal((lanes, 1, D)), jnp.bfloat16)
    # a router over 4 E experts of which the first E are held here
    picks = np.stack([rng.permutation(4 * E)[:k] for _ in range(lanes)])
    picks = np.minimum(picks, E).astype(np.int32)
    picks[list(dead)] = E
    picks = jnp.asarray(picks[:, None])
    wts = jnp.asarray(rng.random((lanes, 1, k)), jnp.float32)

    def layer():
        return jax.vmap(lambda a, p, w: X.routed_experts(a, p, w, g, u, d, 0)
                        )(x, picks, wts)

    y, count = layer()
    want, want_count = _as_it_was_built(monkeypatch, layer)
    np.testing.assert_array_equal(np.asarray(y, np.float32),
                                  np.asarray(want, np.float32))
    np.testing.assert_array_equal(count, want_count)
    for lane in range(lanes):
        alone, _ = X.routed_experts(x[lane], picks[lane], wts[lane], g, u, d,
                                    0)
        assert lane not in dead or not np.asarray(y[lane], np.float32).any()
        np.testing.assert_allclose(np.asarray(y[lane], np.float32),
                                   np.asarray(alone, np.float32), rtol=1e-2,
                                   atol=1e-4)


def _few_row_calls(M, k):
    """The layer's jaxpr at ``M`` tokens of ``k`` picks: ([(name, rows of
    the activation operand, entries of the prefetched vector, dots in the
    kernel) of each pallas_call], the primitives outside the kernels)."""
    import jax
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.ops.pallas import experts as X

    D, F, E = 2048, 1024, 4
    S = jax.ShapeDtypeStruct
    i8, bf16 = jnp.int8, jnp.bfloat16
    gate = [S((1, E, F, D // 2), i8), S((1, E, 1, F, 128), bf16)]
    down = [S((1, E, D // 2, F), i8), S((1, E, D // 2, F // 2), i8),
            S((1, E, 1, D // 2, 128), bf16)]
    jaxpr = jax.make_jaxpr(
        lambda *a: X._routed_raw(("q4k", "q4k", "q6k"), True, *a))(
        S((), jnp.int32), S((M, D), bf16), S((M, k), jnp.int32),
        S((M, k), jnp.float32), *gate, *gate, *down)

    def walk(jp):               # every equation outside the kernels
        for eqn in jp.eqns:
            yield eqn
            if eqn.primitive.name != "pallas_call":
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    yield from walk(sub)

    def dots(jp):
        return sum((e.primitive.name == "dot_general")
                   + sum(dots(s) for s in jax.core.jaxprs_in_params(e.params))
                   for e in jp.eqns)

    eqns = list(walk(jaxpr.jaxpr))
    # a call's operands: the grid's traced extent, the prefetched vector,
    # the rows
    return [(eqn.params["name"], eqn.invars[2].aval.shape[0],
             eqn.invars[1].aval.shape[0], dots(eqn.params["jaxpr"]))
            for eqn in eqns if eqn.primitive.name == "pallas_call"], \
        [eqn.primitive.name for eqn in eqns]


def test_a_call_of_64_rows_or_fewer_is_built_as_it_always_was():
    """Statically, by the rows of the call: at 64 rows (OLMoE's and LFM2's
    decode steps) no row is moved (the activations are repeated, not
    gathered), nothing chooses between two forms, and the layer is three
    calls on all its rows (the down call's K is folded: 128 rows); at 128
    rows the layer holds both forms under one ``cond``: three calls on 64
    rows, three on all of them.  Every call is the same kernel under the
    same name with the same prefetched vector (a Q6_K call holds five dots
    since PR 59: one a quarter of the K tile and the correction columns')."""
    T, few = 4, "expert_matmul_fewrow"
    calls, outside = _few_row_calls(8, 8)
    assert calls == [("q4k_" + few, 64, 2 + T, 3), ("q4k_" + few, 64, 2 + T, 3),
                     ("q6k_" + few, 128, 2 + T, 5)]
    assert "gather" not in outside and "cond" not in outside
    assert "cumsum" in outside          # the slots in use
    calls, outside = _few_row_calls(16, 8)
    assert sorted(calls) == sorted(
        [("q4k_" + few, rows, 2 + T, 3) for rows in (64, 128)] * 2
        + [("q6k_" + few, 2 * rows, 2 + T, 5) for rows in (64, 128)])
    assert "gather" in outside and outside.count("cond") == 1


def test_the_experts_probe_passes_in_interpret_mode():
    from llama_fastapi_k8s_gpu_tpu.ops.pallas.probe import probe_fused_experts

    assert probe_fused_experts() is None


# ---------------------------------------------------------------------------
# the rotary embedding: rotate-half on an ``olmoe`` file
# ---------------------------------------------------------------------------

def _rotate_half_as_published(x, positions, theta):
    """Hugging Face ``apply_rotary_pos_emb``, in numpy float64:
    ``x * cos + rotate_half(x) * sin``, cos/sin of the frequencies twice
    over, ``rotate_half(x) = cat(-x[half:], x[:half])``."""
    hd = x.shape[-1]
    inv = theta ** (-np.arange(0, hd, 2, dtype=np.float64) / hd)
    ang = np.asarray(positions, np.float64)[:, None] * inv[None, :]
    emb = np.concatenate([ang, ang], -1)[:, None, :]
    rot = np.concatenate([-x[..., hd // 2:], x[..., :hd // 2]], -1)
    return x * np.cos(emb) + rot * np.sin(emb)


@pytest.mark.parametrize("neox", [True, False])
def test_rope_pairs_the_halves_of_a_head_on_an_olmoe_file(neox):
    """``rope`` under ``rope_neox`` is the published rotate-half to float32
    rounding (1e-5 of the norm); under the dense block's pairing it is
    another function (tens of per cent off), which on a real file is wrong
    attention with no error."""
    import dataclasses

    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.models.llama import rope
    from llama_fastapi_k8s_gpu_tpu.testing import TINY_OLMOE_CFG

    cfg = dataclasses.replace(TINY_OLMOE_CFG, rope_neox=neox)
    x = np.random.default_rng(1).standard_normal((6, 4, 64))
    pos = np.array([0, 1, 2, 17, 40, 100])
    got = rope(jnp.asarray(x, jnp.float32), jnp.asarray(pos, jnp.int32), cfg)
    want = _rotate_half_as_published(x, pos, cfg.rope_theta)
    np.testing.assert_array_equal(np.asarray(got)[0], x[0].astype(np.float32))
    if neox:
        assert rel(got, want) < 1e-5
    else:
        assert rel(got, want) > 0.3


@pytest.mark.parametrize("fmt", ["bf16", "q4k"])
def test_the_dense_blocks_rope_pairing_fails_the_logit_limit(
        loaded, ref, gguf_path, tokens, fmt):
    """The control of the comparisons below: the same weights under the
    interleaved pairing are not within the limit of the reference (which
    rotates the halves, as published), so the limit tells the two apart on
    seeded random weights."""
    import dataclasses

    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.models.llama import forward, init_cache

    params, cfg = loaded[fmt]
    cfg = dataclasses.replace(cfg, rope_neox=False)
    got, _, picks = forward(params, cfg, jnp.asarray(tokens, jnp.int32),
                            jnp.int32(0), init_cache(cfg), return_all=True,
                            with_picks=True)
    want = reference_with(ref, gguf_path, tokens, picks)
    assert rel(got, want) > 2 * LOGITS[fmt]


# ---------------------------------------------------------------------------
# the router
# ---------------------------------------------------------------------------

def test_route_is_a_softmax_over_all_experts_top_k_unnormalised(ref):
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.models.config import ModelConfig
    from llama_fastapi_k8s_gpu_tpu.models.llama import route

    rng = np.random.default_rng(2)
    u = rng.standard_normal((9, 32)).astype(np.float32)
    w = rng.standard_normal((16, 32)).astype(np.float32)
    cfg = ModelConfig(vocab_size=1, dim=32, n_layers=1, n_heads=1,
                      n_kv_heads=1, ffn_dim=1, n_ctx=1, n_experts=16,
                      n_experts_used=4)
    picks, weights = route(jnp.asarray(u), jnp.asarray(w), cfg)
    probs, want = ref.router({"n_used": 4}, {"ffn_gate_inp": w}, u)
    np.testing.assert_array_equal(picks, want)
    np.testing.assert_allclose(
        weights, np.take_along_axis(np.asarray(probs), np.asarray(want), 1),
        rtol=1e-5)
    assert float(np.asarray(weights).sum(1).max()) < 1.0     # not renormalised
    # ``norm_topk_prob``: the same picks, the reference's probabilities of
    # them over their sum (no served architecture sets it: olmoe's is false)
    import dataclasses
    picks_n, weights_n = route(jnp.asarray(u), jnp.asarray(w),
                               dataclasses.replace(cfg, norm_topk_prob=True))
    np.testing.assert_array_equal(picks_n, want)
    taken = np.take_along_axis(np.asarray(probs), np.asarray(want), 1)
    np.testing.assert_allclose(weights_n, taken / taken.sum(1, keepdims=True),
                               rtol=1e-5)


@pytest.mark.parametrize("fmt", ["bf16", "q4k"])
def test_the_programs_picks_are_the_references(loaded, want, tokens, fmt):
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.models.llama import forward, init_cache

    params, cfg = loaded[fmt]
    _, _, picks = forward(params, cfg, jnp.asarray(tokens, jnp.int32),
                          jnp.int32(0), init_cache(cfg), return_all=True,
                          with_picks=True)
    picks = np.asarray(picks)
    assert picks.shape == (2, len(tokens), 2)
    counted = 0
    for layer, (probs, ref_picks) in enumerate(want[1]):
        top = -np.sort(-probs, axis=1)
        clear = (top[:, 1] - top[:, 2]) / top[:, 1] > GAP
        counted += clear.sum()
        np.testing.assert_array_equal(np.sort(picks[layer][clear], 1),
                                      np.sort(ref_picks[clear], 1))
    assert counted > len(tokens) // 2  # of 2 x 40: not a handful


# ---------------------------------------------------------------------------
# logits: prefill, then decode through the cache, one sequence and lanes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fmt", ["bf16", "q4k"])
def test_prefill_logits_agree_with_the_reference(
        loaded, ref, gguf_path, tokens, fmt):
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.models.llama import forward, init_cache

    params, cfg = loaded[fmt]
    got, _, picks = forward(params, cfg, jnp.asarray(tokens, jnp.int32),
                            jnp.int32(0), init_cache(cfg), return_all=True,
                            with_picks=True)
    want = reference_with(ref, gguf_path, tokens, picks)
    assert np.asarray(got).shape == want.shape
    assert rel(got, want) < LOGITS[fmt]


@pytest.mark.parametrize("fmt", ["bf16", "q4k"])
def test_prefill_then_decode_through_the_cache_agrees(
        loaded, ref, gguf_path, tokens, fmt):
    """The serial engine's two programs (models/generate.py): a padded
    bucket prefill, then one token at a time against the ring."""
    import jax
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.models.llama import forward, init_cache

    params, cfg = loaded[fmt]
    n = 28
    padded = np.zeros(32, np.int32)
    padded[:n] = tokens[:n]
    logits, cache, picks = forward(
        params, cfg, jnp.asarray(padded), jnp.int32(0), init_cache(cfg),
        last_idx=jnp.int32(n - 1), with_picks=True)
    got, picks = [logits], [np.asarray(picks)[:, :n]]
    # (the step as ONE program, as the engine runs it: called op by op,
    # every step compiled the layers' loop anew)
    step = jax.jit(lambda token, pos, cache: forward(
        params, cfg, token, pos, cache, with_picks=True))
    for pos in range(n, len(tokens)):
        logits, cache, pk = step(
            jnp.asarray(tokens[pos:pos + 1], jnp.int32), jnp.int32(pos),
            cache)
        got.append(logits)
        picks.append(np.asarray(pk))
    want = reference_with(ref, gguf_path, tokens, np.concatenate(picks, 1))
    assert rel(np.stack(got[1:]), want[n:]) < LOGITS[fmt]
    assert rel(got[0], want[n - 1]) < LOGITS[fmt]


@pytest.mark.parametrize("fmt", ["bf16", "q4k"])
def test_lanes_decode_at_once_while_lanes_join_and_leave(
        loaded, ref, gguf_path, fmt):
    """The lane engine's step (parallel/batched.py: ``vmap`` of ``forward``
    over per-lane rings, with the scheduler's ``live`` mask): three lanes at
    different positions of three sequences; lane 2 joins at step 2, lane 0
    leaves after step 3.  Every live lane's logits are the reference's at
    that position whatever the other lanes do, and the step's counters see
    the live lanes' picks alone."""
    import jax
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.models.llama import forward, init_cache

    params, cfg = loaded[fmt]
    rng = np.random.default_rng(9)
    starts, steps = (10, 17, 23), 6
    seqs = [rng.integers(0, 256, size=s + steps) for s in starts]
    caches, used = [], []
    for s, n in zip(seqs, starts):
        padded = np.zeros(32, np.int32)
        padded[:n] = s[:n]
        _, cache, pk = forward(params, cfg, jnp.asarray(padded), jnp.int32(0),
                               init_cache(cfg), last_idx=jnp.int32(n - 1),
                               with_picks=True)
        caches.append(cache)
        used.append([np.asarray(pk)[:, :n]])
    caches = jax.tree.map(lambda *a: jnp.stack(a), *caches)

    @jax.jit
    def step(toks, poss, caches, live):
        return jax.vmap(lambda t, p, c, lv: forward(
            params, cfg, t[None], p, c, live=lv, with_stats=True,
            with_picks=True))(toks, poss, caches, live)

    pos = list(starts)
    got = {lane: [] for lane in range(3)}
    for t in range(steps):
        live = np.array([t <= 3, True, t >= 2])
        toks = jnp.asarray([s[p] for s, p in zip(seqs, pos)], jnp.int32)
        logits, new, stats, picks = step(toks, jnp.asarray(pos, jnp.int32),
                                         caches, jnp.asarray(live))
        # a lane that holds no request keeps its ring and position
        caches = jax.tree.map(
            lambda a, b: jnp.where(live.reshape(-1, *[1] * (a.ndim - 1)),
                                   a, b), new, caches)
        stats, picks = np.asarray(stats), np.asarray(picks)
        for lane in range(3):
            if live[lane]:
                got[lane].append(np.asarray(logits[lane]))
                used[lane].append(picks[lane])
                pos[lane] += 1
        assert (stats == stats[0]).all()        # the step's, in every lane
        live_picks = picks[live][:, :, 0, :]     # (lanes, L, k)
        assert stats[0][0] == cfg.n_layers
        assert stats[0][1] == sum(
            len(set(live_picks[:, layer].reshape(-1).tolist()))
            for layer in range(cfg.n_layers))
        np.testing.assert_array_equal(
            stats[0][2:-1], np.bincount(live_picks.reshape(-1),
                                      minlength=cfg.n_experts))
    assert [len(got[lane]) for lane in range(3)] == [4, 6, 4]
    for lane, n in enumerate(starts):
        m = len(got[lane])
        want = reference_with(ref, gguf_path, seqs[lane][:n + m],
                              np.concatenate(used[lane], 1))
        assert rel(np.stack(got[lane]), want[n:]) < LOGITS[fmt], lane


# ---------------------------------------------------------------------------
# the reference can tell: a lower precision and a dropped pick fail
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kw,limit,passes", [
    ({"emulate": "bfloat16"}, "bf16", True),
    ({"emulate": "float8_e4m3fn"}, "q4k", False),
    ({"drop_last_pick": True}, "q4k", False)])
def test_the_limits_pass_bfloat16_and_fail_float8_and_a_dropped_pick(
        ref, gguf_path, tokens, want, kw, limit, passes):
    """The calibrations, on the reference itself, every variant sent to the
    float32 reference's picks (so that they too read arithmetic): its
    matmul inputs rounded to bfloat16 pass the tighter limit; to float8, or
    without each token's last pick, they fail the wider one twice over."""
    import jax.numpy as jnp

    hp, tensors = ref.open_model(gguf_path)
    if "emulate" in kw:
        kw = {"emulate": getattr(jnp, kw["emulate"])}
    off, _ = ref.forward(hp, tensors, tokens,
                         use_picks=[p for _, p in want[1]], **kw)
    err = rel(off, want[0])
    assert err < LOGITS[limit] if passes else err > 2 * LOGITS[limit], err


# ---------------------------------------------------------------------------
# counters, engines, /metrics
# ---------------------------------------------------------------------------

def test_expert_counters_fold_finished_chunks_when_read():
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.engine.expert_counters import (
        ExpertCounters)

    c = ExpertCounters(3, n_slots=3)
    assert c.snapshot() == {"layer_steps": 0, "experts_read": 0,
                            "picks": [0, 0, 0], "picks_held": 0,
                            "picks_total": 0, "picks_zero": 0,
                            "slots_skipped": 0, "rows_skipped": 0}
    for _ in range(70):                 # past the pending bound: still exact
        c.push(jnp.asarray([2, 3, 1, 0, 4, 9], jnp.int32))
    assert c.snapshot(block=True) == {
        "layer_steps": 140, "experts_read": 210, "picks": [70, 0, 280],
        "picks_held": 350, "picks_total": 630, "picks_zero": 0,
        # 140 calls of 3 slots walked 210 of them
        "slots_skipped": 140 * 3 - 210, "rows_skipped": 0}
    d = ExpertCounters(3)               # no grouped few-row call: no slots
    d.push(jnp.asarray([2, 3, 1, 0, 4, 9], jnp.int32))
    assert d.snapshot(block=True)["slots_skipped"] == 0


@pytest.mark.parametrize("n_rows,chunks,want", [
    (0, 3, 0),            # 64 rows or fewer: the call is built as it was
    (128, 0, 0), (128, 1, 2 * 128 - 5), (192, 70, 70 * (2 * 192 - 5))])
def test_rows_skipped_is_the_rows_offered_less_the_picks_held(n_rows, chunks,
                                                              want):
    """A known schedule: each chunk runs 2 (layer, step) pairs whose calls
    of ``n_rows`` rows held 1 + 0 + 4 picks of an expert here."""
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.engine.expert_counters import (
        ExpertCounters)

    c = ExpertCounters(3, n_slots=3, n_rows=n_rows)
    for _ in range(chunks):
        c.push(jnp.asarray([2, 3, 1, 0, 4, 9], jnp.int32))
    snap = c.snapshot(block=True)
    assert snap["rows_skipped"] == want and snap["picks_held"] == 5 * chunks


@pytest.mark.parametrize("engine", ["serial", "lanes"])
def test_the_engines_serve_the_file_and_count_their_decode_steps(
        gguf_path, engine):
    """The normal entry points on an ``olmoe`` file: the serial engine and
    the continuous engine (3 lanes, 5 requests: lanes fill, empty and refill)
    give text, and the counters add up: one (layer, step) pair per layer and
    decode step, ``k`` rows per live lane in each, between ``k`` and
    ``lanes x k`` experts read."""
    from llama_fastapi_k8s_gpu_tpu.engine import Engine
    from llama_fastapi_k8s_gpu_tpu.engine.continuous import ContinuousEngine

    msgs = [[{"role": "user", "content": f"hello {i}"}] for i in range(5)]
    if engine == "serial":
        eng = Engine(gguf_path, weight_format="q4k", n_ctx=128)
        outs = [eng.create_chat_completion(m, max_tokens=9, temperature=0.0)
                for m in msgs[:2]]
        lanes = 1
    else:
        eng = ContinuousEngine(gguf_path, weight_format="q4k", n_ctx=128,
                               batch_size=3)
        try:
            outs = [f.result(timeout=300) for f in
                    [eng.submit(m, max_tokens=9, temperature=0.0)
                     for m in msgs]]
        finally:
            eng.shutdown()
        lanes = 3
    assert all(o["usage"]["completion_tokens"] > 0 for o in outs)
    snap = eng.expert_counters.snapshot(block=True)
    L, k = eng.cfg.n_layers, eng.cfg.n_experts_used
    assert snap["layer_steps"] > 0 and snap["layer_steps"] % L == 0
    rows = sum(snap["picks"])
    steps = snap["layer_steps"]
    assert k * steps <= rows <= lanes * k * steps and rows % k == 0
    assert k * steps <= snap["experts_read"] <= rows
    if engine == "serial":
        assert rows == k * steps == snap["experts_read"]
    # the slots of a step's grouped call (/health engine.expert_slots) and
    # those its grid never walked
    assert eng.expert_slots == min(eng.cfg.n_held, lanes * k)
    assert snap["slots_skipped"] == (steps * eng.expert_slots
                                     - snap["experts_read"]) >= 0
    # at most 3 lanes x k rows: the call is built as it always was
    assert lanes * k <= 64 and eng.expert_rows == 0 == snap["rows_skipped"]


@pytest.mark.anyio
async def test_health_names_the_slots_and_metrics_the_ones_skipped(gguf_path):
    """A file served through the grouped kernels: /health ``engine`` has the
    slots of a decode step's call, /metrics the gauge of those no grid
    walked (host arithmetic on the device's counters)."""
    import httpx

    from llama_fastapi_k8s_gpu_tpu.engine import Engine
    from llama_fastapi_k8s_gpu_tpu.server.app import create_app
    from llama_fastapi_k8s_gpu_tpu.utils.config import Settings

    import jax.numpy as jnp

    eng = Engine(gguf_path, weight_format="q4k", n_ctx=128)
    k, held = eng.cfg.n_experts_used, eng.cfg.n_held
    assert eng.expert_slots == k               # one lane's picks
    # a chunk of 6 (layer, step) pairs that read 2 experts each
    eng.expert_counters.push(jnp.asarray(
        [6, 12] + [0] * held + [6 * k], jnp.int32))
    app = create_app(engine=eng, settings=Settings())
    transport = httpx.ASGITransport(app=app)
    async with transport:
        await app.router.startup()
        async with httpx.AsyncClient(transport=transport,
                                     base_url="http://test") as client:
            info = (await client.get("/health")).json()["engine"]
            assert info["expert_slots"] == k and "expert_rows" not in info
            eng.expert_counters.snapshot(block=True)
            m = (await client.get("/metrics")).text
            assert f"expert_slots_skipped_total {6 * k - 12}" in m
            assert "expert_rows_skipped_total 0" in m
            # the same engine with the lanes of a 128-row step
            eng.batch_size, eng._expert_counters = 128 // k, None
            assert eng.expert_rows == 128
            eng.expert_counters.push(jnp.asarray(
                [6, 12] + [7] + [0] * (held - 1) + [6 * k], jnp.int32))
            info = (await client.get("/health")).json()["engine"]
            assert info["expert_rows"] == 128
            eng.expert_counters.snapshot(block=True)
            m = (await client.get("/metrics")).text
            assert f"expert_rows_skipped_total {6 * 128 - 7}" in m
        await app.router.shutdown()


@pytest.mark.anyio
@pytest.mark.parametrize("routed", [True, False])
async def test_health_names_the_bodies_of_the_grouped_calls(
        gguf_path, tmp_path, routed):
    """/health ``engine.expert_kernel``: the bodies the grouped expert
    calls run, by family, beside ``expert_slots`` on a file served through
    them; no key on a dense file (nor ``expert_slots``)."""
    import httpx

    from llama_fastapi_k8s_gpu_tpu.engine import Engine
    from llama_fastapi_k8s_gpu_tpu.ops.pallas import experts as X
    from llama_fastapi_k8s_gpu_tpu.server.app import create_app
    from llama_fastapi_k8s_gpu_tpu.testing import write_tiny_llama_gguf
    from llama_fastapi_k8s_gpu_tpu.utils.config import Settings

    if routed:
        eng = Engine(gguf_path, weight_format="q4k", n_ctx=128)
    else:
        write_tiny_llama_gguf(str(tmp_path / "d.gguf"))
        eng = Engine(str(tmp_path / "d.gguf"), n_ctx=64)
    app = create_app(engine=eng, settings=Settings())
    transport = httpx.ASGITransport(app=app)
    async with transport:
        await app.router.startup()
        async with httpx.AsyncClient(transport=transport,
                                     base_url="http://test") as client:
            info = (await client.get("/health")).json()["engine"]
        await app.router.shutdown()
    if routed:
        assert [X.FAMILIES[f].body for f in ("q4k", "q6k")] == [
            "q4k-int", "q6k-int"]
        assert eng.expert_kernel == "q4k-int+q6k-int"
        assert info["expert_kernel"] == eng.expert_kernel
        keys = list(info)
        assert keys.index("expert_kernel") == keys.index("expert_slots") + 1
    else:
        assert eng.expert_kernel is None
        assert "expert_kernel" not in info and "expert_slots" not in info


def test_a_dense_file_has_no_expert_counters(tmp_path):
    from llama_fastapi_k8s_gpu_tpu.engine import Engine
    from llama_fastapi_k8s_gpu_tpu.testing import write_tiny_llama_gguf

    path = str(tmp_path / "d.gguf")
    write_tiny_llama_gguf(path)
    eng = Engine(path, n_ctx=64)
    assert eng.expert_counters is None and eng.expert_slots == 0


def test_the_expert_metrics_are_in_the_catalog():
    from llama_fastapi_k8s_gpu_tpu.obs.catalog import GAUGE, METRICS

    for name in ("expert_layer_steps_total", "experts_read_total",
                 "expert_picks_total", "expert_slots_skipped_total",
                 "expert_rows_skipped_total"):
        assert METRICS[name].mtype == GAUGE
    assert METRICS["expert_picks_total"].labels == ("expert",)


# ---------------------------------------------------------------------------
# the benchmark's files for the block (tier-1 collects tests/ only)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def bench(ref):
    """The benchmark's modules, by bare name as its files import them."""
    import importlib
    return {name: importlib.import_module(name)
            for name in ("ggufgen", "costs", "counters")}


def _published():
    import json
    with open(os.path.join(BENCH, "configs",
                           "olmoe-1b-7b-0125-q4km-8lane.json")) as f:
        return json.load(f)


def test_block_file_plans_the_published_file(bench):
    cfg = _published()
    block = bench["ggufgen"].block_of(cfg)
    plan = block.tensor_plan(cfg)
    by_name = {name: (shape, kind) for name, shape, kind in plan}
    assert by_name["blk.15.ffn_down_exps.weight"] == ((64, 2048, 1024), "Q6_K")
    assert by_name["blk.0.ffn_gate_exps.weight"] == ((64, 1024, 2048), "Q4_K")
    assert by_name["blk.3.ffn_gate_inp.weight"] == ((64, 2048), "F32")
    assert by_name["blk.3.attn_k_norm.weight"] == ((2048,), "F32")
    total = sum(bench["ggufgen"].tensor_nbytes(kind, int(np.prod(shape)))
                for _, shape, kind in plan)
    assert 4.6e9 < total < 4.7e9            # the 4.65 GB file, nothing cut
    n = sum(int(np.prod(shape)) for _, shape, _ in plan)
    assert 6.8e9 < n < 7.0e9                # 6.9 B parameters
    meta = dict((k, v) for k, _, v in block.metadata(cfg, "olmoe"))
    assert meta["olmoe.expert_count"] == 64
    assert meta["olmoe.expert_used_count"] == 8
    assert meta["olmoe.feed_forward_length"] == 1024


@pytest.mark.parametrize("read,lo,hi", [(None, 64, 64), (24.0, 24, 24),
                                        (8.0, 8, 8)])
def test_a_steps_expert_bytes_are_the_experts_the_program_counted(
        bench, read, lo, hi):
    """Without a run: the most 8 lanes can touch (all 64).  With the
    program's counters in the run's samples: that many experts' bytes."""
    cfg = _published()
    block = bench["ggufgen"].block_of(cfg)
    run = None
    if read is not None:
        def text(steps):
            return (f"expert_layer_steps_total {steps}\n"
                    f"experts_read_total {steps * read}\n")
        run = {"samples": [(0.0, text(100)), (1.0, text(900))]}
    got = block.experts_read(cfg, 8, run)
    assert lo <= got <= hi
    one = block.split(cfg)[2]
    assert one == 2 * bench["ggufgen"].tensor_nbytes("Q4_K", 1024 * 2048) \
        + bench["ggufgen"].tensor_nbytes("Q6_K", 1024 * 2048)
    assert block.expert_bytes_per_step(cfg, 8, run) == 16 * got * one
    rest = block.decode_step_bytes(cfg, 8, 0, run=run) \
        - block.expert_bytes_per_step(cfg, 8, run)
    assert rest == block.split(cfg)[0] + 8 * 2048 * 2
    # 1.3 B active parameters a token
    assert 2.3e9 < block.decode_step_flops(cfg, 1, 0) < 2.8e9


def _reader(name):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "lm_" + name, os.path.join(BENCH, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _samples(picks_first, picks_last, steps=(10, 110), read=(30, 2430)):
    def text(steps, read, picks):
        return "".join(
            [f"expert_layer_steps_total {steps}\n",
             f"experts_read_total {read}\n"]
            + [f'expert_picks_total{{expert="{e}"}} {n}\n'
               for e, n in enumerate(picks)])
    return [(0.0, text(steps[0], read[0], picks_first)),
            (1.0, text(steps[1], read[1], picks_last))]


def test_counter_readers_read_the_window_and_nothing_on_a_parent(bench):
    cfg = {"num_experts": 4}
    run = {"config": cfg,
           "samples": _samples([5, 5, 5, 5], [105, 55, 25, 15])}
    assert _reader("experts_read_per_layer_step")(run) == 24.0
    assert _reader("expert_load_max_share")(run) == 100.0 * 100 / 180
    # the parent exports no such counter: nothing, and no exception
    bare = {"config": cfg, "samples": [(0.0, "x 1\n"), (1.0, "x 2\n")]}
    assert _reader("experts_read_per_layer_step")(bare) is None
    assert _reader("expert_load_max_share")(bare) is None
    assert _reader("expert_load_max_share")(
        {"config": {}, "samples": bare["samples"]}) is None


def test_the_kernel_group_takes_the_expert_kernels_before_qmatmul(bench):
    import json

    sys.path.insert(0, BENCH)
    try:
        import xplane
    finally:
        sys.path.remove(BENCH)
    groups = {}
    for fn in ("expert_matmul.json", "qmatmul.json"):
        with open(os.path.join(BENCH, "kernels", fn)) as f:
            doc = json.load(f)
        groups[doc["name"]] = doc["patterns"]
    call = ('custom-call(s32[66]{0} %a, bf16[1024,2176]{1,0} %b, '
            's8[16,64,1024,1024]{3,2,1,0} %c), '
            'custom_call_target="tpu_custom_call"')
    ops = {"%q4k_expert_matmul_fewrow.30 = f32[1024,1024]{1,0} " + call: 2.0,
           "%q6k_expert_matmul_manyrow.1 = f32[9,9]{1,0} " + call: 1.0,
           "%q4k_matmul_fewrow.3 = f32[8,4096]{1,0} " + call: 4.0}
    secs = xplane.group_seconds(ops, groups)
    assert secs == {"expert_matmul": 3.0, "qmatmul": 4.0}


def test_expert_roofline_reader_divides_counted_bytes_by_kernel_time(bench):
    """A capture of two decode programs of 8 steps, the few-row expert
    kernels a quarter of their time, the counters at 24 experts a
    layer-step: least time = 16 x 24 experts' bytes over 819 GB/s."""
    cfg = _published()
    one = bench["ggufgen"].block_of(cfg).split(cfg)[2]
    ops = {"%q4k_expert_matmul_fewrow.1 = f32[] custom-call()": 0.03,
           "%q6k_expert_matmul_fewrow.2 = f32[] custom-call()": 0.02,
           "%q6k_expert_matmul_manyrow.2 = f32[] custom-call()": 9.0,
           "%fusion.1 = f32[] fusion()": 0.15}
    chunk = {"name": "decode_chunk", "start": 0.0, "end": 1.0,
             "attrs": {"tokens": 9}, "children": []}
    run = {
        "config": cfg, "notes": {}, "device": {"kind": "TPU v5 lite"},
        "kernel_groups": {"decode_program": ["generate_chunk"]},
        "profile": {"ops": ops, "busy_s": 0.2, "modules": [
            ("jit_batched_generate_chunk_perlane_jit", 0.0, 0.1),
            ("jit_batched_generate_chunk_perlane_jit", 0.1, 0.1)]},
        "traces": [{"root": {"name": "request", "start": 0.0, "end": 1.0,
                             "attrs": {}, "children": [chunk]}}],
        "samples": _samples([0] * 64, [0] * 64, read=(240, 2640)),
    }
    got = _reader("expert_matmul_roofline")(run)
    taken = (0.1 / 8) * 0.05 / 0.2
    least = 16 * 24 * one / 819e9
    assert got == pytest.approx(100 * least / taken)
    assert run["notes"]["expert_matmul_roofline"]["expert_bytes_per_step"] \
        == 16 * 24 * one
    # a dense configuration's block has no experts: nothing, no exception
    dense = dict(run, config=json_of("mistral-7b-v0.2-q4km-8lane"))
    assert _reader("expert_matmul_roofline")(dense) is None
    # the parent's capture holds no such kernel: a zero that says so
    none = dict(run, notes={}, profile=dict(run["profile"], ops={
        "%fusion.1 = f32[] fusion()": 0.2}))
    assert _reader("expert_matmul_roofline")(none) == 0.0
    assert none["notes"]["no_match"] == ["expert_matmul_roofline"]


def _routed_run(case):
    """The ``run`` dict as ``run.py`` hands it to the readers, of the routed
    configuration, lacking nothing: the operations of a 3 s capture of
    ``olmoe.chat-8sat`` by their own names (seconds rounded from the traced
    chip run of PR 29), decode programs of 8 steps at 15.6 ms a step, the
    counters at 22 experts a layer-step over 64 experts picked unevenly.
    ``no_expert_kernel``: a capture that holds none of the grouped kernels
    (their names changed, or a dense parent)."""
    import json

    sys.path.insert(0, BENCH)
    try:
        import xplane
    finally:
        sys.path.remove(BENCH)
    groups = {"decode_program": ["generate_chunk"]}
    for fn in sorted(os.listdir(os.path.join(BENCH, "kernels"))):
        with open(os.path.join(BENCH, "kernels", fn)) as f:
            doc = json.load(f)
        groups[doc["name"]] = doc["patterns"]
    call = (' custom-call(s32[66]{0} %a, bf16[64,2176]{1,0} %b, '
            's8[16,64,1024,1024]{3,2,1,0} %c), '
            'custom_call_target="tpu_custom_call"')
    ops = {"%fusion.221 = bf16[8,16,128]{2,1,0} fusion(bf16[8,16,16,4096,"
           "128]{4,3,2,1,0} %g)": 0.484,
           "%fusion.220 = f32[8,16,4096]{2,1,0} fusion(bf16[8,16,16,4096,"
           "128]{4,3,2,1,0} %g)": 0.443,
           "%q6k_expert_matmul_fewrow.14 = f32[128,1024]{1,0}" + call: 0.418,
           "%q4k_expert_matmul_fewrow.28 = f32[64,1024]{1,0}" + call: 0.203,
           "%q4k_expert_matmul_fewrow.29 = f32[64,1024]{1,0}" + call: 0.203,
           "%q6k_expert_matmul_manyrow.16 = f32[20480,1024]{1,0}" + call:
               0.056,
           "%q6k_matmul_fewrow.27 = f32[8,50304]{1,0}" + call: 0.039,
           "%copy.204 = bf16[20480,1,2048]{2,0,1} copy(%b)": 1.0}
    if case == "no_expert_kernel":
        ops = {n: t for n, t in ops.items() if "_expert_matmul_" not in n}
    busy = sum(ops.values())
    rng = np.random.default_rng(0)
    picked = rng.integers(1000, 3000, size=64)
    chunks = [{"name": "decode_chunk", "start": 0.2 * i, "end": 0.2 * i + 0.17,
               "attrs": {"tokens": 1 + 8 * (i + 1)}, "children": []}
              for i in range(3)]
    return {
        "config": _published(), "notes": {},
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
        "kernel_groups": groups,
        "profile": {"ops": ops, "busy_s": busy, "window_s": busy + 0.0004,
                    "groups": xplane.group_seconds(ops, groups),
                    "modules": [("jit_prefill_chunk_jit(12)", 0.0, 0.09)] + [
                        ("jit_batched_generate_chunk_perlane_jit(9)",
                         0.1 + 0.13 * i, 0.1251 + 1e-5 * i)
                        for i in range(21)]},
        "traces": [{"root": {"name": "request", "start": 0.0, "end": 1.0,
                             "attrs": {}, "children": chunks}}],
        "samples": _samples([0] * 64, list(picked), steps=(160, 32160),
                            read=(3500, 709500)),
    }


@pytest.mark.parametrize("case", ["sound", "no_expert_kernel"])
@pytest.mark.parametrize("name,lo,hi", [
    ("experts_read_per_layer_step", 8.0, 64.0),
    ("expert_load_max_share", 100.0 / 64, 100.0),
    ("expert_busy_share", 0.0, 100.0),
    ("expert_matmul_roofline", 0.0, 100.0)])
def test_a_sound_traced_routed_run_reads_a_number(bench, name, lo, hi, case):
    """What ``benchmarks/tests/test_every_metric.py`` asks of every declared
    metric on a dense run, of the four metrics of the routed block on a
    routed one: a float inside the metric's range (a traced line that
    lacks a declared metric is refused), and, without the kernels, the
    counters as before and a zero that says why from the capture's two."""
    run = _routed_run(case)
    got = _reader(name)(run)
    assert isinstance(got, float), (name, case, got)
    assert got == got and abs(got) != float("inf")
    of_the_capture = name in ("expert_busy_share", "expert_matmul_roofline")
    if case == "no_expert_kernel" and of_the_capture:
        assert got == 0.0 and run["notes"]["no_match"] == [name]
    else:
        assert lo < got <= hi and "no_match" not in run["notes"]
    if case == "sound":
        want = {"experts_read_per_layer_step": 22.06,    # the chip's own
                "expert_busy_share": 31.1,
                "expert_matmul_roofline": 32.9}.get(name)
        assert want is None or got == pytest.approx(want, rel=0.1)


def json_of(config):
    import json
    with open(os.path.join(BENCH, "configs", config + ".json")) as f:
        return json.load(f)
