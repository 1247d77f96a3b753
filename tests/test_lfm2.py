"""The ``lfm2moe`` block (models/lfm2.py) at a tiny size on the CPU, against
the plain float32 reference (benchmarks/reference_lfm2.py): gated short
convolutions and GQA mixed per layer over the sixth cache kind
(``conv-state+ring``: a conv layer carries the last two inputs of its taps,
an attention layer a ring whose rows hold two heads of 64 side by side),
per-head QK-norm, rotate-half RoPE, leading dense layers, the sigmoid router
with its choice bias and its 1e-6, no shared expert, a tied head.

The tiny file (``testing.TINY_LFM2_CFG``) keeps every ratio of the published
block: conv conv attn conv x 2, 3 taps, 4 heads on 2 KV heads of 64, 2 dense
+ 6 routed layers of 8 experts, top-3.  ONE file, one set of compiled
programs and one lane engine serve the whole module.

LIMIT: the program (bf16 inputs to every product, float32 sums, a bf16
stream, carried rows and ring) against the float32 reference on the
program's OWN picks reads 3 % of the logits' norm over blocks of 16
positions on eight layers (the reference with bf16 inputs 2 %); the
controls (the taps newest first, no gate, float8 inputs) read 30 % or more.
"""

from __future__ import annotations

import dataclasses
import os
import sys

import numpy as np
import pytest

from tests.test_mla import (
    load, prefill, programs, rel, rows_that_differ, worst)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")

LIMIT = 6e-2
PICKS = 60           # rows of 6 layers x N_SEQ whose picks may differ
N_CTX = 128
SLICE = 16
N_PROMPT = 52
N_SEQ = 100


@pytest.fixture(scope="module")
def ref():
    """The reference, with each layer's tensors dequantized once for the
    module (it dequantizes them at every call)."""
    sys.path.insert(0, BENCH)
    try:
        import reference_lfm2
        plain, kept = reference_lfm2.layer_weights, {}
        reference_lfm2.layer_weights = lambda tensors, i: kept.get(i) \
            or kept.setdefault(i, plain(tensors, i))
        yield reference_lfm2
        reference_lfm2.layer_weights = plain
    finally:
        sys.path.remove(BENCH)


@pytest.fixture(scope="module")
def gguf_path(tmp_path_factory):
    from llama_fastapi_k8s_gpu_tpu.testing import write_tiny_lfm2_gguf

    path = str(tmp_path_factory.mktemp("lfm2") / "tiny.gguf")
    write_tiny_lfm2_gguf(path, seed=3)
    return path


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(5).integers(4, 260, size=N_SEQ)


@pytest.fixture(scope="module")
def model(ref, gguf_path):
    return ref.open_model(gguf_path)


@pytest.fixture(scope="module")
def loaded(gguf_path):
    return load(gguf_path, n_ctx=N_CTX)


@pytest.fixture(scope="module")
def progs(loaded):
    """The XLA forms' three programs, compiled once for the module."""
    return programs(loaded[1])


def serve(params, cfg, tokens, progs, size=SLICE, n_prompt=N_PROMPT,
          n_seq=N_SEQ):
    """Slices of ``size`` then steps through the cache: (logits (S, V),
    picks (L_moe, S, k), the cache)."""
    import jax.numpy as jnp

    pass_, step, _ = progs
    logits, picks, cache = prefill(params, cfg, tokens, n_prompt, size=size,
                                   pass_=pass_)
    dec, dpicks = [], []
    for t in range(n_prompt, n_seq):
        lg, cache, pk = step(params, jnp.int32(tokens[t]), jnp.int32(t),
                             cache)
        dec.append(np.asarray(lg))
        dpicks.append(np.asarray(pk))
    return (np.concatenate([logits] + ([np.stack(dec)] if dec else [])),
            np.concatenate([picks] + dpicks, axis=1), cache)


@pytest.fixture(scope="module")
def served(loaded, tokens, progs):
    return serve(*loaded, tokens, progs)


def reference(ref, model, seq, served, picks=None):
    """The reference's logits over ``seq`` on the program's picks: the
    served run's, with ``picks`` (L_moe, n, k) in place of the first n
    positions' (attention is causal, so nothing after a position moves it,
    and every call is over N_SEQ positions: the same shapes)."""
    use = served[1].copy()
    if picks is not None:
        use[:, :picks.shape[1]] = picks
    return np.asarray(ref.forward(*model, seq, use_picks=use)[0])


@pytest.fixture(scope="module")
def want(ref, model, tokens, served):
    """The reference on the served run's picks."""
    return reference(ref, model, tokens, served)


# ---------------------------------------------------------------------------
# the program against the reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("size", [2, 3, 16, 64], ids=[
    "narrower_than_the_taps", "the_taps", "the_slice",
    "wider_than_the_prompts_rest"])
def test_slices_then_decode_carry_the_taps_inputs(ref, model, loaded, tokens,
                                                  progs, served, want, size):
    """Prefill in slices narrower than, as wide as and wider than the taps
    and than the slice width (the last one padded past the prompt's end:
    padding never reaches the carried rows), then 48 steps."""
    logits, picks, cache = served if size == SLICE else \
        serve(*loaded, tokens, progs, size=size)
    if not np.array_equal(picks, served[1]):   # a near-tie the other way
        want = reference(ref, model, tokens, served, picks)
    assert worst(logits[:N_PROMPT], want[:N_PROMPT]) < LIMIT
    assert worst(logits[N_PROMPT:], want[N_PROMPT:]) < LIMIT
    # wherever the slices were cut, the rows carried on are the same inputs
    assert rel(cache["conv"], served[2]["conv"]) < 2e-2


def test_the_references_own_picks_are_nearly_the_programs(ref, model, tokens,
                                                          served):
    own = np.stack([p for _, p in ref.forward(*model, tokens)[1]])
    assert rows_that_differ(served[1], own) <= PICKS


@pytest.mark.parametrize("control", ["flip_taps", "no_gate", "float8"])
def test_another_function_fails_the_limit(ref, model, tokens, served, control):
    import jax.numpy as jnp

    kw = {"emulate": jnp.float8_e4m3fn} if control == "float8" \
        else {control: True}
    other = np.asarray(ref.forward(*model, tokens, use_picks=served[1],
                                   **kw)[0])   # (another function: new ops)
    assert worst(served[0], other) > 2 * LIMIT


def test_bfloat16_inputs_pass_the_limit(ref, model, tokens, served, want):
    import jax.numpy as jnp

    near = np.asarray(ref.forward(*model, tokens, use_picks=served[1],
                                  emulate=jnp.bfloat16)[0])
    assert worst(near, want) < LIMIT


@pytest.mark.parametrize("n_prompt", [1, 2])
def test_a_prompt_shorter_than_the_taps(ref, model, loaded, tokens, progs,
                                        served, n_prompt):
    """One and two tokens in a padded slice: the rows carried on are those
    tokens' inputs behind zeros, and three steps read them."""
    n = n_prompt + 3
    logits, picks, cache = serve(*loaded, tokens, progs, n_prompt=n_prompt,
                                 n_seq=n)
    want = reference(ref, model, tokens, served, picks)[:n]
    assert rel(logits, want) < LIMIT


def test_padding_rows_do_not_reach_the_carried_rows(loaded, tokens, progs):
    """The same five real rows before padding of two different tokens leave
    bitwise the same rows, and a pass that starts at 0 ignores what the
    leaf held."""
    import jax
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.models.llama import init_cache

    params, cfg = loaded
    pass_ = progs[0]
    out = []
    for pad, fill in ((9, 0.0), (77, 3.0)):
        part = np.full(SLICE, pad, np.int32)
        part[:5] = tokens[:5]
        dirty = jax.tree.map(lambda a: a + jnp.asarray(fill, a.dtype),
                             init_cache(cfg))
        out.append(pass_(params, jnp.asarray(part), jnp.int32(0),
                         jnp.int32(5), dirty)[1]["conv"])
    assert np.array_equal(np.asarray(out[0], np.float32),
                          np.asarray(out[1], np.float32))
    assert np.asarray(out[0], np.float32).any()


def test_two_lanes_one_dead_then_taken(ref, model, loaded, tokens, progs,
                                       served):
    """A lane beside a dead one whose leaves hold garbage; after four steps
    the dead lane is taken by a request whose prefill started from zero
    rows (the install of a scratch cache: the reset of a freed lane), and
    the first lane is freed after eight.  Every live lane's steps are the
    reference's, and the counters are the step's."""
    import jax
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.models.llama import init_cache

    params, cfg = loaded
    pass_, _, lane_step = progs
    seqs = [tokens, np.roll(tokens, 7)]
    firsts = (33, 11)
    caches = [prefill(params, cfg, s, n, pass_=pass_) for s, n in
              zip(seqs, firsts)]
    garbage = jax.tree.map(lambda a: a + 1, init_cache(cfg))
    stacked = jax.tree.map(lambda *a: jnp.stack(a), caches[0][2], garbage)
    pos, live = [firsts[0], N_CTX - 3], [True, False]
    got = {0: [], 1: []}
    for t in range(12):
        if t == 4:
            stacked = jax.tree.map(lambda a, c: a.at[1].set(c), stacked,
                                   caches[1][2])
            pos[1], live[1] = firsts[1], True
        if t == 8:
            live[0] = False
        toks = [seqs[i][p] if p < N_SEQ else 0 for i, p in enumerate(pos)]
        lg, stacked, st, pk = lane_step(
            params, jnp.asarray(toks, jnp.int32), jnp.asarray(pos, jnp.int32),
            stacked, jnp.asarray(live))
        st = np.asarray(st)
        assert np.array_equal(st[0], st[1])
        assert st[0][0] == 6
        assert st[0][-1] == st[0][2:-1].sum() == sum(live) * 6 * 3
        for lane in (0, 1):
            if live[lane]:
                got[lane].append((np.asarray(lg[lane]), np.asarray(pk[lane])))
        pos = [p + 1 for p in pos]
    for lane, rows in got.items():
        first, n = firsts[lane], firsts[lane] + len(rows)
        use = np.concatenate([caches[lane][1]] + [r[1] for r in rows], axis=1)
        want = reference(ref, model, seqs[lane], served, use)
        assert worst(np.stack([r[0] for r in rows]), want[first:n]) < LIMIT, \
            lane


def test_a_dead_lanes_leaves_are_untouched_by_a_step(loaded, tokens, progs):
    import jax
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.models.llama import init_cache

    params, cfg = loaded
    pass_, _, lane_step = progs
    mine = prefill(params, cfg, tokens, 20, pass_=pass_)[2]
    garbage = jax.tree.map(lambda a: a + 1, init_cache(cfg))
    stacked = jax.tree.map(lambda *a: jnp.stack(a), mine, garbage)
    _, after, _, _ = lane_step(
        params, jnp.asarray([tokens[20], 7], jnp.int32),
        jnp.asarray([20, 40], jnp.int32), stacked, jnp.asarray([True, False]))
    assert np.array_equal(np.asarray(after["conv"][1], np.float32),
                          np.asarray(garbage["conv"], np.float32))
    assert not np.array_equal(np.asarray(after["conv"][0], np.float32),
                              np.asarray(mine["conv"], np.float32))


def test_a_lanes_logits_do_not_depend_on_the_other_lanes(loaded, tokens, progs):
    """A lane beside a near, a far and a dead lane gives bitwise the same
    logits (test_mla's case on this block's programs)."""
    import jax
    import jax.numpy as jnp

    params, cfg = loaded
    pass_, _, lane_step = progs
    near = prefill(params, cfg, tokens[5:], 10, pass_=pass_)[2]
    far = prefill(params, cfg, tokens[9:], 60, pass_=pass_)[2]
    mine = prefill(params, cfg, tokens, 30, pass_=pass_)[2]

    def run(other, other_pos, other_live):
        stacked = jax.tree.map(lambda *a: jnp.stack(a), mine, other)
        out = []
        for t in range(3):
            lg, stacked, _, _ = lane_step(
                params, jnp.asarray([tokens[30 + t], 7], jnp.int32),
                jnp.asarray([30 + t, other_pos + t], jnp.int32),
                stacked, jnp.asarray([True, other_live]))
            out.append(np.asarray(lg[0]))
        return np.stack(out)

    base = run(near, 10, True)
    for other, other_pos, other_live in (
            (far, 60, True), (far, 60, False), (near, 10, False)):
        assert np.array_equal(run(other, other_pos, other_live), base)


def test_heads_of_64_through_the_kernels_in_interpret_mode(loaded, tokens,
                                                           served):
    """The same file served as a TPU serves it: the flash kernel on the
    slices and the decode kernel (it stores the step's row) on rows of two
    heads side by side."""
    from llama_fastapi_k8s_gpu_tpu.models import lfm2
    from llama_fastapi_k8s_gpu_tpu.models.llama import ring_write_impl

    params, cfg = loaded
    cfg = dataclasses.replace(cfg, attn_impl="pallas")
    assert lfm2.CACHE.decode_kernel_block(cfg) == N_CTX
    assert ring_write_impl(cfg) == "kernel"
    logits, _, cache = serve(params, cfg, tokens, programs(cfg), n_seq=60)
    assert worst(logits, served[0][:60]) < 2e-2
    assert rel(cache["k"][:, :, :60], served[2]["k"][:, :, :60]) < 2e-2


def test_two_heads_lie_side_by_side_in_a_row_of_the_ring(loaded):
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.models import lfm2

    cfg = loaded[1]
    g = lfm2.ring_view(cfg)
    assert (lfm2.ring_pack(cfg), g.n_kv_heads, g.head_dim) == (2, 1, 128)
    assert (g.sm_scale, g.n_layers, g.cache_kind) == (0.125, 2, "ring")
    q = jnp.arange(3 * 4 * 64, dtype=jnp.float32).reshape(3, 4, 64) + 1
    qp = np.asarray(lfm2.pack_queries(q, cfg))
    assert qp.shape == (3, 4, 128)
    # heads 0, 1 read KV head 0 (the left columns), heads 2, 3 KV head 1
    assert not qp[:, :2, 64:].any() and not qp[:, 2:, :64].any()
    assert np.array_equal(np.asarray(lfm2.unpack_context(
        jnp.asarray(qp).reshape(3, -1), cfg)).reshape(3, 4, 64), q)
    # heads as wide as a tile, or KV heads no pack divides, stay as they are
    assert lfm2.ring_pack(dataclasses.replace(cfg, head_width=128)) == 1
    assert lfm2.ring_pack(dataclasses.replace(cfg, n_kv_heads=1)) == 1


# ---------------------------------------------------------------------------
# the router, the experts' K = 1536
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("eps", [1e-6, 1e-20])
def test_the_router_divides_by_the_sum_plus_the_configurations_eps(loaded,
                                                                   eps):
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.models.routed import route_grouped

    cfg = dataclasses.replace(loaded[1], expert_weights_eps=eps)
    rng = np.random.default_rng(7)
    # scores of 8e-7 or so: the sum of three is the size of 1e-6
    hn = jnp.asarray(rng.standard_normal((6, 16)) * 0.01, jnp.float32)
    w = jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)
    bias = jnp.asarray(rng.standard_normal(8) * 1e-7, jnp.float32)
    w = w.at[:, 0].set(-14.0)
    hn = hn.at[:, 0].set(1.0)
    picks, wts = route_grouped(hn, w, bias, cfg)
    scores = 1 / (1 + np.exp(-np.asarray(hn) @ np.asarray(w).T))
    choice = scores + np.asarray(bias)[None]
    mine = np.argsort(-choice, -1)[:, :3]
    assert np.array_equal(np.sort(picks, -1), np.sort(mine, -1))
    picked = np.take_along_axis(scores, np.asarray(picks), -1)
    np.testing.assert_allclose(
        wts, picked / (picked.sum(-1, keepdims=True) + eps), rtol=1e-4)
    total = np.asarray(wts).sum(-1)
    assert (total < 0.8).all() if eps == 1e-6 else (total > 0.9999).all()


@pytest.fixture(scope="module")
def experts_1536():
    """Gate, up and down of 4 experts at D 512, F 1536, fused and plain: the
    down planes as models/params.py ``experts`` loads them, each row's K
    tile filled up to 2048 with zero blocks."""
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.gguf import GGMLType, quants
    from llama_fastapi_k8s_gpu_tpu.ops.pallas.experts import prep_experts
    from tests.test_olmoe import _expert_weights

    D, F, E = 512, 1536, 4
    rng = np.random.default_rng(1536)
    g, gd = _expert_weights(E, F, D, "Q4_K", rng)
    u, ud = _expert_weights(E, F, D, "Q4_K", rng)
    fused, plain = [], []
    for _ in range(2):
        w = rng.standard_normal((E, D, F)).astype(np.float32) * F ** -0.5
        raw = np.asarray(quants.quantize(w, GGMLType.Q6_K))
        plain.append(quants.dequantize(raw, GGMLType.Q6_K, w.size
                                       ).reshape(E, D, F))
        raw = raw.reshape(E * D, -1)
        raw = np.pad(raw, ((0, 0), (0, raw.shape[1] // 3))).reshape(-1)
        fused.append(prep_experts(raw, E, D, 2048, GGMLType.Q6_K))
    d = {key: jnp.stack([f[key] for f in fused]) for key in fused[0]}
    dd = {"w": jnp.asarray(np.stack(plain), jnp.bfloat16)}
    assert d["q4"].shape == (2, E, D, 1024)
    return (g, u, d), (gd, ud, dd)


@pytest.mark.parametrize("M", [3, 70], ids=["few_rows", "many_rows"])
def test_the_experts_k_1536_fills_its_tile_up_with_zero_blocks(experts_1536,
                                                               M):
    """``ffn_down_exps`` at K = 1536: the file's blocks with the K tile's
    last quarter filled up with zero blocks, as the loader stores it
    (``padded_k``; nothing requantized), against the dequantized experts."""
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.ops.linear import padded_k as dense_k
    from llama_fastapi_k8s_gpu_tpu.ops.pallas.experts import (
        experts_compatible, fold_factor, padded_k, routed_experts)
    from tests.test_olmoe import SAME

    D, F, E, k = 512, 1536, 4, 2
    assert (padded_k(F), fold_factor(F), fold_factor(1024)) == (2048, 1, 2)
    assert padded_k(1024) == 1024 and padded_k(7168) == 8192
    # the dense matrices keep their quarter: no standing file loads otherwise
    assert dense_k(F) == F and dense_k(11776) == 12288
    assert experts_compatible(2048, padded_k(F))
    fused, plain = experts_1536
    rng = np.random.default_rng(M)
    x = jnp.asarray(rng.standard_normal((M, D)), jnp.bfloat16)
    picks = np.stack([rng.permutation(E)[:k] for _ in range(M)]
                     ).astype(np.int32)
    picks[1] = E
    wts = jnp.asarray(rng.random((M, k)), jnp.float32)
    y, count = routed_experts(x, jnp.asarray(picks), wts, *fused, 1)
    y2, count2 = routed_experts(x, jnp.asarray(picks), wts, *plain, 1)
    assert rel(y, y2) < SAME
    np.testing.assert_array_equal(count, count2)
    assert not np.asarray(y[1], np.float32).any()


# ---------------------------------------------------------------------------
# the file, the cache, the counters, the refusals
# ---------------------------------------------------------------------------

def test_gguf_round_trip_of_the_keys_and_the_layer_kinds(loaded):
    from llama_fastapi_k8s_gpu_tpu.models import lfm2
    from llama_fastapi_k8s_gpu_tpu.models.config import CONV_RING
    from llama_fastapi_k8s_gpu_tpu.testing import TINY_LFM2_CFG as T

    params, cfg = loaded
    assert cfg.cache_kind == CONV_RING == "conv-state+ring"
    for key in ("mixers", "conv_l_cache", "head_dim", "qk_norm_per_head",
                "rope_neox", "n_dense_layers", "expert_ffn_dim", "n_experts",
                "n_experts_used", "expert_gating", "expert_weights_eps",
                "norm_topk_prob", "tie_embeddings", "n_kv_heads"):
        assert getattr(cfg, key) == getattr(T, key), key
    layers = params["layers"]
    assert sorted(layers) == ["attn", "conv", "dense", "moe"]
    assert layers["conv"]["conv"].shape == (6, 256, 3)
    assert layers["conv"]["in_proj"]["w"].shape == (6, 768, 256)
    assert layers["attn"]["attn_q_norm"].shape == (2, 64)
    assert layers["dense"]["ffn_norm"].shape == (2, 256)
    assert layers["moe"]["w_router"].shape == (6, 8, 256)
    assert params["output"]["w"] is params["tok_emb"]        # the tied head
    assert lfm2.runs(cfg) == [
        ("dense", "conv", 0, 0, 2), ("moe", "attn", 0, 0, 1),
        ("moe", "conv", 1, 2, 3), ("moe", "attn", 4, 1, 1),
        ("moe", "conv", 5, 5, 1)]


def published_cfg():
    from llama_fastapi_k8s_gpu_tpu.models.config import ModelConfig

    return ModelConfig(
        vocab_size=65536, dim=2048, n_layers=20, n_heads=32, n_kv_heads=8,
        ffn_dim=11776, n_ctx=16384, rope_theta=1e6, head_width=64,
        qk_norm_per_head=True, rope_neox=True,
        mixers=("conv", "conv", "attn", "conv") * 5, conv_l_cache=3,
        n_dense_layers=2, expert_ffn_dim=1536, n_experts=64,
        n_experts_used=4, norm_topk_prob=True, expert_gating="sigmoid",
        expert_weights_eps=1e-6, tie_embeddings=True)


def test_a_lanes_cache_at_the_published_sizes_is_168_mb_and_123_kb():
    """Computed, not allocated: a ring in 5 of 20 layers, two rows of 2048
    in 15."""
    from llama_fastapi_k8s_gpu_tpu.models import lfm2
    from llama_fastapi_k8s_gpu_tpu.models.llama import cache_nbytes

    cfg = published_cfg()
    assert lfm2.conv_nbytes(cfg) == 15 * 2 * 2048 * 2 == 122880
    assert cache_nbytes(cfg) == 5 * 16384 * 2048 + 122880
    assert round((cache_nbytes(cfg) - 122880) / 1e6) == 168
    assert len(lfm2.runs(cfg)) == 11
    assert cfg.n_linear_weights > 11e9        # ``auto`` serves it fused


@pytest.mark.parametrize("n_ctx", [128, 512])
def test_the_cache_is_what_cache_nbytes_says(loaded, n_ctx):
    from llama_fastapi_k8s_gpu_tpu.models.llama import cache_nbytes, init_cache

    cfg = dataclasses.replace(loaded[1], n_ctx=n_ctx)
    cache = init_cache(cfg)
    assert sum(leaf.nbytes for leaf in cache.values()) == cache_nbytes(cfg)
    assert cache["conv"].shape == (6, 2, 256)
    assert cache["k"].shape == cache["v"].shape == (2, 1, n_ctx, 128)


def test_the_counters_count_conv_layers_and_ring_layers(loaded):
    from llama_fastapi_k8s_gpu_tpu.models import lfm2
    from llama_fastapi_k8s_gpu_tpu.models.llama import decode_chunk_slots

    cfg = loaded[1]
    counts = lfm2.CACHE.new_counts()
    lfm2.CACHE.note_decode(counts, cfg, [10, 40], 4, [10, 40, 90])
    assert counts["state_updates"] == 2 * 4 * 6      # live lane x step x conv
    read = sum(decode_chunk_slots(p, 4, N_CTX, 90)[0] for p in (10, 40))
    live = sum(decode_chunk_slots(p, 4, N_CTX, 90)[1] for p in (10, 40))
    assert (counts["read"], counts["live"]) == (2 * read, 2 * live)
    assert counts["rows_written"] == 0               # XLA stores the rows
    kernel = dataclasses.replace(cfg, attn_impl="pallas")
    lfm2.CACHE.note_decode(counts, kernel, [10, 40], 4, [10, 40, 90])
    assert counts["rows_written"] == 3 * 4 * 2       # the ring layers' alone
    assert lfm2.CACHE.note_prefill(counts, cfg, 50, None) == {}
    assert lfm2.CACHE.note_prefill(counts, cfg, 50, [(0, 16)] * 4) == {
        "conv_layers": 6, "ring_layers": 2, "slices": 4}
    assert counts["state_starts"] == 2
    g = lfm2.CACHE.gauges(counts)
    assert g["conv_state_updates_total"] == 2 * 2 * 4 * 6
    assert g["conv_state_starts_total"] == 2
    assert lfm2.CACHE.widest_slice(cfg) == 256
    assert lfm2.CACHE.widest_slice(kernel) == 0


def test_the_new_metrics_are_in_the_catalog():
    from llama_fastapi_k8s_gpu_tpu.models import lfm2
    from llama_fastapi_k8s_gpu_tpu.obs.catalog import lookup

    for name in lfm2.CACHE.own_gauges:
        assert lookup(name) is not None, name


@pytest.mark.parametrize("meta, words", [
    ({"shortconv.l_cache": 1}, "shortconv.l_cache 1"),
    ({"attention.head_count_kv": 2}, "must be an array with one entry"),
    ({"attention.head_count_kv": [0, 0, 2, 0, 0, 0, 1, 0]},
     "must be one count"),
    ({"expert_gating_func": 3}, "lfm2moe: expert_gating_func 3"),
    ({"expert_shared_count": 1}, "a shared expert"),
])
def test_a_file_the_block_cannot_compute_is_refused_by_name(gguf_path, meta,
                                                            words):
    from llama_fastapi_k8s_gpu_tpu.gguf import GGUFFile
    from llama_fastapi_k8s_gpu_tpu.models.config import ModelConfig

    gf = GGUFFile(gguf_path)
    for key, value in meta.items():
        gf.metadata["lfm2moe." + key] = value
    with pytest.raises(ValueError, match=words):
        ModelConfig.from_gguf(gf, n_ctx=N_CTX)


@pytest.mark.parametrize("feature, setting", [
    ("int8", "LFKT_KV_DTYPE=int8"), ("paged", "LFKT_KV_PAGED=1")])
def test_what_the_kind_cannot_serve_is_refused_by_name(lane_engine, feature,
                                                       setting):
    """Every ask the kind refuses, through the engines' one refusal (the
    engine that serves the module asks again, with the feature on)."""
    from llama_fastapi_k8s_gpu_tpu.models.cache import FEATURES

    assert set(lane_engine.cache.supports) == set(FEATURES) - {"slice"}
    with pytest.raises(ValueError, match=f"{setting} cannot serve "
                                         "architecture 'lfm2moe'"):
        lane_engine._refuse_unsupported({feature: 2})


def test_an_int8_cache_is_refused_at_construction(gguf_path):
    from llama_fastapi_k8s_gpu_tpu.engine import Engine

    with pytest.raises(ValueError, match="LFKT_KV_DTYPE=int8 cannot serve "
                                         "architecture 'lfm2moe'"):
        Engine(gguf_path, n_ctx=N_CTX, kv_dtype="int8")


# ---------------------------------------------------------------------------
# the engines and the server: ONE lane engine for the module
# ---------------------------------------------------------------------------

SYSTEM = "you are a careful assistant who answers in short plain sentences"
MSGS = [{"role": "system", "content": SYSTEM},
        {"role": "user", "content": "tell me about short convolutions"}]
MSGS2 = [{"role": "system", "content": SYSTEM},
         {"role": "user", "content": "and what do two columns carry"}]


@pytest.fixture(scope="module")
def lane_engine(gguf_path):
    from llama_fastapi_k8s_gpu_tpu.engine.continuous import ContinuousEngine

    eng = ContinuousEngine(gguf_path, n_ctx=N_CTX * 4, prefill_chunk=SLICE,
                           decode_chunk=4, batch_size=2)
    yield eng
    eng.shutdown()


def test_lanes_freed_and_taken_again_give_the_serial_engines_text(
        gguf_path, lane_engine):
    """Three requests on two lanes: a lane is freed and taken again, and
    a request gives the same greedy text whichever lane it took and
    whatever that lane held before (its prefill started from zero rows),
    which is the serial engine's prompt count and text."""
    from llama_fastapi_k8s_gpu_tpu.engine import Engine

    serial = Engine(gguf_path, n_ctx=N_CTX * 2, prefill_chunk=SLICE,
                    decode_chunk=4)
    want = serial.create_chat_completion(MSGS, max_tokens=6, temperature=0.0)
    kind = serial.cache_kind
    assert kind["kind"] == "conv-state+ring"
    assert (kind["l_cache"], kind["conv_layers"], kind["ring_layers"]) \
        == (3, 6, 2)
    assert (kind["dense_layers"], kind["routed_layers"]) == (2, 6)
    assert kind["experts_held"] == [0, 8]
    assert kind["bytes_per_lane"] == 2 * 256 * 2 * 64 * 2 * 2 + 6 * 2 * 256 * 2
    assert kind["prefix_reuse"].startswith("off: a convolution")
    assert not serial._prefix_cache and serial.cfg.attn_impl == "xla"
    g = serial.cache_read_gauges()
    assert g["conv_state_starts_total"] == 1
    assert g["conv_state_updates_total"] % 6 == 0 < g["conv_state_updates_total"]
    assert 0 < g["ring_slots_live_total"] <= g["ring_slots_read_total"]
    eng = lane_engine
    assert not eng._lane_prefix
    futs = [eng.submit(m, max_tokens=6, temperature=0.0)
            for m in (MSGS, MSGS2, MSGS)]
    outs = [f.result(timeout=300) for f in futs]
    for o in (outs[0], outs[2]):
        assert o["usage"]["prompt_tokens"] == want["usage"]["prompt_tokens"]
    assert outs[2]["choices"][0]["message"] == outs[0]["choices"][0]["message"]
    assert not eng.scheduler_stats().get("lane_prefix_hits")
    assert eng.cache_read_gauges()["conv_state_starts_total"] == 3
    snap = eng.expert_counters.snapshot(block=True)
    assert 0 < snap["picks_held"] == snap["picks_total"]


@pytest.mark.anyio
async def test_the_server_serves_the_file_and_names_the_kind(lane_engine):
    import json

    import httpx

    from llama_fastapi_k8s_gpu_tpu.server.app import create_app
    from llama_fastapi_k8s_gpu_tpu.utils.config import Settings

    app = create_app(engine=lane_engine, settings=Settings())
    transport = httpx.ASGITransport(app=app)
    async with transport:
        await app.router.startup()
        async with httpx.AsyncClient(transport=transport,
                                     base_url="http://test") as client:
            r = await client.post("/v1/chat/completions", json={
                "messages": MSGS, "max_tokens": 8, "temperature": 0.0,
                "stream": True, "stream_options": {"include_usage": True}})
            assert r.status_code == 200
            events = [json.loads(ln[6:]) for ln in r.text.splitlines()
                      if ln.startswith("data: {")]
            usage = [e["usage"] for e in events if e.get("usage")][-1]
            assert 1 <= usage["completion_tokens"] <= 8
            from tests.test_server import BODY

            r = await client.post("/response", json=BODY)
            assert r.status_code == 200, r.text
            r = await client.post("/response/stream", json=BODY)
            assert r.status_code == 200, r.text
            eng = (await client.get("/health")).json()["engine"]
            assert eng["cache"]["kind"] == "conv-state+ring"
            assert eng["cache"]["l_cache"] == 3
            assert eng["cache"]["heads_per_ring_row"] == 2
            assert eng["ring_write"] == "xla"
            # the experts serve dequantized here: no grouped call, no slots
            assert "expert_slots" not in eng and not lane_engine.expert_slots
            assert set(eng["weight_formats"]) >= {
                "conv.in_proj", "conv.out_proj", "attn.wq", "attn.wo",
                "dense.w_down", "moe.w_gate_exps", "moe.w_down_exps"}
            d = (await client.get("/debug/compiles")).json()
            assert not d.get("degrades")
            m = (await client.get("/metrics")).text
            for name in ("conv_state_updates_total",
                         "conv_state_starts_total", "ring_slots_read_total",
                         "experts_read_total", "expert_layer_steps_total",
                         "expert_slots_skipped_total", "expert_picks_total"):
                assert name in m, name
        await app.router.shutdown()
