"""The ``deepseek32`` block (models/mla.py: ``deepseek2``'s latent attention
with a learned INDEXER that picks the positions each query attends) at a
tiny size on the CPU, against the plain float32 reference
(benchmarks/reference_dsa.py).

The tiny file (``testing.TINY_DSA_CFG``) is ``TINY_MLA_CFG``'s, tensor for
tensor from the same draws, with 4 indexer heads of 16 (8 rotated columns)
and a selection of 16 positions: it bites from the 17th token on, and the
sequences here are 72 long.

LIMITS, each between what the program reads and what the nearest other
function reads:

- ``LIMIT`` (logits, as tests/test_mla.py's): the program against the
  reference on the program's own picks AND selection reads 0.5-1.5 % of the
  logits' norm over blocks of 16 positions; ``no_select`` (the dense layer)
  and the other controls read 6 % or more.
- ``SCORE`` (a layer's indexer scores over the causal part, relative to
  their norm there): the program (a bf16 stream, bf16 operands, float32
  relu, weights and sums) reads 0.7-3 % from the first layer to the third;
  a dropped ``w_h`` and an interleaved rotation read 50 % or more.
- ``SUMS`` (the weighted sum over the heads on GIVEN bf16 operands, the one
  place where float32 sums can be told from bfloat16 ones: against the whole
  model the bf16 operands hide them): the program reads 1e-6, bfloat16 sums
  3e-3.
- ``SLACK`` (the selection): every position the program picked has a
  reference score no lower than the reference's k-th largest of that row
  minus ``SLACK`` x the row's spread (near-ties at rank k swap under bf16
  rounding: the program's selection is then fed to the reference, so the
  logits compare tightly), and each row picks ``min(k, t + 1)`` exactly.
"""

from __future__ import annotations

import dataclasses
import os
import sys

import numpy as np
import pytest

from tests.test_mla import (
    BENCH, LIMIT, N_CTX, N_PROMPT, N_SEQ, PICKS, SLICE, load,
    reference_rows, rel, rows_that_differ, traced_with, with_kernel, worst)

SCORE = 6e-2
SUMS = 1e-4
SLACK = 3e-2
TOPK = 16


@pytest.fixture(scope="module")
def ref():
    sys.path.insert(0, BENCH)
    try:
        import reference_dsa
        yield reference_dsa
    finally:
        sys.path.remove(BENCH)


def _write(path, **kw):
    from llama_fastapi_k8s_gpu_tpu.testing import (
        TINY_DSA_CFG, write_tiny_mla_gguf)

    return write_tiny_mla_gguf(str(path), TINY_DSA_CFG, seed=3, **kw)


@pytest.fixture(scope="module")
def gguf_path(tmp_path_factory):
    path = tmp_path_factory.mktemp("dsa") / "tiny.gguf"
    _write(path)
    return str(path)


@pytest.fixture(scope="module")
def dense_path(tmp_path_factory):
    """The ``deepseek2`` file of the same seed: the same tensors, no indexer."""
    from llama_fastapi_k8s_gpu_tpu.testing import write_tiny_mla_gguf

    path = str(tmp_path_factory.mktemp("dsa") / "dense.gguf")
    write_tiny_mla_gguf(path, seed=3)
    return path


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(5).integers(4, 260, size=N_SEQ)


@pytest.fixture(scope="module")
def model(ref, gguf_path):
    return ref.open_model(gguf_path)


@pytest.fixture(scope="module")
def loaded(gguf_path):
    return load(gguf_path)


def programs(cfg):
    """tests/test_mla.py's three programs, each returning the routers' picks
    and the indexer's scores and selection too; one build a process for
    each (configuration, block widths), as there."""
    from llama_fastapi_k8s_gpu_tpu.models import mla

    key = (*traced_with(cfg), mla.INDEX_ROWS, mla.INDEX_BLOCK)
    if key not in _PROGRAMS:
        _PROGRAMS[key] = _build_programs(cfg)
    return _PROGRAMS[key]


_PROGRAMS = {}


def _build_programs(cfg):
    import jax

    from llama_fastapi_k8s_gpu_tpu.models.mla import forward
    from llama_fastapi_k8s_gpu_tpu.parallel.batched import step_bound

    @jax.jit
    def pass_(params, tokens, off, n, cache):
        return forward(params, cfg, tokens, off, cache, last_idx=n - 1,
                       return_all=True, with_picks=True, with_index=True)

    @jax.jit
    def step(params, token, pos, cache):
        return forward(params, cfg, token[None], pos, cache, with_picks=True,
                       with_index=True)

    @jax.jit
    def lane_step(params, tokens, poss, caches, live):
        bound = step_bound(cfg, poss, live)
        return jax.vmap(lambda t, p, c, lv: forward(
            params, cfg, t[None], p, c, live=lv, kv_bound=bound,
            with_picks=True, with_index=True))(tokens, poss, caches, live)
    return pass_, step, lane_step


def mla_reads(cfg):
    """(a slice's attention, a step's kernel block)."""
    from llama_fastapi_k8s_gpu_tpu.models import mla

    return (mla.slice_read(cfg, SLICE), mla.kernel_block(cfg))


def prefill(params, cfg, seq, n, size=SLICE, pass_=None, cache=None, start=0):
    """Positions [start, n) in passes of ``size``: (logits, picks (L_moe, n,
    k), scores (L, n, n_ctx), selection (L, n, n_ctx), the cache)."""
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.models.llama import init_cache

    pass_ = pass_ or programs(cfg)[0]
    cache = init_cache(cfg) if cache is None else cache
    out = [[], [], [], []]
    for off in range(start, n, size):
        part = np.full(size, 9, np.int32)
        real = seq[off:min(off + size, n)]
        part[:len(real)] = real
        lg, cache, pk, sc, sl = pass_(params, jnp.asarray(part),
                                      jnp.int32(off), jnp.int32(len(real)),
                                      cache)
        out[0].append(np.asarray(lg)[:len(real)])
        for dst, a in zip(out[1:], (pk, sc, sl)):
            dst.append(np.asarray(a)[:, :len(real)])
    return (np.concatenate(out[0]), *(np.concatenate(o, axis=1)
                                      for o in out[1:]), cache)


def serve(params, cfg, tokens, n_prompt=N_PROMPT, n_seq=N_SEQ):
    import jax.numpy as jnp

    pass_, step, _ = programs(cfg)
    logits, picks, scores, sel, cache = prefill(params, cfg, tokens, n_prompt,
                                                pass_=pass_)
    rows = [[logits], [picks], [scores], [sel]]
    for t in range(n_prompt, n_seq):
        lg, cache, pk, sc, sl = step(params, jnp.int32(tokens[t]),
                                     jnp.int32(t), cache)
        rows[0].append(np.asarray(lg)[None])
        for dst, a in zip(rows[1:], (pk, sc, sl)):
            dst.append(np.asarray(a))
    return (np.concatenate(rows[0]),
            *(np.concatenate(r, axis=1) for r in rows[1:]), cache)


@pytest.fixture(scope="module")
def served(loaded, tokens):
    """The serial programs over the whole sequence, slices then steps
    through both leaves of the cache."""
    return serve(*loaded, tokens)


@pytest.fixture(scope="module")
def fed(ref, model, tokens, served):
    """The reference on the program's picks and selection: (logits, the
    indexer's (scores, own selection) per layer)."""
    _, picks, _, sel, _ = served
    logits, _, index = ref.forward(*model, tokens, use_picks=picks,
                                   use_sel=sel[:, :, :N_SEQ])
    return np.asarray(logits), index


def score_error(got, want):
    """The distance of a layer's scores over the causal part, relative to
    their norm there.  ``got`` (S, >= S), ``want`` (S, S)."""
    S = want.shape[0]
    causal = np.tril(np.ones((S, S), bool))
    return rel(got[:, :S][causal], want[causal])


def selection_faults(sel, want_scores, k=TOPK, slack=SLACK):
    """Rows whose selection is NOT ``min(k, t + 1)`` positions at or below
    t, each with a reference score no lower than the reference's k-th
    largest less ``slack`` x the row's spread."""
    S = want_scores.shape[0]
    bad = 0
    for t in range(S):
        row, picked = want_scores[t, :t + 1], np.flatnonzero(sel[t])
        n = min(k, t + 1)
        kth = np.sort(row)[-n]
        floor = kth - slack * (row.max() - row.min() + 1e-30)
        bad += not (len(picked) == n and picked.max() <= t
                    and (row[picked] >= floor).all())
    return bad


# ---------------------------------------------------------------------------
# the program against the reference
# ---------------------------------------------------------------------------

def test_slices_then_decode_through_both_leaves(ref, model, tokens, served,
                                                fed):
    logits, picks, scores, sel, cache = served
    assert set(cache) == {"lat", "idx"} and cache["idx"].shape == (
        3, 1, N_CTX, 128)
    want, index = fed
    for layer, (want_scores, _) in enumerate(index):
        err = score_error(scores[layer], want_scores)
        print("layer", layer, "scores", err)
        assert err < SCORE, layer
        assert selection_faults(sel[layer], want_scores) == 0, layer
        # (benchmarks/compare_dsa.py's measure, by picks and not by rows)
        assert ref.picks_at_fault(sel[layer], want_scores, range(N_SEQ),
                                  TOPK, SLACK) == 0
    # the selection bites: a late query attends 16 of its 60-odd positions
    assert sel[:, -1].sum(-1).tolist() == [TOPK] * 3
    print("read", worst(logits[:N_PROMPT], want[:N_PROMPT]),
          worst(logits[N_PROMPT:], want[N_PROMPT:]))
    assert worst(logits[:N_PROMPT], want[:N_PROMPT]) < LIMIT
    assert worst(logits[N_PROMPT:], want[N_PROMPT:]) < LIMIT


def test_the_references_own_choices_are_nearly_the_programs(ref, model,
                                                            tokens, served):
    """On ITS OWN picks and selection the reference differs from the
    program's in a few rows (near-ties that bf16 rounding orders the other
    way), not many."""
    _, picks, _, sel, _ = served
    _, routes, index = ref.forward(*model, tokens)
    # (a swapped position moves a later router's near-ties too)
    assert rows_that_differ(picks, np.stack([p for _, p in routes])) \
        <= 2 * PICKS
    differ = sum(int(np.any(sel[i][:, :N_SEQ] != own, -1).sum())
                 for i, (_, own) in enumerate(index))
    print("rows whose selection differs", differ, "of", 3 * N_SEQ)
    assert differ <= 3 * N_SEQ // 4


@pytest.mark.parametrize("control,fails", [
    ("no_index_weights", "scores"), ("index_rope_interleaved", "scores"),
    ("no_select", "logits")])
def test_another_function_fails_a_limit(ref, model, tokens, served, control,
                                        fails):
    """Each control computes less than (or other than) the configuration
    states, and reads past the limit of what it changes: the indexer's
    scores (and with them the selection), or the logits."""
    logits, picks, scores, sel, _ = served
    got, _, index = ref.forward(*model, tokens, use_picks=picks,
                                use_sel=sel[:, :, :N_SEQ], **{control: True})
    if fails == "logits":
        assert worst(logits, np.asarray(got)) > LIMIT
        return
    errs = [score_error(scores[i], s) for i, (s, _) in enumerate(index)]
    print(control, errs)
    assert min(errs) > 5 * SCORE
    # another function picks other positions
    assert min(selection_faults(sel[i], s)
               for i, (s, _) in enumerate(index)) > N_SEQ // 2
    assert min(ref.picks_at_fault(sel[i], s, range(N_SEQ), TOPK, SLACK)
               for i, (s, _) in enumerate(index)) > 0.1


def test_bfloat16_sums_over_the_heads_fail_the_limit_of_the_sum(ref):
    """On given bf16 operands (64 heads, as published) the program's sum over
    the heads is the float32 one to rounding; per-head scores, weights and
    partial sums rounded to bfloat16 read thirty times the limit."""
    import jax
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.models import mla
    from llama_fastapi_k8s_gpu_tpu.testing import TINY_DSA_CFG

    cfg = dataclasses.replace(TINY_DSA_CFG, n_ctx=128, index_heads=64)
    keys = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(keys[0], (24, 64, 16)).astype(jnp.bfloat16)
    w = jax.random.normal(keys[1], (24, 64))
    idx = jax.random.normal(keys[2], (3, 1, 128, 128)).astype(jnp.bfloat16)
    got = np.asarray(mla.index_scores(q, w, idx, 2, 127, cfg))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.weighted_relu_sum(q, idx[2, 0, :, :16], w))
        rounded = np.asarray(ref.weighted_relu_sum(
            q, idx[2, 0, :, :16], w, index_dtype=jnp.bfloat16))
    print("read", rel(got, want), rel(rounded, want))
    assert rel(got, want) < SUMS
    assert rel(rounded, want) > 10 * SUMS


def test_the_hadamard_rotation_changes_no_score(ref, model, tokens):
    """The published indexer rotates qI and kI by a Hadamard matrix before
    it quantises them; the rotation is orthogonal, so the float32 scores
    with and without it agree to rounding (the departure ``assumed``
    states)."""
    import jax
    import jax.numpy as jnp

    hp, tensors = model
    w = ref.indexer_weights(tensors, ref.mla.layer_weights(tensors, 1), 1)
    rng = np.random.default_rng(0)
    n = jnp.asarray(rng.standard_normal((48, 256)), jnp.float32)
    c_q = jnp.asarray(rng.standard_normal((48, 64)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        plain = np.asarray(ref.index_scores(hp, w, n, c_q))
        turned = np.asarray(ref.index_scores(hp, w, n, c_q, rotate=True))
    had = ref.hadamard(16)
    assert np.allclose(had @ had.T, np.eye(16), atol=1e-6)
    assert rel(turned, plain) < 1e-5


def test_below_index_topk_the_layer_is_the_dense_latent_layer(
        ref, model, loaded, dense_path, tokens):
    """While a query has no more than ``index_topk`` positions the
    selection is all of them: the ``deepseek32`` file gives the logits of
    the ``deepseek2`` file of the same tensors, bit for bit, and the
    reference with the selection off is the reference."""
    from tests.test_mla import prefill as dense_prefill

    params, cfg = loaded
    got, _, _, sel, _ = prefill(params, cfg, tokens, TOPK)
    assert sel.sum(-1).tolist() == [list(range(1, TOPK + 1))] * 3
    want, _, _ = dense_prefill(*load(dense_path), tokens, TOPK)
    assert np.array_equal(got, want)
    # ... and one token further it no longer is
    more, _, _, _, _ = prefill(params, cfg, tokens, 2 * TOPK)
    dense, _, _ = dense_prefill(*load(dense_path), tokens, 2 * TOPK)
    assert np.array_equal(more[:TOPK], dense[:TOPK])
    assert not np.array_equal(more[TOPK:], dense[TOPK:])
    own = np.asarray(ref.forward(*model, tokens[:TOPK])[0])
    off = np.asarray(ref.forward(*model, tokens[:TOPK], no_select=True)[0])
    assert np.array_equal(own, off)


# ---------------------------------------------------------------------------
# the indexer's pieces
# ---------------------------------------------------------------------------

def _top_by_sort(scores, positions, k):
    out = np.zeros(scores.shape, bool)
    for r, t in enumerate(positions):
        order = np.argsort(-scores[r, :t + 1], kind="stable")[:k]
        out[r, order] = True
    return out


@pytest.mark.parametrize("name", ["random", "ties", "negative_and_zero",
                                  "fewer_than_k", "past_the_scored_blocks"])
def test_the_threshold_search_picks_what_a_stable_sort_picks(name):
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.models import mla

    rng = np.random.default_rng(1)
    S, n, k = 12, 96, 16
    scores = rng.standard_normal((S, n)).astype(np.float32)
    positions = np.arange(80, 80 + S)
    if name == "ties":      # many equal scores around rank k
        scores = np.round(scores * 2) / 2
    if name == "negative_and_zero":
        scores = -np.abs(np.round(scores))       # 0.0, -0.0, -1.0, ...
        scores[:, ::7] = -0.0
    if name == "fewer_than_k":
        positions = np.arange(S) + 3
    if name == "past_the_scored_blocks":         # ``index_scores``' -inf
        scores[:, 64:] = -np.inf
        positions = np.arange(40, 40 + S)
    got = np.asarray(mla.select_topk(jnp.asarray(scores),
                                     jnp.asarray(positions, jnp.int32), k))
    assert np.array_equal(got, _top_by_sort(scores, positions, k))
    assert got.sum(-1).tolist() == [min(k, t + 1) for t in positions]


@pytest.mark.parametrize("S,rows,block", [(1, 8192, 1024), (24, 48, 32),
                                          (24, 24, 32)])
def test_the_scores_loop_is_the_sum_over_heads(monkeypatch, S, rows, block):
    """Blocks of keys up to the bound and groups of heads within a block:
    the same sums, and ``-inf`` past the last block read."""
    import jax
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.models import mla
    from llama_fastapi_k8s_gpu_tpu.testing import TINY_DSA_CFG

    monkeypatch.setattr(mla, "INDEX_ROWS", rows)
    monkeypatch.setattr(mla, "INDEX_BLOCK", block)
    cfg = dataclasses.replace(TINY_DSA_CFG, n_ctx=128)
    keys = jax.random.split(jax.random.PRNGKey(S), 3)
    q = jax.random.normal(keys[0], (S, 4, 16)).astype(jnp.bfloat16)
    w = jax.random.normal(keys[1], (S, 4))
    idx = jax.random.normal(keys[2], (3, 1, 128, 128)).astype(jnp.bfloat16)
    bound = 70
    got = np.asarray(mla.index_scores(q, w, idx, 1, bound, cfg))
    per_head = np.einsum("shd,td->sht", np.asarray(q, np.float32),
                         np.asarray(idx[1, 0, :, :16], np.float32))
    want = np.einsum("sht,sh->st", np.maximum(per_head, 0), np.asarray(w))
    read = (bound // block + 1) * block
    assert rel(got[:, :read], want[:, :read]) < 1e-5
    assert np.all(np.isneginf(got[:, read:]))


@pytest.mark.parametrize("S,off", [(256, 300), (384, 0), (512, 512)])
def test_a_wide_slices_scores_are_its_rows(monkeypatch, S, off):
    """A slice of any width goes through the one loop: the wider it is the
    fewer heads a group holds (``INDEX_ROWS`` (head, query) rows at most),
    and every row scores what it scores in a narrow slice whose one group
    holds all the heads, to float32's order of addition."""
    import jax
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.models import mla
    from llama_fastapi_k8s_gpu_tpu.testing import TINY_DSA_CFG

    monkeypatch.setattr(mla, "INDEX_BLOCK", 128)
    monkeypatch.setattr(mla, "INDEX_ROWS", 512)
    cfg = dataclasses.replace(TINY_DSA_CFG, n_ctx=1024, index_heads=8,
                              index_dim=32)
    keys = jax.random.split(jax.random.PRNGKey(S), 3)
    q = jax.random.normal(keys[0], (S, 8, 32)).astype(jnp.bfloat16)
    w = jax.random.normal(keys[1], (S, 8))
    idx = jax.random.normal(keys[2], (3, 1, 1024, 128)).astype(jnp.bfloat16)
    bound = off + S - 1
    got = np.asarray(mla.index_scores(q, w, idx, 1, bound, cfg))
    read = (bound // 128 + 1) * 128
    for r in range(0, S, 64):       # 8 heads x 64 rows: one group
        want = np.asarray(mla.index_scores(q[r:r + 64], w[r:r + 64], idx, 1,
                                           bound, cfg))
        assert rel(got[r:r + 64, :read], want[:, :read]) < 1e-6
    assert np.all(np.isneginf(got[:, read:]))


def test_which_read_serves_follows_s_and_the_probes(loaded, monkeypatch):
    """A ``deepseek32`` file's engine probes the kernels it will run: the
    two latent kernels WITH the selection's bias operand; a failed probe
    leaves that read on its XLA loop and says so; the indexer's scores are
    a loop in plain XLA at every width and probe nothing."""
    from llama_fastapi_k8s_gpu_tpu.models import mla
    from llama_fastapi_k8s_gpu_tpu.ops.pallas import probe

    _, cfg = loaded
    assert mla.INDEXED.probe_kernels(cfg, "auto", "xla", []) == (cfg, "xla")
    probed = []
    all_, impl = mla.INDEXED.probe_kernels(cfg, "pallas", "xla", probed)
    assert probed == ["latent_decode_select", "latent_prefill_select"] \
        and impl == "xla"
    assert all_.latent_kernel and all_.latent_slice_kernel
    assert mla.INDEXED.engine_health(all_) == {"latent_slice_read": "kernel"}
    assert mla.INDEXED.engine_health(cfg) == {"latent_slice_read": "xla"}
    # a selection comes to the slice kernel as whole bf16 tiles of rows
    assert mla.slice_tile(all_, 16) and not mla.slice_tile(all_, 8)
    from llama_fastapi_k8s_gpu_tpu.obs.devtime import DEVTIME

    monkeypatch.setattr(probe, "probe_latent_prefill_select",
                        lambda: "Mosaic: no")
    before = len(DEVTIME.degrades())
    one, _ = mla.INDEXED.probe_kernels(cfg, "pallas", "xla", [])
    assert one.latent_kernel and not one.latent_slice_kernel
    assert [d["reason"] for d in DEVTIME.degrades()[before:]] == ["Mosaic: no"]
    # (the ledger is the process's: tests/test_mla.py has the reason)
    with DEVTIME._lock:
        DEVTIME._degrades.pop(("<lambda>", "Mosaic: no"))


def _select_inputs(S, n_ctx, k, off):
    import jax
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.models import mla
    from llama_fastapi_k8s_gpu_tpu.testing import TINY_DSA_CFG

    cfg = dataclasses.replace(TINY_DSA_CFG, n_ctx=n_ctx, index_topk=k)
    H, r, d_r = cfg.n_heads, cfg.kv_lora_rank, cfg.qk_rope_dim
    W = mla.leaf_width(cfg)
    keys = jax.random.split(jax.random.PRNGKey(S + off), 3)
    lat = jax.random.normal(keys[0], (3, 1, n_ctx, W)).astype(
        jnp.bfloat16).at[..., r + d_r:].set(0)
    q_full = jax.random.normal(keys[1], (S, H, W)).astype(
        jnp.bfloat16).at[..., r + d_r:].set(0)
    pos = off + jnp.arange(S, dtype=jnp.int32)
    sel = mla.select_topk(jax.random.normal(keys[2], (S, n_ctx)), pos, k)
    return cfg, q_full, lat, pos, sel


@pytest.mark.parametrize("name,S,off", [
    ("one_head_a_tile", 64, 100), ("whole_heads_a_tile", 16, 200),
    ("from_position_0", 32, 0)])
def test_the_slice_kernel_with_a_selection_is_the_masked_loop(name, S, off):
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.models import mla
    from llama_fastapi_k8s_gpu_tpu.ops.pallas import latent_attention_prefill

    cfg, q_full, lat, pos, sel = _select_inputs(S, 256, 24, off)
    want = mla.latent_attention(q_full, lat, 1, pos, off + S - 1, cfg, sel)
    dense = mla.latent_attention(q_full, lat, 1, pos, off + S - 1, cfg)
    got = latent_attention_prefill(
        q_full.transpose(1, 0, 2), lat, 1, jnp.int32(off),
        sm_scale=mla.attn_scale(cfg), v_width=cfg.kv_lora_rank,
        block_q=32, block_k=64, sub_k=32, chains=2, interpret=True, sel=sel)
    assert rel(got.astype(jnp.float32), want) < 1e-2
    if off:
        assert rel(want, dense) > 0.1     # the mask is no small thing


def test_the_decode_kernel_with_a_selection_is_the_masked_loop_per_lane():
    """Under ``vmap``: one kernel over the lanes, each lane its own bias;
    the leaf comes back with the step's row stored, as without a
    selection; a dead lane reads and stores nothing."""
    import jax
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.models import mla
    from llama_fastapi_k8s_gpu_tpu.ops.pallas import latent_attention_decode

    cfg, q_full, lat, _, _ = _select_inputs(3, 64, 8, 0)
    lats = jnp.stack([lat, lat[::-1], lat * 0.5])
    pos = jnp.asarray([37, 5, 50], jnp.int32)
    live = jnp.asarray([True, True, False])
    rows = q_full[:, 0] * 0.25
    sel = mla.select_topk(
        jax.random.normal(jax.random.PRNGKey(9), (3, 64)), pos, 8)

    def kernel(q, leaf, p, lv, row, s):
        return latent_attention_decode(
            q, leaf, 1, p, lv, row, sm_scale=mla.attn_scale(cfg), block_k=16,
            v_width=cfg.kv_lora_rank, interpret=True, sel=s)

    ctx, out = jax.vmap(kernel)(q_full, lats, pos, live, rows, sel)
    for lane in range(3):
        written = jax.lax.dynamic_update_slice(
            lats[lane], rows[lane][None, None, None], (1, 0, pos[lane], 0))
        if not live[lane]:
            assert np.array_equal(out[lane], lats[lane])
            assert not np.asarray(ctx[lane]).any()
            continue
        assert np.array_equal(out[lane], written)
        want = mla.latent_attention(q_full[lane][None], written, 1,
                                    pos[lane][None], pos[lane], cfg,
                                    sel[lane][None])
        assert rel(ctx[lane].reshape(cfg.n_heads, -1).astype(jnp.float32),
                   want[:, 0]) < 1e-2


# ---------------------------------------------------------------------------
# lanes, claims
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("read", ["loop", "kernel"])
def test_lanes_at_their_own_positions_one_dead(ref, model, loaded, tokens,
                                               read, monkeypatch):
    """A step of three lanes (the body of the lane engine's vmapped step):
    each live lane's logits are the reference's on that lane's own
    sequence, picks and selection; by the XLA loop and by the kernels."""
    import jax
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.models.llama import init_cache

    params, cfg = loaded
    if read == "kernel":
        cfg = with_kernel(cfg, monkeypatch)
    pass_, _, lane_step = programs(cfg)
    assert mla_reads(cfg) == {"loop": ("loop", 0),
                              "kernel": ("kernel", 16)}[read]
    seqs, prompts = [tokens, tokens[3:]], (33, 20)
    pre = [prefill(params, cfg, s, n, pass_=pass_)
           for s, n in zip(seqs, prompts)]
    garbage = jax.tree.map(lambda a: a + 1, init_cache(cfg))
    stacked = jax.tree.map(lambda *a: jnp.stack(a), pre[0][-1], pre[1][-1],
                           garbage)
    pos, live = [*prompts, N_CTX - 9], [True, True, False]
    rows = {0: [], 1: []}
    for _ in range(6):
        toks = [seqs[0][pos[0]], seqs[1][pos[1]], 0]
        lg, stacked, pk, sc, sl = lane_step(
            params, jnp.asarray(toks, jnp.int32), jnp.asarray(pos, jnp.int32),
            stacked, jnp.asarray(live))
        for lane in rows:
            rows[lane].append((np.asarray(lg[lane]), np.asarray(pk[lane]),
                               np.asarray(sl[lane])))
        pos = [p + 1 for p in pos]
    for lane, got in rows.items():
        first, n = prompts[lane], prompts[lane] + 6
        picks = np.concatenate([pre[lane][1]] + [g[1] for g in got], axis=1)
        sel = np.concatenate([pre[lane][3]] + [g[2] for g in got], axis=1)
        assert picks.shape[1] == n
        want = reference_rows(ref, model, seqs[lane], picks,
                              use_sel=sel[:, :, :n])
        assert worst(np.stack([g[0] for g in got]), want[first:]) < LIMIT, lane
        assert sel[:, -1].sum(-1).tolist() == [TOPK] * 3


def test_a_claimed_prefix_carries_the_index_keys(loaded, tokens):
    """What a lane claim and the serial prefix reuse rest on: suffix slices
    on a COPY of a cache whose two leaves hold the prefix (and, past it,
    another sequence's rows) give the full prefill's logits and selection;
    with the index-key leaf left behind they do not."""
    import jax
    import jax.numpy as jnp

    params, cfg = loaded
    full = prefill(params, cfg, tokens, 64)
    cache = prefill(params, cfg, tokens, 32)[-1]
    dirty = prefill(params, cfg, tokens[::-1], 64, cache=cache, start=32)[-1]
    claimed = jax.tree.map(lambda a: a.copy(), dirty)
    got = prefill(params, cfg, tokens, 64, cache=claimed, start=32)
    assert worst(got[0], full[0][32:]) < 1e-6
    assert np.array_equal(got[3], full[3][:, 32:])
    # a claim that copied the latents alone
    half = {"lat": dirty["lat"], "idx": jnp.zeros_like(dirty["idx"])}
    lost = prefill(params, cfg, tokens, 64, cache=half, start=32)
    assert not np.array_equal(lost[3], full[3][:, 32:])


SYSTEM = "you are a careful assistant who answers in short plain sentences"
MSGS = [{"role": "system", "content": SYSTEM},
        {"role": "user", "content": "tell me about indexers and latents"}]
MSGS2 = [{"role": "system", "content": SYSTEM},
         {"role": "user", "content": "and which positions does a query read"}]


@pytest.fixture(scope="module")
def engine(gguf_path):
    from llama_fastapi_k8s_gpu_tpu.engine import Engine

    return Engine(gguf_path, n_ctx=N_CTX * 2, prefill_chunk=SLICE,
                  decode_chunk=4, prefix_min=8)


def test_serial_engine_serves_reuses_a_prefix_and_counts(engine):
    out = engine.create_chat_completion(MSGS, max_tokens=12, temperature=0.0)
    assert out["usage"]["completion_tokens"] >= 1
    n_prompt = out["usage"]["prompt_tokens"]
    assert n_prompt > 4 * TOPK                 # the selection bites
    kind = engine.cache_kind
    assert kind == {
        "kind": "latent-ring", "latent": 32, "rotated_key": 8,
        "index_key": 16, "index_heads": 4, "index_topk": TOPK,
        "bytes_per_position": 2 * 3 * (40 + 16),
        "bytes_per_position_laid_out": 2 * 3 * (128 + 128),
        "read": "absorbed, blocks of 512, the selection a mask",
        "dense_layers": 1, "routed_layers": 2, "experts_held": [0, 12],
        "experts_routed": 12, "prefix_reuse": "on",
        "kv_paged": "refused at start"}
    g = engine.cache_read_gauges()
    pre, dec = ('{phase="prefill"}', '{phase="decode"}')
    assert g["index_keys_scored_total" + pre] \
        == 3 * n_prompt * (n_prompt + 1) // 2
    assert g["latents_selected_total" + pre] == 3 * (
        TOPK * (TOPK + 1) // 2 + TOPK * (n_prompt - TOPK))
    assert g["latents_read_total" + pre] >= g["index_keys_scored_total" + pre]
    assert 0 < g["latents_selected_total" + dec] \
        < g["index_keys_scored_total" + dec] <= g["latents_read_total" + dec]
    assert g["latents_selected_total" + dec] % (3 * TOPK) == 0
    # the same request again rides the prefix BOTH leaves still hold, and
    # gives the same greedy text as the full prefill did
    again = engine.create_chat_completion(MSGS, max_tokens=12,
                                          temperature=0.0)
    assert again["choices"][0]["message"] == out["choices"][0]["message"]
    other = engine.create_chat_completion(MSGS2, max_tokens=4,
                                          temperature=0.0)
    assert other["usage"]["completion_tokens"] >= 1


def test_lane_engine_admits_through_a_lane_claim_of_both_leaves(gguf_path,
                                                                engine):
    from llama_fastapi_k8s_gpu_tpu.engine.continuous import ContinuousEngine

    want = engine.create_chat_completion(MSGS, max_tokens=10, temperature=0.0)
    eng = ContinuousEngine(gguf_path, n_ctx=N_CTX * 2, prefill_chunk=SLICE,
                           decode_chunk=4, batch_size=3)
    try:
        assert eng._lane_prefix and eng.cache_kind["prefix_reuse"] == "on"
        assert eng.cache_kind["index_topk"] == TOPK
        first = eng.submit(MSGS, max_tokens=10, temperature=0.0).result(
            timeout=300)
        assert first["usage"] == want["usage"]
        assert first["choices"][0]["message"] == want["choices"][0]["message"]
        outs = [f.result(timeout=300) for f in [
            eng.submit(m, max_tokens=10, temperature=0.0)
            for m in (MSGS, MSGS2, MSGS, MSGS2, MSGS)]]
        # a claim hit gives the text the full prefill gave on these lanes
        for o in (outs[0], outs[2], outs[4]):
            assert o["choices"][0]["message"] == first["choices"][0]["message"]
        assert outs[1]["choices"][0]["message"] \
            == outs[3]["choices"][0]["message"]
        stats = eng.scheduler_stats()
        assert stats["lane_prefix_hits"] >= 3
        g = eng.cache_read_gauges()
        assert 0 < g['latents_selected_total{phase="decode"}'] \
            < g['index_keys_scored_total{phase="decode"}']
    finally:
        eng.shutdown()


@pytest.mark.anyio
async def test_health_metrics_and_spans_name_the_index_leaf(engine):
    import httpx

    from llama_fastapi_k8s_gpu_tpu.server.app import create_app
    from llama_fastapi_k8s_gpu_tpu.utils.config import Settings

    app = create_app(engine=engine, settings=Settings())
    transport = httpx.ASGITransport(app=app)
    async with transport:
        await app.router.startup()
        async with httpx.AsyncClient(transport=transport,
                                     base_url="http://test") as client:
            r = await client.post("/v1/chat/completions", json={
                "messages": MSGS2, "max_tokens": 6, "temperature": 0.0},
                headers={"x-lfkt-trace": "1"})
            assert r.status_code == 200
            eng = (await client.get("/health")).json()["engine"]
            assert eng["cache"]["index_key"] == 16
            assert eng["cache"]["index_heads"] == 4
            assert eng["cache"]["index_topk"] == TOPK
            assert set(eng["weight_formats"]) >= {
                "dense.idx_wq_b", "dense.idx_wk", "moe.idx_wq_b", "moe.idx_wk"}
            d = (await client.get("/debug/compiles")).json()
            assert not d.get("degrades")
            m = (await client.get("/metrics")).text
            for name in ("index_keys_scored_total", "latents_selected_total",
                         "latents_read_total"):
                assert name + '{phase="decode"}' in m, name
                assert name + '{phase="prefill"}' in m, name
        await app.router.shutdown()


# ---------------------------------------------------------------------------
# the share
# ---------------------------------------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer(ref, tmp_path, model, tokens):
    """The routed parts that the three shares (first, count) give, plus
    what every chip computes alike (attention WITH its indexer, the shared
    expert) counted once, add up to what the uncut reference gives for the
    whole layer; and in the reference alone the parts of the shares add up
    exactly."""
    import jax
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.models import mla
    from llama_fastapi_k8s_gpu_tpu.models.llama import init_cache

    hp, tensors = model
    S = 24
    x = np.asarray(ref.mla.tensor(tensors, "token_embd.weight"))[
        tokens[:S]] * 8
    xb = jnp.asarray(x, jnp.bfloat16)
    outs, picks, sel, parts = [], None, None, []
    for n, held in enumerate(((0, 4), (4, 4), (8, 4))):
        path = tmp_path / f"share{n}.gguf"
        _write(path, held=held)
        params, cfg = load(str(path))
        assert (cfg.experts_first, cfg.n_held, cfg.n_experts) == (*held, 12)

        def run(cfg):
            tap = (jnp.zeros((3, S, N_CTX)), jnp.zeros((3, S, N_CTX), bool))
            return jax.jit(lambda h, c: mla.moe_layer(
                h, params["layers"]["moe"], jnp.int32(0), c,
                jnp.arange(S, dtype=jnp.int32), jnp.int32(0), cfg, None,
                None, tap))(xb, init_cache(cfg))

        h, _, (_, pk, _), (_, tap_sel) = run(cfg)
        outs.append(np.asarray(h, np.float32))
        picks, sel = np.asarray(pk), np.asarray(tap_sel)[1, :, :S]
        if n == 0:    # a share that holds nothing this router can pick
            none = np.asarray(run(dataclasses.replace(
                cfg, experts_first=cfg.n_experts))[0], np.float32)
        hp_n, tensors_n = ref.open_model(str(path))
        parts.append((hp_n, ref.mla.layer_weights(tensors_n, 1)))
    got = sum(outs) - 2 * none
    w = ref.indexer_weights(tensors, ref.mla.layer_weights(tensors, 1), 1)
    with jax.default_matmul_precision("highest"):
        att, _, _ = ref.attention(hp, w, xb.astype(jnp.float32), use_sel=sel)
        want = np.asarray(ref.feed_forward(hp, w, att, 1,
                                           use_picks=picks)[0])
        # the reference's own shares: x once, the shared expert once
        x0, _, shared = ref.feed_forward(hp, w, att, 1, use_picks=picks,
                                         parts=True)[0]
        routed = sum(np.asarray(ref.feed_forward(
            hp_n, w_n, att, 1, use_picks=picks, parts=True)[0][1])
            for hp_n, w_n in parts)
    assert rel(np.asarray(x0) + routed + np.asarray(shared), want) < 1e-5
    print("read", rel(got, want))
    assert rel(got, want) < LIMIT
    assert rel(outs[0], want) > 5 * LIMIT


# ---------------------------------------------------------------------------
# the file, the refusals
# ---------------------------------------------------------------------------

def test_gguf_round_trip_of_the_indexers_keys_and_tensors(loaded):
    from llama_fastapi_k8s_gpu_tpu.models.cache import cache_of
    from llama_fastapi_k8s_gpu_tpu.models import mla
    from llama_fastapi_k8s_gpu_tpu.testing import TINY_DSA_CFG, TINY_MLA_CFG

    params, cfg = loaded
    assert (cfg.index_heads, cfg.index_dim, cfg.index_topk) == (4, 16, TOPK)
    assert cfg.cache_kind == "latent-ring" and not cfg.rope_neox
    kind = cache_of(cfg)
    assert kind is mla.INDEXED and kind.rolls_back and kind.counts_prefill
    assert kind.arch_of(cfg) == "deepseek32"
    assert cache_of(TINY_MLA_CFG) is mla.CACHE
    assert kind.nbytes(cfg) == 3 * N_CTX * (128 + 128) * 2
    assert mla.CACHE.nbytes(dataclasses.replace(
        TINY_MLA_CFG, n_ctx=N_CTX)) == 3 * N_CTX * 128 * 2
    assert set(kind.init(cfg)) == {"lat", "idx"}
    for stack, depth in (("dense", 1), ("moe", 2)):
        layer = params["layers"][stack]
        assert layer["idx_wq_b"]["w"].shape == (depth, 4 * 16, 64)
        assert layer["idx_k_norm"].shape == layer["idx_k_norm_b"].shape \
            == (depth, 16)
        assert layer["idx_proj"].shape == (depth, 4, 256)
    assert dataclasses.replace(TINY_DSA_CFG, vocab_size=cfg.vocab_size,
                               rms_eps=cfg.rms_eps,
                               attn_mscale=cfg.attn_mscale, n_ctx=N_CTX) == cfg


def test_a_file_without_its_indexer_is_refused_by_name(tmp_path, gguf_path):
    """A ``deepseek32`` file must state its indexer; and the parent's way of
    reading the family (``deepseek2``) is another architecture's name: a
    reader that knows no ``deepseek32`` refuses the file at the door."""
    from llama_fastapi_k8s_gpu_tpu.gguf import GGUFFile
    from llama_fastapi_k8s_gpu_tpu.gguf import constants
    from llama_fastapi_k8s_gpu_tpu.models.config import ModelConfig

    gf = GGUFFile(gguf_path)
    assert gf.metadata["general.architecture"] == "deepseek32"
    real = gf.hparam
    gf.hparam = lambda key, default=None: default \
        if key == "attention.indexer.top_k" else real(key, default)
    with pytest.raises(ValueError, match="deepseek32.*indexer"):
        ModelConfig.from_gguf(gf, n_ctx=N_CTX)
    served = tuple(a for a in constants.SERVED_ARCHITECTURES
                   if a != "deepseek32")
    import llama_fastapi_k8s_gpu_tpu.gguf.reader as reader

    was = reader.SERVED_ARCHITECTURES
    reader.SERVED_ARCHITECTURES = served
    try:
        with pytest.raises(Exception, match="deepseek32"):
            GGUFFile(gguf_path).require_served()
    finally:
        reader.SERVED_ARCHITECTURES = was


@pytest.mark.parametrize("kw, words", [
    (dict(kv_dtype="int8"), "LFKT_KV_DTYPE=int8.*deepseek32.*bf16 only"),
    (dict(kv_paged=True), "LFKT_KV_PAGED=1.*deepseek32.*one latent row"),
])
def test_what_cannot_hold_the_cache_is_refused_by_name(gguf_path, kw, words):
    from llama_fastapi_k8s_gpu_tpu.engine import Engine

    with pytest.raises(ValueError, match=words):
        Engine(gguf_path, n_ctx=N_CTX, **kw)


def test_the_counters_count_what_was_scored_selected_and_fetched():
    """``CacheKind.note_decode`` / ``note_prefill`` of the indexed ring, at
    the published selection: a lane at 8600 scores 8601 keys a layer and
    step, selects 2048 and, the selection being a mask, fetches the blocks
    up to its position; one at 100 selects all it has."""
    from llama_fastapi_k8s_gpu_tpu.models import mla
    from llama_fastapi_k8s_gpu_tpu.testing import TINY_DSA_CFG

    cfg = dataclasses.replace(TINY_DSA_CFG, n_ctx=16384, index_topk=2048,
                              latent_kernel=True)
    counts = mla.INDEXED.new_counts()
    mla.INDEXED.note_decode(counts, cfg, [8600, 100], 2, live=[8600, 100])
    assert counts["scored_decode"] == 3 * (8601 + 8602 + 101 + 102)
    assert counts["selected_decode"] == 3 * (2048 * 2 + 101 + 102)
    assert counts["read_decode"] == 3 * (2 * 9 * 1024 + 2 * 1024)
    assert counts["read"] == counts["read_decode"] // 3
    attrs = mla.INDEXED.note_prefill(counts, cfg, 8300, [(8192, 256)])
    assert attrs["select"] == "mask" and attrs["latent_read"] == "loop"
    assert attrs["index_keys_scored"] == sum(range(8193, 8301))
    assert attrs["latents_selected"] == 108 * 2048
    assert counts["selected_prefill"] == 3 * 108 * 2048
    assert counts["read_prefill"] == 3 * 108 * 8704
    g = mla.INDEXED.gauges(counts)
    assert g['latents_selected_total{phase="decode"}'] \
        == counts["selected_decode"]
    assert mla.INDEXED.decode_span_attrs(8600)["select"] == "mask"
    assert mla.INDEXED.span_attrs(cfg) == {"index_topk": 2048}
    from llama_fastapi_k8s_gpu_tpu.obs.catalog import METRICS

    for name in mla.INDEXED.own_gauges:
        assert name.partition("{")[0] in METRICS, name
