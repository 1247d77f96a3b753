"""The ``longcat-flash`` block (models/mla.py ``shortcut_layer``) at a tiny
size on the CPU, against the plain float32 reference
(benchmarks/reference_longcat.py): two latent attentions and two dense
feed-forwards a layer on a latent ring of ``2 L`` leaves, one expert branch
a layer that reads sub-block 0's normed rows and joins after sub-block 1, a
softmax router over ``E + Z`` outputs of which the last ``Z`` are identity
experts, un-normalised weights times a scale, both ``mla_scale_*`` factors,
and an expert layer that is told which experts it holds.

The tiny file (``testing.TINY_LONGCAT_CFG``) keeps every ratio of the
published block: 2 double layers, 8 experts + 4 identity outputs, top-3,
scale 3, d_nope 16 / d_rope 8 / d_v 24, q scale 2, latent scale 8^1/2.

LIMIT: the program (bf16 inputs to every product, float32 sums, a bf16
stream and cache) against the float32 reference on the program's OWN picks
reads 1-2 % of the logits' norm over blocks of 16 positions; every control
below (another function: a missing ``mla_scale_*``, a normalised weight, a
dropped identity term, the join moved) reads 8 % or more.  PICKS: rows whose
set of picks differs from the reference's own: near-ties that bf16 rounding
orders the other way.
"""

from __future__ import annotations

import dataclasses
import os
import sys

import numpy as np
import pytest

from tests.test_mla import (
    N_CTX, N_PROMPT, N_SEQ, SLICE, lane_alone, lanes_run, load, prefill,
    programs, reference_rows, rel, rows_that_differ, with_kernel, worst)
from tests.test_olmoe import _as_it_was_built

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")

LIMIT = 4e-2
PICKS = 12           # rows of 2 layers x N_SEQ whose picks may differ
SHARE = (2, 4)       # the held share of the ``share`` cases: experts 2..5 of 8


@pytest.fixture(scope="module")
def ref():
    sys.path.insert(0, BENCH)
    try:
        import reference_longcat
        yield reference_longcat
    finally:
        sys.path.remove(BENCH)


@pytest.fixture(scope="module")
def paths(tmp_path_factory):
    """{"whole": every expert held, "share": experts 2..5 of the SAME
    weights}."""
    from llama_fastapi_k8s_gpu_tpu.testing import write_tiny_longcat_gguf

    d = tmp_path_factory.mktemp("longcat")
    out = {"whole": str(d / "tiny.gguf"), "share": str(d / "share.gguf")}
    write_tiny_longcat_gguf(out["whole"], seed=3)
    write_tiny_longcat_gguf(out["share"], seed=3, held=SHARE)
    return out


@pytest.fixture(scope="module")
def gguf_path(paths):
    return paths["whole"]


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(5).integers(4, 260, size=N_SEQ)


@pytest.fixture(scope="module")
def models(ref, paths):
    return {k: ref.open_model(p) for k, p in paths.items()}


@pytest.fixture(scope="module")
def loadeds(paths):
    return {k: load(p) for k, p in paths.items()}


@pytest.fixture(scope="module")
def loaded(loadeds):
    return loadeds["whole"]


@pytest.fixture(scope="module")
def model(models):
    return models["whole"]


def serve(loaded, tokens):
    """The serial programs over the whole sequence, slices then steps
    through the cache: (logits (S, V), picks (L, S, k), the cache)."""
    import jax.numpy as jnp

    params, cfg = loaded
    pass_, step, _ = programs(cfg)
    logits, picks, cache = prefill(params, cfg, tokens, N_PROMPT, pass_=pass_)
    dec, dpicks = [], []
    for t in range(N_PROMPT, N_SEQ):
        lg, cache, pk = step(params, jnp.int32(tokens[t]), jnp.int32(t),
                             cache)
        dec.append(np.asarray(lg))
        dpicks.append(np.asarray(pk))
    return (np.concatenate([logits, np.stack(dec)]),
            np.concatenate([picks] + dpicks, axis=1), cache)


@pytest.fixture(scope="module")
def served(loaded, tokens):
    return serve(loaded, tokens)


# ---------------------------------------------------------------------------
# the program against the reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("held", ["whole", "share"])
def test_slices_then_decode_through_the_latent_cache(ref, models, loadeds,
                                                     tokens, held):
    logits, picks, _ = serve(loadeds[held], tokens)
    own = np.stack([p for _, p in ref.forward(*models[held], tokens)[1]])
    assert rows_that_differ(picks, own) <= PICKS
    want = np.asarray(ref.forward(*models[held], tokens, use_picks=picks)[0])
    print("read", worst(logits[:N_PROMPT], want[:N_PROMPT]),
          worst(logits[N_PROMPT:], want[N_PROMPT:]))
    assert worst(logits[:N_PROMPT], want[:N_PROMPT]) < LIMIT
    assert worst(logits[N_PROMPT:], want[N_PROMPT:]) < LIMIT
    if held == "share":     # and the share is not the whole
        whole = np.asarray(ref.forward(*models["whole"], tokens,
                                       use_picks=picks)[0])
        assert worst(logits, whole) > LIMIT


@pytest.mark.parametrize("control", [
    "no_bias", "no_q_scale", "no_kv_scale", "norm_weights", "no_identity",
    "join_early"])
def test_another_function_fails_the_limit(ref, model, tokens, served,
                                          control):
    """Each control is a different function: its distance from the program
    is past the limit (``join_early``: the expert branch added where a plain
    mixture of experts would add it, before sub-block 1), or (the bias,
    which moves the CHOICE alone) its own picks differ from the program's in
    many rows."""
    logits, picks, _ = served
    if control == "no_bias":
        theirs = np.stack([p for _, p in ref.forward(
            *model, tokens, no_bias=True)[1]])
        assert rows_that_differ(picks, theirs) > 3 * PICKS
        return
    got = ref.forward(*model, tokens, use_picks=picks, **{control: True})[0]
    print("read", control, worst(logits, np.asarray(got)))
    assert worst(logits, np.asarray(got)) > 2 * LIMIT


@pytest.mark.parametrize("held", ["whole", "share"])
@pytest.mark.parametrize("read", ["loop", "kernel"])
def test_three_lanes_one_dead_then_taken(ref, models, loadeds, tokens, read,
                                         held, monkeypatch):
    """The lane engine's step (``vmap`` over lanes at unlike positions, one
    dead then taken) against the reference; the counters are the step's:
    every live row's picks over all the router's outputs, of which the zero
    ones and the held ones are counted apart, and a dead lane's none."""
    params, cfg = loadeds[held]
    if read == "kernel":
        cfg = with_kernel(cfg, monkeypatch)
    got, seqs, stats = lanes_run(loadeds[held], tokens, cfg)
    for lane, (first, logits, picks) in got.items():
        n = first + len(logits)
        use = np.concatenate(
            [prefill(params, cfg, seqs[lane], first)[1], picks], axis=1)
        assert use.shape[1] == n
        want = reference_rows(ref, models[held], seqs[lane], use)
        assert worst(logits, want[first:]) < LIMIT, lane
    n_held = cfg.n_held
    for st, n_live in stats:
        assert np.array_equal(st[0], st[1]) and np.array_equal(st[0], st[2])
        assert len(st[0]) == 3 + n_held + 1
        layer_steps, took = st[0][0], st[0][2:2 + n_held].sum()
        routed, zero = st[0][2 + n_held], st[0][3 + n_held]
        assert layer_steps == cfg.n_layers
        assert routed == n_live * cfg.n_layers * cfg.n_experts_used
        assert zero + took <= routed and zero > 0
        if held == "whole":
            assert zero + took == routed


def test_a_lanes_logits_do_not_depend_on_the_other_lanes(loaded, tokens):
    lane_alone(loaded, tokens)


# ---------------------------------------------------------------------------
# the share
# ---------------------------------------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer(ref, tmp_path, model, tokens):
    """One test ties the share to the model: the routed parts that the four
    shares (first, count) give, plus what every chip computes alike (both
    attentions, both dense feed-forwards, the identity picks of its own
    tokens) counted once, add up to what the UNCUT reference gives for the
    whole layer."""
    import jax
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.models import mla
    from llama_fastapi_k8s_gpu_tpu.models.llama import init_cache
    from llama_fastapi_k8s_gpu_tpu.testing import write_tiny_longcat_gguf

    hp, tensors = model
    S = 24
    x = np.asarray(ref.tensor(tensors, "token_embd.weight"))[tokens[:S]] * 8
    outs, picks = [], None
    shares = ((0, 2), (2, 2), (4, 2), (6, 2))
    for n, held in enumerate(shares):
        path = str(tmp_path / f"share{n}.gguf")
        write_tiny_longcat_gguf(path, seed=3, held=held)
        params, cfg = load(path)
        assert (cfg.experts_first, cfg.n_held, cfg.n_experts,
                cfg.n_zero_experts) == (*held, 8, 4)
        assert params["layers"]["moe"]["w_gate_exps"]["w"].shape[:2] == (2, 2)

        def run(cfg):
            return jax.jit(lambda h, c: mla.shortcut_layer(
                h, params["layers"], jnp.int32(1), c,
                jnp.arange(S, dtype=jnp.int32), jnp.int32(0), cfg, None,
                None))(jnp.asarray(x, jnp.bfloat16), init_cache(cfg))

        h, _, (count, pk, total, zero) = run(cfg)
        assert int(total) == S * 3 and 0 < int(zero) < S * 3
        assert int(count.sum()) + int(zero) <= int(total)
        outs.append(np.asarray(h, np.float32))
        picks = np.asarray(pk)
        if n == 0:    # a share that holds nothing this router can pick
            none = np.asarray(run(dataclasses.replace(
                cfg, experts_first=cfg.n_experts))[0], np.float32)
    got = sum(outs) - (len(shares) - 1) * none
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.layer(
            hp, ref.layer_weights(tensors, 1),
            jnp.asarray(x, jnp.bfloat16).astype(jnp.float32),
            use_picks=picks)[0])
    print("read", rel(got, want), rel(outs[0], want), rel(none, want))
    assert rel(got, want) < LIMIT
    # one share alone, and what every chip computes alike, are far from it
    assert rel(outs[0], want) > 3 * LIMIT and rel(none, want) > 3 * LIMIT


def test_a_share_counts_what_left_and_what_is_free(loadeds, tokens):
    """A file that holds experts 2..5 of 8: the counter vector tells the
    picks of a held expert from the identity picks and from all."""
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.models.llama import forward, init_cache

    params, cfg = loadeds["share"]
    _, picks, _ = prefill(params, cfg, tokens, 16)
    _, _, stats = forward(params, cfg, jnp.asarray(tokens[:16], jnp.int32),
                          jnp.int32(0), init_cache(cfg), with_stats=True)
    stats = np.asarray(stats)
    assert len(stats) == 3 + 4 + 1 and stats[0] == 2
    assert stats[-2] == 2 * 16 * 3
    held = int(np.sum((picks >= 2) & (picks < 6)))
    zero = int(np.sum(picks >= 8))
    assert stats[2:-2].sum() == held and stats[-1] == zero
    assert 0 < held and 0 < zero and held + zero < stats[-2]


def test_a_step_of_many_lanes_compacts_its_rows_to_the_held_picks(paths,
                                                                  monkeypatch):
    """The lane engines' step (``vmap`` over lanes of ``expert_branch``) on
    a share of the experts, through the grouped kernels: 24 lanes x 3 picks
    are 72 rows of ONE call, compacted to the picks of experts 2..5 (an
    identity pick, a pick of an expert held elsewhere and a dead lane's
    rows reach none) in calls of 64 rows.  Bit for bit the branch with
    every call built as it was before; the counters are the live lanes'."""
    import jax
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.models.routed import MOE, expert_branch
    from llama_fastapi_k8s_gpu_tpu.ops.pallas import experts as X

    params, cfg = load(paths["share"], fmt="q4k")
    layers = params["layers"][MOE]
    assert X.family_of(layers["w_gate_exps"]) == "q4k"
    lanes, k = 24, cfg.n_experts_used
    assert X.compacted_rows(lanes, k) == 72
    hn = jnp.asarray(np.random.default_rng(9).standard_normal(
        (lanes, 1, cfg.dim)), jnp.bfloat16)
    live = jnp.asarray(np.arange(lanes) % 7 != 3)      # dead between live

    def step():
        return jax.vmap(lambda h, on: expert_branch(
            h, layers, jnp.int32(1), cfg, on))(hn, live)

    out, (count, picks, total, zero) = step()
    want, (want_count, *_) = _as_it_was_built(monkeypatch, step)
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(want, np.float32))
    np.testing.assert_array_equal(count, want_count)
    picks, on = np.asarray(picks)[:, 0], np.asarray(live)
    held = (picks >= SHARE[0]) & (picks < SHARE[0] + SHARE[1]) & on[:, None]
    assert 0 < held.sum() < on.sum() * k
    np.testing.assert_array_equal(
        count[0], np.bincount(picks[held] - SHARE[0], minlength=SHARE[1]))
    assert int(total[0]) == on.sum() * k
    assert int(zero[0]) == (picks[on] >= cfg.n_experts).sum() > 0


def test_expert_counters_fold_the_zero_picks_where_the_router_has_any():
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.engine.expert_counters import (
        ExpertCounters)

    c = ExpertCounters(3, n_slots=3, zero=True)
    for _ in range(70):                 # past the pending bound: still exact
        c.push(jnp.asarray([2, 3, 1, 0, 4, 24, 8], jnp.int32))
    assert c.snapshot(block=True) == {
        "layer_steps": 140, "experts_read": 210, "picks": [70, 0, 280],
        "picks_held": 350, "picks_total": 1680, "picks_zero": 560,
        "slots_skipped": 140 * 3 - 210, "rows_skipped": 0}


# ---------------------------------------------------------------------------
# the router and the expert branch
# ---------------------------------------------------------------------------

def _route(loaded, ref, model, tokens, bias=None):
    """(the program's picks and weights, the reference's scores and picks)
    on layer 0's router at the reference's own normed rows."""
    import jax
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.models.routed import route_grouped

    params, cfg = loaded
    hp, tensors = model
    w = ref.layer_weights(tensors, 0)
    if bias is not None:
        w["exp_probs_b"] = bias
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(ref.tensor(tensors, "token_embd.weight"))[tokens] * 8
        u = ref.norm(ref.attention(hp, w[0], x), w[0]["ffn_norm"], hp["eps"])
        u = u.astype(jnp.bfloat16).astype(jnp.float32)
        scores, picks = ref.router(hp, w, u)
        mine, weights = route_grouped(
            u, jnp.asarray(w["ffn_gate_inp"]),
            jnp.asarray(w["exp_probs_b"]), cfg)
    return (np.asarray(mine), np.asarray(weights), np.asarray(scores),
            np.asarray(picks))


def test_the_router_is_a_softmax_over_experts_and_zero_outputs(
        loaded, ref, model, tokens):
    mine, weights, scores, picks = _route(loaded, ref, model, tokens)
    cfg = loaded[1]
    assert scores.shape == (N_SEQ, cfg.n_experts + cfg.n_zero_experts)
    np.testing.assert_allclose(scores.sum(-1), 1.0, rtol=1e-5)
    assert rows_that_differ(mine, picks) <= 2
    assert mine.max() >= cfg.n_experts       # some pick is an identity one
    # the weights: the picked scores, NOT normalised, times the scale
    np.testing.assert_allclose(
        weights, cfg.expert_weights_scale
        * np.take_along_axis(scores, mine, -1), rtol=2e-5)
    assert np.all(weights.sum(-1) < cfg.expert_weights_scale)


def test_a_bias_moves_the_choice_and_not_the_weights(loaded, ref, model,
                                                     tokens):
    cfg = loaded[1]
    flat = np.zeros(cfg.n_experts + cfg.n_zero_experts, np.float32)
    steep = np.linspace(0.3, -0.3, len(flat)).astype(np.float32)
    mine0, _, scores, _ = _route(loaded, ref, model, tokens, bias=flat)
    mine1, weights1, scores1, picks1 = _route(loaded, ref, model, tokens,
                                              bias=steep)
    np.testing.assert_array_equal(scores, scores1)
    assert rows_that_differ(mine0, mine1) > N_SEQ // 2
    assert rows_that_differ(mine1, picks1) <= 2
    np.testing.assert_allclose(
        weights1, cfg.expert_weights_scale
        * np.take_along_axis(scores, mine1, -1), rtol=2e-5)


def test_picks_that_are_all_identity_give_the_scaled_rows_exactly(
        loaded, ref, model, tokens):
    """A bias that puts the four identity outputs first: every pick is one,
    and the branch is ``scale x (sum of the picked scores) x u``: exactly so
    in the reference, to the bf16 of the stream in the program; no expert
    takes a row."""
    import jax
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.models.routed import expert_branch

    params, cfg = loaded
    hp, tensors = model
    E = cfg.n_experts
    bias = np.where(np.arange(E + cfg.n_zero_experts) >= E, 1.0, 0.0
                    ).astype(np.float32)
    mine, weights, scores, picks = _route(loaded, ref, model, tokens, bias)
    assert np.all(mine >= E) and np.all(picks >= E)
    u = jnp.asarray(np.random.default_rng(1).standard_normal(
        (N_SEQ, cfg.dim)), jnp.bfloat16)
    moe = dict(params["layers"]["moe"])
    moe["router_bias"] = jnp.broadcast_to(jnp.asarray(bias),
                                          moe["router_bias"].shape)
    out, (count, pk, total, zero) = jax.jit(
        lambda u: expert_branch(u, moe, jnp.int32(0), cfg, None))(u)
    assert int(count.sum()) == 0 and int(zero) == int(total) == N_SEQ * 3
    from llama_fastapi_k8s_gpu_tpu.models.routed import route_grouped

    _, w = route_grouped(u, moe["w_router"][0], moe["router_bias"][0], cfg)
    want = (jnp.sum(w, -1)[:, None] * u.astype(jnp.float32)
            ).astype(jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(want, np.float32))
    # the reference: M(u) = scale x sum p x u, and nothing else
    uf = u.astype(jnp.float32)
    w0 = ref.layer_weights(tensors, 0)
    w0["exp_probs_b"] = bias
    s, p = ref.router(hp, w0, uf)
    wts = ref.pick_weights(hp, s, p)
    got = ref.expert_branch(hp, w0, uf, p, wts)
    np.testing.assert_array_equal(
        np.asarray(got),
        np.asarray(jnp.sum(wts, -1)[:, None] * uf))
    np.testing.assert_allclose(
        np.asarray(jnp.sum(wts, -1)),
        hp["scale"] * np.sort(np.asarray(s)[:, E:], -1)[:, -3:].sum(-1),
        rtol=1e-5, atol=1e-5)   # (a near-tie beside the bias's 1.0)


def test_an_absent_bias_loads_as_zeros_and_serves(tmp_path, ref, tokens):
    from llama_fastapi_k8s_gpu_tpu.gguf import GGUFFile
    from llama_fastapi_k8s_gpu_tpu.testing import write_tiny_longcat_gguf

    path = str(tmp_path / "nobias.gguf")
    write_tiny_longcat_gguf(path, seed=3, bias_scale=None)
    assert "blk.0.exp_probs_b.bias" not in GGUFFile(path).tensors
    params, cfg = load(path)
    bias = np.asarray(params["layers"]["moe"]["router_bias"])
    assert bias.shape == (2, 12) and not bias.any()
    logits, picks, _ = prefill(params, cfg, tokens, 32)
    model = ref.open_model(path)
    want, routes = ref.forward(*model, tokens[:32], use_picks=picks)
    assert worst(logits, np.asarray(want)) < LIMIT
    assert rows_that_differ(picks, np.stack([p for _, p in routes])) <= PICKS


# ---------------------------------------------------------------------------
# the file, the loader, the cache, the refusals
# ---------------------------------------------------------------------------

def test_gguf_round_trip_of_the_keys_and_the_three_stacks(tmp_path, loaded,
                                                          paths):
    from llama_fastapi_k8s_gpu_tpu.gguf import GGUFFile
    from llama_fastapi_k8s_gpu_tpu.models.config import (LATENT_RING,
                                                         ModelConfig)
    from llama_fastapi_k8s_gpu_tpu.testing import TINY_LONGCAT_CFG

    params, cfg = loaded
    assert cfg.cache_kind == LATENT_RING and not cfg.rope_neox
    for f in dataclasses.fields(TINY_LONGCAT_CFG):
        if f.name in ("vocab_size", "rms_eps", "kv_latent_scale"):
            continue
        assert getattr(cfg, f.name) == getattr(TINY_LONGCAT_CFG, f.name), \
            f.name
    assert abs(cfg.kv_latent_scale - 8 ** 0.5) < 1e-6
    assert cfg.n_attn_sublayers == 4 and cfg.n_held == 8
    layers = params["layers"]
    assert set(layers) == {"attn", "ffn", "moe"}
    assert layers["attn"]["w_uk"]["w"].shape == (4, 4, 16, 32)
    assert layers["attn"]["w_uv"]["w"].shape == (4, 4, 24, 32)
    assert layers["attn"]["attn_norm"].shape == (4, 256)
    assert layers["ffn"]["ffn_norm"].shape == (4, 256)
    assert layers["ffn"]["w_down"]["w"].shape == (4, 256, 512)
    assert layers["moe"]["w_router"].shape == (2, 12, 256)
    assert layers["moe"]["router_bias"].shape == (2, 12)
    assert layers["moe"]["w_gate_exps"]["w"].shape == (2, 8, 256, 256)
    gf = GGUFFile(paths["share"])
    assert gf.hparam("expert_held_first") == 2
    assert gf.hparam("expert_held_count") == 4
    assert gf.hparam("expert_count") == 8
    assert gf.hparam("expert_zero_count") == 4
    assert tuple(gf["blk.1.ffn_gate_exps.weight"].shape) == (256, 256, 4)
    assert tuple(gf["blk.1.ffn_gate_inp.weight"].shape) == (256, 12)
    held = ModelConfig.from_gguf(gf, n_ctx=N_CTX)
    assert (held.experts_first, held.n_held, held.n_experts) == (2, 4, 8)


def test_the_cache_is_a_leaf_an_attention_sublayer(loaded):
    from llama_fastapi_k8s_gpu_tpu.models.llama import (cache_nbytes,
                                                        init_cache)

    _, cfg = loaded
    cache = init_cache(cfg)
    assert {k: v.shape for k, v in cache.items()} \
        == {"lat": (4, 1, N_CTX, 128)}   # 2 layers x 2; 32 + 8 filled up
    assert cache_nbytes(cfg) == sum(v.nbytes for v in cache.values())


def test_a_claimed_prefix_holds_both_sublayers_rows(loaded, tokens):
    """What a lane claim and serial prefix reuse rest on: suffix slices on
    a COPY of a cache that holds the prefix give the full prefill's logits,
    and every one of the 2 L leaves holds the prefix's rows (a copy that
    left a sub-layer's out would read zeros there)."""
    import jax

    params, cfg = loaded
    full, _, whole = prefill(params, cfg, tokens, 64)
    _, _, cache = prefill(params, cfg, tokens, 32)
    lat = np.asarray(cache["lat"], np.float32)
    assert lat.shape[0] == 4 and np.all(np.abs(lat[:, 0, :32]).sum(-1) > 0)
    np.testing.assert_array_equal(
        lat[:, 0, :32], np.asarray(whole["lat"], np.float32)[:, 0, :32])
    _, _, dirty = prefill(params, cfg, tokens[::-1], 64, cache=cache, start=32)
    claimed = jax.tree.map(lambda a: a.copy(), dirty)
    got, _, _ = prefill(params, cfg, tokens, 64, cache=claimed, start=32)
    assert worst(got, full[32:]) < 1e-6


def _file_with(tmp_path, **meta):
    """The tiny file with ``longcat-flash.<key>`` values replaced."""
    from llama_fastapi_k8s_gpu_tpu import testing
    from llama_fastapi_k8s_gpu_tpu.gguf import GGUFWriter

    path = str(tmp_path / "odd.gguf")

    class Odd(GGUFWriter):
        def add_metadata(self, key, value):
            short = key.removeprefix("longcat-flash.")
            super().add_metadata(key, meta.get(short, value))

    orig = testing.GGUFWriter
    testing.GGUFWriter = Odd
    try:
        testing.write_tiny_longcat_gguf(path)
    finally:
        testing.GGUFWriter = orig
    return path


@pytest.mark.parametrize("meta, words", [
    ({"expert_zero_type": "constant"},
     "longcat-flash: expert_zero_type 'constant' is not served"),
    ({"attention.q_lora_rank": 0}, "longcat-flash: attention.q_lora_rank is 0"),
    ({"expert_gating_func": 3}, "longcat-flash: expert_gating_func 3"),
    ({"rope.dimension_count": 7},
     "longcat-flash: attention.key_length 24 must exceed the even"),
])
def test_a_file_the_block_cannot_compute_is_refused_by_name(tmp_path, meta,
                                                            words):
    from llama_fastapi_k8s_gpu_tpu.gguf import GGUFFile
    from llama_fastapi_k8s_gpu_tpu.models.config import ModelConfig

    with pytest.raises(ValueError, match=words):
        ModelConfig.from_gguf(GGUFFile(_file_with(tmp_path, **meta)),
                              n_ctx=N_CTX)


@pytest.mark.parametrize("kw, words", [
    (dict(kv_dtype="int8"), "LFKT_KV_DTYPE=int8 cannot serve architecture "
                            "'longcat-flash'"),
    (dict(kv_paged=True), "LFKT_KV_PAGED=1 cannot serve architecture "
                          "'longcat-flash'"),
])
def test_what_cannot_hold_the_cache_is_refused_by_name(gguf_path, kw, words):
    from llama_fastapi_k8s_gpu_tpu.engine import Engine

    with pytest.raises(ValueError, match=words):
        Engine(gguf_path, n_ctx=N_CTX, **kw)


# ---------------------------------------------------------------------------
# the benchmark's files for the block (tier-1 collects tests/ only)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def bench(ref):
    """The benchmark's modules, by bare name as its files import them."""
    import importlib
    return {name: importlib.import_module(name)
            for name in ("ggufgen", "costs", "counters")}


def _config(*parts):
    import json
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def test_the_blocks_plan_is_what_the_loader_reads(bench, tmp_path, ref):
    """The rehearsal's file through ``ggufgen``: every tensor of the plan is
    read by the loader, the loader asks for none the plan lacks (an absent
    bias is looked for and not found), and the program serves it against
    the reference."""
    from llama_fastapi_k8s_gpu_tpu.gguf import GGUFFile
    from llama_fastapi_k8s_gpu_tpu.models.config import ModelConfig
    from llama_fastapi_k8s_gpu_tpu.models.params import load_params

    cfg_doc = _config("rehearsal", "tiny-scmoe-2lane.json")
    path = str(tmp_path / "planned.gguf")
    bench["ggufgen"].write_gguf(cfg_doc, path)
    gf = GGUFFile(path)
    plan = [name for name, _, _ in
            bench["ggufgen"].block_of(cfg_doc).tensor_plan(cfg_doc)]
    assert sorted(plan) == sorted(gf.tensors)
    assert not any("exp_probs_b" in name for name in plan)
    read = set()

    class Noting(dict):
        def __getitem__(self, name):
            read.add(name)
            return dict.__getitem__(self, name)

    gf.tensors = Noting(gf.tensors)
    cfg = ModelConfig.from_gguf(gf, n_ctx=64)
    assert (cfg.n_experts, cfg.n_zero_experts, cfg.experts_first,
            cfg.n_held, cfg.n_experts_used, cfg.attn_sublayers) \
        == (8, 4, 4, 4, 3, 2)
    assert cfg.q_latent_scale == 1.0 and cfg.kv_latent_scale == 1.0
    params = load_params(gf, cfg, fmt="q4k")
    assert read == set(plan)
    assert not np.asarray(params["layers"]["moe"]["router_bias"]).any()
    seq = np.random.default_rng(2).integers(4, 2000, size=24)
    got, picks, _ = prefill(params, cfg, seq, 24, size=8)
    want = np.asarray(ref.forward(*ref.open_model(path), seq,
                                  use_picks=picks)[0])
    print("read", rel(got, want))
    assert rel(got, want) < 0.06


def test_the_published_file_is_the_catalog_rows_with_two_cuts(bench):
    cfg = _config("configs",
                  "longcat-flash-omni-560b-a27b-q4km-ep8-16lane.json")
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        import json
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "LongCat-Flash-Omni")
    differ = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert differ == set(cfg["reduced"]) == {"num_layers", "n_routed_experts"}
    assert cfg["source"] == row["source_url"]
    assert (cfg["num_layers"], cfg["n_routed_experts"],
            cfg["router_experts"]) == (4, 64, 512)
    block = bench["ggufgen"].block_of(cfg)
    plan = block.tensor_plan(cfg)
    by_name = {name: (shape, kind) for name, shape, kind in plan}
    assert by_name["blk.3.ffn_gate_inp.weight"] == ((768, 6144), "F32")
    assert by_name["blk.0.ffn_down_exps.weight"] == ((64, 6144, 2048), "Q6_K")
    assert by_name["blk.2.1.attn_kv_b.weight"] == ((64 * 256, 512), "Q4_K")
    assert by_name["blk.3.1.ffn_down.weight"] == ((6144, 12288), "Q6_K")
    total = sum(bench["ggufgen"].tensor_nbytes(kind, int(np.prod(shape)))
                for _, shape, kind in plan)
    assert 10.0e9 < total < 10.4e9          # the file: 10.2 GB of weights
    rest_b, rest_w, exp_b, exp_w = block.split(cfg)
    assert exp_w == 3 * 6144 * 2048 and 24.0e6 < exp_b < 25.0e6
    meta = dict((k, v) for k, _, v in block.metadata(cfg, "longcat-flash"))
    assert meta["longcat-flash.expert_count"] == 512
    assert meta["longcat-flash.expert_zero_count"] == 256
    assert meta["longcat-flash.expert_held_count"] == 64
    assert meta["longcat-flash.expert_used_count"] == 12


def _samples(steps, read, routed, held, zero):
    def text(i):
        return (f"expert_layer_steps_total {steps[i]}\n"
                f"experts_read_total {read[i]}\n"
                f"expert_picks_routed_total {routed[i]}\n"
                f"expert_picks_held_total {held[i]}\n"
                f"expert_picks_zero_total {zero[i]}\n")
    return [(0.0, text(0)), (1.0, text(1))]


def _reader(name):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "lm_" + name, os.path.join(BENCH, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_a_steps_costs_count_the_experts_read_and_an_identity_pick_at_no_byte(
        bench):
    """Two attentions and two dense feed-forwards a layer, the held experts
    the program counted, 8 leaves of cache; an identity pick reads no byte
    and costs 2 x 6144 FLOPs."""
    cfg = _config("configs",
                  "longcat-flash-omni-560b-a27b-q4km-ep8-16lane.json")
    block = bench["ggufgen"].block_of(cfg)
    rest_b, rest_w, exp_b, exp_w = block.split(cfg)
    # 100 layer-steps at 14 experts read; 19200 picks, 1600 held, 6400 zero
    run = {"samples": _samples((0, 100), (0, 1400), (0, 19200), (0, 1600),
                               (0, 6400))}
    assert block.expert_bytes_per_step(cfg, 16, run) == 4 * 14 * exp_b
    assert block.expert_bytes_per_step(cfg, 16) == 4 * 64 * exp_b
    assert block.decode_step_bytes(cfg, 16, 100, run=run) \
        == rest_b + 4 * 14 * exp_b + 16 * 100 * 8 * 576 * 2 + 16 * 6144 * 2
    held, zero = block.picks_per_token(cfg, run)
    assert (held, zero) == (1.0, 4.0)
    flops = block.decode_step_flops(cfg, 16, 0, run=run)
    assert flops == 16 * (2 * rest_w + 4 * (2 * 1.0 * exp_w
                                            + 4.0 * 2 * 6144))
    more = {"samples": _samples((0, 100), (0, 1400), (0, 19200), (0, 1600),
                                (0, 12800))}
    assert block.decode_step_flops(cfg, 16, 0, run=more) - flops \
        == 16 * 4 * 4.0 * 2 * 6144
    assert block.decode_step_bytes(cfg, 16, 100, run=more) \
        == block.decode_step_bytes(cfg, 16, 100, run=run)
    # 2.3 GB outside the experts, the head's 0.66 among it
    assert 2.2e9 < rest_b < 2.45e9
    assert block.latent_bytes_per_step(cfg, 1, 1) == 8 * 576 * 2


def test_the_pick_share_readers_read_the_window_and_nothing_on_a_parent(bench):
    # (``bench``: the readers import ``counters`` by its bare name; alone on
    # a worker the test found no such module)
    run = {"config": {}, "samples": _samples(
        (10, 110), (30, 1430), (1000, 20200), (100, 1700), (300, 6700))}
    assert _reader("zero_pick_share")(run) == 100.0 * 6400 / 19200
    assert _reader("real_pick_held_share")(run) == 100.0 * 1600 / 12800
    # the parent exports no zero counter: nothing, and no exception
    parent = {"config": {}, "samples": [
        (0.0, "expert_picks_routed_total 1\nexpert_picks_held_total 1\n"),
        (1.0, "expert_picks_routed_total 9\nexpert_picks_held_total 5\n")]}
    assert _reader("zero_pick_share")(parent) is None
    assert _reader("real_pick_held_share")(parent) is None
    # every pick an identity one: no real pick to take a share of
    free = {"config": {}, "samples": _samples(
        (0, 1), (0, 0), (0, 12), (0, 0), (0, 12))}
    assert _reader("real_pick_held_share")(free) is None


def test_expert_roofline_reader_divides_counted_bytes_by_kernel_time(bench):
    """A capture of two decode programs of 8 steps, the few-row expert
    kernels a quarter of their time, the counters at 14 held experts a
    layer-step: least time = 4 x 14 experts' bytes over 819 GB/s."""
    cfg = _config("configs",
                  "longcat-flash-omni-560b-a27b-q4km-ep8-16lane.json")
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
        "samples": _samples((0, 100), (0, 1400), (0, 19200), (0, 1600),
                            (0, 6400)),
    }
    got = _reader("longcat_expert_roofline")(run)
    taken = (0.1 / 8) * 0.05 / 0.2
    least = 4 * 14 * one / 819e9
    assert abs(got - 100.0 * least / taken) < 1e-9
    assert run["notes"]["longcat_expert_roofline"]["bound"] == "hbm"
    del run["profile"]["ops"]["%q4k_expert_matmul_fewrow.1 = f32[] custom-call()"]
    del run["profile"]["ops"]["%q6k_expert_matmul_fewrow.2 = f32[] custom-call()"]
    assert _reader("longcat_expert_roofline")(run) == 0.0
    assert _reader("longcat_expert_roofline")({**run, "profile": None}) is None


# ---------------------------------------------------------------------------
# the engines
# ---------------------------------------------------------------------------

SYSTEM = "you are a careful assistant who answers in short plain sentences"
MSGS = [{"role": "system", "content": SYSTEM},
        {"role": "user", "content": "tell me about latents and rings"}]
MSGS2 = [{"role": "system", "content": SYSTEM},
         {"role": "user", "content": "and what does an expert hold here"}]


@pytest.fixture(scope="module")
def engine(paths):
    from llama_fastapi_k8s_gpu_tpu.engine import Engine

    return Engine(paths["share"], n_ctx=N_CTX * 6, prefill_chunk=SLICE,
                  decode_chunk=4, prefix_min=8)   # (/response's 460 tokens)


def test_serial_engine_serves_reuses_a_prefix_and_counts(engine):
    out = engine.create_chat_completion(MSGS, max_tokens=12, temperature=0.0)
    assert out["usage"]["completion_tokens"] >= 1
    kind = engine.cache_kind
    assert kind["kind"] == "latent-ring" and kind["prefix_reuse"] == "on"
    assert kind["attn_sublayers"] == 4 and kind["experts_zero"] == 4
    assert kind["bytes_per_position"] == 2 * 4 * 40
    assert kind["bytes_per_position_laid_out"] == 2 * 4 * 128
    assert kind["experts_held"] == [2, 4] and kind["experts_routed"] == 8
    assert kind["dense_layers"] == 0 and kind["routed_layers"] == 2
    assert engine._prefix_cache and engine.cfg.attn_impl == "xla"
    gauges = engine.cache_read_gauges()
    assert 0 < gauges["latent_positions_live_total"] \
        <= gauges["latent_positions_read_total"]
    snap = engine.expert_counters.snapshot(block=True)
    assert snap["picks_held"] == sum(snap["picks"]) and len(snap["picks"]) == 4
    assert 0 < snap["picks_zero"] and 0 < snap["picks_held"]
    assert snap["picks_zero"] + snap["picks_held"] < snap["picks_total"]
    assert snap["picks_total"] == snap["layer_steps"] * 3
    # the same request again rides the prefix the ring still holds, and
    # gives the same greedy text as the full prefill did
    again = engine.create_chat_completion(MSGS, max_tokens=12,
                                          temperature=0.0)
    assert again["choices"][0]["message"] == out["choices"][0]["message"]


def test_lane_engine_serves_and_admits_through_a_lane_claim(paths, engine):
    from llama_fastapi_k8s_gpu_tpu.engine.continuous import ContinuousEngine

    want = engine.create_chat_completion(MSGS, max_tokens=10, temperature=0.0)
    eng = ContinuousEngine(paths["share"], n_ctx=N_CTX * 2,
                           prefill_chunk=SLICE, decode_chunk=4, batch_size=3)
    try:
        assert eng._lane_prefix and eng.cache_kind["prefix_reuse"] == "on"
        first = eng.submit(MSGS, max_tokens=10, temperature=0.0).result(
            timeout=300)
        assert first["usage"] == want["usage"]
        assert first["choices"][0]["message"] == want["choices"][0]["message"]
        futs = [eng.submit(m, max_tokens=10, temperature=0.0)
                for m in (MSGS, MSGS2, MSGS, MSGS2, MSGS)]
        outs = [f.result(timeout=300) for f in futs]
        # a claim hit gives the text the full prefill gave on these lanes
        for o in (outs[0], outs[2], outs[4]):
            assert o["choices"][0]["message"] == first["choices"][0]["message"]
        stats = eng.scheduler_stats()
        assert stats["lane_prefix_hits"] >= 3
        assert stats["lane_prefix_reused_tokens"] >= 3 * SLICE
        snap = eng.expert_counters.snapshot(block=True)
        assert 0 < snap["picks_zero"] + snap["picks_held"] \
            < snap["picks_total"]
    finally:
        eng.shutdown()


def test_the_lane_engine_serves_through_the_kernels(gguf_path):
    """``attn_impl="pallas"`` through the engine itself (what ``auto`` asks
    for on a TPU; interpret mode here): both latent kernels serve the 4
    leaves, a row is stored a live lane, step and SUB-layer."""
    from llama_fastapi_k8s_gpu_tpu.engine.continuous import ContinuousEngine

    eng = ContinuousEngine(gguf_path, n_ctx=N_CTX * 2, prefill_chunk=SLICE,
                           decode_chunk=4, batch_size=2,
                           attn_impl="pallas")
    try:
        assert eng.cfg.latent_kernel and eng.cfg.latent_slice_kernel
        first = eng.submit(MSGS, max_tokens=9, temperature=0.0).result(
            timeout=300)
        again = eng.submit(MSGS, max_tokens=9, temperature=0.0).result(
            timeout=300)
        assert again["choices"][0]["message"] == first["choices"][0]["message"]
        gauges = eng.cache_read_gauges()
        assert gauges["ring_rows_written_total"] > 0
        assert gauges["ring_rows_written_total"] % 4 == 0
        assert gauges["latent_slices_kernel_total"] > 0
        assert gauges["latent_slices_loop_total"] == 0
    finally:
        eng.shutdown()


@pytest.mark.anyio
async def test_response_and_v1_serve_and_health_names_the_block(engine):
    import json

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
            assert isinstance(r.json()["response"], str)
            eng = (await client.get("/health")).json()["engine"]
            assert eng["cache"]["kind"] == "latent-ring"
            assert eng["cache"]["attn_sublayers"] == 4
            assert eng["cache"]["experts_zero"] == 4
            assert eng["cache"]["experts_held"] == [2, 4]
            assert set(eng["weight_formats"]) >= {
                "attn.wq_a", "attn.wkv_a", "attn.w_uk", "ffn.w_gate",
                "ffn.w_down", "moe.w_gate_exps"}
            d = (await client.get("/debug/compiles")).json()
            assert not d.get("degrades")
            m = (await client.get("/metrics")).text
            for name in ("latent_positions_read_total",
                         "expert_picks_routed_total",
                         "expert_picks_held_total",
                         "expert_picks_zero_total", "experts_read_total",
                         "expert_layer_steps_total"):
                assert name in m, name
        await app.router.shutdown()
