"""The ``exaone-moe`` block (models/hybrid.py) at a tiny size on the CPU,
against the plain float32 reference (benchmarks/reference_exaone.py): window
and global attention mixed per layer over the fifth cache kind
(``window+global-ring``: a window layer's leaf holds WINDOW slots that wrap),
per-head QK-norm, rotation in the window layers alone, leading dense layers,
the sigmoid router with its choice bias, routed + shared experts, and an
expert layer that is told which experts it holds.

The tiny file (``testing.TINY_HYBRID_CFG``) keeps every ratio of the
published block: window window window global x 2, a window of 16 on a leaf
of 16 slots, 4 heads on 2 KV heads of 32 (heads x width is not the hidden
size), 1 dense + 7 routed layers of 8 experts, top-3, one shared expert.
The sequences cross the window's 16 positions six times.

LIMIT: the program (bf16 inputs to every product, float32 sums, a bf16
stream and cache) against the float32 reference on the program's OWN picks
reads 1-2.5 % of the logits' norm over blocks of 16 positions on eight
layers; every control below (another function: full attention in the window
layers, rotation in the global layers, no shared expert) reads 10 % or
more.  PICKS: rows whose set of picked experts differs from the reference's
own (near-ties that bf16 rounding orders the other way).
"""

from __future__ import annotations

import dataclasses
import os
import sys

import numpy as np
import pytest

from tests.test_mla import lane_alone as _lane_alone
from tests.test_mla import (
    lanes_run, load, prefill, programs, reference_rows, rel,
    rows_that_differ, worst)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")

LIMIT = 5e-2
PICKS = 100          # rows of 7 layers x N_SEQ whose picks may differ
N_CTX = 128
SLICE = 16
N_PROMPT = 52
N_SEQ = 100


@pytest.fixture(scope="module")
def ref():
    sys.path.insert(0, BENCH)
    try:
        import reference_exaone
        yield reference_exaone
    finally:
        sys.path.remove(BENCH)


@pytest.fixture(scope="module")
def gguf_path(tmp_path_factory):
    from llama_fastapi_k8s_gpu_tpu.testing import write_tiny_hybrid_gguf

    path = str(tmp_path_factory.mktemp("exaone") / "tiny.gguf")
    write_tiny_hybrid_gguf(path, seed=3)
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


def with_kernels(loaded):
    """The same file served as a TPU serves it: the flash kernels (here in
    interpret mode) for the slices and the decode steps of both leaf kinds."""
    params, cfg = loaded
    return params, dataclasses.replace(cfg, attn_impl="pallas")


def serve(params, cfg, tokens, size=SLICE, n_prompt=N_PROMPT, n_seq=N_SEQ):
    """Slices of ``size`` then steps through the cache: (logits (S, V),
    picks (L_moe, S, k), the cache)."""
    import jax.numpy as jnp

    pass_, step, _ = programs(cfg)
    logits, picks, cache = prefill(params, cfg, tokens, n_prompt, size=size,
                                   pass_=pass_)
    dec, dpicks = [], []
    for t in range(n_prompt, n_seq):
        lg, cache, pk = step(params, jnp.int32(tokens[t]), jnp.int32(t),
                             cache)
        dec.append(np.asarray(lg))
        dpicks.append(np.asarray(pk))
    return (np.concatenate([logits, np.stack(dec)]),
            np.concatenate([picks] + dpicks, axis=1), cache)


@pytest.fixture(scope="module")
def served(loaded, tokens):
    return serve(*loaded, tokens)


@pytest.fixture(scope="module")
def own(ref, model, tokens):
    """The reference on its own picks: (logits, picks (L_moe, S, k))."""
    logits, routes = ref.forward(*model, tokens)
    return np.asarray(logits), np.stack([p for _, p in routes])


# ---------------------------------------------------------------------------
# the program against the reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("size", [8, 16, 64],
                         ids=["narrower", "the_leaf", "wider_than_the_leaf"])
def test_slices_then_decode_through_the_window_leaves(ref, model, loaded,
                                                      tokens, own, size):
    """Prefill in slices narrower than, as wide as and wider than a window
    leaf (the last one padded past the prompt's end: padding is never
    stored), then 48 steps, each overwriting the row the window let go."""
    logits, picks, _ = serve(*loaded, tokens, size=size)
    assert rows_that_differ(picks, own[1]) <= PICKS
    want = np.asarray(ref.forward(*model, tokens, use_picks=picks)[0])
    print("read", worst(logits[:N_PROMPT], want[:N_PROMPT]),
          worst(logits[N_PROMPT:], want[N_PROMPT:]))
    assert worst(logits[:N_PROMPT], want[:N_PROMPT]) < LIMIT
    assert worst(logits[N_PROMPT:], want[N_PROMPT:]) < LIMIT


@pytest.mark.parametrize("control", ["no_window", "rope_all", "no_shared"])
def test_another_function_fails_the_limit(ref, model, tokens, served,
                                          control):
    logits, picks, _ = served
    got = np.asarray(ref.forward(*model, tokens, use_picks=picks,
                                 **{control: True})[0])
    print("read", control, worst(logits, got))
    assert worst(logits, got) > 2 * LIMIT


def test_the_kernels_serve_what_the_reference_computes(ref, model, loaded,
                                                        tokens, served):
    """The decode kernel on a leaf that wraps (it stores the row itself) and
    on the global ring, and the flash kernel on a window layer's run of
    keys, as a TPU runs them (here in interpret mode): slices of 16 and 48
    steps against the reference, under the limit the XLA forms are held to."""
    params, cfg = with_kernels(loaded)
    from llama_fastapi_k8s_gpu_tpu.models import hybrid
    from llama_fastapi_k8s_gpu_tpu.models.llama import ring_write_impl

    assert hybrid.window_block(cfg) == 16 and ring_write_impl(cfg) == "kernel"
    logits, picks, cache = serve(params, cfg, tokens)
    want = np.asarray(ref.forward(*model, tokens, use_picks=picks)[0])
    print("read", worst(logits[:N_PROMPT], want[:N_PROMPT]),
          worst(logits[N_PROMPT:], want[N_PROMPT:]))
    assert worst(logits[:N_PROMPT], want[:N_PROMPT]) < LIMIT
    assert worst(logits[N_PROMPT:], want[N_PROMPT:]) < LIMIT
    # the first layer's leaf (the same input on both paths) holds the same
    # rows whoever stored them: the last 16 positions, each in its slot
    for name in ("kw", "vw"):
        assert np.array_equal(np.asarray(cache[name][0], np.float32),
                              np.asarray(served[2][name][0], np.float32))


@pytest.mark.parametrize("slots, block", [(16, 16), (32, 16), (48, 16)])
def test_the_decode_kernel_on_a_leaf_that_wraps(slots, block):
    """``flash_attention_decode(wrap=True)`` against the XLA form on a leaf
    of one, two and three blocks: lanes before and after the wrap, one dead,
    the row stored where ``position % slots`` says."""
    import jax
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.models import hybrid
    from llama_fastapi_k8s_gpu_tpu.models.config import ModelConfig
    from llama_fastapi_k8s_gpu_tpu.ops.pallas import flash_attention_decode

    window = slots - 3
    cfg = ModelConfig(vocab_size=8, dim=64, n_layers=2, n_heads=4,
                      n_kv_heads=2, ffn_dim=8, n_ctx=256, head_width=128,
                      sliding_window=window, attn_kinds=("window", "window"))
    assert cfg.window_slots == slots
    rng = np.random.default_rng(slots)
    B, L, hd = 4, 2, 128
    kw = jnp.asarray(rng.standard_normal((B, L, 2, slots, hd)), jnp.bfloat16)
    vw = jnp.asarray(rng.standard_normal((B, L, 2, slots, hd)), jnp.bfloat16)
    q = jnp.asarray(rng.standard_normal((B, 4, hd)), jnp.bfloat16)
    kn = jnp.asarray(rng.standard_normal((B, 2, hd)), jnp.bfloat16)
    vn = jnp.asarray(rng.standard_normal((B, 2, hd)), jnp.bfloat16)
    pos = jnp.asarray([5, slots - 1, 3 * slots + 7, 200], jnp.int32)
    live = jnp.asarray([True, True, True, False])

    def kernel(q, k, v, p, lv, kn, vn):
        return flash_attention_decode(
            q, k, v, jnp.int32(1), p, lv, sm_scale=hd ** -0.5, block_k=block,
            sliding_window=window, interpret=True, k_new=kn, v_new=vn,
            wrap=True)

    ctx, k2, v2 = jax.jit(jax.vmap(kernel))(q, kw, vw, pos, live, kn, vn)
    for b in range(B):
        slot = int(pos[b]) % slots
        want_k = kw[b].at[1, :, slot].set(kn[b])
        want_v = vw[b].at[1, :, slot].set(vn[b])
        if not live[b]:          # a lane that holds no request stores nothing
            assert np.array_equal(np.asarray(k2[b], np.float32),
                                  np.asarray(kw[b], np.float32))
            continue
        assert np.array_equal(np.asarray(k2[b], np.float32),
                              np.asarray(want_k, np.float32))
        assert np.array_equal(np.asarray(v2[b], np.float32),
                              np.asarray(want_v, np.float32))
        want = hybrid.window_decode_attention(
            q[b][None], want_k[1], want_v[1], pos[b], cfg, jnp.float32)
        assert rel(np.asarray(ctx[b], np.float32), np.asarray(want[0])) < 1e-2


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_three_lanes_one_dead_then_taken(ref, model, loaded, tokens, impl):
    """test_mla's lanes on this block: two lanes past the window and one
    that crosses it while it decodes, beside a dead lane whose leaves hold
    garbage; on the XLA forms and on the kernels."""
    loaded = loaded if impl == "xla" else with_kernels(loaded)
    got, seqs, stats = lanes_run(loaded, tokens)
    params, cfg = loaded
    for lane, (first, logits, picks) in got.items():
        n = first + len(logits)
        use = np.concatenate(
            [prefill(params, cfg, seqs[lane], first)[1], picks], axis=1)
        assert use.shape[1] == n
        want = reference_rows(ref, model, seqs[lane], use, length=N_SEQ)
        assert worst(logits, want[first:]) < LIMIT, lane
    for st, n_live in stats:
        assert np.array_equal(st[0], st[1]) and np.array_equal(st[0], st[2])
        layer_steps, total, held = st[0][0], st[0][-1], st[0][2:-1].sum()
        assert layer_steps == 7
        assert total == held == n_live * 7 * cfg.n_experts_used


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_a_lanes_logits_do_not_depend_on_the_other_lanes(loaded, tokens,
                                                         impl):
    """test_mla's case on this block: a lane past the window beside a near,
    a far and a dead lane gives bitwise the same logits."""
    _lane_alone(loaded if impl == "xla" else with_kernels(loaded), tokens)


# ---------------------------------------------------------------------------
# the share
# ---------------------------------------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer(ref, tmp_path, model, tokens):
    """One test ties the share to the model: the routed parts that the
    eight shares (first, 1) give, plus what every chip computes alike
    (attention, the shared expert) counted once, add up to what the UNCUT
    reference gives for the whole layer."""
    import jax
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.models import hybrid
    from llama_fastapi_k8s_gpu_tpu.models.llama import init_cache
    from llama_fastapi_k8s_gpu_tpu.testing import write_tiny_hybrid_gguf

    hp, tensors = model
    S = 24
    x = np.asarray(ref.tensor(tensors, "token_embd.weight"))[tokens[:S]] * 8
    outs, picks = [], None
    for e in range(8):
        path = str(tmp_path / f"share{e}.gguf")
        write_tiny_hybrid_gguf(path, seed=3, held=(e, 1))
        params, cfg = load(path)
        assert (cfg.experts_first, cfg.n_held, cfg.n_experts) == (e, 1, 8)
        assert params["layers"]["moe"]["w_gate_exps"]["w"].shape[1] == 1

        def run(cfg):
            return jax.jit(lambda h, c: hybrid.layer(
                h, params["layers"]["moe"], jnp.int32(0), jnp.int32(1),
                "moe", "window", c, jnp.arange(S, dtype=jnp.int32),
                jnp.int32(0), jnp.int32(S), cfg))(
                    jnp.asarray(x, jnp.bfloat16), init_cache(cfg))

        h, _, (count, pk, total) = run(cfg)
        assert int(total) == S * 3 and int(count.sum()) < S * 3
        outs.append(np.asarray(h, np.float32))
        picks = np.asarray(pk)
        if e == 0:    # a share that holds nothing this router can pick
            none = np.asarray(run(dataclasses.replace(
                cfg, experts_first=cfg.n_experts))[0], np.float32)
    got = sum(outs) - 7 * none
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.layer(
            hp, ref.layer_weights(tensors, 1),
            jnp.asarray(x, jnp.bfloat16).astype(jnp.float32), 1,
            use_picks=picks)[0])
    print("read", rel(got, want))
    assert rel(got, want) < LIMIT
    assert rel(outs[0], want) > 5 * LIMIT     # one share alone is far


def test_a_share_serves_and_counts_what_left(tmp_path, ref, tokens):
    """A file that holds experts 2..3 of 8: the program and the reference
    given the same share agree; the counters tell held from routed."""
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.models.llama import forward, init_cache
    from llama_fastapi_k8s_gpu_tpu.testing import write_tiny_hybrid_gguf

    path = str(tmp_path / "share.gguf")
    write_tiny_hybrid_gguf(path, seed=3, held=(2, 2))
    params, cfg = load(path)
    logits, picks, _ = prefill(params, cfg, tokens, 48)
    want = np.asarray(ref.forward(*ref.open_model(path), tokens[:48],
                                  use_picks=picks)[0])
    assert worst(logits, want) < LIMIT
    _, _, stats = forward(params, cfg, jnp.asarray(tokens[:16], jnp.int32),
                          jnp.int32(0), init_cache(cfg), with_stats=True)
    stats = np.asarray(stats)
    assert len(stats) == 3 + 2 and stats[0] == 7
    assert stats[-1] == 7 * 16 * 3
    held = int(np.sum((picks[:, :16] >= 2) & (picks[:, :16] < 4)))
    assert stats[2:-1].sum() == held < stats[-1]


# ---------------------------------------------------------------------------
# the file, the cache, the refusals
# ---------------------------------------------------------------------------

def test_gguf_round_trip_of_the_keys_and_the_layer_kinds(loaded):
    from llama_fastapi_k8s_gpu_tpu.models import hybrid
    from llama_fastapi_k8s_gpu_tpu.models.config import WINDOW_GLOBAL_RING
    from llama_fastapi_k8s_gpu_tpu.testing import TINY_HYBRID_CFG as T

    params, cfg = loaded
    assert cfg.cache_kind == WINDOW_GLOBAL_RING == "window+global-ring"
    for key in ("attn_kinds", "rope_kinds", "sliding_window", "head_dim",
                "qk_norm_per_head", "rope_neox", "n_dense_layers",
                "expert_ffn_dim", "n_shared_experts", "n_experts",
                "n_experts_used", "expert_gating", "n_expert_groups",
                "expert_weights_scale", "norm_topk_prob"):
        assert getattr(cfg, key) == getattr(T, key), key
    assert cfg.head_dim * cfg.n_heads != cfg.dim
    assert params["layers"]["dense"]["wq"]["w"].shape[0] == 1
    assert params["layers"]["moe"]["attn_q_norm"].shape == (7, 32)
    # runs of one (feed-forward, attention) kind: weights by the first,
    # cache leaves by the second
    assert hybrid.runs(cfg) == [
        ("dense", "window", 0, 0, 1), ("moe", "window", 0, 1, 2),
        ("moe", "global", 2, 0, 1), ("moe", "window", 3, 3, 3),
        ("moe", "global", 6, 1, 1)]


@pytest.mark.parametrize("n_ctx", [128, 512])
def test_the_cache_is_what_cache_nbytes_says_and_a_window_leaf_is_the_window(
        loaded, n_ctx):
    from llama_fastapi_k8s_gpu_tpu.models.llama import cache_nbytes, init_cache

    cfg = dataclasses.replace(loaded[1], n_ctx=n_ctx)
    cache = init_cache(cfg)
    assert sum(leaf.nbytes for leaf in cache.values()) == cache_nbytes(cfg)
    assert cache["kw"].shape == cache["vw"].shape == (6, 2, 16, 32)
    assert cache["k"].shape == cache["v"].shape == (2, 2, n_ctx, 32)
    # a ring in every layer would hold 8 x n_ctx slots
    assert cache_nbytes(cfg) == 2 * 2 * 32 * 2 * (2 * n_ctx + 6 * 16)


def test_the_counters_are_the_kinds_layer_slots(loaded):
    from llama_fastapi_k8s_gpu_tpu.models import hybrid

    cfg = loaded[1]
    c = hybrid.chunk_counts([5, 40], 2, cfg, bound=40)
    assert c["window_read"] == 6 * 16 * 4
    assert c["window_live"] == 6 * (6 + 7 + 16 + 16)
    assert c["global_live"] == 2 * (6 + 7 + 41 + 42)
    # the XLA loop reads whole blocks up to the largest live lane's bound
    assert c["global_read"] == 2 * 4 * 128


def _file_with(tmp_path, **meta):
    """The tiny file with ``exaone-moe.<key>`` values replaced."""
    from llama_fastapi_k8s_gpu_tpu import testing
    from llama_fastapi_k8s_gpu_tpu.gguf import GGUFWriter

    path = str(tmp_path / "odd.gguf")

    class Odd(GGUFWriter):
        def add_metadata(self, key, value):
            short = key.removeprefix("exaone-moe.")
            super().add_metadata(key, meta.get(short, value))

    orig = testing.GGUFWriter
    testing.GGUFWriter = Odd
    try:
        testing.write_tiny_hybrid_gguf(path)
    finally:
        testing.GGUFWriter = orig
    return path


@pytest.mark.parametrize("meta, words", [
    ({"attention.sliding_window_pattern": 1}, "sliding_window_pattern 1"),
    ({"attention.sliding_window": 0}, "attention.sliding_window 0"),
    ({"attention.value_length": 64}, "key_length and value_length differ"),
    ({"expert_gating_func": 3}, "exaone-moe: expert_gating_func 3"),
    ({"expert_group_count": 3}, "exaone-moe: 8 experts in 3 groups"),
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
                            "'exaone-moe'"),
    (dict(kv_paged=True), "LFKT_KV_PAGED=1 cannot serve architecture "
                          "'exaone-moe'"),
])
def test_what_cannot_hold_the_cache_is_refused_by_name(gguf_path, kw, words):
    from llama_fastapi_k8s_gpu_tpu.engine import Engine

    with pytest.raises(ValueError, match=words):
        Engine(gguf_path, n_ctx=N_CTX, **kw)


# ---------------------------------------------------------------------------
# the engines
# ---------------------------------------------------------------------------

SYSTEM = "you are a careful assistant who answers in short plain sentences"
MSGS = [{"role": "system", "content": SYSTEM},
        {"role": "user", "content": "tell me about windows and rings"}]
MSGS2 = [{"role": "system", "content": SYSTEM},
         {"role": "user", "content": "and what does a window leaf hold"}]


@pytest.fixture(scope="module")
def engine(gguf_path):
    from llama_fastapi_k8s_gpu_tpu.engine import Engine

    return Engine(gguf_path, n_ctx=N_CTX * 2, prefill_chunk=SLICE,
                  decode_chunk=4, prefix_min=8)


def test_serial_engine_serves_without_reuse_and_counts_per_kind(engine):
    out = engine.create_chat_completion(MSGS, max_tokens=12, temperature=0.0)
    assert out["usage"]["completion_tokens"] >= 1
    assert out["usage"]["prompt_tokens"] > 5 * 16   # past the window
    kind = engine.cache_kind
    assert kind["kind"] == "window+global-ring"
    assert kind["prefix_reuse"].startswith("off: a wrapped window")
    assert (kind["window"], kind["window_slots"]) == (16, 16)
    assert (kind["window_layers"], kind["global_layers"]) == (6, 2)
    assert kind["rotated"] == ["window"]
    assert kind["bytes_per_lane"] == 2 * 2 * 32 * 2 * (2 * 256 + 6 * 16)
    assert kind["experts_held"] == [0, 8] and kind["experts_routed"] == 8
    assert kind["kv_paged"] == "refused at start"
    assert not engine._prefix_cache and engine.cfg.attn_impl == "xla"
    g = engine.cache_read_gauges()
    assert 0 < g["window_slots_live_total"] <= g["window_slots_read_total"]
    assert 0 < g["global_slots_live_total"] <= g["global_slots_read_total"]
    # the ring totals keep their meaning: the sum over kinds
    assert g["ring_slots_read_total"] == g["window_slots_read_total"] \
        + g["global_slots_read_total"]
    assert g["ring_slots_live_total"] == g["window_slots_live_total"] \
        + g["global_slots_live_total"]
    snap = engine.expert_counters.snapshot(block=True)
    assert snap["picks_total"] == snap["picks_held"] == sum(snap["picks"]) > 0
    # the same request again prefills again, and gives the same greedy text
    again = engine.create_chat_completion(MSGS, max_tokens=12,
                                          temperature=0.0)
    assert again["choices"][0]["message"] == out["choices"][0]["message"]


def test_lane_engine_gives_the_serial_engines_text_with_claims_off(
        gguf_path, engine):
    from llama_fastapi_k8s_gpu_tpu.engine.continuous import ContinuousEngine

    want = [engine.create_chat_completion(m, max_tokens=10, temperature=0.0)
            for m in (MSGS, MSGS2)]
    eng = ContinuousEngine(gguf_path, n_ctx=N_CTX * 2, prefill_chunk=SLICE,
                           decode_chunk=4, batch_size=3)
    try:
        assert not eng._lane_prefix
        assert eng.cache_kind["prefix_reuse"].startswith("off")
        futs = [eng.submit(m, max_tokens=10, temperature=0.0)
                for m in (MSGS, MSGS2, MSGS, MSGS2, MSGS)]
        outs = [f.result(timeout=300) for f in futs]
        # the lanes' step program rounds otherwise than the serial one: the
        # same request gives the same greedy text on whichever lane, beside
        # whichever neighbours, and the prompt the serial engine counted
        for o, w in zip(outs, (want * 3)[:5]):
            assert o["usage"]["prompt_tokens"] == w["usage"]["prompt_tokens"]
        for o in (outs[2], outs[4]):
            assert o["choices"][0]["message"] == outs[0]["choices"][0]["message"]
        assert outs[3]["choices"][0]["message"] \
            == outs[1]["choices"][0]["message"]
        assert not eng.scheduler_stats().get("lane_prefix_hits")
        g = eng.cache_read_gauges()
        assert 0 < g["window_slots_live_total"] <= g["window_slots_read_total"]
        snap = eng.expert_counters.snapshot(block=True)
        assert 0 < snap["picks_held"] == snap["picks_total"]
    finally:
        eng.shutdown()


@pytest.mark.anyio
async def test_v1_chat_completions_streams_and_health_names_the_kind(engine):
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
            eng = (await client.get("/health")).json()["engine"]
            assert eng["cache"]["kind"] == "window+global-ring"
            assert eng["cache"]["window_slots"] == 16
            assert eng["ring_write"] == "xla"
            assert set(eng["weight_formats"]) >= {
                "dense.wq", "dense.w_gate", "moe.wk", "moe.wo",
                "moe.w_gate_exps", "moe.w_down_sh"}
            d = (await client.get("/debug/compiles")).json()
            assert not d.get("degrades")
            m = (await client.get("/metrics")).text
            for name in ("window_slots_read_total", "window_slots_live_total",
                         "global_slots_read_total", "global_slots_live_total",
                         "expert_picks_routed_total",
                         "expert_picks_held_total", "experts_read_total"):
                assert name in m, name
        await app.router.shutdown()
