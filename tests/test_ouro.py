"""The ``ouro`` block (layers that run several times: models/llama.py
``forward``'s one loop of ``n_layers x ut_steps`` bodies, weights at ``i %
n_layers``, the cache leaf at ``i``) at a tiny size on the CPU, against the
plain float32 reference (benchmarks/reference_ouro.py: the whole sequence,
every pass, no cache).  Logits, never tokens.

The tiny file (``testing.TINY_OURO_CFG``): 2 layers x 3 passes (no 2 x 2
symmetry between the weight index and the cache leaf), 4 MHA heads, norm
gains spread over 0.6-1.4 (each norm its own), the exit gate.  ``q4k``
runs the file's own K-quant blocks through the fused kernels on a file
wide enough that every matrix fuses (2048, as tests/test_dense_reference.py).

The limits are relative errors of the logits' norm at one position (the
worst of a case's positions is held).  The arithmetic is
tests/test_dense_reference.py's (bf16 products and a bf16 stream, the fused
K-quant kernels' bf16 ``d * sc``, an int8 ring), whose limits are for TWO
layer applications; here a token takes SIX, and each pass starts from the
rounding of the one before: cut to 1, 2 and 3 passes the program reads 1.4,
2.8 and 4.0 % over a whole sequence, and the reference with every matmul
input rounded to bfloat16 reads 1.0, 2.1 and 2.7 % of itself.  Read here,
worst position of each case: bf16 7.7-9.1 %, q4k 11-12.4 %, an int8 ring up
to 1.5 % more.  ``LIMIT`` 15 % / 20 %, ``KV_INT8`` + 3 %.

Every control is another function and reads past TWICE the limit, most by
far (mean over the compared positions, against the bf16 program: one pass
fewer 79 %, pass t on pass t-1's leaf 108 %, no norm between passes 87 %,
no norm after attention 102 %, none after the feed-forward 64 %; the
program with its two indices confused, and with the decode step one slot
late, below).
"""

from __future__ import annotations

import dataclasses
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llama_fastapi_k8s_gpu_tpu.models import llama
from llama_fastapi_k8s_gpu_tpu.models.config import ModelConfig
from tests.test_dense_reference import (  # noqa: F401  (fixtures)
    BENCH, CHUNK, N_CTX, chat_of, greedy, lane_step, prefill, rel,
    small_blocks, stale_ring, watched)

LIMIT = {"bf16": 0.15, "q4k": 0.20}
KV_INT8 = 0.03

WEIGHTS = ("bf16", "q4k")
KVS = ("bf16", "int8")
WIDE = dict(dim=2048, ffn_dim=2048, n_heads=16, n_kv_heads=16)


def limit(weights: str, kv: str) -> float:
    return LIMIT[weights] + (KV_INT8 if kv == "int8" else 0.0)


@pytest.fixture(scope="module")
def ref():
    sys.path.insert(0, BENCH)
    try:
        import reference_ouro
        yield reference_ouro
    finally:
        sys.path.remove(BENCH)


@pytest.fixture(scope="module")
def files(tmp_path_factory, ref):
    """``files(weights) -> (path, model, logits)``: ONE tiny file per weight
    format (``q4k``: the wide one), written once; ``logits(tokens, **kw)``
    the reference's answer for the whole sequence, kept per call."""
    from llama_fastapi_k8s_gpu_tpu.testing import (
        TINY_OURO_CFG, write_tiny_ouro_gguf)

    root = tmp_path_factory.mktemp("ouro")
    made: dict = {}

    def get(weights: str):
        if weights not in made:
            path = str(root / f"{weights}.gguf")
            cfg = TINY_OURO_CFG if weights == "bf16" else \
                dataclasses.replace(TINY_OURO_CFG, **WIDE)
            write_tiny_ouro_gguf(path, cfg, seed=5)
            model, seen, keep = ref.open_model(path), {}, {}

            def logits(tokens, **kw):
                key = (tuple(int(t) for t in tokens),
                       tuple(sorted(kw.items())))
                if key not in seen:
                    seen[key] = np.asarray(ref.forward(
                        *model, tokens, keep=keep, **kw)[0])
                return seen[key]

            made[weights] = (path, model, logits)
        return made[weights]

    return get


@pytest.fixture(scope="module")
def models(files):
    """``models(weights, kv) -> (params, cfg, logits)``, loaded once a
    weight format."""
    from llama_fastapi_k8s_gpu_tpu.gguf import GGUFFile
    from llama_fastapi_k8s_gpu_tpu.models.params import load_params

    loaded: dict = {}

    def get(weights: str, kv: str = "bf16"):
        path, _, logits = files(weights)
        if weights not in loaded:
            gf = GGUFFile(path)
            cfg = ModelConfig.from_gguf(gf, n_ctx=N_CTX)
            loaded[weights] = (load_params(gf, cfg, fmt=weights), cfg)
        params, cfg = loaded[weights]
        return params, dataclasses.replace(cfg, kv_dtype=kv), logits

    return get


def matrix(fn):
    for name, values in (("kv", KVS), ("weights", WEIGHTS)):
        fn = pytest.mark.parametrize(name, values)(fn)
    return fn


def serial_errors(params, cfg, tokens, want, shift=0, n=20):
    """The serial engine's two programs: a padded bucket prefill of ``n``
    tokens, then one position at a time through the ring (``shift``: every
    step that many slots late).  The relative error at each position."""
    logits, cache = prefill(params, cfg, tokens, n)
    errs = [rel(logits, want[n - 1])]
    step = jax.jit(lambda t, p, c: llama.decode_step(params, cfg, t, p, c))
    for pos in range(n, len(tokens)):
        logits, cache = step(jnp.int32(tokens[pos]),
                             jnp.int32(pos + shift), cache)
        errs.append(rel(logits, want[pos]))
    return errs


# ---------------------------------------------------------------------------
# the program against the reference
# ---------------------------------------------------------------------------

@matrix
def test_serial_prefill_then_64_decode_steps_agree_with_the_reference(
        models, weights, kv):
    """Positions 20..83 one at a time against a ring of 6 leaves, across
    the read's block edges at 32, 48, 64 and 80."""
    params, cfg, reference_logits = models(weights, kv)
    tokens = np.random.default_rng(1).integers(0, 256, size=84)
    errs = serial_errors(params, cfg, tokens, reference_logits(tokens))
    print("read", weights, kv, max(errs))
    assert max(errs) < limit(weights, kv), (np.argmax(errs), max(errs))


@pytest.mark.parametrize("weights,kv", [("bf16", "bf16"), ("bf16", "int8"),
                                        ("q4k", "bf16")])
def test_lanes_of_different_lengths_join_and_leave(models, weights, kv):
    """The lane engine's step over three lanes (tests/test_dense_reference
    .py's walk at 24 steps): lane 0 (from 10) leaves after step 14 and keeps
    stepping, lane 1 (from 30) stays, lane 2 holds a dead request's stale
    ring of SIX leaves until a request of 5 tokens joins it at step 6."""
    params, cfg, reference_logits = models(weights, kv)
    rng = np.random.default_rng(9)
    starts, steps, leave_0, join_2 = (10, 30, 5), 24, 14, 6
    seqs = [rng.integers(0, 256, size=s + steps) for s in starts]
    fresh = [prefill(params, cfg, s, n)[1] for s, n in zip(seqs, starts)]
    caches = jax.tree.map(lambda *a: jnp.stack(a), fresh[0], fresh[1],
                          stale_ring(cfg, seed=99))
    assert caches["k_q" if kv == "int8" else "k"].shape[1] == 6
    step = lane_step(params, cfg)
    pos, got = [starts[0], starts[1], 100], {lane: [] for lane in range(3)}
    for t in range(steps):
        if t == join_2:
            caches = jax.tree.map(lambda a, b: a.at[2].set(b), caches,
                                  fresh[2])
            pos[2] = starts[2]
        live = np.array([t <= leave_0, True, t >= join_2])
        toks = [int(s[min(p, len(s) - 1)]) if lv else 1
                for s, p, lv in zip(seqs, pos, live)]
        logits, caches = step(jnp.asarray(toks, jnp.int32),
                              jnp.asarray(pos, jnp.int32), caches,
                              jnp.asarray(live))
        for lane in range(3):
            if live[lane]:
                got[lane].append(np.asarray(logits[lane]))
            pos[lane] += 1
    for lane, n in enumerate(starts):
        want = reference_logits(seqs[lane])[n:n + len(got[lane])]
        errs = [rel(a, b) for a, b in zip(got[lane], want)]
        assert max(errs) < limit(weights, kv), (lane, np.argmax(errs))


@pytest.fixture(scope="module")
def engines(files):
    """``engines(kind, weights, kv)``: a serial ``Engine`` or a 2-lane
    ``ContinuousEngine``, built once each (one compiled engine a (weights,
    KV) pair and kind) and shut down with the module."""
    from llama_fastapi_k8s_gpu_tpu.engine import ContinuousEngine, Engine

    built: dict = {}

    def get(kind: str, weights: str, kv: str):
        key = (kind, weights, kv)
        if key not in built:
            common = dict(n_ctx=N_CTX, weight_format=weights, kv_dtype=kv,
                          decode_chunk=4, max_gen_tokens=8,
                          prefill_buckets=(32, 64, 128), prefill_chunk=CHUNK)
            path = files(weights)[0]
            built[key] = ContinuousEngine(path, batch_size=2, **common) \
                if kind == "lanes" else Engine(path, **common)
        return built[key]

    yield get
    for eng in built.values():
        if hasattr(eng, "shutdown"):
            eng.shutdown()


@pytest.mark.parametrize("weights,kv", [("bf16", "bf16"), ("bf16", "int8"),
                                        ("q4k", "bf16")])
@pytest.mark.parametrize("slices", [2, 3])
@pytest.mark.parametrize("kind", ["serial", "lanes"])
def test_sliced_prefill_agrees_with_the_reference(
        engines, files, watched, kind, slices, weights, kv):
    """A prompt of 50 (2 slices) or 90 tokens (3) through both engines'
    sliced prefill: each slice runs EVERY pass before the next slice starts
    (a slice's pass t attends to the earlier slices' pass-t keys, which
    their own programs left in leaf t x n_layers + l), and the logits the
    first token is sampled from are the reference's at the prompt's end."""
    eng = engines(kind, weights, kv)
    messages, ids = chat_of(eng, {2: 50, 3: 90}[slices], seed=slices)
    greedy(eng, messages)
    assert watched["offsets"] == [0, CHUNK, 2 * CHUNK][:slices]
    want = files(weights)[2](ids)[len(ids) - 1]
    assert rel(watched["logits"][0], want) < limit(weights, kv)


@pytest.mark.parametrize("kv", KVS)
@pytest.mark.parametrize("kind", ["serial", "lanes"])
def test_a_reused_prefix_then_its_suffix_agrees_with_the_reference(
        engines, files, watched, kind, kv):
    """The ring rolls back to a prefix of what it holds in ALL its leaves
    (``rolls_back`` stays True): a second chat that shares its first 68
    tokens prefills its suffix alone (the serial ring's own claim, a freed
    lane's claim copied into the scratch ring)."""
    eng = engines(kind, "bf16", kv)
    assert eng.cache.rolls_back
    shared = "".join(np.random.default_rng(3).choice(list("abcdefgh"),
                                                     size=45))
    first, _ = chat_of(eng, 75, seed=11, shared=shared)
    second, ids = chat_of(eng, 90, seed=12, shared=shared)
    greedy(eng, first)
    del watched["logits"][:], watched["offsets"][:]
    greedy(eng, second)
    assert watched["offsets"][0] >= CHUNK, watched["offsets"]
    want = files("bf16")[2](ids)[len(ids) - 1]
    assert rel(watched["logits"][0], want) < limit("bf16", kv)


# ---------------------------------------------------------------------------
# controls: another function fails twice the limit
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def served(models):
    """The sound program's logits over 84 positions (bf16, bf16)."""
    params, cfg, reference_logits = models("bf16")
    tokens = np.random.default_rng(1).integers(0, 256, size=84)
    logits, cache = prefill(params, cfg, tokens, 20)
    out = [np.asarray(logits)]
    step = jax.jit(lambda t, p, c: llama.decode_step(params, cfg, t, p, c))
    for pos in range(20, 84):
        logits, cache = step(jnp.int32(tokens[pos]), jnp.int32(pos), cache)
        out.append(np.asarray(logits))
    return tokens, np.stack(out)


@pytest.mark.parametrize("control", [
    {"passes": 2}, {"shared_leaves": True}, {"no_pass_norm": True},
    {"no_post_attn_norm": True}, {"no_post_ffn_norm": True}],
    ids=lambda c: next(iter(c)))
def test_another_function_fails_twice_the_limit(models, served, control):
    """One pass fewer; pass t attending to the leaf pass t - 1 wrote (one
    leaf a layer); the final norm after the last pass alone; either
    after-norm left out: each is far from what the program computes, and
    the sound reference is near."""
    tokens, got = served
    reference_logits = models("bf16")[2]
    sound = reference_logits(tokens)[19:]
    other = reference_logits(tokens, **control)[19:]
    errs = [rel(a, b) for a, b in zip(got, other)]
    print("read", control, np.mean(errs))
    assert max(rel(a, b) for a, b in zip(got, sound)) < limit("bf16", "bf16")
    assert np.mean(errs) > 2 * limit("bf16", "bf16")


def test_the_decode_step_one_slot_late_fails_twice_the_limit(models):
    params, cfg, reference_logits = models("bf16")
    tokens = np.random.default_rng(1).integers(0, 256, size=84)
    errs = serial_errors(params, cfg, tokens, reference_logits(tokens),
                         shift=1)[1:]
    print("read one slot late", np.mean(errs))
    assert np.mean(errs) > 2 * limit("bf16", "bf16")


@pytest.mark.parametrize("fault", ["shared_leaf", "one_weight_row"])
def test_a_program_with_the_indices_confused_fails_twice_the_limit(
        models, served, fault, monkeypatch):
    """The program's own two indices, confused on purpose: every pass on
    the FIRST pass's leaves (``c = w``), or every body on the weights of
    the leaf's number clamped to the file's layers (``w = min(c, L - 1)``:
    what one index for both would read)."""
    params, cfg, reference_logits = models("bf16")
    tokens, _ = served
    layer = llama._layer

    def confused(h, layers, w, c, *a, **kw):
        if fault == "shared_leaf":
            c = w
        else:
            w = jnp.minimum(c, cfg.n_layers - 1)
        return layer(h, layers, w, c, *a, **kw)

    monkeypatch.setattr(llama, "_layer", confused)
    # (a whole prompt in ONE program attends to what its own pass just
    # wrote, whatever the leaf: shared leaves show from the first step on)
    errs = serial_errors(params, cfg, tokens[:40], reference_logits(tokens))
    print("read", fault, np.mean(errs[1:]))
    assert np.mean(errs[1:]) > 2 * limit("bf16", "bf16")


# ---------------------------------------------------------------------------
# the loop, its gate and its counters
# ---------------------------------------------------------------------------

def test_the_gate_is_in_the_stats_and_changes_no_logit(ref, files, models):
    """``with_stats`` returns the exit mass a pass of the row at
    ``last_idx``: the reference's ``exit_mass`` of its own gate, summing to
    one; the logits are the same with and without it; a lane that holds no
    request counts nothing."""
    params, cfg, _ = models("bf16")
    tokens = np.random.default_rng(2).integers(0, 256, size=32)
    cache = llama.init_cache(cfg)
    plain, _ = llama.forward(params, cfg, jnp.asarray(tokens), jnp.int32(0),
                             cache, last_idx=jnp.int32(24))
    logits, _, mass = llama.forward(
        params, cfg, jnp.asarray(tokens), jnp.int32(0), cache,
        last_idx=jnp.int32(24), with_stats=True)
    assert np.array_equal(np.asarray(plain), np.asarray(logits))
    lam = np.asarray(ref.forward(*files("bf16")[1], tokens)[1])
    want = ref.exit_mass(lam)[:, 24]
    assert mass.shape == (3,) and abs(float(mass.sum()) - 1.0) < 1e-5
    assert np.allclose(np.asarray(mass), want, atol=2e-2), (mass, want)
    assert min(want) > 0.02      # the tiny gate is no constant
    *_, dead = llama.forward(
        params, cfg, jnp.asarray(tokens[:1]), jnp.int32(0), cache,
        live=jnp.bool_(False), with_stats=True)
    assert not np.asarray(dead).any()


def test_the_loops_scopes_are_in_the_program_and_it_is_one_loop(models):
    params, cfg, _ = models("bf16")
    text = jax.jit(lambda t, p, c: llama.forward(
        params, cfg, t, p, c, with_stats=True)).lower(
        jnp.zeros(1, jnp.int32), jnp.int32(5), llama.init_cache(cfg)
    ).as_text(debug_info=True)
    for scope in ("ut_pass", "pass_norm", "exit_gate", "post_attn_norm",
                  "post_ffn_norm"):
        assert scope in text, scope
    # one loop over the 6 (pass, layer) pairs: one body holds the layer
    assert text.count("stablehlo.while") - text.count("attn_scores") // 2 \
        <= 2, "the layers are traced once, not once a pass"
    assert text.count('"wq"') + text.count("/wq") >= 1


def test_engine_counters_spans_and_health(engines, files):
    """A served request: ``layer_passes_total`` grows by 6 a decoded
    lane-step and a prefilled token, ``ring_slots_*`` keep their meaning (a
    sequence's slots, not a leaf's), ``ut_exit_mass_total`` sums to the
    tokens decoded, /health ``engine.loop`` names the loop."""
    eng = engines("serial", "bf16", "bf16")
    before = eng.cache_read_gauges()
    messages, ids = chat_of(eng, 40, seed=4)
    out = eng.create_chat_completion(messages, temperature=0.0, max_tokens=6)
    assert out["usage"]["completion_tokens"] == 6
    after = eng.cache_read_gauges()
    grew = {k: after[k] - before[k] for k in after}
    steps = grew["decode_lane_steps_total"]
    assert steps >= 5
    assert grew['layer_passes_total{phase="decode"}'] == 6 * steps
    assert grew['layer_passes_total{phase="prefill"}'] % 6 == 0 \
        and grew['layer_passes_total{phase="prefill"}'] >= 6 * 40
    mass = [eng.exit_mass.snapshot(block=True)[t] for t in range(3)]
    assert sum(mass) >= steps - 4      # (earlier cases' chunks fold in too)
    assert abs(sum(grew[f'ut_exit_mass_total{{pass="{t}"}}']
                   for t in range(3)) - steps) <= 4 + 1e-3
    assert eng.cache_engine_health == {"loop": {
        "ut_steps": 3, "layers": 2, "cache_leaves": 6,
        "exit_threshold": 1.0}}
    assert eng.cache.span_attrs(eng.cfg) == {"ut_steps": 3, "layer_passes": 6}


def test_a_dense_file_has_no_loop_in_health_spans_or_stats(tmp_path):
    from llama_fastapi_k8s_gpu_tpu.models.cache import cache_of
    from llama_fastapi_k8s_gpu_tpu.testing import TINY_CFG

    cache = cache_of(TINY_CFG)
    assert cache.engine_health(TINY_CFG) == {}
    assert cache.span_attrs(TINY_CFG) == {}
    assert not llama.has_step_stats(TINY_CFG)
    assert TINY_CFG.cache_leaves == TINY_CFG.n_layers == 2


# ---------------------------------------------------------------------------
# everything that sizes a cache counts n_layers x ut_steps leaves
# ---------------------------------------------------------------------------

PUBLISHED = ModelConfig(
    vocab_size=49152, dim=2048, n_layers=48, n_heads=16, n_kv_heads=16,
    ffn_dim=5632, n_ctx=1280, rope_theta=1e6, rms_eps=1e-6, rope_neox=True,
    ut_steps=4, sandwich_norm=True)


def test_every_account_of_the_cache_counts_192_leaves_at_the_published_size():
    """From shapes, no allocation: 192 x 2 x 16 x 128 x 2 B = 1.5 MiB a
    position, 2.01 GB a lane at ``n_ctx`` 1280."""
    from llama_fastapi_k8s_gpu_tpu.parallel.kvpool import page_geometry

    cfg = PUBLISHED
    assert cfg.cache_leaves == 192
    per_position = 192 * 2 * 16 * 128 * 2
    assert per_position == 1536 * 1024
    assert llama.cache_nbytes(cfg) == per_position * 1280 == 2013265920
    spec = jax.eval_shape(lambda: llama.init_cache(cfg))
    assert {k: v.shape for k, v in spec.items()} == {
        "k": (192, 16, 1280, 128), "v": (192, 16, 1280, 128)}
    assert sum(int(np.prod(v.shape)) * v.dtype.itemsize
               for v in spec.values()) == llama.cache_nbytes(cfg)
    assert page_geometry(cfg, 128) == (
        ((192, 16, 128, 128), "bfloat16"),) * 2
    int8 = dataclasses.replace(cfg, kv_dtype="int8")
    assert llama.cache_nbytes(int8) == sum(
        int(np.prod(v.shape)) * v.dtype.itemsize for v in jax.eval_shape(
            lambda: llama.init_cache(int8)).values())
    assert page_geometry(int8, 128)[0] == ((192, 16, 128, 128), "int8")


@pytest.mark.anyio
async def test_health_the_ledger_and_the_pool_count_the_leaves(tmp_path):
    """A live 2-lane engine on the tiny file with the paged pool: /health
    ``kv_cache_bytes``, the memory ledger's KV rows and the pool's page all
    come to 6 leaves (lanes + scratch + the serial ring: four caches)."""
    import httpx

    from llama_fastapi_k8s_gpu_tpu.engine import ContinuousEngine
    from llama_fastapi_k8s_gpu_tpu.obs.memledger import MEMLEDGER
    from llama_fastapi_k8s_gpu_tpu.server.app import create_app

    from llama_fastapi_k8s_gpu_tpu.testing import write_tiny_ouro_gguf

    path = str(tmp_path / "ledger.gguf")   # a model name of its own rows
    write_tiny_ouro_gguf(path)
    eng = ContinuousEngine(
        path, batch_size=2, n_ctx=N_CTX, weight_format="bf16",
        decode_chunk=4, max_gen_tokens=8, prefill_buckets=(32, 64, 128),
        prefill_chunk=CHUNK, kv_paged=True, kv_page_tokens=16)
    try:
        one = llama.cache_nbytes(eng.cfg)
        assert one == 2 * 6 * 4 * N_CTX * 64 * 2
        assert eng.kv_cache_bytes >= 4 * one
        pool = eng._kvpool
        assert pool.page_nbytes == 2 * 6 * 4 * 16 * 64 * 2
        assert eng.kv_cache_bytes == 4 * one + pool.arena_nbytes
        app = create_app(engine=eng)
        async with httpx.AsyncClient(
                transport=httpx.ASGITransport(app=app),
                base_url="http://test") as client:
            health = (await client.get("/health")).json()["engine"]
            assert health["kv_cache_bytes"] == eng.kv_cache_bytes
            assert health["loop"]["cache_leaves"] == 6
        MEMLEDGER.configure(armed=True)
        eng.model_name = "ledger-ouro"      # rows of its own in the ledger
        rows = {r["component"]: r["bytes"]
                for r in MEMLEDGER.snapshot()["components"]
                if r["component"].startswith("kv_")
                and r["model"] == eng.model_name}
        assert rows["kv_ring"] == rows["kv_scratch"] == one, rows
        # (the lanes' row holds their positions, keys and windows too)
        assert 0 <= rows["kv_lanes"] - 2 * one < 4096, rows
        # (the pool's arena has rows of its own, by who holds the pages)
        assert 0 <= sum(rows.values()) + pool.arena_nbytes \
            - eng.kv_cache_bytes < 4096, rows
    finally:
        eng.shutdown()


# ---------------------------------------------------------------------------
# the file
# ---------------------------------------------------------------------------

def test_an_exit_threshold_under_one_is_refused_by_name(tmp_path):
    from llama_fastapi_k8s_gpu_tpu.gguf import GGUFFile
    from llama_fastapi_k8s_gpu_tpu.testing import write_tiny_ouro_gguf

    path = str(tmp_path / "early.gguf")
    write_tiny_ouro_gguf(path, exit_threshold=0.9)
    with pytest.raises(ValueError) as e:
        ModelConfig.from_gguf(GGUFFile(path))
    for words in ("ouro", "early_exit_threshold 0.9", "not served",
                  "leaves the loop early", "cache leaves"):
        assert words in str(e.value).replace("\n", " "), words


def test_gguf_round_trip_of_the_keys_and_the_stack(tmp_path, models):
    from llama_fastapi_k8s_gpu_tpu.gguf import GGUFFile
    from llama_fastapi_k8s_gpu_tpu.testing import (
        TINY_OURO_CFG, write_tiny_ouro_gguf)

    params, cfg, _ = models("bf16")
    assert (cfg.ut_steps, cfg.sandwich_norm, cfg.exit_threshold,
            cfg.rope_neox, cfg.cache_kind) == (3, True, 1.0, True, "ring")
    assert cfg.cache_leaves == 6 and cfg.n_layers == 2
    assert {n: a.shape[0] for n, a in params["layers"].items()
            if not isinstance(a, dict)} == dict.fromkeys(
        ("attn_norm", "ffn_norm", "post_attn_norm", "post_ffn_norm"), 2)
    assert params["exit_gate"]["w"].shape == (cfg.dim,)
    assert params["exit_gate"]["b"].shape == ()
    # an absent threshold reads as 1.0
    path = str(tmp_path / "absent.gguf")
    write_tiny_ouro_gguf(path, exit_threshold=None)
    assert ModelConfig.from_gguf(GGUFFile(path)).exit_threshold == 1.0
    assert TINY_OURO_CFG.ut_steps == 3
