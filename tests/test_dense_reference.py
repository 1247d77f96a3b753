"""The dense block, every way the program runs it, held to the benchmark's
plain float32 reference (``benchmarks/reference.py``: its own GGUF reader
and dequantizers, no kernels, no cache).  Logits, never tokens: on seeded
random weights an argmax sits on near-ties that any rounding flips.

What varies is what a deployment chooses: the weight format (``bf16``,
``int8`` per-row requants, ``q4k``: the file's own K-quant blocks through
the fused kernels, on a file wide enough that every matrix fuses), the KV
ring's dtype (``bf16``, ``int8`` with per-head per-token scales) and the
head layout (GQA, MHA); the serial engine's programs and the lane engine's
step; a prompt prefilled whole, in slices, or as the suffix of a prefix that
is already in a ring.

The limits are relative errors of the logits' norm.  Each was measured here,
over every case below (the worst of a case's 65 positions; weight seed 5),
and is written with what was read:

- ``LIMIT["bf16"]`` 3 %: bf16 products and bf16 activations between the
  layers (tests/test_olmoe.py and test_decode_lanes.py hold the same
  limit); read 1.0-1.7 %.
- ``LIMIT["int8"]`` 7 %: per-row int8 weights times per-row int8
  activations; read 4.2-4.9 %.
- ``LIMIT["q4k"]`` 5 %: the fused kernels keep each sub-block's ``d * sc``
  and ``dmin * m`` in bf16 and multiply in bf16; read 2.0-3.0 %.
- ``KV_INT8`` adds 1.5 %: keys and values rounded to 8 bits per (head,
  token); read up to 0.9 % on top of the weights' own (bf16 1.5 -> 2.4 %,
  int8 4.9 -> 5.3 %, q4k 3.0 -> 3.2 %).

Each limit is shown to mean something by a control at the end of the file:
a program broken in one way lands outside TWICE the limit (read: int8 scales
as ones 132 %, Q4_K minimums dropped 137 %, the other RoPE pairing 75 %,
decode one slot late 23 %).
"""

from __future__ import annotations

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llama_fastapi_k8s_gpu_tpu.models import llama
from llama_fastapi_k8s_gpu_tpu.models.config import ModelConfig
from llama_fastapi_k8s_gpu_tpu.parallel.batched import live_bound

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
BLOCK, N_CTX = 16, 128

LIMIT = {"bf16": 3e-2, "int8": 7e-2, "q4k": 5e-2}
KV_INT8 = 1.5e-2

WEIGHTS = ("bf16", "int8", "q4k")
KVS = ("bf16", "int8")
#: (dim, ffn_dim, heads, KV heads of GQA and of MHA).  The fused kernels
#: take a matrix whose K is a multiple of 2048 (ops/pallas/qmatmul.py
#: ``TK``), so the ``q4k`` file is that wide and every matrix of it fuses;
#: a narrower one would load as int8 requants and test ``int8`` twice.
NARROW = (256, 512, 4, {"gqa": 2, "mha": 4})
WIDE = (2048, 2048, 16, {"gqa": 8, "mha": 16})


def limit(weights: str, kv: str) -> float:
    return LIMIT[weights] + (KV_INT8 if kv == "int8" else 0.0)


def rel(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    """The decode read's block shrunk to 16 slots, so that a ring of 128
    holds eight and 64 steps cross four edges."""
    monkeypatch.setattr(llama, "DECODE_KV_BLOCK", BLOCK)


@pytest.fixture(scope="module")
def reference():
    """``benchmarks/`` is not a package: its files import each other by
    bare name."""
    sys.path.insert(0, BENCH)
    try:
        import reference
        yield reference
    finally:
        sys.path.remove(BENCH)


def write_dense_gguf(path, dim, ffn, n_heads, n_kv_heads, quant, seed=5):
    """A 2-layer ``llama`` file of seeded random weights whose attention
    looks somewhere: norm gains near one (not ``write_tiny_llama_gguf``'s,
    near zero, under which every score is ~0, attention is uniform and no
    position, rotation or slot can show in the logits), Q/K rows 1.5 times
    the others', so that scores spread by about two, and embeddings of
    unit variance, so that the residual stream carries the token as a
    trained model's does and is not all attention output."""
    from llama_fastapi_k8s_gpu_tpu.gguf import GGMLType, GGUFWriter
    from llama_fastapi_k8s_gpu_tpu.testing import (
        byte_vocab_with_specials,
        write_llama_gguf_meta,
    )

    tokens, types = byte_vocab_with_specials()
    cfg = ModelConfig(vocab_size=len(tokens), dim=dim, n_layers=2,
                      n_heads=n_heads, n_kv_heads=n_kv_heads, ffn_dim=ffn,
                      n_ctx=N_CTX, rope_theta=10000.0)
    rng = np.random.default_rng(seed)
    w = GGUFWriter(path)
    write_llama_gguf_meta(w, cfg, tokens, types)
    kv_dim = n_kv_heads * cfg.head_dim

    def t(name, shape, gtype, mul=1.0):
        w.add_tensor(name, rng.standard_normal(shape).astype(np.float32)
                     * dim ** -0.5 * mul, gtype)

    def norm(name):
        w.add_tensor(name, 1.0 + 0.1 * rng.standard_normal(dim).astype(
            np.float32), GGMLType.F32)

    t("token_embd.weight", (cfg.vocab_size, dim), GGMLType.F16, dim ** 0.5)
    for i in range(cfg.n_layers):
        p = f"blk.{i}."
        norm(p + "attn_norm.weight")
        t(p + "attn_q.weight", (dim, dim), quant, 1.5)
        t(p + "attn_k.weight", (kv_dim, dim), quant, 1.5)
        t(p + "attn_v.weight", (kv_dim, dim), quant)
        t(p + "attn_output.weight", (dim, dim), quant)
        norm(p + "ffn_norm.weight")
        t(p + "ffn_gate.weight", (ffn, dim), quant)
        t(p + "ffn_up.weight", (ffn, dim), quant)
        t(p + "ffn_down.weight", (dim, ffn), quant)
    norm("output_norm.weight")
    t("output.weight", (cfg.vocab_size, dim), GGMLType.F16)
    w.write()


@pytest.fixture(scope="module")
def files(tmp_path_factory, reference):
    """``files(weights, heads) -> (path, logits)``: one tiny GGUF file per
    (file quantization, head layout), 2 layers of ``NARROW`` or ``WIDE``,
    written once; ``logits(tokens)`` is the reference's answer for the whole
    sequence, kept per sequence."""
    from llama_fastapi_k8s_gpu_tpu.gguf import GGMLType

    root = tmp_path_factory.mktemp("dense")
    made: dict = {}

    def get(weights: str, heads: str):
        quant = GGMLType.Q4_K if weights == "q4k" else GGMLType.Q8_0
        key = (quant, heads)
        if key not in made:
            path = str(root / f"{quant.name}-{heads}.gguf")
            dim, ffn, n_heads, kv_heads = WIDE if weights == "q4k" else NARROW
            write_dense_gguf(path, dim, ffn, n_heads, kv_heads[heads], quant)
            hp, w = reference.load_weights(path)
            seen: dict = {}

            def logits(tokens, hp=hp, w=w, seen=seen):
                seq = tuple(int(t) for t in tokens)
                if seq not in seen:
                    seen[seq] = np.asarray(reference.forward(hp, w, tokens))
                return seen[seq]

            made[key] = (path, logits)
        return made[key]

    return get


@pytest.fixture(scope="module")
def models(files):
    """``models(weights, kv, heads) -> (params, cfg, logits)``, loaded
    once each."""
    from llama_fastapi_k8s_gpu_tpu.gguf import GGUFFile
    from llama_fastapi_k8s_gpu_tpu.models.params import load_params

    loaded: dict = {}

    def get(weights: str, kv: str, heads: str):
        path, logits = files(weights, heads)
        if (weights, heads) not in loaded:
            gf = GGUFFile(path)
            cfg = ModelConfig.from_gguf(gf, n_ctx=N_CTX)
            loaded[weights, heads] = (load_params(gf, cfg, fmt=weights), cfg)
        params, cfg = loaded[weights, heads]
        return params, dataclasses.replace(cfg, kv_dtype=kv), logits

    return get


def matrix(fn):
    for name, values in (("heads", ("gqa", "mha")), ("kv", KVS),
                         ("weights", WEIGHTS)):
        fn = pytest.mark.parametrize(name, values)(fn)
    return fn


_PREFILLS: dict = {}


def prefill(params, cfg, tokens, n):
    """A padded bucket of 32 through ``forward`` as ONE program: one build
    a process for each configuration, read block and ``llama._layer`` (a
    control that puts another layer in its place gets a program of its
    own); the weights and the real length are operands."""
    padded = np.zeros(32, np.int32)
    padded[:n] = tokens[:n]
    key = (cfg, llama._layer, llama.DECODE_KV_BLOCK)
    if key not in _PREFILLS:
        _PREFILLS[key] = jax.jit(lambda params, padded, last: llama.forward(
            params, cfg, padded, jnp.int32(0), llama.init_cache(cfg),
            last_idx=last))
    return _PREFILLS[key](params, jnp.asarray(padded), jnp.int32(n - 1))


def stale_ring(cfg, seed):
    """A ring with EVERY slot of every leaf filled, as a freed lane's is:
    what lies past a position must not matter."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 8))

    def fill(a):
        if a.dtype == jnp.int8:
            return jax.random.randint(next(keys), a.shape, -127, 128,
                                      jnp.int32).astype(jnp.int8)
        if a.dtype == jnp.float32:      # the scales of an int8 ring
            return jax.random.uniform(next(keys), a.shape, jnp.float32,
                                      0.005, 0.05)
        return jax.random.normal(next(keys), a.shape, a.dtype)

    return jax.tree.map(fill, llama.init_cache(cfg))


def lane_step(params, cfg):
    """The lane program's step (parallel/batched.py ``one_step``), with the
    logits kept: ``vmap`` of ``forward`` over per-lane rings under ONE
    bound, the largest live position."""
    @jax.jit
    def step(toks, poss, caches, live):
        bound = live_bound(poss, live)

        def lane(t, p, c, lv):
            logits, cache = llama.forward(params, cfg, t[None], p, c,
                                          live=lv, kv_bound=bound)
            return logits, cache

        return jax.vmap(lane)(toks, poss, caches, live)

    return step


# ---------------------------------------------------------------------------
# prefill + 64 decode steps through the ring
# ---------------------------------------------------------------------------

@matrix
def test_serial_prefill_then_64_decode_steps_agree_with_the_reference(
        models, weights, kv, heads):
    """The serial engine's two programs: a padded bucket prefill of 20
    tokens, then positions 20..83 one at a time against the ring, across
    the block edges at 32, 48, 64 and 80."""
    params, cfg, reference_logits = models(weights, kv, heads)
    tokens = np.random.default_rng(1).integers(0, 256, size=84)
    want = reference_logits(tokens)
    logits, cache = prefill(params, cfg, tokens, 20)
    errs = [rel(logits, want[19])]
    step = jax.jit(lambda t, p, c: llama.decode_step(params, cfg, t, p, c))
    for pos in range(20, 84):
        logits, cache = step(jnp.int32(tokens[pos]), jnp.int32(pos), cache)
        errs.append(rel(logits, want[pos]))
    assert max(errs) < limit(weights, kv), (np.argmax(errs), max(errs))


@matrix
def test_lanes_of_different_lengths_join_and_leave_over_64_steps(
        models, weights, kv, heads):
    """The lane engine's step over three lanes for 64 steps.  Lane 0 (from
    position 10) leaves after step 40 and, as a freed lane does, keeps
    stepping with its position walking on; lane 1 (from 30) stays; lane 2
    holds a dead request's stale ring at position 100 until a new request
    of 5 tokens joins it at step 8.  Every live lane's logits are the
    reference's at its own position, at every step."""
    params, cfg, reference_logits = models(weights, kv, heads)
    rng = np.random.default_rng(9)
    starts, steps = (10, 30, 5), 64
    leave_0, join_2 = 40, 8
    seqs = [rng.integers(0, 256, size=s + steps) for s in starts]
    fresh = [prefill(params, cfg, s, n)[1] for s, n in zip(seqs, starts)]
    caches = jax.tree.map(lambda *a: jnp.stack(a), fresh[0], fresh[1],
                          stale_ring(cfg, seed=99))
    step = lane_step(params, cfg)

    pos = [starts[0], starts[1], 100]
    got = {lane: [] for lane in range(3)}
    for t in range(steps):
        if t == join_2:             # the admission's lane write
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
            pos[lane] += 1          # every lane steps, live or not
    assert [len(got[lane]) for lane in range(3)] == [41, 64, 56]
    for lane, n in enumerate(starts):
        want = reference_logits(seqs[lane])[n:n + len(got[lane])]
        errs = [rel(a, b) for a, b in zip(got[lane], want)]
        assert max(errs) < limit(weights, kv), (lane, np.argmax(errs))


# ---------------------------------------------------------------------------
# the engines' own prefill paths: sliced, and after a reused prefix
# ---------------------------------------------------------------------------

CHUNK = 32      # the prefill slice; buckets 32 / 64 / 128


@pytest.fixture
def watched(monkeypatch):
    """What the engines hand to the sampler and where their slices start:
    ``sample_jit`` and ``prefill_chunk_jit``, as both engine modules call
    them, wrapped to record the first-token logits and each slice's
    offset.  The served path returns no logits, so this is where a test
    reads them."""
    from llama_fastapi_k8s_gpu_tpu.engine import continuous, engine

    seen = {"logits": [], "offsets": []}

    def sample(logits, *a, **kw):
        seen["logits"].append(np.asarray(logits))
        return engine_sample(logits, *a, **kw)

    def chunk(params, cfg, tokens, off, *a, **kw):
        seen["offsets"].append(int(off))
        return engine_chunk(params, cfg, tokens, off, *a, **kw)

    engine_sample, engine_chunk = engine.sample_jit, engine.prefill_chunk_jit
    for module in (engine, continuous):
        monkeypatch.setattr(module, "sample_jit", sample)
        monkeypatch.setattr(module, "prefill_chunk_jit", chunk)
    return seen


@pytest.fixture(scope="module")
def engines(files):
    """``engines(kind, weights, kv, **kw)``: a serial ``Engine`` or a
    2-lane ``ContinuousEngine`` on the matrix's files, built once each and
    shut down with the module."""
    from llama_fastapi_k8s_gpu_tpu.engine import ContinuousEngine, Engine

    built: dict = {}

    def get(kind: str, weights: str, kv: str, **kw):
        key = (kind, weights, kv, tuple(sorted(kw.items())))
        if key not in built:
            common = dict(n_ctx=N_CTX, weight_format=weights, kv_dtype=kv,
                          decode_chunk=4, max_gen_tokens=8,
                          prefill_buckets=(32, 64, 128), prefill_chunk=CHUNK,
                          **kw)
            path = files(weights, "gqa")[0]
            built[key] = ContinuousEngine(path, batch_size=2, **common) \
                if kind == "lanes" else Engine(path, **common)
        return built[key]

    yield get
    for eng in built.values():
        if hasattr(eng, "shutdown"):
            eng.shutdown()


def chat_of(eng, n: int, seed: int, shared: str = "") -> tuple[list, list]:
    """(messages, token ids) of a one-turn chat that tokenizes to exactly
    ``n`` tokens: lower-case letters after ``shared``, one token a byte."""
    letters = "".join(np.random.default_rng(seed).choice(
        list("abcdefghijklmnopqrstuvwxyz"), size=n))

    def chat(k):
        return [{"role": "user", "content": shared + letters[:k]}]

    k = n - len(eng.tokenize_messages(chat(0)))
    ids = eng.tokenize_messages(chat(k))
    assert k > 0 and len(ids) == n, (k, len(ids))
    return chat(k), ids


def greedy(eng, messages):
    return eng.create_chat_completion(messages, temperature=0.0, max_tokens=2)


@pytest.mark.parametrize("kv", KVS)
@pytest.mark.parametrize("weights", ["bf16", "q4k"])
@pytest.mark.parametrize("slices", [2, 3])
@pytest.mark.parametrize("kind", ["serial", "lanes"])
def test_sliced_prefill_agrees_with_the_reference(
        engines, files, watched, kind, slices, weights, kv):
    """A prompt of 50 (2 slices) or 90 tokens (3) through the serial
    engine's overlapped slices (``Engine._prefill_padded``) and the lane
    engine's chunked admission (``_dispatch_prefill_chunk``): the logits
    the engine samples its first token from are the reference's at the
    prompt's last position.  They depend on every earlier slice's K/V in
    the ring: a slice written at another offset is the off-by-one control's
    fault."""
    eng = engines(kind, weights, kv)
    messages, ids = chat_of(eng, {2: 50, 3: 90}[slices], seed=slices)
    greedy(eng, messages)
    assert watched["offsets"] == [0, CHUNK, 2 * CHUNK][:slices]
    want = files(weights, "gqa")[1](ids)[len(ids) - 1]
    err = rel(watched["logits"][0], want)
    assert err < limit(weights, kv)


@pytest.mark.parametrize("kv", KVS)
@pytest.mark.parametrize("path", ["serial_ring", "lane_claim", "paged_pool"])
def test_a_reused_prefix_then_the_suffix_agrees_with_the_reference(
        engines, files, watched, path, kv):
    """A second chat that shares its first 68 tokens with the one
    before it prefills only its suffix, against K/V that is already there:
    the serial ring's own claim (``Engine._prefix_reuse_len``), a freed
    lane's claim copied into the scratch ring (``_find_lane_reuse``), and
    pages of the pool restored into the ring (``_paged_reuse``).  The first
    slice starts past 0, and the first token's logits are the reference's
    for the WHOLE second prompt."""
    kind, kw = {"serial_ring": ("serial", {}), "lane_claim": ("lanes", {}),
                "paged_pool": ("serial", {"kv_paged": True,
                                          "kv_page_tokens": 16})}[path]
    eng = engines(kind, "bf16", kv, **kw)
    shared = "".join(np.random.default_rng(3).choice(list("abcdefgh"),
                                                     size=45))
    first, _ = chat_of(eng, 75, seed=11, shared=shared)
    second, ids = chat_of(eng, 90, seed=12, shared=shared)
    greedy(eng, first)
    del watched["logits"][:], watched["offsets"][:]
    greedy(eng, second)
    assert watched["offsets"][0] >= CHUNK, watched["offsets"]
    want = files("bf16", "gqa")[1](ids)[len(ids) - 1]
    err = rel(watched["logits"][0], want)
    assert err < limit("bf16", kv)


# ---------------------------------------------------------------------------
# controls: each limit fails a program that is broken in one way
# ---------------------------------------------------------------------------

def scales_are_ones(params, cfg, cache):
    """The int8 ring read as if its scales were 1: the integers alone."""
    return params, cfg, {n: jnp.ones_like(a) if n.endswith("_s") else a
                         for n, a in cache.items()}, 0


def minimums_dropped(params, cfg, cache):
    """Q4_K's ``w = d * sc * q - dmin * m`` without its second term: lanes
    64.. of the kernels' ``sm`` plane hold ``dmin * m`` (qmatmul.py)."""
    layers = {n: {**w, "sm": w["sm"].at[..., 64:].set(0)}
              if isinstance(w, dict) and "sm" in w else w
              for n, w in params["layers"].items()}
    return {**params, "layers": layers}, cfg, cache, 0


def other_rope_pairing(params, cfg, cache):
    """Rotate-half on a file whose Q/K rows are permuted for interleaved
    pairs (a ``llama`` file)."""
    return params, dataclasses.replace(cfg, rope_neox=True), cache, 0


def position_off_by_one(params, cfg, cache):
    """Every decode step one slot late: a hole at slot 20, and every
    rotation one step off against the prompt's."""
    return params, cfg, cache, 1


@pytest.mark.parametrize("fault,weights,kv", [
    (scales_are_ones, "bf16", "int8"),
    (minimums_dropped, "q4k", "bf16"),
    (other_rope_pairing, "bf16", "bf16"),
    (position_off_by_one, "int8", "bf16"),
], ids=lambda v: getattr(v, "__name__", v))
def test_each_limit_fails_a_broken_program(models, fault, weights, kv):
    """The serial test's run again with one fault put in AFTER the prompt's
    prefill (so the prompt's K/V is sound and only the decode steps are
    broken): the mean error over the 64 steps is outside twice the limit
    that the sound program's WORST step is inside.  One control per limit:
    ``KV_INT8``, ``LIMIT["q4k"]``, ``LIMIT["bf16"]``, ``LIMIT["int8"]``."""
    params, cfg, reference_logits = models(weights, kv, "gqa")
    tokens = np.random.default_rng(1).integers(0, 256, size=84)
    want = reference_logits(tokens)
    _, cache = prefill(params, cfg, tokens, 20)
    params, cfg, cache, shift = fault(params, cfg, cache)
    step = jax.jit(lambda t, p, c: llama.decode_step(params, cfg, t, p, c))
    errs = []
    for pos in range(20, 84):
        logits, cache = step(jnp.int32(tokens[pos]), jnp.int32(pos + shift),
                             cache)
        errs.append(rel(logits, want[pos]))
    assert np.mean(errs) > 2 * limit(weights, kv)
