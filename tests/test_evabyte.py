"""The ``evabyte`` block (multi-head EVA attention over an exact blocked
window plus one summary per chunk of every earlier window, unit-offset
norms, a float32 residual, several prediction heads) against its plain
float32 reference (``benchmarks/reference_eva.py``), on the CPU at a tiny
size: 3 layers, hidden 128, 4 heads of 32, feed-forward 192, window W = 64,
chunk C = 4, 2 prediction heads, seeded random weights.  Logits (all
``vocab * heads`` rows), never tokens.

Limits, with their reasons and the controls that fail them:

- ``LIMIT`` 4 %: the program multiplies in bfloat16 and keeps keys, values
  and summaries bfloat16 in the cache; over the compared blocks it reads
  1.4-1.9 % of the logits' norm on the seeds tried, the reference itself
  with only its matmul and attention inputs rounded to bfloat16 1.3 %
  (must pass), rounded to float8 19 % (must fail).  Every CONTROL computes
  another function and reads far over it: the window alone (no summaries)
  about 100 %, the current window's finished chunks made visible 30 %, a
  sliding window of the last W keys in place of the blocked one 85 %, no
  ``mu`` 70 %, a decode step fed one slot late 60 % and more.  A bfloat16
  residual stream (the precision below the one the configuration states)
  is held to the same limit on what the layers ADD to a large stream,
  where it reads over 20 %: on the logits of three layers it is one more
  bfloat16 rounding among many (``test_the_residual_stream_is_float32``).
- ``SAME`` 1 %: two paths of the PROGRAM over the same weights and
  positions (one pass over a window against slices of it) differ by the
  rounding of bfloat16 keys read back from the cache alone.
- bitwise: a lane's logits under any bounds and neighbours.
- ``SUMMARY`` 2.5 %: the cache's summaries against the reference's carry
  the bfloat16 rounding of themselves and of the keys and values they
  pool, which past the first layer come from a bfloat16-multiplied
  stream (0.4 % in layer 0, up to 1.3 % in layer 2); another window's
  summaries in their place read over 100 %.
"""

from __future__ import annotations

import dataclasses
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")

LIMIT = 4e-2
SAME = 1e-2
SUMMARY = 2.5e-2
N_CTX = 320          # five windows of 64
SLICE = 16
N_PROMPT = 80        # crosses a window edge in prefill
N_SEQ = N_PROMPT + 3 * 64 + 10    # and three more while decoding


@pytest.fixture(scope="module")
def ref():
    sys.path.insert(0, BENCH)
    try:
        import reference_eva
        yield reference_eva
    finally:
        sys.path.remove(BENCH)


@pytest.fixture(scope="module")
def gguf_path(tmp_path_factory):
    from llama_fastapi_k8s_gpu_tpu.testing import write_tiny_evabyte_gguf

    path = str(tmp_path_factory.mktemp("evabyte") / "tiny.gguf")
    write_tiny_evabyte_gguf(path, seed=3)
    return path


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(5).integers(64, 320, size=N_SEQ)


@pytest.fixture(scope="module")
def model(ref, gguf_path):
    return ref.open_model(gguf_path)


@pytest.fixture(scope="module")
def want(ref, model, tokens):
    """(logits (S, V * heads), per layer (ktilde, beta)) of the reference."""
    logits, summ = ref.forward(*model, tokens, want_summaries=True)
    return np.asarray(logits), summ


@pytest.fixture(scope="module")
def loaded(gguf_path):
    from llama_fastapi_k8s_gpu_tpu.gguf import GGUFFile
    from llama_fastapi_k8s_gpu_tpu.models.config import ModelConfig
    from llama_fastapi_k8s_gpu_tpu.models.params import load_params

    gf = GGUFFile(gguf_path)
    cfg = ModelConfig.from_gguf(gf, n_ctx=N_CTX)
    return load_params(gf, cfg, fmt="bf16"), cfg


def rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def programs(cfg):
    """The calls the tests make of the program's ``forward`` under ``cfg``:
    a prefill pass, one decode step, one step of lanes (the body of
    ``parallel/batched.py``'s vmapped step, its bounds included).  One
    build a process for each configuration: a second caller gets the
    programs the first one compiled."""
    if cfg not in _PROGRAMS:
        _PROGRAMS[cfg] = _build_programs(cfg)
    return _PROGRAMS[cfg]


_PROGRAMS = {}


def _build_programs(cfg):
    import jax

    from llama_fastapi_k8s_gpu_tpu.models.llama import forward
    from llama_fastapi_k8s_gpu_tpu.parallel.batched import step_bound

    @jax.jit
    def pass_(params, tokens, off, cache):
        return forward(params, cfg, tokens, off, cache, return_all=True,
                       all_heads=True)

    @jax.jit
    def step(params, token, pos, cache):
        return forward(params, cfg, token[None], pos, cache, all_heads=True)

    @jax.jit
    def lane_step(params, tokens, poss, caches, live):
        bound = step_bound(cfg, poss, live)
        return jax.vmap(lambda t, p, c: forward(
            params, cfg, t[None], p, c, kv_bound=bound, all_heads=True))(
                tokens, poss, caches)
    return pass_, step, lane_step


def prefill(params, cfg, seq, n, size=SLICE, pass_=None):
    """Logits of positions [0, n) and the cache, in passes of ``size``."""
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.models.llama import init_cache

    pass_ = pass_ or programs(cfg)[0]
    cache, out = init_cache(cfg), []
    for off in range(0, n, size):
        part = np.zeros(size, np.int32)
        real = seq[off:min(off + size, n)]
        part[:len(real)] = real
        lg, cache = pass_(params, jnp.asarray(part), jnp.int32(off), cache)
        out.append(np.asarray(lg)[:len(real)])
    return np.concatenate(out), cache


@pytest.fixture(scope="module")
def served(loaded, tokens):
    """The serial programs over the whole sequence: (prefill logits,
    decode logits, the cache after the prompt, the cache at the end)."""
    import jax.numpy as jnp

    params, cfg = loaded
    pass_, step, _ = programs(cfg)
    pre, cache0 = prefill(params, cfg, tokens, N_PROMPT, pass_=pass_)
    cache, dec = cache0, []
    for t in range(N_PROMPT, N_SEQ):
        lg, cache = step(params, jnp.int32(tokens[t]), jnp.int32(t), cache)
        dec.append(np.asarray(lg))
    return pre, np.stack(dec), cache0, cache


# ---------------------------------------------------------------------------
# the program against the reference
# ---------------------------------------------------------------------------

def test_the_limit_lies_between_bfloat16_and_float8(ref, model, tokens, want):
    import jax.numpy as jnp

    assert rel(ref.forward(*model, tokens, emulate=jnp.bfloat16),
               want[0]) < LIMIT
    assert rel(ref.forward(*model, tokens, emulate=jnp.float8_e4m3fn),
               want[0]) > LIMIT


def test_prefill_alone(served, want):
    pre = served[0]
    assert pre.shape == (N_PROMPT, 2 * 320)     # every prediction head
    assert rel(pre[:64], want[0][:64]) < LIMIT          # window 0
    assert rel(pre[64:], want[0][64:N_PROMPT]) < LIMIT  # past the edge


def test_prefill_then_decode_across_window_edges(served, want):
    """3 W decode steps through the cache after a prompt that crossed an
    edge itself: every block of 16 steps, the 16 after each edge too."""
    dec = served[1]
    assert dec.shape[0] >= 3 * 64
    for a in range(N_PROMPT, N_SEQ - 15, 16):
        got = dec[a - N_PROMPT:a - N_PROMPT + 16]
        assert rel(got, want[0][a:a + 16]) < LIMIT, a
    for edge in (128, 192, 256):
        got = dec[edge - N_PROMPT:edge - N_PROMPT + 16]
        assert rel(got, want[0][edge:edge + 16]) < LIMIT, edge


@pytest.mark.parametrize("control", ["no_summaries", "own_window", "sliding",
                                     "no_mu"])
def test_another_attention_fails_the_limit(ref, model, tokens, served,
                                           control):
    """The program against the reference with one term of the attention
    changed: past the first edge every one reads far over the limit."""
    other = np.asarray(ref.forward(*model, tokens, **{control: True}))
    assert rel(served[1][64:], other[N_PROMPT + 64:]) > 3 * LIMIT
    assert rel(served[0][64:], other[64:N_PROMPT]) > LIMIT


def test_the_residual_stream_is_float32(ref, tmp_path, tokens):
    """``fp32_skip_add``: what the layers add to the residual stream is
    held to the reference where the stream is large beside it (the
    embedding table 1024 times the other weights' scale, so bfloat16's
    spacing at the stream's size is about the size of one layer's
    addition).  Compared: the stream after the last layer less the
    embedding, over a window of 64 positions.  The control is the
    precision below the stated one, a bfloat16 stream (what this program's
    dense files carry): it loses the additions and reads over 20 %.

    The LOGITS cannot tell the two apart at this depth (1.4 % against
    1.5 %; at 48 layers 3.0 % against 3.7 %): every other rounding of the
    program is bfloat16 too, and a limit with room on both sides does not
    exist for them.  ``benchmarks/compare_eva.py`` holds the same on the
    chip at the published depth, on a stream 4096 times the embedding's
    scale (0.3 % against 1.3 %)."""
    import jax
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.gguf import GGUFFile
    from llama_fastapi_k8s_gpu_tpu.models.config import ModelConfig
    from llama_fastapi_k8s_gpu_tpu.models.llama import _layer, init_cache
    from llama_fastapi_k8s_gpu_tpu.models.params import load_params
    from llama_fastapi_k8s_gpu_tpu.testing import write_tiny_evabyte_gguf

    path = str(tmp_path / "large_stream.gguf")
    write_tiny_evabyte_gguf(path, seed=3, embed_scale=1024.0)
    gf = GGUFFile(path)
    cfg = ModelConfig.from_gguf(gf, n_ctx=N_CTX)
    params = load_params(gf, cfg, fmt="bf16")
    hp, tensors = ref.open_model(path)
    seq = jnp.asarray(tokens[:64], jnp.int32)
    with jax.default_matmul_precision("highest"):
        x0 = jnp.asarray(ref.tensor(tensors, "token_embd.weight"))[seq]
        x = x0
        for i in range(hp["n_layers"]):
            x = ref.layer(hp, ref.layer_weights(tensors, i), x)
    added = np.asarray(x - x0)

    def stream(c):
        h = jnp.take(params["tok_emb"], seq, axis=0).astype(
            jnp.float32 if c.fp32_residual else jnp.bfloat16)
        h0, cache = h, init_cache(c)
        for i in range(c.n_layers):
            h, cache, _ = jax.jit(_layer, static_argnums=(7,))(
                h, params["layers"], jnp.int32(i), jnp.int32(i), cache,
                jnp.arange(64), jnp.int32(0), c)
        return np.asarray(h.astype(jnp.float32) - h0.astype(jnp.float32))

    assert rel(stream(cfg), added) < LIMIT
    assert rel(stream(dataclasses.replace(cfg, fp32_residual=False)),
               added) > 5 * LIMIT


def test_a_decode_step_one_slot_late_fails_the_limit(loaded, tokens, served,
                                                     want):
    import jax.numpy as jnp

    params, cfg = loaded
    _, step, _ = programs(cfg)
    cache, dec = served[2], []
    for t in range(N_PROMPT, N_PROMPT + 16):
        lg, cache = step(params, jnp.int32(tokens[t]), jnp.int32(t + 1), cache)
        dec.append(np.asarray(lg))
    assert rel(np.stack(dec), want[0][N_PROMPT:N_PROMPT + 16]) > 3 * LIMIT


def test_sliced_against_unsliced_prefill(loaded, tokens, want):
    """One pass over a whole window against slices of it, and the summaries
    either leaves behind."""
    params, cfg = loaded
    whole, c1 = prefill(params, cfg, tokens, 128, size=64)
    sliced, c2 = prefill(params, cfg, tokens, 128, size=SLICE)
    assert rel(sliced, whole) < SAME
    assert rel(whole, want[0][:128]) < LIMIT
    for name in ("sk", "sv"):
        a, b = np.asarray(c1[name], np.float32), np.asarray(c2[name], np.float32)
        assert rel(a[:, :, :32], b[:, :, :32]) < SAME


def test_window_close_against_the_reference_summaries(loaded, served, want):
    """Windows 0 (closed by a prefill slice) and 1-3 (closed mid-decode):
    the cache's summaries are the reference's, rounded to bfloat16; the
    slots of the window that no sequence of n_ctx can read stay empty."""
    _, cfg = loaded
    cache = served[3]
    G = cfg.eva_window // cfg.eva_chunk
    n = (N_SEQ // cfg.eva_window) * G
    assert n == 4 * G
    for layer, (ktilde, beta) in enumerate(want[1]):
        for name, ref_s in (("sk", ktilde), ("sv", beta)):
            got = np.asarray(cache[name][layer], np.float32)   # (H, NS, d)
            exp = np.asarray(ref_s)[:n].transpose(1, 0, 2)     # (H, n, d)
            for w in range(4):
                mine = got[:, w * G:(w + 1) * G]
                assert rel(mine, exp[:, w * G:(w + 1) * G]) < SUMMARY, \
                    (layer, w)
                other = (w + 1) % 4
                assert rel(mine, exp[:, other * G:(other + 1) * G]) > 0.5
    assert cache["sk"].shape[2] == 4 * G        # (ceil(320 / 64) - 1) * G


# ---------------------------------------------------------------------------
# lanes
# ---------------------------------------------------------------------------

def test_three_lanes_join_and_leave_one_dead_and_walking(loaded, tokens, want):
    """Lanes of one vmapped step under the bounds of the live lanes: lane 0
    (prompt 80) leaves after 70 steps and walks on, lane 1 (prompt 30)
    stays, lane 2 is dead at first, its position walking past n_ctx, and
    joins at step 40 with a prompt of 120.  Every live lane's logits are
    the reference's of the same sequence."""
    import jax
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.models.llama import init_cache

    params, cfg = loaded
    pass_, _, lane_step = programs(cfg)
    prompts = (80, 30, 120)
    caches = [prefill(params, cfg, tokens, n, pass_=pass_)[1] for n in prompts]
    garbage = jax.tree.map(lambda a: a + 1, init_cache(cfg))
    stacked = jax.tree.map(lambda *a: jnp.stack(a), caches[0], caches[1],
                           garbage)
    pos = [prompts[0], prompts[1], N_CTX - 3]
    live = [True, True, False]
    got = {0: [], 1: [], 2: []}
    for t in range(130):
        if t == 40:      # lane 2 joins: its prefilled cache is written in
            stacked = jax.tree.map(lambda a, c: a.at[2].set(c), stacked,
                                   caches[2])
            pos[2], live[2] = prompts[2], True
        if t == 70:      # lane 0 leaves; it keeps stepping
            live[0] = False
        toks = [tokens[p] if p < N_SEQ else 0 for p in pos]
        lg, stacked = lane_step(params, jnp.asarray(toks, jnp.int32),
                                jnp.asarray(pos, jnp.int32), stacked,
                                jnp.asarray(live))
        for lane in range(3):
            if live[lane]:
                got[lane].append((pos[lane], np.asarray(lg[lane])))
        pos = [p + 1 for p in pos]
    for lane, rows in got.items():
        at = [p for p, _ in rows]
        logits = np.stack([x for _, x in rows])
        assert len(at) >= 70
        for a in range(0, len(at) - 15, 16):
            assert rel(logits[a:a + 16], want[0][at[a]:at[a] + 16]) < LIMIT, \
                (lane, at[a])
    # lane 1 crossed two edges, lane 2 one, while the others read beside
    assert got[1][-1][0] >= 128 + 16 and got[2][-1][0] >= 192 + 16


def test_a_lanes_logits_do_not_depend_on_the_other_lanes(loaded, tokens):
    """Bitwise: the same lane with the same cache under other neighbours,
    other bounds (a neighbour deep in its fourth window, or none live),
    and at a step where the NEIGHBOUR closes a window."""
    import jax
    import jax.numpy as jnp

    params, cfg = loaded
    pass_, _, lane_step = programs(cfg)
    mine = prefill(params, cfg, tokens, 70, pass_=pass_)[1]
    near = prefill(params, cfg, tokens[5:], 20, pass_=pass_)[1]
    far = prefill(params, cfg, tokens[9:], 255, pass_=pass_)[1]

    def run(other, other_pos, other_live):
        stacked = jax.tree.map(lambda *a: jnp.stack(a), mine, other)
        out = []
        for t in range(3):
            lg, stacked = lane_step(
                params, jnp.asarray([tokens[70 + t], 7], jnp.int32),
                jnp.asarray([70 + t, other_pos + t], jnp.int32), stacked,
                jnp.asarray([True, other_live]))
            out.append(np.asarray(lg[0]))
        return np.stack(out), jax.tree.map(lambda a: np.asarray(a[0]), stacked)

    base, cache = run(near, 20, True)
    for other, other_pos, other_live in ((far, 255, True),   # closes at 255
                                         (far, 255, False), (near, 20, False)):
        got, c = run(other, other_pos, other_live)
        assert np.array_equal(got, base)
        for name in cache:
            assert np.array_equal(c[name], cache[name]), name


# ---------------------------------------------------------------------------
# the file, the loader, the refusals
# ---------------------------------------------------------------------------

def test_gguf_round_trip_of_the_new_keys_and_tensors(gguf_path, loaded):
    from llama_fastapi_k8s_gpu_tpu.gguf import GGUFFile
    from llama_fastapi_k8s_gpu_tpu.models.llama import cache_nbytes, init_cache
    from llama_fastapi_k8s_gpu_tpu.testing import TINY_EVABYTE_CFG

    gf = GGUFFile(gguf_path)
    assert gf.architecture == "evabyte"
    assert gf.hparam("attention.window_size") == 64
    assert gf.hparam("attention.chunk_size") == 4
    assert gf.hparam("prediction_heads") == 2
    assert gf.metadata["tokenizer.ggml.model"] == "bytes"
    for name in ("blk.0.attn_eva_phi.weight", "blk.2.attn_eva_mu.weight"):
        assert tuple(gf[name].shape) == (32, 4)      # ggml order: (hd, H)
        assert gf[name].ggml_type.name == "F32"
    params, cfg = loaded
    assert dataclasses.replace(cfg, n_ctx=TINY_EVABYTE_CFG.n_ctx,
                               rms_eps=TINY_EVABYTE_CFG.rms_eps) \
        == TINY_EVABYTE_CFG
    assert cfg.rope_neox and cfg.fp32_residual and cfg.n_pred_heads == 2
    assert params["layers"]["eva_phi"].shape == (3, 4, 32)
    assert params["output"]["w"].shape == (640, 128)
    cache = init_cache(cfg)
    assert {k: v.shape for k, v in cache.items()} == {
        "k": (3, 4, 64, 32), "v": (3, 4, 64, 32),
        "sk": (3, 4, 64, 32), "sv": (3, 4, 64, 32)}
    assert cache_nbytes(cfg) == sum(v.nbytes for v in cache.values())


def test_a_ring_file_loads_what_it_loaded(tmp_path):
    """A dense file's configuration, cache and parameter tree carry nothing
    of the other cache kind."""
    from llama_fastapi_k8s_gpu_tpu.gguf import GGUFFile
    from llama_fastapi_k8s_gpu_tpu.models.config import ModelConfig
    from llama_fastapi_k8s_gpu_tpu.models.llama import init_cache
    from llama_fastapi_k8s_gpu_tpu.models.params import load_params
    from llama_fastapi_k8s_gpu_tpu.testing import write_tiny_llama_gguf

    path = str(tmp_path / "dense.gguf")
    write_tiny_llama_gguf(path)
    gf = GGUFFile(path)
    cfg = ModelConfig.from_gguf(gf, n_ctx=64)
    assert (cfg.eva_window, cfg.n_pred_heads, cfg.fp32_residual) == (0, 1, False)
    assert sorted(init_cache(cfg)) == ["k", "v"]
    assert "eva_phi" not in load_params(gf, cfg)["layers"]


@pytest.mark.parametrize("kw,words", [
    (dict(kv_dtype="int8"), ("LFKT_KV_DTYPE=int8", "evabyte")),
    (dict(kv_paged=True), ("LFKT_KV_PAGED", "evabyte")),
    (dict(prefill_chunk=48), ("LFKT_PREFILL_CHUNK=48", "evabyte", "64")),
    (dict(prefill_chunk=2), ("LFKT_PREFILL_CHUNK=2", "evabyte", "chunk")),
])
def test_what_cannot_hold_the_cache_is_refused_by_name(gguf_path, kw, words):
    from llama_fastapi_k8s_gpu_tpu.engine import Engine

    kw = {"prefill_chunk": SLICE, **kw}
    with pytest.raises(ValueError) as e:
        Engine(gguf_path, n_ctx=N_CTX, **kw)
    assert all(w in str(e.value) for w in words), str(e.value)


def test_one_pass_holds_one_window_at_most(loaded, tokens):
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.models.llama import forward, init_cache

    params, cfg = loaded
    with pytest.raises(ValueError, match="evabyte"):
        forward(params, cfg, jnp.zeros(65, jnp.int32), jnp.int32(0),
                init_cache(cfg))


# ---------------------------------------------------------------------------
# the engines
# ---------------------------------------------------------------------------

MSG = [{"role": "system", "content": "be brief"},
       {"role": "user", "content": "héllo wörld 𝄞 " * 5}]


@pytest.fixture(scope="module")
def engine(gguf_path):
    from llama_fastapi_k8s_gpu_tpu.engine import Engine
    from llama_fastapi_k8s_gpu_tpu.obs.devtime import DEVTIME

    # the jit ledger is the process's: other files of this worker may have
    # compiled serial decode chunks before this engine existed
    earlier = DEVTIME.counters().get("decode_chunk", {}).get("compiles", 0)
    eng = Engine(gguf_path, n_ctx=N_CTX, prefill_chunk=SLICE, decode_chunk=4)
    eng.warmup()
    eng.decode_chunks_compiled_earlier = earlier
    return eng


def test_serial_engine_serves_and_counts(engine):
    """Prompt 100-odd bytes (one window closed in prefill), 100 decoded
    (another mid-decode): the counters are the host's arithmetic, the ring's
    stay 0, reuse finds nothing, and /health's block says why."""
    before = dict(engine.cache_counts)
    r = engine.create_chat_completion(MSG, max_tokens=100, temperature=0.0)
    n = r["usage"]["prompt_tokens"]
    assert 64 < n < 128 and r["usage"]["completion_tokens"] == 100
    d = {k: engine.cache_counts[k] - before[k] for k in before}
    steps = 100                       # 25 chunks of 4, the first token aside
    # window 0 by the prompt, then the steps that write 127 and 191
    assert d["windows_closed"] == 1 + sum(
        (n + t + 1) % 64 == 0 for t in range(steps)) == 3
    assert d["lane_steps"] == steps
    assert d["window_read"] == steps * 64          # one block of W a step
    assert d["window_live"] == sum((n + t) % 64 + 1 for t in range(steps))
    assert d["summaries_read"] == d["summaries_live"] \
        == sum((n + t) // 64 * 16 for t in range(steps))
    assert d["read"] == d["live"] == d["rows_written"] == 0
    assert engine._prefix_reuse_len(list(range(200)), 200, 256) == 0
    kind = engine.cache_kind
    assert kind["kind"] == "window+summaries" and kind["summaries"] == 64
    assert engine.cfg.attn_impl == "xla"
    # the same prompt again: nothing reused, the same bytes
    again = engine.create_chat_completion(MSG, max_tokens=100, temperature=0.0)
    assert again["choices"][0]["message"] == r["choices"][0]["message"]


def test_one_decode_program_before_and_after_a_window_closes(engine):
    """Compile pins: requests that close windows in prefill and mid-decode
    compile nothing after warm-up, and the decode chunk is one program."""
    from llama_fastapi_k8s_gpu_tpu.obs.devtime import DEVTIME

    def compiles():
        return {k: v["compiles"] for k, v in DEVTIME.counters().items()}

    before = compiles()
    engine.create_chat_completion(MSG, max_tokens=100, temperature=0.0)
    long = [{"role": "user", "content": "abcd " * 40}]      # 200-odd bytes
    engine.create_chat_completion(long, max_tokens=80, temperature=0.0)
    assert compiles() == before
    assert before["decode_chunk"] \
        - engine.decode_chunks_compiled_earlier == 1


def test_lane_engine_serves_and_frees_lanes(gguf_path):
    """Four requests over three lanes of the continuous engine (a lane is
    freed, walks, and is taken again); greedy text equals the serial
    engine's where no near-tie decides (not asserted: logits are held to
    the reference above); counters move, lane claims find nothing."""
    from llama_fastapi_k8s_gpu_tpu.engine import ContinuousEngine

    eng = ContinuousEngine(gguf_path, n_ctx=N_CTX, prefill_chunk=SLICE,
                           decode_chunk=4, batch_size=3)
    try:
        eng.warmup()
        futs = [eng.submit([{"role": "user", "content": "abc def " * k}],
                           max_tokens=90, temperature=0.0)
                for k in (3, 9, 14, 5)]
        out = [f.result(timeout=300) for f in futs]
        # (greedy on random weights may sample the end-of-text token)
        assert all(1 <= r["usage"]["completion_tokens"] <= 90 for r in out)
        assert sum(r["usage"]["completion_tokens"] for r in out) > 150
        c = eng.cache_counts
        assert c["windows_closed"] >= 3
        assert 0 < c["window_live"] <= c["window_read"]
        assert 0 < c["summaries_live"] <= c["summaries_read"]
        assert c["read"] == c["live"] == c["rows_written"] == 0
        assert eng._lane_prefix is False
        assert eng._find_lane_reuse(list(range(100)), 100) == (0, None)
    finally:
        eng.shutdown()


def test_chunk_counts_follow_the_live_lanes(loaded):
    from llama_fastapi_k8s_gpu_tpu.models import eva

    _, cfg = loaded
    # one sequence at 126: steps at 126, 127 (closes window 1), 128
    c = eva.chunk_counts([126], 3, cfg)
    assert c == {"lane_steps": 3, "window_read": 3 * 64,
                 "window_live": 63 + 64 + 1,
                 "summaries_read": (1 + 1 + 2) * 16,
                 "summaries_live": (1 + 1 + 2) * 16, "windows_closed": 1}
    # beside a lane in its fourth window it reads that lane's summaries too
    c = eva.chunk_counts([126], 1, cfg, live=[126, 200])
    assert (c["summaries_read"], c["summaries_live"]) == (3 * 16, 16)
    # the last window of n_ctx closes into nothing
    assert eva.chunk_counts([319], 1, cfg)["windows_closed"] == 0
    assert eva.windows_closed_by_prefill(320, cfg) == 4


@pytest.mark.parametrize("k,stored", [
    (11008, 12288), (1792, 2048), (4096, 4096), (14336, 14336),
    (2304, 2304), (256, 256)])
def test_a_k_the_tile_does_not_divide_is_filled_up_to_a_quarter(k, stored):
    from llama_fastapi_k8s_gpu_tpu.ops.linear import padded_k

    assert padded_k(k) == stored


@pytest.mark.parametrize("ffn_dim,down,limit", [
    (1792, "fused", 0.05), (2304, "int8", 0.10)])
def test_fused_and_fallback_widths_load_and_agree(tmp_path, ref, ffn_dim,
                                                  down, limit):
    """The benchmark file's type mix at widths that fuse (K = 2048) and a
    feed-forward width that the fused kernels' K tile does not divide.
    1792 = 2048 - 256, as 11008 = 6 x 2048 - 1280: ``ffn_down``'s last tile
    is filled up with zero blocks and the activations with zeros, so the
    file's own Q6_K blocks serve the fused kernel and the logits stay at
    the fused kernels' distance from the reference (5 %: read 2-3 %, as
    ``tests/test_dense_reference.py``'s q4k files).  2304 = 2048 + 256
    would be padded by three quarters: it takes the loader's int8 fallback
    (int8 weights AND activations per row: 10 %, read 3-5 %; the float8
    reference 19 %)."""
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.gguf import GGUFFile
    from llama_fastapi_k8s_gpu_tpu.models.config import ModelConfig
    from llama_fastapi_k8s_gpu_tpu.models.params import load_params
    from llama_fastapi_k8s_gpu_tpu.testing import (
        EVABYTE_Q4KM_MIX, TINY_EVABYTE_CFG, write_tiny_evabyte_gguf)

    cfg = dataclasses.replace(TINY_EVABYTE_CFG, dim=2048, n_heads=16,
                              n_kv_heads=16, ffn_dim=ffn_dim, n_layers=1,
                              n_ctx=128)
    path = str(tmp_path / "wide.gguf")
    write_tiny_evabyte_gguf(path, cfg, seed=1, mix=EVABYTE_Q4KM_MIX)
    gf = GGUFFile(path)
    cfg = ModelConfig.from_gguf(gf, n_ctx=128)
    params = load_params(gf, cfg, fmt="q4k")
    kinds = {name: sorted(leaf) for name, leaf in params["layers"].items()
             if isinstance(leaf, dict)}
    if down == "fused":
        planes = params["layers"]["w_down"]
        assert "q4" in planes or "q6p" in planes
        k_stored = planes["q6p"].shape[-1] if "q6p" in planes \
            else 2 * planes["q4"].shape[-1]
        assert k_stored == 2048
    else:
        assert kinds["w_down"] == ["q", "s"]             # int8 fallback
    assert "qs" in kinds["wq"] and "qs" in kinds["w_gate"]
    assert "q4" in kinds["wv"] or "q6p" in kinds["wv"]
    assert sorted(params["output"]) == ["w"]             # the heads stay bf16
    seq = np.random.default_rng(2).integers(64, 320, size=72)
    got, cache = prefill(params, cfg, seq, 64, size=8)
    step = programs(cfg)[1]
    lg, _ = step(params, jnp.int32(seq[64]), jnp.int32(64), cache)
    exp = np.asarray(ref.forward(*ref.open_model(path), seq[:65]))
    print("read", rel(got, exp[:64]), rel(np.asarray(lg), exp[64]))
    assert rel(got, exp[:64]) < limit
    assert rel(np.asarray(lg), exp[64]) < limit


# ---------------------------------------------------------------------------
# the byte tokenizer, and the served path
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tokenizer(gguf_path):
    from llama_fastapi_k8s_gpu_tpu.gguf import GGUFFile
    from llama_fastapi_k8s_gpu_tpu.tokenizer import (
        ByteTokenizer, tokenizer_from_gguf)

    tok = tokenizer_from_gguf(GGUFFile(gguf_path))
    assert isinstance(tok, ByteTokenizer) and tok.byte_offset == 64
    return tok


@pytest.mark.parametrize("text", [
    "abc def", " leading and trailing ", "tabs\tand\nnewlines\r\n",
    "héllo wörld", "日本語のテキスト", "𝄞 clef 🎼 and ▁ itself", "",
    "<0x41> is text, not a byte token", "[INST] <unused_5> [/INST]",
])
def test_byte_tokenizer_round_trips_utf8(tokenizer, text):
    ids = tokenizer.encode(text, add_bos=False)
    assert ids == [64 + b for b in text.encode("utf-8")]   # bytes + offset
    assert tokenizer.decode(ids) == text
    assert tokenizer.decode_bytes(ids) == text.encode("utf-8")


def test_a_word_and_its_space_are_four_tokens(tokenizer):
    assert tokenizer.encode("abc ", add_bos=False) == [161, 162, 163, 96]
    assert tokenizer.decode([96]) == " "            # a space comes back one
    ids = tokenizer.encode("abc def", add_bos=True)
    assert ids[0] == tokenizer.bos_id == 1 and len(ids) == 8
    # control tokens by name only where asked, and never in decoded text
    special = tokenizer.encode("<s>hi</s>", add_bos=False, parse_special=True)
    assert special == [1, 64 + ord("h"), 64 + ord("i"), 2]
    assert tokenizer.decode(special) == "hi"
    assert tokenizer.decode(special, skip_special=False) == "<s>hi</s>"
    assert len(tokenizer.encode("<s>", add_bos=False)) == 3
    assert tokenizer.stop_ids == {2}


def test_a_vocabulary_without_the_byte_block_is_refused():
    from llama_fastapi_k8s_gpu_tpu.testing import evabyte_vocab
    from llama_fastapi_k8s_gpu_tpu.tokenizer import ByteTokenizer

    tokens, types = evabyte_vocab()
    with pytest.raises(ValueError, match="256 byte tokens"):
        ByteTokenizer(tokens[:-1], types[:-1])
    swapped = list(tokens)
    swapped[64], swapped[65] = swapped[65], swapped[64]
    with pytest.raises(ValueError, match="in order"):
        ByteTokenizer(swapped, types)


def test_a_character_cut_by_a_chunk_edge_is_held_back(tokenizer):
    """The stream emitter over the byte reader: a 4-byte character whose
    bytes arrive in three decode chunks comes out once, whole, never as
    U+FFFD; the concatenation is the one-shot decode."""
    import types

    from llama_fastapi_k8s_gpu_tpu.engine.engine import Engine, _TextEmitter

    eng = types.SimpleNamespace(
        tokenizer=tokenizer, _find_stop_str=Engine._find_stop_str,
        _stop_prefix_holdback=Engine._stop_prefix_holdback,
        _decode_text=lambda ids: tokenizer.decode(ids, skip_special=True))
    text = "a𝄞é b"
    ids = tokenizer.encode(text, add_bos=False)
    em = _TextEmitter(eng, [])
    out = []
    for cut in (2, 3, 4, 6, len(ids)):     # inside 𝄞 twice, inside é once
        ready, hit = em.process(ids[:cut], live=True)
        assert not hit and "�" not in ready
        out.append(ready)
    assert out == ["a", "", "", "𝄞", "é b"]
    assert em.final(ids, "stop") == ("", "stop")


@pytest.mark.anyio
async def test_v1_chat_completions_streams_bytes(engine):
    """The served path on the serial engine: an SSE stream whose text is
    valid UTF-8 of the generated bytes, usage in bytes, and /health's
    ``engine.cache`` block with an empty degrade ledger."""
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
                "messages": MSG, "max_tokens": 24, "temperature": 0.0,
                "stream": True, "stream_options": {"include_usage": True}})
            assert r.status_code == 200
            events = [json.loads(ln[6:]) for ln in r.text.splitlines()
                      if ln.startswith("data: {")]
            usage = [e["usage"] for e in events if e.get("usage")][-1]
            rendered = engine.tokenizer.decode(engine.tokenize_messages(MSG))
            assert usage["prompt_tokens"] == 1 + len(rendered.encode())
            assert 1 <= usage["completion_tokens"] <= 24
            text = "".join(c["delta"].get("content", "") for e in events
                           for c in e.get("choices", []))
            text.encode("utf-8")
            h = (await client.get("/health")).json()
            assert h["engine"]["cache"]["kind"] == "window+summaries"
            assert h["engine"]["attn_impl"] == "xla"
            d = (await client.get("/debug/compiles")).json()
            assert not d.get("degrades")
            m = (await client.get("/metrics")).text
            assert "eva_window_slots_read_total" in m
            assert "eva_lane_steps_total" in m
        await app.router.shutdown()
