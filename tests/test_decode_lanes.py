"""A lane's decode step does not depend on what the other lanes hold, and
the bounded read of the ring (models/llama.py ``decode_attention``) stays
the model: logits against the whole-ring read it replaced, and the routed
block's prefill + 64 decode steps through the cache against the benchmark's
plain float32 reference (``benchmarks/reference_routed.py``; the dense
block's matrix is tests/test_dense_reference.py).

Why the independence is a property and not luck: lanes ``vmap``ped over
one step share the loop's trip count (``parallel/batched.py live_bound``:
the largest LIVE position), so a short lane also runs the blocks a longer
neighbour needs.  Such a block lies wholly beyond the short lane's
position: every score is -inf, the running max is unchanged, ``exp(m -
m_new)`` is exactly 1.0 and every probability exactly 0.0, so sum and
accumulator come out bit for bit as they went in.  The block that holds
the lane's own position is read under the lane's own mask whatever the
bound.  Hence BITWISE equality below, not a tolerance.

The block is shrunk to 16 slots (``DECODE_KV_BLOCK``, read at trace time)
so that a ring of 128 slots holds eight blocks.
"""

from __future__ import annotations

import dataclasses
import functools
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llama_fastapi_k8s_gpu_tpu.models import llama
from llama_fastapi_k8s_gpu_tpu.models.config import ModelConfig
from llama_fastapi_k8s_gpu_tpu.models.params import synth_params
from llama_fastapi_k8s_gpu_tpu.parallel.batched import live_bound, step_bound

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
BLOCK, N_CTX = 16, 128

# The program multiplies in bfloat16 and keeps activations in bfloat16
# between layers; over two layers that reaches about 1 % of the logits'
# norm against a float32 reference (benchmarks/tests/test_reference.py and
# tests/test_olmoe.py hold the same limit and show that a missing term or a
# lower precision lands far outside it).
REFERENCE = 3e-2


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    monkeypatch.setattr(llama, "DECODE_KV_BLOCK", BLOCK)
    monkeypatch.setattr(llama, "DECODE_KERNEL_BLOCK", (BLOCK, BLOCK, 2048))


def _cfg(heads=(4, 2), window=0, kv_dtype="bf16", attn_impl="xla"):
    """``attn_impl="pallas"``: the decode kernel serves the ring (interpret
    mode here), one bound a lane; ``xla``: the loop under one bound."""
    return ModelConfig(vocab_size=64, dim=16 * heads[0], n_layers=3,
                       n_heads=heads[0], n_kv_heads=heads[1], ffn_dim=96,
                       n_ctx=N_CTX, kv_dtype=kv_dtype, sliding_window=window,
                       attn_impl=attn_impl)


def _random_cache(cfg, seed):
    """A ring with EVERY slot filled: what lies past a position, or in
    another lane, must not matter."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    shape = (cfg.n_layers, cfg.n_kv_heads, cfg.n_ctx, cfg.head_dim)
    return {"k": jax.random.normal(ks[0], shape, jnp.bfloat16),
            "v": jax.random.normal(ks[1], shape, jnp.bfloat16)}


def whole_ring(q, cache, i, pos, bound, cfg, out_dtype):
    """``xla_attention`` over layer ``i``'s whole ring, under
    ``decode_attention``'s signature: the S = 1 read this PR replaced."""
    at = {n: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False)
          for n, a in cache.items()}
    return llama.xla_attention(q, at["k"], at["v"], None, None,
                               jnp.asarray(pos, jnp.int32)[None], cfg,
                               out_dtype)


def bits(a):
    return np.asarray(a, np.float32).view(np.uint32)


def rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


# ---------------------------------------------------------------------------
# logits: the bounded read against the whole-ring read
# ---------------------------------------------------------------------------

def one_step_off(q, cache, i, pos, bound, cfg, out_dtype):
    out = whole_ring(q, cache, i, pos, bound, cfg, jnp.float32)
    return (out * (1 + 2.0 ** -8)).astype(out_dtype)


def a_slot_short(q, cache, i, pos, bound, cfg, out_dtype):
    return whole_ring(q, cache, i, jnp.maximum(pos - 1, 0), bound, cfg,
                      out_dtype)


@functools.cache
def _step_reading(cfg, read):
    """``decode_step``'s logits as ONE program of ``cfg`` whose ring is read
    by ``read`` (``llama.decode_attention`` while the program is traced;
    None: the program's own).  One build a process: the position is an
    operand, so every case of a (configuration, read) runs the program the
    first case compiled."""
    return jax.jit(lambda params, token, pos, cache: llama.decode_step(
        params, cfg, token, pos, cache)[0])


def step_logits(monkeypatch, params, cfg, pos, cache, read=None):
    with monkeypatch.context() as patch:
        if read is not None:
            patch.setattr(llama, "decode_attention", read)
        return np.asarray(_step_reading(cfg, read)(
            params, jnp.int32(7), jnp.int32(pos), cache), np.float32)


@pytest.mark.parametrize("pos", [0, BLOCK - 1, BLOCK, BLOCK + 1, N_CTX - 1])
@pytest.mark.parametrize("heads", [(4, 2), (4, 4)], ids=["gqa", "mha"])
@pytest.mark.parametrize("window", [0, 24], ids=["causal", "window24"])
def test_logits_match_the_whole_ring_read(monkeypatch, window, heads, pos):
    """One decode step through the whole stack at the block's edges, every
    layer's ring random.  The two reads differ by the order of float32 sums
    (1e-6 relative in the float32 state) and by where a probability is
    rounded to bf16: after the cast, at most one bf16 step (2^-8 relative)
    of an attention output.  What one such step is worth in THIS model's
    logits is measured beside it: the whole-ring read with its output
    scaled by 1 + 2^-8 moves them by 0.8-2.1 % of their norm over these
    cases, the bounded read by 0-2.3 %.  The limit is the program's own
    distance from a float32 reference (``REFERENCE``, 3 %): a read that
    dropped or added one live slot of 17 is tens of per cent off."""
    cfg = _cfg(heads, window)
    params = synth_params(cfg, seed=2)
    cache = _random_cache(cfg, seed=pos)

    def logits(read=None):
        return step_logits(monkeypatch, params, cfg, pos, cache, read)

    got = logits()
    want = logits(whole_ring)
    assert got.shape == want.shape == (cfg.vocab_size,)
    assert np.isfinite(got).all()
    assert rel(got, want) < REFERENCE
    assert rel(got, want) < 3 * rel(logits(one_step_off), want) + 1e-6
    if pos:
        assert rel(logits(a_slot_short), want) > 2 * REFERENCE


@pytest.mark.parametrize("pos", [0, BLOCK - 1, BLOCK, BLOCK + 1, 70,
                                 N_CTX - 1])
@pytest.mark.parametrize("heads", [(4, 2), (4, 4)], ids=["gqa", "mha"])
@pytest.mark.parametrize("window", [0, 24], ids=["causal", "window24"])
def test_the_kernels_logits_match_the_whole_ring_read(monkeypatch, window,
                                                      heads, pos):
    """The same step with the decode kernel serving the ring
    (``attn_impl="pallas"``): held to the whole-ring read by the measure
    the loop is held to above (three bf16 steps of the attention output's
    worth in these logits; mid-ring, at 70, the loop itself reads 3.2 %),
    and to the loop by one such step (in interpret mode they are equal)."""
    cfg = _cfg(heads, window)
    params = synth_params(cfg, seed=2)
    cache = _random_cache(cfg, seed=pos)

    def logits(cfg, read=None):
        return step_logits(monkeypatch, params, cfg, pos, cache, read)

    got = logits(dataclasses.replace(cfg, attn_impl="pallas"))
    loop = logits(cfg)
    want = logits(cfg, whole_ring)
    step = rel(logits(cfg, one_step_off), want)
    assert np.isfinite(got).all()
    assert rel(got, want) < 3 * step + 1e-6
    assert rel(got, loop) < step + 1e-6


# ---------------------------------------------------------------------------
# a lane's logits are bitwise the same whatever the other lanes hold
# ---------------------------------------------------------------------------

def _lane_step(params, cfg):
    """The lane program's step (parallel/batched.py ``one_step``), with the
    logits kept: ``vmap`` of ``forward`` over per-lane rings under what
    ``step_bound`` hands it: ONE bound, the largest live position, or
    none where the kernel bounds each lane by itself."""
    @jax.jit
    def step(toks, poss, caches, live):
        bound = step_bound(cfg, poss, live)
        return jax.vmap(lambda t, p, c, lv: forward_lane(t, p, c, lv, bound))(
            toks, poss, caches, live)

    def forward_lane(t, p, c, lv, bound):
        logits, cache, *_ = llama.forward(params, cfg, t[None], p, c, live=lv,
                                          kv_bound=bound)
        return logits, cache

    return step


@functools.cache
def _lanes_program(cfg):
    """``_lane_step`` of ``cfg`` on the weights of seed 1: one build a
    process, whatever position and neighbours a case gives the lanes."""
    return _lane_step(synth_params(cfg, seed=1), cfg)


OTHERS = {
    # the other three lanes: (live, positions, rings)
    "empty": (False, (0, 0, 0), "zeros"),
    "full": (True, (N_CTX - 2, N_CTX - 3, N_CTX - 1), "random"),
    "dead_with_stale_positions": (False, (N_CTX - 2, 77, N_CTX - 1), "random"),
    # a freed lane keeps stepping: its position walks past the ring's end
    "dead_beyond_the_ring": (False, (N_CTX + 40, 5000, N_CTX), "random"),
    "mixed": ((True, False, True), (3, 120, 60), "random"),
}


@pytest.mark.parametrize("others", [k for k in OTHERS if k != "empty"])
@pytest.mark.parametrize("heads", [(4, 2), (4, 4)], ids=["gqa", "mha"])
@pytest.mark.parametrize("pos", [5, BLOCK, 70])
@pytest.mark.parametrize("attn_impl", ["xla", "pallas"],
                         ids=["loop", "kernel"])
def test_a_lanes_logits_do_not_depend_on_the_other_lanes(attn_impl, heads,
                                                         pos, others):
    """Lane 1 holds the same ring, token and position throughout; lanes 0, 2
    and 3 are empty, full to the ring's end, dead with stale positions, dead
    with positions past the ring, or a mix.  Lane 1's logits and the K/V it
    writes are BITWISE those of the run with the other lanes empty, under
    the loop's common bound and under the kernel's bound per lane."""
    cfg = _cfg(heads, attn_impl=attn_impl)
    step = _lanes_program(cfg)
    mine = _random_cache(cfg, seed=11)

    def run(name):
        live, poss, rings = OTHERS[name]
        live = (live,) * 3 if isinstance(live, bool) else live
        lanes = [jax.tree.map(jnp.zeros_like, mine) if rings == "zeros"
                 else _random_cache(cfg, seed=20 + n) for n in range(3)]
        lanes.insert(1, mine)
        caches = jax.tree.map(lambda *a: jnp.stack(a), *lanes)
        logits, new = step(
            jnp.asarray([9, 7, 11, 13], jnp.int32),
            jnp.asarray([poss[0], pos, poss[1], poss[2]], jnp.int32), caches,
            jnp.asarray([live[0], True, live[1], live[2]]))
        return logits, jax.tree.map(lambda a: a[1], new)

    want, want_cache = run("empty")
    got, got_cache = run(others)
    assert np.array_equal(bits(got[1]), bits(want[1]))
    for name in ("k", "v"):
        assert np.array_equal(bits(got_cache[name]), bits(want_cache[name]))
    assert np.isfinite(np.asarray(got[1])).all()
    # and the test can tell: another token in lane 1 moves its logits
    assert not np.array_equal(bits(got[1]), bits(got[0]))


def _xla_write_then_kernel(monkeypatch):
    """``_layer`` as it was before the kernel stored the row: the XLA
    write of the stacked leaf, then the read-only kernel."""
    real = llama._kernel_decode

    def old(q, cache, i, pos, live, cfg, dtype, k_new=None, v_new=None):
        if k_new is None:
            return real(q, cache, i, pos, live, cfg, dtype)
        cache = {n: jax.lax.dynamic_update_slice(
            cache[n], r[None, :, None, :], (i, 0, pos, 0))
            for n, r in (("k", k_new), ("v", v_new))}
        return real(q, cache, i, pos, live, cfg, dtype), cache

    monkeypatch.setattr(llama, "_kernel_decode", old)


@pytest.mark.parametrize("heads", [(4, 2), (4, 4)], ids=["gqa", "mha"])
@pytest.mark.parametrize("window", [0, 24], ids=["causal", "window24"])
def test_64_greedy_steps_with_the_write_folded_serial(monkeypatch, heads,
                                                      window):
    """One sequence, a prefill of 8 and 64 greedy decode steps through
    ``forward`` (the serial engine's program), across the block edges at
    16..64: with the kernel storing the row the logits of every step and
    the ring at the end are BITWISE those of the XLA write followed by the
    read-only kernel (the row is set into the block in VMEM before the
    block is read: the same values in the same order)."""
    cfg = _cfg(heads, window, attn_impl="pallas")
    params = synth_params(cfg, seed=4)

    def run():
        step = jax.jit(lambda t, p, c: llama.decode_step(params, cfg, t, p, c))
        logits, cache = llama.prefill(
            params, cfg, jnp.arange(8, dtype=jnp.int32), jnp.int32(8),
            llama.init_cache(cfg))
        rows = []
        for pos in range(8, 72):
            rows.append(bits(logits))
            logits, cache = step(jnp.argmax(logits).astype(jnp.int32),
                                 jnp.int32(pos), cache)
        return rows, cache

    got, got_cache = run()
    _xla_write_then_kernel(monkeypatch)
    want, want_cache = run()
    assert len({int(np.argmax(r.view(np.float32))) for r in got}) > 4
    for n, (a, b) in enumerate(zip(got, want)):
        assert np.array_equal(a, b), n
    for name in ("k", "v"):
        assert np.array_equal(bits(got_cache[name]), bits(want_cache[name]))


@pytest.mark.parametrize("heads", [(4, 2), (4, 4)], ids=["gqa", "mha"])
def test_64_greedy_steps_with_the_write_folded_lanes(monkeypatch, heads):
    """The lane program's step over 4 lanes at different positions, lane 2
    dead with a random ring and a position that walks on: 64 greedy steps.
    The live lanes' logits at every step and their rings at the end are
    BITWISE those of the XLA write followed by the read-only kernel.  The
    dead lane's ring comes back as it went in under the kernel, and
    changed under the XLA write (the test can tell)."""
    cfg = _cfg(heads, attn_impl="pallas")
    params = synth_params(cfg, seed=4)
    lanes = [_random_cache(cfg, seed=40 + n) for n in range(4)]
    caches0 = jax.tree.map(lambda *a: jnp.stack(a), *lanes)
    live = jnp.asarray([True, True, False, True])
    start = np.array([3, 30, 50, 15])

    def run():
        step = _lane_step(params, cfg)
        toks = jnp.asarray([9, 7, 11, 13], jnp.int32)
        caches, rows = caches0, []
        for t in range(64):
            logits, caches = step(toks, jnp.asarray(start + t, jnp.int32),
                                  caches, live)
            rows.append(bits(logits))
            toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return rows, caches

    got, got_caches = run()
    _xla_write_then_kernel(monkeypatch)
    want, want_caches = run()
    for n, (a, b) in enumerate(zip(got, want)):
        assert np.array_equal(a[[0, 1, 3]], b[[0, 1, 3]]), n
    for name in ("k", "v"):
        for lane in (0, 1, 3):
            assert np.array_equal(bits(got_caches[name][lane]),
                                  bits(want_caches[name][lane]))
        assert np.array_equal(bits(got_caches[name][2]),
                              bits(caches0[name][2]))
        assert not np.array_equal(bits(want_caches[name][2]),
                                  bits(caches0[name][2]))


def test_an_all_masked_block_leaves_the_state_bit_for_bit():
    """The recurrence itself: blocks beyond the position (a later bound)
    change nothing, also under a sliding window whose FIRST blocks hold no
    live slot (running max at its finite floor: no NaN)."""
    for window in (0, 24):
        cfg = _cfg(window=window)
        cache = _random_cache(cfg, seed=3)
        q = jax.random.normal(jax.random.PRNGKey(4),
                              (1, cfg.n_heads, cfg.head_dim), jnp.bfloat16)
        outs = [llama.decode_attention(q, cache, 1, 70, bound, cfg,
                                       jnp.float32)
                for bound in (70, 79, 80, N_CTX - 1, 5000)]
        assert np.isfinite(np.asarray(outs[0])).all()
        for out in outs[1:]:
            assert np.array_equal(bits(out), bits(outs[0]))


# ---------------------------------------------------------------------------
# prefill + 64 decode steps through the cache, against the references
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def reference_routed():
    """``benchmarks/`` is not a package: its files import each other by
    bare name."""
    sys.path.insert(0, BENCH)
    try:
        import reference_routed
        yield reference_routed
    finally:
        sys.path.remove(BENCH)


def _prefill(params, cfg, tokens, n, **kw):
    padded = np.zeros(32, np.int32)
    padded[:n] = tokens[:n]
    return llama.forward(params, cfg, jnp.asarray(padded), jnp.int32(0),
                         llama.init_cache(cfg), last_idx=jnp.int32(n - 1),
                         **kw)


@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_routed_prefill_then_64_decode_steps_agree_with_the_reference(
        tmp_path, reference_routed, kv):
    """The routed block (tiny ``olmoe`` file: 16 / 16 heads' kind, MHA,
    QK-norm, rotate-half) through the same read, on a bf16 and on an int8
    ring, against ``reference_routed.py`` sent the program's own picks
    (near-ties in a random router are not the subject here:
    tests/test_olmoe.py)."""
    from llama_fastapi_k8s_gpu_tpu.gguf import GGUFFile
    from llama_fastapi_k8s_gpu_tpu.models.params import load_params
    from llama_fastapi_k8s_gpu_tpu.testing import write_tiny_olmoe_gguf

    path = str(tmp_path / "tiny.gguf")
    write_tiny_olmoe_gguf(path, seed=3)
    gf = GGUFFile(path)
    cfg = dataclasses.replace(ModelConfig.from_gguf(gf, n_ctx=N_CTX),
                              kv_dtype=kv)
    params = load_params(gf, cfg, fmt="bf16")
    tokens = np.random.default_rng(5).integers(0, 256, size=84)
    logits, cache, pk = _prefill(params, cfg, tokens, 20, with_picks=True)
    got, picks = [], [np.asarray(pk)[:, :20]]
    step = jax.jit(lambda t, p, c: llama.forward(
        params, cfg, t[None], p, c, with_picks=True))
    for pos in range(20, 84):
        logits, cache, pk = step(jnp.int32(tokens[pos]), jnp.int32(pos), cache)
        got.append(np.asarray(logits))
        picks.append(np.asarray(pk))
    hp, tensors = reference_routed.open_model(path)
    want = np.asarray(reference_routed.forward(
        hp, tensors, tokens,
        use_picks=list(np.concatenate(picks, 1)))[0])
    # the limit as tests/test_olmoe.py reads it, over the stacked positions
    # (1.7 % here, and 1.7 % with the whole-ring read; 2.5 % on the int8
    # ring); one position alone of this tiny routed model reaches 3.6 %
    # (the whole-ring read: 4.6 %; the int8 ring: 5.6 %)
    assert rel(np.stack(got), want[20:]) < REFERENCE
    for pos, row in zip(range(20, 84), got):
        assert rel(row, want[pos]) < 2 * REFERENCE, pos


# ---------------------------------------------------------------------------
# the served path: a greedy probe's text over rounds of traffic
# ---------------------------------------------------------------------------

def test_a_greedy_probes_text_is_unchanged_by_the_other_lanes_traffic(
        tmp_path):
    """What ``benchmarks/run.py`` demands of a window, on the lane engine:
    two temperature-0 probes on an idle engine, then traffic that fills the
    other lanes to different lengths (long prompts, long outputs, lanes
    freed with their positions left where they stopped), then the probes
    again, five rounds; and once with the traffic still running.  The text
    is identical every time."""
    from llama_fastapi_k8s_gpu_tpu.engine import ContinuousEngine
    from llama_fastapi_k8s_gpu_tpu.testing import write_tiny_llama_gguf

    path = str(tmp_path / "tiny.gguf")
    write_tiny_llama_gguf(path)
    eng = ContinuousEngine(path, batch_size=4, n_ctx=N_CTX, decode_chunk=4,
                           max_gen_tokens=64, prefill_buckets=(32, 64, 128))
    probes = [[{"role": "user", "content": "Say something."}],
              [{"role": "user", "content": "Count to three, slowly."}]]

    def probe():
        return [eng.create_chat_completion(m, temperature=0.0, max_tokens=12)
                ["choices"][0]["message"]["content"] for m in probes]

    def traffic(round_no):
        return [eng.submit(
            [{"role": "user", "content": f"round {round_no} " * (2 + 3 * i)}],
            temperature=0.8, seed=round_no * 10 + i,
            max_tokens=(8, 60, 24)[i]) for i in range(3)]

    try:
        first = probe()
        assert all(first)
        for round_no in range(5):
            futs = traffic(round_no)
            if round_no == 2:
                assert probe() == first     # neighbours mid-flight
            for f in futs:
                f.result(timeout=300)
            assert probe() == first, round_no
        assert 0 < eng.cache_counts["live"] <= eng.cache_counts["read"]
    finally:
        eng.shutdown()


# ---------------------------------------------------------------------------
# the counters on a schedule the test knows
# ---------------------------------------------------------------------------

def _counting(cfg):
    """What the engines' counting needs of an engine: the configuration,
    its cache kind's object and that kind's counters."""
    from llama_fastapi_k8s_gpu_tpu.models.cache import cache_of

    kind = cache_of(cfg)
    return types.SimpleNamespace(cfg=cfg, cache=kind,
                                 cache_counts=kind.new_counts())


def test_the_lane_counters_on_a_known_schedule():
    """``ContinuousEngine._note_ring_read`` on one chunk of 4 steps over 4
    lanes: lane 0 at slot 14, lane 1 at 30 (the bound), lane 2 empty, lane
    3 finished (its rows are discarded: not counted, and since the chunk
    was dispatched with it live, its position 50 set the bound).  Blocks
    of 16: bounds 50..53 read 4 blocks = 64 slots a lane-step."""
    from llama_fastapi_k8s_gpu_tpu.engine.continuous import ContinuousEngine

    def slot(n_prompt, n_gens, finished=False):
        return types.SimpleNamespace(n_prompt=n_prompt, gens=[0] * n_gens,
                                     finished=finished)

    eng = _counting(_cfg())                      # the XLA loop serves it
    pre = [slot(10, 5), slot(30, 1), None, slot(41, 10, finished=True)]
    ContinuousEngine._note_ring_read(eng, pre, 4)
    assert eng.cache_counts == {
        "read": 2 * 4 * 64,
        "live": (15 + 16 + 17 + 18) + (31 + 32 + 33 + 34),
        "rows_written": 0}
    # without the finished lane the bound is lane 1's: 30, 31 read 2
    # blocks, 32, 33 read 3
    ContinuousEngine._note_ring_read(eng, pre[:3], 4)
    assert eng.cache_counts["read"] == 2 * 4 * 64 + 2 * (32 + 32 + 48 + 48)


def test_the_lane_counters_under_the_kernel_are_per_lane():
    """The same schedule where the decode kernel serves the ring
    (``attn_impl="pallas"``, blocks of 16): a wanted lane reads its OWN
    blocks, whatever the finished lane at 50 and the longer neighbour
    hold: lane 0 at 14..17 reads 16 + 16 + 32 + 32, lane 1 at 30..33
    reads 32 + 32 + 48 + 48."""
    from llama_fastapi_k8s_gpu_tpu.engine.continuous import ContinuousEngine

    def slot(n_prompt, n_gens, finished=False):
        return types.SimpleNamespace(n_prompt=n_prompt, gens=[0] * n_gens,
                                     finished=finished)

    eng = _counting(_cfg(attn_impl="pallas"))
    pre = [slot(10, 5), slot(30, 1), None, slot(41, 10, finished=True)]
    ContinuousEngine._note_ring_read(eng, pre, 4)
    # the kernel stored a K row for each of the THREE lanes the chunk was
    # dispatched with as live (the finished one too: the device does not
    # know yet), in 4 steps x 3 layers; the empty lane stored nothing
    assert eng.cache_counts == {
        "read": (16 + 16 + 32 + 32) + (32 + 32 + 48 + 48),
        "live": (15 + 16 + 17 + 18) + (31 + 32 + 33 + 34),
        "rows_written": 3 * 4 * 3}


@pytest.mark.parametrize("cfg_kw,who", [
    (dict(attn_impl="pallas"), "kernel"),
    (dict(attn_impl="xla"), "xla"),                     # the CPU
    (dict(attn_impl="ring"), "xla"),                    # sequence parallel
    (dict(attn_impl="pallas", kv_dtype="int8"), "xla"),
    (dict(attn_impl="pallas", mixers=("sp", "lin", "sp")), "xla"),
    (dict(attn_impl="pallas", eva_window=64, eva_chunk=4), None)])
def test_who_writes_a_decode_steps_row(cfg_kw, who):
    """``/health`` ``engine.ring_write``: the kernel where ``_layer`` hands
    it the row, XLA on every other ring (``models/sala.py`` writes before
    it calls the kernel), nothing to say where there is no ring."""
    from llama_fastapi_k8s_gpu_tpu.server.app import _ring_write

    cfg = dataclasses.replace(_cfg(), **cfg_kw)
    assert llama.ring_write_impl(cfg) == who
    assert _ring_write(cfg) == who
    assert _ring_write(None) is None


def test_rows_written_count_only_where_the_kernel_writes():
    from llama_fastapi_k8s_gpu_tpu.engine.engine import Engine

    for impl, rows in (("pallas", 2 * 5 * 3), ("xla", 0)):
        eng = _counting(_cfg(attn_impl=impl))
        eng.cache.note_decode(eng.cache_counts, eng.cfg, [7, 40], 5)
        assert eng.cache_counts["rows_written"] == rows
        eng.slice_tokens = {}
        eng.tokenizer = None                # no memo of pieces to count
        assert Engine.cache_read_gauges(eng)["ring_rows_written_total"] == rows


def test_the_kernel_serves_both_engines_and_the_probes_text_holds(tmp_path):
    """``attn_impl="pallas"`` through the engines themselves (interpret
    mode): the serial ``Engine`` is the kernel at one lane, the lane engine
    the kernel over its lanes with freed lanes skipped.  A greedy probe's
    text is the same before, during and after other lanes' traffic, and
    the counters count whole blocks of 16 with most of the read live (the
    loop's common bound, in blocks of 16 too, read well under that)."""
    from llama_fastapi_k8s_gpu_tpu.engine import ContinuousEngine, Engine
    from llama_fastapi_k8s_gpu_tpu.testing import write_tiny_llama_gguf

    path = str(tmp_path / "tiny.gguf")
    write_tiny_llama_gguf(path)
    kw = dict(n_ctx=N_CTX, decode_chunk=4, max_gen_tokens=64,
              prefill_buckets=(32, 64, 128), attn_impl="pallas")
    probe_msgs = [{"role": "user", "content": "Say something."}]
    serial = Engine(path, prefix_cache=False, **kw)
    assert serial.cfg.attn_impl == "pallas"
    assert llama.decode_kernel_block(serial.cfg) == BLOCK
    text = serial.create_chat_completion(
        probe_msgs, temperature=0.0, max_tokens=12)[
            "choices"][0]["message"]["content"]
    assert text
    assert serial.cache_counts["read"] % BLOCK == 0
    assert serial.cache_counts["live"] / serial.cache_counts["read"] > 0.6
    # the kernel stored every decode step's row itself: a K row a layer
    layers = serial.cfg.n_layers
    assert llama.ring_write_impl(serial.cfg) == "kernel"
    assert serial.cache_counts["rows_written"] > 0
    assert serial.cache_counts["rows_written"] % layers == 0
    assert serial.cache_read_gauges()["ring_rows_written_total"] \
        == serial.cache_counts["rows_written"]

    eng = ContinuousEngine(path, batch_size=3, **kw)

    def probe():
        return eng.create_chat_completion(
            probe_msgs, temperature=0.0, max_tokens=12)[
                "choices"][0]["message"]["content"]

    try:
        first = probe()
        assert first
        futs = [eng.submit(
            [{"role": "user", "content": "more words " * (2 + 4 * i)}],
            temperature=0.8, seed=i, max_tokens=(40, 10)[i])
            for i in range(2)]
        assert probe() == first             # neighbours mid-flight
        for f in futs:
            f.result(timeout=300)
        assert probe() == first             # beside freed lanes
        read, live = eng.cache_counts["read"], eng.cache_counts["live"]
        assert read % BLOCK == 0 and 0 < live <= read
        assert live / read > 0.6
        assert eng.cache_counts["rows_written"] > 0
        # lanes dispatched live x steps RUN x layers (a chunk stops where
        # none of its lanes has anything left to decode)
        assert eng.cache_counts["rows_written"] % layers == 0
    finally:
        eng.shutdown()
