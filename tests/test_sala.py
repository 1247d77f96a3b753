"""The ``minicpm-sala`` stack (linear-attention layers over a decaying
float32 state, block-sparse attention layers on a 2-head ring with
compressed keys; ``models/sala.py``) against its plain float32 reference
(``benchmarks/reference_sala.py``: the recurrence token by token, an
explicit set of blocks per query), on the CPU at a tiny size: 8 layers in
the order sp lin lin sp sp lin lin sp (sparse layers adjacent and at both
ends), hidden 128, 4 heads of 32 on 2 KV heads, block 8, stride 2, kernel
4, topk 3, window 16, init_blocks 1, dense_len 48, seeded random weights
with the sparse layers' Q/K norm gains times 3 (so that a block's score is
a property of the weights, not of rounding).  Logits, never tokens; sets of
blocks as sets.

Limits, with their reasons and the controls that fail them:

- ``LIMIT`` 5 %: the program multiplies in bfloat16 and keeps keys, values
  and compressed keys bfloat16; against the reference ON THE PROGRAM'S OWN
  SETS it reads 1.5-2.6 % of the logits' norm per block of 16 positions,
  the reference with its matmul and attention inputs rounded to bfloat16
  2-3 % (must pass), to float8 25 % and more (must fail).  Controls, each
  another function: no decay 25-40 %, the gate left out 45-70 %, the
  branch scalar left out 65-90 %, the embedding scalar 130 %, the head
  scalar 100 %, a lane that keeps a freed lane's state 30 % and more.
- ``STATE`` 1e-4: a bfloat16 STATE is one more rounding among many on the
  logits of 130 positions (1.5 % at most) and on the state leaf itself
  (whose k and v come out of a bfloat16-multiplied stream: 1.4-2.8 % off
  the reference's, a state rounded to bfloat16 after every step 1.0-1.3
  %), so it is held where it shows: the program's own step and slice on
  GIVEN q, k, v against the reference's recurrence on the same values.
  Float32: 1e-6 (the order of the sums); rounded to bfloat16 after every
  step: 0.5 % and more at 130 positions, growing with the root of the
  length in the heads that hardly decay.  The leaf's dtype is held beside.
- ``PICKS`` 2 of some 660: a query's set of blocks (per sparse layer and
  KV head) against the reference's own UNDER THE PROGRAM'S EARLIER SETS, a
  difference counted unless the reference's scores of the blocks
  exchanged lie within ``MARGIN`` 25 % of each other (what a bfloat16
  stream, compressed keys and queries do to a sharp softmax's
  probability: up to 15 % seen; 20 raw differences, 0-2 counted at 15 %,
  0 at 25 %, over three seeds).  Every other selection is told by whose
  side the program is on where the two differ: top-(k - 1), no window
  blocks, no block 0 differ on every set past ``dense_len``, scores of
  the group's first head alone on 400, ``kc`` one stride late on 15-30;
  the program sides with the reference on 70 % of them and more, with
  the control on 30 % and fewer.
- ``SAME`` 1e-5: the chunk form against the recurrence on the same inputs
  (float32 sums in another order).
- bitwise: a lane's logits and cache under any neighbours.
"""

from __future__ import annotations

import dataclasses
import functools
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")

LIMIT = 5e-2
STATE = 1e-4
MARGIN = 0.25
PICKS = 2
SAME = 1e-5
N_CTX = 256
SLICE = 8            # one block of the sparse layers
N_PROMPT = 40        # below dense_len 48: decode crosses it
N_SEQ = 130


@pytest.fixture(scope="module")
def ref():
    sys.path.insert(0, BENCH)
    try:
        import reference_sala
        yield reference_sala
    finally:
        sys.path.remove(BENCH)


@pytest.fixture(scope="module")
def gguf_path(tmp_path_factory):
    from llama_fastapi_k8s_gpu_tpu.testing import write_tiny_sala_gguf

    path = str(tmp_path_factory.mktemp("sala") / "tiny.gguf")
    write_tiny_sala_gguf(path, seed=3, qk_scale=3.0)
    return path


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(5).integers(4, 260, size=N_SEQ)


@pytest.fixture(scope="module")
def model(ref, gguf_path):
    return ref.open_model(gguf_path)


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


def worst(got, want, step=16):
    """The largest ``rel`` over blocks of ``step`` positions."""
    return max(rel(got[a:a + step], want[a:a + step])
               for a in range(0, len(got), step))


def programs(cfg):
    """The calls the tests make of the program's ``forward``: a prefill
    pass (``n`` real positions of the slice), one decode step, one step of
    lanes (the body of ``parallel/batched.py``'s vmapped step, its bounds
    included); each returns the sparse layers' sets too.  One build a
    process for each configuration (and ``starts_sequence``, which one
    control replaces): a second caller gets the programs the first one
    compiled."""
    from llama_fastapi_k8s_gpu_tpu.models import sala

    key = (cfg, sala.starts_sequence)
    if key not in _PROGRAMS:
        _PROGRAMS[key] = _build_programs(cfg)
    return _PROGRAMS[key]


_PROGRAMS = {}


def _build_programs(cfg):
    import jax

    from llama_fastapi_k8s_gpu_tpu.models.llama import forward
    from llama_fastapi_k8s_gpu_tpu.parallel.batched import step_bound

    @jax.jit
    def pass_(params, tokens, off, n, cache):
        return forward(params, cfg, tokens, off, cache, last_idx=n - 1,
                       return_all=True, with_picks=True)

    @jax.jit
    def step(params, token, pos, cache):
        return forward(params, cfg, token[None], pos, cache, with_picks=True)

    @jax.jit
    def lane_step(params, tokens, poss, caches, live):
        bound = step_bound(cfg, poss, live)
        return jax.vmap(lambda t, p, c, lv: forward(
            params, cfg, t[None], p, c, live=lv, kv_bound=bound,
            with_picks=True))(tokens, poss, caches, live)
    return pass_, step, lane_step


def prefill(params, cfg, seq, n, size=SLICE, pass_=None, cache=None):
    """Logits and sets of positions [0, n), and the cache, in passes of
    ``size`` (the last one padded, as a bucket is)."""
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.models.llama import init_cache

    pass_ = pass_ or programs(cfg)[0]
    cache = init_cache(cfg) if cache is None else cache
    out, sets = [], []
    for off in range(0, n, size):
        part = np.full(size, 9, np.int32)
        real = seq[off:min(off + size, n)]
        part[:len(real)] = real
        lg, cache, pk = pass_(params, jnp.asarray(part), jnp.int32(off),
                              jnp.int32(len(real)), cache)
        out.append(np.asarray(lg)[:len(real)])
        sets.append(np.asarray(pk)[:, :, :len(real)])
    return np.concatenate(out), np.concatenate(sets, axis=2), cache


@pytest.fixture(scope="module")
def served(loaded, tokens):
    """The serial programs over the whole sequence, slices then steps:
    (logits (S, V), sets (L_sp, n_kv, S, blocks), the cache at the end)."""
    import jax.numpy as jnp

    params, cfg = loaded
    pass_, step, _ = programs(cfg)
    logits, sets, cache = prefill(params, cfg, tokens, N_PROMPT, pass_=pass_)
    dec, dsets = [], []
    for t in range(N_PROMPT, N_SEQ):
        lg, cache, pk = step(params, jnp.int32(tokens[t]), jnp.int32(t),
                             cache)
        dec.append(np.asarray(lg))
        dsets.append(np.asarray(pk))
    return (np.concatenate([logits, np.stack(dec)]),
            np.concatenate([sets] + dsets, axis=2), cache)


@pytest.fixture(scope="module")
def own(ref, model, tokens):
    """The reference on its own sets: (logits, sets, block scores)."""
    logits, sets, scores = ref.forward(*model, tokens, want_picks=True)
    return np.asarray(logits), np.asarray(sets), np.asarray(scores)


def counted(got, sets, scores, margin=MARGIN):
    """Sets that differ from the reference's beyond the margin: a block
    missing and a block in its place whose reference scores lie within
    ``margin`` of each other (paired best against best) are one rounding's
    work, anything else is another selection."""
    nb = sets.shape[-1]
    got = got[..., :nb]
    assert not got[..., nb:].any() if got.shape[-1] > nb else True
    n = 0
    for idx in zip(*np.nonzero((got != sets).any(-1))):
        missing = np.sort(scores[idx][sets[idx] & ~got[idx]])[::-1]
        extra = np.sort(scores[idx][got[idx] & ~sets[idx]])[::-1]
        if len(missing) != len(extra) or np.any(
                missing - extra > margin * np.maximum(missing, 1e-30)):
            n += 1
    return n


# ---------------------------------------------------------------------------
# the program against the reference
# ---------------------------------------------------------------------------

def test_the_limit_lies_between_bfloat16_and_float8(ref, model, tokens, own):
    import jax.numpy as jnp

    bf16 = ref.forward(*model, tokens, emulate=jnp.bfloat16, picks=own[1])
    f8 = ref.forward(*model, tokens, emulate=jnp.float8_e4m3fn, picks=own[1])
    assert worst(bf16, own[0]) < LIMIT < worst(f8, own[0])


def test_prefill_alone_selects_per_query_past_dense_len(ref, model, loaded,
                                                        tokens, own):
    """The whole sequence as a prompt, in slices of two blocks: 82 of its
    130 queries are past ``dense_len`` and select in the slice."""
    params, cfg = loaded
    logits, sets, _ = prefill(params, cfg, tokens, N_SEQ, size=16)
    want, theirs, scores = ref.forward(*model, tokens, picks=sets,
                                       want_picks=True)
    assert counted(sets, np.asarray(theirs), np.asarray(scores)) <= PICKS
    assert worst(logits, want) < LIMIT


def test_slices_then_decode_across_dense_len_and_kc_closes(ref, model, tokens,
                                                           served, own):
    """A prompt of 40 in slices of one block, then 90 steps: they cross
    ``dense_len`` at 47, close 45 compressed keys and come to leave 9 of
    17 blocks unread."""
    logits, sets, _ = served
    want, theirs, scores = ref.forward(*model, tokens, picks=sets,
                                       want_picks=True)
    assert counted(sets, np.asarray(theirs), np.asarray(scores)) <= PICKS
    assert sets[0, 0, N_SEQ - 1].sum() == 7 < (N_SEQ - 1) // 8 + 1
    assert worst(logits, want) < LIMIT


def test_the_host_counts_what_the_program_selects(loaded, served):
    """``sala.blocks_read`` (the counters' arithmetic) is the size of the
    program's set at every position."""
    from llama_fastapi_k8s_gpu_tpu.models import sala

    _, cfg = loaded
    sets = served[1]
    for t in range(N_SEQ):
        n = sala.blocks_read(t, cfg) if sala.is_sparse(t, cfg) \
            else sala.blocks_visible(t, cfg)
        assert (sets[:, :, t].sum(-1) == n).all(), t


@pytest.mark.parametrize("control", ["no_decay", "no_gate", "no_branch_scale",
                                     "no_emb_scale", "no_logit_scale"])
def test_another_function_fails_the_limit(ref, model, tokens, served, own,
                                          control):
    other = ref.forward(*model, tokens, picks=served[1], **{control: True})
    assert worst(served[0], other) > LIMIT
    assert rel(other, own[0]) > LIMIT


def test_the_state_is_float32_and_a_bfloat16_one_fails(ref, loaded):
    """The program's slice (64 positions, 50 of them real, then 14 more
    in a second slice) and 66 steps on given q, k, v against the
    reference's recurrence on the same values."""
    import jax
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.models import sala
    from llama_fastapi_k8s_gpu_tpu.models.llama import init_cache

    assert init_cache(loaded[1])["state"].dtype == jnp.float32
    rng = np.random.default_rng(1)
    H, hd = 4, 32
    q, k, v = (jnp.asarray(rng.standard_normal((N_SEQ, H, hd)), jnp.bfloat16)
               for _ in range(3))
    slope = jnp.asarray([0.8, 0.1, 1e-3, 1e-5], jnp.float32)
    with jax.default_matmul_precision("highest"):
        state = jnp.zeros((H, hd, hd), jnp.float32)
        pad = lambda x, a, n: jnp.concatenate(     # noqa: E731
            [x[a:a + n], jnp.ones((64 - n, H, hd), x.dtype)])
        o1, state = sala.lin_slice(pad(q, 0, 50), pad(k, 0, 50),
                                   pad(v, 0, 50), state, slope, jnp.int32(50))
        o2, state = sala.lin_slice(pad(q, 50, 14), pad(k, 50, 14),
                                   pad(v, 50, 14), state, slope,
                                   jnp.int32(14))
        outs = [o1[:50], o2[:14]]
        for t in range(64, N_SEQ):
            o, state = sala.lin_step(q[t], k[t], v[t], state, slope)
            outs.append(o[None])
        f32 = [x.astype(jnp.float32) for x in (q, k, v)]
        want_o, want_s = ref.recurrence(*f32, jnp.exp(-slope))
        bad_o, bad_s = ref.recurrence(*f32, jnp.exp(-slope), jnp.bfloat16)
    assert rel(jnp.concatenate(outs), want_o) < STATE
    assert rel(state, want_s) < STATE < rel(bad_s, want_s)
    assert rel(bad_o, want_o) > STATE


@pytest.mark.parametrize("control", ["topk_less", "no_window", "no_init",
                                     "kc_late", "no_group_sum"])
def test_another_selection_is_told_by_whose_side_the_program_is_on(
        ref, model, tokens, served, control):
    """Where the reference's sets and the control's differ, the program's
    are the reference's."""
    sets = served[1]
    theirs = np.asarray(ref.forward(*model, tokens, picks=sets,
                                    want_picks=True)[1])
    other = np.asarray(ref.forward(*model, tokens, picks=sets,
                                   want_picks=True, **{control: True})[1])
    mine = sets[..., :theirs.shape[-1]]
    apart = (theirs != other).any(-1)
    assert apart.sum() >= 8
    assert ((mine == theirs).all(-1) & apart).sum() >= 0.7 * apart.sum()
    assert ((mine == other).all(-1) & apart).sum() <= 0.3 * apart.sum()


def test_chunk_form_against_recurrence():
    """``lin_slice`` against ``lin_step`` one position at a time, from a
    state that is not zero, with and without padding."""
    import jax
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.models import sala

    rng = np.random.default_rng(0)
    C, H, hd = 24, 4, 32
    q, k, v = (jnp.asarray(rng.standard_normal((C, H, hd)), jnp.bfloat16)
               for _ in range(3))
    s_in = jnp.asarray(rng.standard_normal((H, hd, hd)), jnp.float32)
    slope = jnp.asarray([0.8, 0.2, 0.01, 1e-5], jnp.float32)
    with jax.default_matmul_precision("highest"):
        for n in (C, 17):
            o, s_out = sala.lin_slice(q, k, v, s_in, slope, jnp.int32(n))
            state, want = s_in, []
            for t in range(n):
                ot, state = sala.lin_step(q[t], k[t], v[t], state, slope)
                want.append(ot)
            assert rel(o[:n], jnp.stack(want)) < SAME
            assert rel(s_out, state) < SAME


STATE_SLOPE = (0.8, 0.1, 1e-3, 1e-5)


@functools.cache
def _state_step_over_lanes():
    """The kernel under ``vmap`` over lanes, layer 1 of the leaf: ONE
    program for every case below (what is live is an operand), compiled
    once a worker."""
    import jax
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.ops.pallas.linstate import lin_state_step

    decay = jnp.exp(-jnp.asarray(STATE_SLOPE, jnp.float32))
    return jax.jit(jax.vmap(lambda q, k, v, s, lv: lin_state_step(
        q, k, v, s, jnp.int32(1), lv, decay, interpret=True)))


@pytest.mark.parametrize("live", [
    (True, True, True, True), (True, False, True, True),
    (False, False, True, False), (False, False, False, False)])
def test_the_state_kernel_is_the_recurrence_and_skips_dead_lanes(live):
    """``ops/pallas/linstate.py`` (interpret mode here; compiled for the
    chip in ``tests/test_chip_compile.py``) under ``vmap`` over lanes
    against ``lin_step``: a live lane's state and output are the
    recurrence's, a dead lane's state is bit for bit what it was and its
    output 0, and no other layer of the leaf is touched.  The 0 is the
    kernel's own store (``sala.lin_layer`` adds a dead lane's output to
    its stream): interpret mode hands a kernel its outputs filled with
    NaN, so an output block the kernel left alone would fail here."""
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.models import sala

    rng = np.random.default_rng(0)
    B, L, H, hd = 4, 3, 4, 128
    q, k, v = (jnp.asarray(rng.standard_normal((B, H, hd)), jnp.bfloat16)
               for _ in range(3))
    state = jnp.asarray(rng.standard_normal((B, L, H, hd, hd)), jnp.float32)
    slope = jnp.asarray(STATE_SLOPE, jnp.float32)
    o, new = _state_step_over_lanes()(q, k, v, state, jnp.asarray(live))
    for b in range(B):
        if live[b]:
            want_o, want_s = sala.lin_step(q[b], k[b], v[b], state[b, 1],
                                           slope)
            assert rel(o[b], want_o) < SAME and rel(new[b, 1], want_s) < SAME
        else:
            assert np.array_equal(new[b, 1], state[b, 1])
            assert not np.asarray(o[b]).any()
        for other in (0, 2):
            assert np.array_equal(new[b, other], state[b, other])


def _lane_step_parts(lane_step, args):
    """(logits, the ``state`` leaf, the sets) of one lane step, on the host;
    the rest of the step's cache is dropped with this frame."""
    logits, cache, sets = lane_step(*args)
    return np.asarray(logits), np.asarray(cache["state"]), np.asarray(sets)


def test_the_stack_with_the_kernels_is_the_stack_without(loaded, tokens,
                                                         served):
    """``attn_impl="pallas"`` (what a TPU resolves to; interpret mode
    here) against the plain XLA programs, lanes and all: the state kernel
    and the ring's decode kernel in place of their XLA forms."""
    import jax
    import jax.numpy as jnp

    params, cfg = loaded
    kcfg = dataclasses.replace(cfg, attn_impl="pallas", n_ctx=N_CTX)
    _, _, c0 = prefill(params, cfg, tokens, 72)
    _, _, c1 = prefill(params, cfg, tokens, 24)
    stacked = jax.tree.map(lambda *a: jnp.stack(a), c0, c1, c1)
    args = (params, jnp.asarray([tokens[72], tokens[24], 5], jnp.int32),
            jnp.asarray([72, 24, 24], jnp.int32), stacked,
            jnp.asarray([True, True, False]))
    # one stack after the other, the first one's device results freed
    # before the second is compiled: the worker that held both died in the
    # compile of the second (a segmentation fault inside
    # ``backend_compile_and_load`` in the driver's PR 59 run)
    want, wstate, wsets = (np.asarray(a) for a in _lane_step_parts(
        programs(cfg)[2], args))
    got, gstate, gsets = (np.asarray(a) for a in _lane_step_parts(
        programs(kcfg)[2], args))
    assert rel(got[:2], want[:2]) < SAME * 1e3        # bf16 P in the kernel
    assert np.array_equal(gsets[:2], wsets[:2])
    assert rel(gstate[:2], wstate[:2]) < SAME
    assert np.array_equal(gstate[2], np.asarray(stacked["state"][2]))


# ---------------------------------------------------------------------------
# lanes
# ---------------------------------------------------------------------------

def lanes_run(loaded, tokens, monkeypatch=None):
    """Three lanes of one vmapped step: lane 0 (prompt 80, past
    ``dense_len``) leaves after 30 steps and walks on; lane 1 (prompt 24)
    stays and crosses ``dense_len``; lane 2 is dead at first, its position
    walking past n_ctx, and at step 20 takes a NEW sequence (prompt 56)
    prefilled into the scratch cache lane 0's prefill left behind, as
    admission does.  {lane: (first position, logits, sets)}."""
    import jax
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.models.llama import init_cache

    params, cfg = loaded
    pass_, _, lane_step = programs(cfg)
    prompts = (80, 24, 56)
    seq2 = tokens[::-1].copy()
    _, _, c0 = prefill(params, cfg, tokens, prompts[0], pass_=pass_)
    _, _, c1 = prefill(params, cfg, tokens, prompts[1], pass_=pass_)
    # the scratch cache holds lane 0's prompt: its state, its ring
    _, _, c2 = prefill(params, cfg, seq2, prompts[2], pass_=pass_,
                       cache=jax.tree.map(jnp.copy, c0))
    garbage = jax.tree.map(lambda a: a + 1, init_cache(cfg))
    stacked = jax.tree.map(lambda *a: jnp.stack(a), c0, c1, garbage)
    pos = [prompts[0], prompts[1], N_CTX - 3]
    live = [True, True, False]
    seqs = [tokens, tokens, seq2]
    got = {0: [], 1: [], 2: []}
    for t in range(50):
        if t == 20:
            stacked = jax.tree.map(lambda a, c: a.at[2].set(c), stacked, c2)
            pos[2], live[2] = prompts[2], True
        if t == 30:
            live[0] = False
        toks = [seqs[i][p] if p < N_SEQ else 0 for i, p in enumerate(pos)]
        lg, stacked, pk = lane_step(
            params, jnp.asarray(toks, jnp.int32), jnp.asarray(pos, jnp.int32),
            stacked, jnp.asarray(live))
        for lane in range(3):
            if live[lane]:
                got[lane].append((pos[lane], np.asarray(lg[lane]),
                                  np.asarray(pk[lane])))
        pos = [p + 1 for p in pos]
    return {lane: (rows[0][0], np.stack([r[1] for r in rows]),
                   np.concatenate([r[2] for r in rows], axis=2))
            for lane, rows in got.items()}, seqs


def test_three_lanes_at_different_positions_one_freed_and_taken_again(
        ref, model, loaded, tokens):
    got, seqs = lanes_run(loaded, tokens)
    for lane, (first, logits, sets) in got.items():
        n = first + len(logits)
        # the lane's prompt was prefilled by the serial slices: their sets
        use = prefill(*loaded, seqs[lane], first)[1]
        use = np.concatenate([use, sets], axis=2)
        want, theirs, scores = ref.forward(*model, seqs[lane][:n], picks=use,
                                           want_picks=True)
        assert counted(use, np.asarray(theirs), np.asarray(scores)) \
            <= PICKS, lane
        assert worst(logits, np.asarray(want)[first:]) < LIMIT, lane
    assert got[1][0] + len(got[1][1]) > 48 + 16       # crossed dense_len


def test_a_lane_that_keeps_a_freed_lanes_state_fails_the_limit(
        ref, model, loaded, tokens, monkeypatch):
    """The control of the reset: with ``starts_sequence`` never true the
    new sequence in lane 2 integrates on top of lane 0's prompt."""
    from llama_fastapi_k8s_gpu_tpu.models import sala

    monkeypatch.setattr(sala, "starts_sequence", lambda pos: pos < 0)
    got, seqs = lanes_run(loaded, tokens)
    first, logits, _ = got[2]
    want = np.asarray(ref.forward(*model, seqs[2][:first + len(logits)]))
    assert worst(logits, want[first:]) > LIMIT


def test_a_lanes_logits_do_not_depend_on_the_other_lanes(loaded, tokens):
    """Bitwise, on both kinds and both branches: the same lane with the
    same cache under other neighbours (past ``dense_len`` or before it,
    live or dead, one that closes a compressed key)."""
    import jax
    import jax.numpy as jnp

    params, cfg = loaded
    pass_, _, lane_step = programs(cfg)
    near = prefill(params, cfg, tokens[5:], 20, pass_=pass_)[2]
    far = prefill(params, cfg, tokens[9:], 111, pass_=pass_)[2]

    for mine_at in (30, 70):          # the dense branch, the sparse branch
        mine = prefill(params, cfg, tokens, mine_at, pass_=pass_)[2]

        def run(other, other_pos, other_live):
            stacked = jax.tree.map(lambda *a: jnp.stack(a), mine, other)
            out = []
            for t in range(3):
                lg, stacked, _ = lane_step(
                    params, jnp.asarray([tokens[mine_at + t], 7], jnp.int32),
                    jnp.asarray([mine_at + t, other_pos + t], jnp.int32),
                    stacked, jnp.asarray([True, other_live]))
                out.append(np.asarray(lg[0]))
            return np.stack(out), jax.tree.map(
                lambda a: np.asarray(a[0]), stacked)

        base, cache = run(near, 20, True)
        for other, other_pos, other_live in (
                (far, 111, True), (far, 111, False), (near, 20, False)):
            got, c = run(other, other_pos, other_live)
            assert np.array_equal(got, base), (mine_at, other_pos)
            for name in cache:
                assert np.array_equal(c[name], cache[name]), name


# ---------------------------------------------------------------------------
# the file, the loader, the refusals
# ---------------------------------------------------------------------------

def test_gguf_round_trip_of_the_new_keys_and_tensors(gguf_path, loaded):
    from llama_fastapi_k8s_gpu_tpu.gguf import GGUFFile
    from llama_fastapi_k8s_gpu_tpu.models import sala
    from llama_fastapi_k8s_gpu_tpu.testing import TINY_SALA_CFG

    gf = GGUFFile(gguf_path)
    assert gf.architecture == "minicpm-sala"
    params, cfg = loaded
    assert cfg.cache_kind == "state+ring" and cfg.fp32_logits
    want = dataclasses.replace(TINY_SALA_CFG, n_ctx=N_CTX)
    for field in ("mixers", "lin_heads", "emb_scale", "logit_scale",
                  "sp_kernel", "sp_stride", "sp_block", "sp_topk",
                  "sp_window", "sp_init_blocks", "sp_dense_len", "rope_neox"):
        assert getattr(cfg, field) == getattr(want, field), field
    assert abs(cfg.residual_scale - want.residual_scale) < 1e-6
    assert sala.runs(cfg) == [("sp", 0, 1), ("lin", 0, 2), ("sp", 1, 2),
                              ("lin", 2, 2), ("sp", 3, 1)]
    lin, sp = params["layers"]["lin"], params["layers"]["sp"]
    assert lin["wk"]["w"].shape == (4, 128, 128)
    assert sp["wk"]["w"].shape == (4, 64, 128)
    assert "attn_out_norm" in lin and "attn_out_norm" not in sp
    assert "w" in params["output"]          # the F16 head stays float


def test_the_cache_holds_each_kind_side_by_side(loaded):
    from llama_fastapi_k8s_gpu_tpu.models.llama import cache_nbytes, init_cache

    _, cfg = loaded
    cache = init_cache(cfg)
    assert {n: (a.shape, str(a.dtype)) for n, a in cache.items()} == {
        "k": ((4, 2, N_CTX, 32), "bfloat16"),
        "v": ((4, 2, N_CTX, 32), "bfloat16"),
        "kc": ((4, 2, N_CTX // 2 + 1, 32), "bfloat16"),
        "kw": ((4, 2, 4, 32), "bfloat16"),
        "state": ((4, 4, 32, 32), "float32")}
    assert cache_nbytes(cfg) == sum(a.nbytes for a in cache.values())


def test_a_ring_file_loads_what_it_loaded(tmp_path):
    """The dense file's configuration and parameters know nothing of the
    new kinds: one stack, the ring."""
    from llama_fastapi_k8s_gpu_tpu.gguf import GGUFFile
    from llama_fastapi_k8s_gpu_tpu.models.config import ModelConfig
    from llama_fastapi_k8s_gpu_tpu.models.llama import init_cache
    from llama_fastapi_k8s_gpu_tpu.models.params import (flat_layers,
                                                         load_params)
    from llama_fastapi_k8s_gpu_tpu.testing import write_tiny_llama_gguf

    path = str(tmp_path / "llama.gguf")
    write_tiny_llama_gguf(path)
    gf = GGUFFile(path)
    cfg = ModelConfig.from_gguf(gf, n_ctx=64)
    assert cfg.cache_kind == "ring" and not cfg.mixers
    assert set(init_cache(cfg)) == {"k", "v"}
    params = load_params(gf, cfg, fmt="bf16")
    assert [n for n, _ in flat_layers(params["layers"])] \
        == list(params["layers"])


def test_the_benchmark_files_mix_fuses_in_both_kinds(tmp_path, ref):
    """The benchmark file's type mix at widths the fused kernels take (K =
    2048; one layer of each kind, 16 heads of 128 on 2 KV heads, the sparse
    layer's narrow K and V of 256 rows too): every matrix of both kinds is
    a fused K-quant plane, the F16 head stays float (``fp32_logits``: never
    the int8 fallback with quantized activations), and the logits stay at
    the fused kernels' distance from the reference (6 %: read 2-4 %, as
    ``tests/test_evabyte.py``'s fused file)."""
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.gguf import GGUFFile
    from llama_fastapi_k8s_gpu_tpu.models.config import ModelConfig
    from llama_fastapi_k8s_gpu_tpu.models.params import (flat_layers,
                                                         load_params)
    from llama_fastapi_k8s_gpu_tpu.testing import (
        SALA_Q4KM_MIX, TINY_SALA_CFG, write_tiny_sala_gguf)

    cfg = dataclasses.replace(
        TINY_SALA_CFG, dim=2048, n_heads=16, lin_heads=16, ffn_dim=2048,
        n_layers=2, mixers=("lin", "sp"), n_ctx=128, sp_dense_len=40,
        residual_scale=1.4 / 2 ** 0.5)
    path = str(tmp_path / "wide.gguf")
    write_tiny_sala_gguf(path, cfg, seed=1, mix=SALA_Q4KM_MIX)
    gf = GGUFFile(path)
    cfg = ModelConfig.from_gguf(gf, n_ctx=128)
    params = load_params(gf, cfg, fmt="q4k")
    kinds = {name: sorted(leaf) for name, leaf in
             flat_layers(params["layers"]) if isinstance(leaf, dict)}
    for kind in ("lin", "sp"):
        for name in ("wq", "wk", "wo", "wg", "w_gate", "w_up"):
            assert "qs" in kinds[f"{kind}.{name}"], (kind, name)
        for name in ("wv", "w_down"):
            assert {"q4", "q6p"} & set(kinds[f"{kind}.{name}"]), (kind, name)
    assert params["layers"]["sp"]["wk"]["qs"].shape[1] == 256
    assert sorted(params["output"]) == ["w"]
    seq = np.random.default_rng(2).integers(4, 260, size=57)
    got, sets, cache = prefill(params, cfg, seq, 48, size=8)
    step = programs(cfg)[1]
    lg, _, pk = step(params, jnp.int32(seq[48]), jnp.int32(48), cache)
    sets = np.concatenate([sets, np.asarray(pk)], axis=2)
    exp = np.asarray(ref.forward(*ref.open_model(path), seq[:49], picks=sets))
    print("read", rel(got, exp[:48]), rel(np.asarray(lg), exp[48]))
    assert rel(got, exp[:48]) < 0.06
    assert rel(np.asarray(lg), exp[48]) < 0.06


def _file_with(tmp_path, **fields):
    from llama_fastapi_k8s_gpu_tpu.testing import (TINY_SALA_CFG,
                                                   write_tiny_sala_gguf)

    path = str(tmp_path / "odd.gguf")
    write_tiny_sala_gguf(path, dataclasses.replace(TINY_SALA_CFG, **fields))
    return path


@pytest.mark.parametrize("fields, n_ctx, words", [
    (dict(sp_block=9), 252, "multiples of kernel_stride"),
    (dict(), 252, "n_ctx 252 is no multiple of sparse.block_size 8"),
])
def test_a_file_the_blocks_do_not_divide_is_refused_by_name(
        tmp_path, fields, n_ctx, words):
    from llama_fastapi_k8s_gpu_tpu.gguf import GGUFFile
    from llama_fastapi_k8s_gpu_tpu.models.config import ModelConfig

    with pytest.raises(ValueError, match=words):
        ModelConfig.from_gguf(GGUFFile(_file_with(tmp_path, **fields)),
                              n_ctx=n_ctx)


@pytest.mark.parametrize("kw, words", [
    (dict(kv_dtype="int8"), "LFKT_KV_DTYPE=int8 cannot serve architecture "
                            "'minicpm-sala'"),
    (dict(kv_paged=True), "LFKT_KV_PAGED=1 cannot serve architecture "
                          "'minicpm-sala'"),
    (dict(prefill_chunk=12), "LFKT_PREFILL_CHUNK=12 cannot serve "
                             "architecture 'minicpm-sala'"),
])
def test_what_cannot_hold_the_cache_is_refused_by_name(gguf_path, kw, words):
    from llama_fastapi_k8s_gpu_tpu.engine import Engine

    with pytest.raises(ValueError, match=words):
        Engine(gguf_path, n_ctx=N_CTX, **kw)


# ---------------------------------------------------------------------------
# the engines
# ---------------------------------------------------------------------------

MSGS = [{"role": "user", "content": "tell me about rings and states, at "
                                    "some length, would you kindly"}]


@pytest.fixture(scope="module")
def engine(gguf_path):
    from llama_fastapi_k8s_gpu_tpu.engine import Engine

    return Engine(gguf_path, n_ctx=N_CTX, prefill_chunk=SLICE, decode_chunk=4)


def test_serial_engine_serves_counts_and_says_what_it_holds(engine):
    before = dict(engine.cache_counts)
    out = engine.create_chat_completion(MSGS, max_tokens=24, temperature=0.0)
    n_prompt = out["usage"]["prompt_tokens"]
    assert n_prompt > 48 and out["usage"]["completion_tokens"] >= 1
    kind = engine.cache_kind
    assert kind["kind"] == "state+ring" and kind["linear_layers"] == 4 \
        and kind["sparse_layers"] == 4
    assert kind["prefix_reuse"].startswith("off")
    assert kind["kv_paged"] == "refused at start"
    assert kind["chat_template"] == "mistral"
    assert not engine._prefix_cache
    got = {k: engine.cache_counts[k] - before[k] for k in before}
    assert got["queries_sparse"] > 0 and got["queries_dense"] == 4 * 47
    assert 0 < got["blocks_read"] < got["blocks_visible"]
    assert got["state_updates"] % 4 == 0 and got["kc_written"] > 0
    gauges = engine.cache_read_gauges()
    assert gauges['sparse_queries_total{branch="sparse"}'] \
        == engine.cache_counts["queries_sparse"]
    # the same request again: the state starts from nothing, so the same
    # greedy text (a state kept from the last request would change it)
    again = engine.create_chat_completion(MSGS, max_tokens=24,
                                          temperature=0.0)
    assert again["choices"][0]["message"] == out["choices"][0]["message"]


def test_lane_engine_serves_and_takes_freed_lanes_again(gguf_path, engine):
    from llama_fastapi_k8s_gpu_tpu.engine.continuous import ContinuousEngine

    want = engine.create_chat_completion(MSGS, max_tokens=12, temperature=0.0)
    eng = ContinuousEngine(gguf_path, n_ctx=N_CTX, prefill_chunk=SLICE,
                           decode_chunk=4, batch_size=2)
    try:
        assert not eng._lane_prefix and eng.cache_kind["kind"] == "state+ring"
        futs = [eng.submit(MSGS, max_tokens=12, temperature=0.0)
                for _ in range(5)]
        outs = [f.result(timeout=300) for f in futs]
        assert all(o["usage"]["completion_tokens"]
                   == want["usage"]["completion_tokens"] for o in outs)
        assert eng.cache_counts["state_updates"] > 0
        assert eng.cache_counts["blocks_read"] \
            < eng.cache_counts["blocks_visible"]
    finally:
        eng.shutdown()


@pytest.mark.anyio
async def test_v1_chat_completions_streams_and_health_names_the_kinds(engine):
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
                "messages": MSGS, "max_tokens": 12, "temperature": 0.0,
                "stream": True, "stream_options": {"include_usage": True}})
            assert r.status_code == 200
            events = [json.loads(ln[6:]) for ln in r.text.splitlines()
                      if ln.startswith("data: {")]
            usage = [e["usage"] for e in events if e.get("usage")][-1]
            assert 1 <= usage["completion_tokens"] <= 12
            eng = (await client.get("/health")).json()["engine"]
            assert eng["cache"]["kind"] == "state+ring"
            assert eng["cache"]["prefix_reuse"].startswith("off")
            assert set(eng["weight_formats"]) >= {"lin.wq", "sp.wq", "lin.wg",
                                                  "sp.w_down"}
            d = (await client.get("/debug/compiles")).json()
            assert not d.get("degrades")
            m = (await client.get("/metrics")).text
            assert 'sparse_queries_total{branch="sparse"}' in m
            assert "lin_state_updates_total" in m
            assert "sparse_blocks_read_total" in m
        await app.router.shutdown()
