"""The ``phi4flash`` block (models/phi4flash.py) at a tiny size on the CPU,
against the plain float32 reference (benchmarks/reference_phi4flash.py): a
selective scan, a gated memory unit, window and full DIFFERENTIAL attention
and cross layers on one shared K/V leaf, over the seventh cache kind
(``ssm-state+window+shared-ring``), LayerNorms with biases, a tied head.

The tiny file (``testing.TINY_PHI4FLASH_CFG``) has every layer kind: two
(ssm, window) pairs, the (ssm, full) pair, two (gmu, cross) pairs; 4 heads
on 2 KV heads of 64 (one pair of each), a window of 8 positions in 16 slots,
512 channels of 4 states, 4 taps.  ONE file and one lane engine serve the
whole module.

LIMIT: the program (bf16 inputs to every product, float32 sums and states,
a bf16 stream) against the float32 reference reads 3 % of the logits' norm
(the reference with bf16 inputs 3 %); the controls read 30 % or more, or, a
change inside the state-space layers alone, all of ``m``.
"""

from __future__ import annotations

import dataclasses
import hashlib
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")

LIMIT = 6e-2
N_CTX = 128
N_PROMPT = 45
N_SEQ = 72            # the 16-slot windows wrap four times


def rel(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


@pytest.fixture(scope="module")
def ref():
    """The reference, with each layer's tensors dequantized once for the
    module (it dequantizes them at every call)."""
    sys.path.insert(0, BENCH)
    try:
        import reference_phi4flash
        plain, kept = reference_phi4flash.layer_weights, {}
        reference_phi4flash.layer_weights = lambda tensors, i: kept.get(i) \
            or kept.setdefault(i, plain(tensors, i))
        yield reference_phi4flash
        reference_phi4flash.layer_weights = plain
    finally:
        sys.path.remove(BENCH)


@pytest.fixture(scope="module")
def gguf_path(tmp_path_factory):
    from llama_fastapi_k8s_gpu_tpu.testing import write_tiny_phi4flash_gguf

    path = str(tmp_path_factory.mktemp("phi4flash") / "tiny.gguf")
    write_tiny_phi4flash_gguf(path, seed=3)
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
    return load_params(gf, cfg, "bf16"), cfg


@pytest.fixture(scope="module")
def want(ref, model, tokens):
    """(logits (S, V), the stream after the full layer, m) of the float32
    reference over the whole sequence."""
    return tuple(np.asarray(a) for a in ref.forward(*model, tokens))


_PROGRAMS = {}


def programs(cfg):
    """(a slice's program, a step's, a lane step's) of ``cfg``, compiled
    once a configuration for the module."""
    import jax

    from llama_fastapi_k8s_gpu_tpu.models.llama import forward

    if cfg not in _PROGRAMS:
        def pass_(params, toks, off, last, cache):
            return forward(params, cfg, toks, off, cache, last_idx=last)

        def step(params, tok, pos, cache):
            return forward(params, cfg, tok[None], pos, cache)

        def lane_step(params, toks, pos, caches, live):
            # (the body of parallel/batched.py's vmapped step, its bound
            # included)
            from llama_fastapi_k8s_gpu_tpu.parallel.batched import step_bound

            bound = step_bound(cfg, pos, live)
            return jax.vmap(
                lambda t, p, c, lv: forward(params, cfg, t[None], p, c,
                                            live=lv, kv_bound=bound),
                in_axes=(0, 0, 0, 0))(toks, pos, caches, live)

        _PROGRAMS[cfg] = tuple(jax.jit(f) for f in (pass_, step, lane_step))
    return _PROGRAMS[cfg]


def prefill(params, cfg, tokens, n_prompt, sizes=(16,), skip=True):
    """A prompt through slices of ``sizes`` (the last repeated), the
    program of each by whether it holds the prompt's last token as the
    engines choose it: (logits at the last position, the cache)."""
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.models.cache import cache_of
    from llama_fastapi_k8s_gpu_tpu.models.llama import init_cache

    kind = cache_of(cfg)
    cache, off, logits = init_cache(cfg), 0, None
    sizes = list(sizes)
    while off < n_prompt:
        n = sizes.pop(0) if len(sizes) > 1 else sizes[0]
        holds = off <= n_prompt - 1 < off + n
        scfg = kind.slice_cfg(cfg, holds or not skip)
        row = np.zeros(n, np.int32)
        real = tokens[off:min(off + n, n_prompt)]
        row[:len(real)] = real
        lg, cache = programs(scfg)[0](
            params, jnp.asarray(row), jnp.int32(off),
            jnp.int32(min(max(n_prompt - 1 - off, 0), n - 1)), cache)
        if holds:
            logits = np.asarray(lg)
        off += n
    return logits, cache


def serve(params, cfg, tokens, sizes=(16,), n_prompt=N_PROMPT, n_seq=N_SEQ,
          skip=True):
    """Slices then steps: (logits at positions n_prompt - 1 .. n_seq - 1,
    the cache)."""
    import jax.numpy as jnp

    logits, cache = prefill(params, cfg, tokens, n_prompt, sizes, skip)
    out = [logits]
    for t in range(n_prompt, n_seq):
        lg, cache = programs(cfg)[1](params, jnp.int32(tokens[t]),
                                     jnp.int32(t), cache)
        out.append(np.asarray(lg))
    return np.stack(out), cache


@pytest.fixture(scope="module")
def served(loaded, tokens):
    return serve(*loaded, tokens)


def leaves_equal(a: dict, b: dict) -> bool:
    return all(np.array_equal(np.asarray(a[k], np.float32),
                              np.asarray(b[k], np.float32)) for k in a)


# ---------------------------------------------------------------------------
# the stack against the reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sizes", [(16,), (32, 8), (8, 32, 16), (64,)], ids=[
    "narrow", "wide_then_narrow", "three_widths", "one_slice"])
def test_slices_of_two_widths_then_decode(loaded, tokens, want, sizes):
    """Prefill in slices (states, conv rows and windows carried across every
    slice's end, the last slice with padding), then steps through windows
    that wrap four times, against the float32 reference."""
    logits, _ = serve(*loaded, tokens, sizes)
    assert rel(logits, want[0][N_PROMPT - 1:N_SEQ - 0][:len(logits)]) < LIMIT
    worst = max(rel(logits[i], want[0][N_PROMPT - 1 + i])
                for i in range(len(logits)))
    assert worst < 2 * LIMIT


@pytest.mark.parametrize("control", [
    "float8", "no_lam", "flip_taps", "m_after_gate", "bfloat16_state"])
def test_another_function_fails_a_limit(ref, model, tokens, want, control):
    """Each control is a different function: told from the reference by
    the logits, or, where it changes the state-space layers alone (whose
    branch is small in a file of small random values), by ``m``."""
    import jax.numpy as jnp

    kw = {"float8": dict(emulate=jnp.float8_e4m3fn),
          "bfloat16_state": dict(state_dtype=jnp.bfloat16)}.get(
        control, {control: True})
    logits, _, m = (np.asarray(a) for a in ref.forward(*model, tokens, **kw))
    if control == "bfloat16_state":
        # 72 positions carry little rounding: told from float32 at 1e-4,
        # where the float32 state's own form reads 0
        assert rel(m, want[2]) > 1e-4
    elif control == "m_after_gate":
        assert rel(m, want[2]) > 0.5
    else:
        assert rel(logits, want[0]) > 3 * LIMIT


def test_bfloat16_inputs_pass_the_limit(ref, model, tokens, want, served):
    import jax.numpy as jnp

    logits = np.asarray(ref.forward(*model, tokens, emulate=jnp.bfloat16)[0])
    assert rel(logits, want[0]) < LIMIT
    assert rel(served[0], logits[N_PROMPT - 1:]) < LIMIT


def test_the_tap_hands_out_the_stream_and_the_memory(loaded, tokens, want):
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.models import phi4flash
    from llama_fastapi_k8s_gpu_tpu.models.llama import forward, init_cache

    params, cfg = loaded
    seen = []
    phi4flash.TAP = lambda h, m: seen.append((h, m))
    try:
        forward(params, cfg, jnp.asarray(tokens[:48], jnp.int32),
                jnp.int32(0), init_cache(cfg))
    finally:
        phi4flash.TAP = None
    h, m = seen[0]
    assert rel(np.asarray(h, np.float32), want[1][:48]) < LIMIT
    assert rel(m, want[2][:48]) < LIMIT


# ---------------------------------------------------------------------------
# the skip
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sizes", [(16,), (32, 8)], ids=["narrow", "widths"])
def test_the_skip_is_exact_in_logits_and_leaves(loaded, tokens, sizes):
    """A slice that holds no prompt's last token stops after the full
    layer: the last position's logits and EVERY cache leaf are those of the
    whole stack on every slice, to the bit, and so is every decode step."""
    with_, cache = serve(*loaded, tokens, sizes, n_seq=N_PROMPT + 6)
    without, whole = serve(*loaded, tokens, sizes, n_seq=N_PROMPT + 6,
                           skip=False)
    assert np.array_equal(with_, without)
    assert leaves_equal(cache, whole)


def test_the_lower_program_is_a_second_program_of_the_kind_alone(loaded):
    from llama_fastapi_k8s_gpu_tpu.models.cache import cache_of
    from llama_fastapi_k8s_gpu_tpu.models.config import LLAMA3_8B

    cfg = loaded[1]
    kind = cache_of(cfg)
    assert kind.slice_cfg(cfg, True) is cfg
    lower = kind.slice_cfg(cfg, False)
    assert lower.lower_only and lower != cfg
    ring = cache_of(LLAMA3_8B)
    assert ring.slice_cfg(LLAMA3_8B, False) is LLAMA3_8B


def test_a_lower_only_pass_returns_no_logits_anyone_reads(loaded, tokens):
    import jax.numpy as jnp

    params, cfg = loaded
    lower = dataclasses.replace(cfg, lower_only=True)
    from llama_fastapi_k8s_gpu_tpu.models.llama import init_cache

    lg, _ = programs(lower)[0](params, jnp.asarray(tokens[:16], jnp.int32),
                               jnp.int32(0), jnp.int32(15), init_cache(cfg))
    assert lg.shape == (cfg.vocab_size,) and not np.asarray(lg).any()


# ---------------------------------------------------------------------------
# the scan: kernel (interpret) = lax.scan = step by step
# ---------------------------------------------------------------------------

def _scan_inputs(S, C, N, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *s: rng.standard_normal(s).astype(np.float32)  # noqa: E731
    dt = np.log1p(np.exp(f(S, C) - 3.0))
    return (f(S, C), dt, f(S, N), f(S, N), -np.exp(0.3 * f(N, C)), f(C),
            f(3, N, C // 128, 128))


@pytest.mark.parametrize("S,C,N", [(16, 512, 4), (64, 2048, 16),
                                   (24, 1024, 16), (12, 512, 4)])
def test_the_scan_kernel_is_the_scan_is_the_steps(S, C, N):
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.models.phi4flash import selective_scan
    from llama_fastapi_k8s_gpu_tpu.ops.pallas.ssmscan import ssm_scan

    x, dt, b, c, a, d, leaf = _scan_inputs(S, C, N)
    y_k, new = ssm_scan(x, dt, b, c, a, d, jnp.asarray(leaf), 1, False,
                        interpret=True)
    s0 = leaf[1].reshape(N, C)
    y_s, s_s = selective_scan(*map(jnp.asarray, (x, dt, b, c, a, d, s0)))
    s, ys = s0.astype(np.float64), []
    for t in range(S):                      # step by step, in float64
        s = np.exp(dt[t][None] * a) * s + b[t][:, None] * (dt[t] * x[t])[None]
        ys.append((c[t][:, None] * s).sum(0) + d * x[t])
    for y, state in ((y_k, np.asarray(new[1]).reshape(N, C)), (y_s, s_s)):
        assert np.allclose(y, np.stack(ys), rtol=1e-4, atol=1e-4)
        assert np.allclose(state, s, rtol=1e-4, atol=1e-5)
    # the other layers' states are not touched
    assert np.array_equal(new[0], leaf[0]) and np.array_equal(new[2], leaf[2])


def test_the_scan_kernel_honours_n_valid_and_fresh():
    """Rows past the prompt's end arrive with dt = 0 and keep the state to
    the bit; a pass that starts its sequence starts from zero."""
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.ops.pallas.ssmscan import ssm_scan

    S, C, N, n_valid = 32, 512, 4, 19
    x, dt, b, c, a, d, leaf = _scan_inputs(S, C, N, seed=1)
    masked = np.where(np.arange(S)[:, None] < n_valid, dt, 0.0)
    _, padded = ssm_scan(x, masked, b, c, a, d, jnp.asarray(leaf), 2, False,
                         interpret=True)
    # the same rows alone, in a slice of 24 with its own padding
    cut = lambda v: np.concatenate(  # noqa: E731
        [v[:n_valid], np.ones((24 - n_valid, *v.shape[1:]), np.float32)])
    short = np.where(np.arange(24)[:, None] < n_valid, cut(dt), 0.0)
    _, alone = ssm_scan(cut(x), short, cut(b), cut(c), a, d,
                        jnp.asarray(leaf), 2, False, interpret=True)
    assert np.array_equal(padded[2], alone[2])
    _, fresh = ssm_scan(x, masked, b, c, a, d, jnp.asarray(leaf), 2, True,
                        interpret=True)
    _, zero = ssm_scan(x, masked, b, c, a, d,
                       jnp.asarray(np.zeros_like(leaf)), 2, False,
                       interpret=True)
    assert np.array_equal(fresh[2], zero[2])
    assert not np.array_equal(fresh[2], padded[2])


def test_the_stack_through_the_kernels_in_interpret_mode(loaded, tokens,
                                                         served):
    """The same file served as a TPU serves it: the scan kernel on the
    slices, the flash kernel on the slices' attention, the decode kernel
    (``wrap`` form on the windows, per-lane bound on the shared leaf)."""
    from llama_fastapi_k8s_gpu_tpu.models import phi4flash
    from llama_fastapi_k8s_gpu_tpu.models.llama import ring_write_impl

    params, cfg = loaded
    cfg = dataclasses.replace(cfg, attn_impl="pallas", ssm_scan_kernel=True)
    assert phi4flash.CACHE.decode_kernel_block(cfg) == N_CTX
    assert ring_write_impl(cfg) == "kernel"
    logits, cache = serve(params, cfg, tokens, n_seq=60)
    assert rel(logits, served[0][:len(logits)]) < 2e-2
    assert rel(np.asarray(cache["k"][:, :, :60], np.float32),
               np.asarray(served[1]["k"][:, :, :60], np.float32)) < 2e-2


def test_the_probe_of_the_scan_kernel_passes_in_interpret_mode():
    from llama_fastapi_k8s_gpu_tpu.ops.pallas.probe import probe_ssm_scan

    assert probe_ssm_scan() is None


# ---------------------------------------------------------------------------
# padding, freed lanes, dead lanes
# ---------------------------------------------------------------------------

def test_padding_rows_reach_no_state_conv_or_window_leaf(loaded, tokens):
    """A prompt of 21 tokens in slices of 16 (11 rows of padding in the
    second) leaves the state, the conv rows and the window leaves of the
    same prompt in slices of 3 (no padding): to the bit where the
    arithmetic is row by row, and the next step's logits agree."""
    params, cfg = loaded
    _, padded = prefill(params, cfg, tokens, 21, (16,))
    _, exact = prefill(params, cfg, tokens, 21, (3,))
    for leaf in ("conv", "kw", "vw"):
        assert np.allclose(np.asarray(padded[leaf], np.float32),
                           np.asarray(exact[leaf], np.float32), atol=3e-2)
    assert rel(padded["state"], exact["state"]) < 1e-2
    # garbage tokens in the padding rows change nothing
    noisy = tokens.copy()
    noisy[21:32] = 7
    _, other = prefill(params, cfg, noisy, 21, (16,))
    for leaf in ("state", "conv", "kw", "vw"):
        assert np.array_equal(np.asarray(padded[leaf], np.float32),
                              np.asarray(other[leaf], np.float32)), leaf


def test_a_pass_at_position_0_starts_from_zero(loaded, tokens):
    """Admission prefills a scratch cache from position 0 and installs all
    of it in the lane: whatever the scratch held, the result is the same."""
    import jax
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.models.llama import init_cache

    params, cfg = loaded
    garbage = jax.tree.map(lambda a: a + 1, init_cache(cfg))
    row = jnp.asarray(tokens[:16], jnp.int32)
    lg0, clean = programs(cfg)[0](params, row, jnp.int32(0), jnp.int32(15),
                                  init_cache(cfg))
    lg1, dirty = programs(cfg)[0](params, row, jnp.int32(0), jnp.int32(15),
                                  garbage)
    assert np.array_equal(np.asarray(lg0), np.asarray(lg1))
    for leaf in ("state", "conv"):
        assert np.array_equal(np.asarray(clean[leaf], np.float32),
                              np.asarray(dirty[leaf], np.float32))


def test_a_dead_lanes_leaves_are_untouched_and_a_live_lanes_logits_are_its_own(
        loaded, tokens, served):
    import jax
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.models.llama import init_cache

    params, cfg = loaded
    _, mine = prefill(params, cfg, tokens, N_PROMPT)
    garbage = jax.tree.map(lambda a: a + 1, init_cache(cfg))

    def run(other, other_pos, other_live):
        stacked = jax.tree.map(lambda *a: jnp.stack(a), mine, other)
        lg, after = programs(cfg)[2](
            params, jnp.asarray([tokens[N_PROMPT], 7], jnp.int32),
            jnp.asarray([N_PROMPT, other_pos], jnp.int32), stacked,
            jnp.asarray([True, other_live]))
        return np.asarray(lg[0]), after

    base, after = run(garbage, 40, False)
    # (the XLA forms store a dead lane's K and V row, as the ring's do: the
    # kernels store nothing; what INTEGRATES is never touched)
    for leaf in ("state", "conv"):
        assert np.array_equal(np.asarray(after[leaf][1], np.float32),
                              np.asarray(garbage[leaf], np.float32)), leaf
    assert not np.array_equal(np.asarray(after["state"][0]),
                              np.asarray(mine["state"]))
    assert np.array_equal(run(garbage, 90, True)[0], base)
    assert rel(base, served[0][1]) < 3e-2


# ---------------------------------------------------------------------------
# differential attention on packed rows; windows that wrap
# ---------------------------------------------------------------------------

def test_one_pass_on_packed_rows_is_the_four_softmax_form():
    """A query laid into its key's 64 columns of a zero row of 128, against
    rows ``[k1 | k2]`` / ``[v1 | v2]``: ONE softmax a head gives ``a1`` (or
    ``a2``) whole, both value halves; the naive form is four softmaxes a
    pair."""
    import jax
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.models.phi4flash import pack_queries

    rng = np.random.default_rng(2)
    S, H, K, d = 12, 8, 4, 64
    q = rng.standard_normal((S, H, d)).astype(np.float32)
    k = rng.standard_normal((S, K, d)).astype(np.float32)
    v = rng.standard_normal((S, K, d)).astype(np.float32)
    mask = np.tril(np.ones((S, S), bool))

    def soft(qh, kh, vh):
        s = jnp.where(mask, qh @ kh.T * d ** -0.5, -jnp.inf)
        return jax.nn.softmax(s, -1) @ vh

    naive = []
    for p in range(H // 2):                   # query pair p, KV pair p // 2
        j = p // ((H // 2) // (K // 2))
        k1, k2, v1, v2 = k[:, 2 * j], k[:, 2 * j + 1], v[:, 2 * j], \
            v[:, 2 * j + 1]
        naive.append(jnp.concatenate(
            [soft(q[:, 2 * p], k1, v1), soft(q[:, 2 * p], k1, v2)], -1))
        naive.append(jnp.concatenate(
            [soft(q[:, 2 * p + 1], k2, v1), soft(q[:, 2 * p + 1], k2, v2)],
            -1))
    qp = np.asarray(pack_queries(jnp.asarray(q)))        # (S, H, 128)
    kp, vp = k.reshape(S, K // 2, 2 * d), v.reshape(S, K // 2, 2 * d)
    group = H // (K // 2)
    for h in range(H):
        one = soft(qp[:, h], kp[:, h // group], vp[:, h // group])
        assert np.allclose(one, naive[h], atol=1e-5), h


def test_the_windows_wrap_and_the_shared_leaf_does_not(loaded, served, want):
    """After 72 positions a window leaf of 16 slots has wrapped four times
    and holds the last 16 positions' rows; the shared leaf holds all 72;
    every step's logits stood against the reference on the way."""
    params, cfg = loaded
    logits, cache = served
    assert cache["kw"].shape == (2, 1, 16, 128)
    assert cache["k"].shape == (1, 1, N_CTX, 128)
    assert cache["state"].shape == (3, 4, 4, 128)
    assert cache["conv"].shape == (3, 3, 512)
    assert np.asarray(cache["k"][0, 0, N_SEQ:], np.float32).any() == 0
    assert np.asarray(cache["k"][0, 0, :N_SEQ], np.float32).all(axis=1).all()
    steps = [rel(logits[i], want[0][N_PROMPT - 1 + i])
             for i in range(len(logits))]
    assert max(steps[-16:]) < 2 * LIMIT


# ---------------------------------------------------------------------------
# K = 2560 on the fused kernels; the tied head
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k,stored,tail", [
    (2560, 2560, 512), (3072, 3072, 1024), (5120, 5120, 1024),
    (4096, 4096, 0), (7168, 8192, 0), (11008, 12288, 0), (2304, 2304, 0),
    (1536, 1536, 0), (512, 512, 0)])
def test_the_fill_rule_is_narrow(k, stored, tail):
    """By shape alone: a multiple of the tile as it is; a K above one tile,
    a multiple of 512, that filling would widen by a fifth or more, stored
    at K and ending in a TAIL tile; anything else as it always was."""
    from llama_fastapi_k8s_gpu_tpu.ops.linear import padded_k
    from llama_fastapi_k8s_gpu_tpu.ops.pallas.qmatmul import tail_of

    assert padded_k(k) == stored
    assert tail_of(k) == tail


def test_an_expert_matrix_keeps_its_own_rule():
    from llama_fastapi_k8s_gpu_tpu.ops.pallas.experts import padded_k

    assert padded_k(2560) == 2560 and padded_k(1536) == 2048


def _quantized(fmt, n, k, seed=0):
    """(raw blocks, dequantized (n, k)) of a random matrix in ``fmt``."""
    from llama_fastapi_k8s_gpu_tpu.gguf import quants
    from llama_fastapi_k8s_gpu_tpu.gguf.constants import GGMLType

    rng = np.random.default_rng(seed)
    w = (rng.standard_normal((n, k)) * k ** -0.5).astype(np.float32)
    t = {"q4k": GGMLType.Q4_K, "q6k": GGMLType.Q6_K}[fmt]
    raw = quants.quantize(w, t)
    return raw, quants.dequantize(raw, t, n * k).reshape(n, k)


def _filled(raw, n, k, k_pad):
    raw = np.asarray(raw).reshape(n, -1)
    return np.pad(raw, ((0, 0), (0, raw.shape[1] * (k_pad - k) // k))
                  ).reshape(-1)


def _fused_product(fmt, x, w, stacked):
    """``x`` through the fused call of ``w``'s layout: the unstacked call
    (for Q6_K the HEAD's integer body) or the stacked one on a stack of two,
    layer 1."""
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.ops.linear import linear, linear_at

    if not stacked:
        return np.asarray(linear(x, w), np.float32)
    ws = {key: jnp.stack([jnp.zeros_like(a), a]) for key, a in w.items()}
    return np.asarray(linear_at(x, ws, 1), np.float32)


@pytest.mark.parametrize("k", [2560, 5120])
@pytest.mark.parametrize("stacked", [False, True], ids=["plain", "stacked"])
@pytest.mark.parametrize("rows", [3, 300], ids=["few_rows", "many_rows"])
@pytest.mark.parametrize("fmt", ["q4k", "q6k"])
def test_k_2560_on_the_fused_matmuls_against_the_oracle(fmt, rows, stacked,
                                                        k):
    """The FILE's own blocks in the TAIL layout (the whole 2048 tiles as
    they always were, the last 512 / 1024 columns a narrow tile of the same
    layout) through the fused kernels, float bodies and the head's integer
    one: the dequantized matrix's product (gguf/quants.py is the oracle),
    nothing requantized, and the product of the same blocks with each row's
    last tile FILLED UP with zero blocks, up to the order of a tile's
    float32 sums."""
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.ops.linear import padded_k
    from llama_fastapi_k8s_gpu_tpu.ops.pallas.q6matmul import prep_q6k
    from llama_fastapi_k8s_gpu_tpu.ops.pallas.qmatmul import prep_q4k

    n, k_fill = 256, -(-k // 2048) * 2048
    raw, deq = _quantized(fmt, n, k)
    prep = {"q4k": prep_q4k, "q6k": prep_q6k}[fmt]
    assert padded_k(k) == k
    w = prep(raw, n, k)
    assert {"q4k": "qs_t", "q6k": "q4_t"}[fmt] in w
    # (bfloat16 values held as float32: the calls then return their float32
    # sums unrounded, which is where the two layouts may differ)
    x = jnp.asarray(np.random.default_rng(1).standard_normal((rows, k)),
                    jnp.bfloat16).astype(jnp.float32)
    got = _fused_product(fmt, x, w, stacked)
    oracle = np.asarray(x) @ deq.T
    assert rel(got, oracle) < 1e-2
    filled = _fused_product(
        fmt, jnp.pad(x, ((0, 0), (0, k_fill - k))),
        prep(_filled(raw, n, k, k_fill), n, k_fill), stacked)
    assert np.abs(got - filled).max() <= 1e-5 * np.abs(filled).max()


#: sha256[:12] of the prepared planes at the PARENT commit (f9a3546)
PLANES = {("q4k", 4096): "2ceb40ca024e", ("q4k", 7168): "9ac847c7e664",
          ("q6k", 4096): "4c6d0ff72fde", ("q6k", 7168): "951bace9dff9"}


@pytest.mark.parametrize("fmt,k", [
    ("q4k", 4096), ("q6k", 4096), ("q4k", 7168), ("q6k", 7168)])
def test_the_planes_of_other_widths_are_what_they_were(fmt, k):
    """The tail tile moves no other matrix: the prepared planes of a K
    = 4096 and a K = 7168 (-> 8192) matrix hash to what PR 62's parent's
    ``prep_*`` gave on its ``padded_k`` (the digests below were taken
    there, and PR 63 left them as they were)."""
    from llama_fastapi_k8s_gpu_tpu.ops.linear import padded_k
    from llama_fastapi_k8s_gpu_tpu.ops.pallas.q6matmul import prep_q6k
    from llama_fastapi_k8s_gpu_tpu.ops.pallas.qmatmul import prep_q4k

    n = 128
    raw, _ = _quantized(fmt, n, k, seed=7)
    prep = {"q4k": prep_q4k, "q6k": prep_q6k}[fmt]
    k_pad = padded_k(k)
    assert k_pad == {4096: 4096, 7168: 8192}[k]
    w = prep(_filled(raw, n, k, k_pad), n, k_pad)
    h = hashlib.sha256()
    for key in sorted(w):
        h.update(key.encode())
        h.update(np.asarray(w[key]).tobytes())
    assert h.hexdigest()[:12] == PLANES[(fmt, k)]


def test_the_tied_q6k_head_is_the_dequantized_rows():
    """ONE stored tensor: the head's fused Q6_K planes (K 2560: a whole
    tile and a tail of 512) give the logits, and the embedding lookup
    (``q6k-rows``) dequantizes the rows it gathers from the same planes,
    tail and all: both are the file's matrix."""
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.models.phi4flash import embed
    from llama_fastapi_k8s_gpu_tpu.ops.linear import linear, padded_k
    from llama_fastapi_k8s_gpu_tpu.ops.pallas.q6matmul import prep_q6k

    n, k = 384, 2560
    raw, deq = _quantized("q6k", n, k, seed=3)
    assert padded_k(k) == k
    w = prep_q6k(raw, n, k)
    assert w["q4_t"].shape == (n, 256) and w["q2_t"].shape == (n, 128)
    params = {"tok_emb": w, "output": w}
    ids = jnp.asarray([0, 5, 383, 5, 77], jnp.int32)
    rows = np.asarray(embed(params, ids, k), np.float32)
    assert rows.shape == (5, k)
    # (the planes hold d * sc rounded to bfloat16; the rows are bfloat16)
    assert rel(rows, deq[np.asarray(ids)]) < 1e-2
    h = jnp.asarray(np.random.default_rng(4).standard_normal((2, k)),
                    jnp.bfloat16)
    logits = np.asarray(linear(h, params["output"]), np.float32)
    all_rows = np.asarray(embed(params, jnp.arange(n), k), np.float32)
    assert rel(logits, np.asarray(h, np.float32) @ all_rows.T) < 1e-2


@pytest.mark.parametrize("k", [2560, 5120, 4096])
def test_the_row_lookup_follows_the_layout(k):
    """``q6k-rows``: the gathered rows of the head's planes, dequantized and
    put back in the FILE's column order, whole tiles and tail: the file's
    weights with ``d x sc`` rounded to bfloat16 (2^-9), element by
    element."""
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.ops.pallas.q6matmul import (
        dequant_rows6, prep_q6k)

    n = 128
    raw, deq = _quantized("q6k", n, k, seed=11)
    w = prep_q6k(raw, n, k)
    assert ("q4_t" in w) == (k != 4096)
    ids = np.asarray([127, 0, 64, 0])
    rows = np.asarray(dequant_rows6(w, jnp.asarray(ids), k))
    assert rows.shape == (4, k)
    assert (np.abs(rows - deq[ids]) <= 2.0 ** -8 * np.abs(deq[ids])).all()


# ---------------------------------------------------------------------------
# the file, the cache's size, refusals, counters
# ---------------------------------------------------------------------------

def test_gguf_round_trip_of_the_keys_and_the_layer_kinds(loaded):
    from llama_fastapi_k8s_gpu_tpu.models import phi4flash
    from llama_fastapi_k8s_gpu_tpu.models.cache import cache_of
    from llama_fastapi_k8s_gpu_tpu.testing import TINY_PHI4FLASH_CFG as T

    params, cfg = loaded
    assert cfg.cache_kind == "ssm-state+window+shared-ring"
    assert cache_of(cfg) is phi4flash.CACHE
    assert cfg.mixers == T.mixers and cfg.tie_embeddings
    assert (cfg.ssm_d_inner, cfg.ssm_d_state, cfg.ssm_d_conv,
            cfg.ssm_dt_rank) == (512, 4, 4, 16)
    assert (cfg.sliding_window, cfg.window_slots, cfg.head_dim) == (8, 16, 64)
    assert phi4flash.n_pairs(cfg) == (2, 2)
    assert {k: next(iter(v.values())).shape[0] if isinstance(
        next(iter(v.values())), dict) else next(iter(v.values())).shape[0]
        for k, v in params["layers"].items()} == {
        "ssm": 3, "attn": 3, "gmu": 2, "cross": 2, "ffn": 10}
    assert params["layers"]["ssm"]["a"].shape == (3, 4, 512)
    assert (np.asarray(params["layers"]["ssm"]["a"]) < 0).all()
    assert "out_norm_b" in params and params["output"]["w"] is params["tok_emb"]


def test_offsets_from_the_initialisation_fold_at_load(tmp_path, ref):
    """A file of small random ``ssm_a`` / ``ssm_dt.bias`` that says
    ``ssm.values = init_offsets`` loads with A = -exp(log(n + 1) + a) and
    step sizes spread over 1e-3..1e-1, in program and reference alike."""
    from llama_fastapi_k8s_gpu_tpu.gguf import GGUFFile
    from llama_fastapi_k8s_gpu_tpu.models.config import ModelConfig
    from llama_fastapi_k8s_gpu_tpu.models.params import load_params
    from llama_fastapi_k8s_gpu_tpu.testing import write_tiny_phi4flash_gguf

    path = str(tmp_path / "offsets.gguf")
    write_tiny_phi4flash_gguf(path, seed=3, values="init_offsets")
    gf = GGUFFile(path)
    cfg = ModelConfig.from_gguf(gf, n_ctx=N_CTX)
    w = load_params(gf, cfg, "bf16")["layers"]["ssm"]
    a, b_dt = np.asarray(w["a"]), np.asarray(w["dt_b"])
    assert np.allclose(np.median(-a, axis=2)[0], [1, 2, 3, 4], rtol=0.1)
    dt0 = np.log1p(np.exp(b_dt))
    assert 5e-4 < dt0.min() < 2e-3 and 5e-2 < dt0.max() < 2e-1
    hp, tensors = ref.open_model(path)
    ra, rb = ref.ssm_values(hp, {
        name: ref.tensor(tensors, "blk.0." + name)
        for name in ("ssm_a", "ssm_dt.bias")})
    assert np.allclose(ra.T, a[0], rtol=1e-6)
    assert np.allclose(rb, b_dt[0], rtol=1e-6)


def published_cfg():
    from llama_fastapi_k8s_gpu_tpu.models.config import ModelConfig

    return ModelConfig(
        vocab_size=200064, dim=2560, n_layers=32, n_heads=40, n_kv_heads=20,
        ffn_dim=10240, n_ctx=32768, head_width=64, sliding_window=512,
        mixers=("ssm", "window") * 8 + ("ssm", "full") + ("gmu", "cross") * 7,
        ssm_d_inner=5120, ssm_d_state=16, ssm_d_conv=4, ssm_dt_rank=160,
        tie_embeddings=True)


def test_a_lanes_cache_at_the_published_sizes_is_192_mb():
    from llama_fastapi_k8s_gpu_tpu.models import phi4flash

    cfg = published_cfg()
    assert phi4flash.state_nbytes(cfg) == 9 * 5120 * (16 * 4 + 3 * 2)
    assert phi4flash.cache_nbytes(cfg) == 5120 * (32768 + 8 * 512) \
        + 3225600 == 191969280
    assert phi4flash.layers_run(cfg) == 18


@pytest.mark.parametrize("n_ctx", [128, 512])
def test_the_cache_is_what_cache_nbytes_says(loaded, n_ctx):
    from llama_fastapi_k8s_gpu_tpu.models import phi4flash
    from llama_fastapi_k8s_gpu_tpu.models.llama import init_cache

    cfg = dataclasses.replace(loaded[1], n_ctx=n_ctx)
    cache = init_cache(cfg)
    assert sum(a.nbytes for a in cache.values()) == phi4flash.cache_nbytes(cfg)


@pytest.mark.parametrize("meta, words", [
    ({"phi4flash.mixer_types": "ssm,window,full"}, "must name one of"),
    ({"phi4flash.mixer_types": ",".join(
        ("window", "ssm") * 2 + ("ssm", "full") + ("gmu", "cross") * 2)},
     "the block here is"),
    ({"phi4flash.ssm.state_size": 0}, "the file lacks"),
    ({"phi4flash.attention.head_count_kv": 3}, "differential attention"),
])
def test_a_file_the_block_cannot_compute_is_refused_by_name(gguf_path, meta,
                                                            words):
    from llama_fastapi_k8s_gpu_tpu.gguf import GGUFFile
    from llama_fastapi_k8s_gpu_tpu.models.config import ModelConfig

    gf = GGUFFile(gguf_path)
    gf.metadata.update(meta)
    with pytest.raises(ValueError, match=words):
        ModelConfig.from_gguf(gf, n_ctx=N_CTX)


def test_the_new_metrics_are_in_the_catalog():
    from llama_fastapi_k8s_gpu_tpu.models import phi4flash
    from llama_fastapi_k8s_gpu_tpu.obs.catalog import METRICS

    for name in phi4flash.CACHE.own_gauges:
        assert name.split("{")[0] in METRICS, name


def test_the_counters_count_what_the_steps_and_slices_do(loaded):
    from llama_fastapi_k8s_gpu_tpu.models import phi4flash

    cfg = loaded[1]
    kind = phi4flash.CACHE
    c = kind.new_counts()
    # two lanes wanted of three dispatched live, of four in the batch
    kind.note_decode(c, cfg, [20, 40], 4, [20, 40, 9])
    kind.note_lanes(c, cfg, 4, 4)
    assert c["state_updates"] == 2 * 4 * 3 and c["state_steps"] == 4 * 4 * 3
    assert c["shared_reads"] == 2 * 4 * 3 and c["shared_steps"] == 2 * 4
    assert c["window_read"] == 2 * 4 * 2 * 16
    assert c["window_live"] == 2 * 4 * 2 * 8
    # the XLA loop reads up to the largest dispatched position, a reader
    assert c["shared_live"] == 3 * sum(p + t + 1 for p in (20, 40)
                                       for t in range(4))
    assert c["shared_read"] >= c["shared_live"]
    assert c["read"] == c["window_read"] + c["shared_read"]
    g = kind.gauges(c)
    assert g["ssm_state_updates_total"] / g["ssm_state_steps_total"] == 0.5
    assert g["shared_leaf_reads_total"] / g["shared_leaf_steps_total"] == 3
    # a prompt of 40 in slices of 16: two lower programs, one whole
    lower = kind.slice_cfg(cfg, False)
    run = [kind.note_slice(c, lower, 16), kind.note_slice(c, lower, 16),
           kind.note_slice(c, cfg, 16)]
    assert run == [16 * 6, 16 * 6, 16 * 6 + 4]
    assert c["layer_rows_run"] == sum(run)
    assert c["layer_rows_skipped"] == 48 * 10 - sum(run)
    assert (c["slices_lower"], c["slices_whole"]) == (2, 1)
    attrs = kind.note_prefill(c, cfg, 40, [(0, 16), (16, 16), (32, 16)])
    assert c["state_starts"] == 1
    assert attrs["slices_lower_only"] == 2 and attrs["layers_run"] == [6, 10]
    assert attrs["layer_rows_skipped"] == 4 * 47
    assert attrs["windows_wrapped"] == 2


# ---------------------------------------------------------------------------
# the engines and the server: ONE lane engine for the module
# ---------------------------------------------------------------------------

SYSTEM = "you are a careful assistant who answers in short plain sentences"
MSGS = [{"role": "system", "content": SYSTEM},
        {"role": "user", "content": "tell me about selective scans"}]
MSGS2 = [{"role": "system", "content": SYSTEM},
         {"role": "user", "content": "and what does one shared leaf hold"}]


@pytest.fixture(scope="module")
def lane_engine(gguf_path):
    from llama_fastapi_k8s_gpu_tpu.engine.continuous import ContinuousEngine

    eng = ContinuousEngine(gguf_path, n_ctx=N_CTX * 4, prefill_chunk=16,
                           decode_chunk=4, batch_size=2)
    yield eng
    eng.shutdown()


@pytest.mark.parametrize("feature, setting", [
    ("int8", "LFKT_KV_DTYPE=int8"), ("paged", "LFKT_KV_PAGED=1")])
def test_what_the_kind_cannot_serve_is_refused_by_name(gguf_path, feature,
                                                       setting):
    from llama_fastapi_k8s_gpu_tpu.engine import Engine

    kw = {"int8": dict(kv_dtype="int8"), "paged": dict(kv_paged=True)}[feature]
    with pytest.raises(ValueError, match=f"{setting} cannot serve "
                                         "architecture 'phi4flash'"):
        Engine(gguf_path, n_ctx=N_CTX, **kw)


def test_the_kind_takes_any_slice_width_and_rolls_nothing_back(loaded):
    from llama_fastapi_k8s_gpu_tpu.models import phi4flash
    from llama_fastapi_k8s_gpu_tpu.ops.pallas.ssmscan import (
        scan_compatible, time_chunk)

    kind = phi4flash.CACHE
    assert kind.slice_rule(loaded[1], 12) is None and not kind.rolls_back
    assert kind.always_slices and kind.kernel_writes
    assert scan_compatible(512) and scan_compatible(5120)
    assert not scan_compatible(1536 + 64)
    assert [time_chunk(n) for n in (1024, 256, 12, 7)] == [256, 256, 4, 1]


@pytest.mark.parametrize("engine", ["serial", "lanes"])
def test_an_engine_against_the_reference(gguf_path, lane_engine, ref, model,
                                         engine):
    """A request through the engine's own slice plan (wide 64 then narrow
    16, the lower program on every slice but the last) and its decode
    chunks: the greedy tokens are the argmax of the reference's logits on
    the tokens the engine fed, wherever the reference's margin is clear."""
    from llama_fastapi_k8s_gpu_tpu.engine import Engine

    long = [{"role": "system", "content": SYSTEM},
            {"role": "user", "content": "count the waves " * 14}]
    if engine == "serial":
        eng = Engine(gguf_path, n_ctx=N_CTX * 4, prefill_chunk=16,
                     decode_chunk=4)
        out = eng.create_chat_completion(long, max_tokens=10, temperature=0.0)
    else:
        eng = lane_engine
        out = eng.submit(long, max_tokens=10, temperature=0.0).result(
            timeout=600)
    ids = eng.tokenize_messages(long)
    assert out["usage"]["prompt_tokens"] == len(ids) > 64 + 16
    fed = eng.tokenizer.encode(out["choices"][0]["message"]["content"],
                               add_bos=False) \
        if hasattr(eng.tokenizer, "encode") else []
    g = eng.cache_read_gauges()
    assert g['prefill_programs_total{stack="lower"}'] >= 2
    assert g['prefill_programs_total{stack="whole"}'] >= 1
    share = g["prefill_layer_rows_skipped_total"] / (
        g["prefill_layer_rows_skipped_total"]
        + g["prefill_layer_rows_run_total"])
    assert 0.35 < share <= 0.4           # 4 of 10 layers, nearly every row
    logits = np.asarray(ref.forward(*model, list(ids) + list(fed))[0])
    first = int(np.argmax(logits[len(ids) - 1]))
    top2 = np.sort(logits[len(ids) - 1])[-2:]
    if fed and top2[1] - top2[0] > 0.3:
        assert fed[0] == first


def test_lanes_freed_and_taken_again_give_the_serial_engines_text(
        gguf_path, lane_engine):
    """Three requests on two lanes: a lane is freed and taken again, and a
    request gives the same greedy text whichever lane it took and whatever
    that lane held before (its prefill started from zero states)."""
    from llama_fastapi_k8s_gpu_tpu.engine import Engine

    serial = Engine(gguf_path, n_ctx=N_CTX * 2, prefill_chunk=16,
                    decode_chunk=4)
    want = serial.create_chat_completion(MSGS, max_tokens=6, temperature=0.0)
    kind = serial.cache_kind
    assert kind["kind"] == "ssm-state+window+shared-ring"
    assert (kind["ssm_layers"], kind["window_layers"],
            kind["shared_leaf_readers"]) == (3, 2, 3)
    assert kind["prefill_layers"] == [6, 10] and kind["embedding"] == "bf16"
    assert kind["prefix_reuse"].startswith("off: a state")
    assert not serial._prefix_cache and serial.cfg.attn_impl == "xla"
    assert serial.cache_engine_health == {"ssm_scan": "xla"}
    g = serial.cache_read_gauges()
    assert g["ssm_state_starts_total"] == 1
    assert g["ssm_state_updates_total"] == g["ssm_state_steps_total"] > 0
    assert g["shared_leaf_reads_total"] == 3 * g["shared_leaf_steps_total"]
    assert 0 < g["ring_slots_live_total"] <= g["ring_slots_read_total"]
    eng = lane_engine
    assert not eng._lane_prefix
    before = eng.cache_read_gauges()["ssm_state_starts_total"]
    futs = [eng.submit(m, max_tokens=6, temperature=0.0)
            for m in (MSGS, MSGS2, MSGS)]
    outs = [f.result(timeout=600) for f in futs]
    for o in (outs[0], outs[2]):
        assert o["usage"]["prompt_tokens"] == want["usage"]["prompt_tokens"]
    assert outs[2]["choices"][0]["message"] == outs[0]["choices"][0]["message"]
    assert not eng.scheduler_stats().get("lane_prefix_hits")
    g = eng.cache_read_gauges()
    assert g["ssm_state_starts_total"] == before + 3
    # two lanes step whether they hold a request or not
    assert 0 < g["ssm_state_updates_total"] <= g["ssm_state_steps_total"]


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
            eng = (await client.get("/health")).json()["engine"]
            assert eng["cache"]["kind"] == "ssm-state+window+shared-ring"
            assert eng["cache"]["shared_leaf_readers"] == 3
            assert eng["ssm_scan"] == "xla" and eng["ring_write"] == "xla"
            assert set(eng["weight_formats"]) == {
                "ssm.in_proj", "ssm.out_proj", "ssm.x_proj", "attn.wq",
                "attn.wk", "attn.wv", "attn.wo", "gmu.in_proj",
                "gmu.out_proj", "cross.wq", "cross.wo", "ffn.w_gate",
                "ffn.w_up", "ffn.w_down"}
            d = (await client.get("/debug/compiles")).json()
            assert not d.get("degrades")
            m = (await client.get("/metrics")).text
            for name in ("ssm_state_updates_total", "ssm_state_steps_total",
                         "ssm_state_starts_total", "shared_leaf_reads_total",
                         "shared_leaf_steps_total",
                         "prefill_layer_rows_skipped_total",
                         "prefill_layer_rows_run_total",
                         'prefill_programs_total{stack="lower"}',
                         "window_slots_read_total", "ring_slots_read_total"):
                assert name in m, name
        await app.router.shutdown()


@pytest.mark.anyio
@pytest.mark.parametrize("ffn_dim,d_inner,share", [
    (2560, 2560, "none"), (1792, 2048, "some")], ids=["tail", "filled"])
async def test_health_names_the_formats_and_the_fill_share(
        tmp_path, ffn_dim, d_inner, share):
    """``/health`` of a tiny file whose ``ffn_down`` / ``ssm_out`` /
    ``gmu_out`` have a K the fused kernels take (2560: a whole tile and a
    tail, nothing filled; 1792: filled up to 2048): every ``weight_formats``
    entry the published configuration's ``expect_health`` lists is there,
    those that fuse here under the names it expects, ``head_kernel`` is
    reported, and ``weight_fill_share`` is the loader's sum (``/metrics``
    has the same number)."""
    import json

    import httpx

    from llama_fastapi_k8s_gpu_tpu.engine import Engine
    from llama_fastapi_k8s_gpu_tpu.server.app import create_app
    from llama_fastapi_k8s_gpu_tpu.testing import (
        PHI4FLASH_Q4KM_MIX, TINY_PHI4FLASH_CFG, write_tiny_phi4flash_gguf)
    from llama_fastapi_k8s_gpu_tpu.utils.config import Settings

    with open(os.path.join(
            REPO, "benchmarks", "configs",
            "phi4-mini-flash-3.8b-q4km-16lane.json")) as fh:
        expect = json.load(fh)["expect_health"]
    path = str(tmp_path / "wide.gguf")
    write_tiny_phi4flash_gguf(
        path, dataclasses.replace(TINY_PHI4FLASH_CFG, ffn_dim=ffn_dim,
                                  ssm_d_inner=d_inner),
        mix=PHI4FLASH_Q4KM_MIX)
    engine = Engine(path, n_ctx=128, prefill_chunk=16, weight_format="q4k")
    if share == "none":     # the tail's planes serve a request end to end
        out = engine.create_chat_completion(MSGS, max_tokens=2,
                                            temperature=0.0)
        assert out["usage"]["prompt_tokens"] > 16   # (two slices and more)
        assert "q4_t" in engine.params["layers"]["ffn"]["w_down"]
        assert "qs_t" in engine.params["layers"]["ssm"]["out_proj"]
    app = create_app(engine=engine, settings=Settings())
    transport = httpx.ASGITransport(app=app)
    async with transport:
        await app.router.startup()
        async with httpx.AsyncClient(transport=transport,
                                     base_url="http://test") as client:
            eng = (await client.get("/health")).json()["engine"]
            metrics = (await client.get("/metrics")).text
    fmts = eng["weight_formats"]
    assert set(expect["weight_formats"]) <= set(fmts)
    for name in ("ffn.w_down", "ssm.out_proj", "gmu.out_proj"):
        assert fmts[name] == expect["weight_formats"][name]
    assert eng["head_kernel"] in (expect["head_kernel"], "bf16")
    fill = eng["weight_fill_share"]
    assert fill == 0.0 if share == "none" else 5.0 < fill < 12.5
    line = [ln for ln in metrics.splitlines()
            if ln.startswith("weight_fill_share ")]
    assert len(line) == 1 and float(line[0].split()[1]) == fill
