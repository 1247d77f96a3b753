"""The ``jamba`` block (models/jamba.py) at a tiny size on the CPU, against
the plain float32 reference (benchmarks/reference_jamba.py): Mamba-1 layers
with RMSNorms on dt, B and C (the mixer of models/mamba.py, shared with
``phi4flash``) beside unrotated attention layers of several query heads on
ONE K/V head, over the eighth cache kind (``ssm-state+ring``), RMSNorms, a
tied head.

The tiny file (``testing.TINY_JAMBA_CFG``) cuts the period of 14 to 5: nine
layers ssm ssm attn ssm ssm | ssm ssm attn ssm, so scan runs of 2, 4 and 1
lie before, between and after two attention layers; 5 query heads of 128 on
1 KV head (a group that is no multiple of 8), 512 channels of 4 states, 4
taps, a dt rank of 16.  ONE file and one lane engine serve the whole module.

LIMIT: the program (bf16 inputs to every product, float32 sums and states,
a bf16 stream) against the float32 reference reads 2-3 % of the logits'
norm (the reference with bf16 inputs as much); every control reads over
three times the limit.
"""

from __future__ import annotations

import dataclasses
import os
import sys

import numpy as np
import pytest

from tests.test_phi4flash import (
    _scan_inputs, prefill, programs, rel, serve as _serve)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")

LIMIT = 6e-2
N_CTX = 128
N_PROMPT = 45
N_SEQ = 72


def serve(params, cfg, tokens, sizes=(16,), n_prompt=N_PROMPT, n_seq=N_SEQ):
    return _serve(params, cfg, tokens, sizes, n_prompt, n_seq)


@pytest.fixture(scope="module")
def ref():
    """The reference, with each layer's tensors dequantized once for the
    module (it dequantizes them at every call)."""
    sys.path.insert(0, BENCH)
    try:
        import reference_jamba
        plain, kept = reference_jamba.layer_weights, {}
        reference_jamba.layer_weights = lambda tensors, i: kept.get(i) \
            or kept.setdefault(i, plain(tensors, i))
        yield reference_jamba
        reference_jamba.layer_weights = plain
    finally:
        sys.path.remove(BENCH)


@pytest.fixture(scope="module")
def gguf_path(tmp_path_factory):
    from llama_fastapi_k8s_gpu_tpu.testing import write_tiny_jamba_gguf

    path = str(tmp_path_factory.mktemp("jamba") / "tiny.gguf")
    write_tiny_jamba_gguf(path, seed=3)
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
    """The float32 reference's logits (S, V) over the whole sequence."""
    return np.asarray(ref.forward(*model, tokens))


@pytest.fixture(scope="module")
def served(loaded, tokens):
    return serve(*loaded, tokens)


# ---------------------------------------------------------------------------
# the stack against the reference
# ---------------------------------------------------------------------------

def test_the_reference_imports_nothing_from_the_package():
    import ast

    names = set()
    for mod in ("reference_jamba.py", "reference_phi4flash.py",
                "reference_mla.py", "reference.py"):
        with open(os.path.join(BENCH, mod)) as f:
            for node in ast.walk(ast.parse(f.read())):
                if isinstance(node, ast.Import):
                    names |= {a.name for a in node.names}
                elif isinstance(node, ast.ImportFrom):
                    names.add(node.module or "")
    assert not [n for n in names if n.startswith("llama_fastapi")]


def test_the_whole_prompt_in_one_pass(loaded, tokens, want):
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.models.llama import forward, init_cache

    params, cfg = loaded
    logits, _ = forward(params, cfg, jnp.asarray(tokens, jnp.int32),
                        jnp.int32(0), init_cache(cfg), return_all=True)
    assert rel(logits, want) < LIMIT


@pytest.mark.parametrize("sizes", [(16,), (32, 8), (8, 32, 16), (64,)], ids=[
    "narrow", "wide_then_narrow", "three_widths", "one_slice"])
def test_slices_of_unequal_width_then_decode(loaded, tokens, want, sizes):
    """Prefill in slices (states and conv rows carried across every slice's
    end, the rings written slice by slice, the last slice with padding),
    then steps through the cache, against the float32 reference."""
    logits, _ = serve(*loaded, tokens, sizes)
    assert rel(logits, want[N_PROMPT - 1:N_SEQ][:len(logits)]) < LIMIT
    worst = max(rel(logits[i], want[N_PROMPT - 1 + i])
                for i in range(len(logits)))
    assert worst < 2 * LIMIT


@pytest.mark.parametrize("control", [
    "float8", "no_dt_norm", "no_b_norm", "no_c_norm", "no_inner_norms",
    "rotate", "flip_taps", "bfloat16_state"])
def test_another_function_fails_the_limit(ref, model, tokens, want, control):
    """Each control is a different function, told from the reference by
    the logits: a lower precision, each inner norm's absence, a rotation
    that the family does not have, the taps reversed."""
    import jax.numpy as jnp

    kw = {"float8": dict(emulate=jnp.float8_e4m3fn),
          "no_dt_norm": dict(skip_norms=("dt",)),
          "no_b_norm": dict(skip_norms=("b",)),
          "no_c_norm": dict(skip_norms=("c",)),
          "no_inner_norms": dict(skip_norms=("dt", "b", "c")),
          "bfloat16_state": dict(state_dtype=jnp.bfloat16)}.get(
        control, {control: True})
    logits = np.asarray(ref.forward(*model, tokens, **kw))
    if control == "bfloat16_state":
        # 72 positions carry little rounding: told from float32 at 1e-3
        # (the published sizes' limit is the chip comparison's to hold)
        assert 1e-3 < rel(logits, want) < LIMIT
    else:
        assert rel(logits, want) > 3 * LIMIT, rel(logits, want)


def test_bfloat16_inputs_pass_the_limit(ref, model, tokens, want, served):
    import jax.numpy as jnp

    logits = np.asarray(ref.forward(*model, tokens, emulate=jnp.bfloat16))
    assert rel(logits, want) < LIMIT
    assert rel(served[0], logits[N_PROMPT - 1:]) < LIMIT


def test_the_program_without_an_inner_norm_is_caught(loaded, tokens, want):
    """The PROGRAM with one inner norm's weight swapped for a constant that
    makes the norm a plain scaling fails the limit it passes as written."""
    import jax.numpy as jnp

    params, cfg = loaded
    for name in ("dt_norm", "b_norm", "c_norm"):
        ssm = dict(params["layers"]["ssm"])
        ssm[name] = jnp.flip(ssm[name], axis=-1) * 2.0
        broken = {**params, "layers": {**params["layers"], "ssm": ssm}}
        logits, _ = serve(broken, cfg, tokens, n_seq=N_PROMPT + 4)
        assert rel(logits, want[N_PROMPT - 1:N_PROMPT + 4]) > LIMIT, name


# ---------------------------------------------------------------------------
# the kernels in interpret mode
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("S,C,N", [(16, 512, 4), (24, 1024, 16)])
def test_the_scan_kernel_is_the_shared_scan(S, C, N):
    """One source: the kernel against models/mamba.py ``selective_scan``,
    which models/phi4flash.py hands on unchanged."""
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.models import mamba, phi4flash
    from llama_fastapi_k8s_gpu_tpu.ops.pallas.ssmscan import ssm_scan

    assert phi4flash.selective_scan is mamba.selective_scan
    assert phi4flash.state_nbytes is mamba.state_nbytes
    x, dt, b, c, a, d, leaf = _scan_inputs(S, C, N)
    y_k, new = ssm_scan(x, dt, b, c, a, d, jnp.asarray(leaf), 1, False,
                        interpret=True)
    y_s, s_s = mamba.selective_scan(*map(jnp.asarray, (
        x, dt, b, c, a, d, leaf[1].reshape(N, C))))
    assert np.allclose(y_k, y_s, rtol=1e-4, atol=1e-4)
    assert np.allclose(np.asarray(new[1]).reshape(N, C), s_s, rtol=1e-4,
                       atol=1e-5)


def test_the_stack_through_the_kernels_in_interpret_mode(loaded, tokens,
                                                         served):
    """The same file served as a TPU serves it: the scan kernel on the
    slices, the flash kernel on the slices' attention (one step on its head
    axis), the decode kernel at 5 query rows on one K/V head."""
    from llama_fastapi_k8s_gpu_tpu.models import jamba
    from llama_fastapi_k8s_gpu_tpu.models.llama import ring_write_impl

    params, cfg = loaded
    cfg = dataclasses.replace(cfg, attn_impl="pallas", ssm_scan_kernel=True)
    assert jamba.CACHE.decode_kernel_block(cfg) == N_CTX
    assert ring_write_impl(cfg) == "kernel"
    logits, cache = serve(params, cfg, tokens, n_seq=60)
    assert rel(logits, served[0][:len(logits)]) < 2e-2
    assert rel(np.asarray(cache["k"][:, :, :60], np.float32),
               np.asarray(served[1]["k"][:, :, :60], np.float32)) < 2e-2


def _flash_inputs(S, H, n_ctx, seed=0):
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.bfloat16)  # noqa: E731
    return f(S, H, 128), f(1, n_ctx, 128), f(1, n_ctx, 128)


@pytest.mark.parametrize("pos", [0, 48, 100])
def test_a_bounded_walk_is_the_whole_walk_to_the_bit(pos):
    """A ring of more fused blocks than the kernel walks whole: the key axis
    ends at the slice's end (a traced extent).  The same slice on the ring's
    first ``WALK_WHOLE_STEPS`` blocks alone (a ring short enough to be
    walked whole, which holds every key the slice may attend) runs the same
    blocks in the same order: bit for bit; and both are the plain softmax
    within rounding."""
    import jax
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.ops.pallas import attention as A

    S, H, short = 16, 5, 16 * A.WALK_WHOLE_STEPS
    n_ctx = short + 64
    q, k, v = _flash_inputs(S, H, n_ctx, seed=pos)
    kw = dict(sm_scale=128 ** -0.5, block_k=16, kv_unroll=1, interpret=True)
    plan = A.flash_plan(S, H, 1, n_ctx, block_k=16, kv_unroll=1)
    assert plan["bounded"] and plan["key_steps"] == A.WALK_WHOLE_STEPS + 4
    assert not A.flash_plan(S, H, 1, short, block_k=16, kv_unroll=1)["bounded"]
    assert A.flash_steps_walked(plan, pos + S) == (pos + S + 15) // 16
    got = A.flash_attention(q, k, v, jnp.int32(pos), **kw)
    whole = A.flash_attention(q, k[:, :short], v[:, :short], jnp.int32(pos),
                              **kw)
    assert np.array_equal(np.asarray(got, np.float32),
                          np.asarray(whole, np.float32))
    f32 = jnp.float32
    s = jnp.einsum("shd,td->hst", q.astype(f32), k[0].astype(f32)) \
        * 128 ** -0.5
    mask = jnp.arange(n_ctx)[None, :] <= (pos + jnp.arange(S))[:, None]
    p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), -1)
    want = jnp.einsum("hst,td->shd", p, v[0].astype(f32))
    assert np.allclose(np.asarray(got, np.float32), np.asarray(want),
                       atol=3e-2)


def test_a_short_ring_keeps_the_walk_it_had():
    from llama_fastapi_k8s_gpu_tpu.ops.pallas.attention import flash_plan

    # every standing cell's rings: up to 32768 slots at the default blocks
    for n_ctx in (1024, 4096, 16384, 32768):
        assert not flash_plan(1024, 32, 8, n_ctx)["bounded"], n_ctx
    assert flash_plan(1024, 20, 1, 262144) == {
        "bq": 512, "bk": 1024, "unroll": 4, "bkf": 4096, "row_tiles": 40,
        "key_steps": 64, "bounded": True}


@pytest.mark.parametrize("group", [4, 5, 20])
def test_the_decode_kernel_at_any_group_on_one_kv_head(group):
    """A lane's rows are padded to the bf16 tile's multiple whatever the
    group (16 for 4 and 5, 32 for 20): the result is the XLA loop's."""
    import jax
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.ops.pallas.attention import (
        flash_attention_decode)

    rng = np.random.default_rng(group)
    n_ctx, pos = 64, 37
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.bfloat16)  # noqa: E731
    q, k, v = f(group, 128), f(2, 1, n_ctx, 128), f(2, 1, n_ctx, 128)
    row_k, row_v = f(1, 128), f(1, 128)
    ctx, k2, v2 = flash_attention_decode(
        q, k, v, jnp.int32(1), jnp.int32(pos), True, sm_scale=128 ** -0.5,
        block_k=16, interpret=True, k_new=row_k, v_new=row_v)
    kk = k.at[1, 0, pos].set(row_k[0])
    vv = v.at[1, 0, pos].set(row_v[0])
    assert np.array_equal(np.asarray(k2, np.float32),
                          np.asarray(kk, np.float32))
    s = jnp.einsum("gh,th->gt", q.astype(jnp.float32),
                   kk[1, 0, :pos + 1].astype(jnp.float32)) * 128 ** -0.5
    want = jax.nn.softmax(s, -1) @ vv[1, 0, :pos + 1].astype(jnp.float32)
    assert np.allclose(np.asarray(ctx, np.float32).reshape(group, 128),
                       np.asarray(want), atol=3e-2)


def test_the_probes_pass_in_interpret_mode():
    from llama_fastapi_k8s_gpu_tpu.ops.pallas.probe import (
        probe_ring_wide_group, probe_ssm_scan, verdicts)

    assert probe_ssm_scan() is None
    assert probe_ring_wide_group(20) is None
    assert verdicts()["probe_ring_wide_group(20)"] is None


# ---------------------------------------------------------------------------
# padding, freed lanes, dead lanes
# ---------------------------------------------------------------------------

def test_padding_rows_reach_no_leaf(loaded, tokens):
    """A prompt of 21 tokens in slices of 16 (11 rows of padding in the
    second) leaves the state and the conv rows of the same prompt in slices
    of 3 (no padding), and garbage in the padding rows changes no leaf at a
    real position."""
    params, cfg = loaded
    _, padded = prefill(params, cfg, tokens, 21, (16,))
    _, exact = prefill(params, cfg, tokens, 21, (3,))
    assert rel(padded["conv"], exact["conv"]) < 2e-2
    assert rel(padded["state"], exact["state"]) < 2e-2
    noisy = tokens.copy()
    noisy[21:32] = 7
    _, other = prefill(params, cfg, noisy, 21, (16,))
    for leaf in ("state", "conv"):
        assert np.array_equal(np.asarray(padded[leaf], np.float32),
                              np.asarray(other[leaf], np.float32)), leaf
    for leaf in ("k", "v"):      # the ring's rows of the REAL positions
        assert np.array_equal(
            np.asarray(padded[leaf][:, :, :21], np.float32),
            np.asarray(other[leaf][:, :, :21], np.float32)), leaf


def test_a_pass_at_position_0_starts_from_zero(loaded, tokens):
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


def test_two_lanes_of_unequal_length_and_a_dead_lane(loaded, tokens, served,
                                                     want):
    """Two lanes at positions 45 and 21 step beside each other: each lane's
    logits are its own sequence's; a lane that holds no request keeps its
    state and conv rows to the bit whatever it holds."""
    import jax
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.models.llama import init_cache

    params, cfg = loaded
    _, mine = prefill(params, cfg, tokens, N_PROMPT)
    _, short = prefill(params, cfg, tokens, 21)
    garbage = jax.tree.map(lambda a: a + 1, init_cache(cfg))

    def run(other, tok, other_pos, other_live):
        stacked = jax.tree.map(lambda *a: jnp.stack(a), mine, other)
        lg, after = programs(cfg)[2](
            params, jnp.asarray([tokens[N_PROMPT], tok], jnp.int32),
            jnp.asarray([N_PROMPT, other_pos], jnp.int32), stacked,
            jnp.asarray([True, other_live]))
        return np.asarray(lg), after

    both, _ = run(short, tokens[21], 21, True)
    assert rel(both[0], want[N_PROMPT]) < LIMIT
    assert rel(both[1], want[21]) < LIMIT
    base, after = run(garbage, 7, 40, False)
    for leaf in ("state", "conv"):
        assert np.array_equal(np.asarray(after[leaf][1], np.float32),
                              np.asarray(garbage[leaf], np.float32)), leaf
    assert not np.array_equal(np.asarray(after["state"][0]),
                              np.asarray(mine["state"]))
    assert np.array_equal(run(garbage, 7, 90, True)[0][0], base[0])
    assert rel(base[0], served[0][1]) < 3e-2


# ---------------------------------------------------------------------------
# the file, the cache's size, refusals, counters
# ---------------------------------------------------------------------------

def test_gguf_round_trip_of_the_new_tensors_and_the_kv_head_array(
        gguf_path, loaded):
    from llama_fastapi_k8s_gpu_tpu.gguf import GGUFFile
    from llama_fastapi_k8s_gpu_tpu.models import jamba
    from llama_fastapi_k8s_gpu_tpu.models.cache import cache_of
    from llama_fastapi_k8s_gpu_tpu.testing import TINY_JAMBA_CFG as T

    params, cfg = loaded
    gf = GGUFFile(gguf_path)
    assert gf.hparam("attention.head_count_kv") == [0, 0, 1, 0, 0, 0, 0, 1, 0]
    for name, n in (("ssm_dt_norm", 16), ("ssm_b_norm", 4), ("ssm_c_norm", 4)):
        assert gf[f"blk.0.{name}.weight"].shape == (n,)
        assert f"blk.2.{name}.weight" not in gf.tensors     # an attention layer
    assert cfg.cache_kind == "ssm-state+ring" and cache_of(cfg) is jamba.CACHE
    assert cfg.mixers == T.mixers and cfg.tie_embeddings
    assert cfg.ssm_inner_norms and cfg.rms_eps == pytest.approx(1e-6)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (5, 1, 128)
    assert (cfg.ssm_d_inner, cfg.ssm_d_state, cfg.ssm_d_conv,
            cfg.ssm_dt_rank) == (512, 4, 4, 16)
    assert jamba.runs(cfg) == [("ssm", 0, 0, 2), ("attn", 2, 0, 1),
                               ("ssm", 3, 2, 4), ("attn", 7, 1, 1),
                               ("ssm", 8, 6, 1)]
    layers = params["layers"]
    assert set(layers) == {"ssm", "attn", "ffn"}
    assert layers["ssm"]["dt_norm"].shape == (7, 16)
    assert layers["ssm"]["b_norm"].shape == layers["ssm"]["c_norm"].shape \
        == (7, 4)
    assert layers["attn"]["wk"]["w"].shape[:2] == (2, 128)
    assert layers["ffn"]["ffn_norm"].shape == (9, 256)
    assert (np.asarray(layers["ssm"]["a"]) < 0).all()
    assert "out_norm_b" not in params
    assert params["output"]["w"] is params["tok_emb"]


def test_a_lanes_cache_at_the_published_sizes_is_278_mb():
    from llama_fastapi_k8s_gpu_tpu.models import jamba
    from tests.test_chip_compile import jamba_published_cfg

    cfg = jamba_published_cfg()
    assert cfg.mixers.count("attn") == 2 and cfg.mixers[7] == cfg.mixers[21]
    assert jamba.runs(cfg) == [("ssm", 0, 0, 7), ("attn", 7, 0, 1),
                               ("ssm", 8, 7, 13), ("attn", 21, 1, 1),
                               ("ssm", 22, 20, 6)]
    assert jamba.state_nbytes(cfg) == 26 * 5120 * (16 * 4 + 3 * 2)
    assert jamba.ring_nbytes(cfg) == 2 * 2 * 262144 * 128 * 2
    assert jamba.cache_nbytes(cfg) == 277753856


@pytest.mark.parametrize("n_ctx", [128, 512])
def test_the_cache_is_what_cache_nbytes_says(loaded, n_ctx):
    from llama_fastapi_k8s_gpu_tpu.models import jamba
    from llama_fastapi_k8s_gpu_tpu.models.llama import init_cache

    cfg = dataclasses.replace(loaded[1], n_ctx=n_ctx)
    cache = init_cache(cfg)
    assert set(cache) == {"state", "conv", "k", "v"}
    assert cache["state"].shape == (7, 4, 4, 128)
    assert cache["conv"].shape == (7, 3, 512)
    assert cache["k"].shape == (2, 1, n_ctx, 128)
    assert sum(a.nbytes for a in cache.values()) == jamba.cache_nbytes(cfg)


@pytest.mark.parametrize("meta, words", [
    ({"jamba.attention.head_count_kv": 1}, "must be an array"),
    ({"jamba.attention.head_count_kv": [1] * 9}, "names no scan layer"),
    ({"jamba.attention.head_count_kv": [0, 0, 1, 0, 0, 0, 0, 2, 0]},
     "must be one count"),
    ({"jamba.ssm.state_size": 0}, "the file lacks"),
    ({"jamba.expert_count": 16}, "dense feed-forward in every layer"),
    ({"jamba.attention.sliding_window": 64}, "sliding_window is not served"),
])
def test_a_file_the_block_cannot_compute_is_refused_by_name(gguf_path, meta,
                                                            words):
    from llama_fastapi_k8s_gpu_tpu.gguf import GGUFFile
    from llama_fastapi_k8s_gpu_tpu.models.config import ModelConfig

    gf = GGUFFile(gguf_path)
    gf.metadata = {**gf.metadata, **meta}
    with pytest.raises(ValueError, match=words):
        ModelConfig.from_gguf(gf, n_ctx=N_CTX)


def test_the_new_metrics_are_in_the_catalog():
    from llama_fastapi_k8s_gpu_tpu.models import jamba
    from llama_fastapi_k8s_gpu_tpu.obs.catalog import METRICS

    for name in jamba.CACHE.own_gauges:
        assert name.split("{")[0] in METRICS, name


def test_the_counters_count_what_the_steps_and_slices_do(loaded, monkeypatch):
    from llama_fastapi_k8s_gpu_tpu.models import jamba
    from tests.test_chip_compile import jamba_published_cfg

    cfg = loaded[1]
    kind = jamba.CACHE
    c = kind.new_counts()
    # two lanes wanted of three dispatched live, of four in the batch
    kind.note_decode(c, cfg, [20, 40], 4, [20, 40, 9])
    kind.note_lanes(c, cfg, 4, 4)
    assert c["state_updates"] == 2 * 4 * 7 and c["state_steps"] == 4 * 4 * 7
    # the XLA loop (attn_impl xla): two rings, a lane's live positions
    assert c["live"] == 2 * sum(p + t + 1 for p in (20, 40)
                                for t in range(4))
    assert c["read"] >= c["live"] and c["rows_written"] == 0
    g = kind.gauges(c)
    assert g["ssm_state_updates_total"] / g["ssm_state_steps_total"] == 0.5
    # no kernel, no walk: nothing counted
    attrs = kind.note_prefill(c, cfg, 40, [(0, 16), (16, 16), (32, 16)])
    assert c["state_starts"] == 1 and attrs["slices"] == 3
    assert attrs["ring_blocks_walked"] == c["ring_blocks_walked"] == 0
    # under the kernels: the decode kernel stores a row a ring layer
    k = dataclasses.replace(cfg, attn_impl="pallas")
    c = kind.new_counts()
    kind.note_decode(c, k, [20, 40], 4, [20, 40, 9])
    assert c["rows_written"] == 3 * 4 * 2
    # a short ring is walked whole: 5 query heads x 16 rows = 80 rows a
    # slice in FIVE row tiles of 16 (the largest block that divides 80), ONE
    # fused block of 128 keys, two layers, three slices
    kind.note_prefill(c, k, 40, [(0, 16), (16, 16), (32, 16)])
    assert (c["ring_blocks_live"], c["ring_blocks_walked"]) == (30, 30)
    # the published ring: a prompt of 2304 = two wide slices and a narrow
    # one; 20 x 1024 / 512 = 40 row tiles (10 of 256 rows), 2 layers, fused
    # blocks of 4096 keys: every slice ends inside the first block
    big = dataclasses.replace(jamba_published_cfg(), attn_impl="pallas")
    live, walked = jamba.prefill_walk(big, [(0, 1024), (1024, 1024),
                                            (2048, 256)])
    assert live == walked == 2 * (40 + 40 + 10)
    # a slice that ends at 34048 needs 9 of the ring's 64 fused blocks
    assert jamba.prefill_walk(big, [(33792, 256)]) == (2 * 10 * 9,) * 2
    from llama_fastapi_k8s_gpu_tpu.ops.pallas import attention as A

    monkeypatch.setattr(A, "WALK_WHOLE_STEPS", 64)   # walked whole: 9 of 64
    assert jamba.prefill_walk(big, [(33792, 256)]) == (180, 2 * 10 * 64)


# ---------------------------------------------------------------------------
# the engines and the server: ONE lane engine for the module
# ---------------------------------------------------------------------------

SYSTEM = "you are a careful assistant who answers in short plain sentences"
MSGS = [{"role": "system", "content": SYSTEM},
        {"role": "user", "content": "tell me about selective scans"}]
MSGS2 = [{"role": "system", "content": SYSTEM},
         {"role": "user", "content": "and what does one key head hold"}]


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
                                         "architecture 'jamba'"):
        Engine(gguf_path, n_ctx=N_CTX, **kw)


def test_the_kind_takes_any_slice_width_and_rolls_nothing_back(loaded):
    from llama_fastapi_k8s_gpu_tpu.models import jamba

    kind = jamba.CACHE
    assert kind.slice_rule(loaded[1], 12) is None and not kind.rolls_back
    assert kind.always_slices and kind.kernel_writes and kind.counts_prefill
    assert kind.slice_cfg(loaded[1], False) is loaded[1]


@pytest.mark.parametrize("engine", ["serial", "lanes"])
def test_an_engine_against_the_reference(gguf_path, lane_engine, ref, model,
                                         engine):
    """A request through the engine's own slice plan (wide 64 then narrow
    16) and its decode chunks: the first greedy token is the argmax of the
    reference's logits wherever the reference's margin is clear."""
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
    logits = np.asarray(ref.forward(*model, list(ids) + list(fed)))
    first = int(np.argmax(logits[len(ids) - 1]))
    top2 = np.sort(logits[len(ids) - 1])[-2:]
    if fed and top2[1] - top2[0] > 0.3:
        assert fed[0] == first


def test_lanes_freed_and_taken_again_give_the_serial_engines_text(
        gguf_path, lane_engine):
    from llama_fastapi_k8s_gpu_tpu.engine import Engine

    serial = Engine(gguf_path, n_ctx=N_CTX * 2, prefill_chunk=16,
                    decode_chunk=4)
    want = serial.create_chat_completion(MSGS, max_tokens=6, temperature=0.0)
    kind = serial.cache_kind
    assert kind["kind"] == "ssm-state+ring"
    assert (kind["ssm_layers"], kind["ring_layers"], kind["ring_kv_heads"],
            kind["query_heads_per_kv_head"]) == (7, 2, 1, 5)
    assert kind["inner_norms"] == ["dt", "b", "c"]
    assert kind["bytes_per_lane"] == kind["state_bytes"] + kind["ring_bytes"]
    assert kind["embedding"] == "bf16"
    assert kind["prefix_reuse"].startswith("off: a state")
    assert not serial._prefix_cache and serial.cfg.attn_impl == "xla"
    assert serial.cache_engine_health == {"ssm_scan": "xla"}
    g = serial.cache_read_gauges()
    assert g["ssm_state_starts_total"] == 1
    assert g["ssm_state_updates_total"] == g["ssm_state_steps_total"] > 0
    assert 0 < g["ring_slots_live_total"] <= g["ring_slots_read_total"]
    assert g["prefill_ring_blocks_walked_total"] == 0      # no kernel here
    eng = lane_engine
    assert not eng._lane_prefix
    before = eng.cache_read_gauges()["ssm_state_starts_total"]
    futs = [eng.submit(m, max_tokens=6, temperature=0.0)
            for m in (MSGS, MSGS2, MSGS)]
    outs = [f.result(timeout=600) for f in futs]
    for o in (outs[0], outs[2]):
        assert o["usage"]["prompt_tokens"] == want["usage"]["prompt_tokens"]
    assert outs[2]["choices"][0]["message"] == outs[0]["choices"][0]["message"]
    assert outs[0]["choices"][0]["message"] == want["choices"][0]["message"]
    assert not eng.scheduler_stats().get("lane_prefix_hits")
    g = eng.cache_read_gauges()
    assert g["ssm_state_starts_total"] == before + 3
    assert 0 < g["ssm_state_updates_total"] <= g["ssm_state_steps_total"]


def test_an_engine_under_the_kernels_counts_the_walk(gguf_path):
    """``attn_impl="pallas"`` in interpret mode: the engine's own prefill
    plan feeds the two walk counters (a ring of 512 slots is one fused
    block: live == walked), and /health names the kernels."""
    from llama_fastapi_k8s_gpu_tpu.engine import Engine

    eng = Engine(gguf_path, n_ctx=N_CTX * 4, prefill_chunk=16, decode_chunk=4,
                 attn_impl="pallas")
    assert eng.cfg.attn_impl == "pallas" and eng.cfg.ssm_scan_kernel
    assert eng.cache_engine_health == {"ssm_scan": "pallas"}
    long = [{"role": "system", "content": SYSTEM},
            {"role": "user", "content": "count the waves " * 14}]
    n = len(eng.tokenize_messages(long))
    eng.create_chat_completion(long, max_tokens=3, temperature=0.0)
    g = eng.cache_read_gauges()
    from llama_fastapi_k8s_gpu_tpu.engine.slices import plan_slices
    from llama_fastapi_k8s_gpu_tpu.models.jamba import prefill_walk

    plan = plan_slices(0, n, eng.cfg.n_ctx, 16, eng._wide_slice)
    assert len(plan) > 2
    assert g["prefill_ring_blocks_live_total"] \
        == g["prefill_ring_blocks_walked_total"] \
        == prefill_walk(eng.cfg, plan)[0] > 0
    assert g["ring_rows_written_total"] > 0


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
            assert eng["cache"]["kind"] == "ssm-state+ring"
            assert eng["cache"]["ring_layers"] == 2
            assert eng["ssm_scan"] == "xla" and eng["ring_write"] == "xla"
            assert set(eng["weight_formats"]) == {
                "ssm.in_proj", "ssm.out_proj", "ssm.x_proj", "attn.wq",
                "attn.wk", "attn.wv", "attn.wo", "ffn.w_gate", "ffn.w_up",
                "ffn.w_down"}
            d = (await client.get("/debug/compiles")).json()
            assert not d.get("degrades")
            m = (await client.get("/metrics")).text
            for name in ("ssm_state_updates_total", "ssm_state_steps_total",
                         "ssm_state_starts_total",
                         "prefill_ring_blocks_live_total",
                         "prefill_ring_blocks_walked_total",
                         "ring_slots_read_total"):
                assert name in m, name
        await app.router.shutdown()
