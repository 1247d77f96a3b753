"""The ``deepseek2`` block (models/mla.py) at a tiny size on the CPU, against
the plain float32 reference (benchmarks/reference_mla.py): latent attention
over the fourth cache kind (``latent-ring``), leading dense layers, the
grouped sigmoid router with its choice bias, routed + shared experts, and
an expert layer that is told which experts it holds.

The tiny file (``testing.TINY_MLA_CFG``) keeps every ratio of the published
block: 3 groups of 4 experts, 2 groups used, top-3, one shared expert, 1
dense + 2 routed layers, d_nope 16 / d_rope 8 / d_v 24, YaRN on.

LIMIT: the program (bf16 inputs to every product, float32 sums, a bf16
stream and cache) against the float32 reference on the program's OWN picks
reads 0.5-1.5 % of the logits' norm over blocks of 16 positions on three
layers; every control below (another function: a dropped bias, shared
expert, YaRN or routing scale) reads 8 % or more.  PICKS: rows whose set of
picked experts differs from the reference's own: near-ties that bf16
rounding orders the other way; the controls differ in tens of rows.
"""

from __future__ import annotations

import dataclasses
import os
import re
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")

LIMIT = 4e-2
PICKS = 12           # rows of 2 layers x N_SEQ whose picks may differ
N_CTX = 128
SLICE = 16
N_PROMPT = 40
N_SEQ = 72


@pytest.fixture(scope="module")
def ref():
    sys.path.insert(0, BENCH)
    try:
        import reference_mla
        yield reference_mla
    finally:
        sys.path.remove(BENCH)


@pytest.fixture(scope="module")
def gguf_path(tmp_path_factory):
    from llama_fastapi_k8s_gpu_tpu.testing import write_tiny_mla_gguf

    path = str(tmp_path_factory.mktemp("mla") / "tiny.gguf")
    write_tiny_mla_gguf(path, seed=3)
    return path


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(5).integers(4, 260, size=N_SEQ)


@pytest.fixture(scope="module")
def model(ref, gguf_path):
    return ref.open_model(gguf_path)


def load(path, fmt="bf16", n_ctx=N_CTX):
    from llama_fastapi_k8s_gpu_tpu.gguf import GGUFFile
    from llama_fastapi_k8s_gpu_tpu.models.config import ModelConfig
    from llama_fastapi_k8s_gpu_tpu.models.params import load_params

    gf = GGUFFile(path)
    cfg = ModelConfig.from_gguf(gf, n_ctx=n_ctx)
    return load_params(gf, cfg, fmt=fmt), cfg


@pytest.fixture(scope="module")
def loaded(gguf_path):
    return load(gguf_path)


def rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def worst(got, want, step=16):
    """The largest ``rel`` over blocks of ``step`` positions."""
    return max(rel(got[a:a + step], want[a:a + step])
               for a in range(0, len(got), step))


def rows_that_differ(mine, theirs):
    """Rows (layer, position) whose SET of picked experts differs."""
    return int(np.sum(np.any(np.sort(mine, -1) != np.sort(theirs, -1), -1)))


_PROGRAMS = {}


def traced_with(cfg):
    """What a trace of ``cfg``'s programs reads: the configuration and the
    block widths that ``with_kernel`` sets on models/mla.py."""
    from llama_fastapi_k8s_gpu_tpu.models import mla

    return (cfg, mla.LATENT_KERNEL_BLOCK, mla.LATENT_SLICE_BLOCK)


def programs(cfg):
    """A prefill pass (``n`` real positions of the slice), one decode step,
    one step of lanes (the body of ``parallel/batched.py``'s vmapped step,
    its bound included); each returns the routers' picks too.  One build a
    process for each (configuration, block widths): a second caller gets
    the programs the first one compiled."""
    key = traced_with(cfg)
    if key not in _PROGRAMS:
        _PROGRAMS[key] = _build_programs(cfg)
    return _PROGRAMS[key]


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
            with_picks=True, with_stats=True))(tokens, poss, caches, live)
    return pass_, step, lane_step


def prefill(params, cfg, seq, n, size=SLICE, pass_=None, cache=None, start=0):
    """Logits and picks of positions [start, n), and the cache, in passes of
    ``size`` (the last one padded, as a bucket is)."""
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.models.llama import init_cache

    pass_ = pass_ or programs(cfg)[0]
    cache = init_cache(cfg) if cache is None else cache
    out, picks = [], []
    for off in range(start, n, size):
        part = np.full(size, 9, np.int32)
        real = seq[off:min(off + size, n)]
        part[:len(real)] = real
        lg, cache, pk = pass_(params, jnp.asarray(part), jnp.int32(off),
                              jnp.int32(len(real)), cache)
        out.append(np.asarray(lg)[:len(real)])
        picks.append(np.asarray(pk)[:, :len(real)])
    return np.concatenate(out), np.concatenate(picks, axis=1), cache


@pytest.fixture(scope="module")
def served(loaded, tokens):
    """The serial programs over the whole sequence, slices then steps
    through the cache: (logits (S, V), picks (L_moe, S, k), the cache)."""
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
def own(ref, model, tokens):
    """The reference on its own picks: (logits, picks (L_moe, S, k))."""
    logits, routes = ref.forward(*model, tokens)
    return np.asarray(logits), np.stack([p for _, p in routes])


# ---------------------------------------------------------------------------
# the program against the reference
# ---------------------------------------------------------------------------

def test_slices_then_decode_through_the_latent_cache(ref, model, tokens,
                                                     served, own):
    logits, picks, _ = served
    assert rows_that_differ(picks, own[1]) <= PICKS
    want = np.asarray(ref.forward(*model, tokens, use_picks=picks)[0])
    print("read", worst(logits[:N_PROMPT], want[:N_PROMPT]),
          worst(logits[N_PROMPT:], want[N_PROMPT:]))
    assert worst(logits[:N_PROMPT], want[:N_PROMPT]) < LIMIT
    assert worst(logits[N_PROMPT:], want[N_PROMPT:]) < LIMIT


@pytest.mark.parametrize("control", ["no_bias", "no_shared", "no_yarn",
                                     "no_scale"])
def test_another_function_fails_the_limit(ref, model, tokens, served, own,
                                          control):
    """Each control is a different function: its distance from the program
    is past the limit, or (the bias, which moves the CHOICE alone) its own
    picks differ from the program's in many rows."""
    logits, picks, _ = served
    got, routes = ref.forward(*model, tokens, **{control: True})
    if control == "no_bias":
        theirs = np.stack([p for _, p in routes])
        assert rows_that_differ(picks, theirs) > 3 * PICKS
    assert worst(logits, np.asarray(got)) > LIMIT


def test_absorbed_is_expanded():
    """The identity the decode path rests on: W_kvb's key half folded into
    the query and its value half applied after the weighted sum of latents
    equals attention over every head's expanded keys and values."""
    import jax
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.models import mla
    from llama_fastapi_k8s_gpu_tpu.testing import TINY_MLA_CFG

    cfg = dataclasses.replace(TINY_MLA_CFG, n_ctx=64)
    H, r, d_n, d_r, d_v = 4, 32, 16, 8, 24
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    S, T = 5, 37
    q_n = jax.random.normal(keys[0], (S, H, d_n), jnp.float32)
    q_r = jax.random.normal(keys[1], (S, H, d_r), jnp.float32)
    rows = jax.random.normal(keys[2], (T, r + d_r), jnp.float32)
    w_uk = jax.random.normal(keys[3], (H, d_n, r), jnp.float32) * r ** -0.5
    w_uv = jax.random.normal(keys[4], (H, d_v, r), jnp.float32) * r ** -0.5
    positions = jnp.arange(T - S, T, dtype=jnp.int32)
    want = mla.expanded_attention(q_n, q_r, rows, w_uk, w_uv, positions, cfg)
    W = mla.leaf_width(cfg)
    lat = jnp.zeros((3, 1, 64, W), jnp.float32).at[1, 0, :T, :r + d_r].set(
        rows)
    with jax.default_matmul_precision("highest"):
        q_full = jnp.concatenate([mla.absorb_query(q_n, w_uk), q_r,
                                  jnp.zeros((S, H, W - r - d_r))], -1)
        ctx = mla.latent_attention(q_full, lat, 1, positions, T - 1, cfg)
        got = mla.expand_values(ctx, w_uv, jnp.float32)
    assert rel(got, want) < 1e-5
    # and the read does not depend on the bound it is given
    far = mla.latent_attention(q_full, lat, 1, positions, 63, cfg)
    assert np.array_equal(np.asarray(far), np.asarray(ctx))


# ---------------------------------------------------------------------------
# the decode kernel on the latent leaf (ops/pallas/attention.py
# latent_attention_decode, interpret mode) against the XLA write + loop
# ---------------------------------------------------------------------------

KERNEL_CTX, KERNEL_BLOCK = 64, 16


def _kernel_inputs(n_lanes):
    """Lanes' stacked leaves (random: every slot holds something, so a
    read past a position or a store beside it shows), each lane's q_n /
    q_r and the step's row, W_uk / W_uv."""
    import jax
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.models import mla
    from llama_fastapi_k8s_gpu_tpu.testing import TINY_MLA_CFG

    cfg = dataclasses.replace(TINY_MLA_CFG, n_ctx=KERNEL_CTX)
    H, r, d_n, d_r, d_v = (cfg.n_heads, cfg.kv_lora_rank, cfg.qk_nope_dim,
                           cfg.qk_rope_dim, cfg.v_head_dim)
    W = mla.leaf_width(cfg)
    keys = jax.random.split(jax.random.PRNGKey(3), 6)

    def filled(key, shape):
        x = jax.random.normal(key, shape, jnp.float32).astype(jnp.bfloat16)
        return x.at[..., r + d_r:].set(0)

    lat = filled(keys[0], (n_lanes, 3, 1, KERNEL_CTX, W))
    rows = filled(keys[1], (n_lanes, W))
    q_n = jax.random.normal(keys[2], (n_lanes, 1, H, d_n)).astype(jnp.bfloat16)
    q_r = jax.random.normal(keys[3], (n_lanes, 1, H, d_r)).astype(jnp.bfloat16)
    w_uk = (jax.random.normal(keys[4], (H, d_n, r)) * r ** -0.5
            ).astype(jnp.bfloat16)
    w_uv = (jax.random.normal(keys[5], (H, d_v, r)) * r ** -0.5
            ).astype(jnp.bfloat16)
    q_full = jax.vmap(lambda a, b: jnp.concatenate(
        [mla.absorb_query(a, w_uk), b,
         jnp.zeros((1, H, W - r - d_r), b.dtype)], -1))(q_n, q_r)
    return cfg, lat, rows, q_n, q_r, q_full, w_uk, w_uv


def _by_the_loop(cfg, q_full, lat, pos, row, bound, layer=1):
    """What ``mla._attention`` does for S = 1 where the kernel does not
    serve: the XLA write, then the loop."""
    import jax
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.models import mla

    lat = jax.lax.dynamic_update_slice(lat, row[None, None, None],
                                       (layer, 0, pos, 0))
    ctx = mla.latent_attention(q_full, lat, layer, pos[None], bound, cfg)
    return ctx[:, 0].astype(jnp.bfloat16), lat


def _by_the_kernel(cfg, q_full, lat, pos, live, row, layer=1):
    from llama_fastapi_k8s_gpu_tpu.models import mla
    from llama_fastapi_k8s_gpu_tpu.ops.pallas import latent_attention_decode

    ctx, lat = latent_attention_decode(
        q_full[0], lat, layer, pos, live, row, sm_scale=mla.attn_scale(cfg),
        block_k=KERNEL_BLOCK, v_width=cfg.kv_lora_rank, interpret=True)
    return ctx.reshape(cfg.n_heads, cfg.kv_lora_rank), lat


@pytest.mark.parametrize("name,pos,live", [
    ("lanes_at_their_own_positions", (37, 5, 50), (True, True, True)),
    ("a_dead_lane", (37, 20, 63), (True, False, True)),
    ("a_blocks_first_row", (16, 32, 48), (True, True, True)),
    ("a_blocks_last_row", (15, 31, 47), (True, True, True)),
    ("the_leafs_last_slot", (63, 0, 62), (True, True, True)),
    ("past_the_leaf_is_clamped_alike", (64, 70, 63), (True, True, True)),
    ("no_lane_lives", (37, 5, 50), (False, False, False)),
])
def test_the_decode_kernel_is_the_loop_per_lane(name, pos, live):
    """Under ``vmap`` (one kernel over the lanes): a live lane's weighted
    latents are the loop's and, expanded, the expanded form's; the leaf it
    returns is the XLA write's bit for bit; a dead lane reads nothing,
    stores nothing and returns 0."""
    import jax
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.models import mla

    cfg, lat, rows, q_n, q_r, q_full, w_uk, w_uv = _kernel_inputs(3)
    pos, live = jnp.asarray(pos, jnp.int32), jnp.asarray(live)
    want, want_lat = jax.vmap(
        lambda q, c, p, row: _by_the_loop(cfg, q, c, p, row, jnp.max(pos))
    )(q_full, lat, pos, rows)
    got, got_lat = jax.jit(jax.vmap(
        lambda q, c, p, lv, row: _by_the_kernel(cfg, q, c, p, lv, row)
    ))(q_full, lat, pos, live, rows)
    r, d_r = cfg.kv_lora_rank, cfg.qk_rope_dim
    for lane in range(3):
        if not bool(live[lane]):
            assert not np.asarray(got[lane], np.float32).any()
            assert np.array_equal(np.asarray(got_lat[lane], np.float32),
                                  np.asarray(lat[lane], np.float32))
            continue
        assert np.array_equal(np.asarray(got_lat[lane], np.float32),
                              np.asarray(want_lat[lane], np.float32))
        assert rel(got[lane].astype(jnp.float32),
                   want[lane].astype(jnp.float32)) < 1e-2, lane
        n = min(int(pos[lane]), KERNEL_CTX - 1) + 1
        if int(pos[lane]) >= KERNEL_CTX:
            continue        # the expanded form has no clamped row
        full = mla.expanded_attention(
            q_n[lane], q_r[lane], got_lat[lane, 1, 0, :n, :r + d_r], w_uk,
            w_uv, pos[lane][None], cfg)
        mine = mla.expand_values(got[lane][:, None], w_uv, jnp.float32)
        assert rel(mine, full) < 3e-2, lane


def test_the_decode_kernel_serves_one_sequence_without_vmap():
    """The serial engine's call: no lanes' axis, ``live`` a plain True."""
    import jax.numpy as jnp

    cfg, lat, rows, _, _, q_full, _, _ = _kernel_inputs(1)
    pos = jnp.int32(41)
    want, want_lat = _by_the_loop(cfg, q_full[0], lat[0], pos, rows[0], pos)
    got, got_lat = _by_the_kernel(cfg, q_full[0], lat[0], pos, True, rows[0])
    assert np.array_equal(np.asarray(got_lat, np.float32),
                          np.asarray(want_lat, np.float32))
    assert rel(got.astype(jnp.float32), want.astype(jnp.float32)) < 1e-2

# ---------------------------------------------------------------------------
# the prefill slices' kernel on the latent leaf (ops/pallas/attention.py
# latent_attention_prefill, interpret mode) against the XLA loop
# ---------------------------------------------------------------------------

SLICE_CTX = 2048
# tiles of 256 rows against blocks of 512 keys scored 256 a pass: a wide
# slice of the 4 heads is 16 tiles, and a tile walks up to 4 blocks
SLICE_BLOCKS = dict(block_q=256, block_k=512, sub_k=256)


@pytest.mark.parametrize("name,S,off", [
    ("a_narrow_slice_at_offset_0", 256, 0),
    ("a_wide_slice_at_offset_0", 1024, 0),
    # tokens 300..555 of each head: the tile's bound crosses from block 0
    # into block 1, and its first query lies inside a pass of 256 keys
    ("a_bound_that_crosses_a_block_edge", 256, 300),
    # two heads a tile, all of them over every token of the slice
    ("the_narrow_last_slice_at_a_deep_offset", 128, 1800),
    ("a_slice_that_ends_at_n_ctx", 256, SLICE_CTX - 256),
    ("a_bucket_shorter_than_a_tile", 2, 37),
])
def test_the_slice_kernel_is_the_loop(name, S, off):
    """A slice's weighted latents by the kernel are the loop's (bf16 out of
    the same float32 recurrence) and, expanded, the expanded form's; where
    no tile fits the slice's rows the branch keeps the loop, says so, and
    the kernel refuses the shape by name."""
    import jax
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.models import mla
    from llama_fastapi_k8s_gpu_tpu.ops.pallas import latent_attention_prefill
    from llama_fastapi_k8s_gpu_tpu.testing import TINY_MLA_CFG

    cfg = dataclasses.replace(TINY_MLA_CFG, n_ctx=SLICE_CTX,
                              latent_slice_kernel=True)
    H, r, d_n, d_r, d_v = (cfg.n_heads, cfg.kv_lora_rank, cfg.qk_nope_dim,
                           cfg.qk_rope_dim, cfg.v_head_dim)
    W = mla.leaf_width(cfg)
    keys = jax.random.split(jax.random.PRNGKey(S + off), 5)

    def bf16(key, shape, scale=1.0):
        return (jax.random.normal(key, shape) * scale).astype(jnp.bfloat16)

    # every slot holds something: a read past a tile's bound would show
    lat = bf16(keys[0], (3, 1, SLICE_CTX, W)).at[..., r + d_r:].set(0)
    q_n, q_r = bf16(keys[1], (S, H, d_n)), bf16(keys[2], (S, H, d_r))
    w_uk = bf16(keys[3], (H, d_n, r), r ** -0.5)
    w_uv = bf16(keys[4], (H, d_v, r), r ** -0.5)
    q_full = jnp.concatenate(
        [mla.absorb_query(q_n, w_uk), q_r,
         jnp.zeros((S, H, W - r - d_r), q_r.dtype)], -1)
    pos = off + jnp.arange(S, dtype=jnp.int32)

    def kernel():
        return latent_attention_prefill(
            q_full.transpose(1, 0, 2), lat, 1, jnp.int32(off),
            sm_scale=mla.attn_scale(cfg), v_width=r, interpret=True,
            **SLICE_BLOCKS)

    if name == "a_bucket_shorter_than_a_tile":
        assert mla.slice_tile(cfg, S) == 0
        assert mla.slice_read(cfg, S) == "loop"
        with pytest.raises(ValueError, match="no tile of the latent prefill"):
            kernel()
        return
    assert mla.slice_tile(cfg, S) and mla.slice_read(cfg, S) == "kernel"
    want = mla.latent_attention(q_full, lat, 1, pos, off + S - 1, cfg)
    got = kernel()
    assert got.shape == (H, S, r) and got.dtype == jnp.bfloat16
    assert rel(got.astype(jnp.float32), want) < 1e-2
    full = mla.expanded_attention(q_n, q_r, lat[1, 0, :off + S, :r + d_r],
                                  w_uk, w_uv, pos, cfg)
    assert rel(mla.expand_values(got, w_uv, jnp.float32), full) < 3e-2


def test_the_read_of_a_slice_follows_s_the_backend_and_the_probe(
        loaded, tokens, monkeypatch):
    """Which read serves is decided by what the program observes: S (a
    decode step the decode kernel, a slice the slice kernel where a tile
    fits), the backend (``auto`` on the CPU probes nothing and keeps the
    loop) and each kernel's own probe (a failed one keeps the loop for ITS
    read alone, and the degrade ledger says so).  ``/health`` ``cache`` and
    ``attn_impl`` say what they said whichever serves."""
    import types

    import jax
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.engine.engine import Engine
    from llama_fastapi_k8s_gpu_tpu.models import mla
    from llama_fastapi_k8s_gpu_tpu.models.llama import forward, init_cache
    from llama_fastapi_k8s_gpu_tpu.obs.devtime import DEVTIME
    from llama_fastapi_k8s_gpu_tpu.ops.pallas import probe

    params, cfg = loaded
    assert mla.CACHE.probe_kernels(cfg, "auto", "xla", []) == (cfg, "xla")
    probed = []
    both, impl = mla.CACHE.probe_kernels(cfg, "pallas", "xla", probed)
    assert probed == ["latent_decode", "latent_prefill"] and impl == "xla"
    assert both.latent_kernel and both.latent_slice_kernel
    monkeypatch.setattr(probe, "probe_latent_prefill", lambda: "Mosaic: no")
    before = len(DEVTIME.degrades())
    one, _ = mla.CACHE.probe_kernels(cfg, "pallas", "xla", [])
    assert one.latent_kernel and not one.latent_slice_kernel
    assert [d["reason"] for d in DEVTIME.degrades()[before:]] == ["Mosaic: no"]
    # the ledger is the process's: a later test of this worker that holds
    # /debug/compiles to "no degrades" must not find this fake one
    with DEVTIME._lock:
        DEVTIME._degrades.pop(("<lambda>", "Mosaic: no"))

    def kernels(c, S):
        """The Pallas kernels in one pass of S tokens at position 32."""
        jaxpr = jax.make_jaxpr(lambda t, cache: forward(
            params, c, t, jnp.int32(32), cache))(
            jnp.zeros(S, jnp.int32), init_cache(c))
        return sorted(set(re.findall(r"flash_attention_\w+", str(jaxpr))))

    assert kernels(cfg, SLICE) == kernels(cfg, 1) == []
    assert kernels(both, SLICE) == ["flash_attention_prefill_latent"]
    assert kernels(both, 1) == ["flash_attention_decode_latent"]
    assert kernels(both, 2) == []            # 8 rows: no tile, the loop
    assert kernels(one, SLICE) == []
    assert kernels(one, 1) == ["flash_attention_decode_latent"]
    for c, read in ((cfg, "xla"), (both, "kernel"), (one, "xla")):
        eng = types.SimpleNamespace(cfg=c, cache=mla.CACHE, _prefix_cache=None)
        assert Engine.cache_engine_health.fget(eng) == {
            "latent_slice_read": read}
        assert c.attn_impl == "xla"
        assert Engine.cache_kind.fget(eng)["read"] == "absorbed, blocks of 512"
    # the traced prefill span: which read, and rows in the read's own blocks
    wide = dataclasses.replace(both, n_ctx=4096)
    plan = [(0, 1024), (1024, 256), (1280, 2)]
    assert mla.CACHE.note_prefill({}, wide, 1282, plan) == {
        "cache": "latent-ring", "latent_read": "kernel+loop",
        "latent_positions_read": 1024 + 2048 + 1536}
    assert mla.CACHE.note_prefill({}, dataclasses.replace(
        wide, latent_slice_kernel=False), 1282, plan)[
        "latent_positions_read"] == 1024 + 1536 + 1536


def test_yarn_frequencies_are_the_published_blend():
    from llama_fastapi_k8s_gpu_tpu.models import mla
    from llama_fastapi_k8s_gpu_tpu.models.config import ModelConfig

    cfg = ModelConfig(
        vocab_size=8, dim=64, n_layers=1, n_heads=1, n_kv_heads=1, ffn_dim=8,
        n_ctx=8, rope_theta=1e5, kv_lora_rank=512, qk_rope_dim=64,
        qk_nope_dim=128, rope_yarn_factor=64.0, rope_yarn_orig_ctx=4096)
    f = mla.rope_inv_freq(cfg)
    base = 1e5 ** (-np.arange(0, 64, 2) / 64)
    # correction dims of 32 and 1 rotations at 4096: floor(8.4) and ceil(18.1)
    assert np.allclose(f[:9], base[:9], rtol=1e-6)
    assert np.allclose(f[19:], base[19:] / 64, rtol=1e-6)
    assert np.all(np.diff(f) < 0)
    mid = 13
    ramp = (mid - 8) / (19 - 8)
    assert np.isclose(f[mid], base[mid] / 64 * ramp + base[mid] * (1 - ramp),
                      rtol=1e-6)


def test_a_claimed_prefix_gives_the_logits_of_a_full_prefill(loaded, tokens):
    """What lane-claim and serial prefix reuse rest on: suffix slices on a
    COPY of a cache that holds the prefix give the full prefill's logits
    (the latent ring is positional: the rows of a prefix are the rows)."""
    import jax

    params, cfg = loaded
    full, _, _ = prefill(params, cfg, tokens, 64)
    _, _, cache = prefill(params, cfg, tokens, 32)
    # another sequence walks on in the source lane, past the claim
    _, _, dirty = prefill(params, cfg, tokens[::-1], 64, cache=cache, start=32)
    claimed = jax.tree.map(lambda a: a.copy(), dirty)
    got, _, _ = prefill(params, cfg, tokens, 64, cache=claimed, start=32)
    assert worst(got, full[32:]) < 1e-6


def with_kernel(cfg, monkeypatch):
    """``cfg`` as an engine on a TPU leaves it: the decode kernel serves a
    step and the slice kernel a prefill slice (interpret mode here), in
    blocks of 16 so that a lane and a slice of these tests walk several."""
    from llama_fastapi_k8s_gpu_tpu.models import mla
    from llama_fastapi_k8s_gpu_tpu.models.llama import decode_kernel_block

    monkeypatch.setattr(mla, "LATENT_KERNEL_BLOCK", 16)
    monkeypatch.setattr(mla, "LATENT_SLICE_BLOCK", 16)
    cfg = dataclasses.replace(cfg, latent_kernel=True,
                              latent_slice_kernel=True)
    assert decode_kernel_block(cfg) == 16
    assert mla.slice_tile(cfg, SLICE) == cfg.n_heads * SLICE
    return cfg


def reference_rows(ref, model, seq, use, length=N_SEQ, use_sel=None):
    """The reference's float32 logits on ``seq[:n]`` under the picks ``use``
    (L_moe, n, k), computed over ``length`` positions WHATEVER n is: the
    reference is causal, so rows below n of a run over the sequence filled
    up to ``length`` (token 9, the last pick repeated; a selection
    ``use_sel`` (..., n, n) filled up with rows that read position 0) are
    the rows of a run over n, up to float32's rounding of sums taken over
    ``length`` keys for n: NOT to the bit, at most 1.1e-5 on logits of up
    to 4.5 in the lane cases of the four files that call this (PR 60,
    CHANGES.md), where LIMIT is 4e-2; the case below this function holds
    it.  The reference runs eagerly and every
    operation compiles once a shape: one length for every lane of every
    case is one set of shapes a worker, the set the whole-sequence cases
    of the file have built already, where a length per lane was a second
    of compiles for each of its several hundred operations."""
    n = use.shape[1]
    assert n <= length, (n, length)
    seq = np.concatenate([np.asarray(seq[:min(len(seq), length)]),
                          np.full(max(length - len(seq), 0), 9, np.int64)])
    more = {}
    if use_sel is not None:
        use_sel = np.asarray(use_sel)
        full = np.zeros(use_sel.shape[:-2] + (length, length), use_sel.dtype)
        full[..., :n, :n] = use_sel[..., :n, :n]
        full[..., n:, 0] = 1
        more["use_sel"] = full
    picks = np.concatenate(
        [use, np.repeat(use[:, -1:], length - n, axis=1)], axis=1)
    return np.asarray(ref.forward(*model, seq, use_picks=picks,
                                  **more)[0])[:n]


def test_the_reference_over_the_length_is_the_reference_over_a_prefix(
        ref, model, tokens, own):
    """What ``reference_rows`` rests on, shown and not only said: the rows
    of a prefix from a run filled up to ``N_SEQ`` are those of a run over
    the prefix alone (another sequence's tail and other picks behind it),
    to 1e-4 of the largest logit where LIMIT is 4e-2."""
    n = 27
    use = own[1][:, :n]
    seq = np.concatenate([tokens[:n], np.roll(tokens, 7)[n:]])
    got = reference_rows(ref, model, seq, use)
    want = np.asarray(ref.forward(*model, tokens[:n], use_picks=use)[0])
    assert got.shape == want.shape == (n, want.shape[1])
    assert np.abs(got - want).max() < 1e-4 * np.abs(want).max()


def lanes_run(loaded, tokens, cfg=None):
    """Three lanes at different positions; lane 2 dead, then taken."""
    import jax
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.models.llama import init_cache

    params = loaded[0]
    cfg = cfg or loaded[1]
    pass_, _, lane_step = programs(cfg)
    seq2 = np.roll(tokens, 7)
    prompts = (33, 20, 11)
    seqs = [tokens, tokens[3:], seq2]
    caches = [prefill(params, cfg, s, n, pass_=pass_)[2]
              for s, n in zip(seqs, prompts)]
    garbage = jax.tree.map(lambda a: a + 1, init_cache(cfg))
    stacked = jax.tree.map(lambda *a: jnp.stack(a), caches[0], caches[1],
                           garbage)
    pos = [prompts[0], prompts[1], N_CTX - 3]
    live = [True, True, False]
    got = {0: [], 1: [], 2: []}
    stats = []
    for t in range(24):
        if t == 8:
            stacked = jax.tree.map(lambda a, c: a.at[2].set(c), stacked,
                                   caches[2])
            pos[2], live[2] = prompts[2], True
        if t == 16:
            live[0] = False
        toks = [seqs[i][p] if p < len(seqs[i]) else 0
                for i, p in enumerate(pos)]
        lg, stacked, st, pk = lane_step(
            params, jnp.asarray(toks, jnp.int32), jnp.asarray(pos, jnp.int32),
            stacked, jnp.asarray(live))
        stats.append((np.asarray(st), sum(live)))
        for lane in range(3):
            if live[lane]:
                got[lane].append((pos[lane], np.asarray(lg[lane]),
                                  np.asarray(pk[lane])))
        pos = [p + 1 for p in pos]
    return {lane: (rows[0][0], np.stack([r[1] for r in rows]),
                   np.concatenate([r[2] for r in rows], axis=1))
            for lane, rows in got.items()}, seqs, stats


@pytest.mark.parametrize("read", ["loop", "kernel"])
def test_three_lanes_one_dead_then_taken(ref, model, loaded, tokens, read,
                                         monkeypatch):
    params, cfg = loaded
    if read == "kernel":
        cfg = with_kernel(cfg, monkeypatch)
    got, seqs, stats = lanes_run(loaded, tokens, cfg)
    for lane, (first, logits, picks) in got.items():
        n = first + len(logits)
        use = np.concatenate(
            [prefill(params, cfg, seqs[lane], first)[1], picks], axis=1)
        assert use.shape[1] == n
        want = reference_rows(ref, model, seqs[lane], use)
        assert worst(logits, want[first:]) < LIMIT, lane
    # the counters are the step's, the same in every lane: every live row's
    # picks over all experts, all of them held here; a dead lane's reach none
    for st, n_live in stats:
        assert np.array_equal(st[0], st[1]) and np.array_equal(st[0], st[2])
        layer_steps, total, held = st[0][0], st[0][-1], st[0][2:-1].sum()
        assert layer_steps == 2
        assert total == held == n_live * 2 * cfg.n_experts_used


@pytest.mark.parametrize("read", ["loop", "kernel"])
def test_a_lanes_logits_do_not_depend_on_the_other_lanes(loaded, tokens, read,
                                                         monkeypatch):
    params, cfg = loaded
    lane_alone((params, with_kernel(cfg, monkeypatch)
                if read == "kernel" else cfg), tokens)


def lane_alone(loaded, tokens):
    import jax
    import jax.numpy as jnp

    params, cfg = loaded
    pass_, _, lane_step = programs(cfg)
    near = prefill(params, cfg, tokens[5:], 10, pass_=pass_)[2]
    far = prefill(params, cfg, tokens[9:], 60, pass_=pass_)[2]
    mine = prefill(params, cfg, tokens, 30, pass_=pass_)[2]

    def run(other, other_pos, other_live):
        stacked = jax.tree.map(lambda *a: jnp.stack(a), mine, other)
        out = []
        for t in range(3):
            lg, stacked, _, _ = lane_step(
                params, jnp.asarray([tokens[30 + t], 7], jnp.int32),
                jnp.asarray([30 + t, other_pos + t], jnp.int32),
                stacked, jnp.asarray([True, other_live]))
            out.append(np.asarray(lg[0]))
        return np.stack(out)

    base = run(near, 10, True)
    for other, other_pos, other_live in (
            (far, 60, True), (far, 60, False), (near, 10, False)):
        assert np.array_equal(run(other, other_pos, other_live), base)


# ---------------------------------------------------------------------------
# the share
# ---------------------------------------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer(ref, tmp_path, gguf_path,
                                              model, tokens):
    """One test ties the share to the model: the routed parts that the
    three shares (first, count) give, plus what every chip computes alike
    (attention, the shared expert) counted once, add up to what the UNCUT
    reference gives for the whole layer."""
    import jax
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.models import mla
    from llama_fastapi_k8s_gpu_tpu.models.llama import init_cache
    from llama_fastapi_k8s_gpu_tpu.testing import write_tiny_mla_gguf

    hp, tensors = model
    S = 24
    x = np.asarray(ref.tensor(tensors, "token_embd.weight"))[tokens[:S]] * 8
    outs, picks = [], None
    for n, held in enumerate(((0, 4), (4, 4), (8, 4))):
        path = str(tmp_path / f"share{n}.gguf")
        write_tiny_mla_gguf(path, seed=3, held=held)
        params, cfg = load(path)
        assert (cfg.experts_first, cfg.n_held, cfg.n_experts) == (*held, 12)
        assert params["layers"]["moe"]["w_gate_exps"]["w"].shape[1] == 4

        def run(cfg):
            return jax.jit(lambda h, c: mla.moe_layer(
                h, params["layers"]["moe"], jnp.int32(0), c,
                jnp.arange(S, dtype=jnp.int32), jnp.int32(0), cfg, None,
                None))(jnp.asarray(x, jnp.bfloat16), init_cache(cfg))

        h, _, (count, pk, total) = run(cfg)
        assert int(total) == S * 3 and 0 < int(count.sum()) < S * 3
        outs.append(np.asarray(h, np.float32))
        picks = np.asarray(pk)
        if n == 0:    # a share that holds nothing this router can pick
            none = np.asarray(run(dataclasses.replace(
                cfg, experts_first=cfg.n_experts))[0], np.float32)
    got = sum(outs) - 2 * none
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.layer(
            hp, ref.layer_weights(tensors, 1),
            jnp.asarray(x, jnp.bfloat16).astype(jnp.float32), 1,
            use_picks=picks)[0])
    print("read", rel(got, want))
    assert rel(got, want) < LIMIT
    # and one share alone is far from it
    assert rel(outs[0], want) > 5 * LIMIT


def test_a_share_serves_and_counts_what_left(tmp_path, ref, tokens):
    """A file that holds experts 4..7 of 12: the program and the reference
    given the same share agree; the counters tell held from routed."""
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.models.llama import forward
    from llama_fastapi_k8s_gpu_tpu.testing import write_tiny_mla_gguf

    path = str(tmp_path / "share.gguf")
    write_tiny_mla_gguf(path, seed=3, held=(4, 4))
    params, cfg = load(path)
    logits, picks, _ = prefill(params, cfg, tokens, 48)
    want = np.asarray(ref.forward(*ref.open_model(path), tokens[:48],
                                  use_picks=picks)[0])
    assert worst(logits, want) < LIMIT
    from llama_fastapi_k8s_gpu_tpu.models.llama import init_cache

    _, _, stats = forward(params, cfg, jnp.asarray(tokens[:16], jnp.int32),
                          jnp.int32(0), init_cache(cfg), with_stats=True)
    stats = np.asarray(stats)
    assert len(stats) == 3 + 4 and stats[0] == 2
    assert stats[-1] == 2 * 16 * 3
    held = int(np.sum((picks[:, :16] >= 4) & (picks[:, :16] < 8)))
    assert stats[2:-1].sum() == held < stats[-1]


# ---------------------------------------------------------------------------
# the file, the loader, the refusals
# ---------------------------------------------------------------------------

def test_gguf_round_trip_of_the_keys_and_the_held_experts(tmp_path, loaded):
    from llama_fastapi_k8s_gpu_tpu.gguf import GGUFFile
    from llama_fastapi_k8s_gpu_tpu.models.config import (LATENT_RING,
                                                         ModelConfig)
    from llama_fastapi_k8s_gpu_tpu.testing import (TINY_MLA_CFG,
                                                   write_tiny_mla_gguf)

    params, cfg = loaded
    assert cfg.cache_kind == LATENT_RING and not cfg.rope_neox
    for f in dataclasses.fields(TINY_MLA_CFG):
        if f.name in ("vocab_size", "rms_eps", "attn_mscale"):
            continue
        assert getattr(cfg, f.name) == getattr(TINY_MLA_CFG, f.name), f.name
    assert abs(cfg.attn_mscale - TINY_MLA_CFG.attn_mscale) < 1e-6
    assert cfg.n_held == 12 and cfg.experts_held == 0
    assert set(params["layers"]) == {"dense", "moe"}
    assert params["layers"]["moe"]["router_bias"].shape == (2, 12)
    assert params["layers"]["dense"]["w_uk"]["w"].shape == (1, 4, 16, 32)
    assert params["layers"]["moe"]["w_uv"]["w"].shape == (2, 4, 24, 32)
    path = str(tmp_path / "held.gguf")
    write_tiny_mla_gguf(path, held=(8, 4))
    gf = GGUFFile(path)
    assert gf.hparam("expert_held_first") == 8
    assert gf.hparam("expert_held_count") == 4
    assert gf.hparam("expert_count") == 12
    assert tuple(gf["blk.1.ffn_gate_exps.weight"].shape) == (256, 256, 4)
    held = ModelConfig.from_gguf(gf, n_ctx=N_CTX)
    assert (held.experts_first, held.n_held, held.n_experts) == (8, 4, 12)


def test_the_cache_is_one_row_a_position_for_all_heads(loaded):
    from llama_fastapi_k8s_gpu_tpu.models.llama import (cache_nbytes,
                                                        init_cache)

    _, cfg = loaded
    cache = init_cache(cfg)
    assert {k: v.shape for k, v in cache.items()} \
        == {"lat": (3, 1, N_CTX, 128)}        # 32 + 8, filled up to 128
    assert cache_nbytes(cfg) == sum(v.nbytes for v in cache.values())


def test_the_benchmark_files_mix_fuses_with_padded_k_and_rows(tmp_path, ref):
    """The benchmark file's type mix at widths the fused kernels take only
    PADDED, as the published ones are: hidden 1792 (7168 / 4: 0.875 K
    tiles, filled up to 2048 with zero blocks in every plane whose K it is,
    the experts' gate and up among them) and a latent projection of 128 +
    64 = 192 rows filled up to 256.  ``attn_q_b`` and ``attn_kv_b`` (K = a
    latent rank, no kernel's tile) are served bf16, never int8."""
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.models.params import flat_layers
    from llama_fastapi_k8s_gpu_tpu.gguf import GGMLType
    from llama_fastapi_k8s_gpu_tpu.testing import (TINY_MLA_CFG,
                                                   write_tiny_mla_gguf)

    cfg = dataclasses.replace(
        TINY_MLA_CFG, dim=1792, n_heads=2, n_kv_heads=2, n_layers=2,
        q_lora_rank=256, kv_lora_rank=128, qk_nope_dim=32, qk_rope_dim=64,
        v_head_dim=64, ffn_dim=2048, expert_ffn_dim=2048, n_experts=4,
        n_expert_groups=2, n_groups_used=1, n_experts_used=2, n_ctx=64)
    path = str(tmp_path / "wide.gguf")
    write_tiny_mla_gguf(path, cfg, seed=1, held=(2, 2), mix={
        "attn_q_b": GGMLType.Q4_K, "attn_kv_b": GGMLType.Q8_0,
        "attn_output": GGMLType.Q8_0})
    params, cfg = load(path, fmt="q4k", n_ctx=64)
    kinds = {name: sorted(leaf) for name, leaf in
             flat_layers(params["layers"]) if isinstance(leaf, dict)}
    for name in ("dense.wq_a", "dense.wkv_a", "dense.w_gate", "dense.w_up",
                 "moe.w_gate_sh", "moe.w_up_sh", "moe.w_gate_exps",
                 "moe.w_up_exps"):
        assert "qs" in kinds[name], name
    for name in ("dense.w_down", "moe.w_down_sh", "moe.w_down_exps"):
        assert {"q4", "q6p"} & set(kinds[name]), name
    for name in ("dense.wq_b", "moe.wq_b", "moe.w_uk", "moe.w_uv", "moe.wo"):
        assert kinds[name] == ["w"], name
    assert params["layers"]["moe"]["wkv_a"]["qs"].shape[1:] == (256, 1024)
    assert params["layers"]["moe"]["w_gate_exps"]["qs"].shape[1:] \
        == (2, 2048, 1024)
    seq = np.random.default_rng(2).integers(4, 260, size=20)
    got, picks, cache = prefill(params, cfg, seq, 16, size=16)
    step = programs(cfg)[1]
    lg, _, pk = step(params, jnp.int32(seq[16]), jnp.int32(16), cache)
    picks = np.concatenate([picks, np.asarray(pk)], axis=1)
    exp = np.asarray(ref.forward(*ref.open_model(path), seq[:17],
                                 use_picks=picks)[0])
    print("read", rel(got, exp[:16]), rel(np.asarray(lg), exp[16]))
    assert rel(got, exp[:16]) < 0.06
    assert rel(np.asarray(lg), exp[16]) < 0.06


def _file_with(tmp_path, drop=(), **meta):
    """The tiny file with ``deepseek2.<key>`` values replaced."""
    from llama_fastapi_k8s_gpu_tpu.gguf import GGUFWriter
    from llama_fastapi_k8s_gpu_tpu import testing

    path = str(tmp_path / "odd.gguf")

    class Odd(GGUFWriter):
        def add_metadata(self, key, value):
            short = key.removeprefix("deepseek2.")
            super().add_metadata(key, meta.get(short, value))

    orig = testing.GGUFWriter
    testing.GGUFWriter = Odd
    try:
        testing.write_tiny_mla_gguf(path)
    finally:
        testing.GGUFWriter = orig
    return path


@pytest.mark.parametrize("meta, words", [
    ({"attention.q_lora_rank": 0}, "q_lora_rank is 0"),
    ({"expert_gating_func": 3}, "expert_gating_func 3"),
    ({"expert_group_count": 5}, "12 experts in 5 groups"),
    ({"expert_group_used_count": 1, "expert_used_count": 5},
     "5 picks must fit 1 groups of 4"),
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
                            "'deepseek2'"),
    (dict(kv_paged=True), "LFKT_KV_PAGED=1 cannot serve architecture "
                          "'deepseek2'"),
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
        {"role": "user", "content": "tell me about latents and rings"}]
MSGS2 = [{"role": "system", "content": SYSTEM},
         {"role": "user", "content": "and what does an expert hold here"}]


@pytest.fixture(scope="module")
def engine(gguf_path):
    from llama_fastapi_k8s_gpu_tpu.engine import Engine

    return Engine(gguf_path, n_ctx=N_CTX * 2, prefill_chunk=SLICE,
                  decode_chunk=4, prefix_min=8)


def test_serial_engine_serves_reuses_a_prefix_and_counts(engine):
    out = engine.create_chat_completion(MSGS, max_tokens=12, temperature=0.0)
    assert out["usage"]["completion_tokens"] >= 1
    kind = engine.cache_kind
    assert kind["kind"] == "latent-ring" and kind["prefix_reuse"] == "on"
    assert kind["latent"] == 32 and kind["rotated_key"] == 8
    assert kind["bytes_per_position"] == 2 * 3 * 40
    assert kind["bytes_per_position_laid_out"] == 2 * 3 * 128
    assert kind["experts_held"] == [0, 12] and kind["experts_routed"] == 12
    assert kind["kv_paged"] == "refused at start"
    assert engine._prefix_cache and engine.cfg.attn_impl == "xla"
    gauges = engine.cache_read_gauges()
    assert 0 < gauges["latent_positions_live_total"] \
        <= gauges["latent_positions_read_total"]
    snap = engine.expert_counters.snapshot(block=True)
    assert snap["picks_total"] == snap["picks_held"] == sum(snap["picks"]) > 0
    # the same request again rides the prefix the ring still holds, and
    # gives the same greedy text as the full prefill did
    again = engine.create_chat_completion(MSGS, max_tokens=12,
                                          temperature=0.0)
    assert again["choices"][0]["message"] == out["choices"][0]["message"]
    other = engine.create_chat_completion(MSGS2, max_tokens=4,
                                          temperature=0.0)
    assert other["usage"]["completion_tokens"] >= 1


def test_lane_engine_serves_and_admits_through_a_lane_claim(gguf_path,
                                                            engine):
    from llama_fastapi_k8s_gpu_tpu.engine.continuous import ContinuousEngine

    want = engine.create_chat_completion(MSGS, max_tokens=10, temperature=0.0)
    eng = ContinuousEngine(gguf_path, n_ctx=N_CTX * 2, prefill_chunk=SLICE,
                           decode_chunk=4, batch_size=3)
    try:
        assert eng._lane_prefix and eng.cache_kind["prefix_reuse"] == "on"
        first = eng.submit(MSGS, max_tokens=10, temperature=0.0).result(
            timeout=300)
        assert first["usage"] == want["usage"]
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
        assert 0 < snap["picks_held"] == snap["picks_total"]
    finally:
        eng.shutdown()


def test_callers_that_arrive_together_ride_the_first_ones_prompt(gguf_path):
    """A cold lane engine, three requests behind one system line at once
    (the agent cell's warm-up at a small size): the first prefills, the
    others are admitted beside it through its LIVE lane's claim, and each
    gives the text a full prefill gives."""
    from llama_fastapi_k8s_gpu_tpu.engine.continuous import ContinuousEngine

    eng = ContinuousEngine(gguf_path, n_ctx=N_CTX * 2, prefill_chunk=SLICE,
                           decode_chunk=4, batch_size=3)
    try:
        alone = [eng.submit(m, max_tokens=6, temperature=0.0, seed=5).result(
            timeout=300) for m in (MSGS, MSGS2)]   # explicit seed: no reuse
        assert eng.scheduler_stats()["lane_prefix_hits"] == 0
        eng._lane_claims[:] = [None] * eng.batch_size
        futs = [eng.submit(m, max_tokens=n, temperature=0.0)
                for m, n in ((MSGS, 24), (MSGS2, 6), (MSGS, 6))]
        outs = [f.result(timeout=300) for f in futs]
        assert eng.scheduler_stats()["lane_prefix_hits"] == 2
        assert outs[1]["choices"][0]["message"] \
            == alone[1]["choices"][0]["message"]
        assert outs[2]["choices"][0]["message"] \
            == alone[0]["choices"][0]["message"]
    finally:
        eng.shutdown()


@pytest.mark.parametrize("kernel,read,rows,who", [
    # the kernel: each wanted lane its OWN blocks of 1024 (100..103 one,
    # 1500..1503 two), the unwanted lane at 3000 nothing; a row stored a
    # lane dispatched live, step and layer
    (True, 4 * 1024 + 4 * 2048, 3 * 4 * 3, "kernel"),
    # the loop: every wanted lane up to the largest lane's bound, in blocks
    # of 512 (3000..3003: six of them); XLA writes the rows
    (False, 2 * 4 * 3072, 0, "xla"),
])
def test_the_read_counters_follow_who_reads(kernel, read, rows, who):
    """``CacheKind.note_decode`` on a ``latent-ring`` configuration, one
    chunk of 4 steps: lanes at 100 and 1500 wanted, a third at 3000
    dispatched live but finished.  ``/health`` ``engine.ring_write`` names
    who reads and stores a step's row; ``engine.cache`` and ``attn_impl``
    say what they said (the benchmark's ``expect_health`` holds both letter
    for letter: they describe a prefill slice's read, the loop's)."""
    import types

    from llama_fastapi_k8s_gpu_tpu.engine.engine import Engine
    from llama_fastapi_k8s_gpu_tpu.models import mla
    from llama_fastapi_k8s_gpu_tpu.server.app import _ring_write
    from llama_fastapi_k8s_gpu_tpu.testing import TINY_MLA_CFG

    cfg = dataclasses.replace(TINY_MLA_CFG, n_ctx=4096, latent_kernel=kernel)
    eng = types.SimpleNamespace(cfg=cfg, cache=mla.CACHE,
                                cache_counts=mla.CACHE.new_counts(),
                                _prefix_cache=None)
    mla.CACHE.note_decode(eng.cache_counts, cfg, [100, 1500], 4,
                          live=[100, 1500, 3000])
    assert eng.cache_counts == {
        "read": read,
        "live": sum(range(101, 105)) + sum(range(1501, 1505)),
        "rows_written": rows, "slices_kernel": 0, "slices_loop": 0}
    assert _ring_write(cfg) == who and cfg.attn_impl == "xla"
    assert Engine.cache_kind.fget(eng)["read"] == "absorbed, blocks of 512"


def test_the_lane_engine_serves_through_the_kernel(gguf_path):
    """``attn_impl="pallas"`` through the engine itself (what ``auto``
    asks for on a TPU; interpret mode here): the probe passes, the
    steps' rows are the kernel's, the counters count each lane's own
    blocks, and a greedy request gives the same text twice (the second
    through a lane claim on rows the kernel stored)."""
    from llama_fastapi_k8s_gpu_tpu.engine.continuous import ContinuousEngine

    eng = ContinuousEngine(gguf_path, n_ctx=N_CTX * 2, prefill_chunk=SLICE,
                           decode_chunk=4, batch_size=3,
                           attn_impl="pallas")
    try:
        assert eng.cfg.latent_kernel and eng.cfg.latent_slice_kernel
        assert eng.cfg.attn_impl == "xla"
        assert eng.cache_engine_health == {"latent_slice_read": "kernel"}
        first = eng.submit(MSGS, max_tokens=10, temperature=0.0).result(
            timeout=300)
        assert first["usage"]["completion_tokens"] >= 1
        outs = [f.result(timeout=300) for f in [
            eng.submit(m, max_tokens=10, temperature=0.0)
            for m in (MSGS, MSGS2, MSGS)]]
        for o in (outs[0], outs[2]):
            assert o["choices"][0]["message"] == first["choices"][0]["message"]
        assert eng.scheduler_stats()["lane_prefix_hits"] >= 1
        gauges = eng.cache_read_gauges()
        assert gauges["ring_rows_written_total"] > 0
        assert 0 < gauges["latent_positions_live_total"] \
            <= gauges["latent_positions_read_total"]
        # every slice is 16 tokens of 4 heads: one tile of the slice kernel
        assert gauges["latent_slices_kernel_total"] > 0
        assert gauges["latent_slices_loop_total"] == 0
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
            assert eng["cache"]["kind"] == "latent-ring"
            assert eng["cache"]["prefix_reuse"] == "on"
            assert set(eng["weight_formats"]) >= {
                "dense.wq_a", "dense.w_gate", "moe.wkv_a", "moe.w_uk",
                "moe.w_gate_exps", "moe.w_down_sh"}
            d = (await client.get("/debug/compiles")).json()
            assert not d.get("degrades")
            m = (await client.get("/metrics")).text
            assert "latent_positions_read_total" in m
            assert "latent_positions_live_total" in m
            assert "expert_picks_routed_total" in m
            assert "expert_picks_held_total" in m
        await app.router.shutdown()
