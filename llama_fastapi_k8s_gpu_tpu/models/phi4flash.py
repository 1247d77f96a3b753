"""The seventh cache KIND: a selective-scan state, a short window, and ONE
K/V leaf that the whole upper half of the stack reads
(``general.architecture = "phi4flash"``; ``cfg.mixers``; ``cache_kind``
``ssm-state+window+shared-ring``).

Every layer is ``h += mixer(LN1(h)); h += W_down(silu(g) * u)`` with a
LayerNorm that has a bias; nothing rotates.  The mixer is the layer's:

- ``"ssm"`` (Mamba-1; models/mamba.py, the mixer this block shares with
  models/jamba.py): the leaves ``state`` and ``conv``, the slice kernel
  behind ``cfg.ssm_scan_kernel``.  The LAST ssm layer also hands ``m = y``
  (the scan's output before the gate) to the layers above it.
- ``"window"`` / ``"full"``: DIFFERENTIAL attention.  Heads in pairs by even
  and odd: ``a1 = softmax(q1 k1^T) [v1 | v2]``, ``a2 = softmax(q2 k2^T) [v1
  | v2]``, ``a = RMSNorm(a1 - lam a2) (1 - lam0)``.  A pair's ``[k1 | k2]``
  and ``[v1 | v2]`` ARE one 128-wide row of a ring of ``n_kv / 2`` heads
  (models/lfm2.py's packed row), and a query laid into its key's 64 columns
  of a zero row of 128 gives ``a1`` (or ``a2``) whole, both value halves, in
  ONE pass of the ring's kernels as they stand (:func:`pack_queries`).  A
  window layer's leaves ``kw`` / ``vw`` hold ``cfg.window_slots`` rows that
  wrap (models/hybrid.py's functions on a view of the configuration); the
  ONE full layer writes leaf ``k`` / ``v`` (1, n_kv / 2, n_ctx, 128).
- ``"gmu"``: ``W_out (m * silu(W_in hn))``, ``m`` the last ssm layer's at the
  same position.  No cache.
- ``"cross"``: ``q = W_q hn`` alone, on the full layer's leaf, causal over
  all positions; differential with its own ``lam``.  It WRITES no cache.

So no later token reads anything of a gmu or cross layer at a prompt
position, and a prefill slice that holds no prompt's last token stops
after the full layer (``cfg.lower_only``; :func:`_slice_cfg`): exact, not an
approximation.  The stack is walked as loops over the PERIOD: (ssm, window)
pairs, the (ssm, full) pair, (gmu, cross) pairs.  Weights are FIVE stacks:
``ssm``, ``attn`` (window and full: one shape), ``gmu``, ``cross`` and the
feed-forwards of every layer, ``ffn``.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.linear import linear, linear_at
from .cache import CacheKind
from .config import (
    CROSS, FULL, GMU, SSM, SSM_WINDOW_SHARED, WINDOW, ModelConfig)
from .hybrid import chunk_counts as window_chunk_counts
from .hybrid import window_slice, window_step
from .llama import (
    _kernel_decode, _ring_attention, decode_read_slots, embed,
    ring_kernel_block, ring_step_bound, rms_norm)
from .mamba import (     # noqa: F401 — tier-1 reads the scan from here too
    FFN, engine_health as _engine_health, init_leaves, probe_scan_kernel,
    selective_scan, ssm_mixer, state_nbytes, state_shape)
from .routed import swiglu

_LANES = 128
#: the attention stack: the window layers, then the full one
ATTN = "attn"


def n_pairs(cfg: ModelConfig) -> tuple[int, int]:
    """((ssm, window) pairs below the (ssm, full) pair, (gmu, cross) pairs
    above it)."""
    return cfg.n_layers_of(WINDOW), cfg.n_layers_of(CROSS)


def ring_view(cfg: ModelConfig, window: bool = False) -> ModelConfig:
    """The configuration as a ring of PAIRS sees it (what models/llama.py's
    ring functions and models/hybrid.py's window functions take): ``n_kv /
    2`` KV heads of 128 (a pair's two keys side by side), every query head
    a head of 128 at the 64-wide heads' softmax scale; the window layers'
    with the window, the shared leaf's without."""
    return dataclasses.replace(
        cfg, n_kv_heads=cfg.n_kv_heads // 2, head_width=2 * cfg.head_dim,
        attn_scale=cfg.head_dim ** -0.5,
        sliding_window=cfg.sliding_window if window else 0)


def depths(cfg: ModelConfig, *kinds: str) -> np.ndarray:
    """The depth in the stack of each layer of ``kinds``, in order."""
    return np.asarray([i for i, m in enumerate(cfg.mixers) if m in kinds],
                      np.int32)


def init_cache(cfg: ModelConfig, dtype=jnp.bfloat16) -> dict:
    if cfg.kv_dtype not in ("bf16", "bfloat16"):
        raise ValueError(
            f"kv_dtype={cfg.kv_dtype!r} cannot hold architecture "
            "'phi4flash': its state + window + shared-ring cache is bf16 "
            "and float32 only")
    g = ring_view(cfg)
    n_w = cfg.n_layers_of(WINDOW)
    return {
        **init_leaves(cfg, dtype),
        "kw": jnp.zeros((n_w, g.n_kv_heads, cfg.window_slots, g.head_dim),
                        dtype),
        "vw": jnp.zeros((n_w, g.n_kv_heads, cfg.window_slots, g.head_dim),
                        dtype),
        "k": jnp.zeros((1, g.n_kv_heads, cfg.n_ctx, g.head_dim), dtype),
        "v": jnp.zeros((1, g.n_kv_heads, cfg.n_ctx, g.head_dim), dtype)}


def cache_nbytes(cfg: ModelConfig) -> int:
    row = 2 * cfg.n_kv_heads * cfg.head_dim * 2            # K and V, bf16
    return row * (cfg.n_ctx + cfg.n_layers_of(WINDOW) * cfg.window_slots) \
        + state_nbytes(cfg)


# ---------------------------------------------------------------------------
# the pieces
# ---------------------------------------------------------------------------

def layer_norm(x, w, b, eps: float):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    c = x32 - mu
    inv = jax.lax.rsqrt(jnp.mean(c * c, axis=-1, keepdims=True) + eps)
    return (c * inv * w.astype(jnp.float32) + b.astype(jnp.float32)
            ).astype(x.dtype)


def pack_queries(q):
    """(S, n_heads, hd) -> (S, n_heads, 2 hd): an even head (``q1`` of its
    pair) in columns [0, hd), an odd one (``q2``) in [hd, 2 hd) of a row of
    zeros, so that its scores against a packed key row ``[k1 | k2]`` are its
    own key's alone (the zeros add exactly nothing)."""
    even = (jnp.arange(q.shape[1]) % 2 == 0)[None, :, None]
    z = jnp.zeros((), q.dtype)
    return jnp.concatenate([jnp.where(even, q, z), jnp.where(even, z, q)],
                           axis=-1)


def differential(ctx, w, i, depth, cfg: ModelConfig, out_dtype):
    """The pairs' outputs from the heads' contexts.  ``ctx`` (S, n_heads x 2
    hd) float32: head ``2 p`` is ``a1`` of pair ``p`` (``softmax(q1 k1^T) [v1
    | v2]``), head ``2 p + 1`` its ``a2``.  ``w``: the layer's stack, ``i`` the
    layer within it, ``depth`` the layer's depth in the whole stack (a traced
    scalar).  (S, n_heads x hd) in ``out_dtype``."""
    S = ctx.shape[0]
    f32 = jnp.float32
    a = ctx.astype(f32).reshape(S, cfg.n_heads // 2, 2, 2 * cfg.head_dim)
    base = 0.8 - 0.6 * jnp.exp(-0.3 * depth.astype(f32))
    lam = jnp.exp(jnp.sum(w["lam_q1"][i] * w["lam_k1"][i])) \
        - jnp.exp(jnp.sum(w["lam_q2"][i] * w["lam_k2"][i])) + base
    d = rms_norm(a[:, :, 0] - lam * a[:, :, 1], w["sub_norm"][i],
                 cfg.rms_eps) * (1.0 - base)
    return d.reshape(S, -1).astype(out_dtype)


def _ssm(h, w, mi, cache, pos_offset, n_valid, cfg: ModelConfig, live):
    """One ssm layer's mixer branch (models/mamba.py ``ssm_mixer`` behind the
    block's LayerNorm).  Returns (h + branch, cache, y (S, d_inner) f32: the
    scan's output before the gate)."""
    hn = layer_norm(h, w["attn_norm"][mi], w["attn_norm_b"][mi], cfg.rms_eps)
    out, cache, y = ssm_mixer(hn, w, mi, cache, pos_offset, n_valid, cfg,
                              live)
    return h + out, cache, y


def _queries(hn, w, i, cfg: ModelConfig):
    with jax.named_scope("wq"):
        q = linear_at(hn, w["wq"], i) + w["bq"][i].astype(hn.dtype)
    return pack_queries(q.reshape(hn.shape[0], cfg.n_heads, cfg.head_dim))


def _attention(h, w, ai, depth, kind: str, cache, positions, pos_offset,
               n_valid, cfg: ModelConfig, live, kv_bound):
    """A window or the full layer's mixer branch.  ``ai``: the layer within
    the attention stack (and, a window layer, within the window leaves)."""
    S = h.shape[0]
    f32 = jnp.float32

    def lin(x, name, bias):
        with jax.named_scope(name):
            return linear_at(x, w[name], ai) + w[bias][ai].astype(x.dtype)

    hn = layer_norm(h, w["attn_norm"][ai], w["attn_norm_b"][ai], cfg.rms_eps)
    qp = _queries(hn, w, ai, cfg)
    g = ring_view(cfg, kind == WINDOW)
    dtype = cache["k"].dtype
    # a pair's two heads side by side: (n_kv / 2, S, 2 hd)
    kh = lin(hn, "wk", "bk").astype(dtype).reshape(
        S, g.n_kv_heads, g.head_dim).transpose(1, 0, 2)
    vh = lin(hn, "wv", "bv").astype(dtype).reshape(
        S, g.n_kv_heads, g.head_dim).transpose(1, 0, 2)
    if kind == WINDOW:
        with jax.named_scope("window_attn"):
            if S == 1:
                ctx, cache = window_step(qp, kh, vh, cache, ai, pos_offset,
                                         live, g, f32)
            else:
                ctx, cache = window_slice(qp, kh, vh, cache, ai, pos_offset,
                                          n_valid, g, f32)
    else:
        ring = {"k": cache["k"], "v": cache["v"]}
        zero = jnp.int32(0)
        if S == 1 and ring_kernel_block(g):
            ctx, ring = _kernel_decode(qp, ring, zero, pos_offset, live, g,
                                       f32, kh[:, 0], vh[:, 0])
        else:
            with jax.named_scope("kv_write"):
                ring = {"k": jax.lax.dynamic_update_slice(
                            ring["k"], kh[None], (0, 0, pos_offset, 0)),
                        "v": jax.lax.dynamic_update_slice(
                            ring["v"], vh[None], (0, 0, pos_offset, 0))}
            ctx = _ring_attention(qp, ring["k"][0], ring["v"][0], None, None,
                                  ring, zero, positions, pos_offset, kv_bound,
                                  live, g, f32)
        cache = dict(cache, **ring)
    out = differential(ctx, w, ai, depth, cfg, h.dtype)
    return h + lin(out, "wo", "bo"), cache


def _cross(h, w, ci, depth, cache, positions, cfg: ModelConfig, live,
           kv_bound):
    """A cross layer's mixer branch: its own queries on the full layer's
    leaf, which holds this pass's rows already.  Reads, never writes.
    ``positions``: the rows' own (a prefill pass hands it the ONE row whose
    logits are read, wherever in the slice it lies)."""
    S = h.shape[0]
    pos_offset = positions[0]
    f32 = jnp.float32
    hn = layer_norm(h, w["attn_norm"][ci], w["attn_norm_b"][ci], cfg.rms_eps)
    qp = _queries(hn, w, ci, cfg)
    g = ring_view(cfg)
    ring = {"k": cache["k"], "v": cache["v"]}
    zero = jnp.int32(0)
    with jax.named_scope("shared_read"):
        if S == 1 and ring_kernel_block(g):
            ctx = _kernel_decode(qp, ring, zero, pos_offset, live, g, f32)
        else:
            ctx = _ring_attention(qp, ring["k"][0], ring["v"][0], None, None,
                                  ring, zero, positions, pos_offset, kv_bound,
                                  live, g, f32)
    out = differential(ctx, w, ci, depth, cfg, h.dtype)
    with jax.named_scope("wo"):
        return h + linear_at(out, w["wo"], ci) + w["bo"][ci].astype(h.dtype)


def _gmu(h, w, gi, m, cfg: ModelConfig):
    hn = layer_norm(h, w["attn_norm"][gi], w["attn_norm_b"][gi], cfg.rms_eps)
    with jax.named_scope("gmu"):
        gate = jax.nn.silu(linear_at(hn, w["in_proj"], gi).astype(jnp.float32))
        return h + linear_at((m * gate).astype(h.dtype), w["out_proj"], gi)


def _ffn(h, w, fi, cfg: ModelConfig):
    hn = layer_norm(h, w["ffn_norm"][fi], w["ffn_norm_b"][fi], cfg.rms_eps)
    return h + swiglu(hn, w, fi, "w_gate", "w_up", "w_down")


#: where the comparison with the reference reads the stream after the full
#: layer and ``m`` (benchmarks/compare_phi4flash.py): a callable (h, m) ->
#: None called with traced values at TRACE time, else None
TAP = None


def forward(params: dict, cfg: ModelConfig, tokens, pos_offset, cache: dict,
            last_idx=None, return_all: bool = False, live=None,
            with_stats: bool = False, with_picks: bool = False,
            kv_bound=None):
    """``models/llama.py forward`` for a ``phi4flash`` file: the loops over
    the period.  Under ``cfg.lower_only`` the pass ends after the full
    layer, and what it returns for logits is zeros that nobody reads.
    ``kv_bound``: a lane step's ``live_bound`` (the shared leaf's XLA loop)."""
    S = tokens.shape[0]
    layers = params["layers"]
    n_low, n_up = n_pairs(cfg)
    for kind, n in ((SSM, n_low + 1), (ATTN, n_low + 1), (GMU, n_up),
                    (CROSS, n_up), (FFN, cfg.n_layers)):
        for path, leaf in jax.tree_util.tree_flatten_with_path(
                layers[kind])[0]:
            if leaf.shape[0] != n:
                raise ValueError(
                    f"stacked leaf {kind}{jax.tree_util.keystr(path)} has "
                    f"{leaf.shape[0]} layers but the file names {n} of "
                    "that kind")
    h = embed(params, tokens, cfg.dim)
    positions = pos_offset + jnp.arange(S, dtype=jnp.int32)
    n_valid = jnp.int32(S) if last_idx is None else last_idx + 1
    attn_depth = jnp.asarray(depths(cfg, WINDOW, FULL))
    cross_depth = jnp.asarray(depths(cfg, CROSS))

    def low(t, kind, h, cache):
        """The pair (ssm t, attention t) at depths 2 t and 2 t + 1."""
        h, cache, y = _ssm(h, layers[SSM], t, cache, pos_offset, n_valid, cfg,
                           live)
        h = _ffn(h, layers[FFN], 2 * t, cfg)
        h, cache = _attention(h, layers[ATTN], t, attn_depth[t], kind, cache,
                              positions, pos_offset, n_valid, cfg, live,
                              kv_bound)
        return _ffn(h, layers[FFN], 2 * t + 1, cfg), cache, y

    def low_body(t, carry):
        return low(jnp.int32(t), WINDOW, *carry)[:2]

    h, cache = jax.lax.fori_loop(0, n_low, low_body, (h, cache))
    h, cache, m = low(jnp.int32(n_low), FULL, h, cache)
    if TAP is not None:
        TAP(h, m)
    if cfg.lower_only:
        shape = (S, cfg.vocab_size) if return_all else (cfg.vocab_size,)
        return jnp.zeros(shape, jnp.float32), cache

    def upper(h, m, positions):
        """The (gmu, cross) pairs on rows ``h`` at ``positions``."""
        def body(t, h):
            t = jnp.int32(t)
            first = 2 * (n_low + 1) + 2 * t
            h = _gmu(h, layers[GMU], t, m, cfg)
            h = _ffn(h, layers[FFN], first, cfg)
            h = _cross(h, layers[CROSS], t, cross_depth[t], cache, positions,
                       cfg, live, kv_bound)
            return _ffn(h, layers[FFN], first + 1, cfg)

        return jax.lax.fori_loop(0, n_up, body, h)

    def head(x):
        hn = layer_norm(x, params["out_norm"], params["out_norm_b"],
                        cfg.rms_eps)
        with jax.named_scope("head"):
            return linear(hn.astype(jnp.bfloat16), params["output"]
                          ).astype(jnp.float32)[:, :cfg.vocab_size]

    if return_all:
        return head(upper(h, m, positions)), cache
    # the upper half on the ONE row whose logits are read: nothing above
    # the full layer writes a cache, so the other rows' are nobody's
    if last_idx is None:
        last_idx = jnp.int32(S - 1)
    if S > 1:
        h = jax.lax.dynamic_slice_in_dim(h, last_idx, 1, axis=0)
        m = jax.lax.dynamic_slice_in_dim(m, last_idx, 1, axis=0)
        positions = jax.lax.dynamic_slice_in_dim(positions, last_idx, 1)
    return head(upper(h, m, positions))[0], cache


# ---------------------------------------------------------------------------
# the kind's object
# ---------------------------------------------------------------------------

def layers_run(cfg: ModelConfig) -> int:
    """Layers a slice's program runs on all its rows: under
    ``cfg.lower_only`` those up to the full layer, which every program
    runs so; the layers above it run on ONE row of a slice that holds a
    prompt's last token, and on none of any other."""
    return cfg.n_layers - 2 * n_pairs(cfg)[1]


def _slice_cfg(cfg: ModelConfig, holds_last: bool) -> ModelConfig:
    return cfg if holds_last else dataclasses.replace(cfg, lower_only=True)


def _health(cfg: ModelConfig, engine) -> dict:
    n_low, n_up = n_pairs(cfg)
    return {
        "kind": SSM_WINDOW_SHARED,
        "ssm_layers": cfg.n_layers_of(SSM),
        "window_layers": n_low,
        "window": cfg.sliding_window,
        "window_slots": cfg.window_slots,
        "shared_leaf_readers": 1 + n_up,
        "gmu_layers": cfg.n_layers_of(GMU),
        "heads_per_ring_row": 2,
        "bytes_per_lane": cache_nbytes(cfg),
        "state_bytes": state_nbytes(cfg),
        "prefill_layers": [layers_run(cfg), cfg.n_layers],
        "embedding": "q6k-rows" if isinstance(
            engine.params.get("tok_emb"), dict) else "bf16",
        "prefix_reuse": "off: a state that has integrated a prompt cannot "
                        "be rolled back to a prefix's end",
        "kv_paged": "refused at start"}


def _note_decode(counts: dict, cfg: ModelConfig, wanted: list, n_steps: int,
                 live: list | None = None) -> None:
    dispatched = wanted if live is None else live
    g = ring_view(cfg)
    block = ring_kernel_block(g)
    n_low, n_up = n_pairs(cfg)
    readers = 1 + n_up
    counts["state_updates"] += len(wanted) * n_steps * cfg.n_layers_of(SSM)
    counts["shared_reads"] += len(wanted) * n_steps * readers
    counts["shared_steps"] += len(wanted) * n_steps
    if block:    # the kernels store the step's rows: a window layer, the full
        counts["rows_written"] += len(dispatched) * n_steps * (n_low + 1)
    # the window leaves, whole, against the windows' live positions
    w = window_chunk_counts(wanted, n_steps, dataclasses.replace(
        cfg, attn_kinds=(WINDOW,) * n_low), max(dispatched, default=0))
    counts["window_read"] += w["window_read"]
    counts["window_live"] += w["window_live"]
    # the shared leaf, once a reading layer
    bound = None if block else max(dispatched, default=0)
    for p in wanted:
        for t in range(n_steps):
            at = (p if bound is None else bound) + t
            counts["shared_read"] += readers * decode_read_slots(
                at, cfg.n_ctx, block)[1]
            counts["shared_live"] += readers * min(p + t + 1, cfg.n_ctx)
    # the ring totals keep their meaning: the sum over the leaves
    counts["read"] = counts["window_read"] + counts["shared_read"]
    counts["live"] = counts["window_live"] + counts["shared_live"]


def _note_lanes(counts: dict, cfg: ModelConfig, lanes: int,
                n_steps: int) -> None:
    counts["state_steps"] += lanes * n_steps * cfg.n_layers_of(SSM)


def _note_slice(counts: dict, cfg: ModelConfig, tokens: int) -> int:
    """One prefill program's layer-rows: those it ran, and those of the
    layers above the full layer that it did not (a slice that holds a
    prompt's last token runs them on that ONE row)."""
    above = cfg.n_layers - layers_run(cfg)
    run = tokens * layers_run(cfg) + (0 if cfg.lower_only else above)
    counts["layer_rows_run"] += run
    counts["layer_rows_skipped"] += tokens * cfg.n_layers - run
    counts["slices_lower" if cfg.lower_only else "slices_whole"] += 1
    return run


def _note_prefill(counts, cfg: ModelConfig, n_prompt: int, slices) -> dict:
    # nothing is reused, so every prompt's first pass is at position 0
    counts["state_starts"] += 1
    if slices is None:
        return {}
    wraps = max(n_prompt - 1, 0) // cfg.window_slots
    return {"slices": len(slices), "slices_lower_only": len(slices) - 1,
            "layers_run": [layers_run(cfg), cfg.n_layers],
            "layer_rows_skipped": (cfg.n_layers - layers_run(cfg)) * (
                sum(n for _, n in slices) - 1),
            "windows_wrapped": wraps}


def _span_attrs(cfg: ModelConfig) -> dict:
    return {"ssm_layers": cfg.n_layers_of(SSM),
            "shared_leaf_readers": 1 + n_pairs(cfg)[1],
            "window_slots": cfg.window_slots}


def _attn_impl(cfg: ModelConfig, asked: str) -> str:
    """``auto`` by the width of a row of the RING (a pair's: 128), which is
    what the kernels read, not by a head's."""
    if asked == "auto" and jax.default_backend() == "tpu" \
            and ring_view(cfg).head_dim % _LANES == 0:
        return "pallas"
    return asked


def _probe_kernels(cfg: ModelConfig, asked: str, attn_impl: str, probed):
    """The slice's scan is a kernel of its own (ops/pallas/ssmscan.py)."""
    return probe_scan_kernel(cfg, attn_impl, probed), attn_impl


CACHE = CacheKind(
    name=SSM_WINDOW_SHARED, arch="phi4flash",
    init=init_cache, nbytes=cache_nbytes, forward=forward,
    step_bound=ring_step_bound,      # the shared leaf's XLA loop
    supports={
        "int8": "its state + window + shared-ring cache is float32 states "
                "and bf16 rows only",
        "paged": "a pool page is a run of ring slots by token position, and "
                 "its ssm layers keep a state and its window layers slots "
                 "that cannot be rolled back to a shared prefix"},
    attn_impl=_attn_impl, probe_kernels=_probe_kernels,
    # a slice's XLA attention holds (heads, rows, n_ctx) float32 scores
    widest_slice=lambda cfg: 0 if cfg.attn_impl == "pallas" else 256,
    decode_kernel_block=lambda cfg: ring_kernel_block(ring_view(cfg)),
    health=_health, engine_health=_engine_health,
    own_gauges={"ssm_state_updates_total": "state_updates",
                "ssm_state_steps_total": "state_steps",
                "ssm_state_starts_total": "state_starts",
                "shared_leaf_reads_total": "shared_reads",
                "shared_leaf_steps_total": "shared_steps",
                "shared_leaf_slots_read_total": "shared_read",
                "shared_leaf_slots_live_total": "shared_live",
                "window_slots_read_total": "window_read",
                "window_slots_live_total": "window_live",
                "prefill_layer_rows_run_total": "layer_rows_run",
                "prefill_layer_rows_skipped_total": "layer_rows_skipped",
                'prefill_programs_total{stack="lower"}': "slices_lower",
                'prefill_programs_total{stack="whole"}': "slices_whole"},
    note_decode=_note_decode, note_prefill=_note_prefill,
    note_slice=_note_slice, note_lanes=_note_lanes, slice_cfg=_slice_cfg,
    span_attrs=_span_attrs)
