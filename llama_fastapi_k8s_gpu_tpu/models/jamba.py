"""The eighth cache KIND: a selective-scan state in nearly every layer and
a ring of ONE K/V head in the few that attend (``general.architecture =
"jamba"``; ``cfg.mixers``; ``cache_kind`` ``ssm-state+ring``).

Every layer is ``h += mixer(RMS_in(h)); h += W_down(silu(W_gate n) * W_up
n)``, ``n = RMS_ff(h)``; a final RMSNorm, then the tied head.  Nothing
rotates: there is no positional encoding anywhere.  The mixer is the layer's
(``attention.head_count_kv`` is an array, 0 in a scan layer):

- ``"ssm"`` (Mamba-1, models/mamba.py: the mixer this block shares with
  models/phi4flash.py) with the family's three INNER RMSNorms, on ``dt``
  (over ``ssm_dt_rank``), ``B`` and ``C`` (over ``ssm_d_state``), between
  ``x_proj`` and ``dt_proj`` / the scan.  Leaves ``state`` (ssm layers,
  d_state, d_inner / 128, 128) float32 and ``conv`` (ssm layers, d_conv - 1,
  d_inner): a sequence's whole cache in such a layer, however long it is.
- ``"attn"``: ``q = W_q hn`` (``n_heads`` of ``head_dim``), ``k = W_k hn``,
  ``v = W_v hn`` (``n_kv_heads``: ONE in the published model), no biases,
  causal over every earlier position, on a ring ``k`` / ``v`` (attention
  layers, n_kv, n_ctx, head_dim) read by models/llama.py's ring functions
  and kernels as they stand.  At 20 query heads on one KV head the flash
  kernel's head axis has one step and the decode kernel pads a lane's 20
  rows to 32; at a ring of 262144 slots the flash kernel's walk ends at the
  slice's own end (ops/pallas/attention.py ``WALK_WHOLE_STEPS``), which
  :func:`prefill_walk` counts.

What the state kinds share holds here: a row of padding past the prompt's
end reaches no leaf, **the pass that starts at position 0 starts from
zero**, a lane that holds no request keeps its leaves, nothing can be rolled
back to an earlier position (prefix reuse and lane claims are off).  The
stack is walked as RUNS of one mixer kind, each run of scan layers a
``fori_loop`` (7 / 13 / 6 around layers 7 and 21 of the published 28).
Weights are THREE stacks: ``ssm``, ``attn`` and every layer's feed-forward,
``ffn``.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ..ops.linear import linear, linear_at
from .cache import CacheKind
from .config import ATTN, SSM, SSM_RING, ModelConfig
from .llama import (
    _kernel_decode, _ring_attention, embed, note_ring_decode,
    ring_kernel_block, ring_step_bound, rms_norm)
from .mamba import (
    FFN, engine_health, init_leaves, probe_scan_kernel, ssm_mixer,
    state_nbytes)
from .routed import swiglu


def runs(cfg: ModelConfig) -> list[tuple[str, int, int, int]]:
    """The stack as runs of one mixer kind, in order: (mixer kind, the
    run's first layer in the stack, within its kind's weights and leaves,
    layers in the run)."""
    out = []
    seen = {SSM: 0, ATTN: 0}
    for li, mixer in enumerate(cfg.mixers):
        if out and out[-1][0] == mixer:
            out[-1][3] += 1
        else:
            out.append([mixer, li, seen[mixer], 1])
        seen[mixer] += 1
    return [tuple(r) for r in out]


def ring_shape(cfg: ModelConfig) -> tuple:
    return (cfg.n_layers_of(ATTN), cfg.n_kv_heads, cfg.n_ctx, cfg.head_dim)


def init_cache(cfg: ModelConfig, dtype=jnp.bfloat16) -> dict:
    if cfg.kv_dtype not in ("bf16", "bfloat16"):
        raise ValueError(
            f"kv_dtype={cfg.kv_dtype!r} cannot hold architecture 'jamba': "
            "its ssm-state + ring cache is float32 states and bf16 rows only")
    return {**init_leaves(cfg, dtype),
            "k": jnp.zeros(ring_shape(cfg), dtype),
            "v": jnp.zeros(ring_shape(cfg), dtype)}


def ring_nbytes(cfg: ModelConfig) -> int:
    row = 2 * cfg.n_kv_heads * cfg.head_dim * 2            # K and V, bf16
    return row * cfg.n_layers_of(ATTN) * cfg.n_ctx


def cache_nbytes(cfg: ModelConfig) -> int:
    return ring_nbytes(cfg) + state_nbytes(cfg)


# ---------------------------------------------------------------------------
# the two mixers
# ---------------------------------------------------------------------------

def _ssm(h, w, mi, cache, pos_offset, n_valid, cfg: ModelConfig, live):
    """A scan layer's mixer branch: models/mamba.py ``ssm_mixer`` behind the
    block's RMSNorm, with the three inner norms.  Returns (h + branch,
    cache)."""
    hn = rms_norm(h, w["attn_norm"][mi], cfg.rms_eps)
    out, cache, _ = ssm_mixer(
        hn, w, mi, cache, pos_offset, n_valid, cfg, live,
        inner_norm=lambda x, name: rms_norm(x, w[name][mi], cfg.rms_eps))
    return h + out, cache


def _attention(h, w, ai, cache, positions, pos_offset, cfg: ModelConfig,
               live, kv_bound):
    """An attention layer's mixer branch, unrotated.  ``ai``: the layer
    within the attention layers' weights and rings.  Returns (h + branch,
    cache)."""
    S = h.shape[0]
    n_kv, hd = cfg.n_kv_heads, cfg.head_dim

    def lin(x, name):
        with jax.named_scope(name):
            return linear_at(x, w[name], ai)

    hn = rms_norm(h, w["attn_norm"][ai], cfg.rms_eps)
    q = lin(hn, "wq").reshape(S, cfg.n_heads, hd)
    dtype = cache["k"].dtype
    kh = lin(hn, "wk").astype(dtype).reshape(S, n_kv, hd).transpose(1, 0, 2)
    vh = lin(hn, "wv").astype(dtype).reshape(S, n_kv, hd).transpose(1, 0, 2)
    ring = {"k": cache["k"], "v": cache["v"]}
    if S == 1 and ring_kernel_block(cfg):
        ctx, ring = _kernel_decode(q, ring, ai, pos_offset, live, cfg,
                                   h.dtype, kh[:, 0], vh[:, 0])
    else:
        with jax.named_scope("kv_write"):
            ring = {"k": jax.lax.dynamic_update_slice(
                        ring["k"], kh[None], (ai, 0, pos_offset, 0)),
                    "v": jax.lax.dynamic_update_slice(
                        ring["v"], vh[None], (ai, 0, pos_offset, 0))}
        ck = jax.lax.dynamic_index_in_dim(ring["k"], ai, 0, keepdims=False)
        cv = jax.lax.dynamic_index_in_dim(ring["v"], ai, 0, keepdims=False)
        ctx = _ring_attention(q, ck, cv, None, None, ring, ai, positions,
                              pos_offset, kv_bound, live, cfg, h.dtype)
    return h + lin(ctx, "wo"), dict(cache, **ring)


def _ffn(h, w, fi, cfg: ModelConfig):
    hn = rms_norm(h, w["ffn_norm"][fi], cfg.rms_eps)
    return h + swiglu(hn, w, fi, "w_gate", "w_up", "w_down")


def forward(params: dict, cfg: ModelConfig, tokens, pos_offset, cache: dict,
            last_idx=None, return_all: bool = False, live=None,
            with_stats: bool = False, with_picks: bool = False,
            kv_bound=None):
    """``models/llama.py forward`` for a ``jamba`` file: the runs of
    :func:`runs` in order, each a ``fori_loop`` over its kind's stack and
    leaves: runs of scan layers, an attention layer between them.
    ``kv_bound``: a lane step's ``live_bound`` (the rings' XLA loop)."""
    S = tokens.shape[0]
    layers = params["layers"]
    for kind, n in ((SSM, cfg.n_layers_of(SSM)), (ATTN, cfg.n_layers_of(ATTN)),
                    (FFN, cfg.n_layers)):
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

    def layer(mixer, li, mi, h, cache):
        if mixer == SSM:
            h, cache = _ssm(h, layers[SSM], mi, cache, pos_offset, n_valid,
                            cfg, live)
        else:
            h, cache = _attention(h, layers[ATTN], mi, cache, positions,
                                  pos_offset, cfg, live, kv_bound)
        return _ffn(h, layers[FFN], li, cfg), cache

    carry = (h, cache)
    # (a run of ONE layer is a loop too: under ``vmap`` a kernel called
    # outside any loop is named ``vmap(<kernel>)`` in the compiled program,
    # and the benchmark's readers find the kernels by their own names)
    for mixer, first, mfirst, count in runs(cfg):
        carry = jax.lax.fori_loop(
            0, count,
            lambda t, c, mixer=mixer, first=first, mfirst=mfirst: layer(
                mixer, jnp.int32(first + t), jnp.int32(mfirst + t), *c),
            carry)
    h, cache = carry

    def head(x):
        hn = rms_norm(x, params["out_norm"], cfg.rms_eps)
        with jax.named_scope("head"):
            return linear(hn.astype(jnp.bfloat16), params["output"]
                          ).astype(jnp.float32)[:, :cfg.vocab_size]

    if return_all:
        return head(h), cache
    if last_idx is None:
        last_idx = jnp.int32(S - 1)
    h_last = jax.lax.dynamic_slice_in_dim(h, last_idx, 1, axis=0)
    return head(h_last)[0], cache


# ---------------------------------------------------------------------------
# the kind's object
# ---------------------------------------------------------------------------

def prefill_walk(cfg: ModelConfig, slices) -> tuple[int, int]:
    """(key blocks the prefill attention calls of a prompt NEEDED, key
    blocks their grids WALKED), over the plan's ``slices`` [(offset,
    tokens)] and the attention layers: a call of ``tokens`` rows at
    ``offset`` needs the fused blocks up to its last row's position, once a
    row tile, and walks what ops/pallas/attention.py ``flash_plan`` says it
    walks (the same function builds the kernel's grid).  (0, 0) where the
    slices' attention is no kernel."""
    if cfg.attn_impl != "pallas":
        return 0, 0
    from ..ops.pallas.attention import flash_plan, flash_steps_walked

    live = walked = 0
    for off, n in slices:
        if n == 1:
            continue
        plan = flash_plan(n, cfg.n_heads, cfg.n_kv_heads, cfg.n_ctx)
        end = off + n
        tiles = plan["row_tiles"] * cfg.n_kv_heads * cfg.n_layers_of(ATTN)
        live += tiles * min(-(-end // plan["bkf"]), plan["key_steps"])
        walked += tiles * flash_steps_walked(plan, end)
    return live, walked


def _health(cfg: ModelConfig, engine) -> dict:
    return {
        "kind": SSM_RING,
        "ssm_layers": cfg.n_layers_of(SSM),
        "ring_layers": cfg.n_layers_of(ATTN),
        "ring_kv_heads": cfg.n_kv_heads,
        "query_heads_per_kv_head": cfg.n_heads // cfg.n_kv_heads,
        "inner_norms": ["dt", "b", "c"],
        "bytes_per_lane": cache_nbytes(cfg),
        "state_bytes": state_nbytes(cfg),
        "ring_bytes": ring_nbytes(cfg),
        "embedding": "q6k-rows" if isinstance(
            engine.params.get("tok_emb"), dict) else "bf16",
        "prefix_reuse": "off: a state that has integrated a prompt cannot "
                        "be rolled back to a prefix's end",
        "kv_paged": "refused at start"}


def _note_decode(counts: dict, cfg: ModelConfig, wanted: list, n_steps: int,
                 live: list | None = None) -> None:
    counts["state_updates"] += len(wanted) * n_steps * cfg.n_layers_of(SSM)
    # the ring's three, summed over the attention layers and no others
    g = dataclasses.replace(cfg, n_layers=cfg.n_layers_of(ATTN))
    one = dict.fromkeys(("read", "live", "rows_written"), 0)
    note_ring_decode(one, g, wanted, n_steps, live)
    counts["rows_written"] += one["rows_written"]       # a row a ring layer
    counts["read"] += one["read"] * g.n_layers
    counts["live"] += one["live"] * g.n_layers


def _note_lanes(counts: dict, cfg: ModelConfig, lanes: int,
                n_steps: int) -> None:
    counts["state_steps"] += lanes * n_steps * cfg.n_layers_of(SSM)


def _note_prefill(counts, cfg: ModelConfig, n_prompt: int, slices) -> dict:
    # nothing is reused, so every prompt's first pass is at position 0
    counts["state_starts"] += 1
    if slices is None:
        return {}
    live, walked = prefill_walk(cfg, slices)
    counts["ring_blocks_live"] += live
    counts["ring_blocks_walked"] += walked
    return {"slices": len(slices), "ring_blocks_live": live,
            "ring_blocks_walked": walked}


def _span_attrs(cfg: ModelConfig) -> dict:
    return {"ssm_layers": cfg.n_layers_of(SSM),
            "ring_layers": cfg.n_layers_of(ATTN)}


def _probe_kernels(cfg: ModelConfig, asked: str, attn_impl: str, probed):
    """The ring's kernels at THIS file's heads (more than 16 query heads on
    a KV head, or a ring long enough for the bounded walk, are forms the
    engine's probe does not compile), then the slice's scan."""
    if attn_impl == "pallas":
        import logging

        from ..ops.pallas.probe import probe_ring_wide_group

        probed.append("ring_wide_group")
        err = probe_ring_wide_group(cfg.n_heads // cfg.n_kv_heads)
        if err is not None:
            logging.getLogger(__name__).error(
                "the ring's kernels failed their compile probe at %d query "
                "heads a KV head; serving with attn_impl=xla: %s",
                cfg.n_heads // cfg.n_kv_heads, err)
            attn_impl = "xla"
    return probe_scan_kernel(cfg, attn_impl, probed), attn_impl


CACHE = CacheKind(
    name=SSM_RING, arch="jamba",
    init=init_cache, nbytes=cache_nbytes, forward=forward,
    step_bound=ring_step_bound,      # the rings' XLA loop
    supports={
        "int8": "its ssm-state + ring cache is float32 states and bf16 "
                "rows only",
        "paged": "a pool page is a run of ring slots by token position, and "
                 "its scan layers keep a state that cannot be rolled back "
                 "to a shared prefix"},
    probe_kernels=_probe_kernels,
    # a slice's XLA attention holds (heads, rows, n_ctx) float32 scores
    widest_slice=lambda cfg: 0 if cfg.attn_impl == "pallas" else 256,
    decode_kernel_block=ring_kernel_block,
    health=_health, engine_health=engine_health,
    own_gauges={"ssm_state_updates_total": "state_updates",
                "ssm_state_steps_total": "state_steps",
                "ssm_state_starts_total": "state_starts",
                "prefill_ring_blocks_live_total": "ring_blocks_live",
                "prefill_ring_blocks_walked_total": "ring_blocks_walked"},
    note_decode=_note_decode, note_prefill=_note_prefill,
    counts_prefill=True, note_lanes=_note_lanes, span_attrs=_span_attrs)
