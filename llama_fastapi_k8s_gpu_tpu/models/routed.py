"""A feed-forward kind per LAYER, and an expert layer that holds a SHARE of
its experts: what the ``deepseek2`` block (models/mla.py) and the
``exaone-moe`` block (models/hybrid.py) have in common.

- The first ``cfg.n_dense_layers`` layers' feed-forward is the dense SwiGLU
  of ``cfg.ffn_dim``; the others' a float32 router (:func:`route_grouped`)
  over ``cfg.n_experts`` experts of ``cfg.expert_ffn_dim`` plus a shared
  expert on every token (:func:`expert_branch`).  The two kinds are two
  stacks of weights (``params["layers"]["dense" | "moe"]``).
- The expert layer is told which experts it HOLDS (``cfg.experts_first``,
  ``cfg.n_held``): the router scores all ``n_experts`` and picks as
  published; a pick outside the held ones becomes the sentinel "no pick"
  of ``ops/pallas/experts.py`` and adds nothing.  That is one chip's share
  of an expert-parallel layer without its exchange.
- The router may have outputs that are NOT experts (``cfg.n_zero_experts``;
  ``longcat-flash``'s identity experts): it scores ``n_experts +
  n_zero_experts`` outputs, and a pick ``e >= n_experts`` adds its weight
  times the layer's own input (:func:`expert_branch`), here, in full,
  whatever share of the experts is held: it needs no weight and no
  exchange.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.custom_batching import custom_vmap

from ..ops.linear import linear_at
from .config import ModelConfig

DENSE, MOE = "dense", "moe"
HI = jax.lax.Precision.HIGHEST


def n_moe_layers(cfg: ModelConfig) -> int:
    return cfg.n_layers - cfg.n_dense_layers


def check_stacks(params: dict, cfg: ModelConfig, more: tuple = ()) -> None:
    """Every stacked leaf of a kind is as deep as the file names layers of
    that kind (a loop over layer ids would otherwise clamp its gathers).
    ``more``: further (kind, layers) stacks beside the two feed-forward
    kinds' (models/lfm2.py: its mixer kinds')."""
    for kind, n in ((DENSE, cfg.n_dense_layers), (MOE, n_moe_layers(cfg)),
                    *more):
        for path, leaf in jax.tree_util.tree_flatten_with_path(
                params["layers"].get(kind, {}))[0]:
            if leaf.shape[0] != n:
                raise ValueError(
                    f"stacked leaf {kind}{jax.tree_util.keystr(path)} has "
                    f"{leaf.shape[0]} layers but the file names {n} of "
                    "that kind")


def route_grouped(hn, w_router, bias, cfg: ModelConfig):
    """The router of a routed layer, float32.  Scores
    ``sigmoid`` (or ``softmax``) of ``W_r hn`` over ALL ``n_experts`` (and
    the ``n_zero_experts`` outputs after them: ``E`` below is their sum,
    the router's rows); the
    CHOICE on ``scores + bias``: a group's score is the sum of its two
    largest, the ``n_groups_used`` best groups are kept, the
    ``n_experts_used`` largest inside them picked; the weights are the
    picked experts' UNBIASED scores, divided by their sum (+
    ``expert_weights_eps``) where
    ``norm_topk_prob``, times ``expert_weights_scale``.  hn (S, dim),
    w_router (E, dim), bias (E,) -> (picks (S, k) int32 in [0, E), weights
    (S, k) f32)."""
    logits = jnp.einsum("sd,ed->se", hn.astype(jnp.float32), w_router,
                        precision=HI)
    scores = jax.nn.sigmoid(logits) if cfg.expert_gating == "sigmoid" \
        else jax.nn.softmax(logits, axis=-1)
    choice = scores + bias[None, :]
    S, E = choice.shape
    G = cfg.n_expert_groups
    if G > 1 and cfg.n_groups_used < G:
        grouped = choice.reshape(S, G, E // G)
        gscore = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)   # (S, G)
        _, keep = jax.lax.top_k(gscore, cfg.n_groups_used)
        kept = jnp.zeros((S, G), bool).at[
            jnp.arange(S)[:, None], keep].set(True)
        choice = jnp.where(kept[:, :, None], grouped, -jnp.inf
                           ).reshape(S, E)
    _, picks = jax.lax.top_k(choice, cfg.n_experts_used)
    weights = jnp.take_along_axis(scores, picks, axis=-1)
    if cfg.norm_topk_prob:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                             + cfg.expert_weights_eps)
    return picks.astype(jnp.int32), weights * cfg.expert_weights_scale


@custom_vmap
def _sum_over_lanes(n):
    """An int32 scalar that, under ``vmap`` over lanes, becomes its SUM over
    them as an unbatched value: the counters of a step are the step's, the
    same in every lane (as ``routed_experts``' rows per expert are)."""
    return n


@_sum_over_lanes.def_vmap
def _sum_over_lanes_rule(axis_size, in_batched, n):
    return _sum_over_lanes(jnp.sum(n) if in_batched[0] else n * axis_size), \
        False


def held_picks(picks, cfg: ModelConfig):
    """The router's picks as indices into the HELD experts' planes:
    ``pick - experts_first`` where this process holds the expert, else the
    sentinel ``n_held`` ("no pick": ops/pallas/experts.py); a pick of a
    zero expert (``>= n_experts``) is no held one's."""
    local = picks - cfg.experts_first
    mine = (local >= 0) & (local < cfg.n_held)
    if cfg.n_zero_experts:      # whatever range is held
        mine &= picks < cfg.n_experts
    return jnp.where(mine, local, cfg.n_held)


def swiglu(hn, layers, i, gate, up, down):
    def lin(x, name):
        with jax.named_scope(name):
            return linear_at(x, layers[name], i)

    gated = jax.nn.silu(lin(hn, gate).astype(jnp.float32)).astype(hn.dtype)
    return lin(gated * lin(hn, up), down)


def expert_branch(hn, layers, i, cfg: ModelConfig, live):
    """A routed layer's feed-forward on the normed rows ``hn``: the router,
    the held experts' products for their picks, the shared expert, the
    identity picks' ``(sum of their weights) * hn``.  Returns (out, (rows
    each HELD expert took (n_held,), the router's picks (S, k) over all its
    outputs, picks of live rows[, of those the picks of a zero expert:
    only where the router has any])."""
    from ..ops.pallas.experts import routed_experts

    with jax.named_scope("router"):
        picks, weights = route_grouped(
            hn, layers["w_router"][i], layers["router_bias"][i], cfg)
    mine = held_picks(picks, cfg)
    total = jnp.int32(picks.size)
    if live is not None:
        mine = jnp.where(live, mine, cfg.n_held)
        total = jnp.where(live, total, 0)
    total = _sum_over_lanes(total)
    with jax.named_scope("experts"):
        out, count = routed_experts(
            hn, mine, weights, layers["w_gate_exps"], layers["w_up_exps"],
            layers["w_down_exps"], i)
    if cfg.n_shared_experts:
        with jax.named_scope("shared_expert"):
            out = out + swiglu(hn, layers, i, "w_gate_sh", "w_up_sh",
                               "w_down_sh")
    if not cfg.n_zero_experts:
        return out, (count, picks, total)
    with jax.named_scope("zero_experts"):
        is_zero = picks >= cfg.n_experts
        w_zero = jnp.sum(jnp.where(is_zero, weights, 0.0), axis=-1)
        out = (out.astype(jnp.float32) + w_zero[:, None]
               * hn.astype(jnp.float32)).astype(out.dtype)
        n_zero = jnp.sum(is_zero, dtype=jnp.int32)
        if live is not None:
            n_zero = jnp.where(live, n_zero, 0)
    return out, (count, picks, total, _sum_over_lanes(n_zero))


def moe_stats(stats, all_picks, i, routed):
    """A routed layer's :func:`expert_branch` counters folded into the
    forward's carry: the counter vector of ``llama.expert_stats_len`` and
    the picks of layer ``i`` of the routed stack."""
    count, picks, *totals = routed      # the picks made[, the zero ones]
    read = jnp.sum(count > 0, dtype=jnp.int32)
    stats = stats + jnp.concatenate(
        [jnp.stack([jnp.int32(1), read]), count, jnp.stack(totals)])
    return stats, jax.lax.dynamic_update_slice(
        all_picks, picks[None], (i, 0, 0))
