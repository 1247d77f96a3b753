"""Device meshes and sharding rules.

The reference has no distributed backend at all — its only parallelism is
k8s-replica data parallelism behind a Service (SURVEY.md §2A "Parallelism
strategies").  On TPU the equivalent *and more* is declarative: build a
``jax.sharding.Mesh`` over the chips, annotate the param/cache pytrees with
``NamedSharding``s, and XLA inserts the collectives (all-gather /
psum / reduce-scatter) over ICI.  There is no NCCL analogue to wrap —
declaring shardings IS the communication backend on TPU (SURVEY.md §5
"Distributed communication backend").

Axes:
- ``dp`` — data parallel over concurrent requests (batch dim).
- ``tp`` — tensor parallel (Megatron-style): attention heads and FFN hidden
  sharded column-wise, output projections row-wise (psum on exit),
  KV cache sharded over kv-heads, LM head sharded over vocab.

The same rules drive the v5e-4 serving config and the virtual 8-device CPU
mesh used by tests and the driver's multi-chip dryrun.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.cache import cache_of
from ..models.config import ModelConfig


def make_mesh(dp: int = 1, tp: int = 1, sp: int = 1, devices=None) -> Mesh:
    """dp × tp × sp device mesh.  ``sp`` is the sequence-parallel axis used
    by ring attention (parallel/ring.py); it defaults to 1 so dp/tp-only
    callers see the same layouts as before."""
    if devices is None:
        devices = jax.devices()
    n = dp * tp * sp
    if n > len(devices):
        raise ValueError(f"mesh {dp}x{tp}x{sp} needs {n} devices, have {len(devices)}")
    mesh_devices = mesh_utils.create_device_mesh((dp, tp, sp), devices=devices[:n])
    return Mesh(mesh_devices, axis_names=("dp", "tp", "sp"))


def _ns(mesh: Mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, P(*spec))


def _linear_sharding(mesh: Mesh, col_parallel: bool) -> dict:
    """Sharding for a stacked linear {'w': (L,out,in)}, {'q','s'} (int8), or
    {'qs','sm'} (fused Q4_K; qs (L,out,in/2), sm (L,in/2048,out,128)).

    Column-parallel (wq/wk/wv/w_gate/w_up): shard the output dim.
    Row-parallel (wo/w_down): shard the input dim; XLA inserts the psum.

    Fused Q4_K shards its OUTPUT dim in both cases: the pallas matmul
    partitions over N (custom_partitioning in ops/pallas/qmatmul.py) but
    never over the contraction dim (K tiles are 2048-wide and e.g. ffn_down's
    7 tiles don't divide tp) — and for row-parallel layers, all-gathering the
    small activations beats all-gathering the quantized weights by ~3 orders
    of magnitude at decode (B=1: KBs of activations vs GBs of weights).
    """
    # fused layouts ({qs,sm} Q4_K / {q5s,q5h,sm5} Q5_K / {q4,q2,sm6} Q6_K)
    # shard their OUTPUT dim in both cases — see the docstring above
    fused_col = {
        "qs": _ns(mesh, None, "tp", None),
        "sm": _ns(mesh, None, None, "tp", None),
        "q5s": _ns(mesh, None, "tp", None),
        "q5h": _ns(mesh, None, "tp", None),
        "q5p": _ns(mesh, None, "tp", None),
        "sm5": _ns(mesh, None, None, "tp", None),
        "q4": _ns(mesh, None, "tp", None),
        "q2": _ns(mesh, None, "tp", None),
        "q6p": _ns(mesh, None, "tp", None),
        "sm6": _ns(mesh, None, None, "tp", None),
        "q8": _ns(mesh, None, "tp", None),
        "sm8": _ns(mesh, None, None, "tp", None),
    }
    if col_parallel:
        return {"w": _ns(mesh, None, "tp", None),
                "q": _ns(mesh, None, "tp", None),
                "s": _ns(mesh, None, "tp"),
                **fused_col}
    return {"w": _ns(mesh, None, None, "tp"),
            "q": _ns(mesh, None, None, "tp"),
            "s": _ns(mesh, None, None),
            **fused_col}


def _match_linear(shardings: dict, linear: dict) -> dict:
    return {k: shardings[k] for k in linear}


def param_shardings(params: dict, mesh: Mesh) -> dict:
    """NamedSharding pytree matching a param pytree from models.params."""
    col = _linear_sharding(mesh, True)
    row = _linear_sharding(mesh, False)
    layers = params["layers"]
    layer_shard = {}
    for name, leaf in layers.items():
        if name in ("attn_norm", "ffn_norm"):
            layer_shard[name] = _ns(mesh, None, None)
        elif not isinstance(leaf, dict) or name.endswith("_exps") \
                or any(isinstance(v, dict) for v in leaf.values()):
            # (and a layer kind's own stack, models/sala.py: tp = 1 there)
            # the routed block's router and QK-norm vectors, and its expert
            # planes: replicated (the grouped expert matmul has no
            # partitioning rule, so experts do not span a tp mesh)
            layer_shard[name] = jax.tree.map(lambda _: _ns(mesh), leaf)
        elif name in ("wq", "wk", "wv", "w_gate", "w_up"):
            layer_shard[name] = _match_linear(col, leaf)
        else:  # wo, w_down
            layer_shard[name] = _match_linear(row, leaf)
    out = params["output"]
    head = {"w": _ns(mesh, "tp", None), "q": _ns(mesh, "tp", None),
            "s": _ns(mesh, "tp"), "qs": _ns(mesh, "tp", None),
            "sm": _ns(mesh, None, "tp", None),
            "q5s": _ns(mesh, "tp", None), "q5h": _ns(mesh, "tp", None),
            "q5p": _ns(mesh, "tp", None),
            "sm5": _ns(mesh, None, "tp", None),
            "q4": _ns(mesh, "tp", None), "q2": _ns(mesh, "tp", None),
            "q6p": _ns(mesh, "tp", None),
            "sm6": _ns(mesh, None, "tp", None),
            "q8": _ns(mesh, "tp", None),
            "sm8": _ns(mesh, None, "tp", None)}
    out_shard = {k: head[k] for k in out}
    return {
        "tok_emb": _ns(mesh, None, None),      # replicated (gather-heavy)
        "layers": layer_shard,
        "out_norm": _ns(mesh, None),
        "output": out_shard,
        # a looped stack's exit gate (models/llama.py): whole on every chip
        **({"exit_gate": jax.tree.map(lambda _: _ns(mesh),
                                      params["exit_gate"])}
           if "exit_gate" in params else {}),
    }


def cache_shardings(cfg: ModelConfig, mesh: Mesh, batched: bool = False):
    """The cache leaves' layout, by the cache kind
    (``CacheKind.shardings``: a head-major (L, n_kv, ctx, hd) leaf's
    kv-heads over tp, a leaf with no heads whole on every chip); batch (if
    any) over dp."""
    lead = ("dp",) if batched else ()
    return {name: _ns(mesh, *lead, *axes)
            for name, axes in cache_of(cfg).shardings(cfg).items()}


def state_shardings(cfg: ModelConfig, mesh: Mesh, batched: bool = False) -> dict:
    """Shardings for the generation-state pytree (models.generate.init_state)."""
    if batched:
        scalar = _ns(mesh, "dp")
        vec = _ns(mesh, "dp", None)
    else:
        scalar = _ns(mesh)
        vec = _ns(mesh, None)
    return {
        "cache": cache_shardings(cfg, mesh, batched),
        "pos": scalar,
        "token": scalar,
        "window": vec,
        "wpos": scalar,
        "key": vec,
    }


def _fit_sharding(arr, ns: NamedSharding) -> NamedSharding:
    """Drop spec axes an array can't honor (dim not divisible by the mesh
    axis) — e.g. tiny test vocabularies vs a tp-sharded LM head.  Real model
    dims divide evenly and keep the full spec."""
    mesh = ns.mesh
    spec = list(ns.spec) + [None] * (arr.ndim - len(ns.spec))
    fixed = []
    for dim, axes in zip(arr.shape, spec):
        if axes is None:
            fixed.append(None)
            continue
        names = (axes,) if isinstance(axes, str) else tuple(axes)
        size = int(np.prod([mesh.shape[a] for a in names]))
        fixed.append(axes if dim % size == 0 else None)
    return NamedSharding(mesh, P(*fixed))


# layout → main leaf (the plane whose N dim decides the whole group's fit);
# "q6p" is the Q6_K `pre` layout's single combined plane
_FUSED_MAIN_KEY = {"qs": "qs", "q4": "q4", "q6p": "q6p",
                   "q5s": "q5s", "q5p": "q5p", "q8": "q8"}


def _fused_key(p: dict) -> str | None:
    for k in _FUSED_MAIN_KEY:
        if k in p:
            return k
    return None


def _fit_q4k(leaf: dict, shard: dict) -> dict:
    """Fused Q4_K/Q6_K leaves: keep the N sharding only if every local shard
    still satisfies the kernel's N tiling (128 sublanes on TPU, 8 in
    interpret mode); otherwise replicate the whole leaf — a half-sharded
    {qs, sm} / {q4, q2, sm6} group would just reshard inside the
    partition rule."""
    from ..ops.pallas import use_interpret

    gran = 8 if use_interpret() else 128
    key = _fused_key(leaf)
    qs = leaf[key]
    ns = shard[key]
    n_dim = qs.ndim - 2                      # (L, N, K/x) or (N, K/x)
    spec = list(ns.spec) + [None] * (qs.ndim - len(ns.spec))
    axes = spec[n_dim]
    keep = True
    if axes is not None:
        names = (axes,) if isinstance(axes, str) else tuple(axes)
        size = int(np.prod([ns.mesh.shape[a] for a in names]))
        N = qs.shape[n_dim]
        keep = N % size == 0 and (N // size) % gran == 0
    if keep:
        return {k: _fit_sharding(leaf[k], shard[k]) for k in leaf}
    return {k: NamedSharding(ns.mesh, P(*([None] * leaf[k].ndim)))
            for k in leaf}


def shard_fused_linear(w: dict, mesh: Mesh, axis: str = "tp") -> dict:
    """Shardings for ONE unstacked fused-layout linear ({qs,sm} /
    {q5s,q5h,sm5} / {q4,q2,sm6} without the layer dim): quantized planes
    (N, K/x) shard their output dim N; scale tables (kt, N, 128) shard N in
    the middle.  The single source for tests/dryruns that shard a bare
    fused dict — the stacked serving path uses :func:`param_shardings`."""
    return {k: (_ns(mesh, axis, None) if w[k].ndim == 2
                else _ns(mesh, None, axis, None)) for k in w}


def fit_shardings(params: dict, shardings: dict) -> dict:
    def fit(p, s):
        if isinstance(p, dict) and _fused_key(p):
            return _fit_q4k(p, s)
        return jax.tree.map(_fit_sharding, p, s)

    return jax.tree.map(
        fit, params, shardings,
        is_leaf=lambda x: isinstance(x, dict) and _fused_key(x) is not None)


def shard_params(params: dict, mesh: Mesh) -> dict:
    return jax.device_put(
        params, fit_shardings(params, param_shardings(params, mesh)))

