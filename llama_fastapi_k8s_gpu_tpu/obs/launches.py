"""Deterministic kernel-launch audit of one decode step (lfkt-perf).

The devtime registry (obs/devtime.py) counts HOST dispatches — one per
jit entry call — which is the right grain for compile attribution but
blind to what this repo's round-5 profiling showed actually bounds
decode: the number of *device kernel launches inside* one decode step
(the per-layer fused-matmul / attention / KV-write chain).  This module
makes that number an exact, device-independent integer, the same way the
dispatch pins are: trace the step, walk its jaxpr, and count the
launch-bearing primitives (``pallas_call`` + ``dot_general`` — the MXU /
Mosaic programs XLA cannot fuse away; elementwise ops fuse into their
consumers and are not launches) weighted by the runtime trip count of
every enclosing ``scan`` (``fori_loop`` over layers lowers to one).

That makes the step's launch count a CPU-pinnable fact: the layer loop
traces L × chain launch primitives, and a new dot on the decode path (or
a lost loop) changes the integer in tier-1 (tests/test_perf_pins.py)
without a chip.

Caveats, stated rather than hidden: a ``while`` body's trip count is not
static — its launches are counted ONCE and the audit marks
``while_loops`` so a reader knows the total is a floor; branch
(``cond``) arms are counted at the maximum over arms.  The decode step
has one ``while``: the decode attention's block loop over the live part
of the ring (models/llama.py ``decode_attention``), whose two
contractions are therefore counted once per layer.
"""

from __future__ import annotations

import functools

__all__ = ["count_launches", "decode_step_launches"]

#: primitives that survive XLA fusion as their own device kernel launch
#: (a Mosaic program or an MXU dot); everything else fuses into a
#: neighbor's loop nest
LAUNCH_PRIMS = frozenset({
    "pallas_call", "dot_general", "conv_general_dilated",
})

#: primitives whose params carry sub-jaxprs to inline transparently
#: (no runtime multiplier of their own)
_INLINE_PARAMS = ("jaxpr", "call_jaxpr")


def _sub_jaxpr(obj):
    """ClosedJaxpr | Jaxpr -> Jaxpr."""
    return getattr(obj, "jaxpr", obj)


def _walk(jaxpr, audit: dict, mult: int, in_loop: bool) -> None:
    for eq in jaxpr.eqns:
        name = eq.primitive.name
        if name in LAUNCH_PRIMS:
            audit["total"] += mult
            audit["in_loop" if in_loop else "outside"] += mult
            key = name if in_loop else f"{name}(flat)"
            audit["by_prim"][key] = audit["by_prim"].get(key, 0) + mult
            # ONE launch regardless of its body: a pallas_call's params
            # carry the kernel jaxpr (visible in interpret mode) — its
            # inner dots execute inside this launch and must not be
            # double-counted as launches of their own
            continue
        if name == "scan":
            trip = int(eq.params["length"])
            audit["loop_trips"].append(trip)
            _walk(_sub_jaxpr(eq.params["jaxpr"]), audit, mult * trip, True)
        elif name == "while":
            audit["while_loops"] += 1     # trip unknown: counted once (floor)
            _walk(_sub_jaxpr(eq.params["body_jaxpr"]), audit, mult, True)
        elif name == "cond":
            # count the heaviest arm: launches the step MAY pay
            arms = []
            for br in eq.params["branches"]:
                sub = {"total": 0, "in_loop": 0, "outside": 0,
                       "by_prim": {}, "loop_trips": [], "while_loops": 0}
                _walk(_sub_jaxpr(br), sub, mult, in_loop)
                arms.append(sub)
            if arms:
                worst = max(arms, key=lambda a: a["total"])
                for k in ("total", "in_loop", "outside", "while_loops"):
                    audit[k] += worst[k]
                for k, v in worst["by_prim"].items():
                    audit["by_prim"][k] = audit["by_prim"].get(k, 0) + v
                audit["loop_trips"].extend(worst["loop_trips"])
        else:
            for pname in _INLINE_PARAMS:
                sub = eq.params.get(pname) if eq.params else None
                if sub is not None and hasattr(_sub_jaxpr(sub), "eqns"):
                    _walk(_sub_jaxpr(sub), audit, mult, in_loop)


def count_launches(fn, *args) -> dict:
    """Trace ``fn(*args)`` (shape-only: args may be ShapeDtypeStructs) and
    return its launch audit::

        {"total":      launch primitives executed per call (trip-weighted),
         "in_loop":    the subset inside any scan (the layer loop),
         "outside":    flat launches (embedding epilogue, output head),
         "loop_trips": scan trip counts encountered (outermost first),
         "by_prim":    {primitive: weighted count},
         "while_loops": bodies counted once because their trip count is
                        not static (1 for the decode step)}
    """
    import jax

    jx = jax.make_jaxpr(fn)(*args)
    audit = {"total": 0, "in_loop": 0, "outside": 0, "by_prim": {},
             "loop_trips": [], "while_loops": 0}
    _walk(jx.jaxpr, audit, 1, False)
    return audit


def decode_step_launches(params, cfg) -> dict:
    """Launch audit of ONE single-token decode step under ``cfg`` —
    :func:`models.llama.decode_step` traced at shape level (no device
    work, no allocation of a real ring)."""
    import jax
    import jax.numpy as jnp

    from ..models.llama import decode_step, init_cache

    cache = jax.eval_shape(functools.partial(init_cache, cfg))
    tok = jax.ShapeDtypeStruct((), jnp.int32)
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    shaped = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
    return count_launches(
        lambda p, t, po, c: decode_step(p, cfg, t, po, c),
        shaped, tok, pos, cache)
