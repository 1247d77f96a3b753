"""Layer-looped decode step: K transformer layers per Pallas launch.

ROADMAP item 2 ("Kernel Looping: Eliminating Synchronization Boundaries",
PAPERS.md).  Round-5 profiling showed the decode ceiling is launch/DMA
overhead, not compute: an all-skip 8k flash probe still cost 14.3 of
15.4 ms/layer, and the per-layer path dispatches a separate fused-matmul /
attention / KV-write chain for every one of the L layers on every decode
step.  This module extends the ``kv_unroll`` idea ("U KV blocks per
launch", ops/pallas/attention.py) across the LAYER axis: one
``pallas_call`` whose grid iterates K layers in-kernel — rms-norm → QKV
matmuls → RoPE → KV write(-quantize) → decode attention (int8
fused-dequant reads included, "BitDecoding" PAPERS.md) → output proj →
MLP — so a decode step goes from O(L × ops) launches to O(L/K)
(``LFKT_DECODE_LAYER_UNROLL``; ``-1`` = all layers in ONE launch).

Bit-exactness contract: the kernel body executes the SAME source the
per-layer path executes — :func:`models.llama.rms_norm` /
:func:`~models.llama.rope` / :func:`~models.llama.
decode_attention`, :func:`ops.linear.linear` on the per-layer weight dicts,
:func:`~.kvquant.quantize_kv_xla`, and the same ``dynamic_update_slice``
ring write — traced per layer in the same order, on the same dtypes.  On
the CPU dev-gate (interpret mode) the looped greedy decode is therefore
bit-identical to the per-layer reference (tests/test_decode_loop.py, the
resplit/vbf32 adjudication pattern); on chip the Mosaic program is
adjudicated by ``bench.py --decode-unroll-sweep`` + the perf gate.

Residency: each grid step holds one layer's weights + its full KV ring
block in VMEM.  That bounds the serving shapes Mosaic will accept —
the startup probe (ops/pallas/probe.py: ``probe_decode_loop``) compiles
the engine's REAL ring geometry, so an over-budget shape degrades the
pod to the per-layer path at construction time with attribution
(``/debug/compiles`` degrade ledger), never at first traffic.  The probe
also verifies the partial-grid aliasing contract this kernel leans on:
cache layers outside the launched [layer0, layer0+K) window must retain
their input bytes through the aliased output.

The residual stream ``h`` rides VMEM scratch across grid steps (TPU
grids execute sequentially — the flash kernel's accumulator idiom); the
KV ring leaves are input/output-aliased so the update is in place, and
each layer's ring block is written exactly once by its own grid step.
"""

from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...models.params import LOOP_LINEARS as _LINEARS
from ...obs.devtime import register_program

logger = logging.getLogger(__name__)

__all__ = [
    "decode_loop_disabled",
    "decode_loop_step",
    "disable_decode_loop",
    "effective_unroll",
    "forward_layers_looped",
    "note_degrade",
]

#: probe-degrade pins (the ``force_xla_quant`` idiom, but PER GEOMETRY):
#: a Mosaic failure at engine construction pins the per-layer path for
#: that kernel geometry — keyed exactly like the probe's lru_cache, so a
#: co-resident model whose own geometry probes clean keeps looping
#: (serving/manifest.py's per-model ``decode_layer_unroll`` override)
_DISABLED: dict[tuple, str] = {}

#: reasons already attributed this process (note_degrade logs once per
#: distinct reason; the devtime degrade ledger keeps the counts)
_NOTED: set[str] = set()


def loop_geometry(cfg, fmts: dict) -> tuple:
    """The kernel-geometry key a compile verdict is valid for — the
    probe's argument tuple (ops/pallas/probe.py: ``probe_decode_loop``)
    derived from a config + weight plan.  Everything that changes the
    Mosaic program's residency or structure is in here; ``n_layers`` is
    not (the layer count only changes the grid, never the per-step
    shape)."""
    return (cfg.kv_dtype == "int8", fmts["wq"] == "int8",
            cfg.n_kv_heads, cfg.head_dim, cfg.n_ctx, cfg.sliding_window,
            cfg.n_heads, cfg.ffn_dim)


def disable_decode_loop(reason: str | None, key: tuple = ()) -> None:
    """Pin the per-layer decode path for one kernel geometry (set by the
    engine when the looped kernel fails its startup compile probe on
    TPU); ``None`` re-arms everything (tests)."""
    if reason is None:
        _DISABLED.clear()
    else:
        _DISABLED[key] = reason


def decode_loop_disabled(key: tuple = ()) -> str | None:
    return _DISABLED.get(key)


def note_degrade(program: str, reason: str) -> None:
    """Attribute one degrade decision: a structured log line (once per
    distinct reason per process) + the /debug/compiles degrade ledger
    (obs/devtime.py).  Called at trace/probe time only — never on the
    steady-state dispatch path."""
    from ...obs.devtime import DEVTIME

    DEVTIME.record_degrade(program, reason)
    if reason not in _NOTED:
        _NOTED.add(reason)
        logger.warning("%s degraded: %s", program, reason)


def effective_unroll(cfg) -> int:
    """Clamp ``cfg.decode_layer_unroll`` to a divisor of ``n_layers``:
    ``-1`` (or K ≥ L) fuses all layers into one launch; any other K walks
    down to the nearest divisor so the group scan covers every layer
    exactly once (the flash ``kv_unroll`` clamp idiom).  0 stays 0."""
    K = int(cfg.decode_layer_unroll)
    L = int(cfg.n_layers)
    if K == 0:
        return 0
    if K < -1:
        raise ValueError(
            f"decode_layer_unroll must be >= -1, got {K} "
            "(0 = off, -1 = all layers per launch)")
    if K < 0 or K >= L:
        return L
    while K > 1 and L % K:
        K -= 1
    return K


# ---------------------------------------------------------------------------
# the kernel
# ---------------------------------------------------------------------------

def _loop_kernel(s_ref, h_ref, *rest, cfg, fmts, out_count: int):
    """One grid step = one transformer layer against the residual stream
    held in VMEM scratch.

    ``s_ref``: prefetched scalars ``[pos, layer0]`` — the ring slot of the
    new token and the first layer of this launch's window (index maps
    address layer ``layer0 + program_id``).  ``rest`` is the flat operand
    list built by :func:`decode_loop_step` — per-linear weight planes,
    norms, cache leaves — then the outputs (``h_out`` + new cache leaves)
    and the ``h`` scratch.  All math below is the per-layer path's own
    source (models/llama.py, ops/linear.py, kvquant.py), which is the
    whole bit-exactness argument."""
    from ...models.llama import decode_attention, rms_norm, rope
    from ...ops.linear import linear
    from .kvquant import quantize_kv_xla

    quant = cfg.kv_dtype == "int8"
    refs = list(rest)
    hscr = refs.pop()
    outs = refs[len(refs) - out_count:]
    ins = refs[:len(refs) - out_count]

    it = iter(ins)
    wrefs: dict[str, tuple] = {}
    for name in _LINEARS:
        if fmts[name] == "int8":
            wrefs[name] = (next(it), next(it))
        else:
            wrefs[name] = (next(it),)
    attn_norm = next(it)
    ffn_norm = next(it)
    cache_ins = list(it)
    h_out, *cache_outs = outs

    l = pl.program_id(0)

    @pl.when(l == 0)
    def _seed():
        hscr[...] = h_ref[...]

    h = hscr[...]                                    # (1, D)
    pos = s_ref[0]
    # the reference's ``positions = pos_offset + jnp.arange(S)`` at S=1
    positions = pos + jnp.arange(1, dtype=jnp.int32)

    def lin(x, name):
        r = wrefs[name]
        if fmts[name] == "int8":
            w = {"q": r[0][0], "s": r[1][0]}
        else:
            w = {"w": r[0][0]}
        return linear(x, w)

    hd, n_kv = cfg.head_dim, cfg.n_kv_heads
    hn = rms_norm(h, attn_norm[0], cfg.rms_eps)
    q = lin(hn, "wq").reshape(1, cfg.n_heads, hd)
    k = lin(hn, "wk").reshape(1, n_kv, hd)
    v = lin(hn, "wv").reshape(1, n_kv, hd)
    q = rope(q, positions, cfg)
    k = rope(k, positions, cfg)

    if quant:
        # the XLA quantize formulation, not quantize_kv_pallas: a
        # pallas_call cannot nest inside a kernel.  On the CPU dev-gate
        # the per-layer reference quantizes through the same XLA source,
        # so the gate compares identical math (kvquant.py docstring).
        kq, ks = quantize_kv_xla(k.transpose(1, 0, 2))   # (n_kv, 1, hd)
        vq, vs = quantize_kv_xla(v.transpose(1, 0, 2))
        kq_in, vq_in, ks_in, vs_in = cache_ins
        ck = jax.lax.dynamic_update_slice(kq_in[0], kq, (0, pos, 0))
        cv = jax.lax.dynamic_update_slice(vq_in[0], vq, (0, pos, 0))
        cks = jax.lax.dynamic_update_slice(ks_in[0], ks, (0, pos))
        cvs = jax.lax.dynamic_update_slice(vs_in[0], vs, (0, pos))
        for ref, val in zip(cache_outs, (ck, cv, cks, cvs)):
            ref[...] = val[None]
    else:
        k_in, v_in = cache_ins
        kh = k.astype(k_in.dtype).transpose(1, 0, 2)     # (n_kv, 1, hd)
        vh = v.astype(v_in.dtype).transpose(1, 0, 2)
        ck = jax.lax.dynamic_update_slice(k_in[0], kh, (0, pos, 0))
        cv = jax.lax.dynamic_update_slice(v_in[0], vh, (0, pos, 0))
        cache_outs[0][...] = ck[None]
        cache_outs[1][...] = cv[None]
        cks = cvs = None

    # the per-layer path's own read of the live part of the ring, on this
    # layer's ring as a stack of one
    ring = {"k_q": ck, "v_q": cv, "k_s": cks, "v_s": cvs} if quant \
        else {"k": ck, "v": cv}
    ctx = decode_attention(q, {n: a[None] for n, a in ring.items()},
                           0, pos, pos, cfg, h.dtype)
    h = h + lin(ctx, "wo")

    hn = rms_norm(h, ffn_norm[0], cfg.rms_eps)
    gated = jax.nn.silu(lin(hn, "w_gate").astype(jnp.float32)).astype(h.dtype)
    h = h + lin(gated * lin(hn, "w_up"), "w_down")
    hscr[...] = h

    @pl.when(l == pl.num_programs(0) - 1)
    def _finish():
        h_out[...] = h


def _layer_spec(shape: tuple) -> pl.BlockSpec:
    """Per-layer block of a layer-major stacked array: block (1, *rest)
    addressed at layer ``layer0 + l`` (``s_ref[1]`` is the prefetched
    window start)."""
    rest = shape[1:]
    zeros = (0,) * len(rest)
    return pl.BlockSpec(
        (1, *rest), lambda l, s, _z=zeros: (s[1] + l, *_z))


def _whole_spec(shape: tuple) -> pl.BlockSpec:
    """A block covering the whole (small) array, same for every grid step
    — the residual stream in/out."""
    zeros = (0,) * len(shape)
    return pl.BlockSpec(shape, lambda l, s, _z=zeros: _z)


def decode_loop_step(layers: dict, cache: dict, h: jax.Array, pos,
                     layer0, cfg, fmts: dict, unroll: int,
                     interpret: bool = False):
    """Run layers [layer0, layer0 + unroll) of a single-token decode step
    as ONE ``pallas_call`` (grid = the K layers; the residual stream rides
    VMEM scratch between them).

    ``layers``: the stacked param tree (models/params.py); ``cache``: the
    full stacked KV ring pytree — its leaves are input/output-aliased, so
    layers outside this launch's window keep their bytes and the K
    launched layers are updated in place.  ``fmts``: the
    :func:`~models.params.decode_loop_plan` tags.  Returns ``(h, cache)``
    with the same pytree structure the per-layer path carries.
    """
    quant = cfg.kv_dtype == "int8"
    cache_keys = ("k_q", "v_q", "k_s", "v_s") if quant else ("k", "v")

    operands: list = [h]
    in_specs: list = [_whole_spec(h.shape)]
    for name in _LINEARS:
        w = layers[name]
        if fmts[name] == "int8":
            planes = (w["q"], w["s"])
        else:
            planes = (w["w"],)
        for p in planes:
            operands.append(p)
            in_specs.append(_layer_spec(p.shape))
    for nm in ("attn_norm", "ffn_norm"):
        operands.append(layers[nm])
        in_specs.append(_layer_spec(layers[nm].shape))
    alias_base = len(operands) + 1      # +1: the scalar-prefetch operand
    for key in cache_keys:
        operands.append(cache[key])
        in_specs.append(_layer_spec(cache[key].shape))

    out_specs = [_whole_spec(h.shape)]
    out_shape = [jax.ShapeDtypeStruct(h.shape, h.dtype)]
    aliases = {}
    for i, key in enumerate(cache_keys):
        leaf = cache[key]
        out_specs.append(_layer_spec(leaf.shape))
        out_shape.append(jax.ShapeDtypeStruct(leaf.shape, leaf.dtype))
        aliases[alias_base + i] = 1 + i

    kernel = functools.partial(
        _loop_kernel, cfg=cfg, fmts=fmts, out_count=1 + len(cache_keys))
    scalars = jnp.stack([jnp.asarray(pos, jnp.int32).reshape(()),
                         jnp.asarray(layer0, jnp.int32).reshape(())])
    res = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(unroll,),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM(tuple(h.shape), h.dtype)],
        ),
        out_shape=out_shape,
        input_output_aliases=aliases,
        interpret=interpret,
    )(scalars, *operands)
    h_new = res[0]
    new_cache = dict(zip(cache_keys, res[1:]))
    return h_new, new_cache


def forward_layers_looped(layers: dict, cfg, h: jax.Array, pos_offset,
                          cache: dict, unroll: int, fmts: dict):
    """The layer stack of one decode step as O(L / unroll) launches: a
    ``lax.scan`` over layer groups, each group one
    :func:`decode_loop_step` launch.  ``unroll`` divides ``n_layers``
    and ``fmts`` is the validated weight plan — both come from the
    caller's :func:`models.llama._loop_unroll` eligibility pass (clamp +
    plan walk happen once per trace, there).  With ``unroll ==
    n_layers`` the scan disappears and the whole step is ONE launch."""
    from . import use_interpret

    interpret = use_interpret()
    n_groups = cfg.n_layers // unroll
    if n_groups == 1:
        return decode_loop_step(layers, cache, h, pos_offset,
                                jnp.int32(0), cfg, fmts, unroll,
                                interpret=interpret)

    def body(carry, g):
        hh, cc = carry
        hh, cc = decode_loop_step(layers, cc, hh, pos_offset, g * unroll,
                                  cfg, fmts, unroll, interpret=interpret)
        return (hh, cc), None

    (h, cache), _ = jax.lax.scan(
        body, (h, cache), jnp.arange(n_groups, dtype=jnp.int32))
    return h, cache


# devtime inventory (lfkt-lint PERF001): the looped decode kernel
# (decode_loop_step's pallas_call) is a TRACE-INNER dispatch site — it
# compiles as part of the decode-chunk entry programs that select it
# (obs/devtime.py; /debug/compiles shows it under kind="inner", and the
# "decode_loop" degrade-ledger entries carry the reason whenever an armed
# pod serves per-layer instead)
register_program("decode_loop_step", site="ops.pallas.decode_loop")
