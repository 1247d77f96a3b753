"""Device-side K-quant dequantization (Pallas).

The reference dequantizes lazily inside llama.cpp's CUDA kernels (reference
docker/Dockerfile.base:30-32).  Here dequantization happens once at load
(weights-resident design, SURVEY.md §7 stage 7): the host uploads the *raw
quantized bytes* and the TPU expands them, so for an 8B Q4_K_M model the
host→device transfer is ~4.9 GB instead of the 16-32 GB a host-side
dequant would ship.

Split of labor per format:

- the *bandwidth-heavy* part of every block (the packed 4/5-bit nibbles,
  ≥72% of the bytes) is unpacked on device by a Pallas kernel;
- the *tiny* per-block headers (f16 super-scales, 6-bit sub-scales — ≤11%
  of the bytes) are pre-folded on the host with numpy into effective
  per-sub-block f32 scale/min vectors, which keeps the kernels free of
  f16 bit-twiddling and awkward 12-byte layouts.

Bit layouts follow ``gguf/quants.py`` (the numpy oracle these kernels are
tested bit-exact against).  Packed bytes are shipped as int8 (bit-identical
to uint8; int8 is the dtype Mosaic tiles natively) and unpacked with
``(q >> k) & mask`` arithmetic, which is sign-safe.

All kernels view data as (rows, 128) tiles — 128 is the TPU lane width.
Row counts that don't divide the tile height are handled by running the
numpy reference on the short tail and concatenating.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ...gguf.constants import GGML_BLOCK_SIZES, GGMLType, QK_K
from ...obs.devtime import register_program
from ...gguf.quants import dequantize as np_dequantize, unpack_scale_min_k4

# rows per grid step (row = one 128-lane vector of packed bytes)
_TILE = 256


def _interpret(override: bool | None) -> bool:
    if override is not None:
        return override
    from . import use_interpret

    return use_interpret()


def _f16_f32(b: np.ndarray) -> np.ndarray:
    return np.ascontiguousarray(b).view(np.float16).astype(np.float32).reshape(-1)


def _split_tail(nb: int) -> tuple[int, int]:
    """(kernel rows, tail rows) with kernel rows a multiple of _TILE."""
    main = (nb // _TILE) * _TILE
    return main, nb - main


def _expand(s: jax.Array, repeats: int, width: int = 128) -> jax.Array:
    """(T, n) → (T, n*repeats) blockwise ([s0×r, s1×r, …]) via a select
    chain — broadcast/select only, so it lowers on any backend."""
    T, n = s.shape
    assert n * repeats == width
    g = jax.lax.broadcasted_iota(jnp.int32, (T, width), 1) // repeats
    out = jnp.broadcast_to(s[:, 0:1], (T, width))
    for j in range(1, n):
        out = jnp.where(g == j, s[:, j:j + 1], out)
    return out


# ---------------------------------------------------------------------------
# Q8_0 — rows of 4 blocks of 32 int8 + f32 scale each
# ---------------------------------------------------------------------------

def _q8_0_kernel(q_ref, s_ref, o_ref):
    o_ref[...] = (
        q_ref[...].astype(jnp.float32) * _expand(s_ref[...], 32)
    ).astype(o_ref.dtype)


def dequant_q8_0_device(buf: np.ndarray, n: int, dtype=jnp.float32,
                        interpret: bool | None = None) -> jax.Array:
    """Flat Q8_0 bytes → (n,) device array."""
    nb = n // 32
    blocks = buf[: nb * 34].reshape(nb, 34)
    d = _f16_f32(blocks[:, :2])                       # (nb,)
    rows = nb // 4
    main, _ = _split_tail(rows)
    parts = []
    if main:
        q = blocks[:main * 4, 2:].view(np.int8).reshape(main, 128)
        out = pl.pallas_call(
            _q8_0_kernel,
            grid=(main // _TILE,),
            in_specs=[
                pl.BlockSpec((_TILE, 128), lambda i: (i, 0)),
                pl.BlockSpec((_TILE, 4), lambda i: (i, 0)),
            ],
            out_specs=pl.BlockSpec((_TILE, 128), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((main, 128), dtype),
            interpret=_interpret(interpret),
        )(jnp.asarray(q), jnp.asarray(d[: main * 4].reshape(main, 4)))
        parts.append(out.reshape(-1))
    n_main = main * 128
    if n - n_main:
        parts.append(jnp.asarray(
            np_dequantize(buf[(main * 4) * 34:], GGMLType.Q8_0, n - n_main),
            dtype,
        ))
    return jnp.concatenate(parts) if len(parts) > 1 else parts[0]


# ---------------------------------------------------------------------------
# Q4_K — 256-elem super-blocks; device unpacks the 128 nibble bytes
# ---------------------------------------------------------------------------

def _q4_k_kernel(qs_ref, slo_ref, shi_ref, mlo_ref, mhi_ref, lo_ref, hi_ref):
    qs = qs_ref[...].astype(jnp.int32)
    lo = (qs & 0x0F).astype(jnp.float32)
    hi = ((qs >> 4) & 0x0F).astype(jnp.float32)
    lo_ref[...] = (lo * _expand(slo_ref[...], 32)
                   - _expand(mlo_ref[...], 32)).astype(lo_ref.dtype)
    hi_ref[...] = (hi * _expand(shi_ref[...], 32)
                   - _expand(mhi_ref[...], 32)).astype(hi_ref.dtype)


def _k4_headers(blocks: np.ndarray):
    """Common Q4_K/Q5_K header folding → eff. scale/min (nb, 8) f32."""
    d = _f16_f32(blocks[:, 0:2])
    dmin = _f16_f32(blocks[:, 2:4])
    sc, mn = unpack_scale_min_k4(blocks[:, 4:16])     # (nb, 8) uint8
    scale = d[:, None] * sc.astype(np.float32)
    minv = dmin[:, None] * mn.astype(np.float32)
    return scale, minv


def _interleave_lo_hi(lo: jax.Array, hi: jax.Array, nb: int) -> jax.Array:
    """lo/hi (nb, 128) — lane g*32+i is sub-block 2g (resp. 2g+1) element i
    → flat element order (sub-block-major)."""
    y = jnp.stack([lo.reshape(nb, 4, 32), hi.reshape(nb, 4, 32)], axis=2)
    # flat in TWO steps: over the one reshape (nb, 4, 2, 32) -> (nb * 256,)
    # the TPU's compiler takes 102 s at 49152 blocks (an 8192 x 1536 tensor)
    # where it takes 0.15 s at 65536 or 147456; over these two, 0.6 s at
    # each (PERF.md section 6, PR 58: 100 s of every uncached start)
    return y.reshape(nb, QK_K).reshape(nb * QK_K)


_K4_SPECS = dict(
    in_specs=[
        pl.BlockSpec((_TILE, 128), lambda i: (i, 0)),
        pl.BlockSpec((_TILE, 4), lambda i: (i, 0)),
        pl.BlockSpec((_TILE, 4), lambda i: (i, 0)),
        pl.BlockSpec((_TILE, 4), lambda i: (i, 0)),
        pl.BlockSpec((_TILE, 4), lambda i: (i, 0)),
    ],
    out_specs=(
        pl.BlockSpec((_TILE, 128), lambda i: (i, 0)),
        pl.BlockSpec((_TILE, 128), lambda i: (i, 0)),
    ),
)


def dequant_q4_k_device(buf: np.ndarray, n: int, dtype=jnp.float32,
                        interpret: bool | None = None) -> jax.Array:
    nb = n // QK_K
    bs = GGML_BLOCK_SIZES[GGMLType.Q4_K][1]           # 144
    blocks = buf[: nb * bs].reshape(nb, bs)
    main, tail = _split_tail(nb)
    parts = []
    if main:
        scale, minv = _k4_headers(blocks[:main])
        qs = blocks[:main, 16:].view(np.int8)         # (main, 128)
        lo, hi = pl.pallas_call(
            _q4_k_kernel,
            grid=(main // _TILE,),
            out_shape=(jax.ShapeDtypeStruct((main, 128), dtype),
                       jax.ShapeDtypeStruct((main, 128), dtype)),
            interpret=_interpret(interpret),
            **_K4_SPECS,
        )(
            jnp.asarray(qs),
            jnp.asarray(scale[:, 0::2]), jnp.asarray(scale[:, 1::2]),
            jnp.asarray(minv[:, 0::2]), jnp.asarray(minv[:, 1::2]),
        )
        parts.append(_interleave_lo_hi(lo, hi, main))
    if tail:
        parts.append(jnp.asarray(
            np_dequantize(blocks[main:].reshape(-1), GGMLType.Q4_K, tail * QK_K),
            dtype,
        ))
    return jnp.concatenate(parts) if len(parts) > 1 else parts[0]


# ---------------------------------------------------------------------------
# Q5_K — Q4_K + one high bit per element from the 32-byte qh array
# ---------------------------------------------------------------------------

def _q5_k_kernel(qs_ref, qh_ref, slo_ref, shi_ref, mlo_ref, mhi_ref,
                 lo_ref, hi_ref):
    qs = qs_ref[...].astype(jnp.int32)
    lo = qs & 0x0F
    hi = (qs >> 4) & 0x0F
    T = qs.shape[0]
    # qh byte for lane g*32+i is qh[i]; tile the 32 bytes across the 4 groups
    qh = qh_ref[...].astype(jnp.int32)                # (T, 32)
    qh4 = jnp.concatenate([qh, qh, qh, qh], axis=1)   # (T, 128)
    # sub-block index: lo lanes → 2g, hi lanes → 2g+1 where g = lane // 32
    g2 = 2 * (jax.lax.broadcasted_iota(jnp.int32, (T, 128), 1) // 32)
    hb_lo = (qh4 >> g2) & 1
    hb_hi = (qh4 >> (g2 + 1)) & 1
    lo_ref[...] = ((lo + 16 * hb_lo).astype(jnp.float32)
                   * _expand(slo_ref[...], 32)
                   - _expand(mlo_ref[...], 32)).astype(lo_ref.dtype)
    hi_ref[...] = ((hi + 16 * hb_hi).astype(jnp.float32)
                   * _expand(shi_ref[...], 32)
                   - _expand(mhi_ref[...], 32)).astype(hi_ref.dtype)


def dequant_q5_k_device(buf: np.ndarray, n: int, dtype=jnp.float32,
                        interpret: bool | None = None) -> jax.Array:
    nb = n // QK_K
    bs = GGML_BLOCK_SIZES[GGMLType.Q5_K][1]           # 176
    blocks = buf[: nb * bs].reshape(nb, bs)
    main, tail = _split_tail(nb)
    parts = []
    if main:
        scale, minv = _k4_headers(blocks[:main])
        qh = blocks[:main, 16:48].view(np.int8)       # (main, 32)
        qs = blocks[:main, 48:].view(np.int8)         # (main, 128)
        specs = dict(_K4_SPECS)
        specs["in_specs"] = (
            [_K4_SPECS["in_specs"][0],
             pl.BlockSpec((_TILE, 32), lambda i: (i, 0))]
            + _K4_SPECS["in_specs"][1:]
        )
        lo, hi = pl.pallas_call(
            _q5_k_kernel,
            grid=(main // _TILE,),
            out_shape=(jax.ShapeDtypeStruct((main, 128), dtype),
                       jax.ShapeDtypeStruct((main, 128), dtype)),
            interpret=_interpret(interpret),
            **specs,
        )(
            jnp.asarray(qs), jnp.asarray(qh),
            jnp.asarray(scale[:, 0::2]), jnp.asarray(scale[:, 1::2]),
            jnp.asarray(minv[:, 0::2]), jnp.asarray(minv[:, 1::2]),
        )
        parts.append(_interleave_lo_hi(lo, hi, main))
    if tail:
        parts.append(jnp.asarray(
            np_dequantize(blocks[main:].reshape(-1), GGMLType.Q5_K, tail * QK_K),
            dtype,
        ))
    return jnp.concatenate(parts) if len(parts) > 1 else parts[0]


# ---------------------------------------------------------------------------
# Q6_K — host unpacks the 6-bit values to int8 (minority format: only the
# output head / a few tensors in Q4_K_M files), device applies scales.
# ---------------------------------------------------------------------------

def _q6_k_kernel(q_ref, s_ref, o_ref):
    o_ref[...] = (
        q_ref[...].astype(jnp.float32) * _expand(s_ref[...], 16)
    ).astype(o_ref.dtype)


def dequant_q6_k_device(buf: np.ndarray, n: int, dtype=jnp.float32,
                        interpret: bool | None = None) -> jax.Array:
    nb = n // QK_K
    bs = GGML_BLOCK_SIZES[GGMLType.Q6_K][1]           # 210
    blocks = buf[: nb * bs].reshape(nb, bs)
    ql = blocks[:, 0:128].reshape(nb, 2, 64)
    qh = blocks[:, 128:192].reshape(nb, 2, 32)
    sc = np.ascontiguousarray(blocks[:, 192:208]).view(np.int8).astype(np.float32)
    d = _f16_f32(blocks[:, 208:210])
    low = np.empty((nb, 2, 128), dtype=np.uint8)
    low[:, :, 0:64] = ql & 0x0F
    low[:, :, 64:128] = ql >> 4
    hi = np.empty((nb, 2, 128), dtype=np.uint8)
    hi[:, :, 0:32] = qh & 3
    hi[:, :, 32:64] = (qh >> 2) & 3
    hi[:, :, 64:96] = (qh >> 4) & 3
    hi[:, :, 96:128] = qh >> 6
    q8 = ((low | (hi << 4)).astype(np.int16) - 32).astype(np.int8)
    q8 = q8.reshape(nb * 2, 128)                               # element order
    eff = (d[:, None] * sc).astype(np.float32).reshape(nb * 2, 8)
    rows = nb * 2
    main, tail = _split_tail(rows)
    parts = []
    if main:
        out = pl.pallas_call(
            _q6_k_kernel,
            grid=(main // _TILE,),
            in_specs=[
                pl.BlockSpec((_TILE, 128), lambda i: (i, 0)),
                pl.BlockSpec((_TILE, 8), lambda i: (i, 0)),
            ],
            out_specs=pl.BlockSpec((_TILE, 128), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((main, 128), dtype),
            interpret=_interpret(interpret),
        )(jnp.asarray(q8[:main]), jnp.asarray(eff[:main]))
        parts.append(out.reshape(-1))
    if tail:
        y = q8[main:].astype(np.float32) * np.repeat(eff[main:], 16, axis=1)
        parts.append(jnp.asarray(y.reshape(-1), dtype))
    return jnp.concatenate(parts) if len(parts) > 1 else parts[0]


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------

_DEVICE_DEQUANT = {
    GGMLType.Q8_0: dequant_q8_0_device,
    GGMLType.Q4_K: dequant_q4_k_device,
    GGMLType.Q5_K: dequant_q5_k_device,
    GGMLType.Q6_K: dequant_q6_k_device,
}

#: latched True after the first Mosaic failure so a broken lowering pays
#: ONE failed compile, not one per tensor.  Same probe-and-degrade
#: contract as ops/pallas/probe.py (lfkt-lint KER002), applied lazily
#: because these kernels only ever run during the weight load — a startup
#: probe would just duplicate the first tensor's compile.
_FORCE_HOST = False


def _host_fallback(buf: np.ndarray, ggml_type: GGMLType, n: int,
                   dtype) -> jax.Array:
    """Numpy codec + plain upload: the degrade path when a device kernel
    is unavailable (format without a kernel) or failed to lower."""
    return jnp.asarray(np_dequantize(buf, ggml_type, n), dtype)


def device_dequant(buf: np.ndarray, ggml_type: GGMLType, n: int,
                   dtype=jnp.float32,
                   interpret: bool | None = None) -> jax.Array:  # lfkt: degrades[_FORCE_HOST]
    """Flat raw bytes → (n,) device array; falls back to the numpy codec
    (+ upload) for formats without a device kernel (F16/F32/BF16/Q4_0) and
    for ALL tensors once a device kernel fails to lower (new libtpu /
    unexpected geometry): the load completes slower instead of crash-
    looping the pod."""
    global _FORCE_HOST
    fn = _DEVICE_DEQUANT.get(GGMLType(ggml_type))
    if fn is None or _FORCE_HOST:
        return _host_fallback(buf, ggml_type, n, dtype)
    try:
        return fn(np.asarray(buf, dtype=np.uint8).reshape(-1), n, dtype,
                  interpret)
    except Exception as e:  # noqa: BLE001 — any failure means "degrade"
        _FORCE_HOST = True
        import logging

        logging.getLogger(__name__).error(
            "device dequant kernel failed for %s; loading via the numpy "
            "codec from here on: %s", GGMLType(ggml_type).name, e)
        return _host_fallback(buf, ggml_type, n, dtype)


# devtime inventory (lfkt-lint PERF001): the weight-load dequant kernels
# are host-called once per layer during load; their walls ride the load
# phases already reported by coldstart artifacts, so they are registered
# as inventory rather than wrapped (obs/devtime.py)
register_program("dequant_q8_0_device", site="ops.pallas.dequant")
register_program("dequant_q4_k_device", site="ops.pallas.dequant")
register_program("dequant_q5_k_device", site="ops.pallas.dequant")
register_program("dequant_q6_k_device", site="ops.pallas.dequant")
