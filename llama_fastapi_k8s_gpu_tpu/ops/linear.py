"""Linear-layer formats and the matmul dispatch.

The reference's weights live inside llama.cpp's ggml tensors and are consumed
by cuBLAS kernels (reference docker/Dockerfile.base:30-32).  Here a linear is
a small pytree whose keys select the compute path — the structure is static
under jit, so dispatch costs nothing:

- ``{"w": bf16 (out, in)}``               — plain MXU matmul.
- ``{"q": int8 (out, in), "s": f32 (out,)}`` — weight-only int8 with dynamic
  per-row activation quantization; both operands int8 so the MXU runs its
  int8 path and HBM traffic per decoded token is halved vs bf16.  This is
  what lets Llama-3-8B (16 GB at bf16) fit a single v5e chip (16 GB HBM).

A Pallas fused dequant-matmul over raw Q4_K blocks (ops/pallas) is the next
step down the memory-footprint ladder.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.devtime import timed_jit


def make_linear_bf16(w: np.ndarray) -> dict:
    """w: (out, in) float."""
    return {"w": jnp.asarray(w, dtype=jnp.bfloat16)}


def make_linear_int8(w: np.ndarray) -> dict:
    """Symmetric per-output-channel int8 quantization of (out, in) weights."""
    w = np.asarray(w, dtype=np.float32)
    amax = np.abs(w).max(axis=1)
    scale = np.where(amax > 0, amax / 127.0, 1.0).astype(np.float32)
    q = np.clip(np.round(w / scale[:, None]), -127, 127).astype(np.int8)
    return {"q": jnp.asarray(q), "s": jnp.asarray(scale)}


@jax.jit
def make_linear_int8_device(w: jax.Array) -> dict:
    """:func:`make_linear_int8` on device — used by the Pallas load path so
    requantization never round-trips through the host."""
    w = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(w), axis=1)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(w / scale[:, None]), -127, 127).astype(jnp.int8)
    return {"q": q, "s": scale}


make_linear_int8_device = timed_jit("load_linear_int8",
                                    make_linear_int8_device,
                                    site="ops.linear")


def make_linear_q4k(w: np.ndarray) -> dict:
    """(out, in) float weights → fused-kernel Q4_K layout (quantize with the
    in-tree codec, then pack for ops/pallas/qmatmul.py).  ~5 bit/weight in
    HBM; the decode-bandwidth format."""
    from ..gguf.quants import quant_q4_k
    from .pallas.qmatmul import prep_q4k

    w = np.ascontiguousarray(w, dtype=np.float32)
    n_out, k_in = w.shape
    return prep_q4k(quant_q4_k(w.reshape(-1)), n_out, k_in)


def make_linear_q8(w: np.ndarray) -> dict:
    """(out, in) float weights → fused-kernel Q8_0 layout (quantize with the
    in-tree codec, then pack for ops/pallas/q8matmul.py).  ~9 bit/weight on
    the file's own per-32-block quantization grid (scales folded to bf16)."""
    from ..gguf.quants import quant_q8_0
    from .pallas.q8matmul import prep_q8_0

    w = np.ascontiguousarray(w, dtype=np.float32)
    n_out, k_in = w.shape
    return prep_q8_0(quant_q8_0(w.reshape(-1)), n_out, k_in)


def make_linear_q6k(w: np.ndarray) -> dict:
    """(out, in) float weights → fused-kernel Q6_K layout (quantize with the
    in-tree codec, then pack for ops/pallas/q6matmul.py).  ~7 bit/weight in
    HBM; the format Q4_K_M files use for ffn_down / attn_v / output."""
    from ..gguf.quants import quant_q6_k
    from .pallas.q6matmul import prep_q6k

    w = np.ascontiguousarray(w, dtype=np.float32)
    n_out, k_in = w.shape
    return prep_q6k(quant_q6_k(w.reshape(-1)), n_out, k_in)


def make_linear_q5k(w: np.ndarray) -> dict:
    """(out, in) float weights → fused-kernel Q5_K layout (quantize with the
    in-tree codec, then pack for ops/pallas/q5matmul.py).  ~6 bit/weight."""
    from ..gguf.quants import quant_q5_k
    from .pallas.q5matmul import prep_q5k

    w = np.ascontiguousarray(w, dtype=np.float32)
    n_out, k_in = w.shape
    return prep_q5k(quant_q5_k(w.reshape(-1)), n_out, k_in)


def filled_k(k_in: int, share: int) -> int:
    """``k_in`` filled up to the next multiple of the kernels' K tile where
    that adds at most ``k_in / share``, else ``k_in`` itself."""
    from .pallas.qmatmul import TK

    k_pad = -(-k_in // TK) * TK
    return k_pad if share * (k_pad - k_in) <= k_in else k_in


def padded_k(k_in: int) -> int:
    """The K a fused layout of a ``k_in``-wide dense matrix is stored at, by
    shape alone.  A multiple of the kernels' K tile: as it is.  A K that
    ends in a TAIL tile (``ops/pallas/qmatmul.py tail_of``: above one tile,
    a multiple of 512, where filling would add a fifth of K or more; 2560,
    3072, 5120): as it is too, its last 512 or 1024 columns a narrow tile of
    the same layout at its own period, nothing filled.  Else the next
    multiple of the tile where that adds at most a quarter (7168 -> 8192,
    11008 -> 12288: the loader fills the last tile up with zero blocks and
    :func:`linear` the activations with zeros), else ``k_in`` itself (2304,
    1536, 512: no fused kernel's shape).  The grouped expert kernels keep a
    rule of their own (ops/pallas/experts.py ``padded_k``: a third, no
    tail)."""
    from .pallas.qmatmul import tail_of

    return k_in if tail_of(k_in) else filled_k(k_in, 4)


def _pad_k(x: jax.Array) -> jax.Array:
    pad = padded_k(x.shape[-1]) - x.shape[-1]
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)]) if pad else x


def _fused_fns(w: dict):
    """(matmul, matmul_stacked) for a fused-layout weight dict, or None.
    The single dispatch point shared by :func:`linear` and
    :func:`linear_at` — one place to extend when a format is added."""
    if "qs" in w:
        from .pallas import qmatmul as m

        return m.q4k_matmul, m.q4k_matmul_stacked
    if "q4" in w or "q6p" in w:   # split or `pre` Q6_K layout
        from .pallas import q6matmul as m

        return m.q6k_matmul, m.q6k_matmul_stacked
    if "q5s" in w or "q5p" in w:  # split or `pre` Q5_K layout
        from .pallas import q5matmul as m

        return m.q5k_matmul, m.q5k_matmul_stacked
    if "q8" in w:
        from .pallas import q8matmul as m

        return m.q8_matmul, m.q8_matmul_stacked
    return None


def linear(x: jax.Array, w: dict) -> jax.Array:
    """x: (..., in) bf16 → (..., out) bf16."""
    fns = _fused_fns(w)
    if fns is not None:
        return fns[0](_pad_k(x), w)
    if "w" in w:
        return jax.lax.dot_general(
            x, w["w"],
            dimension_numbers=(((x.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(x.dtype)
    # int8 weight-only: dynamically quantize activations per row
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    xs = jnp.where(amax > 0, amax / 127.0, 1.0)
    xq = jnp.round(xf / xs).astype(jnp.int8)
    acc = jax.lax.dot_general(
        xq, w["q"],
        dimension_numbers=(((x.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    y = acc.astype(jnp.float32) * xs * w["s"]
    return y.astype(x.dtype)


def linear_at(x: jax.Array, w: dict, idx) -> jax.Array:
    """:func:`linear` against layer ``idx`` of weights stacked as (L, ...)
    arrays — the form the model scans over (models/llama.py).

    Fused Pallas layouts stream their blocks straight from the stacked HBM
    array via scalar prefetch: slicing them per layer (what ``lax.scan``
    over weight xs does) materializes a copy of every layer's quantized
    planes before each pallas_call, measured at +6.3 ms/token for 8B Q4_K
    decode on v5e (tools/decode_breakdown.py).  Non-fused formats slice at
    ``idx`` — XLA fuses that dynamic-slice into the dot_general read, so
    it was never the bottleneck."""
    fns = _fused_fns(w)
    if fns is not None:
        return fns[1](_pad_k(x), w, idx)
    return linear(x, jax.tree_util.tree_map(lambda a: a[idx], w))
