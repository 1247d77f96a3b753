"""Startup compile probes for the Pallas kernels.

A Mosaic lowering failure (new libtpu, unexpected geometry) must degrade a
pod to a slower path — not crash-loop it behind a misleading traceback.
These probes compile each risky kernel once on a tiny shape at engine
construction time, so the *caller* can pick the fallback (int8 weights /
XLA attention) with correct attribution, for both engines (serial and
continuous — they construct through ``Engine.__init__``) and for the
benches.

Each probe returns ``None`` on success or a short error string; results are
cached per process (the real warmup then reuses the compiled programs'
cache lineage at different shapes, so the probe cost is one small Mosaic
compile each, TPU only — interpret mode always passes cheaply)."""

from __future__ import annotations

import functools

import numpy as np

__all__ = ["probe_fused_q4k", "probe_fused_q5k", "probe_fused_q6k",
           "probe_fused_q8", "probe_fused_experts", "probe_flash_attention",
           "probe_kv_quant"]


#: every verdict this process has reached: {"probe_x" or "probe_x(args)":
#: None or the error}.  The executable store's key holds them
#: (utils/execstore.py): a verdict decides which kernels a trace may use
_VERDICTS: dict[str, str | None] = {}


def verdicts() -> dict[str, str | None]:
    """The verdicts so far (a copy); a probe that has not run is absent."""
    return dict(_VERDICTS)


def _once(fn):
    """Run a probe once per process and argument tuple, and write its
    verdict down for :func:`verdicts`."""
    cached = functools.lru_cache(maxsize=None)(fn)

    @functools.wraps(fn)
    def probe(*args, **kwargs):
        verdict = cached(*args, **kwargs)
        asked = ",".join([*map(repr, args),
                          *(f"{k}={v!r}" for k, v in sorted(kwargs.items()))])
        _VERDICTS[fn.__name__ + (f"({asked})" if asked else "")] = verdict
        return verdict

    return probe


def _err(e: BaseException) -> str:
    return f"{type(e).__name__}: {e}"[:400]


def _probe_n() -> int:
    """N for the matmul probes: 512 on TPU so the kernel compiles with the
    TN=512 tile every 8B serving shape uses (qmatmul._pick_tn); 8 in
    interpret mode to keep CPU tests fast.  A probe at a toy tile size
    would miss tile-dependent Mosaic regressions."""
    from . import use_interpret

    return 8 if use_interpret() else 512


@_once
def probe_fused_q4k() -> str | None:
    """Compile + run the fused Q4_K matmul at the serving tile geometry."""
    try:
        import jax.numpy as jnp

        from .qmatmul import prep_q4k, q4k_matmul, q4k_matmul_stacked

        rng = np.random.default_rng(0)
        from ...gguf.quants import quant_q4_k

        n = _probe_n()
        w = prep_q4k(quant_q4_k(
            rng.standard_normal(n * 2048).astype(np.float32) * 0.02),
            n, 2048)
        x = jnp.ones((1, 2048), jnp.bfloat16)
        y = q4k_matmul(x, w)          # unstacked: the output head's path
        y.block_until_ready()
        # stacked scalar-prefetch variant: the per-layer serving path
        ws = {k: jnp.stack([v, v]) for k, v in w.items()}
        float(q4k_matmul_stacked(x, ws, 1).sum())
        return None
    except Exception as e:  # noqa: BLE001 — any failure means "don't use it"
        return _err(e)


@_once
def probe_fused_q5k() -> str | None:
    """Compile + run the fused Q5_K matmul at the serving tile geometry."""
    try:
        import jax.numpy as jnp

        from ...gguf.quants import quant_q5_k
        from .q5matmul import prep_q5k, q5k_matmul, q5k_matmul_stacked

        rng = np.random.default_rng(0)
        n = _probe_n()
        w = prep_q5k(quant_q5_k(
            rng.standard_normal(n * 2048).astype(np.float32) * 0.02),
            n, 2048)
        x = jnp.ones((1, 2048), jnp.bfloat16)
        float(q5k_matmul(x, w).sum())
        ws = {k: jnp.stack([v, v]) for k, v in w.items()}
        float(q5k_matmul_stacked(x, ws, 1).sum())
        return None
    except Exception as e:  # noqa: BLE001
        return _err(e)


@_once
def probe_fused_q6k() -> str | None:
    """Compile + run the fused Q6_K matmul at the serving tile geometry."""
    try:
        import jax.numpy as jnp

        from ...gguf.quants import quant_q6_k
        from .q6matmul import prep_q6k, q6k_matmul, q6k_matmul_stacked

        rng = np.random.default_rng(0)
        n = _probe_n()
        w = prep_q6k(quant_q6_k(
            rng.standard_normal(n * 2048).astype(np.float32) * 0.02),
            n, 2048)
        x = jnp.ones((1, 2048), jnp.bfloat16)
        float(q6k_matmul(x, w).sum())
        ws = {k: jnp.stack([v, v]) for k, v in w.items()}
        float(q6k_matmul_stacked(x, ws, 1).sum())
        return None
    except Exception as e:  # noqa: BLE001
        return _err(e)


@_once
def probe_fused_q8() -> str | None:
    """Compile + run the fused Q8_0 matmul at the serving tile geometry."""
    try:
        import jax.numpy as jnp

        from ...gguf.quants import quant_q8_0
        from .q8matmul import prep_q8_0, q8_matmul, q8_matmul_stacked

        rng = np.random.default_rng(0)
        n = _probe_n()
        w = prep_q8_0(quant_q8_0(
            rng.standard_normal(n * 2048).astype(np.float32) * 0.02),
            n, 2048)
        x = jnp.ones((1, 2048), jnp.bfloat16)
        float(q8_matmul(x, w).sum())
        ws = {k: jnp.stack([v, v]) for k, v in w.items()}
        float(q8_matmul_stacked(x, ws, 1).sum())
        return None
    except Exception as e:  # noqa: BLE001
        return _err(e)


@_once
def probe_fused_experts() -> str | None:
    """Compile + run the grouped expert matmuls (ops/pallas/experts.py) at
    the serving tile geometry, in both row regimes: Q4_K gate/up (1024,
    2048), Q6_K down (2048, 1024: folded), two experts.  Called only for a
    file that has experts, so a dense pod's start pays nothing for it."""
    try:
        import jax.numpy as jnp

        from ...gguf.constants import GGMLType
        from ...gguf.quants import quant_q4_k, quant_q6_k
        from . import use_interpret
        from .experts import prep_experts, routed_experts

        rng = np.random.default_rng(0)
        e = 2
        d, f = (256, 256) if use_interpret() else (2048, 1024)

        def planes(quant, gtype, n_out, k_in):
            raw = quant(rng.standard_normal(e * n_out * k_in).astype(
                np.float32) * 0.02)
            w = prep_experts(np.asarray(raw), e, n_out, k_in, gtype)
            return {k: v[None] for k, v in w.items()}      # one layer

        gate = planes(quant_q4_k, GGMLType.Q4_K, f, d)
        down = planes(quant_q6_k, GGMLType.Q6_K, d, f)
        for rows in (1, 80):        # few-row and many-row tiles
            x = jnp.ones((rows, d), jnp.bfloat16)
            picks = jnp.tile(jnp.arange(e, dtype=jnp.int32), (rows, 1))
            wts = jnp.full((rows, e), 0.5, jnp.float32)
            y, _ = routed_experts(x, picks, wts, gate, gate, down, 0)
            float(y.astype(jnp.float32).sum())
        return None
    except Exception as e:  # noqa: BLE001
        return _err(e)


@_once
def probe_flash_attention(quantized: bool = False) -> str | None:
    """Compile + run the flash prefill kernel at the Llama-3-8B head
    layout (32 q heads / 8 kv heads / head_dim 128) on a short sequence,
    and with it the decode step's kernel (bf16 rings: ``attn_impl=pallas``
    serves both, so both degrade together).
    ``quantized=True`` probes the int8-cache fused-dequant variant
    (kv_dtype=int8 engines call both: the two lower to different Mosaic
    programs and must degrade independently)."""
    try:
        import jax.numpy as jnp

        from . import use_interpret
        from .attention import _env_kv_unroll, flash_attention

        itp = use_interpret()
        S, H, KV, HD, CTX = (8, 2, 2, 128, 32) if itp else (128, 32, 8, 128, 256)
        q = jnp.ones((S, H, HD), jnp.bfloat16)
        if quantized:
            k = jnp.ones((KV, CTX, HD), jnp.int8)
            v = jnp.ones((KV, CTX, HD), jnp.int8)
            ks = jnp.full((KV, CTX), 1 / 127.0, jnp.float32)
            y = flash_attention(q, k, v, jnp.int32(0), sm_scale=HD ** -0.5,
                                k_scale=ks, v_scale=ks, interpret=itp)
        else:
            k = jnp.ones((KV, CTX, HD), jnp.bfloat16)  # head-major ring layout
            v = jnp.ones((KV, CTX, HD), jnp.bfloat16)
            y = flash_attention(q, k, v, jnp.int32(0), sm_scale=HD ** -0.5,
                                interpret=itp)
        float(y.astype(jnp.float32).sum())
        if not quantized:
            # the decode step's kernel over a bf16 ring (manual copies, a
            # dynamic trip count: another Mosaic program), two blocks deep;
            # ONE program, so a start pays one compile (or a cache hit) and
            # not a dispatch per pad, slice and sum around the kernel
            import jax

            from .attention import flash_attention_decode

            def decode(q):
                ring = jnp.ones((2, KV, CTX, HD), jnp.bfloat16)
                kw = dict(sm_scale=HD ** -0.5, block_k=CTX // 2,
                          interpret=itp)
                at = (jnp.int32(1), jnp.int32(CTX - 1), True)
                # both forms: the one that stores the step's row (a dense
                # stack's decode step) and the read-only one (a ring that
                # was written before the call: models/sala.py)
                row = jnp.ones((KV, HD), jnp.bfloat16)
                ctx, k, v = flash_attention_decode(
                    q[0], ring, ring, *at, k_new=row, v_new=row, **kw)
                return (ctx + flash_attention_decode(q[0], k, v, *at, **kw)
                        ).astype(jnp.float32).sum()

            float(jax.jit(decode)(q))
        if _env_kv_unroll() > 1:
            # the multi-KV-block inner loop (LFKT_FLASH_KV_UNROLL > 1) is a
            # structurally different Mosaic program (fused K/V fetch +
            # in-kernel sub-block loop); probe it at small explicit blocks
            # so a lowering failure degrades attn_impl instead of crashing
            # the first long-context prefill.  The probe shapes above clamp
            # the unroll to 1 (ring == one block), so they cannot cover it.
            ctx2 = 4 * 128
            if quantized:
                k2 = jnp.ones((KV, ctx2, HD), jnp.int8)
                ks2 = jnp.full((KV, ctx2), 1 / 127.0, jnp.float32)
                y = flash_attention(q, k2, k2, jnp.int32(0),
                                    sm_scale=HD ** -0.5, block_q=128,
                                    block_k=128, k_scale=ks2, v_scale=ks2,
                                    interpret=itp)
            else:
                k2 = jnp.ones((KV, ctx2, HD), jnp.bfloat16)
                y = flash_attention(q, k2, k2, jnp.int32(0),
                                    sm_scale=HD ** -0.5, block_q=128,
                                    block_k=128, interpret=itp)
            float(y.astype(jnp.float32).sum())
        return None
    except Exception as e:  # noqa: BLE001
        return _err(e)


def _latent_decode(heads: int, select: bool) -> str | None:
    try:
        import jax
        import jax.numpy as jnp

        from . import use_interpret
        from .attention import latent_attention_decode

        from ...models.mla import LATENT_KERNEL_BLOCK

        itp = use_interpret()
        H, W, R, CTX = (4, 256, 128, 32) if itp \
            else (heads, 640, 512, 2 * LATENT_KERNEL_BLOCK)
        sel = {"sel": jnp.arange(CTX) % 2 == 1} if select else {}

        def decode(q, live):
            lat = jnp.ones((2, 1, CTX, W), jnp.bfloat16)
            ctx, lat = latent_attention_decode(
                q, lat, jnp.int32(1), jnp.int32(CTX - 1), live, q[0],
                sm_scale=W ** -0.5, block_k=CTX // 2, v_width=R,
                interpret=itp, **sel)
            return ctx.astype(jnp.float32).sum() + lat[1, 0, CTX - 1, 0]

        out = jax.jit(jax.vmap(decode))(jnp.ones((2, H, W), jnp.bfloat16),
                                        jnp.asarray([True, False]))
        float(out.sum())
        return None
    except Exception as e:  # noqa: BLE001
        return _err(e)


@_once
def probe_latent_decode() -> str | None:
    """Compile + run the decode kernel on a latent leaf (one ring of rows
    for all heads, the values a row's first columns: ops/pallas/
    attention.py ``latent_attention_decode``) over two lanes, one of them
    dead, two blocks deep, at the published widths and the block that
    serves (64 heads, rows of 512 + 64 laid out as 640, blocks of
    ``mla.LATENT_KERNEL_BLOCK``; 4 heads, 128 + 128 and 16 in interpret
    mode).  A
    failure leaves a ``deepseek2`` file's decode steps on the XLA loop of
    ``models/mla.py latent_attention`` (``cfg.latent_kernel`` stays
    False)."""
    return _latent_decode(64, False)


@_once
def probe_latent_decode_select() -> str | None:
    """:func:`probe_latent_decode` for a ``deepseek32`` file: 128 heads, and
    the kernel WITH a selection's bias operand (another Mosaic program:
    ``flash_attention_decode_latent_select``)."""
    return _latent_decode(128, True)


def _latent_prefill(heads: int, select: bool) -> str | None:
    try:
        import jax
        import jax.numpy as jnp

        from . import use_interpret
        from .attention import latent_attention_prefill

        from ...models.mla import LATENT_SLICE_BLOCK

        itp = use_interpret()
        H, S, W, R, T = (4, 16, 256, 128, 16) if itp \
            else (heads, 256, 640, 512, LATENT_SLICE_BLOCK)
        sel = {"sel": (jnp.arange(2 * T) % 2 == 1)[None, :].repeat(S, 0)} \
            if select else {}

        def slice_(q):
            lat = jnp.ones((2, 1, 2 * T, W), jnp.bfloat16)
            return latent_attention_prefill(
                q, lat, jnp.int32(1), jnp.int32(2 * T - S),
                sm_scale=W ** -0.5, v_width=R, block_k=T, interpret=itp,
                **sel).astype(jnp.float32).sum()

        float(jax.jit(slice_)(jnp.ones((H, S, W), jnp.bfloat16)))
        return None
    except Exception as e:  # noqa: BLE001
        return _err(e)


@_once
def probe_latent_prefill() -> str | None:
    """Compile + run the prefill slices' kernel on a latent leaf
    (ops/pallas/attention.py ``latent_attention_prefill``: the scores of a
    slice in VMEM, the leaf read in place) on one narrow slice two blocks
    deep, at the published widths and the tile and block that serve (64
    heads x 256 tokens in tiles of 1024 rows, rows of 512 + 64 laid out as
    640, blocks of ``mla.LATENT_SLICE_BLOCK``; 4 heads x 16 tokens, 128 +
    128 and blocks of 16 in interpret mode).  A failure leaves a
    ``deepseek2`` file's prefill slices on the XLA loop of ``models/mla.py
    latent_attention`` (``cfg.latent_slice_kernel`` stays False)."""
    return _latent_prefill(64, False)


@_once
def probe_latent_prefill_select() -> str | None:
    """:func:`probe_latent_prefill` for a ``deepseek32`` file: 128 heads,
    and the kernel WITH a selection's bias operand
    (``flash_attention_prefill_latent_select``)."""
    return _latent_prefill(128, True)


@_once
def probe_kv_quant() -> str | None:
    """Compile + run the int8 KV-cache write-quantize kernel
    (ops/pallas/kvquant.py) at a decode-like shape.  A failure degrades
    writes to the identical XLA formulation (force_xla_quant) instead of
    crash-looping the pod at its first prefill."""
    try:
        import jax.numpy as jnp

        from . import use_interpret
        from .kvquant import quantize_kv_pallas

        q, s = quantize_kv_pallas(jnp.ones((8, 8, 128), jnp.bfloat16),
                                  interpret=use_interpret())
        float(s.sum()) + float(q.astype(jnp.float32).sum())
        return None
    except Exception as e:  # noqa: BLE001
        return _err(e)


# devtime inventory (lfkt-lint PERF001): the decode kernel's probe is one
# small jit, compiled before any engine program exists
from ...obs.devtime import register_program  # noqa: E402

register_program("probe_flash_attention", site="ops.pallas.probe")
register_program("probe_lin_state", site="ops.pallas.probe")
register_program("probe_ssm_scan", site="ops.pallas.probe")
register_program("probe_ring_wide_group", site="ops.pallas.probe")
register_program("probe_latent_decode", site="ops.pallas.probe")
register_program("probe_latent_prefill", site="ops.pallas.probe")
register_program("_latent_decode", site="ops.pallas.probe")
register_program("_latent_prefill", site="ops.pallas.probe")


@_once
def probe_lin_state() -> str | None:
    """Compile + run the linear-attention layers' state step
    (ops/pallas/linstate.py) over two lanes, one of them dead, at the
    published head layout (32 heads of 128; 4 in interpret mode).  A
    failure degrades a ``minicpm-sala`` file to ``attn_impl=xla``: the
    plain XLA recurrence (``models/sala.py lin_step``), and with it the
    ring's XLA read."""
    try:
        import jax
        import jax.numpy as jnp

        from . import use_interpret
        from .linstate import lin_state_step

        itp = use_interpret()
        H, HD = (4 if itp else 32), 128
        x = jnp.ones((2, H, HD), jnp.bfloat16)
        state = jnp.zeros((2, 2, H, HD, HD), jnp.float32)
        o, new = jax.jit(jax.vmap(lambda q, s, lv: lin_state_step(
            q, q, q, s, jnp.int32(1), lv, jnp.full((H,), 0.5), interpret=itp)
        ))(x, state, jnp.asarray([True, False]))
        float(o.sum()) + float(new.sum())
        return None
    except Exception as e:  # noqa: BLE001
        return _err(e)


@_once
def probe_ssm_scan() -> str | None:
    """Compile + run the selective scan of a prefill slice
    (ops/pallas/ssmscan.py) at the published channel tile (two tiles of
    1024 channels, 16 states; one tile of 128 and 4 states in interpret
    mode) over two time chunks, into the second layer of a leaf of two.  A
    failure leaves a ``phi4flash`` file's slices to the plain ``lax.scan``
    (``models/phi4flash.py selective_scan``); the ring's kernels stay."""
    try:
        import jax
        import jax.numpy as jnp

        from . import use_interpret
        from .ssmscan import TIME_CHUNK, ssm_scan

        itp = use_interpret()
        C, N, S = (128, 4, 16) if itp else (2048, 16, 2 * TIME_CHUNK)
        x = jnp.ones((S, C), jnp.float32)
        state = jnp.zeros((2, N, C // 128, 128), jnp.float32)
        y, new = jax.jit(lambda x, s: ssm_scan(
            x, 0.01 * x, x[:, :N], x[:, :N], -jnp.ones((N, C)), x[0], s,
            jnp.int32(1), jnp.bool_(False), interpret=itp))(x, state)
        float(y.sum()) + float(new.sum())
        return None
    except Exception as e:  # noqa: BLE001
        return _err(e)



@_once
def probe_ring_wide_group(group: int) -> str | None:
    """Compile + run the ring's two kernels at ``group`` query heads on ONE
    KV head of 128 (a ``jamba`` file's attention layers: 20), the forms
    :func:`probe_flash_attention` does not reach: the decode kernel with a
    lane's rows padded past one tile, the flash kernel with one step on its
    head axis and, on a ring of more fused blocks than it walks whole
    (ops/pallas/attention.py ``WALK_WHOLE_STEPS``), a key axis that ends at
    the slice's end.  A failure degrades such a file to ``attn_impl=xla``."""
    try:
        import jax
        import jax.numpy as jnp

        from . import use_interpret
        from .attention import (
            WALK_WHOLE_STEPS, flash_attention, flash_attention_decode)

        itp = use_interpret()
        S, HD, BK = (8, 128, 16) if itp else (128, 128, 128)
        CTX = BK * (WALK_WHOLE_STEPS + 2)

        def both(q):
            ring = jnp.ones((2, 1, CTX, HD), jnp.bfloat16)
            y = flash_attention(q, ring[0], ring[1], jnp.int32(BK),
                                sm_scale=HD ** -0.5, block_k=BK, kv_unroll=1,
                                interpret=itp)
            row = jnp.ones((1, HD), jnp.bfloat16)
            ctx, _, _ = flash_attention_decode(
                q[0], ring, ring, jnp.int32(1), jnp.int32(CTX - 1), True,
                sm_scale=HD ** -0.5, block_k=BK, interpret=itp, k_new=row,
                v_new=row)
            return y.astype(jnp.float32).sum() + ctx.astype(jnp.float32).sum()

        float(jax.jit(both)(jnp.ones((S, group, HD), jnp.bfloat16)))
        return None
    except Exception as e:  # noqa: BLE001
        return _err(e)
