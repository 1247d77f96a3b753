"""Fused dequant-matmul kernel A/B microbench (run ALONE on the chip).

Times every LFKT_Q*_KERNEL variant of the fused kernels on the 8B decode
shapes, against the int8 control and the HBM-bandwidth roofline, so kernel
restructurings can be picked on data (VERDICT r3 #2: raise Q4_K from 57% of
roofline toward the int8 path's 85%).

(``LFKT_Q6K_KERNEL`` names a LAYOUT since PR 64, ``split`` | ``pre``: the
split layout's calls run one body, the integer dequantization the head has
had since PR 57, and the float bodies the knob used to choose among are
gone.  That format's rows go through ``linear_at`` on a stack of one: the
stacked call, as a layer makes it.  ``tools/time_head_call.py`` times the
head's call and the stacked one alone, and sweeps their tiling.)

Method: each (fmt, variant, shape, B) cell times a jitted x -> x-chained
matvec (output reduced back into the input row so nothing hoists), double
warm-up discarded (docs/PERF.md "Measurement hygiene"), then the mean of
``iters`` chained steps.  Variant env knobs are flipped in-process — they
are part of every jit cache key (ops/pallas/qmatmul.py:_env_variant).

Prints one JSON object (diagnostics, not the driver bench contract).

``python tools/kernel_microbench.py rows`` is the ROW sweep of the fused
matmuls a prefill slice runs (:func:`rows_sweep`): us a call and us per 256
rows at 8 / 256 / 512 / 1024 / 2048 rows, as one many-row call and as the
256-row calls a wider operand was cut into before (docs/PERF.md "Rows of a
fused matmul call" holds its table).

``python tools/kernel_microbench.py tail`` times the dense calls of a K that
ends in a TAIL tile (:func:`tail_sweep`: ``phi4flash``'s matrices, K 2560
and 5120, at 16 and 1024 rows) in the tail layout and with the last tile
filled up with zero blocks, as it was stored before PR 63: us a call and the
share of ``stored bytes / 819 GB/s`` (PERF.md section 6, PR 63).
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

HBM_GBPS = 819.0  # v5e HBM bandwidth (spec)

# 8B Llama decode shapes (N, K): qkv-ish square, ffn up/gate, ffn down
SHAPES = [(4096, 4096), (14336, 4096), (4096, 14336)]
BATCHES = (1, 8)
# on-device scan steps per timed window: the window carries a fixed
# dispatch+fetch overhead, so iters must be large enough that
# overhead/iters is small vs the ~12-54 us kernels
ITERS = 1000
# On-chip deviation gate vs the reference variant.  Exact-math restructurings
# sit at bf16-rounding scale (~1e-3 of max |y|); the rejected inexact `vb`
# ablation measured 3.3e-2.  Anything past 5e-3 means a plane was silently
# truncated (e.g. an f32 dot lowered to single-pass bf16) — the row is
# marked dev_fail and the variant must not be selected, whatever its us.
REL_DEV_GATE = 5e-3

from llama_fastapi_k8s_gpu_tpu.ops.pallas.q5matmul import Q5K_VARIANTS
from llama_fastapi_k8s_gpu_tpu.ops.pallas.q6matmul import Q6K_LAYOUTS
from llama_fastapi_k8s_gpu_tpu.ops.pallas.qmatmul import Q4K_VARIANTS

VARIANTS = {
    "q4k": Q4K_VARIANTS,
    "q5k": Q5K_VARIANTS,
    "q6k": Q6K_LAYOUTS,
    "q8": ("cur",),
    "int8": ("cur",),
}
KNOB = {"q4k": "LFKT_Q4K_KERNEL", "q5k": "LFKT_Q5K_KERNEL",
        "q6k": "LFKT_Q6K_KERNEL"}


def weight_bytes(fmt: str, n: int, k: int, variant: str = "") -> int:
    """HBM bytes one matvec must read (weights; activations negligible).
    ``variant`` matters for LAYOUT variants: q6k `pre` stores one combined
    int8 plane (1 B/weight) instead of the 0.75 B/weight split."""
    if fmt == "q4k":                       # qs N*K/2 + sm (K/2048)*N*128*2
        return n * k // 2 + (k // 2048) * n * 128 * 2
    if fmt == "q5k" and variant == "pre":  # combined plane + sm
        return n * k + (k // 2048) * n * 128 * 2
    if fmt == "q5k":                       # q4 plane + hi-bit plane + sm
        return n * k // 2 + n * k // 8 + (k // 2048) * n * 128 * 2
    if fmt == "q6k" and variant == "pre":  # combined plane + bf16 scales/16
        return n * k + (k // 16) * n * 2
    if fmt == "q6k":                       # 6 bit/w planes + bf16 scales/16
        return n * k * 3 // 4 + (k // 16) * n * 2
    if fmt == "q8":                        # int8 + bf16 scale per 32
        return n * k + (k // 32) * n * 2
    if fmt == "int8":                      # int8 + one bf16 scale per row
        return n * k + n * 2
    raise ValueError(fmt)


def make_weight(fmt: str, wf: np.ndarray) -> dict:
    """Build the fused layout for float weights ``wf``.  Called per
    (fmt, variant) cell AFTER the variant env knob is set: `pre`-class
    variants change the PREP layout, so prepping once per shape would
    silently time the split kernel under the pre label.  The float array
    is shared across variants so the numerics cross-check stays valid."""
    import importlib

    # ops/__init__ re-exports the `linear` FUNCTION under the submodule's
    # name, so plain attribute imports resolve to the function
    L = importlib.import_module("llama_fastapi_k8s_gpu_tpu.ops.linear")

    mk = {"q4k": L.make_linear_q4k, "q5k": L.make_linear_q5k,
          "q6k": L.make_linear_q6k, "q8": L.make_linear_q8,
          "int8": L.make_linear_int8}[fmt]
    w = mk(wf)
    if fmt == "q6k":                # the stacked call, as a layer makes it
        w = {k: v[None] for k, v in w.items()}
    return jax.device_put(w)


# the row sweep: (format, N, K) of the widest matmuls of the two dense
# configurations with long-prompt cells (solar: ffn 14336, sala: ffn 16384)
ROW_SHAPES = [("q4k", 14336, 4096), ("q6k", 4096, 14336),
              ("q4k", 16384, 4096), ("q6k", 4096, 16384)]
ROW_COUNTS = (8, 256, 512, 1024, 2048)
ROW_ITERS = 200
MXU_TFLOPS = 197.0  # v5e bf16 peak (spec)


def rows_sweep(linear) -> list:
    """us a call of ``rows`` rows, as ``linear`` serves it (``form``
    ``call``) and cut into 256-row calls (``cut256``: what every call of
    more rows was before the many-row kernel), beside the MXU's time for
    the call's FLOPs and the few-row call (8 rows: the weight pass alone)."""
    rng = np.random.default_rng(0)
    rows = []
    for fmt, n, k in ROW_SHAPES:
        wf = rng.standard_normal((n, k)).astype(np.float32) * (k ** -0.5)
        w = make_weight(fmt, wf)

        def cut256(x, w):
            return jnp.concatenate([linear(x[i:i + 256], w)
                                    for i in range(0, x.shape[0], 256)])

        for b in ROW_COUNTS:
            for form, fn in (("call", linear), ("cut256", cut256)):
                if form == "cut256" and b <= 256:
                    continue
                dt = timed_chain(fn, w, b, k, n, ROW_ITERS)
                row = {"fmt": fmt, "n": n, "k": k, "rows": b, "form": form,
                       "us": round(dt * 1e6, 1),
                       "us_per_256": round(dt * 1e6 * 256 / max(b, 256), 1),
                       "mxu_us": round(2.0 * b * n * k / MXU_TFLOPS / 1e6, 1)}
                rows.append(row)
                print(json.dumps(row), file=sys.stderr, flush=True)
        del w
    return rows


# the tail sweep: (name, format, N, K, stacked) of ``phi4flash``'s matrices
# (the head is the one unstacked call: the Q6_K integer body)
TAIL_SHAPES = [("gate_up", "q4k", 10240, 2560, True),
               ("ssm_in", "q4k", 10240, 2560, True),
               ("wq", "q4k", 2560, 2560, True),
               ("wv", "q6k", 1280, 2560, True),
               ("head", "q6k", 200064, 2560, False),
               ("ssm_out", "q4k", 2560, 5120, True)]
TAIL_ROWS = ((16, 400), (1024, 40))         # (rows, chained calls)


def random_planes(fmt: str, n: int, k: int, key) -> dict:
    """Planes of a (n, k) matrix in ``fmt``'s layout as ``prep_*`` stores a
    K of ``k`` (whole tiles, and a tail's beside them), of random bytes and
    small scales: a call's time does not depend on the values, and the
    codecs would take minutes at these sizes."""
    from llama_fastapi_k8s_gpu_tpu.ops.pallas.qmatmul import TK, tail_of

    def ints(i, *shape):
        return jax.random.randint(jax.random.fold_in(key, i), shape, -128,
                                  128, jnp.int8)

    def scales(i, tiles):
        return (jax.random.normal(jax.random.fold_in(key, i),
                                  (tiles, n, 128)) * 1e-3
                ).astype(jnp.bfloat16)

    tail = tail_of(k)
    kw = k - tail
    if fmt == "q4k":
        w = {"qs": ints(0, n, kw // 2), "sm": scales(1, kw // TK)}
        if tail:
            w.update(qs_t=ints(2, n, tail // 2), sm_t=scales(3, 1))
        return w
    w = {"q4": ints(0, n, kw // 2), "q2": ints(1, n, kw // 4),
         "sm6": scales(2, kw // TK)}
    if tail:
        w.update(q4_t=ints(3, n, tail // 2), q2_t=ints(4, n, tail // 4),
                 sm6_t=scales(5, 1))
    return w


def tail_sweep(linear, linear_at) -> list:
    """us a call in the ``tail`` layout and ``filled`` (the K filled up to
    the next multiple of 2048, the activations with zeros, as both were
    before PR 63), with the planes' bytes and their time at 819 GB/s."""
    from llama_fastapi_k8s_gpu_tpu.ops.pallas.qmatmul import TK

    out = []
    for name, fmt, n, k, stacked in TAIL_SHAPES:
        k_fill = -(-k // TK) * TK
        for layout, kk in (("tail", k), ("filled", k_fill)):
            w = random_planes(fmt, n, kk, jax.random.PRNGKey(n + k))
            nbytes = sum(a.nbytes for a in w.values())
            if stacked:
                w = {key: a[None] for key, a in w.items()}

            def fn(x, w, pad=kk - k):
                x = jnp.pad(x, ((0, 0), (0, pad))) if pad else x
                return linear_at(x, w, 0) if stacked else linear(x, w)

            for b, iters in TAIL_ROWS:
                if not stacked and b > 16:
                    iters = 8       # (a 0.8 GB float32 result a call)
                row = {"name": name, "fmt": fmt, "n": n, "k": k, "rows": b,
                       "layout": layout, "MB": round(nbytes / 1e6, 2),
                       "floor_us": round(nbytes / (HBM_GBPS * 1e3), 1)}
                try:
                    dt = timed_chain(fn, w, b, k, n, iters)
                except Exception as e:  # noqa: BLE001 — what the chip refuses
                    row["error"] = str(e)[:300]
                else:
                    row.update(us=round(dt * 1e6, 1), floor_share=round(
                        100 * row["floor_us"] / (dt * 1e6), 1))
                out.append(row)
                print(json.dumps(row), file=sys.stderr, flush=True)
            del w
    return out


def timed_chain(linear_fn, w, b: int, k: int, n: int, iters: int) -> float:
    """Mean per-matmul time over an ``iters``-step ON-DEVICE chain.

    The chain must live inside ONE jit (``lax.scan``): a Python-level loop
    of jit calls pays the dispatch round trip per step and measures the
    dispatch, not the kernel.  The per-step coupling (output
    folded back into the input row) is non-zero so XLA can neither hoist
    the matmul (input changes every iteration) nor dead-code it."""
    @jax.jit
    def chain(x):
        def body(x, _):
            y = linear_fn(x, w)                   # (B, N) bf16
            # (the first 128 columns: a many-row output is not read whole)
            r = jnp.sum(y[:, :128], axis=1, keepdims=True).astype(jnp.bfloat16)
            return x + r * jnp.bfloat16(1e-8), ()

        x, _ = jax.lax.scan(body, x, None, length=iters)
        return x

    def sync(x):
        float(jnp.sum(x).astype(jnp.float32))     # host fetch: reliable sync

    x = jnp.ones((b, k), jnp.bfloat16)
    sync(chain(x))                                # compile
    sync(chain(x))                                # second warm (slow-start)
    t0 = time.perf_counter()
    sync(chain(x))
    return (time.perf_counter() - t0) / iters


def main() -> None:
    from llama_fastapi_k8s_gpu_tpu.utils.jaxcache import setup_compile_cache

    setup_compile_cache()
    from llama_fastapi_k8s_gpu_tpu.ops.linear import linear as linear_, linear_at

    def linear(x, w):
        # (a Q6_K weight arrives as a stack of one: see make_weight)
        return linear_at(x, w, 0) if "sm6" in w else linear_(x, w)

    dev = jax.devices()[0]
    if sys.argv[1:] == ["tail"]:
        print(json.dumps({"device": str(dev), "hbm_gbps": HBM_GBPS,
                          "rows": tail_sweep(linear_, linear_at)}),
              flush=True)
        return
    if sys.argv[1:] == ["rows"]:
        print(json.dumps({"device": str(dev), "iters": ROW_ITERS,
                          "mxu_tflops": MXU_TFLOPS,
                          "rows": rows_sweep(linear)}), flush=True)
        return
    out: dict = {"device": str(dev), "iters": ITERS, "hbm_gbps": HBM_GBPS}
    rows = []
    rng = np.random.default_rng(0)
    sel = [f for f in os.environ.get(
        "KMB_FMTS", ",".join(VARIANTS)).split(",") if f]
    bad = [f for f in sel if f not in VARIANTS]
    if bad or not sel:  # fail loud — a typo'd (or empty) A/B must not
        raise SystemExit(  # silently bench nothing
            f"KMB_FMTS: unknown format(s) {bad or '(empty)'}; "
            f"valid: {list(VARIANTS)}")
    fmts = [f for f in VARIANTS if f in sel]
    for fmt in fmts:
        for (n, k) in SHAPES:
            wf = (rng.standard_normal((n, k)).astype(np.float32)
                  * (k ** -0.5))
            # roof_us = bytes / (GB/s · 1e3): set per-variant below (the
            # q6k `pre` layout reads different bytes than the split)
            xprobe = jnp.asarray(
                rng.standard_normal((8, k)) * 0.5, jnp.bfloat16)
            yref = ref_var = None
            for var in VARIANTS[fmt]:
                if fmt in KNOB:
                    os.environ[KNOB[fmt]] = var
                w = make_weight(fmt, wf)   # after the env: layout variants
                roof_us = weight_bytes(fmt, n, k, var) / (HBM_GBPS * 1e3)
                # on-chip numerics cross-check vs the reference variant
                # (named in dev_ref; normally the default) — catches
                # toolchain-specific plane truncation (e.g. an f32 dot
                # silently lowered to single-pass bf16) that the CPU
                # interpret tests cannot see.  A probe failure does NOT
                # skip timing (B=8 is one of the benchmarked sizes, but a
                # variant may still fail one shape and serve others).
                rel_dev = None
                try:
                    y = np.asarray(linear(xprobe, w), dtype=np.float32)
                except Exception as e:
                    rows.append({"fmt": fmt, "variant": var, "n": n, "k": k,
                                 "probe_error": str(e)[:200]})
                    print(f"PROBE FAIL {fmt}/{var} ({n},{k}): {str(e)[:120]}",
                          file=sys.stderr, flush=True)
                    y = None
                dev_fail = False
                if y is not None:
                    if yref is None:
                        yref, ref_var, rel_dev = y, var, 0.0
                    else:
                        rel_dev = float(np.abs(y - yref).max()
                                        / (np.abs(yref).max() + 1e-9))
                        dev_fail = rel_dev > REL_DEV_GATE
                        if dev_fail:
                            print(f"DEV GATE FAIL {fmt}/{var} ({n},{k}): "
                                  f"rel_dev {rel_dev:.2e} > {REL_DEV_GATE}",
                                  file=sys.stderr, flush=True)
                for b in BATCHES:
                    try:
                        dt = timed_chain(linear, w, b, k, n, ITERS)
                    except Exception as e:  # variant may not compile on-chip
                        rows.append({"fmt": fmt, "variant": var, "n": n,
                                     "k": k, "b": b,
                                     "error": str(e)[:200]})
                        print(f"FAIL {fmt}/{var} ({n},{k}) B={b}: "
                              f"{str(e)[:120]}", file=sys.stderr, flush=True)
                        continue
                    rows.append({
                        "fmt": fmt, "variant": var, "n": n, "k": k, "b": b,
                        "us": round(dt * 1e6, 1),
                        "roofline_us": round(roof_us, 1),
                        "pct_roofline": round(100 * roof_us / (dt * 1e6), 1),
                        "rel_dev": None if rel_dev is None
                        else round(rel_dev, 6),
                        "dev_fail": dev_fail,
                        "dev_ref": ref_var,
                    })
                    print(f"{fmt}/{var} ({n},{k}) B={b}: "
                          f"{dt*1e6:.1f} us ({100*roof_us/(dt*1e6):.0f}% "
                          f"roof, dev {rel_dev} vs {ref_var})",
                          file=sys.stderr, flush=True)
                del w              # free this variant's planes before the next
                if fmt in KNOB:
                    del os.environ[KNOB[fmt]]
    out["rows"] = rows
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
