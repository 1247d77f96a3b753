"""Time the split-layout Q6_K calls alone (run ALONE on the chip).

Two callers run ``ops/pallas/q6matmul.py``'s one body under one builder
(``_q6k_call``): the vocabulary head, the one unstacked fused Q6_K tensor of
a served file (``_q6k_2d_raw``), and every layer's stacked ``w_down`` /
``wv`` (``_q6k_2d_stacked_raw``, ``--stacked``: the dense configurations'
shapes, on layer 1 of a stack of two).  This times the call at 1 and 16 rows
(``--rows`` for more: a call of up to 256 rows takes the few-row tiling, a
taller one the many-row tiles), beside the PARENT commit's call where its
``q6matmul.py`` is at ``--parent`` (``git archive --prefix=.parent_check/
<parent> | tar x``; its other imports are this tree's), and prints for each
the milliseconds a call and the share of ``bytes / 819 GB/s`` (bytes: the
planes as the kernel stores them, ``q4`` N x K/2, ``q2`` N x K/4, ``sm6`` N
x K/8, with K filled up to the K tile: 7168 -> 8192, or ending in a tail
tile: 2560).

    chiprun -- python tools/time_head_call.py
    chiprun -- python tools/time_head_call.py --only kexaone --tn-units 2,4,8
    chiprun -- python tools/time_head_call.py --stacked --rows 1,16,256 \
        --tiling 1024:1,512:7,256:7

``--tiling``: (N tile):(K tiles a step) pairs put in the place of
``_q6k_tiling``'s choice for the few-row calls (a pair that does not divide
the shape is left out).

Method: ``calls`` and ``3 x calls`` chained calls inside ONE jit (the
result folded back into the activations, so nothing hoists), the slope
``(t(3n) - t(n)) / 2n`` of the medians of ``reps`` runs.  ``same_bits``:
the call's float32 result equals the first side's bit for bit.  One JSON
line a (shape, rows, side); all of them again in ``--out``.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

HBM_GBPS = 819.0  # v5e HBM bandwidth (spec)

# (name, N, K as the file has it): the heads of kexaone, gigachat, longcat,
# the 32000 x 4096 of mistral / solar, and the two of one K tile: olmoe
# (50304 = 131 x 384, so an N tile of 384) and ouro
SHAPES = [("kexaone", 153600, 6144), ("gigachat", 128256, 7168),
          ("longcat", 131072, 6144), ("llama32k", 32000, 4096),
          ("olmoe", 50304, 2048), ("ouro", 49152, 2048)]
# the stacked calls of the dense configurations: ``w_down`` of mistral /
# solar, sala, ouro, evabyte (K 11008 filled to 12288), phi4flash, longcat's
# dense layers; ``wv`` of mistral / solar, and the widths whose K ends in a
# tail tile (phi4flash's ``wv`` at K 2560, a K of 5120)
STACKED = [("down.mistral", 4096, 14336), ("down.sala", 4096, 16384),
           ("down.ouro", 2048, 5632), ("down.evabyte", 4096, 11008),
           ("down.phi4flash", 2560, 10240), ("down.longcat", 6144, 12288),
           ("wv.mistral", 1024, 4096), ("wv.ouro", 2048, 2048),
           ("wv.phi4flash", 1280, 2560), ("tail.5120", 2560, 5120)]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="", help="comma list of shape names")
    ap.add_argument("--rows", default="1,16")
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--tn-units", default="",
                    help="sweep the tiling: comma list of HEAD_TN_UNITS, or "
                    "units:tiles to set HEAD_W_BLOCK to `tiles` K tiles of "
                    "that N tile too (default: as built)")
    ap.add_argument("--stacked", action="store_true",
                    help="the stacked calls' shapes in place of the heads'")
    ap.add_argument("--tiling", default="",
                    help="comma list of tn:tiles pairs for the few-row calls")
    ap.add_argument("--parent", default=".parent_check/llama_fastapi_k8s_"
                    "gpu_tpu/ops/pallas/q6matmul.py",
                    help="the parent commit's q6matmul.py (side `old`)")
    ap.add_argument("--no-old", action="store_true")
    ap.add_argument("--out", default="chiprun_out/time_head_call.jsonl")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from llama_fastapi_k8s_gpu_tpu.ops.linear import padded_k
    from llama_fastapi_k8s_gpu_tpu.ops.pallas import q6matmul as Q6
    from llama_fastapi_k8s_gpu_tpu.ops.pallas import qmatmul as Q4

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"needs the chip, found {dev}")
    lines = []

    def say(**row):
        lines.append(row)
        print(json.dumps(row), flush=True)

    say(device=str(dev), kind=dev.device_kind, hbm_gbps=HBM_GBPS)

    parent = None
    if not args.no_old and os.path.exists(args.parent):
        spec = importlib.util.spec_from_file_location(
            Q6.__name__ + "_parent", args.parent)
        parent = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(parent)

    def call_of(mod):
        """``mod``'s call on the planes as they are stored: the head's, or
        the stacked one on layer 1 of a stack of two."""
        if not args.stacked:
            return lambda xpa, q4, q2, sm, *tail: mod._q6k_2d_raw(
                xpa, q4, q2, sm, False, tail)
        return lambda xpa, *planes: mod._q6k_2d_stacked_raw(
            jnp.ones(1, jnp.int32), xpa, *planes, interpret=False)

    def chain_of(call, n):
        def run(xpa, *planes):
            def step(_, xpa):
                y = call(xpa, *planes)
                r = jnp.sum(y[:, :128], axis=1, keepdims=True)
                return xpa + (r * 1e-9).astype(xpa.dtype)
            return jax.lax.fori_loop(0, n, step, xpa)
        return jax.jit(run)

    def slope_ms(call, *a):
        ts = []
        for n in (args.calls, 3 * args.calls):
            fn = chain_of(call, n)
            fn(*a).block_until_ready()                # compiled
            t = []
            for _ in range(args.reps):
                t0 = time.perf_counter()
                fn(*a).block_until_ready()
                t.append(time.perf_counter() - t0)
            ts.append(float(np.median(t)))
        return 1e3 * (ts[1] - ts[0]) / (2 * args.calls)

    only = [s for s in args.only.split(",") if s]
    units = [tuple(int(v) for v in u.split(":"))
             for u in args.tn_units.split(",") if u]
    tilings = [tuple(int(v) for v in u.split(":"))
               for u in args.tiling.split(",") if u]
    built = (Q6.HEAD_TN_UNITS, Q6.HEAD_W_BLOCK, Q6._q6k_tiling)
    lead = (2,) if args.stacked else ()
    for name, N, k_file in STACKED if args.stacked else SHAPES:
        if only and not any(o in name for o in only):
            continue
        T = Q4.tail_of(k_file)
        K = k_file if T else padded_k(k_file)
        kt = (K - T) // Q4.TK
        key = jax.random.PRNGKey(N)

        def plane(i, shape, scale=None):
            k = jax.random.fold_in(key, i)
            if scale is None:
                return jax.random.randint(k, lead + shape, -128, 128,
                                          jnp.int8)
            return (jax.random.normal(k, lead + shape) * scale
                    ).astype(jnp.bfloat16)

        planes = [plane(0, (N, (K - T) // 2)), plane(1, (N, (K - T) // 4)),
                  plane(2, (kt, N, 128), 1e-3)]
        if T:
            planes += [plane(4, (N, T // 2)), plane(5, (N, T // 4)),
                       plane(6, (1, N, 128), 1e-3)]
        nbytes = sum(p.size * p.dtype.itemsize for p in planes) // (
            2 if args.stacked else 1)
        floor_ms = nbytes / (HBM_GBPS * 1e6)
        for B in (int(r) for r in args.rows.split(",")):
            x = jax.random.normal(jax.random.fold_in(key, 3), (B, K),
                                  jnp.bfloat16)
            xpa = Q6.augment_x6(Q6.permute_x6(x))
            want = None
            sides = [("old", None)] if parent is not None else []
            sides += [("new", None)] + [("new", ("units", *u)) for u in units]
            if B <= Q4.TM:
                sides += [("new", ("tiling", *t)) for t in tilings
                          if N % t[0] == 0 and kt % t[1] == 0]
            for side, u in sides:
                Q6.HEAD_TN_UNITS, Q6.HEAD_W_BLOCK, Q6._q6k_tiling = built
                if u is not None and u[0] == "units":
                    Q6.HEAD_TN_UNITS = u[1]
                    if len(u) > 2:
                        Q6.HEAD_W_BLOCK = 128 * u[1] * u[2] * Q4.TK
                elif u is not None:
                    Q6._q6k_tiling = lambda *a, _t=u[1:]: _t
                row = dict(shape=name, N=N, K=K, rows=B, side=side,
                           MB=round(nbytes / 1e6, 1),
                           floor_ms=round(floor_ms, 4))
                if side == "new":
                    row["TN"], row["k_tiles_a_step"] = Q6._q6k_tiling(
                        N, B, kt, False)
                    row["chosen"] = u is None
                call = call_of(parent if side == "old" else Q6)
                try:
                    ms = slope_ms(call, xpa, *planes)
                    got = np.asarray(jax.jit(call)(xpa, *planes))
                except Exception as e:  # noqa: BLE001 — a tiling the chip refuses
                    say(**row, error=str(e)[:300])
                    continue
                row.update(ms=round(ms, 5),
                           floor_share=round(100 * floor_ms / ms, 1))
                if want is None:
                    want = got
                else:
                    row["same_bits"] = bool(np.array_equal(
                        got.view(np.uint32), want.view(np.uint32)))
                    row["max_dev"] = float(np.abs(got - want).max()
                                           / (np.abs(want).max() + 1e-30))
                say(**row)
        Q6.HEAD_TN_UNITS, Q6.HEAD_W_BLOCK, Q6._q6k_tiling = built
        del planes
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as fh:
        fh.writelines(json.dumps(row) + "\n" for row in lines)
    return 0


if __name__ == "__main__":
    sys.exit(main())
