"""Time the head's Q6_K call alone (run ALONE on the chip).

The vocabulary head is the one unstacked fused Q6_K tensor of a served
file (``ops/pallas/q6matmul.py _q6k_2d_raw``).  This times that call at the
heads of every configuration that serves a fused Q6_K head, at 1 and 16
rows (``--rows`` for more: a call of up to 256 rows takes the head's tiling,
a taller one the many-row tiles), beside the call as it was before PR 57
(the stacked calls' body ``_q6k_matmul_kernel`` under their tiling, rebuilt
here from the parts that still serve them), and prints for each the milliseconds a
call and the share of ``bytes / 819 GB/s`` (bytes: the planes as the
kernel stores them, ``q4`` N x K/2, ``q2`` N x K/4, ``sm6`` N x K/8, with
K filled up to the K tile: 7168 -> 8192).

    chiprun -- python tools/time_head_call.py
    chiprun -- python tools/time_head_call.py --only kexaone --tn-units 2,4,8

Method: ``calls`` and ``3 x calls`` chained calls inside ONE jit (the
result folded back into the activations, so nothing hoists), the slope
``(t(3n) - t(n)) / 2n`` of the medians of ``reps`` runs.  ``same_bits``:
the new call's float32 result equals the old one's bit for bit.  One JSON
line a (shape, rows, side); all of them again in ``--out``.
"""

from __future__ import annotations

import argparse
import functools
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

    def old_call(xpa, q4, q2, sm):
        """The unstacked call as it was: the stacked calls' body and
        tiling, one K tile of activations fetched a grid step."""
        B, N, K = xpa.shape[0], q4.shape[0], q4.shape[1] * 2
        TN = Q4._pick_tn(N, False, prefs=Q4.tn_prefs(B, Q6._TN_PREFS_Q6K))
        in_specs, out_spec = Q6._q6k_specs(B, TN)
        return Q4.plain_pallas_call(
            functools.partial(Q6._q6k_matmul_kernel, interpret=False,
                              variant="cur"),
            (N // TN, K // Q4.TK), in_specs, out_spec,
            jax.ShapeDtypeStruct((B, N), jnp.float32), False,
            Q4.kernel_name("q6k", B))(xpa, q4, q2, sm)

    def new_call(xpa, q4, q2, sm):
        return Q6._q6k_2d_raw(xpa, q4, q2, sm, False)

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
             for u in args.tn_units.split(",") if u] or [None]
    for name, N, k_file in SHAPES:
        if only and name not in only:
            continue
        K = padded_k(k_file)
        key = jax.random.PRNGKey(N)
        q4 = jax.random.randint(key, (N, K // 2), -128, 128, jnp.int8)
        q2 = jax.random.randint(jax.random.fold_in(key, 1), (N, K // 4),
                                -128, 128, jnp.int8)
        sm = (jax.random.normal(jax.random.fold_in(key, 2),
                                (K // Q4.TK, N, 128)) * 1e-3
              ).astype(jnp.bfloat16)
        nbytes = q4.size + q2.size + sm.size * 2
        floor_ms = nbytes / (HBM_GBPS * 1e6)
        for B in (int(r) for r in args.rows.split(",")):
            x = jax.random.normal(jax.random.fold_in(key, 3), (B, K),
                                  jnp.bfloat16)
            xpa = Q6.augment_x6(Q6.permute_x6(x))
            want = None
            sides = [] if args.no_old else [("old", None)]
            sides += [("new", u) for u in units]
            for side, u in sides:
                call = old_call if side == "old" else new_call
                if u is not None:
                    Q6.HEAD_TN_UNITS = u[0]
                    if len(u) > 1:
                        Q6.HEAD_W_BLOCK = 128 * u[0] * u[1] * Q4.TK
                row = dict(shape=name, N=N, K=K, rows=B, side=side,
                           MB=round(nbytes / 1e6, 1),
                           floor_ms=round(floor_ms, 4))
                if side == "new":
                    row["TN"], row["k_tiles_a_step"] = Q6._head_tiling(
                        N, B, K // Q4.TK, False)
                try:
                    ms = slope_ms(call, xpa, q4, q2, sm)
                    got = np.asarray(jax.jit(call)(xpa, q4, q2, sm))
                except Exception as e:  # noqa: BLE001 — a tiling the chip refuses
                    say(**row, error=str(e)[:300])
                    continue
                row.update(ms=round(ms, 4),
                           floor_share=round(100 * floor_ms / ms, 1))
                if want is None:
                    want = got
                else:
                    row["same_bits"] = bool(np.array_equal(
                        got.view(np.uint32), want.view(np.uint32)))
                    row["max_dev"] = float(np.abs(got - want).max()
                                           / (np.abs(want).max() + 1e-30))
                say(**row)
        del q4, q2, sm
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as fh:
        fh.writelines(json.dumps(row) + "\n" for row in lines)
    return 0


if __name__ == "__main__":
    sys.exit(main())
