"""Attribute the in-model fused-Q4_K decode gap (run ALONE on the chip).

BENCH_r03 interim runs put the full-model fused-Q4_K decode at ~53.5 tok/s
(18.7 ms/token) while the int8 path does 80.6 (12.4 ms) — yet a per-op
microbench had the fused kernel beating int8 at every 8B shape.  This script times, with the same
hoist-proof scan harness, the pieces that differ between the two paths:

- chained per-layer matmul stacks (the 7 linears of a Llama layer, output
  fed back) for q4k vs int8 — in-model per-op cost incl. permute/augment
  and pallas launch overhead;
- the permute+augment activation prep alone;
- a combined-QKV + combined-gate/up variant (4 pallas calls per layer
  instead of 7) to size the win before wiring it into the model.

Prints one JSON object (not the driver bench contract — a diagnostics tool).
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def timed(fn, state, iters, *, sync):
    # warm TWICE and discard: the first executions of a program family in
    # a fresh process run 20-40x slow on this platform (docs/PERF.md
    # "Measurement hygiene") — without this, whichever variant is timed
    # first looks artificially slow
    out = fn(state)
    sync(out)
    out = fn(state)
    sync(out)
    t0 = time.time()
    out = fn(state)
    sync(out)
    t1 = time.time()
    n = max(1, iters)
    t2 = time.time()
    for _ in range(n):
        out = fn(out)
    sync(out)
    dt = (time.time() - t2) / n
    return dt, t1 - t0


def main() -> None:
    from llama_fastapi_k8s_gpu_tpu.models.config import LLAMA3_8B
    from llama_fastapi_k8s_gpu_tpu.ops.linear import linear
    from llama_fastapi_k8s_gpu_tpu.ops.pallas.qmatmul import (
        augment_x,
        permute_x,
    )

    cfg = LLAMA3_8B
    dev = jax.devices()[0]
    print(f"device: {dev}", file=sys.stderr, flush=True)

    from bench import synth_params_device

    L = cfg.n_layers
    results: dict = {"device": str(dev)}

    @functools.partial(jax.jit, static_argnums=())
    def run_stack(layers, x):
        # the 7 linears of one Llama layer, chained through x via cheap
        # reductions so nothing hoists; scanned over all 32 layers
        def body(x, lp):
            q = linear(x, lp["wq"])
            k = linear(x, lp["wk"])
            v = linear(x, lp["wv"])
            o = linear(q, lp["wo"])
            g = linear(x, lp["w_gate"])
            u = linear(x, lp["w_up"])
            d = linear((g * u)[:, : cfg.ffn_dim], lp["w_down"])
            x = x + o + d + k.sum() + v.sum()
            return x, ()
        x, _ = jax.lax.scan(body, x, layers)
        return x

    @jax.jit
    def run_head(w, x):
        return linear(x, w)[:, : cfg.dim].astype(jnp.bfloat16)

    def sync(out):
        float(jnp.sum(out[0] if isinstance(out, tuple) else out).astype(jnp.float32))

    for fmt in ("q4k", "int8"):
        params = synth_params_device(cfg, fmt=fmt)
        sync(params["tok_emb"])
        x0 = jnp.ones((1, cfg.dim), jnp.bfloat16)
        dt, _ = timed(lambda x: run_stack(params["layers"], x), x0, 20,
                      sync=sync)
        results[f"stack_ms_{fmt}"] = round(dt * 1e3, 3)
        dt, _ = timed(lambda x: run_head(params["output"], x), x0, 20,
                      sync=sync)
        results[f"head_ms_{fmt}"] = round(dt * 1e3, 3)
        del params

    # permute+augment alone (4 unique activations per layer in the real model)
    def prep(x):
        for _ in range(4 * L):
            x = augment_x(permute_x(x).reshape(1, -1))[:, : cfg.dim].astype(
                jnp.bfloat16)
        return x
    dt, _ = timed(jax.jit(prep), jnp.ones((1, cfg.dim), jnp.bfloat16), 10,
                  sync=sync)
    results["permute_augment_128x_ms"] = round(dt * 1e3, 3)

    # combined QKV + gate/up: 4 fused calls per layer instead of 7
    params = synth_params_device(cfg, fmt="q4k")
    sync(params["tok_emb"])

    def cat(ws):
        return {
            "qs": jnp.concatenate([w["qs"] for w in ws], axis=1),
            "sm": jnp.concatenate([w["sm"] for w in ws], axis=2),
        }

    lay = params["layers"]
    comb = {
        "wqkv": cat([lay["wq"], lay["wk"], lay["wv"]]),
        "wo": lay["wo"],
        "w_gu": cat([lay["w_gate"], lay["w_up"]]),
        "w_down": lay["w_down"],
    }
    sync(comb["wqkv"]["qs"])

    @jax.jit
    def run_comb(comb, x):
        def body(x, lp):
            qkv = linear(x, lp["wqkv"])
            q = qkv[:, : cfg.dim]
            kv = qkv[:, cfg.dim:]
            o = linear(q, lp["wo"])
            gu = linear(x, lp["w_gu"])
            d = linear(gu[:, : cfg.ffn_dim] * gu[:, cfg.ffn_dim:],
                       lp["w_down"])
            x = x + o + d + kv.sum()
            return x, ()
        x, _ = jax.lax.scan(body, x, comb)
        return x

    dt, _ = timed(lambda x: run_comb(comb, x),
                  jnp.ones((1, cfg.dim), jnp.bfloat16), 20, sync=sync)
    results["stack_ms_q4k_combined"] = round(dt * 1e3, 3)
    del comb

    # UNROLLED layer loop: per-layer weights as separate buffers, so each
    # pallas_call reads its operand directly from HBM.  If the scanned
    # variant is slower by ~2x, the per-layer dynamic-slice of the stacked
    # (L, ...) weight array is being materialized (copied) before every
    # pallas_call — a copy XLA fuses away for the int8 dot_general path.
    unrolled = [
        jax.tree_util.tree_map(lambda a: a[i], lay) for i in range(L)
    ]
    sync(unrolled[0]["wq"]["qs"])

    @jax.jit
    def run_unrolled(layers, x):
        for lp in layers:
            q = linear(x, lp["wq"])
            k = linear(x, lp["wk"])
            v = linear(x, lp["wv"])
            o = linear(q, lp["wo"])
            g = linear(x, lp["w_gate"])
            u = linear(x, lp["w_up"])
            d = linear((g * u)[:, : cfg.ffn_dim], lp["w_down"])
            x = x + o + d + k.sum() + v.sum()
        return x

    dt, _ = timed(lambda x: run_unrolled(unrolled, x),
                  jnp.ones((1, cfg.dim), jnp.bfloat16), 20, sync=sync)
    results["stack_ms_q4k_unrolled"] = round(dt * 1e3, 3)

    print(json.dumps(results), flush=True)


if __name__ == "__main__":
    main()
