"""Time a prefill slice's latent attention ALONE on the chip, at the
published widths (64 heads, rows of 512 + 64 laid out as 640, a leaf of 7
layers x 16384 positions): the slice kernel (ops/pallas/attention.py
``latent_attention_prefill``) at each ``--blocks`` geometry against the XLA
loop (``models/mla.py latent_attention``), at the (S, bound) points a
``longdoc`` prompt's first, middle and last wide slice and an ``agent``
turn's narrow slice read.  One JSON line a timing: ms a call, and ms per
block of 512 keys the loop would walk ((bound + 512) // 512), which is what
PERF.md section 6 (PR 50) compares with the MXU's 0.39 ms.

    chiprun -- python tools/time_latent_prefill.py

A device number: it refuses to run without a TPU."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

POINTS = ((1024, 1023), (1024, 5631), (1024, 11263), (256, 8959))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--blocks", default="512,1024,512",
                    help="block_q,block_k,sub_k[,chains]; several separated "
                         "by ':'")
    ap.add_argument("--reps", type=int, default=8)
    ap.add_argument("--out", default="chiprun_out/time_latent_prefill.jsonl")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from llama_fastapi_k8s_gpu_tpu.models import mla
    from llama_fastapi_k8s_gpu_tpu.models.config import ModelConfig
    from llama_fastapi_k8s_gpu_tpu.ops.pallas import latent_attention_prefill

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(json.dumps({"error": f"platform {dev.platform!r}: a device "
                          "number comes from a chip"}))
        return 2
    H, r, d_r, L, n_ctx = 64, 512, 64, 7, 16384
    cfg = ModelConfig(
        vocab_size=256, dim=7168, n_layers=L, n_heads=H, n_kv_heads=H,
        ffn_dim=18432, n_ctx=n_ctx, rope_theta=1e5, rms_eps=1e-6,
        attn_impl="xla", q_lora_rank=1536, kv_lora_rank=r, qk_nope_dim=128,
        qk_rope_dim=d_r, v_head_dim=192, attn_mscale=2.0048)
    W, scale = mla.leaf_width(cfg), mla.attn_scale(cfg)
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    lat = jax.random.normal(k1, (L, 1, n_ctx, W), jnp.bfloat16
                            ).at[..., r + d_r:].set(0)
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    lines = []

    def timed(fn, *a):
        out = fn(*a)
        out.block_until_ready()                   # compiled
        ts = []
        for _ in range(args.reps):
            t = time.perf_counter()
            fn(*a).block_until_ready()
            ts.append(time.perf_counter() - t)
        return out, 1e3 * float(np.median(ts)), 1e3 * min(ts)

    def say(**row):
        row["device_kind"] = dev.device_kind
        lines.append(row)
        print(json.dumps(row), flush=True)

    geometries = [tuple(int(x) for x in g.split(","))
                  for g in args.blocks.split(":")]
    for S, bound in POINTS:
        off = bound - S + 1
        n512 = (bound + 512) // 512
        q = (jax.random.normal(k2, (S, H, W), jnp.bfloat16) * 0.3
             ).at[..., r + d_r:].set(0)
        pos = off + jnp.arange(S, dtype=jnp.int32)
        loop = jax.jit(lambda q, lat, o, p: mla.latent_attention(
            q, lat, jnp.int32(3), p, o + S - 1, cfg))
        want, med, best = timed(loop, q, lat, jnp.int32(off), pos)
        say(read="loop", S=S, bound=bound, ms=med, ms_min=best,
            ms_per_512_block=med / n512)
        want = np.asarray(want, np.float64)
        qh = q.transpose(1, 0, 2)
        for bq, bk, sk, *ch in geometries:
            fn = jax.jit(lambda q, lat, o: latent_attention_prefill(
                q, lat, jnp.int32(3), o, sm_scale=scale, v_width=r,
                block_q=bq, block_k=bk, sub_k=sk, chains=(ch or [1])[0],
                interpret=False))
            try:
                got, med, best = timed(fn, qh, lat, jnp.int32(off))
            except Exception as e:  # noqa: BLE001 -- a geometry may not fit
                say(read="kernel", S=S, bound=bound, blocks=[bq, bk, sk, *ch],
                    error=f"{type(e).__name__}: {e}"[:300])
                continue
            got = np.asarray(got.astype(jnp.float32), np.float64)
            say(read="kernel", S=S, bound=bound, blocks=[bq, bk, sk, *ch], ms=med,
                ms_min=best, ms_per_512_block=med / n512,
                rel_to_loop=float(np.linalg.norm(got - want)
                                  / np.linalg.norm(want)))
    with open(args.out, "w") as f:
        f.writelines(json.dumps(row) + "\n" for row in lines)
    return 0


if __name__ == "__main__":
    sys.exit(main())
