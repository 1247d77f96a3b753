#!/usr/bin/env python3
"""Times, on the chip, the pieces of a ``deepseek32`` layer's sparse
attention at the published shapes (64 indexer heads of 128, 128 heads of
latent attention, ``index_topk`` 2048, a leaf of 16384 positions, queries
near 8.7k), each alone, so that the choices in models/mla.py rest on
numbers (PERF.md section 6, PR 58):

- the indexer's scores (``mla.index_scores``, a loop in plain XLA): a decode
  step's 16 lanes, a slice of 256 / 512 / 1024 rows, the wide ones scored in
  runs of ``INDEX_SLICE`` queries and, beside that, in one run;
- the selection: the threshold search (``select_topk``) against
  ``jax.lax.top_k`` at the same rows;
- the selected read of a decode step: the decode kernel with the selection
  as a MASK on the blocks it walks, against a GATHER of the 2048 selected
  rows a lane into a compact array (the gather alone: the attention over
  it is not counted), and the dense kernel with no selection beside both;
- a slice's masked read (the slice kernel with the bias operand) beside the
  dense slice kernel.

    chiprun -- python tools/time_dsa_select.py

One JSON line a measurement: {"what", "ms"}.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from llama_fastapi_k8s_gpu_tpu.models import mla
from llama_fastapi_k8s_gpu_tpu.models.config import ModelConfig
from llama_fastapi_k8s_gpu_tpu.ops.pallas import (
    latent_attention_decode, latent_attention_prefill)

N_CTX, POS, LANES, TOPK = 16384, 8700, 16, 2048
CFG = ModelConfig(
    vocab_size=8, dim=7168, n_layers=6, n_heads=128, n_kv_heads=128,
    ffn_dim=8, n_ctx=N_CTX, q_lora_rank=1536, kv_lora_rank=512,
    qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128, index_heads=64,
    index_dim=128, index_topk=TOPK)


def timed(what, fn, *args, reps=10):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    print(json.dumps({"what": what,
                      "ms": (time.perf_counter() - t0) / reps * 1e3}),
          flush=True)
    return out


def main():
    interpret = jax.default_backend() != "tpu"
    key = jax.random.split(jax.random.PRNGKey(0), 8)
    idx = jax.random.normal(key[0], (6, 1, N_CTX, 128)).astype(jnp.bfloat16)
    lat = jax.random.normal(key[1], (6, 1, N_CTX, 640)).astype(jnp.bfloat16)
    # -- the indexer's scores ------------------------------------------------
    for S in (256, 512, 1024):
        q = jax.random.normal(key[2], (S, 64, 128)).astype(jnp.bfloat16)
        w = jax.random.normal(key[3], (S, 64))
        # (``INDEX_SLICE`` is read while the program is traced)
        served = mla.INDEX_SLICE
        for run in (served,) if S == served else (128, served, S):
            mla.INDEX_SLICE = run
            out = timed(
                f"index_scores slice of {S} rows x {POS} keys, runs of "
                f"{min(run, S)} queries", jax.jit(
                    lambda q, w, idx, S=S: mla.index_scores(
                        q, w, idx, 3, POS + S - 1, CFG)), q, w, idx)
            if run == served:
                scores = out
        mla.INDEX_SLICE = served
        pos = POS + jnp.arange(S, dtype=jnp.int32)
        sel = timed(f"select_topk (threshold) {S} rows",
                    jax.jit(lambda s, p: mla.select_topk(s, p, TOPK)),
                    scores, pos)
        timed(f"lax.top_k {S} rows", jax.jit(
            lambda s: jax.lax.top_k(s, TOPK)), scores)
        qf = jax.random.normal(key[4], (128, S, 640)).astype(jnp.bfloat16)
        kw = dict(sm_scale=0.1, v_width=512, interpret=interpret)
        timed(f"slice kernel, dense, {S} rows x 128 heads", jax.jit(
            lambda q, lat: latent_attention_prefill(
                q, lat, 3, jnp.int32(POS), **kw)), qf, lat)
        timed(f"slice kernel, selection as a mask, {S} rows x 128 heads",
              jax.jit(lambda q, lat, sel: latent_attention_prefill(
                  q, lat, 3, jnp.int32(POS), sel=sel, **kw)), qf, lat, sel)
    # -- a decode step of 16 lanes ---------------------------------------------
    idxs = jnp.broadcast_to(idx, (LANES, *idx.shape))
    lats = jnp.broadcast_to(lat, (LANES, *lat.shape)) + 0
    q = jax.random.normal(key[5], (LANES, 1, 64, 128)).astype(jnp.bfloat16)
    w = jax.random.normal(key[6], (LANES, 1, 64))
    pos = POS + 37 * jnp.arange(LANES, dtype=jnp.int32)
    bound = jnp.max(pos)
    scores = timed("index_scores step of 16 lanes", jax.jit(jax.vmap(
        lambda q, w, idx: mla.index_scores(q, w, idx, 3, bound, CFG))),
        q, w, idxs)
    sel = timed("select_topk (threshold) step of 16 lanes", jax.jit(jax.vmap(
        lambda s, p: mla.select_topk(s, p[None], TOPK))), scores, pos)
    top = timed("lax.top_k step of 16 lanes", jax.jit(
        lambda s: jax.lax.top_k(s[:, 0], TOPK)[1]), scores)
    timed("gather of 2048 selected rows a lane (16 x 2048 x 640 bf16)",
          jax.jit(lambda lats, top: jnp.take_along_axis(
              lats[:, 3, 0], top[:, :, None], axis=1)), lats, top)
    qf = jax.random.normal(key[7], (LANES, 128, 640)).astype(jnp.bfloat16)
    row = jnp.zeros((LANES, 640), jnp.bfloat16)
    live = jnp.ones((LANES,), jnp.bool_)
    kw = dict(sm_scale=0.1, block_k=1024, v_width=512, interpret=interpret)

    # (the leaf is donated and handed back, as the decode chunk holds it:
    # without that a call copies the lanes' 2 GB leaf for its aliased result)
    def dense(lats, q, pos, live, row):
        return jax.vmap(lambda q, lat, p, lv, r: latent_attention_decode(
            q, lat, 3, p, lv, r, **kw))(q, lats, pos, live, row)[::-1]

    def masked(lats, q, pos, live, row, sel):
        return jax.vmap(lambda q, lat, p, lv, r, s: latent_attention_decode(
            q, lat, 3, p, lv, r, sel=s, **kw))(q, lats, pos, live, row,
                                               sel)[::-1]

    for what, fn, extra in (
            ("decode kernel, dense, 16 lanes x 128 heads", dense, ()),
            ("decode kernel, selection as a mask, 16 lanes x 128 heads",
             masked, (sel[:, 0],))):
        f = jax.jit(fn, donate_argnums=0)
        lats, _ = f(lats, qf, pos, live, row, *extra)
        jax.block_until_ready(lats)
        t0 = time.perf_counter()
        for _ in range(10):
            lats, ctx = f(lats, qf, pos, live, row, *extra)
        jax.block_until_ready(ctx)
        print(json.dumps({"what": what,
                          "ms": (time.perf_counter() - t0) / 10 * 1e3}),
              flush=True)


if __name__ == "__main__":
    main()
