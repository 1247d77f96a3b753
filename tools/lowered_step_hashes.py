"""Hashes of the LOWERED text of the step programs (``lane_decode_chunk``,
``decode_chunk``, ``prefill_chunk``) of the tiny files of the standing
architectures, for telling whether a change to models/llama.py, its callers
or a cache kind moved a program that it should have left alone.

    git archive --prefix=.parent_check/ <parent> | tar x
    JAX_PLATFORMS=cpu python tools/lowered_step_hashes.py .parent_check > /tmp/parent.txt
    JAX_PLATFORMS=cpu python tools/lowered_step_hashes.py . > /tmp/change.txt
    diff /tmp/parent.txt /tmp/change.txt        # empty: the same programs

On the CPU the Pallas kernels lower in interpret form, as plain HLO with no
source locations (``as_text()`` without debug info), so two trees whose
programs are the same give the same text, whatever lines moved.  bf16 and
int8 rings, the XLA loop and the decode kernel (``attn_impl`` ``pallas``),
dense, routed, window + summaries, state + ring, conv-state + ring.  Needs
no chip; a minute a tree."""

from __future__ import annotations

import dataclasses
import hashlib
import os
import sys
import tempfile


def main(tree: str) -> int:
    sys.path.insert(0, os.path.abspath(tree))
    import jax
    import jax.numpy as jnp

    import llama_fastapi_k8s_gpu_tpu as pkg
    from llama_fastapi_k8s_gpu_tpu import testing as T
    from llama_fastapi_k8s_gpu_tpu.gguf import GGUFFile
    from llama_fastapi_k8s_gpu_tpu.models import generate
    from llama_fastapi_k8s_gpu_tpu.models.config import ModelConfig
    from llama_fastapi_k8s_gpu_tpu.models.params import load_params
    from llama_fastapi_k8s_gpu_tpu.parallel import batched
    from llama_fastapi_k8s_gpu_tpu.sampling.sample import (
        SamplingParams, sampling_tensors)

    assert os.path.realpath(pkg.__file__).startswith(
        os.path.realpath(tree)), pkg.__file__
    tmp = tempfile.mkdtemp()
    st = sampling_tensors(SamplingParams())
    lanes = 3
    lane_st = jax.tree.map(
        lambda x: jnp.broadcast_to(x, (lanes,) + jnp.shape(x)), st)
    for name, writer, fmt, n_ctx, rows in (
            ("llama", T.write_tiny_llama_gguf, "bf16", 128, 32),
            ("llama", T.write_tiny_llama_gguf, "int8", 128, 32),
            ("mistral", T.write_tiny_mistral_gguf, "bf16", 128, 32),
            ("olmoe", T.write_tiny_olmoe_gguf, "q4k", 128, 32),
            ("evabyte", T.write_tiny_evabyte_gguf, "bf16", 320, 64),
            ("sala", T.write_tiny_sala_gguf, "bf16", 256, 32),
            ("lfm2", T.write_tiny_lfm2_gguf, "q4k", 128, 32)):
        path = os.path.join(tmp, f"{name}-{fmt}.gguf")
        writer(path)
        gf = GGUFFile(path)
        cfg = ModelConfig.from_gguf(gf, n_ctx=n_ctx)
        params = load_params(gf, cfg, fmt=fmt)
        for kv, impl in (("bf16", "xla"), ("bf16", "pallas"),
                         ("int8", "xla")):
            if name in ("evabyte", "sala", "lfm2") and (kv, impl) != (
                    "bf16", "xla"):
                continue
            c = dataclasses.replace(cfg, kv_dtype=kv, attn_impl=impl)
            texts = {
                "lane_decode_chunk":
                batched.batched_generate_chunk_perlane_jit.__wrapped__.lower(
                    params, c, batched.init_batched_state(c, lanes), lane_st,
                    batched.init_lane_left(lanes), n_steps=4, top_k=40,
                    live=jnp.ones(lanes, bool), stop_ids=(2,)),
                "decode_chunk": generate.generate_chunk_jit.__wrapped__.lower(
                    params, c, generate.init_state(c), st, n_steps=4,
                    top_k=40),
                "prefill_chunk": generate.prefill_chunk_jit.__wrapped__.lower(
                    params, c, jnp.zeros(rows, jnp.int32), jnp.int32(0),
                    jnp.int32(5), generate.init_cache(c)),
            }
            for prog, low in texts.items():
                text = low.as_text()
                print(name, fmt, kv, impl, prog, len(text),
                      hashlib.sha256(text.encode()).hexdigest()[:16],
                      flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1 else "."))
