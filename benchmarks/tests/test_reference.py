"""The program's model code against the plain float32 reference, on the CPU
at a tiny size, on one GGUF file written by the benchmark's own writer."""

import numpy as np

import ggufgen
import reference
from test_ggufgen import tiny_cfg

# The program multiplies in bfloat16 (8 bits of mantissa: 2^-9 relative
# rounding per operand) and keeps activations in bfloat16 between layers;
# over two layers that reaches about 1 % of the logits' norm.  Computing a
# layer in a lower precision than that, or leaving a term out, lands far
# outside: one missing residual is tens of per cent.
TOLERANCE = 3e-2


def rel_l2(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def test_prefill_logits_agree_with_the_reference(tmp_path):
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.gguf import GGUFFile
    from llama_fastapi_k8s_gpu_tpu.models.config import ModelConfig
    from llama_fastapi_k8s_gpu_tpu.models.llama import forward, init_cache
    from llama_fastapi_k8s_gpu_tpu.models.params import load_params

    path = str(tmp_path / "t.gguf")
    ggufgen.write_gguf(tiny_cfg(), path)
    tokens = np.random.default_rng(0).integers(300, 18000, size=48)

    hp, w = reference.load_weights(path)
    want = np.asarray(reference.forward(hp, w, tokens))

    gf = GGUFFile(path)
    cfg = ModelConfig.from_gguf(gf, n_ctx=64)
    params = load_params(gf, cfg, fmt="bf16")
    got, _ = forward(params, cfg, jnp.asarray(tokens, jnp.int32),
                     jnp.int32(0), init_cache(cfg), return_all=True)
    got = np.asarray(got)
    assert got.shape == want.shape == (48, 19000)
    err = rel_l2(got, want)
    assert err < TOLERANCE, err
    # and the reference is not trivially insensitive: dropping the last
    # layer moves it by far more than the tolerance
    hp1 = {**hp, "n_layers": hp["n_layers"] - 1}
    assert rel_l2(np.asarray(reference.forward(hp1, w, tokens)), want) \
        > 10 * TOLERANCE


def test_prefill_then_decode_through_the_cache_agrees(tmp_path):
    import jax.numpy as jnp

    from llama_fastapi_k8s_gpu_tpu.gguf import GGUFFile
    from llama_fastapi_k8s_gpu_tpu.models.config import ModelConfig
    from llama_fastapi_k8s_gpu_tpu.models.llama import (
        decode_step, init_cache, prefill)
    from llama_fastapi_k8s_gpu_tpu.models.params import load_params

    path = str(tmp_path / "t.gguf")
    ggufgen.write_gguf(tiny_cfg(), path)
    tokens = np.random.default_rng(1).integers(300, 18000, size=24)
    hp, w = reference.load_weights(path)
    want = np.asarray(reference.forward(hp, w, tokens))

    gf = GGUFFile(path)
    cfg = ModelConfig.from_gguf(gf, n_ctx=64)
    params = load_params(gf, cfg, fmt="bf16")
    padded = np.zeros(32, np.int32)
    padded[:20] = tokens[:20]
    logits, cache = prefill(params, cfg, jnp.asarray(padded), jnp.int32(20),
                            init_cache(cfg))
    assert rel_l2(logits, want[19]) < TOLERANCE
    for pos in range(20, 24):
        logits, cache = decode_step(params, cfg,
                                    jnp.asarray(tokens[pos], jnp.int32),
                                    jnp.int32(pos), cache)
        assert rel_l2(logits, want[pos]) < TOLERANCE, pos
