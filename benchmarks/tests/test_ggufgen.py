"""The benchmark's GGUF writer against the program's reader, codecs and
tokenizer, and the benchmark's own dequantizers against the program's."""

import json
import os

import numpy as np
import pytest

import ggufgen
import reference
from conftest import BENCH

KINDS = ("Q4_K", "Q5_K", "Q6_K", "Q8_0", "F16")


def tiny_cfg(kind="Q4_K", down="Q6_K"):
    return {
        "name": "tiny-256", "hidden_size": 256, "intermediate_size": 512,
        "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "vocab_size": 19000,
        "max_position_embeddings": 128, "rms_norm_eps": 1e-5,
        "rope_theta": 10000.0, "sliding_window": None,
        "gguf": {"architecture": "llama", "weights_seed": 3, "tensor_types": {
            "token_embd": "F16", "attn_q": kind, "attn_k": kind,
            "attn_v": down, "attn_output": kind, "ffn_gate": kind,
            "ffn_up": kind, "ffn_down": down, "output": down}},
    }


@pytest.mark.parametrize("kind", KINDS)
def test_random_blocks_are_valid_zero_mean_and_scaled(kind):
    from llama_fastapi_k8s_gpu_tpu.gguf import GGMLType, quants

    n, std = 256 * 512, 4096 ** -0.5
    raw = ggufgen.random_blocks(np.random.default_rng(0), kind, n, std)
    assert raw.nbytes == ggufgen.tensor_nbytes(kind, n)
    mine = reference.dequantize(kind, raw, (n,))
    theirs = quants.dequantize(raw, GGMLType[kind], n).reshape(-1)
    np.testing.assert_allclose(mine, theirs, rtol=1e-6, atol=1e-9)
    assert np.isfinite(mine).all()
    assert abs(mine.mean()) < 0.05 * std
    assert 0.6 * std < mine.std() < 1.6 * std


def test_file_reads_back_through_the_programs_reader(tmp_path):
    from llama_fastapi_k8s_gpu_tpu.gguf import GGUFFile
    from llama_fastapi_k8s_gpu_tpu.models.config import ModelConfig

    cfg = tiny_cfg()
    path = str(tmp_path / "t.gguf")
    size = ggufgen.write_gguf(cfg, path)
    assert size == os.path.getsize(path)
    gf = GGUFFile(path)
    mc = ModelConfig.from_gguf(gf, n_ctx=64)
    assert (mc.dim, mc.n_layers, mc.n_heads, mc.n_kv_heads, mc.ffn_dim,
            mc.vocab_size) == (256, 2, 4, 2, 512, 19000)
    assert mc.rope_theta == 10000.0 and not mc.tie_embeddings
    plan = ggufgen.tensor_plan(cfg)
    assert set(gf.tensors) == {name for name, _, _ in plan}
    meta, tensors = reference.read_gguf(path)
    for name, shape, kind in plan:
        assert tensors[name][0] == shape and tensors[name][1] == kind
        assert tuple(reversed(gf.tensors[name].shape)) == shape  # ggml order
    # the same bytes for the same seed
    again = str(tmp_path / "u.gguf")
    ggufgen.write_gguf(cfg, again)
    assert open(path, "rb").read() == open(again, "rb").read()


def test_a_three_letter_word_is_one_token(tmp_path):
    from llama_fastapi_k8s_gpu_tpu.gguf import GGUFFile
    from llama_fastapi_k8s_gpu_tpu.tokenizer.loader import tokenizer_from_gguf

    path = str(tmp_path / "t.gguf")
    ggufgen.write_gguf(tiny_cfg(), path)
    tok = tokenizer_from_gguf(GGUFFile(path))
    words = [ggufgen.word(i * 997) for i in range(300)]
    ids = tok.encode(" ".join(words), add_bos=False)
    assert len(ids) == 300
    assert tok.decode(ids) == " ".join(words)
    assert len(set(ggufgen.word(i) for i in range(26 ** 3))) == 26 ** 3


def test_vocab_sizes():
    tokens, types, scores = ggufgen.synth_spm_vocab(32000)
    assert len(tokens) == len(types) == len(scores) == 32000
    assert len(set(tokens)) == 32000
    with pytest.raises(ValueError):
        ggufgen.synth_spm_vocab(1000)


@pytest.mark.parametrize("name", sorted(
    f[:-5] for f in os.listdir(os.path.join(BENCH, "configs"))))
def test_published_configs_plan(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        cfg = json.load(f)
    plan = ggufgen.tensor_plan(cfg)
    assert len(plan) == 9 * cfg["num_hidden_layers"] + 3
    total = sum(ggufgen.tensor_nbytes(k, int(np.prod(s))) for _, s, k in plan)
    assert 4.5e9 < total < 8e9
    assert cfg["reduced"] == []
