"""Test/bench fixtures: tiny synthetic models and GGUF files.

No network egress exists in any deployment of this framework's CI or bench
(BASELINE.md), so every test artifact is synthesized: byte-level vocabularies
and random weights written through the real GGUF writer, then loaded through
the real reader/dequant/tokenizer/model path.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

from .gguf import GGMLType, GGUFWriter
from .models.config import ModelConfig
from .tokenizer.base import TokenType
from .tokenizer.bpe import bytes_to_unicode

TINY_CFG = ModelConfig(
    vocab_size=256 + 7, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
    ffn_dim=128, n_ctx=128, rope_theta=10000.0,
)

LLAMA3_SPECIALS = [
    "<|begin_of_text|>", "<|end_of_text|>", "<|start_header_id|>",
    "<|end_header_id|>", "<|eot_id|>", "<|python_tag|>", "<|eom_id|>",
]


def byte_vocab_with_specials() -> tuple[list[str], list[int]]:
    """256 byte tokens + llama-3 control tokens; ids stable and dense."""
    tokens = [bytes_to_unicode()[b] for b in range(256)] + list(LLAMA3_SPECIALS)
    types = [int(TokenType.NORMAL)] * 256 + [int(TokenType.CONTROL)] * len(LLAMA3_SPECIALS)
    return tokens, types


LLAMA3_CHAT_TEMPLATE = (
    "{{bos_token}}{% for m in messages %}<|start_header_id|>{{m['role']}}"
    "<|end_header_id|>\n\n{{m['content']}}<|eot_id|>{% endfor %}"
)


def write_llama_gguf_meta(
    w: GGUFWriter,
    cfg: ModelConfig,
    tokens: list[str],
    types: list[int],
    merges: list[str] | None = None,
    name: str = "tiny-llama-test",
    n_ctx: int | None = None,
    chat_template: str | None = LLAMA3_CHAT_TEMPLATE,
    arch: str = "llama",
) -> None:
    """The GGUF metadata block (hparams under ``<arch>.*`` + BPE tokenizer)
    shared by the tiny test fixtures and the full-size cold-start bench."""
    w.add_metadata("general.architecture", arch)
    w.add_metadata("general.name", name)
    w.add_metadata(f"{arch}.block_count", cfg.n_layers)
    w.add_metadata(f"{arch}.context_length", n_ctx or cfg.n_ctx)
    w.add_metadata(f"{arch}.embedding_length", cfg.dim)
    w.add_metadata(f"{arch}.feed_forward_length", cfg.ffn_dim)
    w.add_metadata(f"{arch}.attention.head_count", cfg.n_heads)
    w.add_metadata(f"{arch}.attention.head_count_kv", cfg.n_kv_heads)
    w.add_metadata(f"{arch}.attention.layer_norm_rms_epsilon", cfg.rms_eps)
    w.add_metadata(f"{arch}.rope.freq_base", cfg.rope_theta)
    w.add_metadata(f"{arch}.vocab_size", cfg.vocab_size)
    if cfg.sliding_window:
        w.add_metadata(f"{arch}.attention.sliding_window", cfg.sliding_window)
    if cfg.n_experts:
        w.add_metadata(f"{arch}.expert_count", cfg.n_experts)
        w.add_metadata(f"{arch}.expert_used_count", cfg.n_experts_used)
    w.add_metadata("tokenizer.ggml.model", "gpt2")
    w.add_metadata("tokenizer.ggml.pre", "llama-bpe")
    w.add_metadata("tokenizer.ggml.tokens", tokens)
    w.add_metadata("tokenizer.ggml.token_type", types)
    w.add_metadata("tokenizer.ggml.merges", list(merges or []))
    w.add_metadata("tokenizer.ggml.bos_token_id",
                   tokens.index("<|begin_of_text|>"))
    w.add_metadata("tokenizer.ggml.eos_token_id", tokens.index("<|eot_id|>"))
    if chat_template:
        w.add_metadata("tokenizer.chat_template", chat_template)


def write_tiny_llama_gguf(
    path: str,
    cfg: ModelConfig = TINY_CFG,
    seed: int = 0,
    quant: GGMLType = GGMLType.Q8_0,
    ffn_quant: GGMLType | None = None,
) -> ModelConfig:
    """Write a random-weight llama GGUF with a byte-level BPE tokenizer.

    vocab_size is forced to 256+len(specials) so every byte is encodable.
    """
    tokens, types = byte_vocab_with_specials()
    cfg = ModelConfig(**{**cfg.__dict__, "vocab_size": len(tokens)})
    rng = np.random.default_rng(seed)
    scale = cfg.dim ** -0.5

    w = GGUFWriter(path)
    write_llama_gguf_meta(w, cfg, tokens, types)

    if ffn_quant is None:
        ffn_quant = quant
    kv_dim = cfg.n_kv_heads * cfg.head_dim

    def t(name, shape, gtype):
        w.add_tensor(name, rng.standard_normal(shape).astype(np.float32) * scale, gtype)

    t("token_embd.weight", (cfg.vocab_size, cfg.dim), GGMLType.F16)
    for i in range(cfg.n_layers):
        p = f"blk.{i}."
        t(p + "attn_norm.weight", (cfg.dim,), GGMLType.F32)
        t(p + "attn_q.weight", (cfg.dim, cfg.dim), quant)
        t(p + "attn_k.weight", (kv_dim, cfg.dim), quant)
        t(p + "attn_v.weight", (kv_dim, cfg.dim), quant)
        t(p + "attn_output.weight", (cfg.dim, cfg.dim), quant)
        t(p + "ffn_norm.weight", (cfg.dim,), GGMLType.F32)
        t(p + "ffn_gate.weight", (cfg.ffn_dim, cfg.dim), ffn_quant)
        t(p + "ffn_up.weight", (cfg.ffn_dim, cfg.dim), ffn_quant)
        t(p + "ffn_down.weight", (cfg.dim, cfg.ffn_dim), ffn_quant)
    t("output_norm.weight", (cfg.dim,), GGMLType.F32)
    t("output.weight", (cfg.vocab_size, cfg.dim), GGMLType.F16)
    w.write()
    return cfg


TINY_OLMOE_CFG = ModelConfig(
    vocab_size=256 + 7, dim=256, n_layers=2, n_heads=4, n_kv_heads=4,
    ffn_dim=256, n_ctx=128, rope_theta=10000.0,
    n_experts=8, n_experts_used=2, qk_norm=True, rope_neox=True,
)

#: llama.cpp's Q4_K_M mix on an ``olmoe`` file, as the benchmark writes it
OLMOE_Q4KM_MIX = {
    "attn_q": GGMLType.Q4_K, "attn_k": GGMLType.Q4_K,
    "attn_v": GGMLType.Q6_K, "attn_output": GGMLType.Q4_K,
    "ffn_gate_exps": GGMLType.Q4_K, "ffn_up_exps": GGMLType.Q4_K,
    "ffn_down_exps": GGMLType.Q6_K, "output": GGMLType.Q6_K,
}


def write_tiny_olmoe_gguf(path: str, cfg: ModelConfig = TINY_OLMOE_CFG,
                          seed: int = 0, mix: dict | None = None,
                          router_scale: float = 4.0) -> ModelConfig:
    """Write a random-weight ``olmoe`` GGUF (the routed block: QK-norm, an
    F32 router, 3-D expert tensors) with the byte-level tokenizer of
    :func:`write_tiny_llama_gguf`.  ``mix`` maps tensor names to ggml types
    (default :data:`OLMOE_Q4KM_MIX`; embeddings F16, router and norms F32).
    The router's weights are ``router_scale`` times the others', so that
    picks rarely sit on a near-tie that rounding could flip."""
    tokens, types = byte_vocab_with_specials()
    cfg = ModelConfig(**{**cfg.__dict__, "vocab_size": len(tokens)})
    rng = np.random.default_rng(seed)
    scale = cfg.dim ** -0.5
    mix = {**OLMOE_Q4KM_MIX, **(mix or {})}
    w = GGUFWriter(path)
    write_llama_gguf_meta(w, cfg, tokens, types, name="tiny-olmoe-test",
                          arch="olmoe")
    kv_dim = cfg.n_kv_heads * cfg.head_dim
    E, F, D = cfg.n_experts, cfg.ffn_dim, cfg.dim

    def t(name, shape, gtype, mul=1.0):
        w.add_tensor(name, rng.standard_normal(shape).astype(np.float32)
                     * scale * mul, gtype)

    def norm(name, n):   # near one, not one: a norm that is skipped shows
        w.add_tensor(name, 1.0 + 0.1 * rng.standard_normal(n).astype(
            np.float32), GGMLType.F32)

    t("token_embd.weight", (cfg.vocab_size, D), GGMLType.F16)
    for i in range(cfg.n_layers):
        p = f"blk.{i}."
        norm(p + "attn_norm.weight", D)
        t(p + "attn_q.weight", (D, D), mix["attn_q"])
        t(p + "attn_k.weight", (kv_dim, D), mix["attn_k"])
        t(p + "attn_v.weight", (kv_dim, D), mix["attn_v"])
        t(p + "attn_output.weight", (D, D), mix["attn_output"])
        norm(p + "attn_q_norm.weight", D)
        norm(p + "attn_k_norm.weight", kv_dim)
        norm(p + "ffn_norm.weight", D)
        t(p + "ffn_gate_inp.weight", (E, D), GGMLType.F32, router_scale)
        t(p + "ffn_gate_exps.weight", (E, F, D), mix["ffn_gate_exps"])
        t(p + "ffn_up_exps.weight", (E, F, D), mix["ffn_up_exps"])
        t(p + "ffn_down_exps.weight", (E, D, F), mix["ffn_down_exps"])
    norm("output_norm.weight", D)
    t("output.weight", (cfg.vocab_size, D), mix["output"])
    w.write()
    return cfg


#: a tiny ``ouro`` file (layers that run several times; models/llama.py):
#: 2 layers x 3 passes (no 2 x 2 symmetry between the weight index and the
#: cache leaf), multi-head as published, sandwich norms, the exit gate
TINY_OURO_CFG = ModelConfig(
    vocab_size=256 + 7, dim=256, n_layers=2, n_heads=4, n_kv_heads=4,
    ffn_dim=512, n_ctx=256, rope_theta=1000000.0, rms_eps=1e-6,
    rope_neox=True, ut_steps=3, sandwich_norm=True,
)

#: llama.cpp's Q4_K_M mix on an ``ouro`` file, as the benchmark writes it
OURO_Q4KM_MIX = {
    "attn_q": GGMLType.Q4_K, "attn_k": GGMLType.Q4_K,
    "attn_v": GGMLType.Q6_K, "attn_output": GGMLType.Q4_K,
    "ffn_gate": GGMLType.Q4_K, "ffn_up": GGMLType.Q4_K,
    "ffn_down": GGMLType.Q6_K, "output": GGMLType.Q6_K,
}


def write_tiny_ouro_gguf(path: str, cfg: ModelConfig = TINY_OURO_CFG,
                         seed: int = 0, mix: dict | None = None,
                         exit_threshold: float | None = 1.0) -> ModelConfig:
    """Write a random-weight ``ouro`` GGUF (the dense block run
    ``cfg.ut_steps`` passes a token: ``post_attention_norm`` /
    ``post_ffw_norm``, the F32 exit gate and its bias) with the byte-level
    tokenizer of :func:`write_tiny_llama_gguf`.  ``mix`` maps tensor names
    to ggml types (default :data:`OURO_Q4KM_MIX`; embeddings F16, norms and
    the gate F32).  The norm gains are spread about one (0.6 to 1.4), each
    norm's its own, so that a norm left out, or another layer's taken in its
    place, moves the logits; Q/K rows are 1.5 times the others' and the
    embeddings of unit variance, so that attention looks somewhere and the
    stream carries the token (tests/test_dense_reference.py's file).
    ``exit_threshold``: the file's key (None:
    absent, which reads as 1.0)."""
    tokens, types = byte_vocab_with_specials()
    cfg = ModelConfig(**{**cfg.__dict__, "vocab_size": len(tokens)})
    rng = np.random.default_rng(seed)
    scale = cfg.dim ** -0.5
    mix = {**OURO_Q4KM_MIX, **(mix or {})}
    w = GGUFWriter(path)
    write_llama_gguf_meta(w, cfg, tokens, types, name="tiny-ouro-test",
                          arch="ouro")
    w.add_metadata("ouro.ut_steps", cfg.ut_steps)
    if exit_threshold is not None:
        w.add_metadata("ouro.early_exit_threshold", float(exit_threshold))
    w.add_metadata("ouro.attention.key_length", cfg.head_dim)
    kv_dim = cfg.n_kv_heads * cfg.head_dim
    F, D = cfg.ffn_dim, cfg.dim

    def t(name, shape, gtype, mul=1.0):
        w.add_tensor(name, rng.standard_normal(shape).astype(np.float32)
                     * scale * mul, gtype)

    def norm(name):
        w.add_tensor(name, rng.uniform(0.6, 1.4, D).astype(np.float32),
                     GGMLType.F32)

    t("token_embd.weight", (cfg.vocab_size, D), GGMLType.F16, D ** 0.5)
    for i in range(cfg.n_layers):
        p = f"blk.{i}."
        norm(p + "attn_norm.weight")
        t(p + "attn_q.weight", (D, D), mix["attn_q"], 1.5)
        t(p + "attn_k.weight", (kv_dim, D), mix["attn_k"], 1.5)
        t(p + "attn_v.weight", (kv_dim, D), mix["attn_v"])
        t(p + "attn_output.weight", (D, D), mix["attn_output"])
        norm(p + "post_attention_norm.weight")
        norm(p + "ffn_norm.weight")
        t(p + "ffn_gate.weight", (F, D), mix["ffn_gate"])
        t(p + "ffn_up.weight", (F, D), mix["ffn_up"])
        t(p + "ffn_down.weight", (D, F), mix["ffn_down"])
        norm(p + "post_ffw_norm.weight")
    norm("output_norm.weight")
    t("output.weight", (cfg.vocab_size, D), mix["output"])
    # a gate whose outputs lie well inside (0, 1) and differ by pass
    t("ut_exit_gate.weight", (1, D), GGMLType.F32, 2.0)
    w.add_tensor("ut_exit_gate.bias", np.array([-0.5], np.float32),
                 GGMLType.F32)
    w.write()
    return cfg


#: a tiny ``deepseek2`` file that keeps every ratio of the published block
#: (models/mla.py): 3 groups of 4 experts, 2 groups used, top-3, one shared
#: expert, 1 leading dense layer + 2 routed, d_nope / d_rope / d_v distinct,
#: YaRN on
TINY_MLA_CFG = ModelConfig(
    vocab_size=256 + 7, dim=256, n_layers=3, n_heads=4, n_kv_heads=4,
    ffn_dim=512, n_ctx=128, rope_theta=10000.0, rms_eps=1e-6,
    q_lora_rank=64, kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8,
    v_head_dim=24, rope_yarn_factor=4.0, rope_yarn_orig_ctx=32,
    attn_mscale=(0.1 * math.log(4.0) + 1.0) ** 2,
    n_dense_layers=1, expert_ffn_dim=256, n_shared_experts=1,
    n_experts=12, n_experts_used=3, norm_topk_prob=True,
    expert_gating="sigmoid", n_expert_groups=3, n_groups_used=2,
    expert_weights_scale=2.5,
)

#: the Q4_K_M mix on a ``deepseek2`` file, as the benchmark writes it (the
#: narrow-K matrices of the tiny file Q8_0: a K-quant block is 256 wide)
MLA_Q4KM_MIX = {
    "attn_q_a": GGMLType.Q4_K, "attn_q_b": GGMLType.Q8_0,
    "attn_kv_a_mqa": GGMLType.Q4_K, "attn_kv_b": GGMLType.Q8_0,
    "attn_output": GGMLType.Q8_0,
    "ffn_gate": GGMLType.Q4_K, "ffn_up": GGMLType.Q4_K,
    "ffn_down": GGMLType.Q6_K,
    "ffn_gate_exps": GGMLType.Q4_K, "ffn_up_exps": GGMLType.Q4_K,
    "ffn_down_exps": GGMLType.Q6_K,
    "ffn_gate_shexp": GGMLType.Q4_K, "ffn_up_shexp": GGMLType.Q4_K,
    "ffn_down_shexp": GGMLType.Q6_K, "output": GGMLType.Q6_K,
}


#: the tiny ``deepseek2`` file with a learned indexer (``deepseek32``:
#: models/mla.py): 4 indexer heads of 16 (the first 8 columns rotated), and
#: a selection of 16 positions, which bites from the 17th token on
TINY_DSA_CFG = dataclasses.replace(
    TINY_MLA_CFG, index_heads=4, index_dim=16, index_topk=16)


def write_tiny_mla_gguf(path: str, cfg: ModelConfig = TINY_MLA_CFG,
                        seed: int = 0, mix: dict | None = None,
                        held: tuple[int, int] | None = None,
                        router_scale: float = 4.0,
                        bias_scale: float = 0.2) -> ModelConfig:
    """Write a random-weight ``deepseek2`` GGUF (latent attention, leading
    dense layers, a grouped sigmoid router with its choice bias, routed +
    shared experts) with the byte-level tokenizer of
    :func:`write_tiny_llama_gguf`.  ``held`` = (first, count): the 3-D
    expert tensors hold those experts alone, of the SAME weights a file
    with all of them has (the share of an expert-parallel layer), and the
    file says so under ``expert_held_first`` / ``expert_held_count``.
    ``bias_scale``: the choice bias's spread, large enough that dropping it
    changes picks.  A ``cfg`` with an indexer (``index_topk``:
    :data:`TINY_DSA_CFG`) writes a ``deepseek32`` file: the same tensors
    from the same draws, then each layer's indexer tensors."""
    tokens, types = byte_vocab_with_specials()
    first, count = held or (0, 0)
    cfg = ModelConfig(**{**cfg.__dict__, "vocab_size": len(tokens),
                         "experts_first": first, "experts_held": count})
    rng = np.random.default_rng(seed)
    scale = cfg.dim ** -0.5
    mix = {**MLA_Q4KM_MIX, **(mix or {})}
    w = GGUFWriter(path)
    arch = "deepseek32" if cfg.index_topk else "deepseek2"
    write_llama_gguf_meta(w, cfg, tokens, types, name="tiny-mla-test",
                          arch=arch)
    if cfg.index_topk:
        for key, value in (("head_count", cfg.index_heads),
                           ("key_length", cfg.index_dim),
                           ("top_k", cfg.index_topk)):
            w.add_metadata(f"{arch}.attention.indexer.{key}", value)
    for key, value in (
            ("leading_dense_block_count", cfg.n_dense_layers),
            ("expert_feed_forward_length", cfg.expert_ffn_dim),
            ("expert_shared_count", cfg.n_shared_experts),
            ("expert_weights_scale", float(cfg.expert_weights_scale)),
            ("expert_weights_norm", bool(cfg.norm_topk_prob)),
            ("expert_gating_func",
             {"softmax": 1, "sigmoid": 2}[cfg.expert_gating]),
            ("expert_group_count", cfg.n_expert_groups),
            ("expert_group_used_count", cfg.n_groups_used),
            ("attention.q_lora_rank", cfg.q_lora_rank),
            ("attention.kv_lora_rank", cfg.kv_lora_rank),
            ("attention.key_length", cfg.qk_nope_dim + cfg.qk_rope_dim),
            ("attention.value_length", cfg.v_head_dim),
            ("rope.dimension_count", cfg.qk_rope_dim)):
        w.add_metadata(f"{arch}.{key}", value)
    if cfg.rope_yarn_factor:
        w.add_metadata(f"{arch}.rope.scaling.type", "yarn")
        w.add_metadata(f"{arch}.rope.scaling.factor",
                       float(cfg.rope_yarn_factor))
        w.add_metadata(f"{arch}.rope.scaling.original_context_length",
                       cfg.rope_yarn_orig_ctx)
        w.add_metadata(
            f"{arch}.rope.scaling.yarn_log_multiplier",
            float((math.sqrt(cfg.attn_mscale) - 1.0)
                  / math.log(cfg.rope_yarn_factor)))
        w.add_metadata(f"{arch}.rope.scaling.yarn_beta_fast",
                       float(cfg.rope_yarn_beta_fast))
        w.add_metadata(f"{arch}.rope.scaling.yarn_beta_slow",
                       float(cfg.rope_yarn_beta_slow))
    if held:
        w.add_metadata(f"{arch}.expert_held_first", first)
        w.add_metadata(f"{arch}.expert_held_count", count)
    D, H, E = cfg.dim, cfg.n_heads, cfg.n_experts
    r_q, r_kv = cfg.q_lora_rank, cfg.kv_lora_rank
    d_n, d_r, d_v = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    F, Fe = cfg.ffn_dim, cfg.expert_ffn_dim

    def t(name, shape, gtype, mul=1.0, rows=None):
        x = rng.standard_normal(shape).astype(np.float32) * scale * mul
        w.add_tensor(name, x if rows is None else x[rows], gtype)

    def norm(name, n):   # near one, not one: a norm that is skipped shows
        w.add_tensor(name, 1.0 + 0.1 * rng.standard_normal(n).astype(
            np.float32), GGMLType.F32)

    mine = slice(first, first + count) if held else None
    t("token_embd.weight", (cfg.vocab_size, D), GGMLType.F16)
    for i in range(cfg.n_layers):
        p = f"blk.{i}."
        norm(p + "attn_norm.weight", D)
        t(p + "attn_q_a.weight", (r_q, D), mix["attn_q_a"])
        norm(p + "attn_q_a_norm.weight", r_q)
        t(p + "attn_q_b.weight", (H * (d_n + d_r), r_q), mix["attn_q_b"],
          (D / r_q) ** 0.5)
        t(p + "attn_kv_a_mqa.weight", (r_kv + d_r, D), mix["attn_kv_a_mqa"])
        norm(p + "attn_kv_a_norm.weight", r_kv)
        t(p + "attn_kv_b.weight", (H * (d_n + d_v), r_kv), mix["attn_kv_b"],
          (D / r_kv) ** 0.5)
        t(p + "attn_output.weight", (D, H * d_v), mix["attn_output"])
        norm(p + "ffn_norm.weight", D)
        if i < cfg.n_dense_layers:
            t(p + "ffn_gate.weight", (F, D), mix["ffn_gate"])
            t(p + "ffn_up.weight", (F, D), mix["ffn_up"])
            t(p + "ffn_down.weight", (D, F), mix["ffn_down"])
            continue
        t(p + "ffn_gate_inp.weight", (E, D), GGMLType.F32, router_scale)
        w.add_tensor(p + "exp_probs_b.bias", bias_scale * rng.standard_normal(
            E).astype(np.float32), GGMLType.F32)
        t(p + "ffn_gate_exps.weight", (E, Fe, D), mix["ffn_gate_exps"],
          rows=mine)
        t(p + "ffn_up_exps.weight", (E, Fe, D), mix["ffn_up_exps"], rows=mine)
        t(p + "ffn_down_exps.weight", (E, D, Fe), mix["ffn_down_exps"],
          rows=mine)
        sh = Fe * cfg.n_shared_experts
        t(p + "ffn_gate_shexp.weight", (sh, D), mix["ffn_gate_shexp"])
        t(p + "ffn_up_shexp.weight", (sh, D), mix["ffn_up_shexp"])
        t(p + "ffn_down_shexp.weight", (D, sh), mix["ffn_down_shexp"])
    norm("output_norm.weight", D)
    t("output.weight", (cfg.vocab_size, D), mix["output"])
    # (after everything else: the other tensors are a ``deepseek2`` file's
    # of the same seed, draw for draw)
    for i in range(cfg.n_layers if cfg.index_topk else 0):
        p = f"blk.{i}."
        Hi, dI = cfg.index_heads, cfg.index_dim
        t(p + "indexer_q_b.weight", (Hi * dI, r_q), GGMLType.Q8_0,
          (D / r_q) ** 0.5)
        t(p + "indexer_k.weight", (dI, D), GGMLType.Q8_0)
        norm(p + "indexer_k_norm.weight", dI)
        w.add_tensor(p + "indexer_k_norm.bias", 0.1 * rng.standard_normal(
            dI).astype(np.float32), GGMLType.F32)
        # signed weights of order 1 a head: the scale is the program's
        t(p + "indexer_proj.weight", (Hi, D), GGMLType.F32, 4.0)
    w.write()
    return cfg


#: a tiny ``longcat-flash`` file that keeps every ratio of the published
#: block (models/mla.py ``shortcut_layer``): 2 layers of two sub-blocks, a
#: softmax router over 8 experts + 4 identity outputs, top-3, weights not
#: normalised and times 3, both ``mla_scale_*`` factors on, no rope scaling
TINY_LONGCAT_CFG = ModelConfig(
    vocab_size=256 + 7, dim=256, n_layers=2, n_heads=4, n_kv_heads=4,
    ffn_dim=512, n_ctx=128, rope_theta=10000.0, rms_eps=1e-5,
    q_lora_rank=64, kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8,
    v_head_dim=24, expert_ffn_dim=256, n_experts=8, n_experts_used=3,
    n_zero_experts=4, expert_gating="softmax", expert_weights_scale=3.0,
    attn_sublayers=2, q_latent_scale=2.0, kv_latent_scale=8.0 ** 0.5,
)


def write_tiny_longcat_gguf(path: str, cfg: ModelConfig = TINY_LONGCAT_CFG,
                            seed: int = 0, mix: dict | None = None,
                            held: tuple[int, int] | None = None,
                            router_scale: float = 4.0,
                            bias_scale: float | None = 0.05,
                            zero_type: str = "identity") -> ModelConfig:
    """Write a random-weight ``longcat-flash`` GGUF (gguf/constants.py) with
    the byte-level tokenizer of :func:`write_tiny_llama_gguf`.  ``held`` as
    :func:`write_tiny_mla_gguf`'s.  ``bias_scale``: the choice bias's spread
    (beside softmax scores of about 1/12 it moves picks); None leaves the
    tensor out of the file.  ``zero_type``: the file's
    ``expert_zero_type``."""
    tokens, types = byte_vocab_with_specials()
    first, count = held or (0, 0)
    cfg = ModelConfig(**{**cfg.__dict__, "vocab_size": len(tokens),
                         "experts_first": first, "experts_held": count})
    rng = np.random.default_rng(seed)
    scale = cfg.dim ** -0.5
    mix = {**MLA_Q4KM_MIX, **(mix or {})}
    w = GGUFWriter(path)
    arch = "longcat-flash"
    write_llama_gguf_meta(w, cfg, tokens, types, name="tiny-longcat-test",
                          arch=arch)
    for key, value in (
            ("expert_feed_forward_length", cfg.expert_ffn_dim),
            ("expert_weights_scale", float(cfg.expert_weights_scale)),
            ("expert_weights_norm", bool(cfg.norm_topk_prob)),
            ("expert_gating_func",
             {"softmax": 1, "sigmoid": 2}[cfg.expert_gating]),
            ("expert_zero_count", cfg.n_zero_experts),
            ("expert_zero_type", zero_type),
            ("attention.q_lora_rank", cfg.q_lora_rank),
            ("attention.kv_lora_rank", cfg.kv_lora_rank),
            ("attention.key_length", cfg.qk_nope_dim + cfg.qk_rope_dim),
            ("attention.value_length", cfg.v_head_dim),
            ("attention.scale_q_lora", cfg.q_latent_scale != 1.0),
            ("attention.scale_kv_lora", cfg.kv_latent_scale != 1.0),
            ("rope.dimension_count", cfg.qk_rope_dim)):
        w.add_metadata(f"{arch}.{key}", value)
    if held:
        w.add_metadata(f"{arch}.expert_held_first", first)
        w.add_metadata(f"{arch}.expert_held_count", count)
    D, H, E = cfg.dim, cfg.n_heads, cfg.n_experts
    r_q, r_kv = cfg.q_lora_rank, cfg.kv_lora_rank
    d_n, d_r, d_v = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    F, Fe, n_out = cfg.ffn_dim, cfg.expert_ffn_dim, E + cfg.n_zero_experts

    def t(name, shape, gtype, mul=1.0, rows=None):
        x = rng.standard_normal(shape).astype(np.float32) * scale * mul
        w.add_tensor(name, x if rows is None else x[rows], gtype)

    def norm(name, n):   # near one, not one: a norm that is skipped shows
        w.add_tensor(name, 1.0 + 0.1 * rng.standard_normal(n).astype(
            np.float32), GGMLType.F32)

    mine = slice(first, first + count) if held else None
    t("token_embd.weight", (cfg.vocab_size, D), GGMLType.F16)
    for i in range(cfg.n_layers):
        for s in (0, 1):
            p = f"blk.{i}.{s}."
            norm(p + "attn_norm.weight", D)
            t(p + "attn_q_a.weight", (r_q, D), mix["attn_q_a"])
            norm(p + "attn_q_a_norm.weight", r_q)
            t(p + "attn_q_b.weight", (H * (d_n + d_r), r_q), mix["attn_q_b"])
            t(p + "attn_kv_a_mqa.weight", (r_kv + d_r, D),
              mix["attn_kv_a_mqa"])
            norm(p + "attn_kv_a_norm.weight", r_kv)
            t(p + "attn_kv_b.weight", (H * (d_n + d_v), r_kv),
              mix["attn_kv_b"])
            t(p + "attn_output.weight", (D, H * d_v), mix["attn_output"])
            norm(p + "ffn_norm.weight", D)
            t(p + "ffn_gate.weight", (F, D), mix["ffn_gate"])
            t(p + "ffn_up.weight", (F, D), mix["ffn_up"])
            t(p + "ffn_down.weight", (D, F), mix["ffn_down"])
        p = f"blk.{i}."
        t(p + "ffn_gate_inp.weight", (n_out, D), GGMLType.F32, router_scale)
        bias = rng.standard_normal(n_out).astype(np.float32)  # drawn always
        if bias_scale is not None:
            w.add_tensor(p + "exp_probs_b.bias", bias_scale * bias,
                         GGMLType.F32)
        t(p + "ffn_gate_exps.weight", (E, Fe, D), mix["ffn_gate_exps"],
          rows=mine)
        t(p + "ffn_up_exps.weight", (E, Fe, D), mix["ffn_up_exps"], rows=mine)
        t(p + "ffn_down_exps.weight", (E, D, Fe), mix["ffn_down_exps"],
          rows=mine)
    norm("output_norm.weight", D)
    t("output.weight", (cfg.vocab_size, D), mix["output"])
    w.write()
    return cfg


#: a tiny ``exaone-moe`` file that keeps every ratio of the published
#: block (models/hybrid.py): window window window global x 2, a window of
#: 16 positions, 4 heads on 2 KV heads of 32 (heads x width is not the
#: hidden size), per-head QK-norm, 1 leading dense layer + 7 routed of 8
#: experts, top-3 in one group, sigmoid scores, one shared expert
TINY_HYBRID_CFG = ModelConfig(
    vocab_size=256 + 7, dim=256, n_layers=8, n_heads=4, n_kv_heads=2,
    ffn_dim=512, n_ctx=128, rope_theta=10000.0, rms_eps=1e-5,
    sliding_window=16, head_width=32, qk_norm_per_head=True, rope_neox=True,
    attn_kinds=("window", "window", "window", "global") * 2,
    rope_kinds=("window",),
    n_dense_layers=1, expert_ffn_dim=256, n_shared_experts=1,
    n_experts=8, n_experts_used=3, norm_topk_prob=True,
    expert_gating="sigmoid", n_expert_groups=1, n_groups_used=1,
    expert_weights_scale=2.5,
)

#: the Q4_K_M mix on an ``exaone-moe`` file, as the benchmark writes it
#: (``attn_output`` of the tiny file Q8_0: its K is 128, a K-quant block
#: is 256 wide)
HYBRID_Q4KM_MIX = {
    "attn_q": GGMLType.Q4_K, "attn_k": GGMLType.Q4_K,
    "attn_v": GGMLType.Q6_K, "attn_output": GGMLType.Q8_0,
    "ffn_gate": GGMLType.Q4_K, "ffn_up": GGMLType.Q4_K,
    "ffn_down": GGMLType.Q6_K,
    "ffn_gate_exps": GGMLType.Q4_K, "ffn_up_exps": GGMLType.Q4_K,
    "ffn_down_exps": GGMLType.Q6_K,
    "ffn_gate_shexp": GGMLType.Q4_K, "ffn_up_shexp": GGMLType.Q4_K,
    "ffn_down_shexp": GGMLType.Q6_K, "output": GGMLType.Q6_K,
}


def write_tiny_hybrid_gguf(path: str, cfg: ModelConfig = TINY_HYBRID_CFG,
                           seed: int = 0, mix: dict | None = None,
                           held: tuple[int, int] | None = None,
                           router_scale: float = 4.0,
                           bias_scale: float = 0.2) -> ModelConfig:
    """Write a random-weight ``exaone-moe`` GGUF (window and global layers
    by ``attention.sliding_window_pattern``, per-head QK-norm, leading dense
    layers, a sigmoid router with its choice bias, routed + shared experts)
    with the byte-level tokenizer of :func:`write_tiny_llama_gguf`.
    ``held`` as :func:`write_tiny_mla_gguf` has it."""
    tokens, types = byte_vocab_with_specials()
    first, count = held or (0, 0)
    cfg = ModelConfig(**{**cfg.__dict__, "vocab_size": len(tokens),
                         "experts_first": first, "experts_held": count})
    period = cfg.attn_kinds.index("global") + 1
    if cfg.attn_kinds != tuple(
            "global" if (i + 1) % period == 0 else "window"
            for i in range(cfg.n_layers)):
        raise ValueError("the file states its layer kinds as a period")
    rng = np.random.default_rng(seed)
    scale = cfg.dim ** -0.5
    mix = {**HYBRID_Q4KM_MIX, **(mix or {})}
    w = GGUFWriter(path)
    arch = "exaone-moe"
    write_llama_gguf_meta(w, cfg, tokens, types, name="tiny-hybrid-test",
                          arch=arch)
    for key, value in (
            ("attention.key_length", cfg.head_dim),
            ("attention.value_length", cfg.head_dim),
            ("attention.sliding_window_pattern", period),
            ("rope.dimension_count", cfg.head_dim),
            ("leading_dense_block_count", cfg.n_dense_layers),
            ("expert_feed_forward_length", cfg.expert_ffn_dim),
            ("expert_shared_count", cfg.n_shared_experts),
            ("expert_weights_scale", float(cfg.expert_weights_scale)),
            ("expert_weights_norm", bool(cfg.norm_topk_prob)),
            ("expert_gating_func",
             {"softmax": 1, "sigmoid": 2}[cfg.expert_gating]),
            ("expert_group_count", cfg.n_expert_groups),
            ("expert_group_used_count", cfg.n_groups_used)):
        w.add_metadata(f"{arch}.{key}", value)
    if held:
        w.add_metadata(f"{arch}.expert_held_first", first)
        w.add_metadata(f"{arch}.expert_held_count", count)
    D, E, hd = cfg.dim, cfg.n_experts, cfg.head_dim
    q_dim, kv_dim = cfg.n_heads * hd, cfg.n_kv_heads * hd
    F, Fe = cfg.ffn_dim, cfg.expert_ffn_dim

    def t(name, shape, gtype, mul=1.0, rows=None):
        x = rng.standard_normal(shape).astype(np.float32) * scale * mul
        w.add_tensor(name, x if rows is None else x[rows], gtype)

    def norm(name, n):   # near one, not one: a norm that is skipped shows
        w.add_tensor(name, 1.0 + 0.1 * rng.standard_normal(n).astype(
            np.float32), GGMLType.F32)

    mine = slice(first, first + count) if held else None
    t("token_embd.weight", (cfg.vocab_size, D), GGMLType.F16)
    for i in range(cfg.n_layers):
        p = f"blk.{i}."
        norm(p + "attn_norm.weight", D)
        t(p + "attn_q.weight", (q_dim, D), mix["attn_q"])
        t(p + "attn_k.weight", (kv_dim, D), mix["attn_k"])
        t(p + "attn_v.weight", (kv_dim, D), mix["attn_v"])
        norm(p + "attn_q_norm.weight", hd)
        norm(p + "attn_k_norm.weight", hd)
        t(p + "attn_output.weight", (D, q_dim), mix["attn_output"],
          (D / q_dim) ** 0.5)
        norm(p + "ffn_norm.weight", D)
        if i < cfg.n_dense_layers:
            t(p + "ffn_gate.weight", (F, D), mix["ffn_gate"])
            t(p + "ffn_up.weight", (F, D), mix["ffn_up"])
            t(p + "ffn_down.weight", (D, F), mix["ffn_down"])
            continue
        t(p + "ffn_gate_inp.weight", (E, D), GGMLType.F32, router_scale)
        w.add_tensor(p + "exp_probs_b.bias", bias_scale * rng.standard_normal(
            E).astype(np.float32), GGMLType.F32)
        t(p + "ffn_gate_exps.weight", (E, Fe, D), mix["ffn_gate_exps"],
          rows=mine)
        t(p + "ffn_up_exps.weight", (E, Fe, D), mix["ffn_up_exps"], rows=mine)
        t(p + "ffn_down_exps.weight", (E, D, Fe), mix["ffn_down_exps"],
          rows=mine)
        sh = Fe * cfg.n_shared_experts
        t(p + "ffn_gate_shexp.weight", (sh, D), mix["ffn_gate_shexp"])
        t(p + "ffn_up_shexp.weight", (sh, D), mix["ffn_up_shexp"])
        t(p + "ffn_down_shexp.weight", (D, sh), mix["ffn_down_shexp"])
    norm("output_norm.weight", D)
    t("output.weight", (cfg.vocab_size, D), mix["output"])
    w.write()
    return cfg


#: a tiny ``lfm2moe`` file (models/lfm2.py): two periods of conv conv attn
#: conv, 4 heads on 2 KV heads of 64 (two side by side in a row of the
#: ring), 3 taps, 2 leading dense layers + 6 routed of 8 experts, top-3,
#: sigmoid scores + a choice bias, no shared expert, the head tied
TINY_LFM2_CFG = ModelConfig(
    vocab_size=256 + 7, dim=256, n_layers=8, n_heads=4, n_kv_heads=2,
    ffn_dim=512, n_ctx=256, rope_theta=1e6, rms_eps=1e-5,
    head_width=64, qk_norm_per_head=True, rope_neox=True,
    mixers=("conv", "conv", "attn", "conv") * 2, conv_l_cache=3,
    n_dense_layers=2, expert_ffn_dim=256, n_experts=8, n_experts_used=3,
    norm_topk_prob=True, expert_gating="sigmoid", expert_weights_eps=1e-6,
    tie_embeddings=True,
)

#: the Q4_K_M mix on a ``lfm2moe`` file, as the benchmark writes it
LFM2_Q4KM_MIX = {
    "shortconv.in_proj": GGMLType.Q4_K, "shortconv.out_proj": GGMLType.Q4_K,
    "attn_q": GGMLType.Q4_K, "attn_k": GGMLType.Q4_K,
    "attn_v": GGMLType.Q6_K, "attn_output": GGMLType.Q4_K,
    "ffn_gate": GGMLType.Q4_K, "ffn_up": GGMLType.Q4_K,
    "ffn_down": GGMLType.Q6_K,
    "ffn_gate_exps": GGMLType.Q4_K, "ffn_up_exps": GGMLType.Q4_K,
    "ffn_down_exps": GGMLType.Q6_K,
}


def write_lfm2_meta(w: GGUFWriter, cfg: ModelConfig) -> None:
    """The ``lfm2moe`` keys beside :func:`write_llama_gguf_meta`'s (whose
    one KV-head count this replaces by the array, 0 in a conv layer)."""
    arch = "lfm2moe"
    key = f"{arch}.attention.head_count_kv"
    w.metadata = [m for m in w.metadata if m[0] != key]
    w.add_metadata(key, [cfg.n_kv_heads if m == "attn" else 0
                         for m in cfg.mixers])
    for key, value in (
            ("shortconv.l_cache", cfg.conv_l_cache),
            ("attention.key_length", cfg.head_dim),
            ("attention.value_length", cfg.head_dim),
            ("rope.dimension_count", cfg.head_dim),
            ("leading_dense_block_count", cfg.n_dense_layers),
            ("expert_feed_forward_length", cfg.expert_ffn_dim),
            ("expert_weights_scale", float(cfg.expert_weights_scale)),
            ("expert_weights_norm", bool(cfg.norm_topk_prob)),
            ("expert_gating_func",
             {"softmax": 1, "sigmoid": 2}[cfg.expert_gating])):
        w.add_metadata(f"{arch}.{key}", value)


def write_tiny_lfm2_gguf(path: str, cfg: ModelConfig = TINY_LFM2_CFG,
                         seed: int = 0, mix: dict | None = None,
                         router_scale: float = 4.0,
                         bias_scale: float = 0.2) -> ModelConfig:
    """Write a random-weight ``lfm2moe`` GGUF (conv and attention layers by
    the per-layer KV-head array, F32 depthwise taps, per-head QK-norm,
    leading dense layers, a sigmoid router with its choice bias, no shared
    expert, no ``output.weight``) with the byte-level tokenizer of
    :func:`write_tiny_llama_gguf`."""
    tokens, types = byte_vocab_with_specials()
    cfg = ModelConfig(**{**cfg.__dict__, "vocab_size": len(tokens)})
    rng = np.random.default_rng(seed)
    scale = cfg.dim ** -0.5
    mix = {**LFM2_Q4KM_MIX, **(mix or {})}
    w = GGUFWriter(path)
    write_llama_gguf_meta(w, cfg, tokens, types, name="tiny-lfm2-test",
                          arch="lfm2moe")
    write_lfm2_meta(w, cfg)
    D, E, hd, L = cfg.dim, cfg.n_experts, cfg.head_dim, cfg.conv_l_cache
    q_dim, kv_dim = cfg.n_heads * hd, cfg.n_kv_heads * hd
    F, Fe = cfg.ffn_dim, cfg.expert_ffn_dim

    def t(name, shape, gtype, mul=1.0):
        x = rng.standard_normal(shape).astype(np.float32) * scale * mul
        w.add_tensor(name, x, gtype)

    def norm(name, n):   # near one, not one: a norm that is skipped shows
        w.add_tensor(name, 1.0 + 0.1 * rng.standard_normal(n).astype(
            np.float32), GGMLType.F32)

    t("token_embd.weight", (cfg.vocab_size, D), GGMLType.F16)
    for i, mixer in enumerate(cfg.mixers):
        p = f"blk.{i}."
        norm(p + "attn_norm.weight", D)
        if mixer == "conv":
            # b * x is a product of two unit-size projections: larger ones
            # keep the branch the size of the others
            t(p + "shortconv.in_proj.weight", (3 * D, D),
              mix["shortconv.in_proj"], 2.0)
            w.add_tensor(p + "shortconv.conv.weight", (
                rng.standard_normal((D, L)) * L ** -0.5).astype(np.float32),
                GGMLType.F32)
            t(p + "shortconv.out_proj.weight", (D, D),
              mix["shortconv.out_proj"])
        else:
            t(p + "attn_q.weight", (q_dim, D), mix["attn_q"])
            t(p + "attn_k.weight", (kv_dim, D), mix["attn_k"])
            t(p + "attn_v.weight", (kv_dim, D), mix["attn_v"])
            norm(p + "attn_q_norm.weight", hd)
            norm(p + "attn_k_norm.weight", hd)
            t(p + "attn_output.weight", (D, q_dim), mix["attn_output"],
              (D / q_dim) ** 0.5)
        norm(p + "ffn_norm.weight", D)
        if i < cfg.n_dense_layers:
            t(p + "ffn_gate.weight", (F, D), mix["ffn_gate"])
            t(p + "ffn_up.weight", (F, D), mix["ffn_up"])
            t(p + "ffn_down.weight", (D, F), mix["ffn_down"])
            continue
        t(p + "ffn_gate_inp.weight", (E, D), GGMLType.F32, router_scale)
        w.add_tensor(p + "exp_probs_b.bias", bias_scale * rng.standard_normal(
            E).astype(np.float32), GGMLType.F32)
        t(p + "ffn_gate_exps.weight", (E, Fe, D), mix["ffn_gate_exps"])
        t(p + "ffn_up_exps.weight", (E, Fe, D), mix["ffn_up_exps"])
        t(p + "ffn_down_exps.weight", (E, D, Fe), mix["ffn_down_exps"])
    norm("token_embd_norm.weight", D)
    w.write()
    return cfg


def _write_ssm_tensors(w: GGUFWriter, rng, p: str, cfg: ModelConfig,
                       mix: dict, values: str, inner_norms: bool = False,
                       dt_mul: float = 1.0) -> None:
    """One Mamba-1 layer's tensors under llama.cpp's names (``blk.N.`` =
    ``p``), as :func:`write_tiny_phi4flash_gguf` and
    :func:`write_tiny_jamba_gguf` write them (``values``: see the former).
    ``inner_norms``: the ``jamba`` family's RMSNorms on dt, B and C, drawn
    with a WIDE spread (one that is skipped, or applied with another's
    weight, moves the branch); ``dt_mul`` scales ``ssm_dt.weight`` (dt is
    unit-size after its norm)."""
    D, scale = cfg.dim, cfg.dim ** -0.5
    C, N, L, R = (cfg.ssm_d_inner, cfg.ssm_d_state, cfg.ssm_d_conv,
                  cfg.ssm_dt_rank)

    def t(name, shape, gtype=GGMLType.F32, mul=1.0):
        x = rng.standard_normal(shape).astype(np.float32) * scale * mul
        w.add_tensor(name, x, gtype)

    t(p + "ssm_in.weight", (2 * C, D), mix["ssm_in"])
    w.add_tensor(p + "ssm_conv1d.weight", (
        rng.standard_normal((C, L)) * L ** -0.5).astype(np.float32),
        GGMLType.F32)
    t(p + "ssm_conv1d.bias", (C,))
    t(p + "ssm_x.weight", (R + 2 * N, C), mix["ssm_x"]
      if C % 256 == 0 else GGMLType.F16, (D / C) ** 0.5)
    if inner_norms:
        for name, n in (("dt", R), ("b", N), ("c", N)):
            w.add_tensor(p + f"ssm_{name}_norm.weight",
                         1.0 + 0.3 * rng.standard_normal(n).astype(
                             np.float32), GGMLType.F32)
    t(p + "ssm_dt.weight", (C, R), mul=(D / R) ** 0.5 * dt_mul)
    noise = 0.1 * rng.standard_normal((C, N)).astype(np.float32)
    dt_noise = 0.1 * rng.standard_normal(C).astype(np.float32)
    if values == "stored":
        a = -np.exp(np.log(np.arange(1, N + 1, dtype=np.float32))[None]
                    + noise)
        dt0 = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), C))
        b_dt = (dt0 + np.log(-np.expm1(-dt0))).astype(np.float32)
    else:
        a, b_dt = noise, dt_noise
    w.add_tensor(p + "ssm_a", a.astype(np.float32), GGMLType.F32)
    w.add_tensor(p + "ssm_dt.bias", b_dt, GGMLType.F32)
    w.add_tensor(p + "ssm_d", (1.0 + 0.1 * rng.standard_normal(C)
                               ).astype(np.float32), GGMLType.F32)
    t(p + "ssm_out.weight", (D, C), mix["ssm_out"], (D / C) ** 0.5)


#: a tiny ``phi4flash`` file (models/phi4flash.py) with every layer kind:
#: two (ssm, window) pairs, the (ssm, full) pair, two (gmu, cross) pairs;
#: 4 heads on 2 KV heads of 64 (one pair of each), a window of 8 positions
#: in 16 slots, 512 channels of 4 states, 4 taps, the head tied
TINY_PHI4FLASH_CFG = ModelConfig(
    vocab_size=256 + 7, dim=256, n_layers=10, n_heads=4, n_kv_heads=2,
    ffn_dim=512, n_ctx=256, rms_eps=1e-5, head_width=64, sliding_window=8,
    mixers=("ssm", "window") * 2 + ("ssm", "full") + ("gmu", "cross") * 2,
    ssm_d_inner=512, ssm_d_state=4, ssm_d_conv=4, ssm_dt_rank=16,
    tie_embeddings=True,
)

#: the Q4_K_M mix on a ``phi4flash`` file, as the benchmark writes it
PHI4FLASH_Q4KM_MIX = {
    "token_embd": GGMLType.Q6_K, "ssm_in": GGMLType.Q4_K,
    "ssm_x": GGMLType.Q4_K, "ssm_out": GGMLType.Q4_K,
    "attn_q": GGMLType.Q4_K, "attn_k": GGMLType.Q4_K,
    "attn_v": GGMLType.Q6_K, "attn_output": GGMLType.Q4_K,
    "gmu_in": GGMLType.Q4_K, "gmu_out": GGMLType.Q4_K,
    "ffn_gate": GGMLType.Q4_K, "ffn_up": GGMLType.Q4_K,
    "ffn_down": GGMLType.Q6_K,
}


def write_tiny_phi4flash_gguf(path: str,
                              cfg: ModelConfig = TINY_PHI4FLASH_CFG,
                              seed: int = 0, mix: dict | None = None,
                              values: str = "stored") -> ModelConfig:
    """Write a random-weight ``phi4flash`` GGUF (llama.cpp's Mamba tensor
    names, LayerNorms with biases, projection biases, the differential
    form's lambdas, no ``output.weight``) with the byte-level tokenizer of
    :func:`write_tiny_llama_gguf`.  ``values``: ``stored`` writes ``ssm_a``
    as A itself (-(1..d_state) and noise) and ``ssm_dt.bias`` for step
    sizes of 1e-3..1e-1; ``init_offsets`` small random offsets, as the
    benchmark's writer would (models/params.py ``ssm_values``)."""
    tokens, types = byte_vocab_with_specials()
    cfg = ModelConfig(**{**cfg.__dict__, "vocab_size": len(tokens)})
    rng = np.random.default_rng(seed)
    scale = cfg.dim ** -0.5
    mix = {**PHI4FLASH_Q4KM_MIX, **(mix or {})}
    arch = "phi4flash"
    w = GGUFWriter(path)
    write_llama_gguf_meta(w, cfg, tokens, types, name="tiny-phi4flash-test",
                          arch=arch)
    for key, value in (
            ("mixer_types", ",".join(cfg.mixers)),
            ("attention.key_length", cfg.head_dim),
            ("ssm.conv_kernel", cfg.ssm_d_conv),
            ("ssm.inner_size", cfg.ssm_d_inner),
            ("ssm.state_size", cfg.ssm_d_state),
            ("ssm.time_step_rank", cfg.ssm_dt_rank),
            ("ssm.values", values)):
        w.add_metadata(f"{arch}.{key}", value)
    D, hd, F = cfg.dim, cfg.head_dim, cfg.ffn_dim
    C = cfg.ssm_d_inner
    q_dim, kv_dim = cfg.n_heads * hd, cfg.n_kv_heads * hd

    def t(name, shape, gtype=GGMLType.F32, mul=1.0):
        x = rng.standard_normal(shape).astype(np.float32) * scale * mul
        w.add_tensor(name, x, gtype)

    def norm(name, n):   # near one, not one; a bias that is skipped shows
        w.add_tensor(name + ".weight", 1.0 + 0.1 * rng.standard_normal(
            n).astype(np.float32), GGMLType.F32)
        w.add_tensor(name + ".bias", 0.1 * rng.standard_normal(n).astype(
            np.float32), GGMLType.F32)

    def attention(p, cross):
        t(p + "attn_q.weight", (q_dim, D), mix["attn_q"])
        t(p + "attn_q.bias", (q_dim,))
        if not cross:
            t(p + "attn_k.weight", (kv_dim, D), mix["attn_k"])
            t(p + "attn_k.bias", (kv_dim,))
            t(p + "attn_v.weight", (kv_dim, D), mix["attn_v"])
            t(p + "attn_v.bias", (kv_dim,))
        for k in ("q1", "k1", "q2", "k2"):     # the published init: N(0, 0.1)
            w.add_tensor(p + f"attn_lambda_{k}", 0.1 * rng.standard_normal(
                hd).astype(np.float32), GGMLType.F32)
        w.add_tensor(p + "attn_sub_norm.weight", 1.0 + 0.1 * rng
                     .standard_normal(2 * hd).astype(np.float32), GGMLType.F32)
        t(p + "attn_output.weight", (D, q_dim), mix["attn_output"])
        t(p + "attn_output.bias", (D,))

    t("token_embd.weight", (cfg.vocab_size, D), mix["token_embd"]
      if (cfg.vocab_size * D) % 256 == 0 else GGMLType.F16)
    for i, mixer in enumerate(cfg.mixers):
        p = f"blk.{i}."
        norm(p + "attn_norm", D)
        if mixer == "ssm":
            _write_ssm_tensors(w, rng, p, cfg, mix, values)
        elif mixer == "gmu":
            t(p + "gmu_in.weight", (C, D), mix["gmu_in"])
            t(p + "gmu_out.weight", (D, C), mix["gmu_out"], (D / C) ** 0.5)
        else:
            attention(p, mixer == "cross")
        norm(p + "ffn_norm", D)
        t(p + "ffn_gate.weight", (F, D), mix["ffn_gate"])
        t(p + "ffn_up.weight", (F, D), mix["ffn_up"])
        t(p + "ffn_down.weight", (D, F), mix["ffn_down"])
    norm("output_norm", D)
    w.write()
    return cfg

#: a tiny ``jamba`` file (models/jamba.py): the period cut to 5 (offset 2), so
#: that scan runs lie on both sides of an attention layer and between two
#: (ssm ssm attn ssm ssm | ssm ssm attn ssm); 5 query heads of 128 on ONE KV
#: head (a group that is no multiple of 8), 512 channels of 4 states, 4 taps,
#: a dt rank of 16, the head tied
TINY_JAMBA_CFG = ModelConfig(
    vocab_size=256 + 7, dim=256, n_layers=9, n_heads=5, n_kv_heads=1,
    ffn_dim=512, n_ctx=256, rms_eps=1e-6, head_width=128,
    mixers=("ssm", "ssm", "attn", "ssm", "ssm") + ("ssm", "ssm", "attn",
                                                    "ssm"),
    ssm_d_inner=512, ssm_d_state=4, ssm_d_conv=4, ssm_dt_rank=16,
    ssm_inner_norms=True, tie_embeddings=True,
)

#: the Q4_K_M mix on a ``jamba`` file, as the benchmark writes it
JAMBA_Q4KM_MIX = {
    "token_embd": GGMLType.Q6_K, "ssm_in": GGMLType.Q4_K,
    "ssm_x": GGMLType.Q4_K, "ssm_out": GGMLType.Q4_K,
    "attn_q": GGMLType.Q4_K, "attn_k": GGMLType.Q4_K,
    "attn_v": GGMLType.Q6_K, "attn_output": GGMLType.Q4_K,
    "ffn_gate": GGMLType.Q4_K, "ffn_up": GGMLType.Q4_K,
    "ffn_down": GGMLType.Q6_K,
}


def write_tiny_jamba_gguf(path: str, cfg: ModelConfig = TINY_JAMBA_CFG,
                          seed: int = 0, mix: dict | None = None,
                          values: str = "stored") -> ModelConfig:
    """Write a random-weight ``jamba`` GGUF (llama.cpp's Mamba tensor names
    and the family's ``ssm_{dt,b,c}_norm``, RMSNorms near one, the per-layer
    KV-head array with 0 in a scan layer, no biases but the conv's and
    ``ssm_dt``'s, no ``output.weight``) with the byte-level tokenizer of
    :func:`write_tiny_llama_gguf`.  ``values`` as
    :func:`write_tiny_phi4flash_gguf`'s."""
    tokens, types = byte_vocab_with_specials()
    cfg = ModelConfig(**{**cfg.__dict__, "vocab_size": len(tokens)})
    rng = np.random.default_rng(seed)
    scale = cfg.dim ** -0.5
    mix = {**JAMBA_Q4KM_MIX, **(mix or {})}
    arch = "jamba"
    w = GGUFWriter(path)
    write_llama_gguf_meta(w, cfg, tokens, types, name="tiny-jamba-test",
                          arch=arch)
    key = f"{arch}.attention.head_count_kv"
    w.metadata = [m for m in w.metadata if m[0] != key]
    w.add_metadata(key, [cfg.n_kv_heads if m == "attn" else 0
                         for m in cfg.mixers])
    for key, value in (
            ("attention.key_length", cfg.head_dim),
            ("attention.value_length", cfg.head_dim),
            ("ssm.conv_kernel", cfg.ssm_d_conv),
            ("ssm.inner_size", cfg.ssm_d_inner),
            ("ssm.state_size", cfg.ssm_d_state),
            ("ssm.time_step_rank", cfg.ssm_dt_rank),
            ("ssm.values", values)):
        w.add_metadata(f"{arch}.{key}", value)
    D, hd, F = cfg.dim, cfg.head_dim, cfg.ffn_dim
    q_dim, kv_dim = cfg.n_heads * hd, cfg.n_kv_heads * hd

    def t(name, shape, gtype=GGMLType.F32, mul=1.0):
        x = rng.standard_normal(shape).astype(np.float32) * scale * mul
        w.add_tensor(name, x, gtype)

    def norm(name, n):   # near one, not one: a norm that is skipped shows
        w.add_tensor(name + ".weight", 1.0 + 0.1 * rng.standard_normal(
            n).astype(np.float32), GGMLType.F32)

    t("token_embd.weight", (cfg.vocab_size, D), mix["token_embd"]
      if (cfg.vocab_size * D) % 256 == 0 else GGMLType.F16)
    for i, mixer in enumerate(cfg.mixers):
        p = f"blk.{i}."
        norm(p + "attn_norm", D)
        if mixer == "ssm":
            _write_ssm_tensors(w, rng, p, cfg, mix, values, inner_norms=True,
                               dt_mul=0.5)
        else:
            t(p + "attn_q.weight", (q_dim, D), mix["attn_q"])
            t(p + "attn_k.weight", (kv_dim, D), mix["attn_k"])
            t(p + "attn_v.weight", (kv_dim, D), mix["attn_v"])
            t(p + "attn_output.weight", (D, q_dim), mix["attn_output"],
              (D / q_dim) ** 0.5)
        norm(p + "ffn_norm", D)
        t(p + "ffn_gate.weight", (F, D), mix["ffn_gate"])
        t(p + "ffn_up.weight", (F, D), mix["ffn_up"])
        t(p + "ffn_down.weight", (D, F), mix["ffn_down"])
    norm("output_norm", D)
    w.write()
    return cfg


def synth_bpe_vocab(n_merges: int = 280_000, seed: int = 0,
                    ) -> tuple[list[str], list[str], list[int]]:
    """Deterministic Llama-3-*scale* BPE vocab: 256 byte tokens + specials +
    ``n_merges`` merge rules (~the real 128k-token / 280k-merge table's order
    of magnitude, which the reference's tokenizer runs through llama.cpp —
    reference api.py:56-57).  Returns (tokens, merges, token_types).

    Construction (all seeded, no I/O):
    - a *doubling chain* over "ab" (ab, abab, ...·2) so a long unbroken
      letter run exercises ~log-depth cascading merges — the shape that made
      the round-2 O(n²)-per-merge loop a latency cliff;
    - all 26² lowercase pairs, then seeded random concatenations of existing
      tokens (capped length) until ``n_merges`` rules exist.
    """
    rng = np.random.default_rng(seed)
    b2u = bytes_to_unicode()
    base = [b2u[b] for b in range(256)]
    tokens: list[str] = list(base)
    token_set = set(tokens)
    pair_set: set[tuple[str, str]] = set()
    merges: list[str] = []

    def add_merge(left: str, right: str) -> None:
        if (left, right) in pair_set:
            return
        pair_set.add((left, right))
        merges.append(f"{left} {right}")
        merged = left + right
        if merged not in token_set:
            token_set.add(merged)
            tokens.append(merged)

    cur = "ab"
    add_merge("a", "b")
    while len(cur) < 8192:
        add_merge(cur, cur)
        cur += cur
    for a in "abcdefghijklmnopqrstuvwxyz":
        for b in "abcdefghijklmnopqrstuvwxyz":
            add_merge(a, b)
    # bulk: seeded random concatenations of existing tokens (drawn from the
    # earlier/shorter end so chains stay plausible), capped length
    while len(merges) < n_merges:
        n_tok = len(tokens)
        li = rng.integers(0, min(n_tok, 60_000), size=4096)
        ri = rng.integers(0, min(n_tok, 60_000), size=4096)
        for i, j in zip(li, ri):
            left, right = tokens[int(i)], tokens[int(j)]
            if len(left) + len(right) > 24:
                continue
            add_merge(left, right)
            if len(merges) >= n_merges:
                break
    tokens.extend(LLAMA3_SPECIALS)
    types = [int(TokenType.NORMAL)] * (len(tokens) - len(LLAMA3_SPECIALS)) \
        + [int(TokenType.CONTROL)] * len(LLAMA3_SPECIALS)
    return tokens, merges, types


def rand_q4k_blocks(rng, n_elem: int) -> np.ndarray:
    """Valid random Q4_K block bytes (layout per gguf/quants.py: f16 d |
    f16 dmin | 12B packed scale/min | 128B nibbles), zero-mean: every
    sub-block's 6-bit min equals its scale and ``dmin = 7.5 d``, so a
    weight is ``d * sc * (q - 7.5)`` with q uniform in 0..15 and a std of
    about ``dim ** -0.5`` at dim 4096.  Activations then stay in the range
    a trained model's do, and logits can be compared across formats."""
    nb = n_elem // 256
    blk = np.empty((nb, 144), dtype=np.uint8)
    blk[:, 0:2] = np.full(nb, 1.5e-4, np.float16).view(np.uint8).reshape(nb, 2)
    blk[:, 2:4] = np.full(nb, 7.5 * 1.5e-4, np.float16).view(np.uint8).reshape(nb, 2)
    sc = rng.integers(0, 64, (nb, 4), dtype=np.uint8)   # sub-blocks 0-3
    lo = rng.integers(0, 16, (nb, 4), dtype=np.uint8)   # sub-blocks 4-7
    blk[:, 4:8] = sc                                    # scales 0-3
    blk[:, 8:12] = sc                                   # mins 0-3
    blk[:, 12:16] = lo | (lo << 4)                      # scale | min << 4
    blk[:, 16:144] = rng.integers(0, 256, (nb, 128), dtype=np.uint8)
    return blk.reshape(-1)


def rand_q6k_blocks(rng, n_elem: int) -> np.ndarray:
    """Valid random Q6_K block bytes (128B ql | 64B qh | 16×i8 scales |
    f16 d): ``d * sc * (q - 32)``, zero-mean, std about ``dim ** -0.5``."""
    nb = n_elem // 256
    blk = np.empty((nb, 210), dtype=np.uint8)
    blk[:, 0:192] = rng.integers(0, 256, (nb, 192), dtype=np.uint8)
    blk[:, 192:208] = rng.integers(1, 4, (nb, 16), dtype=np.uint8)
    blk[:, 208:210] = np.full(nb, 4e-4, np.float16).view(np.uint8).reshape(nb, 2)
    return blk.reshape(-1)


def write_llama3_8b_q4km_gguf(path: str, n_layers: int | None = None,
                              seed: int = 0) -> ModelConfig:
    """Write a Llama-3-8B GGUF at full width with the tensor mix of
    llama.cpp's Q4_K_M files (Q4_K attn/ffn, Q6_K attn_v + ffn_down +
    output, F16 embeddings), random weights from ``seed``, and a
    Llama-3-scale BPE vocabulary.  ``n_layers`` cuts depth only.  Pure
    numpy: safe in a process that never touches a device."""
    import dataclasses

    from .models.config import LLAMA3_8B

    cfg = dataclasses.replace(LLAMA3_8B, n_layers=n_layers or LLAMA3_8B.n_layers)
    rng = np.random.default_rng(seed)
    tokens, merges, types = synth_bpe_vocab(n_merges=280_000)
    # pad/trim to the exact 8B vocab so tensor shapes are authentic
    specials = tokens[-7:]
    need = cfg.vocab_size - len(specials)
    body = tokens[:-7]
    body = (body + [f"<pad{i}>" for i in range(need - len(body))])[:need]
    tokens = body + specials
    types = [1] * need + [3] * len(specials)
    w = GGUFWriter(path)
    write_llama_gguf_meta(w, cfg, tokens, types, merges=merges,
                          name="llama3-8b-synthetic-q4km", n_ctx=8192)
    kv_dim = cfg.n_kv_heads * cfg.head_dim

    def raw(name, shape, kind):
        # `shape` is numpy order (out, in); GGUF tensor shapes are
        # innermost-first, which is what add_raw_tensor stores verbatim
        n = int(np.prod(shape))
        if kind == GGMLType.Q4_K:
            data = rand_q4k_blocks(rng, n)
        elif kind == GGMLType.Q6_K:
            data = rand_q6k_blocks(rng, n)
        else:  # F16
            data = (rng.standard_normal(n, dtype=np.float32)
                    * cfg.dim ** -0.5).astype(np.float16).view(np.uint8)
        w.add_raw_tensor(name, tuple(reversed(shape)), kind, data)

    def f32(name, shape):
        w.add_tensor(name, np.ones(shape, np.float32), GGMLType.F32)

    raw("token_embd.weight", (cfg.vocab_size, cfg.dim), GGMLType.F16)
    for i in range(cfg.n_layers):
        p = f"blk.{i}."
        f32(p + "attn_norm.weight", (cfg.dim,))
        raw(p + "attn_q.weight", (cfg.dim, cfg.dim), GGMLType.Q4_K)
        raw(p + "attn_k.weight", (kv_dim, cfg.dim), GGMLType.Q4_K)
        raw(p + "attn_v.weight", (kv_dim, cfg.dim), GGMLType.Q6_K)
        raw(p + "attn_output.weight", (cfg.dim, cfg.dim), GGMLType.Q4_K)
        f32(p + "ffn_norm.weight", (cfg.dim,))
        raw(p + "ffn_gate.weight", (cfg.ffn_dim, cfg.dim), GGMLType.Q4_K)
        raw(p + "ffn_up.weight", (cfg.ffn_dim, cfg.dim), GGMLType.Q4_K)
        raw(p + "ffn_down.weight", (cfg.dim, cfg.ffn_dim), GGMLType.Q6_K)
    f32("output_norm.weight", (cfg.dim,))
    raw("output.weight", (cfg.vocab_size, cfg.dim), GGMLType.Q6_K)
    w.write()
    return cfg


TINY_EVABYTE_CFG = ModelConfig(
    vocab_size=64 + 256, dim=128, n_layers=3, n_heads=4, n_kv_heads=4,
    ffn_dim=192, n_ctx=320, rope_theta=100000.0, rope_neox=True,
    eva_window=64, eva_chunk=4, n_pred_heads=2, fp32_residual=True,
)

#: the type mix of the benchmark's ``evabyte`` file (llama.cpp's Q4_K_M
#: recipe; the embeddings and the prediction heads stay F16)
EVABYTE_Q4KM_MIX = {
    "attn_q": GGMLType.Q4_K, "attn_k": GGMLType.Q4_K,
    "attn_v": GGMLType.Q6_K, "attn_output": GGMLType.Q4_K,
    "ffn_gate": GGMLType.Q4_K, "ffn_up": GGMLType.Q4_K,
    "ffn_down": GGMLType.Q6_K,
}

EVABYTE_CONTROL = 64      # control tokens before the 256 byte tokens


def evabyte_vocab(n_control: int = EVABYTE_CONTROL
                  ) -> tuple[list[str], list[int]]:
    """``n_control`` control tokens (``<pad>``, ``<s>``, ``</s>``, then
    placeholders), then one BYTE token per byte value: the ``bytes``
    vocabulary of tokenizer/bytes.py."""
    named = ["<pad>", "<s>", "</s>"]
    tokens = named + [f"<unused_{i}>" for i in range(len(named), n_control)]
    tokens += [f"<0x{b:02X}>" for b in range(256)]
    types = [int(TokenType.CONTROL)] * n_control + [int(TokenType.BYTE)] * 256
    return tokens, types


MISTRAL_CHAT_TEMPLATE = (
    "{{bos_token}}{% for m in messages %}{% if m['role'] == 'user' %}"
    "[INST] {{m['content']}} [/INST]{% else %}{{m['content']}}</s>"
    "{% endif %}{% endfor %}")


def write_tiny_evabyte_gguf(path: str, cfg: ModelConfig = TINY_EVABYTE_CFG,
                            seed: int = 0, quant: GGMLType = GGMLType.F16,
                            mix: dict | None = None,
                            pool_scale: float = 8.0,
                            embed_scale: float = 1.0) -> ModelConfig:
    """Write a random-weight ``evabyte`` GGUF: the dense block over the
    window + summary cache (``<arch>.attention.window_size`` /
    ``chunk_size``), the two F32 pooling vectors a layer
    (``attn_eva_phi`` / ``attn_eva_mu``, (n_heads, head_dim)),
    ``<arch>.prediction_heads`` heads in one F16 output matrix, and the
    ``bytes`` vocabulary.  Every matrix is ``quant`` unless ``mix`` names
    its type (:data:`EVABYTE_Q4KM_MIX`).  The pooling vectors are
    ``pool_scale`` times a unit normal's ``head_dim ** -0.5``, so that the
    weights inside a chunk are far from uniform and ``mu`` is no rounding
    error: a program that drops either shows.  ``embed_scale`` multiplies
    the embedding table: a residual stream that is large beside what a
    layer adds to it is where a bfloat16 stream loses the additions, which
    is what the float32 one (``fp32_skip_add``) is for."""
    tokens, types = evabyte_vocab(cfg.vocab_size - 256)
    rng = np.random.default_rng(seed)
    scale = cfg.dim ** -0.5
    arch = "evabyte"
    w = GGUFWriter(path)
    w.add_metadata("general.architecture", arch)
    w.add_metadata("general.name", "tiny-evabyte-test")
    w.add_metadata(f"{arch}.block_count", cfg.n_layers)
    w.add_metadata(f"{arch}.context_length", cfg.n_ctx)
    w.add_metadata(f"{arch}.embedding_length", cfg.dim)
    w.add_metadata(f"{arch}.feed_forward_length", cfg.ffn_dim)
    w.add_metadata(f"{arch}.attention.head_count", cfg.n_heads)
    w.add_metadata(f"{arch}.attention.head_count_kv", cfg.n_kv_heads)
    w.add_metadata(f"{arch}.attention.layer_norm_rms_epsilon", cfg.rms_eps)
    w.add_metadata(f"{arch}.rope.freq_base", cfg.rope_theta)
    w.add_metadata(f"{arch}.vocab_size", cfg.vocab_size)
    w.add_metadata(f"{arch}.attention.window_size", cfg.eva_window)
    w.add_metadata(f"{arch}.attention.chunk_size", cfg.eva_chunk)
    w.add_metadata(f"{arch}.prediction_heads", cfg.n_pred_heads)
    w.add_metadata("tokenizer.ggml.model", "bytes")
    w.add_metadata("tokenizer.ggml.tokens", tokens)
    w.add_metadata("tokenizer.ggml.token_type", types)
    w.add_metadata("tokenizer.ggml.bos_token_id", tokens.index("<s>"))
    w.add_metadata("tokenizer.ggml.eos_token_id", tokens.index("</s>"))
    w.add_metadata("tokenizer.chat_template", MISTRAL_CHAT_TEMPLATE)
    mix = mix or {}
    D, F, hd = cfg.dim, cfg.ffn_dim, cfg.head_dim

    def t(name, shape, gtype=None, mul=scale):
        short = name.split(".")[-2]
        w.add_tensor(name, rng.standard_normal(shape).astype(np.float32)
                     * mul, mix.get(short, quant) if gtype is None else gtype)

    def norm(name, n):   # (1 + g), stored as applied; near one, not one
        w.add_tensor(name, 1.0 + 0.1 * rng.standard_normal(n).astype(
            np.float32), GGMLType.F32)

    t("token_embd.weight", (cfg.vocab_size, D), GGMLType.F16,
      scale * embed_scale)
    for i in range(cfg.n_layers):
        p = f"blk.{i}."
        norm(p + "attn_norm.weight", D)
        t(p + "attn_q.weight", (D, D))
        t(p + "attn_k.weight", (D, D))
        t(p + "attn_v.weight", (D, D))
        t(p + "attn_output.weight", (D, D))
        for name in ("attn_eva_phi", "attn_eva_mu"):
            t(p + name + ".weight", (cfg.n_heads, hd), GGMLType.F32,
              pool_scale * hd ** -0.5)
        norm(p + "ffn_norm.weight", D)
        t(p + "ffn_gate.weight", (F, D))
        t(p + "ffn_up.weight", (F, D))
        t(p + "ffn_down.weight", (D, F))
    norm("output_norm.weight", D)
    t("output.weight", (cfg.vocab_size * cfg.n_pred_heads, D), GGMLType.F16)
    w.write()
    return cfg


#: a tiny ``minicpm-sala`` stack: sparse layers adjacent and at both ends,
#: 2 KV heads under 4 query heads, constants small enough that a sequence of
#: a hundred positions crosses ``dense_len``, closes many compressed keys and
#: leaves blocks unselected
TINY_SALA_CFG = ModelConfig(
    vocab_size=4 + 256, dim=128, n_layers=8, n_heads=4, n_kv_heads=2,
    ffn_dim=192, n_ctx=256, rope_theta=10000.0, rms_eps=1e-6, rope_neox=True,
    mixers=("sp", "lin", "lin", "sp", "sp", "lin", "lin", "sp"),
    lin_heads=4, emb_scale=12.0, residual_scale=1.4 / 8 ** 0.5,
    logit_scale=0.5, fp32_logits=True,
    sp_kernel=4, sp_stride=2, sp_block=8, sp_topk=3, sp_window=16,
    sp_init_blocks=1, sp_dense_len=48,
)

SALA_MIXER_NAMES = {"sp": "minicpm4", "lin": "lightning-attn"}

#: the type mix of the benchmark's ``minicpm-sala`` file (llama.cpp's
#: Q4_K_M recipe; the gate as the other square projections; embeddings and
#: the head stay F16)
SALA_Q4KM_MIX = {
    "attn_q": GGMLType.Q4_K, "attn_k": GGMLType.Q4_K,
    "attn_v": GGMLType.Q6_K, "attn_output": GGMLType.Q4_K,
    "attn_gate": GGMLType.Q4_K,
    "ffn_gate": GGMLType.Q4_K, "ffn_up": GGMLType.Q4_K,
    "ffn_down": GGMLType.Q6_K,
}


def write_tiny_sala_gguf(path: str, cfg: ModelConfig = TINY_SALA_CFG,
                         seed: int = 0, quant: GGMLType = GGMLType.F16,
                         mix: dict | None = None,
                         qk_scale: float = 1.0) -> ModelConfig:
    """Write a random-weight ``minicpm-sala`` GGUF (models/sala.py): the
    mixer of each layer as ``<arch>.mixer_types``, the family's three
    scalars, the sparse layers' constants, per kind its tensors
    (``attn_gate`` in both, ``attn_out_norm`` in the linear layers; K and V
    of ``n_kv_heads`` heads in the sparse ones), an F16 head and the
    SentencePiece byte vocabulary.  Every matrix is ``quant`` unless
    ``mix`` names its type (:data:`SALA_Q4KM_MIX`).  ``qk_scale``
    multiplies the Q/K norm gains of the sparse layers: above 1 the
    compressed-key scores are far from uniform, so that a selection is a
    property of the weights and not of rounding."""
    tokens, types, scores = spm_byte_vocab()
    rng = np.random.default_rng(seed)
    scale = cfg.dim ** -0.5
    arch = "minicpm-sala"
    w = GGUFWriter(path)
    w.add_metadata("general.architecture", arch)
    w.add_metadata("general.name", "tiny-sala-test")
    w.add_metadata(f"{arch}.block_count", cfg.n_layers)
    w.add_metadata(f"{arch}.context_length", cfg.n_ctx)
    w.add_metadata(f"{arch}.embedding_length", cfg.dim)
    w.add_metadata(f"{arch}.feed_forward_length", cfg.ffn_dim)
    w.add_metadata(f"{arch}.attention.head_count", cfg.n_heads)
    w.add_metadata(f"{arch}.attention.head_count_kv", cfg.n_kv_heads)
    w.add_metadata(f"{arch}.attention.layer_norm_rms_epsilon", cfg.rms_eps)
    w.add_metadata(f"{arch}.rope.freq_base", cfg.rope_theta)
    w.add_metadata(f"{arch}.vocab_size", cfg.vocab_size)
    w.add_metadata(f"{arch}.mixer_types",
                   ",".join(SALA_MIXER_NAMES[m] for m in cfg.mixers))
    w.add_metadata(f"{arch}.lightning.head_count", cfg.lin_heads)
    w.add_metadata(f"{arch}.embedding_scale", cfg.emb_scale)
    w.add_metadata(f"{arch}.residual_scale", cfg.residual_scale)
    w.add_metadata(f"{arch}.logit_scale", cfg.logit_scale)
    for key, field in (("kernel_size", "kernel"), ("kernel_stride", "stride"),
                       ("block_size", "block"), ("topk", "topk"),
                       ("window_size", "window"),
                       ("init_blocks", "init_blocks"),
                       ("dense_len", "dense_len")):
        w.add_metadata(f"{arch}.sparse.{key}", getattr(cfg, f"sp_{field}"))
    w.add_metadata("tokenizer.ggml.model", "llama")
    w.add_metadata("tokenizer.ggml.tokens", tokens)
    w.add_metadata("tokenizer.ggml.token_type", types)
    w.add_metadata("tokenizer.ggml.scores", scores)
    w.add_metadata("tokenizer.ggml.bos_token_id", 1)
    w.add_metadata("tokenizer.ggml.eos_token_id", 2)
    w.add_metadata("tokenizer.ggml.add_bos_token", True)
    w.add_metadata("tokenizer.chat_template", MISTRAL_CHAT_TEMPLATE)
    mix = mix or {}
    D, F, hd = cfg.dim, cfg.ffn_dim, cfg.head_dim

    def t(name, shape, gtype=None):
        short = name.split(".")[-2]
        w.add_tensor(name, rng.standard_normal(shape).astype(np.float32)
                     * scale, mix.get(short, quant) if gtype is None
                     else gtype)

    def norm(name, n, mul=1.0):   # near one, not one
        w.add_tensor(name, mul * (1.0 + 0.1 * rng.standard_normal(n).astype(
            np.float32)), GGMLType.F32)

    t("token_embd.weight", (cfg.vocab_size, D), GGMLType.F16)
    for i, kind in enumerate(cfg.mixers):
        p = f"blk.{i}."
        kv = D if kind == "lin" else cfg.n_kv_heads * hd
        norm(p + "attn_norm.weight", D)
        t(p + "attn_q.weight", (D, D))
        t(p + "attn_k.weight", (kv, D))
        t(p + "attn_v.weight", (kv, D))
        t(p + "attn_output.weight", (D, D))
        t(p + "attn_gate.weight", (D, D))
        sharp = qk_scale if kind == "sp" else 1.0
        norm(p + "attn_q_norm.weight", hd, sharp)
        norm(p + "attn_k_norm.weight", hd, sharp)
        if kind == "lin":
            norm(p + "attn_out_norm.weight", hd)
        norm(p + "ffn_norm.weight", D)
        t(p + "ffn_gate.weight", (F, D))
        t(p + "ffn_up.weight", (F, D))
        t(p + "ffn_down.weight", (D, F))
    norm("output_norm.weight", D)
    t("output.weight", (cfg.vocab_size, D), GGMLType.F16)
    w.write()
    return cfg


def spm_byte_vocab() -> tuple[list[str], list[int], list[float]]:
    """Minimal SentencePiece-style vocab: specials + full byte fallback."""
    tokens = ["<unk>", "<s>", "</s>", "▁"]
    types = [int(TokenType.UNKNOWN)] + [int(TokenType.CONTROL)] * 2 + [
        int(TokenType.NORMAL)]
    scores = [0.0, 0.0, 0.0, -1.0]
    for b in range(256):
        tokens.append(f"<0x{b:02X}>")
        types.append(int(TokenType.BYTE))
        scores.append(0.0)
    return tokens, types, scores


def write_tiny_mistral_gguf(
    path: str,
    cfg: ModelConfig | None = None,
    seed: int = 0,
    quant: GGMLType = GGMLType.Q8_0,
) -> ModelConfig:
    """Random-weight **mistral**-architecture GGUF: SPM tokenizer with byte
    fallback, sliding-window attention, [INST] chat template — the
    reference-baseline "Mistral-7B sliding-window" config (BASELINE.json)
    at test scale."""
    tokens, types, scores = spm_byte_vocab()
    base = cfg or TINY_CFG
    cfg = ModelConfig(**{**base.__dict__, "vocab_size": len(tokens),
                         "sliding_window": base.sliding_window or 16})
    rng = np.random.default_rng(seed)
    scale = cfg.dim ** -0.5

    w = GGUFWriter(path)
    w.add_metadata("general.architecture", "mistral")
    w.add_metadata("general.name", "tiny-mistral-test")
    w.add_metadata("mistral.block_count", cfg.n_layers)
    w.add_metadata("mistral.context_length", cfg.n_ctx)
    w.add_metadata("mistral.embedding_length", cfg.dim)
    w.add_metadata("mistral.feed_forward_length", cfg.ffn_dim)
    w.add_metadata("mistral.attention.head_count", cfg.n_heads)
    w.add_metadata("mistral.attention.head_count_kv", cfg.n_kv_heads)
    w.add_metadata("mistral.attention.layer_norm_rms_epsilon", cfg.rms_eps)
    w.add_metadata("mistral.attention.sliding_window", cfg.sliding_window)
    w.add_metadata("mistral.rope.freq_base", cfg.rope_theta)
    w.add_metadata("mistral.vocab_size", cfg.vocab_size)
    w.add_metadata("tokenizer.ggml.model", "llama")
    w.add_metadata("tokenizer.ggml.tokens", tokens)
    w.add_metadata("tokenizer.ggml.token_type", types)
    w.add_metadata("tokenizer.ggml.scores", scores)
    w.add_metadata("tokenizer.ggml.bos_token_id", 1)
    w.add_metadata("tokenizer.ggml.eos_token_id", 2)
    w.add_metadata("tokenizer.chat_template", MISTRAL_CHAT_TEMPLATE)

    kv_dim = cfg.n_kv_heads * cfg.head_dim

    def t(name, shape, gtype):
        w.add_tensor(name, rng.standard_normal(shape).astype(np.float32) * scale, gtype)

    t("token_embd.weight", (cfg.vocab_size, cfg.dim), GGMLType.F16)
    for i in range(cfg.n_layers):
        p = f"blk.{i}."
        t(p + "attn_norm.weight", (cfg.dim,), GGMLType.F32)
        t(p + "attn_q.weight", (cfg.dim, cfg.dim), quant)
        t(p + "attn_k.weight", (kv_dim, cfg.dim), quant)
        t(p + "attn_v.weight", (kv_dim, cfg.dim), quant)
        t(p + "attn_output.weight", (cfg.dim, cfg.dim), quant)
        t(p + "ffn_norm.weight", (cfg.dim,), GGMLType.F32)
        t(p + "ffn_gate.weight", (cfg.ffn_dim, cfg.dim), quant)
        t(p + "ffn_up.weight", (cfg.ffn_dim, cfg.dim), quant)
        t(p + "ffn_down.weight", (cfg.dim, cfg.ffn_dim), quant)
    t("output_norm.weight", (cfg.dim,), GGMLType.F32)
    t("output.weight", (cfg.vocab_size, cfg.dim), GGMLType.F16)
    w.write()
    return cfg
