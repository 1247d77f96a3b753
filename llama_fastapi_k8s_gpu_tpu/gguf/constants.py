"""GGUF container + GGML quant-type constants.

The reference consumes GGUF files through the opaque native engine
(``llama_cpp.Llama(model_path=...)``, reference api.py:24-28, pulling
``*Q4_K_M.gguf`` artifacts — reference api.py:14,
helm/templates/deployment.yaml:32).  This module pins the file-format contract
that the in-tree TPU engine implements instead.

Layouts follow the public GGUF spec (ggml-org/ggml docs/gguf.md) and the GGML
quantization block formats; values are the on-disk wire constants.
"""

from __future__ import annotations

import enum

GGUF_MAGIC = 0x46554747  # b"GGUF" little-endian
GGUF_VERSION = 3
GGUF_DEFAULT_ALIGNMENT = 32

QK_K = 256  # K-quant super-block size
QK8_0 = 32
QK4_0 = 32
QK5_0 = 32


class GGUFValueType(enum.IntEnum):
    UINT8 = 0
    INT8 = 1
    UINT16 = 2
    INT16 = 3
    UINT32 = 4
    INT32 = 5
    FLOAT32 = 6
    BOOL = 7
    STRING = 8
    ARRAY = 9
    UINT64 = 10
    INT64 = 11
    FLOAT64 = 12


# struct format for each scalar metadata value type (shared by reader/writer)
GGUF_SCALAR_FMT = {
    GGUFValueType.UINT8: "<B",
    GGUFValueType.INT8: "<b",
    GGUFValueType.UINT16: "<H",
    GGUFValueType.INT16: "<h",
    GGUFValueType.UINT32: "<I",
    GGUFValueType.INT32: "<i",
    GGUFValueType.FLOAT32: "<f",
    GGUFValueType.UINT64: "<Q",
    GGUFValueType.INT64: "<q",
    GGUFValueType.FLOAT64: "<d",
}


class GGMLType(enum.IntEnum):
    F32 = 0
    F16 = 1
    Q4_0 = 2
    Q4_1 = 3
    Q5_0 = 6
    Q5_1 = 7
    Q8_0 = 8
    Q8_1 = 9
    Q2_K = 10
    Q3_K = 11
    Q4_K = 12
    Q5_K = 13
    Q6_K = 14
    Q8_K = 15
    IQ4_NL = 20
    IQ4_XS = 23
    I8 = 24
    I16 = 25
    I32 = 26
    I64 = 27
    F64 = 28
    BF16 = 30


# (elements per block, bytes per block)
GGML_BLOCK_SIZES: dict[GGMLType, tuple[int, int]] = {
    GGMLType.F32: (1, 4),
    GGMLType.F16: (1, 2),
    GGMLType.BF16: (1, 2),
    GGMLType.I8: (1, 1),
    GGMLType.I16: (1, 2),
    GGMLType.I32: (1, 4),
    GGMLType.I64: (1, 8),
    GGMLType.F64: (1, 8),
    GGMLType.Q4_0: (QK4_0, 2 + 16),
    GGMLType.Q4_1: (QK4_0, 2 + 2 + 16),
    GGMLType.Q5_0: (QK5_0, 2 + 4 + 16),
    GGMLType.Q5_1: (QK5_0, 2 + 2 + 4 + 16),
    GGMLType.Q8_0: (QK8_0, 2 + 32),
    GGMLType.Q2_K: (QK_K, QK_K // 16 + QK_K // 4 + 2 + 2),
    GGMLType.Q3_K: (QK_K, QK_K // 8 + QK_K // 4 + 12 + 2),
    GGMLType.Q4_K: (QK_K, 2 + 2 + 12 + QK_K // 2),
    GGMLType.Q5_K: (QK_K, 2 + 2 + 12 + QK_K // 8 + QK_K // 2),
    GGMLType.Q6_K: (QK_K, QK_K // 2 + QK_K // 4 + QK_K // 16 + 2),
    GGMLType.IQ4_NL: (32, 2 + 16),
    GGMLType.IQ4_XS: (QK_K, 2 + 2 + QK_K // 64 + QK_K // 2),
}


#: ``general.architecture`` values whose block of layers the program
#: computes (models/llama.py): ``llama``/``mistral`` are the dense block
#: (GQA attention + SwiGLU), ``olmoe`` the routed block (QK-norm over the
#: whole projection, a float32 router over ``<arch>.expert_count`` SwiGLU
#: experts stacked in 3-D ``ffn_*_exps`` tensors, ``expert_used_count`` of
#: them per token, their probabilities unnormalised); ``evabyte`` is the dense
#: block over the second cache kind (models/eva.py): multi-head attention
#: over an exact window of ``<arch>.attention.window_size`` positions plus
#: one summary per ``<arch>.attention.chunk_size`` positions of every
#: earlier window, pooled with the two F32 tensors ``blk.N.attn_eva_phi`` /
#: ``attn_eva_mu`` (n_heads, head_dim) (this repo's names: llama.cpp has
#: none), a float32 residual stream, and an output matrix of ``vocab_size *
#: <arch>.prediction_heads`` rows.  A unit-offset norm gain is stored as
#: applied (1 + g), as llama.cpp's converters store them.
#: ``minicpm-sala`` (this repo's name: llama.cpp has none) is a stack of TWO
#: layer kinds in the order ``<arch>.mixer_types`` gives (a comma-separated
#: string of ``minicpm4`` | ``lightning-attn``, one per layer;
#: models/sala.py): linear-attention layers that keep a decaying float32
#: state per head (``blk.N.attn_{q,k,v,output,gate}``, ``attn_{q,k}_norm``
#: and ``attn_out_norm`` of head width; ``<arch>.lightning.head_count``),
#: and block-sparse attention layers on a ring of ``attention.head_count_kv``
#: heads with no rotation (the same names without ``attn_out_norm``; the
#: ``<arch>.sparse.*`` constants); the MiniCPM family's three scalars under
#: llama.cpp's keys for it, ``<arch>.embedding_scale``, ``residual_scale``
#: (scale_depth / sqrt(layers), as applied) and ``logit_scale`` (dim_model_base
#: / hidden_size, as applied to the final norm's output).
#: ``deepseek2`` (llama.cpp's name for the DeepSeek-V2/V3 family;
#: models/mla.py) is latent attention over the fourth cache kind and a
#: feed-forward kind per layer.  Tensors: ``blk.N.attn_q_a`` (r_q, dim),
#: ``attn_q_a_norm``, ``attn_q_b`` (heads x (d_nope + d_rope), r_q),
#: ``attn_kv_a_mqa`` (r_kv + d_rope, dim), ``attn_kv_a_norm``, ``attn_kv_b``
#: (heads x (d_nope + d_v), r_kv), ``attn_output`` (dim, heads x d_v); the
#: first ``<arch>.leading_dense_block_count`` layers the dense
#: ``ffn_{gate,up,down}``, the others the F32 router ``ffn_gate_inp``
#: (experts, dim), its F32 choice bias ``exp_probs_b.bias`` (experts), the
#: 3-D ``ffn_{gate,up,down}_exps`` and the shared ``ffn_{gate,up,down}_shexp``.
#: Keys, llama.cpp's: ``attention.q_lora_rank``, ``attention.kv_lora_rank``,
#: ``attention.key_length`` (d_nope + d_rope), ``attention.value_length``,
#: ``rope.dimension_count`` (d_rope), ``leading_dense_block_count``,
#: ``expert_feed_forward_length``, ``expert_count``, ``expert_used_count``,
#: ``expert_shared_count``, ``expert_weights_scale``, ``expert_weights_norm``,
#: ``expert_gating_func`` (1 softmax, 2 sigmoid), ``expert_group_count``,
#: ``expert_group_used_count``, ``rope.scaling.{type,factor,
#: original_context_length,yarn_log_multiplier}`` (the last 0.1 x
#: mscale_all_dim); this repo's own: ``rope.scaling.yarn_beta_{fast,slow}``
#: and ``expert_held_first`` / ``expert_held_count`` (the 3-D expert tensors
#: hold that many experts from that one on, of the router's
#: ``expert_count``: one chip's share of an expert-parallel layer; absent:
#: all).  Q and K rotate on interleaved pairs (ggml's NORM mode).
#: ``exaone-moe`` (llama.cpp's name for the K-EXAONE family as remembered;
#: models/hybrid.py) is an attention kind per layer over the fifth cache
#: kind, and ``deepseek2``'s feed-forward kinds.  Tensors: ``blk.N.attn_q``
#: (heads x key_length, dim), ``attn_k`` / ``attn_v`` (kv heads x
#: key_length, dim), ``attn_q_norm`` / ``attn_k_norm`` (key_length: RMSNorm
#: over EACH head), ``attn_output`` (dim, heads x key_length), ``attn_norm``,
#: ``ffn_norm``; the feed-forward tensors and keys of ``deepseek2`` above
#: (``leading_dense_block_count`` ... ``expert_group_used_count``,
#: ``expert_held_first`` / ``expert_held_count``).  Its own keys:
#: ``attention.key_length`` / ``value_length`` (a head's width, which is
#: not ``embedding_length / head_count``), ``attention.sliding_window`` and
#: ``attention.sliding_window_pattern`` (n: layer i is a window layer
#: unless (i + 1) % n == 0, llama.cpp's ``set_swa_pattern``).  Window
#: layers rotate Q and K (rotate-half), global layers do not.
#: ``lfm2moe`` (llama.cpp's name for the LFM2 mixture-of-experts family as
#: remembered; models/lfm2.py) is a MIXER kind per layer over the sixth cache
#: kind, a gated short convolution or GQA, and ``deepseek2``'s feed-forward
#: kinds with no shared expert.  Tensors: a conv layer's
#: ``blk.N.shortconv.in_proj`` (3 x dim, dim: the rows of b, c, x in that
#: order), ``shortconv.conv`` (dim, l_cache: F32 depthwise taps, oldest
#: first) and ``shortconv.out_proj`` (dim, dim); an attention layer's
#: ``attn_{q,k,v,output}`` and ``attn_{q,k}_norm`` (a head's width); every
#: layer's ``attn_norm`` (the family's ``operator_norm``) and ``ffn_norm``;
#: the feed-forward tensors of ``deepseek2`` without the ``_shexp`` ones;
#: ``token_embd_norm`` is the FINAL norm and there is no ``output.weight``
#: (the head is the embedding).  Keys: ``shortconv.l_cache`` (taps),
#: ``attention.head_count_kv`` as an ARRAY with one entry a layer, 0 in a
#: conv layer; ``leading_dense_block_count`` ... ``expert_gating_func`` as
#: above.  Q and K rotate on halves.
#: ``longcat-flash`` (this repo's name for the LongCat-Flash family:
#: llama.cpp's, if it has one, is not known here; models/mla.py) is
#: ``deepseek2``'s latent attention TWICE a layer, a dense feed-forward
#: after each, and one shortcut-connected expert branch a layer whose
#: softmax router has outputs that are identity ("zero-compute") experts.
#: Tensors: a sub-block ``s`` in (0, 1) of layer N has ``blk.N.s.attn_norm``,
#: ``attn_q_a``, ``attn_q_a_norm``, ``attn_q_b``, ``attn_kv_a_mqa``,
#: ``attn_kv_a_norm``, ``attn_kv_b``, ``attn_output`` (shapes as
#: ``deepseek2``'s), ``ffn_norm`` and the dense ``ffn_{gate,up,down}``;
#: the layer has the F32 router ``blk.N.ffn_gate_inp`` (expert_count +
#: expert_zero_count rows: the zero experts' outputs come last), its F32
#: choice bias ``blk.N.exp_probs_b.bias`` (as many; ABSENT reads as zeros:
#: the published buffer starts there) and the 3-D ``ffn_{gate,up,down}_exps``.
#: Keys: ``deepseek2``'s attention keys; ``expert_feed_forward_length``,
#: ``expert_count`` (real experts), ``expert_used_count``,
#: ``expert_weights_scale``, ``expert_weights_norm``, ``expert_gating_func``,
#: ``expert_held_first`` / ``expert_held_count``; its own:
#: ``expert_zero_count``, ``expert_zero_type`` (``identity``: anything else
#: is refused by name), ``attention.scale_q_lora`` / ``scale_kv_lora``
#: (bool: the query after ``attn_q_b`` times (embedding_length /
#: q_lora_rank)^1/2, the normed latent times (embedding_length /
#: kv_lora_rank)^1/2).  Q and K rotate on interleaved pairs; no rope scaling.
#: ``deepseek32`` (this repo's name for DeepSeek-V3.2's block, ``model_type
#: deepseek_v32``: llama.cpp's, if it has one, is not known here;
#: models/mla.py) is ``deepseek2`` with a learned INDEXER beside every
#: layer's latent attention (DeepSeek Sparse Attention): every tensor and
#: key of ``deepseek2`` above, and per layer ``blk.N.indexer_q_b``
#: (indexer heads x key_length, r_q: from the SAME normed query latent as
#: ``attn_q_b``), ``indexer_k`` (key_length, dim: ONE index key a position),
#: ``indexer_k_norm.weight`` / ``indexer_k_norm.bias`` (a LayerNorm over the
#: key, F32) and the F32 ``indexer_proj`` (indexer heads, dim: a signed
#: weight a head and query).  Keys: ``attention.indexer.head_count``,
#: ``attention.indexer.key_length``, ``attention.indexer.top_k`` (all three
#: needed: a file without them is refused by name), and
#: ``attention.indexer.layer_norm_epsilon`` (absent: 1e-6).  The main
#: attention's Q and K rotate on interleaved pairs as ``deepseek2``'s; the
#: indexer's first ``rope.dimension_count`` columns rotate on HALVES.
#: ``ouro`` (this repo's name for the Ouro looped language models:
#: llama.cpp's, if it has one, is not known here; models/llama.py) is the
#: dense block whose ``block_count`` layers run ``<arch>.ut_steps`` passes a
#: token: the same weights every pass, a cache leaf per (pass, layer), the
#: final norm ``output_norm`` after EVERY pass.  Tensors: the dense block's,
#: and ``blk.N.post_attention_norm`` / ``blk.N.post_ffw_norm`` (llama.cpp's
#: names for a norm AFTER a sub-block: the output of attention and of the
#: feed-forward is normed before it joins the stream), and the exit gate's
#: F32 ``ut_exit_gate.weight`` (1, dim) and ``ut_exit_gate.bias`` (1).
#: Keys: the dense block's; ``ut_steps`` (``total_ut_steps``),
#: ``early_exit_threshold`` (under 1.0 refused by name:
#: models/config.py), ``attention.key_length`` (a head's width).  Q and K
#: rotate on halves.
#: ``phi4flash`` (this repo's name for Phi-4-mini-flash-reasoning's block,
#: ``model_type phi4flash``: llama.cpp's, if it has one, is not known here;
#: models/phi4flash.py) is a MIXER kind per layer over the seventh cache
#: kind and a dense SwiGLU in every layer; every norm is a LayerNorm
#: (``*_norm.weight`` AND ``*_norm.bias``), nothing rotates, and there is
#: no ``output.weight``: the head is ``token_embd``.  Key ``mixer_types``:
#: a comma-joined kind a layer, ``ssm`` | ``window`` | ``full`` | ``gmu`` |
#: ``cross``, in the order (ssm, window) pairs, ONE (ssm, full) pair,
#: (gmu, cross) pairs.  Tensors: an ``ssm`` layer has llama.cpp's Mamba
#: names ``blk.N.ssm_in`` (2 x inner, dim: the rows of x then z),
#: ``ssm_conv1d.weight`` (inner, conv_kernel: F32 taps, oldest first) and
#: ``ssm_conv1d.bias``, ``ssm_x`` (time_step_rank + 2 x state_size, inner:
#: dt, B, C in that order), ``ssm_dt.weight`` (inner, time_step_rank) and
#: ``ssm_dt.bias``, ``ssm_a`` (inner, state_size: A itself, negative, as
#: llama.cpp's converter stores it), ``ssm_d`` (inner), ``ssm_out`` (dim,
#: inner); a ``window`` / ``full`` layer ``attn_{q,k,v,output}`` with
#: ``.bias``, the differential form's ``attn_lambda_{q1,k1,q2,k2}`` (a
#: head's width, F32) and ``attn_sub_norm.weight`` (2 x a head's width: an
#: RMSNorm); a ``gmu`` layer ``gmu_in`` (inner, dim) and ``gmu_out`` (dim,
#: inner); a ``cross`` layer ``attn_q``, ``attn_output`` and the lambdas
#: and ``attn_sub_norm`` alone.  Keys: llama.cpp's ``ssm.conv_kernel`` /
#: ``ssm.inner_size`` / ``ssm.state_size`` / ``ssm.time_step_rank``,
#: ``attention.sliding_window``, ``attention.key_length``; and
#: ``ssm.values`` (absent: ``stored``): ``init_offsets`` says that
#: ``ssm_a`` and ``ssm_dt.bias`` hold OFFSETS from Mamba's initialisation
#: (models/params.py ``ssm_values``), which is how a file of random values
#: gets the time scales a trained one has.
#: ``jamba`` (llama.cpp's name for the Jamba family as remembered;
#: models/jamba.py) is a MIXER kind per layer over the eighth cache kind,
#: Mamba-1 or unrotated GQA, and a dense SwiGLU in every layer; every norm
#: is an RMSNorm, nothing rotates, and there is no ``output.weight``: the
#: head is ``token_embd``.  Key ``attention.head_count_kv``: an ARRAY with
#: one entry a layer, 0 in a scan layer (``lfm2moe``'s device).  Tensors: a
#: scan layer has the Mamba names above (``ssm_in`` ... ``ssm_out``,
#: ``ssm_a`` as stored or as ``ssm.values`` says) and the family's three
#: inner RMSNorms ``ssm_dt_norm.weight`` (time_step_rank), ``ssm_b_norm
#: .weight`` and ``ssm_c_norm.weight`` (state_size), applied to dt, B and C
#: between ``ssm_x`` and ``ssm_dt`` / the scan; an attention layer
#: ``attn_{q,k,v,output}`` without biases; every layer ``attn_norm``,
#: ``ffn_norm`` and ``ffn_{gate,up,down}``; ``output_norm``.  Keys:
#: ``ssm.conv_kernel`` / ``inner_size`` / ``state_size`` /
#: ``time_step_rank``, ``attention.key_length``, ``ssm.values``;
#: ``expert_count`` above 1 (the family's routed layers) and
#: ``attention.sliding_window`` are refused by name.
#: A file of any other architecture is refused by name at load
#: (gguf/reader.py).
SERVED_ARCHITECTURES = ("llama", "mistral", "olmoe", "evabyte", "minicpm-sala",
                        "deepseek2", "exaone-moe", "lfm2moe", "longcat-flash",
                        "ouro", "deepseek32", "phi4flash", "jamba")

#: Of those, the architectures whose rotary embedding pairs dimension i
#: with i + head_dim/2 ("rotate-half", llama.cpp's LLAMA_ROPE_TYPE_NEOX):
#: their converter leaves Q/K as Hugging Face stores them.  The others'
#: converter permutes Q/K rows so that the pairs are (2i, 2i+1) (ggml's
#: NORM mode).  ``olmoe`` could not be permuted: its QK-norm weight spans
#: the whole projection.
NEOX_ROPE_ARCHITECTURES = ("olmoe", "evabyte", "minicpm-sala",
                           "exaone-moe", "lfm2moe", "ouro")


def align_up(n: int, alignment: int) -> int:
    return (n + alignment - 1) // alignment * alignment


def tensor_nbytes(ggml_type: GGMLType, n_elements: int) -> int:
    block, nbytes = GGML_BLOCK_SIZES[ggml_type]
    if n_elements % block != 0:
        raise ValueError(
            f"{ggml_type.name}: element count {n_elements} not divisible by block {block}"
        )
    return (n_elements // block) * nbytes
