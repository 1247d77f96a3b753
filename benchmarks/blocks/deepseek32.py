"""The ``deepseek32`` block (DeepSeek-V3.2-Exp's, ``model_type
deepseek_v32``; this repo's name for it: gguf/constants.py): ``deepseek2``'s
block (``blocks/deepseek2.py``, imported, not copied) with a learned INDEXER
beside every layer's latent attention (DeepSeek Sparse Attention).

- every tensor and key of ``deepseek2``;
- per layer, after ``attn_output``: ``indexer_q_b`` (``index_n_heads`` x
  ``index_head_dim`` rows of ``q_lora_rank``: the indexer's queries from the
  SAME normed query latent), ``indexer_k`` (``index_head_dim`` rows of
  ``hidden_size``: ONE index key a position), ``indexer_k_norm.weight`` /
  ``.bias`` (a LayerNorm over the key, F32) and the F32 ``indexer_proj``
  (``index_n_heads`` rows: a signed weight a head and query);
- keys ``attention.indexer.head_count`` / ``key_length`` / ``top_k``.

The cache holds, beside the latent and the rotated key, the index key:
(kv_lora_rank + qk_rope_head_dim + index_head_dim) x 2 B a layer and position.

Costs are the ALGORITHM's: a step reads every live position's INDEX KEY
(the indexer scores them all), but only ``min(context, index_topk)`` latents
a lane and layer (what the selection leaves), and the attention's FLOPs are
over those; the indexer's own FLOPs (a product over ``index_head_dim`` per
head and live position, a relu, a weight and a sum) are counted.  A
program that reads every latent behind a mask therefore shows as distance
from the roofline, and one that gathers what it selected cannot read over
100 %.
"""

from ggufgen import block_of, tensor_nbytes

ds2 = block_of({"block": "deepseek2"})

n_moe, router_experts = ds2.n_moe, ds2.router_experts
experts_read, held_picks_per_token = ds2.experts_read, ds2.held_picks_per_token
expert_bytes_per_step = ds2.expert_bytes_per_step


def indexer_plan(cfg, i):
    hi, di = cfg["index_n_heads"], cfg["index_head_dim"]
    tt = cfg["gguf"]["tensor_types"]
    p = f"blk.{i}."
    return [
        (p + "indexer_q_b.weight", (hi * di, cfg["q_lora_rank"]),
         tt["indexer_q_b"]),
        (p + "indexer_k.weight", (di, cfg["hidden_size"]), tt["indexer_k"]),
        (p + "indexer_k_norm.weight", (di,), "F32"),
        (p + "indexer_k_norm.bias", (di,), "F32"),
        (p + "indexer_proj.weight", (hi, cfg["hidden_size"]), "F32"),
    ]


def tensor_plan(cfg):
    plan = []
    for entry in ds2.tensor_plan(cfg):
        plan.append(entry)
        name = entry[0]
        if name.endswith(".attn_output.weight"):
            plan += indexer_plan(cfg, int(name.split(".")[1]))
    return plan


def metadata(cfg, arch):
    return ds2.metadata(cfg, arch) + [
        (f"{arch}.attention.indexer.head_count", "u32", cfg["index_n_heads"]),
        (f"{arch}.attention.indexer.key_length", "u32", cfg["index_head_dim"]),
        (f"{arch}.attention.indexer.top_k", "u32", cfg["index_topk"]),
    ]


def split(cfg):
    """``blocks/deepseek2.py split`` with the indexer's tensors among
    everything outside the routed experts."""
    rest_b, rest_w, exp_b, exp_w = ds2.split(cfg)
    for i in range(cfg["num_hidden_layers"]):
        for _, shape, kind in indexer_plan(cfg, i):
            n = 1
            for dim in shape:
                n *= dim
            rest_b += tensor_nbytes(kind, n)
            rest_w += n if len(shape) == 2 else 0
    return rest_b, rest_w, exp_b, exp_w


def selected(cfg, context_tokens):
    """Latents a query at ``context_tokens`` attends."""
    return min(context_tokens, cfg["index_topk"])


def index_bytes_per_step(cfg, lanes, context_tokens, kv_bytes=2, run=None):
    """Every live lane's index keys up to its position, all layers."""
    return lanes * context_tokens * cfg["num_hidden_layers"] \
        * cfg["index_head_dim"] * kv_bytes


def index_flops_per_step(cfg, lanes, context_tokens, run=None):
    """Per head and live position a product over index_head_dim, then a
    relu, a weight and a sum."""
    return lanes * context_tokens * cfg["num_hidden_layers"] \
        * cfg["index_n_heads"] * (2 * cfg["index_head_dim"] + 3)


def selected_bytes_per_step(cfg, lanes, context_tokens, kv_bytes=2, run=None):
    """The SELECTED latents and rotated keys, once a lane and layer."""
    return ds2.latent_bytes_per_step(cfg, lanes, selected(cfg, context_tokens),
                                     kv_bytes)


def selected_flops_per_step(cfg, lanes, context_tokens, run=None):
    return ds2.latent_flops_per_step(cfg, lanes,
                                     selected(cfg, context_tokens))


def decode_step_bytes(cfg, lanes, context_tokens, kv_bytes=2, run=None):
    return (split(cfg)[0] + expert_bytes_per_step(cfg, lanes, run)
            + index_bytes_per_step(cfg, lanes, context_tokens, kv_bytes)
            + selected_bytes_per_step(cfg, lanes, context_tokens, kv_bytes)
            + lanes * cfg["hidden_size"] * 2)


def _per_token_weights(cfg, run=None):
    _, rest_w, _, exp_w = split(cfg)
    return rest_w + n_moe(cfg) * held_picks_per_token(cfg, run) * exp_w


def decode_step_flops(cfg, lanes, context_tokens, run=None):
    return lanes * 2 * _per_token_weights(cfg, run) \
        + index_flops_per_step(cfg, lanes, context_tokens) \
        + selected_flops_per_step(cfg, lanes, context_tokens)


def prefill_flops(cfg, n_tokens, run=None):
    """One pass over the per-token weights a position (the head once), the
    indexer over the causal half (every query scores every position at or
    below it), and the attention in the EXPANDED form over what each query
    selected: ``min(t + 1, index_topk)`` positions."""
    head = cfg["vocab_size"] * cfg["hidden_size"]
    per_score = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] \
        + cfg["v_head_dim"]
    k = min(n_tokens, cfg["index_topk"])
    attended = k * (k + 1) / 2 + (n_tokens - k) * k
    layers = cfg["num_hidden_layers"]
    attn = 2.0 * cfg["num_attention_heads"] * per_score * attended * layers
    index = cfg["index_n_heads"] * (2 * cfg["index_head_dim"] + 3) \
        * n_tokens * (n_tokens + 1) / 2 * layers
    return 2.0 * (_per_token_weights(cfg, run) - head) * n_tokens \
        + 2.0 * head + attn + index
