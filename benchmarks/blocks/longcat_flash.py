"""The ``longcat-flash`` block (LongCat-Flash's; this repo's name for the
family: gguf/constants.py): TWO sub-blocks a layer, each latent attention
and a dense SwiGLU, and one shortcut-connected expert branch a layer whose
softmax router has outputs that are identity ("zero-compute") experts.

- a sub-block ``s`` of layer ``N`` (``blk.N.s.*``): ``deepseek2``'s attention
  tensors (``attn_q_a`` ... ``attn_output``; ``blocks/deepseek2.py`` has the
  shapes), ``ffn_norm`` and the dense ``ffn_{gate,up,down}`` of
  ``ffn_hidden_size``.  The cache holds, per SUB-layer and position, the
  scaled latent and the rotated key: (kv_lora_rank + qk_rope_head_dim) x 2 B;
- the layer (``blk.N.*``): the F32 router ``ffn_gate_inp`` over
  ``router_experts + zero_expert_num`` outputs, ``moe_topk`` a token, and 3-D
  ``ffn_*_exps`` tensors of ``expert_ffn_hidden_size`` that hold
  ``n_routed_experts`` experts from ``experts_held_first`` on (one chip's
  share: ``expert_held_first`` / ``expert_held_count`` in the file).  The
  choice bias ``exp_probs_b.bias`` is NOT written: ``ggufgen.py`` would draw
  it at ``hidden_size ** -0.5``, ten times a softmax score over 768 outputs,
  so that every token picked the same twelve; the loader reads an absent
  bias as zeros, which is where the published buffer starts.

Costs are the ALGORITHM's, on the bytes the FILE stores: a step reads every
matrix outside the experts once (two attentions and two dense feed-forwards
a layer), of the held experts those the live lanes picked (the program's
counters ``experts_read_total`` over ``expert_layer_steps_total``; without a
run, what the lanes could pick), every live lane's latents once a lane and
SUB-layer, and for an identity pick NOTHING: it costs ``2 x hidden_size``
FLOPs (a scale and an add of the token's own row) and no byte.
"""

from counters import ratio
from ggufgen import tensor_nbytes


def lat_width(cfg):
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def router_outputs(cfg):
    """The router's width: the published experts (whatever is held) and the
    zero ones after them."""
    return (cfg.get("router_experts") or cfg["n_routed_experts"]) \
        + cfg["zero_expert_num"]


def tensor_plan(cfg):
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    h = cfg["num_attention_heads"]
    r_q, r_kv, d_r = cfg["q_lora_rank"], cfg["kv_lora_rank"], \
        cfg["qk_rope_head_dim"]
    d_n, d_v = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    f, fe = cfg["ffn_hidden_size"], cfg["expert_ffn_hidden_size"]
    e_held = cfg["n_routed_experts"]
    tt = cfg["gguf"]["tensor_types"]
    plan = [("token_embd.weight", (v, d), tt["token_embd"])]
    for i in range(cfg["num_layers"]):
        for s in (0, 1):
            p = f"blk.{i}.{s}."
            plan += [
                (p + "attn_norm.weight", (d,), "F32"),
                (p + "attn_q_a.weight", (r_q, d), tt["attn_q_a"]),
                (p + "attn_q_a_norm.weight", (r_q,), "F32"),
                (p + "attn_q_b.weight", (h * (d_n + d_r), r_q),
                 tt["attn_q_b"]),
                (p + "attn_kv_a_mqa.weight", (r_kv + d_r, d),
                 tt["attn_kv_a_mqa"]),
                (p + "attn_kv_a_norm.weight", (r_kv,), "F32"),
                (p + "attn_kv_b.weight", (h * (d_n + d_v), r_kv),
                 tt["attn_kv_b"]),
                (p + "attn_output.weight", (d, h * d_v), tt["attn_output"]),
                (p + "ffn_norm.weight", (d,), "F32"),
                (p + "ffn_gate.weight", (f, d), tt["ffn_gate"]),
                (p + "ffn_up.weight", (f, d), tt["ffn_up"]),
                (p + "ffn_down.weight", (d, f), tt["ffn_down"]),
            ]
        p = f"blk.{i}."
        plan += [       # (no ``exp_probs_b.bias``: the module docstring)
            (p + "ffn_gate_inp.weight", (router_outputs(cfg), d), "F32"),
            (p + "ffn_gate_exps.weight", (e_held, fe, d), tt["ffn_gate_exps"]),
            (p + "ffn_up_exps.weight", (e_held, fe, d), tt["ffn_up_exps"]),
            (p + "ffn_down_exps.weight", (e_held, d, fe), tt["ffn_down_exps"]),
        ]
    plan += [("output_norm.weight", (d,), "F32"),
             ("output.weight", (v, d), tt["output"])]
    return plan


def metadata(cfg, arch):
    if cfg["zero_expert_type"] != "identity":
        raise ValueError(
            f"zero_expert_type {cfg['zero_expert_type']!r} is not written")
    if cfg["attention_bias"] or cfg["attention_method"] != "MLA":
        raise ValueError("attention_bias / attention_method are not written")
    routed = cfg.get("router_experts") or cfg["n_routed_experts"]
    meta = [
        (f"{arch}.block_count", "u32", cfg["num_layers"]),
        (f"{arch}.context_length", "u32", cfg["max_position_embeddings"]),
        (f"{arch}.embedding_length", "u32", cfg["hidden_size"]),
        (f"{arch}.feed_forward_length", "u32", cfg["ffn_hidden_size"]),
        (f"{arch}.attention.head_count", "u32", cfg["num_attention_heads"]),
        (f"{arch}.attention.head_count_kv", "u32",
         cfg["num_attention_heads"]),
        (f"{arch}.attention.layer_norm_rms_epsilon", "f32",
         cfg["rms_norm_eps"]),
        (f"{arch}.rope.freq_base", "f32", float(cfg["rope_theta"])),
        (f"{arch}.vocab_size", "u32", cfg["vocab_size"]),
        (f"{arch}.rope.dimension_count", "u32", cfg["qk_rope_head_dim"]),
        (f"{arch}.attention.q_lora_rank", "u32", cfg["q_lora_rank"]),
        (f"{arch}.attention.kv_lora_rank", "u32", cfg["kv_lora_rank"]),
        (f"{arch}.attention.key_length", "u32",
         cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]),
        (f"{arch}.attention.value_length", "u32", cfg["v_head_dim"]),
        (f"{arch}.attention.scale_q_lora", "bool", cfg["mla_scale_q_lora"]),
        (f"{arch}.attention.scale_kv_lora", "bool", cfg["mla_scale_kv_lora"]),
        (f"{arch}.expert_feed_forward_length", "u32",
         cfg["expert_ffn_hidden_size"]),
        (f"{arch}.expert_count", "u32", routed),
        (f"{arch}.expert_used_count", "u32", cfg["moe_topk"]),
        (f"{arch}.expert_zero_count", "u32", cfg["zero_expert_num"]),
        (f"{arch}.expert_zero_type", "str", cfg["zero_expert_type"]),
        (f"{arch}.expert_weights_scale", "f32",
         float(cfg["routed_scaling_factor"])),
        (f"{arch}.expert_weights_norm", "bool", False),
        (f"{arch}.expert_gating_func", "u32", 1),       # softmax
    ]
    if cfg["n_routed_experts"] != routed:
        meta += [
            (f"{arch}.expert_held_first", "u32",
             cfg.get("experts_held_first", 0)),
            (f"{arch}.expert_held_count", "u32", cfg["n_routed_experts"]),
        ]
    return meta


def split(cfg):
    """(bytes, matrix weights) of everything outside the routed experts and
    the embedding table, and of ONE routed expert of one layer."""
    rest_b = rest_w = exp_b = exp_w = 0
    for name, shape, kind in tensor_plan(cfg):
        if name == "token_embd.weight":
            continue
        n = 1
        for dim in shape:
            n *= dim
        if name.endswith("_exps.weight"):
            if name.startswith("blk.0."):
                exp_b += tensor_nbytes(kind, n) // shape[0]
                exp_w += n // shape[0]
        else:
            rest_b += tensor_nbytes(kind, n)
            rest_w += n if len(shape) == 2 else 0
    return rest_b, rest_w, exp_b, exp_w


def experts_read(cfg, lanes, run):
    """Held experts a layer's step read: counted by the program where the
    run has the counters, else what the lanes' picks could reach of them."""
    counted = ratio(run, "experts_read_total", "expert_layer_steps_total") \
        if run else None
    if counted is not None:
        return counted
    return min(cfg["n_routed_experts"], lanes * cfg["moe_topk"])


def picks_per_token(cfg, run):
    """(picks of a token that reach an expert held here, picks that are
    identity experts): the program's ``expert_picks_held_total`` and
    ``expert_picks_zero_total`` over ``expert_picks_routed_total`` of the
    token's ``moe_topk``, else the shares of the router's outputs."""
    held = ratio(run, "expert_picks_held_total",
                 "expert_picks_routed_total") if run else None
    zero = ratio(run, "expert_picks_zero_total",
                 "expert_picks_routed_total") if run else None
    if held is None or zero is None:
        held = cfg["n_routed_experts"] / router_outputs(cfg)
        zero = cfg["zero_expert_num"] / router_outputs(cfg)
    return held * cfg["moe_topk"], zero * cfg["moe_topk"]


def expert_bytes_per_step(cfg, lanes, run=None):
    """An identity pick reads no byte."""
    return cfg["num_layers"] * experts_read(cfg, lanes, run) * split(cfg)[2]


def latent_bytes_per_step(cfg, lanes, context_tokens, kv_bytes=2, run=None):
    """Every live lane's latents and rotated keys up to its position, both
    sub-layers of every layer, ONCE a lane (not once a head)."""
    return lanes * context_tokens * 2 * cfg["num_layers"] \
        * lat_width(cfg) * kv_bytes


def latent_flops_per_step(cfg, lanes, context_tokens, run=None):
    """The absorbed attention: per head and cached position a score over
    kv_lora_rank + qk_rope_head_dim and a weighted sum over kv_lora_rank."""
    return lanes * context_tokens * 2 * cfg["num_layers"] \
        * cfg["num_attention_heads"] \
        * (lat_width(cfg) + cfg["kv_lora_rank"]) * 2


def decode_step_bytes(cfg, lanes, context_tokens, kv_bytes=2, run=None):
    return (split(cfg)[0] + expert_bytes_per_step(cfg, lanes, run)
            + latent_bytes_per_step(cfg, lanes, context_tokens, kv_bytes)
            + lanes * cfg["hidden_size"] * 2)


def _per_token_flops(cfg, run=None):
    """FLOPs of a token's pass over the weights: 2 a weight outside the
    experts and in the held experts it picked, and ``2 x hidden_size`` for
    each identity pick."""
    _, rest_w, _, exp_w = split(cfg)
    held, zero = picks_per_token(cfg, run)
    return 2 * rest_w + cfg["num_layers"] * (
        2 * held * exp_w + zero * 2 * cfg["hidden_size"])


def decode_step_flops(cfg, lanes, context_tokens, run=None):
    return lanes * _per_token_flops(cfg, run) \
        + latent_flops_per_step(cfg, lanes, context_tokens)


def prefill_flops(cfg, n_tokens, run=None):
    """One pass over the per-token weights a position (the head once), and
    the causal half of attention in the EXPANDED form, the cheaper one for
    many queries: per head and (query, key) a score over qk_nope + qk_rope
    and a weighted sum over v_head_dim, in both sub-layers of a layer."""
    head = cfg["vocab_size"] * cfg["hidden_size"]
    per_score = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] \
        + cfg["v_head_dim"]
    attn = cfg["num_attention_heads"] * per_score * n_tokens * n_tokens \
        * 2 * cfg["num_layers"]
    return (_per_token_flops(cfg, run) - 2.0 * head) * n_tokens \
        + 2.0 * head + attn
