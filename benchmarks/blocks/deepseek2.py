"""The ``deepseek2`` block (DeepSeek-V2/V3's; llama.cpp's name for the
family): latent attention and a feed-forward kind per layer.

- attention, every layer: the query through a normed latent of
  ``q_lora_rank`` (``attn_q_a``, ``attn_q_a_norm``, ``attn_q_b``: heads x
  (``qk_nope_head_dim`` + ``qk_rope_head_dim``) rows); keys and values
  through ONE normed latent of ``kv_lora_rank`` plus one rotated key of
  ``qk_rope_head_dim`` shared by all heads (``attn_kv_a_mqa``: kv_lora_rank +
  qk_rope_head_dim rows; ``attn_kv_a_norm``; ``attn_kv_b``: heads x
  (qk_nope_head_dim + ``v_head_dim``) rows of kv_lora_rank); ``attn_output``
  on heads x v_head_dim.  The cache holds the latent and the rotated key:
  (kv_lora_rank + qk_rope_head_dim) x 2 B a layer and position, for all heads;
- feed-forward: the first ``first_k_dense_replace`` layers dense SwiGLU of
  ``intermediate_size``; the others an F32 router over ``router_experts``
  (``ffn_gate_inp``, its choice bias ``exp_probs_b.bias``) in ``n_group``
  groups, ``num_experts_per_tok`` a token, experts of
  ``moe_intermediate_size`` in 3-D ``ffn_*_exps`` tensors that hold
  ``n_routed_experts`` of them from ``experts_held_first`` on (one chip's
  share of an expert-parallel layer: the file says so under this repo's
  keys ``expert_held_first`` / ``expert_held_count``), plus
  ``n_shared_experts`` shared ones (``ffn_*_shexp``) on every token.

Costs are the ALGORITHM's, on the bytes the FILE stores: a step reads every
matrix outside the experts once, of the held experts those the live lanes
picked (the program's counters ``experts_read_total`` over
``expert_layer_steps_total``; without a run, what the lanes could pick),
and every live lane's latents ONCE a lane, not once a head.  Padding of K
7168 to the kernels' 8192 and a bf16 ``attn_q_b`` / ``attn_kv_b`` in the
program therefore show as distance from the roofline.
"""

import costs
from counters import ratio
from ggufgen import tensor_nbytes


def _r(cfg):
    return cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]


def lat_width(cfg):
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def n_moe(cfg):
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def router_experts(cfg):
    """The router's width: the published expert count, whatever is held."""
    return cfg.get("router_experts") or cfg["n_routed_experts"]


def tensor_plan(cfg):
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    h = cfg["num_attention_heads"]
    r_q, (r_kv, d_r) = cfg["q_lora_rank"], _r(cfg)
    d_n, d_v = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    f, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    e_held, e_all = cfg["n_routed_experts"], router_experts(cfg)
    sh = fe * cfg["n_shared_experts"]
    tt = cfg["gguf"]["tensor_types"]
    plan = [("token_embd.weight", (v, d), tt["token_embd"])]
    for i in range(cfg["num_hidden_layers"]):
        p = f"blk.{i}."
        plan += [
            (p + "attn_norm.weight", (d,), "F32"),
            (p + "attn_q_a.weight", (r_q, d), tt["attn_q_a"]),
            (p + "attn_q_a_norm.weight", (r_q,), "F32"),
            (p + "attn_q_b.weight", (h * (d_n + d_r), r_q), tt["attn_q_b"]),
            (p + "attn_kv_a_mqa.weight", (r_kv + d_r, d), tt["attn_kv_a_mqa"]),
            (p + "attn_kv_a_norm.weight", (r_kv,), "F32"),
            (p + "attn_kv_b.weight", (h * (d_n + d_v), r_kv), tt["attn_kv_b"]),
            (p + "attn_output.weight", (d, h * d_v), tt["attn_output"]),
            (p + "ffn_norm.weight", (d,), "F32"),
        ]
        if i < cfg["first_k_dense_replace"]:
            plan += [
                (p + "ffn_gate.weight", (f, d), tt["ffn_gate"]),
                (p + "ffn_up.weight", (f, d), tt["ffn_up"]),
                (p + "ffn_down.weight", (d, f), tt["ffn_down"]),
            ]
            continue
        plan += [
            (p + "ffn_gate_inp.weight", (e_all, d), "F32"),
            (p + "exp_probs_b.bias", (e_all,), "F32"),
            (p + "ffn_gate_exps.weight", (e_held, fe, d), tt["ffn_gate_exps"]),
            (p + "ffn_up_exps.weight", (e_held, fe, d), tt["ffn_up_exps"]),
            (p + "ffn_down_exps.weight", (e_held, d, fe), tt["ffn_down_exps"]),
            (p + "ffn_gate_shexp.weight", (sh, d), tt["ffn_gate_shexp"]),
            (p + "ffn_up_shexp.weight", (sh, d), tt["ffn_up_shexp"]),
            (p + "ffn_down_shexp.weight", (d, sh), tt["ffn_down_shexp"]),
        ]
    plan += [("output_norm.weight", (d,), "F32"),
             ("output.weight", (v, d), tt["output"])]
    return plan


def metadata(cfg, arch):
    rs = cfg.get("rope_scaling") or {}
    meta = [
        (f"{arch}.block_count", "u32", cfg["num_hidden_layers"]),
        (f"{arch}.context_length", "u32", cfg["max_position_embeddings"]),
        (f"{arch}.embedding_length", "u32", cfg["hidden_size"]),
        (f"{arch}.feed_forward_length", "u32", cfg["intermediate_size"]),
        (f"{arch}.attention.head_count", "u32", cfg["num_attention_heads"]),
        (f"{arch}.attention.head_count_kv", "u32", cfg["num_key_value_heads"]),
        (f"{arch}.attention.layer_norm_rms_epsilon", "f32",
         cfg["rms_norm_eps"]),
        (f"{arch}.rope.freq_base", "f32", cfg["rope_theta"]),
        (f"{arch}.vocab_size", "u32", cfg["vocab_size"]),
        (f"{arch}.rope.dimension_count", "u32", cfg["qk_rope_head_dim"]),
        (f"{arch}.attention.q_lora_rank", "u32", cfg["q_lora_rank"]),
        (f"{arch}.attention.kv_lora_rank", "u32", cfg["kv_lora_rank"]),
        (f"{arch}.attention.key_length", "u32",
         cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]),
        (f"{arch}.attention.value_length", "u32", cfg["v_head_dim"]),
        (f"{arch}.leading_dense_block_count", "u32",
         cfg["first_k_dense_replace"]),
        (f"{arch}.expert_feed_forward_length", "u32",
         cfg["moe_intermediate_size"]),
        (f"{arch}.expert_count", "u32", router_experts(cfg)),
        (f"{arch}.expert_used_count", "u32", cfg["num_experts_per_tok"]),
        (f"{arch}.expert_shared_count", "u32", cfg["n_shared_experts"]),
        (f"{arch}.expert_weights_scale", "f32", cfg["routed_scaling_factor"]),
        (f"{arch}.expert_weights_norm", "bool", cfg["norm_topk_prob"]),
        (f"{arch}.expert_gating_func", "u32",
         {"softmax": 1, "sigmoid": 2}[cfg["scoring_func"]]),
        (f"{arch}.expert_group_count", "u32", cfg["n_group"]),
        (f"{arch}.expert_group_used_count", "u32", cfg["topk_group"]),
    ]
    if rs.get("rope_type") == "yarn":
        meta += [
            (f"{arch}.rope.scaling.type", "str", "yarn"),
            (f"{arch}.rope.scaling.factor", "f32", rs["factor"]),
            (f"{arch}.rope.scaling.original_context_length", "u32",
             rs["original_max_position_embeddings"]),
            (f"{arch}.rope.scaling.yarn_log_multiplier", "f32",
             0.1 * rs["mscale_all_dim"]),
            (f"{arch}.rope.scaling.yarn_beta_fast", "f32", rs["beta_fast"]),
            (f"{arch}.rope.scaling.yarn_beta_slow", "f32", rs["beta_slow"]),
        ]
    if cfg["n_routed_experts"] != router_experts(cfg):
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
    first_moe = f"blk.{cfg['first_k_dense_replace']}."
    for name, shape, kind in tensor_plan(cfg):
        if name == "token_embd.weight":
            continue
        n = 1
        for dim in shape:
            n *= dim
        if name.endswith("_exps.weight"):
            if name.startswith(first_moe):
                exp_b += tensor_nbytes(kind, n) // shape[0]
                exp_w += n // shape[0]
        else:
            rest_b += tensor_nbytes(kind, n)
            rest_w += n if len(shape) == 2 else 0
    return rest_b, rest_w, exp_b, exp_w


def experts_read(cfg, lanes, run):
    """Held experts a routed layer's step read: counted by the program
    where the run has the counters, else what the lanes' picks could reach
    of the held ones."""
    counted = ratio(run, "experts_read_total", "expert_layer_steps_total") \
        if run else None
    if counted is not None:
        return counted
    return min(cfg["n_routed_experts"], lanes * cfg["num_experts_per_tok"])


def held_picks_per_token(cfg, run):
    """Picks of a token that reach an expert held here: the program's
    ``expert_picks_held_total`` over ``expert_picks_routed_total`` of the
    token's ``num_experts_per_tok``, else the held share of the router."""
    share = ratio(run, "expert_picks_held_total",
                  "expert_picks_routed_total") if run else None
    if share is None:
        share = cfg["n_routed_experts"] / router_experts(cfg)
    return share * cfg["num_experts_per_tok"]


def expert_bytes_per_step(cfg, lanes, run=None):
    return n_moe(cfg) * experts_read(cfg, lanes, run) * split(cfg)[2]


def latent_bytes_per_step(cfg, lanes, context_tokens, kv_bytes=2, run=None):
    """Every live lane's latents and rotated keys up to its position, all
    layers, ONCE a lane (not once a head)."""
    return lanes * context_tokens * cfg["num_hidden_layers"] \
        * lat_width(cfg) * kv_bytes


def latent_flops_per_step(cfg, lanes, context_tokens, run=None):
    """The absorbed attention: per head and cached position a score over
    kv_lora_rank + qk_rope_head_dim and a weighted sum over kv_lora_rank."""
    return lanes * context_tokens * cfg["num_hidden_layers"] \
        * cfg["num_attention_heads"] \
        * (lat_width(cfg) + cfg["kv_lora_rank"]) * 2


def decode_step_bytes(cfg, lanes, context_tokens, kv_bytes=2, run=None):
    return (split(cfg)[0] + expert_bytes_per_step(cfg, lanes, run)
            + latent_bytes_per_step(cfg, lanes, context_tokens, kv_bytes)
            + lanes * cfg["hidden_size"] * 2)


def _per_token_weights(cfg, run=None):
    _, rest_w, _, exp_w = split(cfg)
    return rest_w + n_moe(cfg) * held_picks_per_token(cfg, run) * exp_w


def decode_step_flops(cfg, lanes, context_tokens, run=None):
    return lanes * 2 * _per_token_weights(cfg, run) \
        + latent_flops_per_step(cfg, lanes, context_tokens)


def prefill_flops(cfg, n_tokens, run=None):
    """One pass over the per-token weights a position (the head once), and
    the causal half of attention in the EXPANDED form, the cheaper one for
    many queries: per head and (query, key) a score over qk_nope + qk_rope
    and a weighted sum over v_head_dim (the expansion of a position's keys
    and values is ``attn_kv_b``, among the weights)."""
    head = cfg["vocab_size"] * cfg["hidden_size"]
    per_score = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] \
        + cfg["v_head_dim"]
    attn = cfg["num_attention_heads"] * per_score * n_tokens * n_tokens \
        * cfg["num_hidden_layers"]
    return 2.0 * (_per_token_weights(cfg, run) - head) * n_tokens \
        + 2.0 * head + attn
