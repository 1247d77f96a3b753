"""The routed block of ``olmoe``: multi-head attention with an RMSNorm of Q
and K over the whole projection, and in place of the dense feed-forward a
float32 router (``ffn_gate_inp``, an F32 matrix) over ``num_experts`` SwiGLU
experts of width ``intermediate_size`` stacked in 3-D ``ffn_*_exps``
tensors, ``num_experts_per_tok`` of them per token, no shared expert.

A token passes through ``num_experts_per_tok`` experts, but a decode STEP
reads every expert that any of its live lanes picked: between one token's
eight and all sixty-four, and only the program knows how many.  So the
step's expert bytes are the experts the program *counted*
(``experts_read_total`` over ``expert_layer_steps_total`` in the run's
``/metrics`` samples: distinct experts per layer and step) times one
expert's stored bytes.  Without a run (or on a program without the
counters) it is the most a step can touch.  All experts would read over
100 % of a roofline; one token's eight would flatter a full batch.
"""

import costs
from counters import ratio
from ggufgen import tensor_nbytes, transformer_metadata


def tensor_plan(cfg):
    d, f, e = cfg["hidden_size"], cfg["intermediate_size"], cfg["num_experts"]
    _, q_dim, kv_dim = costs.dims(cfg)
    v = cfg["vocab_size"]
    tt = cfg["gguf"]["tensor_types"]
    plan = [("token_embd.weight", (v, d), tt["token_embd"])]
    for i in range(cfg["num_hidden_layers"]):
        p = f"blk.{i}."
        plan += [
            (p + "attn_norm.weight", (d,), "F32"),
            (p + "attn_q.weight", (q_dim, d), tt["attn_q"]),
            (p + "attn_k.weight", (kv_dim, d), tt["attn_k"]),
            (p + "attn_v.weight", (kv_dim, d), tt["attn_v"]),
            (p + "attn_output.weight", (d, q_dim), tt["attn_output"]),
            (p + "attn_q_norm.weight", (q_dim,), "F32"),
            (p + "attn_k_norm.weight", (kv_dim,), "F32"),
            (p + "ffn_norm.weight", (d,), "F32"),
            (p + "ffn_gate_inp.weight", (e, d), "F32"),
            (p + "ffn_gate_exps.weight", (e, f, d), tt["ffn_gate_exps"]),
            (p + "ffn_up_exps.weight", (e, f, d), tt["ffn_up_exps"]),
            (p + "ffn_down_exps.weight", (e, d, f), tt["ffn_down_exps"]),
        ]
    plan += [("output_norm.weight", (d,), "F32"),
             ("output.weight", (v, d), tt["output"])]
    return plan


def metadata(cfg, arch):
    return transformer_metadata(cfg, arch) + [
        (f"{arch}.expert_count", "u32", cfg["num_experts"]),
        (f"{arch}.expert_used_count", "u32", cfg["num_experts_per_tok"]),
    ]


def experts_read(cfg, lanes, run):
    """Experts a layer's step read: counted by the program where the run
    has the counters, else every expert the lanes could have picked."""
    counted = ratio(run, "experts_read_total", "expert_layer_steps_total") \
        if run else None
    if counted is not None:
        return counted
    return min(cfg["num_experts"], lanes * cfg["num_experts_per_tok"])


def split(cfg):
    """(bytes, matrix weights) of everything outside the experts and the
    embedding table, and of ONE expert of one layer."""
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


def expert_bytes_per_step(cfg, lanes, run=None):
    """What the grouped expert matmuls of one decode step have to read:
    the experts read, every layer, as the file stores them."""
    return cfg["num_hidden_layers"] * experts_read(cfg, lanes, run) \
        * split(cfg)[2]


def decode_step_bytes(cfg, lanes, context_tokens, kv_bytes=2, run=None):
    return (split(cfg)[0] + expert_bytes_per_step(cfg, lanes, run)
            + lanes * context_tokens * costs.kv_bytes_per_token(cfg, kv_bytes)
            + lanes * cfg["hidden_size"] * 2)


def _per_token_weights(cfg):
    _, rest_w, _, exp_w = split(cfg)
    return rest_w + cfg["num_hidden_layers"] \
        * cfg["num_experts_per_tok"] * exp_w


def decode_step_flops(cfg, lanes, context_tokens, run=None):
    attn = 4 * costs.dims(cfg)[1] * context_tokens * cfg["num_hidden_layers"]
    return lanes * (2 * _per_token_weights(cfg) + attn)


def prefill_flops(cfg, n_tokens, run=None):
    head = cfg["vocab_size"] * cfg["hidden_size"]
    attn = 2 * costs.dims(cfg)[1] * n_tokens * n_tokens \
        * cfg["num_hidden_layers"]
    return 2.0 * (_per_token_weights(cfg) - head) * n_tokens \
        + 2.0 * head + attn
