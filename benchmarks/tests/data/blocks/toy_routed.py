"""A toy routed block, kept as test data: the dense block's attention, and
in place of its feed-forward a router (an F32 matrix) over ``num_experts``
SwiGLU experts stacked in 3-D tensors, ``num_experts_per_tok`` of them per
token.  No program serves it; it proves that a second kind of block is new
files only (``tests/test_blocks.py``).  Its decode step reads the experts
the program *counted* (``router_experts_read`` over ``router_steps`` in the
run's ``/metrics`` samples), and without a run the most a step can touch.
"""

import costs
from counters import ratio
from ggufgen import tensor_nbytes, transformer_metadata

CALLS = []          # (function name, run is not None), for the tests


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


def _experts_read(cfg, lanes, run):
    """Experts a layer's step read: counted by the program where the run
    has the counters, else every expert the lanes could have picked."""
    counted = ratio(run, "router_experts_read", "router_steps") if run else None
    most = min(cfg["num_experts"], lanes * cfg["num_experts_per_tok"])
    return counted / cfg["num_hidden_layers"] if counted is not None else most


def _split(cfg):
    """(bytes, weights) of everything outside the experts, and of one
    expert of one layer."""
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


def decode_step_bytes(cfg, lanes, context_tokens, kv_bytes=2, run=None):
    CALLS.append(("decode_step_bytes", run is not None))
    rest_b, _, exp_b, _ = _split(cfg)
    return (rest_b
            + cfg["num_hidden_layers"] * _experts_read(cfg, lanes, run) * exp_b
            + lanes * context_tokens * costs.kv_bytes_per_token(cfg, kv_bytes)
            + lanes * cfg["hidden_size"] * 2)


def decode_step_flops(cfg, lanes, context_tokens, run=None):
    CALLS.append(("decode_step_flops", run is not None))
    _, rest_w, _, exp_w = _split(cfg)
    per_token = rest_w + cfg["num_hidden_layers"] \
        * cfg["num_experts_per_tok"] * exp_w
    attn = 4 * costs.dims(cfg)[1] * context_tokens * cfg["num_hidden_layers"]
    return lanes * (2 * per_token + attn)


def prefill_flops(cfg, n_tokens, run=None):
    CALLS.append(("prefill_flops", run is not None))
    _, rest_w, _, exp_w = _split(cfg)
    head = cfg["vocab_size"] * cfg["hidden_size"]
    per_token = rest_w - head + cfg["num_hidden_layers"] \
        * cfg["num_experts_per_tok"] * exp_w
    attn = 2 * costs.dims(cfg)[1] * n_tokens * n_tokens * cfg["num_hidden_layers"]
    return 2.0 * per_token * n_tokens + 2.0 * head + attn
