"""The ``ouro`` block: layers that run several times.  The dense block's
nine tensors a layer (multi-head attention + SwiGLU, no biases) with a
second RMSNorm AFTER each sub-block (``post_attention_norm`` /
``post_ffw_norm``), the same ``num_hidden_layers`` layers run
``total_ut_steps`` passes a token, the final norm after every pass, and an
exit gate of one F32 row and its bias.

What the loop multiplies, and what it does not: a pass has its own keys and
values (a cache leaf per pass and layer: ``leaves``), the weights have not.
So a decode step needs ``total_ut_steps`` passes over the layers' stored
bytes (1.58 GB do not fit the chip's 128 MiB of VMEM: each pass reads them
from HBM again), the head once, and the live context of every leaf; a
prefill ``total_ut_steps`` times the layers' FLOPs.  Counted once,
``decode_step_roofline`` would read a quarter.
"""

import costs
from ggufgen import tensor_nbytes, transformer_metadata
from server import parse_gauge


def tensor_plan(cfg):
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
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
            (p + "post_attention_norm.weight", (d,), "F32"),
            (p + "ffn_norm.weight", (d,), "F32"),
            (p + "ffn_gate.weight", (f, d), tt["ffn_gate"]),
            (p + "ffn_up.weight", (f, d), tt["ffn_up"]),
            (p + "ffn_down.weight", (d, f), tt["ffn_down"]),
            (p + "post_ffw_norm.weight", (d,), "F32"),
        ]
    plan += [("output_norm.weight", (d,), "F32"),
             ("output.weight", (v, d), tt["output"]),
             ("ut_exit_gate.weight", (1, d), "F32"),
             ("ut_exit_gate.bias", (1,), "F32")]
    return plan


def metadata(cfg, arch):
    return transformer_metadata(cfg, arch) + [
        (f"{arch}.attention.key_length", "u32", costs.dims(cfg)[0]),
        (f"{arch}.ut_steps", "u32", cfg["total_ut_steps"]),
        (f"{arch}.early_exit_threshold", "f32", cfg["early_exit_threshold"]),
    ]


def passes(cfg):
    return int(cfg["total_ut_steps"])


def leaves(cfg):
    """Cache leaves of a sequence: one a (pass, layer) pair."""
    return passes(cfg) * cfg["num_hidden_layers"]


def split(cfg):
    """(stored bytes of the layers' tensors, of everything else a step
    multiplies by: the head, the final norm, the gate; weights in the
    layers' matrices, in the head)."""
    layer_b = rest_b = layer_w = head_w = 0
    for name, shape, kind in tensor_plan(cfg):
        n = 1
        for dim in shape:
            n *= dim
        if name == "token_embd.weight":
            continue
        if name.startswith("blk."):
            layer_b += tensor_nbytes(kind, n)
            layer_w += n if len(shape) == 2 else 0
        else:
            rest_b += tensor_nbytes(kind, n)
            head_w += n if name == "output.weight" else 0
    return layer_b, rest_b, layer_w, head_w


def live_lanes(lanes, run=None):
    """Lanes whose cache a decode step reads: the mean of the scheduler's
    gauge over the run's samples that saw a live lane, else every lane."""
    vals = [parse_gauge(text, "scheduler_lanes_live")
            for _, text in (run or {}).get("samples") or []]
    vals = [v for v in vals if v]
    return sum(vals) / len(vals) if vals else lanes


def ring_bytes_per_step(cfg, lanes, context_tokens, kv_bytes=2, run=None):
    """The keys and values a decode step's attention needs: every live
    lane's live positions in EVERY leaf (192 x 8192 B a position at 16 KV
    heads of 128, bf16)."""
    return live_lanes(lanes, run) * context_tokens * leaves(cfg) \
        * 2 * costs.dims(cfg)[2] * kv_bytes


def decode_step_bytes(cfg, lanes, context_tokens, kv_bytes=2, run=None):
    """``total_ut_steps`` passes over the layers as the file stores them,
    the head once, the live context of every leaf, one embedding row a
    lane."""
    layer_b, rest_b, _, _ = split(cfg)
    return (passes(cfg) * layer_b + rest_b
            + ring_bytes_per_step(cfg, lanes, context_tokens, kv_bytes, run)
            + lanes * cfg["hidden_size"] * 2)


def decode_step_flops(cfg, lanes, context_tokens, run=None):
    _, _, layer_w, head_w = split(cfg)
    attn = 4 * costs.dims(cfg)[1] * context_tokens * leaves(cfg)
    return lanes * (2 * (passes(cfg) * layer_w + head_w) + attn)


def prefill_flops(cfg, n_tokens, run=None):
    """Two per weight and token in the layers a PASS, the head for the last
    position only, causal attention (QK^T and PV over half the square) in
    every leaf."""
    _, _, layer_w, head_w = split(cfg)
    attn = 2 * costs.dims(cfg)[1] * n_tokens * n_tokens * leaves(cfg)
    return 2.0 * passes(cfg) * layer_w * n_tokens + 2.0 * head_w + attn
