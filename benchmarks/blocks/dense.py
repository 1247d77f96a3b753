"""The dense block: grouped-query attention + SwiGLU, RMSNorm, no biases.
Every layer has the same nine tensors and every token passes through all
of them, so a decode step reads each weight once whatever the lanes hold,
and the costs need nothing of the ``run``.

A block file is the one place that knows a block's shapes (``ggufgen
.block_of`` finds it by the configuration's ``block`` key):

- ``tensor_plan(cfg)``: (name, numpy-order shape of any rank, ggml type
  from ``cfg["gguf"]["tensor_types"]``) of every tensor, in file order;
- ``metadata(cfg, arch)``: (key, ``u32`` | ``f32`` | ``str`` | ``bool``,
  value) of the GGUF keys between ``general.*`` and ``tokenizer.*``;
- ``decode_step_bytes``, ``decode_step_flops``, ``prefill_flops``: what the
  algorithm needs, as ``costs.py`` says; ``run`` is the traced run's dict
  (None from a caller that has none), for a block whose step depends on
  what the program counted.
"""

import costs
from ggufgen import transformer_metadata


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
            (p + "ffn_norm.weight", (d,), "F32"),
            (p + "ffn_gate.weight", (f, d), tt["ffn_gate"]),
            (p + "ffn_up.weight", (f, d), tt["ffn_up"]),
            (p + "ffn_down.weight", (d, f), tt["ffn_down"]),
        ]
    plan += [("output_norm.weight", (d,), "F32"),
             ("output.weight", (v, d), tt["output"])]
    return plan


def metadata(cfg, arch):
    return transformer_metadata(cfg, arch)


def decode_step_bytes(cfg, lanes, context_tokens, kv_bytes=2, run=None):
    """One pass over the weights, the live context's keys and values of
    each lane, one embedding row a lane."""
    return (costs.weight_bytes_per_step(cfg)
            + lanes * context_tokens * costs.kv_bytes_per_token(cfg, kv_bytes)
            + lanes * cfg["hidden_size"] * 2)


def decode_step_flops(cfg, lanes, context_tokens, run=None):
    _, q_dim, _ = costs.dims(cfg)
    attn = 4 * q_dim * context_tokens * cfg["num_hidden_layers"]
    return lanes * (2 * costs.linear_params(cfg) + attn)


def prefill_flops(cfg, n_tokens, run=None):
    """Two per weight and token in the layers, the head for the last
    position only, and causal attention (QK^T and PV over half the
    square)."""
    _, q_dim, _ = costs.dims(cfg)
    head = cfg["vocab_size"] * cfg["hidden_size"]
    layers = costs.linear_params(cfg) - head
    attn = 2 * q_dim * n_tokens * n_tokens * cfg["num_hidden_layers"]
    return 2.0 * layers * n_tokens + 2.0 * head + attn
