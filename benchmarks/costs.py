"""What a decode step must read and what a prefill must compute, from a
configuration file's shapes alone.  These are the algorithm's needs, not
what the program happens to do: weights in the types the file stores them
in (one pass per step, shared by all lanes), keys and values of the live
context only, the causal half of attention.  Roofline shares divide these
by the chip's peaks (``peaks.json``) and by measured device time."""

from __future__ import annotations

import json
import os

from ggufgen import tensor_nbytes, tensor_plan

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an error,
    never a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"no peaks for device kind {device_kind!r} in peaks.json")
    return table[device_kind]


def _dims(cfg: dict) -> tuple[int, int, int]:
    head_dim = cfg.get("head_dim") or \
        cfg["hidden_size"] // cfg["num_attention_heads"]
    return (head_dim, cfg["num_attention_heads"] * head_dim,
            cfg["num_key_value_heads"] * head_dim)


def weight_bytes_per_step(cfg: dict) -> int:
    """Bytes of every tensor a decode step multiplies by: all of the file
    but the embedding table, of which a step reads one row per lane."""
    total = 0
    for name, shape, kind in tensor_plan(cfg):
        n = 1
        for dim in shape:
            n *= dim
        if name == "token_embd.weight":
            continue
        total += tensor_nbytes(kind, n)
    return total


def kv_bytes_per_token(cfg: dict, kv_bytes: int = 2) -> int:
    """Key and value bytes one context position holds, all layers."""
    _, _, kv_dim = _dims(cfg)
    return 2 * cfg["num_hidden_layers"] * kv_dim * kv_bytes


def decode_step_bytes(cfg: dict, lanes: int, context_tokens: float,
                      kv_bytes: int = 2) -> float:
    """HBM bytes one decode step needs: one pass over the weights, the live
    context's keys and values of each lane, one embedding row a lane."""
    return (weight_bytes_per_step(cfg)
            + lanes * context_tokens * kv_bytes_per_token(cfg, kv_bytes)
            + lanes * cfg["hidden_size"] * 2)


def linear_params(cfg: dict) -> int:
    """Weights in the layers' matrices and the output head."""
    total = 0
    for name, shape, _ in tensor_plan(cfg):
        if len(shape) == 2 and name != "token_embd.weight":
            total += shape[0] * shape[1]
    return total


def decode_step_flops(cfg: dict, lanes: int, context_tokens: float) -> float:
    _, q_dim, _ = _dims(cfg)
    attn = 4 * q_dim * context_tokens * cfg["num_hidden_layers"]
    return lanes * (2 * linear_params(cfg) + attn)


def prefill_flops(cfg: dict, n_tokens: int) -> float:
    """FLOPs of one prompt of ``n_tokens``: two per weight and token in the
    layers, the head for the last position only, and causal attention
    (QK^T and PV over half the square)."""
    _, q_dim, _ = _dims(cfg)
    head = cfg["vocab_size"] * cfg["hidden_size"]
    layers = linear_params(cfg) - head
    attn = 2 * q_dim * n_tokens * n_tokens * cfg["num_hidden_layers"]
    return 2.0 * layers * n_tokens + 2.0 * head + attn


def roofline_seconds(flops: float, nbytes: float, peak: dict
                     ) -> tuple[float, str]:
    """The least time the chip could take, and which peak bounds it."""
    t_c = flops / peak["bf16_flops"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "hbm")
