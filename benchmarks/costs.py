"""What a decode step must read and what a prefill must compute, from a
configuration file's shapes.  These are the algorithm's needs, not what the
program happens to do: weights in the types the file stores them in (one
pass per step, shared by all lanes), keys and values of the live context
only, the causal half of attention.  Roofline shares divide these by the
chip's peaks (``peaks.json``) and by measured device time.

Which tensors a step reads is the block's to say: ``decode_step_bytes``,
``decode_step_flops`` and ``prefill_flops`` are those of the
configuration's block file (``ggufgen.block_of``), which may read the
traced ``run`` for what shapes alone do not give (the experts a routed step
really read).  The sums over a tensor plan that any block can use are
here."""

from __future__ import annotations

import json
import os

from ggufgen import block_of, tensor_nbytes, tensor_plan

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an error,
    never a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"no peaks for device kind {device_kind!r} in peaks.json")
    return table[device_kind]


def dims(cfg: dict) -> tuple[int, int, int]:
    """(width of one head, of all query heads, of all key or value heads)."""
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
    _, _, kv_dim = dims(cfg)
    return 2 * cfg["num_hidden_layers"] * kv_dim * kv_bytes


def linear_params(cfg: dict) -> int:
    """Weights in the layers' matrices and the output head."""
    total = 0
    for name, shape, _ in tensor_plan(cfg):
        if len(shape) == 2 and name != "token_embd.weight":
            total += shape[0] * shape[1]
    return total


def decode_step_bytes(cfg: dict, lanes: int, context_tokens: float,
                      kv_bytes: int = 2, run: dict | None = None) -> float:
    """HBM bytes one decode step needs, by the configuration's block."""
    return block_of(cfg).decode_step_bytes(cfg, lanes, context_tokens,
                                           kv_bytes, run)


def decode_step_flops(cfg: dict, lanes: int, context_tokens: float,
                      run: dict | None = None) -> float:
    return block_of(cfg).decode_step_flops(cfg, lanes, context_tokens, run)


def prefill_flops(cfg: dict, n_tokens: int, run: dict | None = None) -> float:
    """FLOPs of one prompt of ``n_tokens``, by the configuration's block."""
    return block_of(cfg).prefill_flops(cfg, n_tokens, run)


def roofline_seconds(flops: float, nbytes: float, peak: dict
                     ) -> tuple[float, str]:
    """The least time the chip could take, and which peak bounds it."""
    t_c = flops / peak["bf16_flops"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "hbm")
