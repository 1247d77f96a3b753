"""The ``evabyte`` block: multi-head EVA attention (an exact blocked window
of ``window_size`` positions plus one summary per ``chunk_size`` positions
of every earlier window, pooled with two F32 vectors a head and layer,
``attn_eva_phi`` / ``attn_eva_mu``), SwiGLU, unit-offset RMSNorm (gains
stored as applied), and ``num_pred_heads`` prediction heads in one output
matrix of ``vocab_size * num_pred_heads`` rows.

What a decode step must read of the cache is not the context: per lane the
live slots of ONE window (position mod W, + 1) and one summary for every
chunk of the windows before (position // W x W / C), each entry a key and
a value over all layers (``costs.kv_bytes_per_token``: 524 KB here).  Both
depend on where in their windows the lanes stand, which only the program
knows: so the entries a lane-step needed are those the program *counted*
(``eva_window_slots_live_total + eva_summaries_live_total`` over
``eva_lane_steps_total`` in the run's ``/metrics`` samples), and the lanes
that read them are the live ones (the mean of ``scheduler_lanes_live``).
Without a run (or on a program without the counters) they are computed
from the mean context, every lane full.
"""

import costs
from counters import delta, ratio
from ggufgen import transformer_metadata
from server import parse_gauge


def tensor_plan(cfg):
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hd, q_dim, kv_dim = costs.dims(cfg)
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
            (p + "attn_eva_phi.weight", (cfg["num_attention_heads"], hd), "F32"),
            (p + "attn_eva_mu.weight", (cfg["num_attention_heads"], hd), "F32"),
            (p + "ffn_norm.weight", (d,), "F32"),
            (p + "ffn_gate.weight", (f, d), tt["ffn_gate"]),
            (p + "ffn_up.weight", (f, d), tt["ffn_up"]),
            (p + "ffn_down.weight", (d, f), tt["ffn_down"]),
        ]
    plan += [("output_norm.weight", (d,), "F32"),
             ("output.weight", (v * cfg["num_pred_heads"], d), tt["output"])]
    return plan


def metadata(cfg, arch):
    return transformer_metadata(cfg, arch) + [
        (f"{arch}.attention.window_size", "u32", cfg["window_size"]),
        (f"{arch}.attention.chunk_size", "u32", cfg["chunk_size"]),
        (f"{arch}.prediction_heads", "u32", cfg["num_pred_heads"]),
    ]


def entries_at(cfg, position):
    """Cache entries the attention of a query at ``position`` needs: the
    exact keys of its own window up to itself, one summary per chunk of
    the windows before."""
    w, c = cfg["window_size"], cfg["chunk_size"]
    return position % w + 1 + position // w * (w // c)


def live_entries(cfg, context_tokens, run=None):
    """Entries one lane's decode step needed, averaged over the window:
    counted by the program where the run has the counters, else those of
    a query at the mean context."""
    if run:
        w = delta(run, "eva_window_slots_live_total")
        s = delta(run, "eva_summaries_live_total")
        steps = delta(run, "eva_lane_steps_total")
        if w is not None and s is not None and steps:
            return (w + s) / steps
    return entries_at(cfg, int(context_tokens))


def live_lanes(lanes, run=None):
    """Lanes whose cache a step reads: the mean of the scheduler's gauge
    over the run's samples, else every lane."""
    vals = [parse_gauge(text, "scheduler_lanes_live")
            for _, text in (run or {}).get("samples") or []]
    vals = [v for v in vals if v is not None]
    return sum(vals) / len(vals) if vals else lanes


def cache_read_bytes_per_step(cfg, lanes, context_tokens, kv_bytes=2,
                              run=None):
    """What the attention of one decode step has to read of the cache."""
    return live_lanes(lanes, run) * live_entries(cfg, context_tokens, run) \
        * costs.kv_bytes_per_token(cfg, kv_bytes)


def decode_step_bytes(cfg, lanes, context_tokens, kv_bytes=2, run=None):
    """One pass over the weights as the file stores them, the live window
    and summaries of each live lane, one embedding row a lane."""
    return (costs.weight_bytes_per_step(cfg)
            + cache_read_bytes_per_step(cfg, lanes, context_tokens, kv_bytes,
                                        run)
            + lanes * cfg["hidden_size"] * 2)


def decode_step_flops(cfg, lanes, context_tokens, run=None):
    _, q_dim, _ = costs.dims(cfg)
    attn = 4 * q_dim * live_entries(cfg, context_tokens, run) \
        * cfg["num_hidden_layers"]
    return lanes * (2 * costs.linear_params(cfg) + attn)


def prefill_flops(cfg, n_tokens, run=None):
    """Two per weight and token in the layers, the heads for the last
    position only, and for every query its window's keys up to itself and
    the summaries before (QK^T and PV).  The pooling of a closed window
    (2 x 2 x W x head width a head) is left out: a thousandth of this."""
    _, q_dim, _ = costs.dims(cfg)
    head = cfg["vocab_size"] * cfg["num_pred_heads"] * cfg["hidden_size"]
    layers = costs.linear_params(cfg) - head
    pairs = sum(entries_at(cfg, t) for t in range(int(n_tokens)))
    attn = 4 * q_dim * pairs * cfg["num_hidden_layers"]
    return 2.0 * layers * n_tokens + 2.0 * head + attn
