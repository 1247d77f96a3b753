"""The ``sala`` block (``minicpm-sala``): a stack of TWO layer kinds in the
order ``mixer_types`` gives, every layer with the same SwiGLU feed-forward.

- ``lightning-attn``: ``lightning_nh`` heads of ``lightning_head_dim``,
  five square matrices (``attn_q``, ``attn_k``, ``attn_v``, ``attn_output``,
  ``attn_gate``), per-head norm gains ``attn_{q,k,out}_norm``; its cache is
  a float32 state of head_dim x head_dim per head, whatever the length.
- ``minicpm4``: ``num_attention_heads`` query heads on
  ``num_key_value_heads`` KV heads (``attn_k`` / ``attn_v`` are narrow),
  ``attn_gate``, per-head norm gains ``attn_{q,k}_norm``; its cache is a
  ring, one compressed key every ``kernel_stride`` positions, and past
  ``dense_len`` a query reads ``init_blocks`` + window + ``topk`` blocks.

What a decode step must move beside one pass over the weights: per live
lane every linear layer's state, read and written (:func:`lin_state_bytes_
per_step`), and per sparse layer what the branch the lane is in must read:
the ring's live part before ``dense_len``, the visible compressed keys and
the selected blocks after it (:func:`sparse_read_bytes_per_step`).  Which
branch, how many blocks and how many lanes are live only the program knows,
so they are the program's counters over the window (``lin_state_updates_
total``, ``sparse_blocks_{read,visible}_total``, ``ring_slots_live_total``
in the run's ``/metrics`` samples) where the run has them, else they are
computed from the mean context with every lane full.
"""

import costs
from counters import delta
from ggufgen import transformer_metadata
from server import parse_gauge

SPARSE = {"kernel_size": 32, "kernel_stride": 16, "block_size": 64,
          "topk": 64, "window_size": 2048, "init_blocks": 1,
          "dense_len": 8192}


def sparse_config(cfg):
    """The sparse layers' constants: the configuration's ``assumed.
    sparse_config`` (the catalog row's config has none), else the family's
    published ones."""
    return {**SPARSE, **((cfg.get("assumed") or {}).get("sparse_config")
                         or {})}


def kinds(cfg):
    return [{"minicpm4": "sp", "lightning-attn": "lin"}[m]
            for m in cfg["mixer_types"]]


def tensor_plan(cfg):
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hd, q_dim, kv_dim = costs.dims(cfg)
    lin_dim = cfg["lightning_nh"] * cfg["lightning_head_dim"]
    lin_kv = cfg["lightning_nkv"] * cfg["lightning_head_dim"]
    v = cfg["vocab_size"]
    tt = cfg["gguf"]["tensor_types"]
    plan = [("token_embd.weight", (v, d), tt["token_embd"])]
    for i, kind in enumerate(kinds(cfg)):
        p = f"blk.{i}."
        q, kv, h = (lin_dim, lin_kv, cfg["lightning_head_dim"]) \
            if kind == "lin" else (q_dim, kv_dim, hd)
        plan += [
            (p + "attn_norm.weight", (d,), "F32"),
            (p + "attn_q.weight", (q, d), tt["attn_q"]),
            (p + "attn_k.weight", (kv, d), tt["attn_k"]),
            (p + "attn_v.weight", (kv, d), tt["attn_v"]),
            (p + "attn_output.weight", (d, q), tt["attn_output"]),
            (p + "attn_gate.weight", (q, d), tt["attn_gate"]),
            (p + "attn_q_norm.weight", (h,), "F32"),
            (p + "attn_k_norm.weight", (h,), "F32"),
        ]
        if kind == "lin":
            plan.append((p + "attn_out_norm.weight", (h,), "F32"))
        plan += [
            (p + "ffn_norm.weight", (d,), "F32"),
            (p + "ffn_gate.weight", (f, d), tt["ffn_gate"]),
            (p + "ffn_up.weight", (f, d), tt["ffn_up"]),
            (p + "ffn_down.weight", (d, f), tt["ffn_down"]),
        ]
    plan += [("output_norm.weight", (d,), "F32"),
             ("output.weight", (v, d), tt["output"])]
    return plan


def metadata(cfg, arch):
    sp = sparse_config(cfg)
    return transformer_metadata(cfg, arch) + [
        (f"{arch}.mixer_types", "str", ",".join(cfg["mixer_types"])),
        (f"{arch}.lightning.head_count", "u32", cfg["lightning_nh"]),
        (f"{arch}.embedding_scale", "f32", float(cfg["scale_emb"])),
        (f"{arch}.residual_scale", "f32",
         cfg["scale_depth"] / cfg["num_hidden_layers"] ** 0.5),
        (f"{arch}.logit_scale", "f32",
         cfg["dim_model_base"] / cfg["hidden_size"]),
    ] + [(f"{arch}.sparse.{k}", "u32", sp[k]) for k in SPARSE]


def n_of(cfg, kind):
    return kinds(cfg).count(kind)


def state_bytes(cfg):
    """One sequence's state over all linear layers (float32)."""
    return n_of(cfg, "lin") * cfg["lightning_nh"] \
        * cfg["lightning_head_dim"] ** 2 * 4


def live_lanes(lanes, run=None):
    """Lanes whose cache a step touches: the mean of the scheduler's gauge
    over the run's samples, else every lane."""
    vals = [parse_gauge(text, "scheduler_lanes_live")
            for _, text in (run or {}).get("samples") or []]
    vals = [v for v in vals if v is not None]
    return sum(vals) / len(vals) if vals else lanes


def lane_steps(run):
    """Decode steps summed over live lanes, as the program counted them
    (one state update a linear layer); None without the counter."""
    n = delta(run, "lin_state_updates_total") if run else None
    return n / n_of(run["config"], "lin") if n else None


def lin_state_bytes_per_step(cfg, lanes, context_tokens=0, kv_bytes=2,
                             run=None):
    """Every live lane's state read and written once."""
    return live_lanes(lanes, run) * 2 * state_bytes(cfg)


def blocks_at(cfg, position):
    """(blocks read, blocks visible, compressed keys visible) of a query
    at ``position`` in the sparse branch."""
    sp = sparse_config(cfg)
    b = sp["block_size"]
    visible = position // b + 1
    first_win = max((position - sp["window_size"] + 1) // b, 0)
    forced = len(set(range(min(sp["init_blocks"], visible)))
                 | set(range(first_win, visible)))
    return (min(forced + sp["topk"], visible), visible,
            max((position + 1 - sp["kernel_size"]) // sp["kernel_stride"] + 1,
                0))


def sparse_read_bytes_per_step(cfg, lanes, context_tokens, kv_bytes=2,
                               run=None):
    """What the sparse layers' attention of one decode step has to read:
    per live lane, sparse layer and KV head the selected blocks (keys and
    values) and the visible compressed keys past ``dense_len``, the live
    part of the ring before it."""
    hd, _, kv_dim = costs.dims(cfg)
    sp = sparse_config(cfg)
    row = hd * kv_bytes                       # one key or value, one head
    steps = lane_steps(run)
    if steps:
        read = delta(run, "sparse_blocks_read_total") or 0.0
        visible = delta(run, "sparse_blocks_visible_total") or 0.0
        ring = delta(run, "ring_slots_live_total") or 0.0
        per_lane_step = (
            read * sp["block_size"] * 2 * row
            + visible * (sp["block_size"] // sp["kernel_stride"]) * row
            + ring * n_of(cfg, "sp") * 2 * kv_dim * kv_bytes) / steps
        return live_lanes(lanes, run) * per_lane_step
    heads = cfg["num_key_value_heads"] * n_of(cfg, "sp")
    pos = int(context_tokens)
    if pos + 1 >= sp["dense_len"]:
        n_read, _, n_kc = blocks_at(cfg, pos)
        per_lane = heads * (n_read * sp["block_size"] * 2 + n_kc) * row
    else:
        per_lane = heads * (pos + 1) * 2 * row
    return lanes * per_lane


def decode_step_bytes(cfg, lanes, context_tokens, kv_bytes=2, run=None):
    """One pass over the weights as the file stores them, every live
    lane's state read and written, what the sparse layers' branch must
    read, one embedding row a lane."""
    return (costs.weight_bytes_per_step(cfg)
            + lin_state_bytes_per_step(cfg, lanes, run=run)
            + sparse_read_bytes_per_step(cfg, lanes, context_tokens, kv_bytes,
                                         run)
            + lanes * cfg["hidden_size"] * 2)


def decode_step_flops(cfg, lanes, context_tokens, run=None):
    """Two per weight; per linear layer and head the outer product, the
    decay and the read of the state (5 d^2); per sparse layer QK^T and PV
    over what the branch reads."""
    hd, q_dim, _ = costs.dims(cfg)
    sp = sparse_config(cfg)
    lin = 5 * cfg["lightning_nh"] * cfg["lightning_head_dim"] ** 2 \
        * n_of(cfg, "lin")
    pos = int(context_tokens)
    keys = blocks_at(cfg, pos)[0] * sp["block_size"] + blocks_at(cfg, pos)[2] \
        if pos + 1 >= sp["dense_len"] else pos + 1
    return lanes * (2 * costs.linear_params(cfg) + lin
                    + 4 * q_dim * keys * n_of(cfg, "sp"))


def prefill_flops(cfg, n_tokens, run=None):
    """Two per weight and token in the layers, the head for the last
    position only; the linear layers' chunk form (scores inside a slice of
    256, the state's read and update: about 4 d (256 + 2 d) a token and
    head); the sparse layers' causal attention over what each query's
    branch reads."""
    hd, q_dim, _ = costs.dims(cfg)
    sp = sparse_config(cfg)
    head = cfg["vocab_size"] * cfg["hidden_size"]
    layers = costs.linear_params(cfg) - head
    d = cfg["lightning_head_dim"]
    lin = 4 * d * (256 + 2 * d) * cfg["lightning_nh"] * n_of(cfg, "lin") \
        * n_tokens
    n = int(n_tokens)
    dense = min(n, sp["dense_len"] - 1)
    pairs = dense * (dense + 1) / 2 + sum(
        blocks_at(cfg, t)[0] * sp["block_size"] + blocks_at(cfg, t)[2]
        for t in range(dense, n))
    return 2.0 * layers * n_tokens + 2.0 * head + lin \
        + 4 * q_dim * pairs * n_of(cfg, "sp")
