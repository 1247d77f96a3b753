"""The ``exaone-moe`` block (K-EXAONE's; llama.cpp's name for the family as
remembered): an attention kind per layer and a feed-forward kind per layer.

- attention, every layer: ``attn_q`` (heads x ``head_dim`` rows, which is
  not ``hidden_size``), ``attn_k`` / ``attn_v`` (KV heads x head_dim),
  ``attn_q_norm`` / ``attn_k_norm`` (head_dim: RMSNorm over each head),
  ``attn_output``.  The layer's kind is ``layer_types[i]``:
  ``sliding_attention`` (causal over the last ``sliding_window`` positions,
  Q and K rotated; its cache holds WINDOW slots) or ``full_attention``
  (causal over all, unrotated; its cache holds the context).  The file
  states the kinds as ``attention.sliding_window_pattern`` (the period:
  every pattern-th layer is full), so the layers run must be whole periods
  of ``layer_types`` from its start;
- feed-forward: the first ``first_k_dense_replace`` layers dense SwiGLU of
  ``intermediate_size``; the others an F32 router over ``router_experts``
  (``ffn_gate_inp``, its choice bias ``exp_probs_b.bias``),
  ``num_experts_per_tok`` a token, experts of ``moe_intermediate_size`` in
  3-D ``ffn_*_exps`` tensors that hold ``num_experts`` of them from
  ``experts_held_first`` on (one chip's share of an expert-parallel layer:
  ``expert_held_first`` / ``expert_held_count``), plus ``num_shared_experts``
  shared ones (``ffn_*_shexp``) on every token.

Costs are the ALGORITHM's, on the bytes the FILE stores: a step reads every
matrix outside the experts once, of the held experts those the live lanes
picked (the program's counters), and of every live lane's keys and values
what the layer's kind attends to: the whole context in a full layer,
``min(context, sliding_window)`` positions in a sliding one.
"""

import costs
from counters import ratio
from ggufgen import tensor_nbytes, transformer_metadata
from server import parse_gauge


def n_layers(cfg):
    return cfg["num_hidden_layers"]


def kinds(cfg):
    """The layers' attention kinds as run: ``layer_types`` from its start."""
    return cfg["layer_types"][:n_layers(cfg)]


def n_kind(cfg, kind):
    return sum(k == kind for k in kinds(cfg))


def period(cfg):
    """``attention.sliding_window_pattern``: every period-th layer is full;
    an error where the layers run are not that."""
    ks = kinds(cfg)
    p = ks.index("full_attention") + 1
    want = ["full_attention" if (i + 1) % p == 0 else "sliding_attention"
            for i in range(len(ks))]
    if ks != want:
        raise ValueError(f"layer_types[:{len(ks)}] is no period of {p}")
    return p


def n_moe(cfg):
    return n_layers(cfg) - cfg["first_k_dense_replace"]


def router_experts(cfg):
    """The router's width: the published expert count, whatever is held."""
    return cfg.get("router_experts") or cfg["num_experts"]


def tensor_plan(cfg):
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    hd, q_dim, kv_dim = costs.dims(cfg)
    f, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    e_held, e_all = cfg["num_experts"], router_experts(cfg)
    sh = fe * cfg["num_shared_experts"]
    tt = cfg["gguf"]["tensor_types"]
    plan = [("token_embd.weight", (v, d), tt["token_embd"])]
    for i in range(n_layers(cfg)):
        p = f"blk.{i}."
        plan += [
            (p + "attn_norm.weight", (d,), "F32"),
            (p + "attn_q.weight", (q_dim, d), tt["attn_q"]),
            (p + "attn_k.weight", (kv_dim, d), tt["attn_k"]),
            (p + "attn_v.weight", (kv_dim, d), tt["attn_v"]),
            (p + "attn_q_norm.weight", (hd,), "F32"),
            (p + "attn_k_norm.weight", (hd,), "F32"),
            (p + "attn_output.weight", (d, q_dim), tt["attn_output"]),
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
    hd = costs.dims(cfg)[0]
    rope = cfg["rope_parameters"]
    if rope.get("rope_type", "default") != "default":
        raise ValueError(f"rope_type {rope['rope_type']!r} is not written")
    meta = transformer_metadata(
        {**cfg, "rope_theta": rope["rope_theta"]}, arch)
    meta += [
        (f"{arch}.attention.key_length", "u32", hd),
        (f"{arch}.attention.value_length", "u32", hd),
        (f"{arch}.attention.sliding_window_pattern", "u32", period(cfg)),
        (f"{arch}.leading_dense_block_count", "u32",
         cfg["first_k_dense_replace"]),
        (f"{arch}.expert_feed_forward_length", "u32",
         cfg["moe_intermediate_size"]),
        (f"{arch}.expert_count", "u32", router_experts(cfg)),
        (f"{arch}.expert_used_count", "u32", cfg["num_experts_per_tok"]),
        (f"{arch}.expert_shared_count", "u32", cfg["num_shared_experts"]),
        (f"{arch}.expert_weights_scale", "f32", cfg["routed_scaling_factor"]),
        (f"{arch}.expert_weights_norm", "bool", cfg["norm_topk_prob"]),
        (f"{arch}.expert_gating_func", "u32",
         {"softmax": 1, "sigmoid": 2}[cfg["scoring_func"]]),
        (f"{arch}.expert_group_count", "u32", cfg["n_group"]),
        (f"{arch}.expert_group_used_count", "u32", cfg["topk_group"]),
    ]
    if cfg["num_experts"] != router_experts(cfg):
        meta += [
            (f"{arch}.expert_held_first", "u32",
             cfg.get("experts_held_first", 0)),
            (f"{arch}.expert_held_count", "u32", cfg["num_experts"]),
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
    return min(cfg["num_experts"], lanes * cfg["num_experts_per_tok"])


def held_picks_per_token(cfg, run):
    """Picks of a token that reach an expert held here, as the program
    counted them (``expert_picks_held_total`` over
    ``expert_picks_routed_total``), else the held share of the router."""
    share = ratio(run, "expert_picks_held_total",
                  "expert_picks_routed_total") if run else None
    if share is None:
        share = cfg["num_experts"] / router_experts(cfg)
    return share * cfg["num_experts_per_tok"]


def expert_bytes_per_step(cfg, lanes, run=None):
    return n_moe(cfg) * experts_read(cfg, lanes, run) * split(cfg)[2]


def layer_positions(cfg, context_tokens):
    """Cached positions a token at ``context_tokens`` attends to, summed
    over the layers: the context in a full layer, the window in a sliding
    one."""
    return n_kind(cfg, "full_attention") * context_tokens \
        + n_kind(cfg, "sliding_attention") \
        * min(context_tokens, cfg["sliding_window"])


def live_lanes(lanes, run=None):
    """Lanes whose cache a decode step reads: the mean of the scheduler's
    gauge over the run's samples that saw a live lane (the decode programs
    step only while some lane is alive: a sample that saw none was taken
    during a prefill or between requests), else every lane."""
    vals = [parse_gauge(text, "scheduler_lanes_live")
            for _, text in (run or {}).get("samples") or []]
    vals = [v for v in vals if v]
    return sum(vals) / len(vals) if vals else lanes


def ring_bytes_per_step(cfg, lanes, context_tokens, kv_bytes=2, run=None):
    """The keys and values a decode step's attention needs: every live
    lane's live positions of both layer kinds, K and V of every KV head
    (4096 B a layer-position at 8 heads of 128, bf16)."""
    return live_lanes(lanes, run) * layer_positions(cfg, context_tokens) \
        * 2 * costs.dims(cfg)[2] * kv_bytes


def decode_step_bytes(cfg, lanes, context_tokens, kv_bytes=2, run=None):
    return (split(cfg)[0] + expert_bytes_per_step(cfg, lanes, run)
            + ring_bytes_per_step(cfg, lanes, context_tokens, kv_bytes, run)
            + lanes * cfg["hidden_size"] * 2)


def _per_token_weights(cfg, run=None):
    _, rest_w, _, exp_w = split(cfg)
    return rest_w + n_moe(cfg) * held_picks_per_token(cfg, run) * exp_w


def decode_step_flops(cfg, lanes, context_tokens, run=None):
    attn = 4 * costs.dims(cfg)[1] * layer_positions(cfg, context_tokens)
    return lanes * 2 * _per_token_weights(cfg, run) \
        + live_lanes(lanes, run) * attn


def prefill_flops(cfg, n_tokens, run=None):
    """One pass over the per-token weights a position (the head once), and
    the causal half of attention in a full layer, the window's band in a
    sliding one: a query at position p scores min(p + 1, window) keys."""
    head = cfg["vocab_size"] * cfg["hidden_size"]
    w = min(cfg["sliding_window"], n_tokens)
    band = n_tokens * w - w * (w - 1) / 2          # sum of min(p + 1, w)
    pairs = n_kind(cfg, "full_attention") * n_tokens * n_tokens / 2 \
        + n_kind(cfg, "sliding_attention") * band
    return 2.0 * (_per_token_weights(cfg, run) - head) * n_tokens \
        + 2.0 * head + 4 * costs.dims(cfg)[1] * pairs
