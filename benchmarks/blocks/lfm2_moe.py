"""The ``lfm2moe`` block (LFM2-24B-A2B's ``model_type: lfm2_moe``; llama.cpp's
name for the family as remembered): a mixer kind per layer and a
feed-forward kind per layer.

- mixer, by ``layer_types[i]``: ``conv`` is a gated short convolution,
  ``shortconv.in_proj`` (3 x hidden rows: b, c, x), the F32 depthwise taps
  ``shortconv.conv`` (hidden, ``conv_L_cache``) and ``shortconv.out_proj``;
  its cache is the last ``conv_L_cache - 1`` inputs of the taps, whatever
  the context.  ``full_attention`` is GQA, ``attn_q`` / ``attn_k`` /
  ``attn_v`` / ``attn_output`` with ``attn_q_norm`` / ``attn_k_norm`` over
  each head's width (``hidden_size / num_attention_heads``: 64); its cache
  holds the context.  Every layer has ``attn_norm`` (the family's
  ``operator_norm``) and ``ffn_norm``.  The file states the kinds as
  ``attention.head_count_kv``, an array with 0 in a conv layer;
- feed-forward: the first ``num_dense_layers`` layers dense SwiGLU of
  ``intermediate_size``; the others an F32 router over ``num_experts``
  (``ffn_gate_inp``, its choice bias ``exp_probs_b.bias``),
  ``num_experts_per_tok`` a token, experts of ``moe_intermediate_size`` in
  3-D ``ffn_*_exps`` tensors, ALL held, no shared expert;
- the final norm (``output_norm`` here; ``token_embd_norm`` in llama.cpp's
  files, as remembered) and no ``output.weight``: the head is the embedding.

Costs are the ALGORITHM's, on the bytes the FILE stores: a step reads every
matrix outside the experts once (the embedding table too: it is the head),
of the experts those the live lanes picked (the program's counters), of
every live lane's keys and values the whole context in an attention layer,
and reads and writes its carried rows in a conv layer.
"""

import costs
from counters import ratio
from ggufgen import tensor_nbytes, transformer_metadata
from server import parse_gauge


def n_layers(cfg):
    return cfg["num_hidden_layers"]


def kinds(cfg):
    """The layers' mixer kinds as run: ``layer_types`` from its start."""
    return cfg["layer_types"][:n_layers(cfg)]


def n_kind(cfg, kind):
    return sum(k == kind for k in kinds(cfg))


def n_moe(cfg):
    return n_layers(cfg) - cfg["num_dense_layers"]


def tensor_plan(cfg):
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    hd, q_dim, kv_dim = costs.dims(cfg)
    f, fe, e = cfg["intermediate_size"], cfg["moe_intermediate_size"], \
        cfg["num_experts"]
    tt = cfg["gguf"]["tensor_types"]
    plan = [("token_embd.weight", (v, d), tt["token_embd"])]
    for i, kind in enumerate(kinds(cfg)):
        p = f"blk.{i}."
        plan.append((p + "attn_norm.weight", (d,), "F32"))
        if kind == "conv":
            plan += [
                (p + "shortconv.in_proj.weight", (3 * d, d),
                 tt["shortconv.in_proj"]),
                (p + "shortconv.conv.weight", (d, cfg["conv_L_cache"]), "F32"),
                (p + "shortconv.out_proj.weight", (d, d),
                 tt["shortconv.out_proj"]),
            ]
        else:
            plan += [
                (p + "attn_q.weight", (q_dim, d), tt["attn_q"]),
                (p + "attn_k.weight", (kv_dim, d), tt["attn_k"]),
                (p + "attn_v.weight", (kv_dim, d), tt["attn_v"]),
                (p + "attn_q_norm.weight", (hd,), "F32"),
                (p + "attn_k_norm.weight", (hd,), "F32"),
                (p + "attn_output.weight", (d, q_dim), tt["attn_output"]),
            ]
        plan.append((p + "ffn_norm.weight", (d,), "F32"))
        if i < cfg["num_dense_layers"]:
            plan += [
                (p + "ffn_gate.weight", (f, d), tt["ffn_gate"]),
                (p + "ffn_up.weight", (f, d), tt["ffn_up"]),
                (p + "ffn_down.weight", (d, f), tt["ffn_down"]),
            ]
            continue
        plan += [
            (p + "ffn_gate_inp.weight", (e, d), "F32"),
            (p + "exp_probs_b.bias", (e,), "F32"),
            (p + "ffn_gate_exps.weight", (e, fe, d), tt["ffn_gate_exps"]),
            (p + "ffn_up_exps.weight", (e, fe, d), tt["ffn_up_exps"]),
            (p + "ffn_down_exps.weight", (e, d, fe), tt["ffn_down_exps"]),
        ]
    # (the final norm: llama.cpp's converter names it ``token_embd_norm``
    # for this family, as remembered, and the loader reads either name; this
    # file keeps ``output_norm``, which tests/test_ggufgen.py holds every
    # configuration's plan to)
    plan.append(("output_norm.weight", (d,), "F32"))
    return plan


def metadata(cfg, arch):
    hd = costs.dims(cfg)[0]
    rope = cfg["rope_parameters"]
    if rope.get("rope_type", "default") != "default":
        raise ValueError(f"rope_type {rope['rope_type']!r} is not written")
    if cfg["conv_bias"]:
        raise ValueError("conv_bias is not written")
    meta = [m for m in transformer_metadata(
        {**cfg, "rope_theta": rope["rope_theta"],
         "rms_norm_eps": cfg["norm_eps"]}, arch)
        if not m[0].endswith(".attention.head_count_kv")]
    meta += [
        (f"{arch}.attention.head_count_kv", "i32[]",
         [cfg["num_key_value_heads"] if k == "full_attention" else 0
          for k in kinds(cfg)]),
        (f"{arch}.shortconv.l_cache", "u32", cfg["conv_L_cache"]),
        (f"{arch}.attention.key_length", "u32", hd),
        (f"{arch}.attention.value_length", "u32", hd),
        (f"{arch}.leading_dense_block_count", "u32", cfg["num_dense_layers"]),
        (f"{arch}.expert_feed_forward_length", "u32",
         cfg["moe_intermediate_size"]),
        (f"{arch}.expert_count", "u32", cfg["num_experts"]),
        (f"{arch}.expert_used_count", "u32", cfg["num_experts_per_tok"]),
        (f"{arch}.expert_weights_scale", "f32",
         float(cfg["routed_scaling_factor"])),
        (f"{arch}.expert_weights_norm", "bool", cfg["norm_topk_prob"]),
        (f"{arch}.expert_gating_func", "u32", 2),       # sigmoid
    ]
    return meta


def split(cfg):
    """(bytes, matrix weights) of everything outside the routed experts,
    the embedding table counted once as the head it is, and of ONE routed
    expert of one layer."""
    rest_b = rest_w = exp_b = exp_w = 0
    first_moe = f"blk.{cfg['num_dense_layers']}."
    for name, shape, kind in tensor_plan(cfg):
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


def expert_bytes(cfg):
    """One routed expert's stored bytes (gate, up, down)."""
    return split(cfg)[2]


def experts_read(cfg, lanes, run):
    """Experts a routed layer's step read: counted by the program where the
    run has the counters, else what the lanes' picks could reach."""
    counted = ratio(run, "experts_read_total", "expert_layer_steps_total") \
        if run else None
    if counted is not None:
        return counted
    return min(cfg["num_experts"], lanes * cfg["num_experts_per_tok"])


def expert_bytes_per_step(cfg, lanes, run=None):
    return n_moe(cfg) * experts_read(cfg, lanes, run) * expert_bytes(cfg)


def lanes_alive(lanes, run=None):
    """Lanes whose cache a decode step reads: the mean of the scheduler's
    gauge over the run's samples that saw a live lane, else every lane."""
    vals = [parse_gauge(text, "scheduler_lanes_live")
            for _, text in (run or {}).get("samples") or []]
    vals = [v for v in vals if v]
    return sum(vals) / len(vals) if vals else lanes


def conv_state_bytes(cfg):
    """One sequence's carried rows: ``conv_L_cache - 1`` rows of hidden
    bf16 a conv layer (122 880 B at 15 layers of 2 x 2048)."""
    return n_kind(cfg, "conv") * (cfg["conv_L_cache"] - 1) \
        * cfg["hidden_size"] * 2


def cache_bytes_per_lane(cfg, n_ctx):
    """One sequence's cache: K and V rows of every KV head a position and
    attention layer, and the conv layers' carried rows."""
    return n_kind(cfg, "full_attention") * n_ctx * 2 * costs.dims(cfg)[2] * 2 \
        + conv_state_bytes(cfg)


def cache_bytes_per_step(cfg, lanes, context_tokens, kv_bytes=2, run=None):
    """What a decode step's mixers need of the cache: every live lane's
    context in an attention layer (K and V of every KV head), its carried
    rows in a conv layer read and written."""
    live = lanes_alive(lanes, run)
    ring = n_kind(cfg, "full_attention") * context_tokens \
        * 2 * costs.dims(cfg)[2] * kv_bytes
    return live * (ring + 2 * conv_state_bytes(cfg))


def decode_step_bytes(cfg, lanes, context_tokens, kv_bytes=2, run=None):
    return (split(cfg)[0] + expert_bytes_per_step(cfg, lanes, run)
            + cache_bytes_per_step(cfg, lanes, context_tokens, kv_bytes, run)
            + lanes * cfg["hidden_size"] * 2)


def _per_token_weights(cfg):
    _, rest_w, _, exp_w = split(cfg)
    return rest_w + n_moe(cfg) * cfg["num_experts_per_tok"] * exp_w


def conv_flops_per_token(cfg):
    """The taps and the two gates of the conv layers, a token."""
    return n_kind(cfg, "conv") * cfg["hidden_size"] \
        * (2 * cfg["conv_L_cache"] + 2)


def decode_step_flops(cfg, lanes, context_tokens, run=None):
    attn = 4 * costs.dims(cfg)[1] * n_kind(cfg, "full_attention") \
        * context_tokens
    return lanes * (2 * _per_token_weights(cfg) + conv_flops_per_token(cfg)) \
        + lanes_alive(lanes, run) * attn


def prefill_flops(cfg, n_tokens, run=None):
    """One pass over the per-token weights a position (the head once), the
    conv layers' taps, and the causal half of attention in an attention
    layer."""
    head = cfg["vocab_size"] * cfg["hidden_size"]
    pairs = n_kind(cfg, "full_attention") * n_tokens * n_tokens / 2
    return (2.0 * (_per_token_weights(cfg) - head)
            + conv_flops_per_token(cfg)) * n_tokens \
        + 2.0 * head + 4 * costs.dims(cfg)[1] * pairs


def expert_slice_cost(cfg, rows):
    """(bytes, FLOPs) of the routed experts in ONE prefill slice of ``rows``
    tokens: every expert's stored bytes in every routed layer (at 4 picks
    of 64 a slice of 256 rows touches them all), and the picked experts'
    products on the slice's rows."""
    _, _, exp_b, exp_w = split(cfg)
    return (n_moe(cfg) * cfg["num_experts"] * exp_b,
            2.0 * n_moe(cfg) * cfg["num_experts_per_tok"] * exp_w * rows)
