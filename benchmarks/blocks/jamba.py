"""The ``jamba`` block (AI21-Jamba2-3B's ``model_type: jamba``; llama.cpp's
name and Mamba tensor names for the family as remembered): a mixer kind per
layer (``attn_layer_period`` / ``attn_layer_offset``: layer ``i`` attends
where ``i % period == offset``, every other layer is Mamba-1) and a dense
SwiGLU in every layer (``num_experts`` 1), RMSNorms, nothing rotated.

- ``ssm`` (Mamba-1 with the family's inner norms): ``ssm_in`` (2 x inner,
  hidden: x then z), ``ssm_conv1d`` (inner, conv taps, F32) and its bias,
  ``ssm_x`` (dt_rank + 2 x state, inner), ``ssm_dt_norm`` (dt_rank),
  ``ssm_b_norm`` / ``ssm_c_norm`` (state): RMSNorms on dt, B and C,
  ``ssm_dt`` (inner, dt_rank, F32) and its bias, ``ssm_a`` (inner, state),
  ``ssm_d`` (inner), ``ssm_out`` (hidden, inner); its cache is a float32
  state (inner x state) and conv - 1 carried rows, whatever the context;
- ``attn``: ``attn_q`` (heads x 128, hidden), ``attn_k`` / ``attn_v`` (KV
  heads x 128: ONE head), ``attn_output``, no biases; its cache holds the
  context, 512 B a position;
- ``output_norm`` and no ``output.weight``: the head is ``token_embd``.

The file states the kinds as ``attention.head_count_kv``, an array with 0 in
a scan layer.  ``ggufgen.write_gguf`` gives a block no say over a tensor's
VALUES, so ``ssm_a`` and ``ssm_dt.bias`` hold small random numbers and the
file says ``ssm.values = init_offsets`` (``blocks/phi4flash.py`` has why).

Costs are the ALGORITHM's, on the bytes the FILE stores: a step reads every
matrix once (the embedding table too: it is the head); every live lane's
states and carried rows are read and written; an attention layer reads its
live positions.
"""

import costs
from ggufgen import tensor_nbytes, transformer_metadata
from server import parse_gauge


def kinds(cfg):
    period, offset = cfg["attn_layer_period"], cfg["attn_layer_offset"]
    return ["attn" if i % period == offset else "ssm"
            for i in range(cfg["num_hidden_layers"])]


def n_kind(cfg, *names):
    return sum(k in names for k in kinds(cfg))


def ssm_sizes(cfg):
    """(inner, state, conv taps, dt rank)."""
    return (cfg["mamba_expand"] * cfg["hidden_size"], cfg["mamba_d_state"],
            cfg["mamba_d_conv"], cfg["mamba_dt_rank"])


def tensor_plan(cfg):
    d, v, f = cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"]
    _, q_dim, kv_dim = costs.dims(cfg)
    c, n, taps, r = ssm_sizes(cfg)
    tt = cfg["gguf"]["tensor_types"]
    plan = [("token_embd.weight", (v, d), tt["token_embd"])]
    for i, kind in enumerate(kinds(cfg)):
        p = f"blk.{i}."
        plan.append((p + "attn_norm.weight", (d,), "F32"))
        if kind == "ssm":
            plan += [
                (p + "ssm_in.weight", (2 * c, d), tt["ssm_in"]),
                (p + "ssm_conv1d.weight", (c, taps), "F32"),
                (p + "ssm_conv1d.bias", (c,), "F32"),
                (p + "ssm_x.weight", (r + 2 * n, c), tt["ssm_x"]),
                (p + "ssm_dt_norm.weight", (r,), "F32"),
                (p + "ssm_b_norm.weight", (n,), "F32"),
                (p + "ssm_c_norm.weight", (n,), "F32"),
                (p + "ssm_dt.weight", (c, r), "F32"),
                (p + "ssm_dt.bias", (c,), "F32"),
                (p + "ssm_a", (c, n), "F32"),
                (p + "ssm_d", (c,), "F32"),
                (p + "ssm_out.weight", (d, c), tt["ssm_out"]),
            ]
        else:
            plan += [(p + "attn_q.weight", (q_dim, d), tt["attn_q"]),
                     (p + "attn_k.weight", (kv_dim, d), tt["attn_k"]),
                     (p + "attn_v.weight", (kv_dim, d), tt["attn_v"]),
                     (p + "attn_output.weight", (d, q_dim),
                      tt["attn_output"])]
        plan += [(p + "ffn_norm.weight", (d,), "F32"),
                 (p + "ffn_gate.weight", (f, d), tt["ffn_gate"]),
                 (p + "ffn_up.weight", (f, d), tt["ffn_up"]),
                 (p + "ffn_down.weight", (d, f), tt["ffn_down"])]
    plan.append(("output_norm.weight", (d,), "F32"))
    return plan


def metadata(cfg, arch):
    if not cfg["tie_word_embeddings"] or cfg["mamba_proj_bias"] \
            or not cfg["mamba_conv_bias"] or cfg["num_experts"] != 1 \
            or cfg.get("sliding_window"):
        raise ValueError("an untied head, a projection bias, no conv bias, "
                         "routed experts or a sliding window is not written")
    hd = costs.dims(cfg)[0]
    c, n, taps, r = ssm_sizes(cfg)
    meta = [m for m in transformer_metadata(
        {**cfg, "rope_theta": 10000.0}, arch)
        if not m[0].endswith(".attention.head_count_kv")]
    meta += [
        (f"{arch}.attention.head_count_kv", "i32[]",
         [cfg["num_key_value_heads"] if k == "attn" else 0
          for k in kinds(cfg)]),
        (f"{arch}.attention.key_length", "u32", hd),
        (f"{arch}.attention.value_length", "u32", hd),
        (f"{arch}.ssm.conv_kernel", "u32", taps),
        (f"{arch}.ssm.inner_size", "u32", c),
        (f"{arch}.ssm.state_size", "u32", n),
        (f"{arch}.ssm.time_step_rank", "u32", r),
        (f"{arch}.ssm.values", "str", "init_offsets"),
    ]
    return meta


def file_bytes(cfg):
    """Every tensor of the file, the embedding table once: it is the head."""
    total = 0
    for _, shape, kind in tensor_plan(cfg):
        n = 1
        for dim in shape:
            n *= dim
        total += tensor_nbytes(kind, n)
    return total


def matrix_weights(cfg):
    """Weights of the matrices (the F32 taps and ``ssm_a`` are none)."""
    total = 0
    for name, shape, _ in tensor_plan(cfg):
        if len(shape) != 2 or name.endswith(("ssm_a", "ssm_conv1d.weight")):
            continue
        total += shape[0] * shape[1]
    return total


def live_lanes(lanes, run=None):
    """Lanes whose cache a decode step reads: the mean of the scheduler's
    gauge over the run's samples that saw a live lane, else every lane."""
    vals = [parse_gauge(text, "scheduler_lanes_live")
            for _, text in (run or {}).get("samples") or []]
    vals = [v for v in vals if v]
    return sum(vals) / len(vals) if vals else lanes


def state_bytes(cfg):
    """One sequence's float32 states and bf16 carried rows (8 519 680 +
    798 720 B at 26 layers of 5120 x 16 and 3 x 5120)."""
    c, n, taps, _ = ssm_sizes(cfg)
    return n_kind(cfg, "ssm") * c * (n * 4 + (taps - 1) * 2)


def kv_row_bytes(cfg, kv_bytes=2):
    """K and V of every KV head at one position of one layer (512 B)."""
    return 2 * costs.dims(cfg)[2] * kv_bytes


def cache_bytes_per_lane(cfg, n_ctx):
    return n_kind(cfg, "attn") * kv_row_bytes(cfg) * n_ctx + state_bytes(cfg)


def ssm_state_bytes_per_step(cfg, lanes, context_tokens=0, run=None):
    """The live lanes' states and carried rows, read and written."""
    return live_lanes(lanes, run) * 2 * state_bytes(cfg)


def ring_bytes_per_step(cfg, lanes, context_tokens, kv_bytes=2, run=None):
    """The keys and values a decode step's attention needs: every live
    lane's live positions in the attention layers."""
    return live_lanes(lanes, run) * n_kind(cfg, "attn") * context_tokens \
        * kv_row_bytes(cfg, kv_bytes)


def decode_step_bytes(cfg, lanes, context_tokens, kv_bytes=2, run=None):
    return (file_bytes(cfg)
            + ring_bytes_per_step(cfg, lanes, context_tokens, kv_bytes, run)
            + ssm_state_bytes_per_step(cfg, lanes, context_tokens, run)
            + lanes * cfg["hidden_size"] * 2)


def scan_ops_per_row(cfg):
    """(multiply-adds and other vector operations, exps) of ONE ssm layer's
    recurrence at one position (``blocks/phi4flash.py`` has the count: six
    and one a channel and state, two a channel)."""
    c, n, _, _ = ssm_sizes(cfg)
    return c * (6 * n + 2), c * n


def scan_bytes_per_row(cfg):
    """What ONE ssm layer's slice kernel moves a position: x and dt in, y
    out (float32 a channel), B and C."""
    c, n, _, _ = ssm_sizes(cfg)
    return 3 * 4 * c + 2 * 4 * n


def _attn_flops_per_pair(cfg):
    """A query head against one key position: its score and its share of
    the weighted sum, a head's width each."""
    return 4 * costs.dims(cfg)[0]


def decode_step_flops(cfg, lanes, context_tokens, run=None):
    ops, exps = scan_ops_per_row(cfg)
    attn = cfg["num_attention_heads"] * _attn_flops_per_pair(cfg) \
        * n_kind(cfg, "attn") * context_tokens
    return lanes * (2 * matrix_weights(cfg)
                    + n_kind(cfg, "ssm") * (ops + exps)) \
        + live_lanes(lanes, run) * attn


def prefill_flops(cfg, n_tokens, run=None):
    """What a prompt NEEDS: every layer's matrices and scans at every
    position, the causal half of attention in the attention layers, the
    head at the LAST position alone."""
    head = cfg["vocab_size"] * cfg["hidden_size"]
    ops, exps = scan_ops_per_row(cfg)
    pairs = n_kind(cfg, "attn") * n_tokens * n_tokens / 2
    return (2.0 * (matrix_weights(cfg) - head)
            + n_kind(cfg, "ssm") * (ops + exps)) * n_tokens \
        + 2.0 * head \
        + cfg["num_attention_heads"] * _attn_flops_per_pair(cfg) * pairs
