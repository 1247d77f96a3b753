"""The ``phi4flash`` block (Phi-4-mini-flash-reasoning's ``model_type:
phi4flash``; this repo's name for the architecture, llama.cpp's Mamba names
for the state-space tensors): a mixer kind per layer (the configuration's
``mixer_types``) and a dense SwiGLU in every layer, LayerNorms with biases.

- ``ssm`` (Mamba-1): ``ssm_in`` (2 x inner, hidden: x then z),
  ``ssm_conv1d`` (inner, conv taps, F32) and its bias, ``ssm_x`` (dt_rank +
  2 x state, inner), ``ssm_dt`` (inner, dt_rank, F32) and its bias,
  ``ssm_a`` (inner, state), ``ssm_d`` (inner), ``ssm_out`` (hidden, inner);
  its cache is a float32 state (inner x state) and conv - 1 carried rows,
  whatever the context;
- ``window`` / ``full``: differential attention, ``attn_q`` / ``attn_k`` /
  ``attn_v`` / ``attn_output`` with biases, four F32 lambda vectors of a
  head's width and ``attn_sub_norm`` (2 x a head's width); a window layer's
  cache holds ``sliding_window`` positions, the ONE full layer's the context;
- ``gmu``: ``gmu_in`` (inner, hidden), ``gmu_out`` (hidden, inner); no cache;
- ``cross``: ``attn_q``, ``attn_output``, the lambdas and the sub-norm; it
  READS the full layer's keys and values and has no cache of its own;
- ``output_norm`` and no ``output.weight``: the head is ``token_embd``.

``ggufgen.write_gguf`` gives a block no say over a tensor's VALUES (ones in a
``*_norm.weight``, else N(0, hidden^-1/2)), so ``ssm_a`` and ``ssm_dt.bias``
hold small random numbers and the file says ``ssm.values = init_offsets``:
program and reference read them as offsets from Mamba's initialisation.

Costs are the ALGORITHM's, on the bytes the FILE stores: a step reads every
matrix once (the embedding table too: it is the head); every live lane's
states and carried rows are read and written; a window layer reads its live
slots; and the full layer's leaf is read by the full layer and by EVERY
cross layer, so its live positions count once a reading layer: a program
that read them once for all would pass 100 % of nothing, and one that
fills K 2560 up to 4096 shows the fill as distance.
"""

import costs
from ggufgen import tensor_nbytes, transformer_metadata
from server import parse_gauge


def kinds(cfg):
    return cfg["mixer_types"][:cfg["num_hidden_layers"]]


def n_kind(cfg, *names):
    return sum(k in names for k in kinds(cfg))


def ssm_sizes(cfg):
    """(inner, state, conv taps, dt rank): the ``assumed`` Mamba sizes."""
    m = cfg["mamba"]
    return m["d_inner"], m["d_state"], m["d_conv"], m["dt_rank"]


def tensor_plan(cfg):
    d, v, f = cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"]
    hd, q_dim, kv_dim = costs.dims(cfg)
    c, n, taps, r = ssm_sizes(cfg)
    tt = cfg["gguf"]["tensor_types"]
    plan = [("token_embd.weight", (v, d), tt["token_embd"])]

    def norm(p, name, width):
        return [(p + name + ".weight", (width,), "F32"),
                (p + name + ".bias", (width,), "F32")]

    def differential(p):
        return [(p + f"attn_lambda_{k}", (hd,), "F32")
                for k in ("q1", "k1", "q2", "k2")] \
            + [(p + "attn_sub_norm.weight", (2 * hd,), "F32")]

    for i, kind in enumerate(kinds(cfg)):
        p = f"blk.{i}."
        plan += norm(p, "attn_norm", d)
        if kind == "ssm":
            plan += [
                (p + "ssm_in.weight", (2 * c, d), tt["ssm_in"]),
                (p + "ssm_conv1d.weight", (c, taps), "F32"),
                (p + "ssm_conv1d.bias", (c,), "F32"),
                (p + "ssm_x.weight", (r + 2 * n, c), tt["ssm_x"]),
                (p + "ssm_dt.weight", (c, r), "F32"),
                (p + "ssm_dt.bias", (c,), "F32"),
                (p + "ssm_a", (c, n), "F32"),
                (p + "ssm_d", (c,), "F32"),
                (p + "ssm_out.weight", (d, c), tt["ssm_out"]),
            ]
        elif kind == "gmu":
            plan += [(p + "gmu_in.weight", (c, d), tt["gmu_in"]),
                     (p + "gmu_out.weight", (d, c), tt["gmu_out"])]
        else:
            plan += [(p + "attn_q.weight", (q_dim, d), tt["attn_q"]),
                     (p + "attn_q.bias", (q_dim,), "F32")]
            if kind != "cross":
                plan += [(p + "attn_k.weight", (kv_dim, d), tt["attn_k"]),
                         (p + "attn_k.bias", (kv_dim,), "F32"),
                         (p + "attn_v.weight", (kv_dim, d), tt["attn_v"]),
                         (p + "attn_v.bias", (kv_dim,), "F32")]
            plan += differential(p)
            plan += [(p + "attn_output.weight", (d, q_dim), tt["attn_output"]),
                     (p + "attn_output.bias", (d,), "F32")]
        plan += norm(p, "ffn_norm", d)
        plan += [(p + "ffn_gate.weight", (f, d), tt["ffn_gate"]),
                 (p + "ffn_up.weight", (f, d), tt["ffn_up"]),
                 (p + "ffn_down.weight", (d, f), tt["ffn_down"])]
    plan += norm("", "output_norm", d)
    return plan


def metadata(cfg, arch):
    if not cfg["tie_word_embeddings"] or cfg["mlp_bias"] \
            or cfg["lm_head_bias"]:
        raise ValueError("an untied head or a feed-forward / head bias is "
                         "not written")
    c, n, taps, r = ssm_sizes(cfg)
    meta = transformer_metadata(
        {**cfg, "rms_norm_eps": cfg["layer_norm_eps"], "rope_theta": 10000.0},
        arch)
    meta += [
        (f"{arch}.mixer_types", "str", ",".join(kinds(cfg))),
        (f"{arch}.attention.key_length", "u32", costs.dims(cfg)[0]),
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


def lanes_alive(lanes, run=None):
    """Lanes whose cache a decode step reads: the mean of the scheduler's
    gauge over the run's samples that saw a live lane, else every lane."""
    vals = [parse_gauge(text, "scheduler_lanes_live")
            for _, text in (run or {}).get("samples") or []]
    vals = [v for v in vals if v]
    return sum(vals) / len(vals) if vals else lanes


def state_bytes(cfg):
    """One sequence's float32 states and bf16 carried rows (2 949 120 +
    276 480 B at 9 layers of 5120 x 16 and 3 x 5120)."""
    c, n, taps, _ = ssm_sizes(cfg)
    return n_kind(cfg, "ssm") * c * (n * 4 + (taps - 1) * 2)


def kv_row_bytes(cfg, kv_bytes=2):
    """K and V of every KV head at one position of one layer."""
    return 2 * costs.dims(cfg)[2] * kv_bytes


def shared_readers(cfg):
    """Layers that read the full layer's leaf a decode step."""
    return n_kind(cfg, "full", "cross")


def cache_bytes_per_lane(cfg, n_ctx):
    return kv_row_bytes(cfg) * (
        n_ctx + n_kind(cfg, "window") * cfg["sliding_window"]) \
        + state_bytes(cfg)


def ssm_state_bytes_per_step(cfg, lanes, context_tokens=0, run=None):
    """The live lanes' states and carried rows, read and written."""
    return lanes_alive(lanes, run) * 2 * state_bytes(cfg)


def cache_bytes_per_step(cfg, lanes, context_tokens, kv_bytes=2, run=None):
    live = lanes_alive(lanes, run)
    window = n_kind(cfg, "window") * min(context_tokens,
                                         cfg["sliding_window"])
    shared = shared_readers(cfg) * context_tokens
    return live * ((window + shared) * kv_row_bytes(cfg, kv_bytes)
                   + 2 * state_bytes(cfg))


def decode_step_bytes(cfg, lanes, context_tokens, kv_bytes=2, run=None):
    return (file_bytes(cfg)
            + cache_bytes_per_step(cfg, lanes, context_tokens, kv_bytes, run)
            + lanes * cfg["hidden_size"] * 2)


def scan_ops_per_row(cfg):
    """(multiply-adds and other vector operations, exps) of ONE ssm layer's
    recurrence at one position: per channel and state ``exp(dt A)`` (a
    product and an exp), ``* s``, ``dt x B`` (a product; ``dt x`` once a
    channel), the sum, ``C s`` and its sum: six and one."""
    c, n, _, _ = ssm_sizes(cfg)
    return c * (6 * n + 2), c * n


def scan_bytes_per_row(cfg):
    """What ONE ssm layer's slice kernel moves a position: x and dt in, y
    out (float32 a channel), B and C."""
    c, n, _, _ = ssm_sizes(cfg)
    return 3 * 4 * c + 2 * 4 * n


def _attn_flops_per_pair(cfg):
    """A query head against one key position, differential: its scores on
    a head's width, its sum over TWO value halves."""
    return 2 * costs.dims(cfg)[0] + 4 * costs.dims(cfg)[0]


def decode_step_flops(cfg, lanes, context_tokens, run=None):
    ops, exps = scan_ops_per_row(cfg)
    per_pos = cfg["num_attention_heads"] * _attn_flops_per_pair(cfg)
    attn = per_pos * (n_kind(cfg, "window") * min(context_tokens,
                                                  cfg["sliding_window"])
                      + shared_readers(cfg) * context_tokens)
    return lanes * (2 * matrix_weights(cfg)
                    + n_kind(cfg, "ssm") * (ops + exps)) \
        + lanes_alive(lanes, run) * attn


def lower_weights(cfg):
    """(matrix weights of the layers up to the full one, of those above it
    without the head)."""
    full = kinds(cfg).index("full")
    low = up = 0
    for name, shape, _ in tensor_plan(cfg):
        if len(shape) != 2 or not name.startswith("blk.") \
                or name.endswith(("ssm_a", "ssm_conv1d.weight")):
            continue
        if int(name.split(".")[1]) <= full:
            low += shape[0] * shape[1]
        else:
            up += shape[0] * shape[1]
    return low, up


def prefill_flops(cfg, n_tokens, run=None):
    """What a prompt NEEDS: the layers up to the full one at every position
    (their matrices, the scans, the causal window and the causal half of the
    full layer), the layers above it and the head at the LAST position alone
    (nothing above the full layer writes a cache)."""
    low, up = lower_weights(cfg)
    head = cfg["vocab_size"] * cfg["hidden_size"]
    ops, exps = scan_ops_per_row(cfg)
    per_pos = cfg["num_attention_heads"] * _attn_flops_per_pair(cfg)
    w = min(n_tokens, cfg["sliding_window"])
    pairs = n_kind(cfg, "window") * (n_tokens * w - w * w / 2) \
        + n_tokens * n_tokens / 2
    return (2.0 * low + n_kind(cfg, "ssm") * (ops + exps)) * n_tokens \
        + 2.0 * (up + head) + per_pos * (
            pairs + n_kind(cfg, "cross") * n_tokens)
