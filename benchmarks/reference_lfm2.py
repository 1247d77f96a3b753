"""The plain reference of the ``lfm2moe`` block (LFM2-24B-A2B's ``model_type:
lfm2_moe``), beside ``reference.py`` (whose GGUF reader and dequantizers it
uses: ggml's published block layouts, nothing of the program) and
``reference_mla.py`` (whose rounding helpers, sigmoid router and SwiGLU it
uses: the same published routing): straightforward
``jax.numpy`` float32 under ``default_matmul_precision("highest")``, the
whole sequence at once, no cache, no carried rows, no lanes, no kernels, no
heads laid side by side.  Written from the issue's description of the
layers, not from the program.

No bias in any matrix; ``x`` the residual stream (S, D); ``n = rms_norm(x;
attn_norm)`` (the family's ``operator_norm``), eps from the file.

A CONV layer (``attention.head_count_kv[i] == 0``), L = ``shortconv.l_cache``
taps ``k`` (D, L), oldest first:

    [b, c, z] = W_in n             one matrix, rows in that order
    u_t = b_t * z_t
    v_t = sum_j k[:, j] * u_(t - (L - 1) + j),  u_s = 0 for s < 0
    x + W_out (c_t * v_t)

An ATTENTION layer: H query heads on K key/value heads of width d:

    q = W_q n (H x d)   k = W_k n, v = W_v n (K x d)
    q, k = rms_norm over EACH head's d (attn_q_norm, attn_k_norm)
    q, k rotated on pairs (j, j + d/2) by pos * theta^(-2j/d)
    scores = q . k / sqrt(d), causal; softmax; o = P v; x + W_o o

Feed-forward on ``n' = rms_norm(x; ffn_norm)``: the first
``leading_dense_block_count`` layers W_down(silu(W_gate n') * W_up n'); the
others sigmoid scores over all E experts in float32, the choice the top k
of scores + exp_probs_b, the weights the picked UNBIASED scores over (their
sum + 1e-6) times expert_weights_scale, the experts' SwiGLU outputs summed;
no shared expert.  Then rms_norm(x; token_embd_norm) and the head, which is
the embedding matrix.

ASSUMED (the configuration file's ``assumed`` says the same): the three
conv equations and the order b, c, x of ``in_proj``'s rows as the family's
modelling code has them as remembered, the taps oldest first, rotate-half,
per-head QK-norm, the router's rule and its 1e-6, a tied head.  Departure
from the published model (1): the file holds the first 20 of 40 layers, so
the final norm and the head sit on layer 19's output.

``use_picks`` and ``emulate`` as ``reference_mla.py``.  The CONTROLS, each a
different function that a comparison with a sound limit must tell from this
one: ``flip_taps`` (the taps newest first), ``no_gate`` (no ``c *``),
``no_qk_norm``, ``router_dtype`` (a bf16 router), ``no_bias``.
"""

from __future__ import annotations

import numpy as np

from reference import read_gguf
from reference_mla import (  # noqa: F401  (the callers' names)
    _mm, _r, layer_weights, norm, router, swiglu, tensor)

ROWS = 256
ROW_BUCKET = 64
WEIGHTS_EPS = 1e-6


def open_model(path: str) -> tuple[dict, dict]:
    """(hyper-parameters, {tensor name: (shape, ggml type, raw bytes)})."""
    meta, tensors = read_gguf(path)
    arch = meta["general.architecture"]
    g = lambda key, default=None: meta.get(f"{arch}.{key}", default)  # noqa: E731
    E = g("expert_count", 0)
    kv = [int(n) for n in g("attention.head_count_kv")]
    hp = {
        "n_layers": g("block_count"),
        "n_dense": g("leading_dense_block_count", 0),
        "n_heads": g("attention.head_count"),
        "kv_heads": kv, "n_kv": max(kv),
        "d": g("attention.key_length"),
        "taps": g("shortconv.l_cache"),
        "eps": g("attention.layer_norm_rms_epsilon"),
        "theta": g("rope.freq_base"), "vocab": g("vocab_size"),
        "n_experts": E, "n_used": g("expert_used_count", 0),
        "n_groups": 1, "groups_used": 1,
        "scale": g("expert_weights_scale", 1.0),
        "norm_w": bool(g("expert_weights_norm", False)),
        "gating": g("expert_gating_func", 1),
    }
    return hp, tensors


def kind_of(hp: dict, i: int) -> str:
    return "attn" if hp["kv_heads"][i] else "conv"


def short_conv(hp: dict, w: dict, x, emulate=None, flip_taps=False,
               no_gate=False):
    """A conv layer's mixer branch over the whole sequence ``x``."""
    import jax.numpy as jnp

    S, D = x.shape
    L = hp["taps"]
    n = norm(x, w["attn_norm"], hp["eps"])
    bcz = _mm(n, w["shortconv.in_proj"], emulate)
    b, c, z = bcz[:, :D], bcz[:, D:2 * D], bcz[:, 2 * D:]
    # the program's stream holds u in bfloat16; an ``emulate`` run rounds
    # it as it rounds every other product's input
    u = _r(b * z, emulate)
    k = jnp.asarray(w["shortconv.conv"])                    # (D, L)
    if flip_taps:
        k = k[:, ::-1]
    run = jnp.concatenate([jnp.zeros((L - 1, D), u.dtype), u])
    v = sum(k[:, j][None, :] * run[j:j + S] for j in range(L))
    return x + _mm(v if no_gate else c * v, w["shortconv.out_proj"], emulate)


def attention(hp: dict, w: dict, x, emulate=None, no_qk_norm=False):
    """An attention layer's mixer branch over the whole sequence ``x``."""
    import jax
    import jax.numpy as jnp

    S = x.shape[0]
    H, K, d, eps = hp["n_heads"], hp["n_kv"], hp["d"], hp["eps"]

    def rope(v):                       # pairs (j, j + d/2): rotate-half
        half = d // 2
        freqs = hp["theta"] ** (-jnp.arange(half, dtype=jnp.float32) / half)
        ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs[None]
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        a, b = v[..., :half], v[..., half:]
        return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)

    n = norm(x, w["attn_norm"], eps)
    q = _mm(n, w["attn_q"], emulate).reshape(S, H, d)
    k = _mm(n, w["attn_k"], emulate).reshape(S, K, d)
    v = _mm(n, w["attn_v"], emulate).reshape(S, K, d)
    if not no_qk_norm:
        q = norm(q, w["attn_q_norm"], eps)
        k = norm(k, w["attn_k_norm"], eps)
    q, k = rope(q).reshape(S, K, H // K, d), rope(k)
    out = []
    for lo in range(0, S, ROWS):       # query rows in blocks: the same sums
        qb = q[lo:lo + ROWS]
        kb, vb = k[:lo + ROWS], v[:lo + ROWS]
        s = jnp.einsum("qkgd,tkd->kgqt", _r(qb, emulate), _r(kb, emulate)) \
            * d ** -0.5
        mask = jnp.arange(kb.shape[0])[None, :] \
            <= (lo + jnp.arange(qb.shape[0]))[:, None]
        p = jax.nn.softmax(jnp.where(mask[None, None], s, -jnp.inf), -1)
        out.append(jnp.einsum("kgqt,tkd->qkgd", _r(p, emulate),
                              _r(vb, emulate)))
    att = jnp.concatenate(out, 0).reshape(S, H * d)
    return x + _mm(att, w["attn_output"], emulate)


def mixer(hp: dict, w: dict, x, i: int, emulate=None, flip_taps=False,
          no_gate=False, no_qk_norm=False):
    """Layer ``i``'s mixer branch, by its kind."""
    if kind_of(hp, i) == "conv":
        return short_conv(hp, w, x, emulate, flip_taps, no_gate)
    return attention(hp, w, x, emulate, no_qk_norm)


def pick_weights(hp: dict, scores, picks):
    import jax.numpy as jnp

    wts = jnp.take_along_axis(scores, jnp.asarray(picks), -1)
    if hp["norm_w"]:
        wts = wts / (jnp.sum(wts, -1, keepdims=True) + WEIGHTS_EPS)
    return wts * hp["scale"]


def routed(hp: dict, w: dict, u, used, wts, emulate=None):
    """sum over a token's picks of the experts' SwiGLU outputs, every
    expert held; ``used`` (S, k) expert ids, ``wts`` (S, k).  An expert's
    rows are gathered to a multiple of ROW_BUCKET (row 0 again, at weight
    zero), so that jax compiles a handful of shapes."""
    import jax.numpy as jnp

    used, wts, y = np.asarray(used), np.asarray(wts), jnp.zeros_like(u)
    for e in range(hp["n_experts"]):
        hit_rows, hit_k = np.nonzero(used == e)
        if hit_rows.size == 0:
            continue
        n = -(-hit_rows.size // ROW_BUCKET) * ROW_BUCKET
        rows, p_e = np.zeros(n, np.int64), np.zeros(n, np.float32)
        rows[:hit_rows.size] = hit_rows
        p_e[:hit_rows.size] = wts[hit_rows, hit_k]
        out = swiglu(u[rows], w["ffn_gate_exps"][e], w["ffn_up_exps"][e],
                     w["ffn_down_exps"][e], emulate)
        y = y.at[rows].add(jnp.asarray(p_e)[:, None] * out)
    return y


def layer(hp: dict, w: dict, x, i: int, emulate=None, use_picks=None,
          router_dtype=None, no_bias=False, **mixer_controls):
    """One block over the whole sequence.  Returns (y, scores or None,
    picks or None)."""
    x = mixer(hp, w, x, i, emulate, **mixer_controls)
    u = norm(x, w["ffn_norm"], hp["eps"])
    if i < hp["n_dense"]:
        return x + swiglu(u, w["ffn_gate"], w["ffn_up"], w["ffn_down"],
                          emulate), None, None
    scores, picks = router(hp, w, u, router_dtype, no_bias)
    used = picks if use_picks is None else use_picks
    y = routed(hp, w, u, used, pick_weights(hp, scores, used), emulate)
    return x + y, scores, picks


def head(hp: dict, tensors: dict, x, emulate=None):
    """The final norm and the head, which is the embedding (ASSUMED tied)."""
    final = "token_embd_norm.weight" if "token_embd_norm.weight" in tensors \
        else "output_norm.weight"      # (the benchmark's file: the same norm)
    return _mm(norm(x, tensor(tensors, final), hp["eps"]),
               tensor(tensors, "token_embd.weight"), emulate)


def forward(hp: dict, tensors: dict, tokens, emulate=None, use_picks=None,
            **controls):
    """Logits (S, vocab) in float32 of the whole sequence ``tokens``, and
    per routed layer the router's (scores, picks)."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(tensor(tensors, "token_embd.weight"))[
            jnp.asarray(tokens, jnp.int32)]
        routes = []
        for i in range(hp["n_layers"]):
            j = i - hp["n_dense"]
            x, scores, picks = layer(
                hp, layer_weights(tensors, i), x, i, emulate,
                None if use_picks is None or j < 0 else use_picks[j],
                **controls)
            if scores is not None:
                routes.append((np.asarray(scores), np.asarray(picks)))
        return head(hp, tensors, x, emulate), routes
