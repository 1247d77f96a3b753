"""The plain reference of the ``longcat-flash`` block (LongCat-Flash's
published modelling code as remembered: ``LongcatFlashDecoderLayer``,
``LongcatFlashMLA``, ``LongcatFlashTopkRouter``, ``LongcatFlashMoE``), beside
``reference.py`` (whose GGUF reader and dequantizers it uses through
``reference_mla.py``'s small helpers: ggml's published block layouts, nothing
of the program): straightforward ``jax.numpy`` float32 under
``default_matmul_precision("highest")``, the whole sequence at once, no
cache, no lanes, no kernels, keys and values EXPANDED for every head and
position (never the absorbed form), the experts a plain loop.

``N(x; g)`` is RMSNorm (eps ``attention.layer_norm_rms_epsilon``); no bias in
any matrix.  A layer ``l`` owns two attentions ``A0, A1``, two dense SwiGLU
feed-forwards ``F0, F1``, four norms and ONE expert branch ``M``:

    a  = h + A0(N(h; in0))                 sub-block 0
    u  = N(a; post0)
    m  = M(u)                              the branch reads sub-block 0's rows
    b  = a + F0(u)
    c  = b + A1(N(b; in1))                 sub-block 1
    h' = c + F1(N(c; post1)) + m           ... and joins only here

Attention (both alike, own weights; H heads, r_q / r_kv the latent ranks,
d_n / d_r / d_v a head's unrotated key, rotated key and value widths):

    q = W_qb N(W_qa x; q_a_norm), per head [q_n | q_r]
    [z | k_r] = W_kva x         z^ = N(z; kv_a_norm)
    q  <- q  * (dim / r_q)^1/2     (both parts)   where attention.scale_q_lora
    z^ <- z^ * (dim / r_kv)^1/2    (k_r is not)   where attention.scale_kv_lora
    q_r, k_r rotated on interleaved pairs (2i, 2i+1) by pos * theta^(-2i/d_r)
    [k_n | v] = W_kvb z^, per head     k_r: ONE head, shared by all
    scores = (q_n . k_n + q_r . k_r) * (d_n + d_r)^-1/2, causal softmax, W_o

Expert branch ``M(u)``, E real experts and Z zero ("identity") ones:

    p = softmax(W_r u) over E + Z outputs          (float32)
    the CHOICE: top k of p + b (exp_probs_b; absent: zeros), no groups
    w = the picked p (not p + b), NOT normalised, * expert_weights_scale
    M(u) = sum_{picked e < E} w_e SwiGLU_e(u) + (sum_{picked e >= E} w_e) u

DEPARTURES from the published code, each at its line below: (1) the rotated
rows are taken as the GGUF converter leaves them, interleaved pairs (the
published code rotates halves of de-interleaved rows: the same map on
permuted rows); (2) the experts HELD are ``expert_held_first`` .. +
``expert_held_count`` of the router's E: a pick of a real expert outside
them adds nothing (one chip's share of an expert-parallel layer; the
program is given the same share), while the identity picks are computed in
full (they need no weight: the chip a token lives on computes them); (3)
text in, text out: the Omni model's audio and vision encoders and its codec
decoder are not in the file; (4) queries are walked in blocks of ``ROWS``
rows against all keys (the same sums), and weights are dequantized one
layer at a time.

``use_picks`` (L, S, k): the router outputs to USE in place of the
reference's own picks, at the reference's own scores (the program's picks,
so that logits are compared on equal sets; the reference's own are
returned).

``emulate`` rounds the two inputs of every matmul and of the attention
products to that dtype (sums stay float32).  The CONTROLS, each a different
function that a comparison with a sound limit must tell from this one:
``router_dtype`` (a bf16 router), ``no_bias`` (``exp_probs_b`` dropped),
``no_q_scale`` / ``no_kv_scale`` (a missing ``mla_scale_*``), ``norm_weights``
(the picked weights divided by their sum), ``no_identity`` (the zero
experts' term dropped), ``join_early`` (the branch joins after sub-block 0's
feed-forward, where a plain mixture of experts would put it).
"""

from __future__ import annotations

import numpy as np

from reference import read_gguf
from reference_mla import (  # noqa: F401  (``tensor``, ``head``: callers')
    ROWS, _mm, _r, head, norm, routed, swiglu, tensor)


def open_model(path: str) -> tuple[dict, dict]:
    """(hyper-parameters, {tensor name: (shape, ggml type, raw bytes)})."""
    meta, tensors = read_gguf(path)
    arch = meta["general.architecture"]
    g = lambda key, default=None: meta.get(f"{arch}.{key}", default)  # noqa: E731
    E, dim = g("expert_count"), g("embedding_length")
    if g("expert_zero_count", 0) and g("expert_zero_type") != "identity":
        raise ValueError(f"expert_zero_type {g('expert_zero_type')!r}")
    hp = {
        "n_layers": g("block_count"), "dim": dim,
        "n_heads": g("attention.head_count"),
        "r_q": g("attention.q_lora_rank"), "r_kv": g("attention.kv_lora_rank"),
        "d_r": g("rope.dimension_count"),
        "d_n": g("attention.key_length") - g("rope.dimension_count"),
        "d_v": g("attention.value_length"),
        "eps": g("attention.layer_norm_rms_epsilon"),
        "theta": g("rope.freq_base"), "vocab": g("vocab_size"),
        "q_scale": (dim / g("attention.q_lora_rank")) ** 0.5
        if g("attention.scale_q_lora", False) else 1.0,
        "kv_scale": (dim / g("attention.kv_lora_rank")) ** 0.5
        if g("attention.scale_kv_lora", False) else 1.0,
        "n_experts": E, "n_zero": g("expert_zero_count", 0),
        "n_used": g("expert_used_count"),
        "scale": g("expert_weights_scale", 1.0),
        "norm_w": bool(g("expert_weights_norm", False)),
        "held_first": g("expert_held_first", 0),
        "held": g("expert_held_count", 0) or E,
    }
    return hp, tensors


def layer_weights(tensors: dict, l: int) -> dict:
    """Layer ``l``'s tensors in float32: ``{0: sub-block 0's by their short
    names, 1: sub-block 1's, <short name>: the layer's own}``."""
    p = f"blk.{l}."
    out = {0: {}, 1: {}}
    for name in tensors:
        if not name.startswith(p):
            continue
        short = name[len(p):].rsplit(".", 1)[0]
        if short[:2] in ("0.", "1."):
            out[int(short[0])][short[2:]] = tensor(tensors, name)
        else:
            out[short] = tensor(tensors, name)
    return out


def attention(hp: dict, w: dict, x, emulate=None, no_q_scale=False,
              no_kv_scale=False):
    """One sub-block's attention branch over the whole sequence ``x`` (S,
    dim), ``w`` that sub-block's weights: ``x + A(N(x))``."""
    import jax
    import jax.numpy as jnp

    S = x.shape[0]
    H, r, d_n, d_r, d_v, eps = (hp["n_heads"], hp["r_kv"], hp["d_n"],
                                hp["d_r"], hp["d_v"], hp["eps"])
    inv_freq = hp["theta"] ** (-np.arange(0, d_r, 2, dtype=np.float64) / d_r)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv_freq.astype(np.float32))[None]   # no rope scaling
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]

    def rope(v):            # departure (1): pairs (2i, 2i+1), as the file has them
        a, b = v[..., 0::2], v[..., 1::2]
        return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                         -1).reshape(v.shape)

    n = norm(x, w["attn_norm"], eps)
    c_q = norm(_mm(n, w["attn_q_a"], emulate), w["attn_q_a_norm"], eps)
    q = _mm(c_q, w["attn_q_b"], emulate).reshape(S, H, d_n + d_r)
    if not no_q_scale:
        q = q * hp["q_scale"]                            # mla_scale_q_lora
    q_n, q_r = q[..., :d_n], rope(q[..., d_n:])
    kv = _mm(n, w["attn_kv_a_mqa"], emulate)
    z = norm(kv[:, :r], w["attn_kv_a_norm"], eps)
    if not no_kv_scale:
        z = z * hp["kv_scale"]                           # mla_scale_kv_lora
    k_r = rope(kv[:, None, r:])                          # (S, 1, d_r), unscaled
    kvb = _mm(z, w["attn_kv_b"], emulate).reshape(S, H, d_n + d_v)
    k = jnp.concatenate([kvb[..., :d_n],
                         jnp.broadcast_to(k_r, (S, H, d_r))], -1)
    v = kvb[..., d_n:]
    qf = jnp.concatenate([q_n, q_r], -1)
    scale = (d_n + d_r) ** -0.5
    key_pos = jnp.arange(S)
    out = []
    for lo in range(0, S, ROWS):        # departure (4): query rows in blocks
        qb = qf[lo:lo + ROWS]
        s = jnp.einsum("qhd,khd->hqk", _r(qb, emulate), _r(k, emulate)) * scale
        mask = key_pos[None, :] <= (lo + jnp.arange(qb.shape[0]))[:, None]
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), -1)
        out.append(jnp.einsum("hqk,khd->qhd", _r(p, emulate), _r(v, emulate)))
    att = jnp.concatenate(out, 0).reshape(S, H * d_v)
    return x + _mm(att, w["attn_output"], emulate)


def router(hp: dict, w: dict, u, router_dtype=None, no_bias=False):
    """(scores (S, E + Z) over all the router's outputs, picks (S, k) by
    falling biased score).  ``w``: the layer's own tensors; an absent
    ``exp_probs_b`` is zeros."""
    import jax
    import jax.numpy as jnp

    logits = _mm(u, w["ffn_gate_inp"], router_dtype)
    if router_dtype is not None:
        logits = _r(logits, router_dtype)
    scores = jax.nn.softmax(logits, -1)
    choice = scores if no_bias or "exp_probs_b" not in w \
        else scores + jnp.asarray(w["exp_probs_b"])[None]
    picks = jnp.argsort(-choice, -1)[:, :hp["n_used"]]
    return scores, picks


def pick_weights(hp: dict, scores, picks, norm_weights=False):
    import jax.numpy as jnp

    wts = jnp.take_along_axis(scores, jnp.asarray(picks), -1)
    if hp["norm_w"] or norm_weights:    # (the published flag is off)
        wts = wts / (jnp.sum(wts, -1, keepdims=True) + 1e-20)
    return wts * hp["scale"]


def expert_branch(hp: dict, w: dict, u, used, wts, emulate=None,
                  no_identity=False):
    """``M(u)`` for the router outputs ``used`` (S, k) at weights ``wts``:
    the HELD real experts' part (departure (2): ``reference_mla.routed``,
    a pick outside them, a zero one among them, matches none) and the
    identity picks' part, in full."""
    import jax.numpy as jnp

    y = routed(hp, w, u, used, wts, emulate)
    if no_identity or not hp["n_zero"]:
        return y
    w_zero = jnp.sum(jnp.where(jnp.asarray(used) >= hp["n_experts"],
                               wts, 0.0), -1)
    return y + w_zero[:, None] * u


def layer(hp: dict, w: dict, x, emulate=None, use_picks=None,
          router_dtype=None, no_bias=False, no_q_scale=False,
          no_kv_scale=False, norm_weights=False, no_identity=False,
          join_early=False):
    """One layer over the whole sequence.  Returns (y, scores, picks)."""
    att = dict(emulate=emulate, no_q_scale=no_q_scale,
               no_kv_scale=no_kv_scale)
    a = attention(hp, w[0], x, **att)
    u = norm(a, w[0]["ffn_norm"], hp["eps"])
    scores, picks = router(hp, w, u, router_dtype, no_bias)
    used = picks if use_picks is None else use_picks
    m = expert_branch(hp, w, u, used,
                      pick_weights(hp, scores, used, norm_weights), emulate,
                      no_identity)
    b = a + swiglu(u, w[0]["ffn_gate"], w[0]["ffn_up"], w[0]["ffn_down"],
                   emulate)
    if join_early:
        b, m = b + m, 0.0
    c = attention(hp, w[1], b, **att)
    u1 = norm(c, w[1]["ffn_norm"], hp["eps"])
    y = c + swiglu(u1, w[1]["ffn_gate"], w[1]["ffn_up"], w[1]["ffn_down"],
                   emulate) + m
    return y, scores, picks


def forward(hp: dict, tensors: dict, tokens, emulate=None, use_picks=None,
            **controls):
    """Logits (S, vocab) in float32 of the whole sequence ``tokens``, and
    per layer the router's (scores, picks).  ``use_picks``: per layer, see
    the module docstring.  Departure (3): text in, text out."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(tensor(tensors, "token_embd.weight"))[
            jnp.asarray(tokens, jnp.int32)]
        routes = []
        for l in range(hp["n_layers"]):
            x, scores, picks = layer(
                hp, layer_weights(tensors, l), x, emulate,
                None if use_picks is None else use_picks[l], **controls)
            routes.append((np.asarray(scores), np.asarray(picks)))
        return head(hp, tensors, x, emulate), routes
