"""The plain reference of the ``deepseek2`` block (DeepSeek-V3's published
modelling code, as GigaChat3.1-702B-A36B's ``model_type: deepseek_v3`` names
it), beside ``reference.py`` (whose GGUF reader and dequantizers it uses:
ggml's published block layouts, nothing of the program): straightforward
``jax.numpy`` float32 under ``default_matmul_precision("highest")``, the
whole sequence at once, no cache, no lanes, no kernels, keys and values
EXPANDED for every head and position (never the absorbed form).

No bias in any matrix; ``x`` the residual stream, ``n = rms_norm(x)``; H
heads; r_q, r_kv the latent ranks; d_n, d_r, d_v a head's unrotated key,
rotated key and value widths.

Attention, every layer:

    c_q = rms_norm(W_qa n)                       [q_n | q_r] = W_qb c_q, per head
    [c_kv | k_r] = W_kva n     c = rms_norm(c_kv)
    [k_n | v] = W_kvb c, per head                k_r: ONE head, shared by all
    q_r, k_r rotated on interleaved pairs (2i, 2i+1) by pos * inv_freq_i
    scores = (q_n . k_n + q_r . k_r) * s,  causal softmax,  o = P v,  W_o o
    s = (d_n + d_r)^-1/2 * (yarn_log_multiplier * ln(factor) + 1)^2
    inv_freq: base_i = theta^(-2i/d_r); YaRN: base_i / factor blended with
      base_i by the linear ramp between the correction dims of beta_fast and
      beta_slow at original_context_length (cos/sin scale mscale /
      mscale_all_dim = 1)

Feed-forward, the first ``leading_dense_block_count`` layers: W_down(silu(
W_gate n') * W_up n').  The others:

    s = sigmoid(W_r n') over all E experts          (float32)
    s' = s + b                                      (exp_probs_b: the choice only)
    G groups of E / G: a group's score the sum of its two largest s'; the
      ``expert_group_used_count`` best groups kept; top-k of s' inside them
    weights = the picked s (not s'), / (their sum + 1e-20), * expert_weights_scale
    y = sum over the picks of expert_e(n') + shared_expert(n')

DEPARTURES from the published code, each at its line below: (1) the rotated
rows are taken as the GGUF converter leaves them, interleaved pairs (the
published code de-interleaves and rotates halves: the same map on permuted
rows); (2) the experts HELD are ``expert_held_first`` .. + ``expert_held_count``
of the router's E: a pick outside them adds nothing (one chip's share of an
expert-parallel layer; the program is given the same share); (3) the
multi-token-prediction module is not in the file; (4) queries are walked in
blocks of ``ROWS`` rows against all keys (the same sums), and weights are
dequantized one layer at a time, so that 12k positions fit.

``use_picks`` (L_moe, S, k): the experts to USE in place of the reference's
own picks, at the reference's own scores (the program's picks, so that
logits are compared on equal sets; the reference's own are returned).

``emulate`` rounds the two inputs of every matmul and of the attention
products to that dtype (sums stay float32).  The CONTROLS, each a different
function that a comparison with a sound limit must tell from this one:
``router_dtype`` (the router's inputs, weights and scores rounded to it: a
bf16 router), ``no_bias`` (``exp_probs_b`` dropped), ``no_shared``, ``no_yarn``
(plain frequencies and scale), ``no_scale`` (``expert_weights_scale`` 1).
"""

from __future__ import annotations

import math

import numpy as np

from reference import dequantize, read_gguf

ROWS = 256
ROW_BUCKET = 64


def open_model(path: str) -> tuple[dict, dict]:
    """(hyper-parameters, {tensor name: (shape, ggml type, raw bytes)})."""
    meta, tensors = read_gguf(path)
    arch = meta["general.architecture"]
    g = lambda key, default=None: meta.get(f"{arch}.{key}", default)  # noqa: E731
    E = g("expert_count", 0)
    hp = {
        "n_layers": g("block_count"), "n_dense": g("leading_dense_block_count", 0),
        "n_heads": g("attention.head_count"),
        "r_q": g("attention.q_lora_rank"), "r_kv": g("attention.kv_lora_rank"),
        "d_r": g("rope.dimension_count"),
        "d_n": g("attention.key_length") - g("rope.dimension_count"),
        "d_v": g("attention.value_length"),
        "eps": g("attention.layer_norm_rms_epsilon"),
        "theta": g("rope.freq_base"), "vocab": g("vocab_size"),
        "n_experts": E, "n_used": g("expert_used_count", 0),
        "n_groups": g("expert_group_count", 1),
        "groups_used": g("expert_group_used_count", 1),
        "scale": g("expert_weights_scale", 1.0),
        "norm_w": bool(g("expert_weights_norm", False)),
        "gating": g("expert_gating_func", 1),
        "held_first": g("expert_held_first", 0),
        "held": g("expert_held_count", 0) or E,
        "yarn": g("rope.scaling.type", "none") == "yarn",
        "yarn_factor": g("rope.scaling.factor", 1.0),
        "yarn_orig": g("rope.scaling.original_context_length", 0),
        "yarn_log_mul": g("rope.scaling.yarn_log_multiplier", 0.0),
        "beta_fast": g("rope.scaling.yarn_beta_fast", 32.0),
        "beta_slow": g("rope.scaling.yarn_beta_slow", 1.0),
    }
    return hp, tensors


def tensor(tensors: dict, name: str) -> np.ndarray:
    shape, kind, raw = tensors[name]
    return dequantize(kind, raw, shape)


def layer_weights(tensors: dict, i: int) -> dict:
    """Layer ``i``'s tensors in float32, by their short names."""
    p = f"blk.{i}."
    return {name[len(p):].rsplit(".", 1)[0]: tensor(tensors, name)
            for name in tensors if name.startswith(p)}


def _r(a, emulate):
    import jax.numpy as jnp

    a = jnp.asarray(a)
    return a if emulate is None else a.astype(emulate).astype(jnp.float32)


def _mm(a, b, emulate):
    """a @ b.T; with ``emulate`` both inputs rounded to that dtype."""
    return _r(a, emulate) @ _r(b, emulate).T


def norm(v, g, eps):
    import jax
    import jax.numpy as jnp

    return v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True) + eps) \
        * jnp.asarray(g)


def inv_freq(hp: dict, no_yarn: bool = False) -> np.ndarray:
    d = hp["d_r"]
    base = hp["theta"] ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    if not hp["yarn"] or no_yarn or hp["yarn_factor"] <= 1:
        return base.astype(np.float32)

    def corr(n_rot):
        return d * math.log(hp["yarn_orig"] / (n_rot * 2 * math.pi)) \
            / (2 * math.log(hp["theta"]))

    low = max(math.floor(corr(hp["beta_fast"])), 0)
    high = min(math.ceil(corr(hp["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    mask = 1.0 - ramp
    return (base / hp["yarn_factor"] * (1 - mask) + base * mask
            ).astype(np.float32)


def softmax_scale(hp: dict, no_yarn: bool = False) -> float:
    s = (hp["d_n"] + hp["d_r"]) ** -0.5
    if hp["yarn"] and not no_yarn and hp["yarn_factor"] > 1:
        m = hp["yarn_log_mul"] * math.log(hp["yarn_factor"]) + 1.0
        s *= m * m
    return s


def attention(hp: dict, w: dict, x, emulate=None, no_yarn=False):
    """The attention branch over the whole sequence ``x`` (S, dim)."""
    import jax
    import jax.numpy as jnp

    S = x.shape[0]
    H, r, d_n, d_r, d_v, eps = (hp["n_heads"], hp["r_kv"], hp["d_n"],
                                hp["d_r"], hp["d_v"], hp["eps"])
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv_freq(hp, no_yarn))[None]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]

    def rope(v):            # departure (1): pairs (2i, 2i+1), as the file has them
        a, b = v[..., 0::2], v[..., 1::2]
        return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                         -1).reshape(v.shape)

    n = norm(x, w["attn_norm"], eps)
    c_q = norm(_mm(n, w["attn_q_a"], emulate), w["attn_q_a_norm"], eps)
    q = _mm(c_q, w["attn_q_b"], emulate).reshape(S, H, d_n + d_r)
    q_n, q_r = q[..., :d_n], rope(q[..., d_n:])
    kv = _mm(n, w["attn_kv_a_mqa"], emulate)
    c = norm(kv[:, :r], w["attn_kv_a_norm"], eps)
    k_r = rope(kv[:, None, r:])                          # (S, 1, d_r)
    kvb = _mm(c, w["attn_kv_b"], emulate).reshape(S, H, d_n + d_v)
    k = jnp.concatenate([kvb[..., :d_n],
                         jnp.broadcast_to(k_r, (S, H, d_r))], -1)
    v = kvb[..., d_n:]
    qf = jnp.concatenate([q_n, q_r], -1)
    scale = softmax_scale(hp, no_yarn)
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
    """(scores (S, E) over all experts, picks (S, k) by falling biased
    score inside the kept groups)."""
    import jax
    import jax.numpy as jnp

    logits = _mm(u, w["ffn_gate_inp"], router_dtype)
    if router_dtype is not None:
        logits = _r(logits, router_dtype)
    scores = jax.nn.sigmoid(logits) if hp["gating"] == 2 \
        else jax.nn.softmax(logits, -1)
    choice = scores if no_bias else scores + jnp.asarray(w["exp_probs_b"])[None]
    S, E = choice.shape
    G = hp["n_groups"]
    if G > 1 and hp["groups_used"] < G:
        grouped = choice.reshape(S, G, E // G)
        gscore = jnp.sum(jnp.sort(grouped, -1)[..., -2:], -1)
        keep = jnp.argsort(-gscore, -1)[:, :hp["groups_used"]]
        kept = jnp.zeros((S, G), bool).at[jnp.arange(S)[:, None], keep].set(True)
        choice = jnp.where(kept[:, :, None], grouped, -jnp.inf).reshape(S, E)
    picks = jnp.argsort(-choice, -1)[:, :hp["n_used"]]
    return scores, picks


def pick_weights(hp: dict, scores, picks, no_scale=False):
    import jax.numpy as jnp

    wts = jnp.take_along_axis(scores, jnp.asarray(picks), -1)
    if hp["norm_w"]:
        wts = wts / (jnp.sum(wts, -1, keepdims=True) + 1e-20)
    return wts * (1.0 if no_scale else hp["scale"])


def swiglu(u, gate, up, down, emulate):
    import jax

    return _mm(jax.nn.silu(_mm(u, gate, emulate)) * _mm(u, up, emulate),
               down, emulate)


def routed(hp: dict, w: dict, u, used, wts, emulate=None):
    """sum over a token's picks of the HELD experts' outputs; ``used`` (S,
    k) expert ids over all E, ``wts`` (S, k).  Departure (2): the expert
    tensors hold experts ``held_first`` .. alone; other picks add nothing."""
    import jax.numpy as jnp

    used, y = np.asarray(used), jnp.zeros_like(u)
    for j in range(hp["held"]):
        e = hp["held_first"] + j
        hit_rows, hit_k = np.nonzero(used == e)
        if hit_rows.size == 0:
            continue
        # to a multiple of ROW_BUCKET rows (row 0 again, at weight zero), so
        # that jax compiles a handful of shapes and not one per expert
        rows = np.zeros(-(-hit_rows.size // ROW_BUCKET) * ROW_BUCKET, np.int64)
        rows[:hit_rows.size] = hit_rows
        p_e = jnp.zeros(rows.size).at[:hit_rows.size].set(
            wts[hit_rows, hit_k])
        out = swiglu(u[rows], w["ffn_gate_exps"][j], w["ffn_up_exps"][j],
                     w["ffn_down_exps"][j], emulate)
        y = y.at[rows].add(p_e[:, None] * out)
    return y


def layer(hp: dict, w: dict, x, i: int, emulate=None, use_picks=None,
          router_dtype=None, no_bias=False, no_shared=False, no_yarn=False,
          no_scale=False):
    """One block over the whole sequence.  Returns (y, scores or None,
    picks or None)."""
    x = attention(hp, w, x, emulate, no_yarn)
    u = norm(x, w["ffn_norm"], hp["eps"])
    if i < hp["n_dense"]:
        return x + swiglu(u, w["ffn_gate"], w["ffn_up"], w["ffn_down"],
                          emulate), None, None
    scores, picks = router(hp, w, u, router_dtype, no_bias)
    used = picks if use_picks is None else use_picks
    y = routed(hp, w, u, used, pick_weights(hp, scores, used, no_scale),
               emulate)
    shared = 0.0 if no_shared else swiglu(
        u, w["ffn_gate_shexp"], w["ffn_up_shexp"], w["ffn_down_shexp"],
        emulate)
    return x + y + shared, scores, picks


def head(hp: dict, tensors: dict, x, emulate=None):
    return _mm(norm(x, tensor(tensors, "output_norm.weight"), hp["eps"]),
               tensor(tensors, "output.weight"), emulate)


def forward(hp: dict, tensors: dict, tokens, emulate=None, use_picks=None,
            **controls):
    """Logits (S, vocab) in float32 of the whole sequence ``tokens``, and
    per routed layer the router's (scores, picks).  ``use_picks``: per
    routed layer, see the module docstring.  Departure (3): the file holds
    no prediction module, so the next-token logits are all there is."""
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
