"""The plain reference of the ``exaone-moe`` block (K-EXAONE-236B-A23B's
``model_type: exaone_moe``), beside ``reference.py`` (whose GGUF reader and
dequantizers it uses: ggml's published block layouts, nothing of the
program) and ``reference_mla.py`` (whose router, pick weights, held experts
and SwiGLU it uses: the same published sigmoid routing): straightforward
``jax.numpy`` float32 under ``default_matmul_precision("highest")``, the
whole sequence at once, no cache, no window slots, no lanes, no kernels.

No bias in any matrix; ``x`` the residual stream, ``n = rms_norm(x)``; H
query heads on K key/value heads of width d (H x d is not the hidden size).

Attention, layer i of kind ``window`` unless (i + 1) % pattern == 0
(``global``):

    q = W_q n (H x d)   k = W_k n, v = W_v n (K x d)
    q, k = rms_norm over EACH head's d (weights attn_q_norm, attn_k_norm)
    window layers: q, k rotated on pairs (j, j + d/2) by pos * theta^(-2j/d);
      global layers: NOT rotated
    scores = q . k / sqrt(d), every key head serving H / K query heads,
      masked to key_pos <= q_pos, in a window layer also key_pos > q_pos - W
    softmax, o = P v, x + W_o o

Feed-forward, the first ``leading_dense_block_count`` layers: W_down(silu(
W_gate n') * W_up n').  The others, as ``reference_mla.py``: sigmoid scores
over all E experts in float32, the choice on scores + exp_probs_b (one
group: a plain top-k), weights the picked scores over their sum times
expert_weights_scale, the HELD experts' outputs (a pick outside them adds
nothing) plus the shared expert.

ASSUMED (the configuration file's ``assumed`` says the same): per-head
QK-norm and the hybrid rotation rule as EXAONE 4.0 has them, pre-norm
residuals, the choice bias as DeepSeek-V3's.  The multi-token-prediction
layer is not in the file.

``use_picks`` and ``emulate`` as ``reference_mla.py``.  The CONTROLS, each a
different function that a comparison with a sound limit must tell from this
one: ``no_window`` (full causal attention in the window layers),
``rope_all`` (the global layers rotate too), ``no_shared``, ``router_dtype``
(a bf16 router), ``no_bias``.
"""

from __future__ import annotations

import numpy as np

from reference import read_gguf
from reference_mla import (  # noqa: F401  (the callers' names)
    _mm, _r, head, layer_weights, norm, pick_weights, routed, router, swiglu,
    tensor)

ROWS = 256


def open_model(path: str) -> tuple[dict, dict]:
    """(hyper-parameters, {tensor name: (shape, ggml type, raw bytes)})."""
    meta, tensors = read_gguf(path)
    arch = meta["general.architecture"]
    g = lambda key, default=None: meta.get(f"{arch}.{key}", default)  # noqa: E731
    E = g("expert_count", 0)
    hp = {
        "n_layers": g("block_count"),
        "n_dense": g("leading_dense_block_count", 0),
        "n_heads": g("attention.head_count"),
        "n_kv": g("attention.head_count_kv"),
        "d": g("attention.key_length"),
        "window": g("attention.sliding_window"),
        "pattern": g("attention.sliding_window_pattern"),
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
    }
    return hp, tensors


def kind_of(hp: dict, i: int) -> str:
    return "global" if (i + 1) % hp["pattern"] == 0 else "window"


def attention(hp: dict, w: dict, x, i: int, emulate=None, no_window=False,
              rope_all=False):
    """Layer ``i``'s attention branch over the whole sequence ``x``."""
    import jax
    import jax.numpy as jnp

    S = x.shape[0]
    H, K, d, eps = hp["n_heads"], hp["n_kv"], hp["d"], hp["eps"]
    windowed = kind_of(hp, i) == "window"
    W = hp["window"] if windowed and not no_window else 0

    def rope(v):                       # pairs (j, j + d/2): rotate-half
        half = d // 2
        freqs = hp["theta"] ** (-jnp.arange(half, dtype=jnp.float32) / half)
        ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs[None]
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        a, b = v[..., :half], v[..., half:]
        return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)

    n = norm(x, w["attn_norm"], eps)
    q = norm(_mm(n, w["attn_q"], emulate).reshape(S, H, d),
             w["attn_q_norm"], eps)
    k = norm(_mm(n, w["attn_k"], emulate).reshape(S, K, d),
             w["attn_k_norm"], eps)
    v = _mm(n, w["attn_v"], emulate).reshape(S, K, d)
    if windowed or rope_all:
        q, k = rope(q), rope(k)
    q = q.reshape(S, K, H // K, d)
    out = []
    for lo in range(0, S, ROWS):       # query rows in blocks: the same sums
        qb = q[lo:lo + ROWS]
        k0 = max(lo - W + 1, 0) if W else 0
        kb, vb = k[k0:lo + ROWS], v[k0:lo + ROWS]
        s = jnp.einsum("qkgd,tkd->kgqt", _r(qb, emulate), _r(kb, emulate)) \
            * d ** -0.5
        q_pos = (lo + jnp.arange(qb.shape[0]))[:, None]
        t_pos = (k0 + jnp.arange(kb.shape[0]))[None, :]
        mask = t_pos <= q_pos
        if W:
            mask &= t_pos > q_pos - W
        p = jax.nn.softmax(jnp.where(mask[None, None], s, -jnp.inf), -1)
        out.append(jnp.einsum("kgqt,tkd->qkgd", _r(p, emulate),
                              _r(vb, emulate)))
    att = jnp.concatenate(out, 0).reshape(S, H * d)
    return x + _mm(att, w["attn_output"], emulate)


def layer(hp: dict, w: dict, x, i: int, emulate=None, use_picks=None,
          router_dtype=None, no_bias=False, no_shared=False, no_window=False,
          rope_all=False):
    """One block over the whole sequence.  Returns (y, scores or None,
    picks or None)."""
    x = attention(hp, w, x, i, emulate, no_window, rope_all)
    u = norm(x, w["ffn_norm"], hp["eps"])
    if i < hp["n_dense"]:
        return x + swiglu(u, w["ffn_gate"], w["ffn_up"], w["ffn_down"],
                          emulate), None, None
    scores, picks = router(hp, w, u, router_dtype, no_bias)
    used = picks if use_picks is None else use_picks
    y = routed(hp, w, u, used, pick_weights(hp, scores, used), emulate)
    shared = 0.0 if no_shared else swiglu(
        u, w["ffn_gate_shexp"], w["ffn_up_shexp"], w["ffn_down_shexp"],
        emulate)
    return x + y + shared, scores, picks


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
