"""The plain reference of the routed block (``olmoe``), beside
``reference.py`` (the dense block's, whose GGUF reader and dequantizers it
uses: ggml's published block layouts, nothing of the program): attention
with an RMSNorm of Q and K, and a feed-forward of routed SwiGLU experts, in
straightforward ``jax.numpy`` float32 under
``default_matmul_precision("highest")``: no kernels, no cache, no batching.

Layer equations, as published (Hugging Face ``modeling_olmoe.py``):

    h = x + Wo . Attn(rope(q), rope(k), v)
        q = RMSNorm_q(Wq . RMSNorm(x)),  k = RMSNorm_k(Wk . RMSNorm(x))
        (the QK-norm over the WHOLE projection width, before the split into
        heads and before RoPE; no clipping, no biases, causal)
    y = h + sum_{e in top-k} p_e . Wdown_e(silu(Wgate_e . u) * (Wup_e . u))
        u = RMSNorm(h),  p = softmax(Wr . u) over ALL experts in float32,
        the k largest picked, their probabilities used AS THEY ARE
        (``norm_topk_prob`` false), no shared expert, no capacity limit.

The rotary embedding is the published one: "rotate-half", dimension i of a
head paired with i + head_dim/2.  ``reference.py`` (the dense block) rotates
the interleaved pairs (2i, 2i+1) because llama.cpp's converter permutes the
Q/K rows of a ``llama`` file to that; it leaves an ``olmoe`` file's as
Hugging Face stores them (a permutation could not pass the QK-norm, whose
weight spans all heads) and computes ggml's NEOX mode, which is rotate-half.

Departures, noted as the guide asks: (1) an expert is computed for the rows
that picked it, gathered on the host (``numpy.nonzero``; padded to a
multiple of 64 rows at weight zero), not for every row and masked: the same
sum, a fraction of the work; (2) weights are dequantized one layer at a
time, so that the 6.9 B parameters of the published configuration never
stand in memory as float32 at once.

``emulate`` computes the same function in a lower precision, for the
calibrations of ``compare_routed.py``: every matmul's two inputs are
rounded to that dtype (the accumulation stays float32).
"""

from __future__ import annotations

import numpy as np

from reference import dequantize, read_gguf

ROW_BUCKET = 64


def open_model(path: str) -> tuple[dict, dict]:
    """(hyper-parameters, {tensor name: (shape, ggml type, raw bytes)})."""
    meta, tensors = read_gguf(path)
    arch = meta["general.architecture"]
    hp = {
        "n_layers": meta[f"{arch}.block_count"],
        "n_heads": meta[f"{arch}.attention.head_count"],
        "n_kv_heads": meta[f"{arch}.attention.head_count_kv"],
        "n_experts": meta[f"{arch}.expert_count"],
        "n_used": meta[f"{arch}.expert_used_count"],
        "eps": meta[f"{arch}.attention.layer_norm_rms_epsilon"],
        "theta": meta[f"{arch}.rope.freq_base"],
    }
    return hp, tensors


def tensor(tensors: dict, name: str) -> np.ndarray:
    shape, kind, raw = tensors[name]
    return dequantize(kind, raw, shape)


def layer_weights(tensors: dict, i: int) -> dict:
    """Layer ``i``'s tensors in float32, by their short names."""
    p = f"blk.{i}."
    return {name[len(p):-len(".weight")]: tensor(tensors, name)
            for name in tensors if name.startswith(p)}


def _mm(a, b, emulate):
    """a @ b.T; with ``emulate`` both inputs rounded to that dtype."""
    import jax.numpy as jnp

    a, b = jnp.asarray(a), jnp.asarray(b)
    if emulate is not None:
        a = a.astype(emulate).astype(jnp.float32)
        b = b.astype(emulate).astype(jnp.float32)
    return a @ b.T


def norm(v, g, eps):
    import jax
    import jax.numpy as jnp

    return v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True) + eps) \
        * jnp.asarray(g)


def router(hp: dict, w: dict, u) -> tuple:
    """(probabilities (S, E) over all experts, picks (S, k) by falling
    probability)."""
    import jax
    import jax.numpy as jnp

    probs = jax.nn.softmax(jnp.asarray(u) @ jnp.asarray(w["ffn_gate_inp"]).T,
                           axis=-1)
    picks = jnp.argsort(-probs, axis=-1)[:, :hp["n_used"]]
    return probs, picks


def layer(hp: dict, w: dict, x, emulate=None, drop_last_pick=False,
          use_picks=None):
    """One block over the whole sequence ``x`` (S, dim).  Returns (y, the
    router's probabilities (S, E), its picks (S, k)).  ``use_picks`` (S, k)
    sends each token to THOSE experts (at the router's own probabilities)
    and not to the router's: for a comparison at a size so small that one
    near-tie ordered the other way moves the logits by tens of per cent,
    which holds the arithmetic to the reference apart from the picks."""
    import jax
    import jax.numpy as jnp

    S, dim = x.shape
    H, KV, eps = hp["n_heads"], hp["n_kv_heads"], hp["eps"]
    hd = dim // H
    pos = jnp.arange(S, dtype=jnp.float32)
    freqs = hp["theta"] ** (-jnp.arange(hd // 2, dtype=jnp.float32)
                            / (hd // 2))
    cos = jnp.cos(pos[:, None] * freqs)[:, None, :]
    sin = jnp.sin(pos[:, None] * freqs)[:, None, :]

    def rope(v):                       # (S, heads, hd), rotate-half
        a, b = v[..., :hd // 2], v[..., hd // 2:]
        return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)

    h = norm(x, w["attn_norm"], eps)
    q = norm(_mm(h, w["attn_q"], emulate), w["attn_q_norm"], eps)
    k = norm(_mm(h, w["attn_k"], emulate), w["attn_k_norm"], eps)
    q = rope(q.reshape(S, H, hd))
    k = rope(k.reshape(S, KV, hd))
    v = _mm(h, w["attn_v"], emulate).reshape(S, KV, hd)
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(float(hd))
    scores = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None], scores,
                       -jnp.inf)
    att = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
    x = x + _mm(att.reshape(S, H * hd), w["attn_output"], emulate)

    u = norm(x, w["ffn_norm"], eps)
    probs, picks = router(hp, w, u)
    used = np.asarray(picks if use_picks is None else use_picks)
    if drop_last_pick:
        used = used[:, :-1]
    y = jnp.zeros_like(x)
    for e in range(hp["n_experts"]):
        hit = np.nonzero((used == e).any(axis=1))[0]
        if hit.size == 0:
            continue
        # to a multiple of ROW_BUCKET rows (row 0 again, at weight zero), so
        # that jax compiles a handful of shapes and not one per expert
        rows = np.zeros(-(-hit.size // ROW_BUCKET) * ROW_BUCKET, np.int64)
        rows[:hit.size] = hit
        p_e = jnp.zeros(rows.size).at[:hit.size].set(probs[hit, e])
        ue = u[rows]
        act = jax.nn.silu(_mm(ue, w["ffn_gate_exps"][e], emulate)) \
            * _mm(ue, w["ffn_up_exps"][e], emulate)
        out = _mm(act, w["ffn_down_exps"][e], emulate)
        y = y.at[rows].add(p_e[:, None] * out)
    return x + y, probs, picks


def forward(hp: dict, tensors: dict, tokens, emulate=None,
            drop_last_pick=False, use_picks=None):
    """Logits (S, vocab) in float32 of the whole sequence ``tokens``, and
    per layer the router's (probabilities, picks).  ``use_picks``: per
    layer, see :func:`layer`.  (``compare_routed.py`` walks several
    sequences through each layer as it is dequantized, with :func:`layer`
    and :func:`head`.)"""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(tensor(tensors, "token_embd.weight"))[
            jnp.asarray(tokens, jnp.int32)]
        routed = []
        for i in range(hp["n_layers"]):
            x, probs, picks = layer(
                hp, layer_weights(tensors, i), x, emulate, drop_last_pick,
                None if use_picks is None else use_picks[i])
            routed.append((np.asarray(probs), np.asarray(picks)))
        return head(hp, tensors, x, emulate), routed


def head(hp: dict, tensors: dict, x, emulate=None):
    return _mm(norm(x, tensor(tensors, "output_norm.weight"), hp["eps"]),
               tensor(tensors, "output.weight"), emulate)
