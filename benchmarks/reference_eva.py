"""The plain reference of the ``evabyte`` block, beside ``reference.py``
(whose GGUF reader and dequantizers it uses: ggml's published block
layouts, nothing of the program): multi-head EVA attention over an exact
blocked window plus one summary per chunk of every earlier window, SwiGLU,
unit-offset RMSNorm, and ``num_pred_heads`` prediction heads, in
straightforward ``jax.numpy`` float32 under
``default_matmul_precision("highest")``: the whole sequence at once, no
cache, no kernels, no batching.

Layer equations (per head, head width d, scale s = d ** -0.5, window W,
chunk C; no bias anywhere; ``x`` the residual stream in float32):

    n = rms_norm(x) * (1 + g)              (``norm_add_unit_offset``)
    q_t, k_t, v_t = Wq n_t, Wk n_t, Wv n_t;  q, k rotated by RoPE
        (rotate-half, theta from the file) at the absolute position t
    for every chunk c = positions [cC, cC + C):
        a_j      = softmax_j(s * phi . k_j)        over the chunk's C keys
        ktilde_c = sum_j a_j k_j + mu
        beta_c   = sum_j a_j v_j
    attention of query t, window w(t) = t // W: ONE softmax over
        s * q_t . k_m       for the exact keys w(t) W <= m <= t
                            (the window is BLOCKED, not sliding), and
        s * q_t . ktilde_c  for every chunk c of every EARLIER window,
                            c < w(t) W / C (none of the current window);
        output = the probabilities times v_m and beta_c, then Wo
    x += Wo att;  x += Wdown(silu(Wgate n') * Wup n'),  n' the second norm
    logits = Whead (final norm of x): ``vocab_size * num_pred_heads`` rows,
        head 0 (the first ``vocab_size``) the next byte, heads 1.. the
        bytes after it.

ASSUMED, because the published ``config.json`` does not say and there is no
network here (each also under ``assumed`` in the configuration file): the
names ``phi`` / ``mu`` (the released code's ``adaptive_phi`` /
``adaptive_mu_k``) and their shape (one vector of width d per head and
layer); the scale s inside ``a_j``; that ``mu`` is added to the pooled key
and nothing to the pooled value; that the pooled keys are the ROTATED keys;
that head h of the output matrix is rows ``[h V, (h + 1) V)``.

Departures, noted as the guide asks: (1) a norm gain is read from the file
as applied, ``1 + g``: llama.cpp's converters store unit-offset norms so,
and the file's writer follows them; (2) queries are walked in blocks of
``ROWS`` rows against all keys and all summaries behind masks, so that the
score matrix of a 4700-byte sequence never stands whole in memory: the same
sums; (3) weights are dequantized one layer at a time
(``compare_eva.py``).

``emulate`` computes the same function in a lower precision, for the
calibrations of ``compare_eva.py``: the two inputs of every matmul and of
both attention products are rounded to that dtype (sums stay float32).

The keyword switches of :func:`layer` are the CONTROLS: each computes a
different function that a comparison with a sound limit must tell from
this one (``tests/test_evabyte.py``, ``compare_eva.py``): ``no_summaries``
(the window alone), ``own_window`` (the current window's finished chunks
visible too), ``sliding`` (the last W keys in place of the blocked
window), ``no_mu``.
"""

from __future__ import annotations

import numpy as np

from reference import dequantize, read_gguf

ROWS = 512


def open_model(path: str) -> tuple[dict, dict]:
    """(hyper-parameters, {tensor name: (shape, ggml type, raw bytes)})."""
    meta, tensors = read_gguf(path)
    arch = meta["general.architecture"]
    hp = {
        "n_layers": meta[f"{arch}.block_count"],
        "n_heads": meta[f"{arch}.attention.head_count"],
        "eps": meta[f"{arch}.attention.layer_norm_rms_epsilon"],
        "theta": meta[f"{arch}.rope.freq_base"],
        "window": meta[f"{arch}.attention.window_size"],
        "chunk": meta[f"{arch}.attention.chunk_size"],
        "vocab": meta[f"{arch}.vocab_size"],
        "n_pred_heads": meta[f"{arch}.prediction_heads"],
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


def summaries(hp: dict, w: dict, k, v, no_mu=False):
    """(ktilde, beta), each (chunks, H, d), of every COMPLETE chunk of the
    rotated keys ``k`` and the values ``v`` (S, H, d)."""
    import jax
    import jax.numpy as jnp

    S, H, d = k.shape
    C = hp["chunk"]
    n = S // C
    kc = k[:n * C].reshape(n, C, H, d)
    vc = v[:n * C].reshape(n, C, H, d)
    phi, mu = jnp.asarray(w["attn_eva_phi"]), jnp.asarray(w["attn_eva_mu"])
    a = jax.nn.softmax(
        jnp.einsum("hd,nchd->nch", phi, kc) * d ** -0.5, axis=1)
    ktilde = jnp.einsum("nch,nchd->nhd", a, kc)
    if not no_mu:
        ktilde = ktilde + mu[None]
    return ktilde, jnp.einsum("nch,nchd->nhd", a, vc)


def layer(hp: dict, w: dict, x, emulate=None, no_summaries=False,
          own_window=False, sliding=False, no_mu=False, want_summaries=False):
    """One block over the whole sequence ``x`` (S, dim) float32.  Returns
    the block's output; with ``want_summaries`` also (ktilde, beta)."""
    import jax
    import jax.numpy as jnp

    S, dim = x.shape
    H, eps, W, C = hp["n_heads"], hp["eps"], hp["window"], hp["chunk"]
    d = dim // H
    pos = jnp.arange(S)
    freqs = hp["theta"] ** (-jnp.arange(d // 2, dtype=jnp.float32) / (d // 2))
    ang = pos.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]

    def rope(t):                       # (S, heads, d), rotate-half
        a, b = t[..., :d // 2], t[..., d // 2:]
        return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)

    n = norm(x, w["attn_norm"], eps)
    q = rope(_mm(n, w["attn_q"], emulate).reshape(S, H, d))
    k = rope(_mm(n, w["attn_k"], emulate).reshape(S, H, d))
    v = _mm(n, w["attn_v"], emulate).reshape(S, H, d)
    ktilde, beta = summaries(hp, w, k, v, no_mu)
    chunk_window = (jnp.arange(ktilde.shape[0]) * C) // W   # a chunk's window
    kr, vr = _r(k, emulate), _r(v, emulate)
    ktr, br = _r(ktilde, emulate), _r(beta, emulate)
    out = []
    for a in range(0, S, ROWS):
        t = pos[a:a + ROWS]
        qb = _r(q[a:a + ROWS], emulate)
        s_x = jnp.einsum("qhd,khd->hqk", qb, kr) * d ** -0.5
        s_s = jnp.einsum("qhd,khd->hqk", qb, ktr) * d ** -0.5
        if sliding:
            m_x = (pos[None, :] <= t[:, None]) & (pos[None, :] > t[:, None] - W)
        else:
            m_x = (pos[None, :] <= t[:, None]) \
                & (pos[None, :] // W == t[:, None] // W)
        seen = t[:, None] // W + (1 if own_window else 0)
        m_s = chunk_window[None, :] < seen
        if own_window:    # a chunk of the own window once it is finished
            m_s &= (jnp.arange(ktilde.shape[0]) + 1)[None, :] * C - 1 \
                <= t[:, None]
        if no_summaries:
            m_s = jnp.zeros_like(m_s)
        scores = jnp.concatenate(
            [jnp.where(m_x[None], s_x, -jnp.inf),
             jnp.where(m_s[None], s_s, -jnp.inf)], axis=-1)
        p = _r(jax.nn.softmax(scores, axis=-1), emulate)
        out.append(jnp.einsum("hqk,khd->qhd", p[..., :S], vr)
                   + jnp.einsum("hqk,khd->qhd", p[..., S:], br))
    att = jnp.concatenate(out).reshape(S, H * d)
    x = x + _mm(att, w["attn_output"], emulate)
    n = norm(x, w["ffn_norm"], eps)
    act = jax.nn.silu(_mm(n, w["ffn_gate"], emulate)) \
        * _mm(n, w["ffn_up"], emulate)
    x = x + _mm(act, w["ffn_down"], emulate)
    return (x, (ktilde, beta)) if want_summaries else x


def head(hp: dict, tensors: dict, x, emulate=None):
    """Logits (S, vocab * num_pred_heads) float32."""
    return _mm(norm(x, tensor(tensors, "output_norm.weight"), hp["eps"]),
               tensor(tensors, "output.weight"), emulate)


def forward(hp: dict, tensors: dict, tokens, emulate=None,
            want_summaries=False, **control):
    """Logits (S, vocab * num_pred_heads) in float32 of the whole sequence
    ``tokens``; with ``want_summaries`` also every layer's (ktilde, beta).
    ``control``: see :func:`layer`."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(tensor(tensors, "token_embd.weight"))[
            jnp.asarray(tokens, jnp.int32)]
        summ = []
        for i in range(hp["n_layers"]):
            x = layer(hp, layer_weights(tensors, i), x, emulate,
                      want_summaries=want_summaries, **control)
            if want_summaries:
                x, s = x
                summ.append(s)
        logits = head(hp, tensors, x, emulate)
        return (logits, summ) if want_summaries else logits
