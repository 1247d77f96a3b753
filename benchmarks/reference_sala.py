"""The plain reference of the ``minicpm-sala`` stack, beside ``reference.py``
(whose GGUF reader and dequantizers it uses: ggml's published block
layouts, nothing of the program): two kinds of layer in the order the file
names, in straightforward ``jax.numpy`` float32 under
``default_matmul_precision("highest")``: the whole sequence at once, no
cache, no chunks, no kernels, no batching.  The linear layer is the
RECURRENCE, token by token (a ``lax.scan`` over positions); the sparse layer
works out, per query, an explicit set of blocks and one softmax over their
causal positions.

No bias anywhere; d the head width, s = d ** -0.5; ``x`` the residual
stream; ``n = rms_norm(x)``.  The family's three scalars (file keys
``embedding_scale``, ``residual_scale``, ``logit_scale``, as applied):

    x_0 = embedding_scale * E[token]
    x  += residual_scale * branch          (every branch, both kinds)
    logits = W_head (logit_scale * rms_norm(x_L))
    feed-forward branch, every layer: W_down(silu(W_gate n') * W_up n')

``lightning-attn`` layer (H heads of d, as many keys as queries):

    q_t, k_t = rope(rms_norm_head(W_q n_t)), rope(rms_norm_head(W_k n_t))
        (a norm gain of width d shared by the heads; rotate-half, theta
        from the file, absolute position t);  v_t = W_v n_t
    S_t = lambda_h S_(t-1) + k_t v_t^T    (d x d per head, S_(-1) = 0)
    o_t = s * q_t^T S_t
    branch = W_o( rms_norm_head(o_t) * sigmoid(W_g n_t) )
    lambda_h = exp(-slope), slope = 2^(-8 (h + 1) / H) * (1 - l / (L_lin -
        1) + 1e-5), l the layer's number among the linear layers

``minicpm4`` layer (Hq query heads on Hkv key heads, group g = Hq / Hkv; no
rotation):

    q_t, k_t = rms_norm_head(W_q n_t), rms_norm_head(W_k n_t);  v_t = W_v n_t
    t + 1 < dense_len:  plain causal attention.
    otherwise:
      kc_j   = mean of k[stride j : stride j + kernel]  (per key head),
               visible to t once stride j + kernel - 1 <= t
      p      = softmax_j(s * q_t . kc_j) per query head over the visible j,
               summed over the g heads of the group
      score_b = max of p over the j whose kernel overlaps block b
               (positions [block b, block (b + 1)))
      set    = blocks < init_blocks, the blocks that hold positions t -
               window + 1 .. t, and of the other visible blocks the topk
               with the largest score                      (per key head)
      ONE softmax, scale s, over the positions <= t of the set's blocks
    branch = W_o( o_t * sigmoid(W_g n_t) )

ASSUMED, because the catalog row's ``config`` does not say and there is no
network here (each also under ``assumed`` in the configuration file): the
decay schedule above (the family's published lightning attention); no
activation on q, k, v beyond the norm; ``sparse_config`` (kernel 32, stride
16, block 64, topk 64, window 2048, init_blocks 1, dense_len 8192; MiniCPM4 /
InfLLM v2 as released); compression by the MEAN; the max over overlapping
kernels (the released max-pool of width 5, stride 4, padding 1 over the
compressed scores); rotate-half pairing; the GGUF names and keys.
ONE DEPARTURE from the released code: it chooses the branch per call by
the length so far, so a one-shot prefill of a long prompt runs every query
sparse while token-by-token generation runs the early ones dense; the rule
here is the query's position (``t + 1 >= dense_len``), what generation
gives.  Program and reference share every assumed term, so seeded random
weights cannot show one of them wrong.

Noted as the guide asks: queries of a sparse layer are walked in blocks of
``ROWS`` rows against all keys behind each query's mask (the same sums);
weights are dequantized one layer at a time.

``picks`` (L_sp, Hkv, S, blocks) bool: the sets to USE in place of the
reference's own for the queries past ``dense_len`` (the program's, so that
logits are compared on equal sets); the reference's own sets and scores are
returned beside the logits with ``want_picks``.

``emulate`` rounds the two inputs of every matmul and of the attention
products to that dtype (sums and the state stay float32).  The CONTROLS,
each a different function that a comparison with a sound limit must tell
from this one: ``state_dtype`` (the state rounded to it after every step),
``no_decay``, ``no_gate``, ``no_branch_scale``, ``no_emb_scale``,
``no_logit_scale`` (logits); ``topk_less`` (top k - 1), ``no_window``,
``no_init``, ``kc_late`` (a compressed key visible one stride late),
``no_group_sum`` (the group's first head in place of the sum) (picks).
"""

from __future__ import annotations

import numpy as np

from reference import dequantize, read_gguf

ROWS = 256

MIXERS = {"minicpm4": "sp", "lightning-attn": "lin"}


def open_model(path: str) -> tuple[dict, dict]:
    """(hyper-parameters, {tensor name: (shape, ggml type, raw bytes)})."""
    meta, tensors = read_gguf(path)
    arch = meta["general.architecture"]
    g = lambda key: meta[f"{arch}.{key}"]    # noqa: E731
    hp = {
        "n_layers": g("block_count"),
        "mixers": [MIXERS[m] for m in g("mixer_types").split(",")],
        "n_heads": g("attention.head_count"),
        "n_kv_heads": g("attention.head_count_kv"),
        "lin_heads": g("lightning.head_count"),
        "eps": g("attention.layer_norm_rms_epsilon"),
        "theta": g("rope.freq_base"),
        "vocab": g("vocab_size"),
        "emb_scale": g("embedding_scale"),
        "residual_scale": g("residual_scale"),
        "logit_scale": g("logit_scale"),
        **{k: g(f"sparse.{k}") for k in (
            "kernel_size", "kernel_stride", "block_size", "topk",
            "window_size", "init_blocks", "dense_len")},
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


def slopes(hp: dict) -> np.ndarray:
    """(L_lin, H): lambda = exp(-slope)."""
    L, H = hp["mixers"].count("lin"), hp["lin_heads"]
    base = 2.0 ** (-8.0 * (np.arange(H) + 1) / H)
    depth = 1.0 - np.arange(L) / max(L - 1, 1) + 1e-5
    return (depth[:, None] * base[None, :]).astype(np.float32)


def _branches(hp, w, x, n, att, emulate, no_gate, no_branch_scale):
    """Gate and output projection of an attention branch, then the
    feed-forward branch."""
    import jax

    rs = 1.0 if no_branch_scale else hp["residual_scale"]
    if not no_gate:
        att = att * jax.nn.sigmoid(_mm(n, w["attn_gate"], emulate))
    x = x + rs * _mm(att, w["attn_output"], emulate)
    n = norm(x, w["ffn_norm"], hp["eps"])
    act = jax.nn.silu(_mm(n, w["ffn_gate"], emulate)) \
        * _mm(n, w["ffn_up"], emulate)
    return x + rs * _mm(act, w["ffn_down"], emulate)


def recurrence(q, k, v, lam, state_dtype=None):
    """The linear layer's sum, token by token: ``q``/``k``/``v`` (S, H, d)
    float32, ``lam`` (H,).  Returns (o (S, H, d), the last state (H, d,
    d)); ``state_dtype`` rounds the state to it after every step."""
    import jax
    import jax.numpy as jnp

    S, H, d = q.shape

    def step(state, qkv):
        q_t, k_t, v_t = qkv
        state = lam[:, None, None] * state + k_t[:, :, None] * v_t[:, None, :]
        if state_dtype is not None:
            # reduce_precision, not a pair of converts: inside a compiled
            # loop the TPU compiler drops a float32 -> bfloat16 -> float32
            # round trip as "excess precision" and the control reads 0
            fi = jnp.finfo(state_dtype)
            state = jax.lax.reduce_precision(state, fi.nexp, fi.nmant)
        return state, jnp.einsum("hd,hde->he", q_t, state) * d ** -0.5

    last, o = jax.lax.scan(step, jnp.zeros((H, d, d), jnp.float32), (q, k, v))
    return o, last


def lin_layer(hp: dict, w: dict, x, l_lin: int, emulate=None,
              state_dtype=None, no_decay=False, no_gate=False,
              no_branch_scale=False, want_state=False, **_):
    """One ``lightning-attn`` layer over the whole sequence ``x`` (S, dim)
    float32, the recurrence token by token; with ``want_state`` also the
    state after the last position (H, d, d)."""
    import jax
    import jax.numpy as jnp

    S, dim = x.shape
    H, eps = hp["lin_heads"], hp["eps"]
    d = dim // H
    freqs = hp["theta"] ** (-jnp.arange(d // 2, dtype=jnp.float32) / (d // 2))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]

    def rope(t):                       # (S, heads, d), rotate-half
        a, b = t[..., :d // 2], t[..., d // 2:]
        return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)

    n = norm(x, w["attn_norm"], eps)
    q = rope(norm(_mm(n, w["attn_q"], emulate).reshape(S, H, d),
                  w["attn_q_norm"], eps))
    k = rope(norm(_mm(n, w["attn_k"], emulate).reshape(S, H, d),
                  w["attn_k_norm"], eps))
    v = _mm(n, w["attn_v"], emulate).reshape(S, H, d)
    # lambda in float64 on the host: the recurrence multiplies by it once a
    # position, and a device's exp is off by 1e-6 of a value near 1
    lam = jnp.ones(H) if no_decay else jnp.asarray(
        np.exp(-np.asarray(slopes(hp)[l_lin], np.float64)), jnp.float32)
    o, last = recurrence(_r(q, emulate), _r(k, emulate), _r(v, emulate), lam,
                         state_dtype)
    att = norm(o, w["attn_out_norm"], eps).reshape(S, dim)
    x = _branches(hp, w, x, n, att, emulate, no_gate, no_branch_scale)
    return (x, last) if want_state else x


def sp_layer(hp: dict, w: dict, x, emulate=None, picks=None, no_gate=False,
             no_branch_scale=False, topk_less=False, no_window=False,
             no_init=False, kc_late=False, no_group_sum=False, **_):
    """One ``minicpm4`` layer over the whole sequence.  Returns (x, own
    sets (Hkv, S, blocks) bool, block scores (Hkv, S, blocks))."""
    import jax
    import jax.numpy as jnp

    S, dim = x.shape
    Hq, Hkv, eps = hp["n_heads"], hp["n_kv_heads"], hp["eps"]
    d, g = dim // Hq, Hq // Hkv
    K, St, B = hp["kernel_size"], hp["kernel_stride"], hp["block_size"]
    topk = hp["topk"] - (1 if topk_less else 0)
    NB = -(-S // B)
    pos = jnp.arange(S)
    n = norm(x, w["attn_norm"], eps)
    q = norm(_mm(n, w["attn_q"], emulate).reshape(S, Hq, d),
             w["attn_q_norm"], eps)
    k = _r(norm(_mm(n, w["attn_k"], emulate).reshape(S, Hkv, d),
                w["attn_k_norm"], eps), emulate)
    v = _r(_mm(n, w["attn_v"], emulate).reshape(S, Hkv, d), emulate)
    # compressed keys with a full kernel
    nj = max((S - K) // St + 1, 0)
    first = St * jnp.arange(nj)
    kc = _r(jnp.mean(k[first[:, None] + jnp.arange(K)[None, :]], axis=1),
            emulate) if nj else jnp.zeros((0, Hkv, d))
    closes = first + K - 1 + (St if kc_late else 0)
    # the compressed keys whose kernel overlaps block b
    j_of = (B // St) * jnp.arange(NB)[:, None] \
        + jnp.arange(-(K // St - 1), B // St)[None, :]
    j_ok = (j_of >= 0) & (j_of < nj)
    blocks = jnp.arange(NB)
    out, own_all, score_all = [], [], []
    for a in range(0, S, ROWS):
        t = pos[a:a + ROWS]
        R = t.shape[0]
        qb = _r(q[a:a + ROWS], emulate).reshape(R, Hkv, g, d)
        if nj:
            sc = jnp.einsum("rngd,jnd->rngj", qb, kc) * d ** -0.5
            vis = (closes[None, :] <= t[:, None])[:, None, None, :]
            p = jax.nn.softmax(jnp.where(vis, sc, -1e30), axis=-1) * vis
            pg = p[:, :, 0] if no_group_sum else jnp.sum(p, axis=2)
            score = jnp.max(jnp.where(
                j_ok, pg[..., jnp.clip(j_of, 0, nj - 1)], 0.0), axis=-1)
        else:
            score = jnp.zeros((R, Hkv, NB))
        tb = t[:, None, None]
        visible = blocks <= tb // B
        forced = jnp.zeros_like(visible)
        if not no_init:
            forced |= blocks < hp["init_blocks"]
        if not no_window:
            forced |= blocks >= (tb - hp["window_size"] + 1) // B
        forced &= visible
        cand = visible & ~forced
        order = jnp.argsort(-jnp.where(cand, score, -1.0), axis=-1)
        rank = jnp.argsort(order, axis=-1)
        own = forced | (cand & (rank < topk))
        sparse_q = (t + 1 >= hp["dense_len"])[:, None, None]
        own = jnp.where(sparse_q, own, visible)
        use = own if picks is None else jnp.where(
            sparse_q, jnp.asarray(picks)[:, a:a + ROWS, :NB].transpose(
                1, 0, 2), own)
        mask = use[..., pos // B] & (pos[None, None, :] <= tb)
        s_x = jnp.einsum("rngd,snd->rngs", qb, k) * d ** -0.5
        pr = _r(jax.nn.softmax(jnp.where(mask[:, :, None], s_x, -jnp.inf),
                               axis=-1), emulate)
        out.append(jnp.einsum("rngs,snd->rngd", pr, v).reshape(R, dim))
        own_all.append(own)
        score_all.append(score)
    x = _branches(hp, w, x, n, jnp.concatenate(out), emulate, no_gate,
                  no_branch_scale)
    return (x, jnp.concatenate(own_all).transpose(1, 0, 2),
            jnp.concatenate(score_all).transpose(1, 0, 2))


def head(hp: dict, tensors: dict, x, emulate=None, no_logit_scale=False):
    """Logits (S, vocab) float32."""
    scale = 1.0 if no_logit_scale else hp["logit_scale"]
    return _mm(scale * norm(x, tensor(tensors, "output_norm.weight"),
                            hp["eps"]),
               tensor(tensors, "output.weight"), emulate)


def forward(hp: dict, tensors: dict, tokens, emulate=None, picks=None,
            want_picks=False, no_emb_scale=False, no_logit_scale=False,
            **control):
    """Logits (S, vocab) in float32 of the whole sequence ``tokens``; with
    ``want_picks`` also the sparse layers' own sets and block scores, each
    (L_sp, Hkv, S, blocks).  ``picks``: the sets to use past ``dense_len``
    (see the module's docstring); ``control``: see :func:`lin_layer`,
    :func:`sp_layer`."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(tensor(tensors, "token_embd.weight"))[
            jnp.asarray(tokens, jnp.int32)]
        if not no_emb_scale:
            x = hp["emb_scale"] * x
        seen = {"lin": 0, "sp": 0}
        own, scores = [], []
        for i, kind in enumerate(hp["mixers"]):
            w = layer_weights(tensors, i)
            if kind == "lin":
                x = lin_layer(hp, w, x, seen[kind], emulate, **control)
            else:
                x, mine, sc = sp_layer(
                    hp, w, x, emulate,
                    None if picks is None else picks[seen[kind]], **control)
                own.append(mine)
                scores.append(sc)
            seen[kind] += 1
        logits = head(hp, tensors, x, emulate, no_logit_scale)
        if want_picks:
            return logits, jnp.stack(own), jnp.stack(scores)
        return logits
