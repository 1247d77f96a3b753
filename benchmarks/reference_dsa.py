"""The plain reference of the ``deepseek32`` block (DeepSeek-V3.2-Exp,
``model_type: deepseek_v32``; the published ``inference/model.py`` of the
``deepseek-ai/DeepSeek-V3.2-Exp`` repository as remembered): ``deepseek2``'s
block (``reference_mla.py``, whose pieces this file imports and does not
edit) with a learned INDEXER beside every layer's latent attention, which
picks the positions each query attends.  Straightforward ``jax.numpy``
float32 under ``default_matmul_precision("highest")``, the whole sequence at
once, no cache, no lanes, no kernels, keys and values EXPANDED for every
head and position, the same share of experts as the program is given.

``n = rms_norm(x)`` a layer's normed input, ``t`` a query position, ``s <=
t`` a cached one; Hi indexer heads of dI; d_r the rotated width; k the
selection's size (``attention.indexer.top_k``).

Latent attention as ``reference_mla.py`` has it (``c_q``, ``[q_n | q_r]``,
``c``, ``k_r``, ``[k_n | v]``, interleaved-pair RoPE with YaRN, the scale).

The indexer:

    qI_h = W_Iq c_q                 Hi heads of dI, from the SAME normed query
                                    latent c_q as the main attention's query
    kI = LayerNorm(W_Ik n)          weight AND bias, eps 1e-6; ONE dI-vector a
                                    position, shared by the heads
    qI_h[:d_r], kI[:d_r] rotated on HALVES (column i with i + d_r / 2) by
      pos * inv_freq_i, the main attention's YaRN frequencies; the other
      dI - d_r columns are left as they are
    w = W_Iw n * Hi^-1/2 * dI^-1/2  Hi signed scalars a query
    I(t, s) = sum_h w_h(t) relu(qI_h(t) . kI(s))

The selection: ``S_t`` = the ``min(k, t + 1)`` positions ``s <= t`` of
largest ``I(t, s)``; of equal scores the LOWER position is taken (the tie
rule, ``assumed``: a stable sort by falling score).

Attention over ``S_t`` only: the softmax runs over ``s in S_t``; every
other position has probability 0.  While ``t < k`` the layer is the dense
latent layer exactly.

Feed-forward: ``reference_mla.py``'s, unchanged.

DEPARTURES from the published code beside ``reference_mla.py``'s four: (5)
the published indexer rotates ``qI`` and ``kI`` by a Hadamard matrix and
quantises both to FP8 before the products; the rotation is orthogonal and
leaves every ``qI . kI`` as it was (``hadamard=True`` computes it so: the
two agree to float32 rounding), and the reference keeps float32 where the
program keeps bfloat16 (v5e has no FP8 matrix unit); (6) the multi-token
prediction layer is not in the file.

``use_sel`` (L, S, S) bool: the positions to ATTEND in place of the
reference's own selection (the program's, so that logits are compared on
equal sets where a near-tie at rank k swapped a pick; the reference's own
scores and selection are returned all the same).  The CONTROLS, each a
different function that a comparison with a sound limit must tell from this
one: ``index_dtype`` (the indexer's operands, per-head scores, weights AND
sums rounded to it: bfloat16 sums; ``weighted_relu_sum`` is the sum alone,
on given operands, which is where a comparison can tell float32 sums from
bfloat16 ones: against the whole model the bfloat16 OPERANDS that the
program is allowed hide it), ``no_index_weights`` (every ``w_h``
the same: the scale alone), ``index_rope_interleaved`` (the indexer's
rotation on pairs (2i, 2i+1), the main attention's layout), ``no_select``
(every query attends every position: the dense layer); and
``reference_mla.py``'s own.
"""

from __future__ import annotations

import numpy as np

import reference_mla as mla
from reference import read_gguf

ROWS = mla.ROWS
LN_EPS = 1e-6


def open_model(path: str) -> tuple[dict, dict]:
    """``reference_mla.open_model`` and the indexer's three keys."""
    hp, tensors = mla.open_model(path)
    meta, _ = read_gguf(path)
    arch = meta["general.architecture"]
    g = lambda key, default=None: meta.get(  # noqa: E731
        f"{arch}.attention.indexer.{key}", default)
    hp.update(index_heads=g("head_count"), index_dim=g("key_length"),
              index_topk=g("top_k"),
              index_eps=g("layer_norm_epsilon", LN_EPS))
    return hp, tensors


def layer_norm(v, weight, bias, eps):
    import jax
    import jax.numpy as jnp

    mu = jnp.mean(v, -1, keepdims=True)
    var = jnp.mean((v - mu) ** 2, -1, keepdims=True)
    return (v - mu) * jax.lax.rsqrt(var + eps) * jnp.asarray(weight) \
        + jnp.asarray(bias)


def hadamard(n: int) -> np.ndarray:
    """The orthogonal (n, n) Hadamard matrix, n a power of two (Sylvester's,
    over n^1/2)."""
    h = np.ones((1, 1), np.float32)
    while h.shape[0] < n:
        h = np.block([[h, h], [h, -h]])
    if h.shape[0] != n:
        raise ValueError(f"no Sylvester Hadamard matrix of order {n}")
    return h / np.sqrt(n)


def _round(a, dtype):
    import jax.numpy as jnp

    return a if dtype is None else a.astype(dtype).astype(jnp.float32)


def index_inputs(hp: dict, w: dict, x, emulate=None):
    """(n, c_q): a layer's normed input and its normed query latent, what
    the main attention's query and the indexer both start from."""
    n = mla.norm(x, w["attn_norm"], hp["eps"])
    return n, mla.norm(mla._mm(n, w["attn_q_a"], emulate),
                       w["attn_q_a_norm"], hp["eps"])


def index_scores(hp: dict, w: dict, n, c_q, emulate=None, index_dtype=None,
                 **controls):
    """I (S, S) float32: every query row against every position (the causal
    part is the selection's); ``controls``: :func:`index_operands`'."""
    q_i, k_i, wts = index_operands(hp, w, n, c_q, emulate, **controls)
    rnd = index_dtype if index_dtype is not None else emulate
    return weighted_relu_sum(_round(q_i, rnd), _round(k_i, rnd), wts,
                             index_dtype)


def index_operands(hp: dict, w: dict, n, c_q, emulate=None,
                   no_index_weights=False, index_rope_interleaved=False,
                   no_yarn=False, rotate=False):
    """(qI (S, Hi, dI), kI (S, dI), w (S, Hi)) in float32.  ``rotate``:
    departure (5)'s Hadamard rotation applied to qI and kI."""
    import jax.numpy as jnp

    S = n.shape[0]
    Hi, dI, d_r = hp["index_heads"], hp["index_dim"], hp["d_r"]
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] \
        * jnp.asarray(mla.inv_freq(hp, no_yarn))[None]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]

    def rope(v):            # the first d_r columns of (S, heads, dI)
        head, rest = v[..., :d_r], v[..., d_r:]
        if index_rope_interleaved:          # a CONTROL: pairs (2i, 2i+1)
            a, b = head[..., 0::2], head[..., 1::2]
            rot = jnp.stack([a * cos - b * sin, a * sin + b * cos],
                            -1).reshape(head.shape)
        else:                               # halves (i, i + d_r / 2)
            a, b = head[..., :d_r // 2], head[..., d_r // 2:]
            rot = jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)
        return jnp.concatenate([rot, rest], -1)

    q_i = rope(mla._mm(c_q, w["indexer_q_b"], emulate).reshape(S, Hi, dI))
    k_i = rope(layer_norm(mla._mm(n, w["indexer_k"], emulate),
                          w["indexer_k_norm"], w["indexer_k_norm.bias"],
                          hp["index_eps"])[:, None])[:, 0]
    if rotate:
        had = jnp.asarray(hadamard(dI))
        q_i, k_i = q_i @ had, k_i @ had
    wts = (n @ jnp.asarray(w["indexer_proj"]).T) * Hi ** -0.5 * dI ** -0.5
    if no_index_weights:                    # a CONTROL: the scale alone
        wts = jnp.full_like(wts, Hi ** -0.5 * dI ** -0.5)
    return q_i, k_i, wts


def weighted_relu_sum(q_i, k_i, wts, index_dtype=None):
    """``I(t, s) = sum_h w_h(t) relu(qI_h(t) . kI(s))`` of given operands:
    q_i (S, Hi, dI), k_i (T, dI), wts (S, Hi) -> (S, T) float32.
    ``index_dtype`` (a CONTROL): the per-head scores, the weights and every
    partial sum over the heads rounded to it."""
    import jax.numpy as jnp

    q_i, k_i, wts = (jnp.asarray(a, jnp.float32) for a in (q_i, k_i, wts))
    out = []
    for lo in range(0, q_i.shape[0], ROWS):
        s = jnp.einsum("qhd,kd->qhk", q_i[lo:lo + ROWS], k_i)
        if index_dtype is None:
            out.append(jnp.einsum("qhk,qh->qk", jnp.maximum(s, 0.0),
                                  wts[lo:lo + ROWS]))
            continue
        terms = _round(jnp.maximum(_round(s, index_dtype), 0.0)
                       * _round(wts[lo:lo + ROWS], index_dtype)[..., None],
                       index_dtype)
        acc = jnp.zeros_like(terms[:, 0])
        for h in range(q_i.shape[1]):
            acc = _round(acc + terms[:, h], index_dtype)
        out.append(acc)
    return jnp.concatenate(out, 0)


def select(scores, k: int):
    """(S, S) bool: row t has the ``min(k, t + 1)`` positions ``s <= t`` of
    largest score, the lower position first among equals."""
    import jax.numpy as jnp

    S = scores.shape[0]
    pos = jnp.arange(S)
    out = []
    for lo in range(0, S, ROWS):
        rows = pos[lo:lo + ROWS]
        causal = pos[None, :] <= rows[:, None]
        # a stable sort by falling score: equal scores stay in position order
        order = jnp.argsort(-jnp.where(causal, scores[lo:lo + ROWS] + 0.0,
                                       -jnp.inf), -1, stable=True)
        rank = jnp.zeros_like(order).at[
            jnp.arange(len(rows))[:, None], order].set(pos[None, :])
        out.append(causal & (rank < k))
    return jnp.concatenate(out, 0)


def picks_at_fault(sel_rows, want_rows, positions, k: int,
                   slack: float) -> float:
    """The share of a program's picks that a reference's scores do not
    bear out.  ``sel_rows`` (rows, >= S) bool: what the program selected
    for the queries at ``positions``; ``want_rows`` (rows, S): the
    reference's scores of those queries.  A pick is at fault where its
    reference score lies below the reference's k-th largest of the row by
    more than ``slack`` x the row's spread (near the k-th the order is
    rounding's to decide), or beyond the query's position; a row that does
    not hold exactly ``min(k, t + 1)`` picks is at fault whole."""
    bad = total = 0
    for row, want, t in zip(np.asarray(sel_rows), np.asarray(want_rows),
                            positions):
        w = np.asarray(want[:t + 1], np.float64)
        picked = np.flatnonzero(row)
        n = min(k, int(t) + 1)
        total += n
        if len(picked) != n or picked.max() > t:
            bad += n
            continue
        floor = np.partition(w, len(w) - n)[len(w) - n] \
            - slack * (w.max() - w.min() + 1e-30)
        bad += int(np.sum(w[picked] < floor))
    return bad / max(total, 1)


def attention(hp: dict, w: dict, x, emulate=None, no_yarn=False,
              use_sel=None, no_select=False, use_sel_rows=None,
              **index_controls):
    """The attention branch over the whole sequence ``x`` (S, dim): returns
    (x + branch, the indexer's scores (S, S), its own selection (S, S)).
    ``use_sel_rows`` = (rows, (len(rows), S) bool): the positions to attend
    at THOSE query rows, the reference's own selection at the others (a
    prefix that the program did not compute in this request)."""
    import jax
    import jax.numpy as jnp

    S = x.shape[0]
    H, r, d_n, d_r, d_v, eps = (hp["n_heads"], hp["r_kv"], hp["d_n"],
                                hp["d_r"], hp["d_v"], hp["eps"])
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] \
        * jnp.asarray(mla.inv_freq(hp, no_yarn))[None]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]

    def rope(v):            # the main attention's: pairs (2i, 2i+1)
        a, b = v[..., 0::2], v[..., 1::2]
        return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                         -1).reshape(v.shape)

    n, c_q = index_inputs(hp, w, x, emulate)
    scores = index_scores(hp, w, n, c_q, emulate, no_yarn=no_yarn,
                          **index_controls)
    own = select(scores, hp["index_topk"])
    key_pos = jnp.arange(S)
    attend = own if use_sel is None else jnp.asarray(use_sel)
    if use_sel_rows is not None:
        attend = attend.at[jnp.asarray(use_sel_rows[0])].set(
            jnp.asarray(use_sel_rows[1]))
    if no_select:                           # a CONTROL: the dense layer
        attend = key_pos[None, :] <= key_pos[:, None]
    q = mla._mm(c_q, w["attn_q_b"], emulate).reshape(S, H, d_n + d_r)
    q_n, q_r = q[..., :d_n], rope(q[..., d_n:])
    kv = mla._mm(n, w["attn_kv_a_mqa"], emulate)
    c = mla.norm(kv[:, :r], w["attn_kv_a_norm"], eps)
    k_r = rope(kv[:, None, r:])
    kvb = mla._mm(c, w["attn_kv_b"], emulate).reshape(S, H, d_n + d_v)
    k = jnp.concatenate([kvb[..., :d_n],
                         jnp.broadcast_to(k_r, (S, H, d_r))], -1)
    v = kvb[..., d_n:]
    qf = jnp.concatenate([q_n, q_r], -1)
    scale = mla.softmax_scale(hp, no_yarn)
    out = []
    for lo in range(0, S, ROWS):
        qb = qf[lo:lo + ROWS]
        s = jnp.einsum("qhd,khd->hqk", mla._r(qb, emulate),
                       mla._r(k, emulate)) * scale
        mask = (key_pos[None, :] <= (lo + jnp.arange(qb.shape[0]))[:, None]) \
            & attend[lo:lo + ROWS]
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), -1)
        out.append(jnp.einsum("hqk,khd->qhd", mla._r(p, emulate),
                              mla._r(v, emulate)))
    att = jnp.concatenate(out, 0).reshape(S, H * d_v)
    return x + mla._mm(att, w["attn_output"], emulate), scores, own


def feed_forward(hp: dict, w: dict, x, i: int, emulate=None, use_picks=None,
                 router_dtype=None, no_bias=False, no_shared=False,
                 no_scale=False, parts=False):
    """``reference_mla.layer``'s feed-forward half on the stream ``x`` after
    attention: (y, scores or None, picks or None).  ``parts``: a routed
    layer's y is returned as (x, routed experts' sum, shared expert), the
    terms ONE chip's share gives: over the 8 shares of a layer the routed
    sums add up and the shared expert counts once."""
    u = mla.norm(x, w["ffn_norm"], hp["eps"])
    if i < hp["n_dense"]:
        return x + mla.swiglu(u, w["ffn_gate"], w["ffn_up"], w["ffn_down"],
                              emulate), None, None
    scores, picks = mla.router(hp, w, u, router_dtype, no_bias)
    used = picks if use_picks is None else use_picks
    y = mla.routed(hp, w, u, used, mla.pick_weights(hp, scores, used, no_scale),
                   emulate)
    shared = 0.0 if no_shared else mla.swiglu(
        u, w["ffn_gate_shexp"], w["ffn_up_shexp"], w["ffn_down_shexp"],
        emulate)
    return ((x, y, shared) if parts else x + y + shared), scores, picks


_INDEX_CONTROLS = ("index_dtype", "no_index_weights",
                   "index_rope_interleaved", "rotate")


def indexer_weights(tensors: dict, w: dict, i: int) -> dict:
    """``reference_mla.layer_weights`` names a tensor by what stands before
    its last dot, so the index key's norm and its bias share a name: both
    under names of their own."""
    w["indexer_k_norm.bias"] = mla.tensor(tensors,
                                          f"blk.{i}.indexer_k_norm.bias")
    w["indexer_k_norm"] = mla.tensor(tensors,
                                     f"blk.{i}.indexer_k_norm.weight")
    return w


def forward(hp: dict, tensors: dict, tokens, emulate=None, use_picks=None,
            use_sel=None, no_select=False, no_yarn=False, **controls):
    """Logits (S, vocab) in float32 of the whole sequence ``tokens``; per
    routed layer the router's (scores, picks); per layer the indexer's
    (scores (S, S), selection (S, S) bool).  ``use_picks`` / ``use_sel``:
    per routed layer / per layer, see the module docstring."""
    import jax
    import jax.numpy as jnp

    index_controls = {k: controls.pop(k) for k in _INDEX_CONTROLS
                      if k in controls}
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(mla.tensor(tensors, "token_embd.weight"))[
            jnp.asarray(tokens, jnp.int32)]
        routes, index = [], []
        for i in range(hp["n_layers"]):
            j = i - hp["n_dense"]
            w = indexer_weights(tensors, mla.layer_weights(tensors, i), i)
            x, scores, sel = attention(
                hp, w, x, emulate, no_yarn,
                None if use_sel is None else use_sel[i], no_select,
                **index_controls)
            index.append((np.asarray(scores), np.asarray(sel)))
            x, scores, picks = feed_forward(
                hp, w, x, i, emulate,
                None if use_picks is None or j < 0 else use_picks[j],
                **controls)
            if scores is not None:
                routes.append((np.asarray(scores), np.asarray(picks)))
        return mla.head(hp, tensors, x, emulate), routes, index
