"""The plain reference of the ``ouro`` block (layers that run several
times), beside ``reference.py`` (whose GGUF reader and dequantizers it uses:
ggml's published block layouts, nothing of the program): multi-head
attention + SwiGLU with a norm before AND after each sub-block, the same
``n_layers`` layers run ``ut_steps`` passes over the whole sequence, the
final norm after every pass, an exit gate on each pass's normed output; in
straightforward ``jax.numpy`` float32 under
``default_matmul_precision("highest")``: the uncached full forward, no
kernels, no batching.

Layer equations (``x`` the rows of the whole sequence in float32, ``t`` the
pass, ``l`` the layer; no bias anywhere; RMSNorm eps from the file):

    x = E[tokens]
    for t in 0 .. ut_steps - 1:              # the SAME layers each pass
      for l in 0 .. n_layers - 1:
        n = N1a_l(x)
        q, k, v = Wq n, Wk n, Wv n;  q, k rotated by RoPE (rotate-half,
            theta from the file) at the token's position, the same in
            every pass
        a = x + N2a_l( Wo softmax(q k^T / sqrt(d), causal) v )
        x = a + N2f_l( Wdown( silu(Wgate N1f_l(a)) * Wup N1f_l(a) ) )
      x = Norm_final(x)                      # after EVERY pass
      lam_t = sigmoid(w_exit . x + b_exit)
    logits = W_out x                         # of the last pass: threshold 1.0

The keys and values a pass attends to are the ones THAT pass projected from
its own input (in a served cache: leaf ``t * n_layers + l``).  The exit
rule (``exit_mass``): ``p_t = lam_t prod_{j<t} (1 - lam_j)``, the last pass
takes what is left; a token leaves at the first pass whose cumulative mass
reaches ``early_exit_threshold``.  At the published 1.0 that is always the
last pass, so the gate changes no logit; it is computed and returned.

ASSUMED, because the catalog's ``config`` does not carry them and there is
no network here (each also under ``assumed`` in the configuration file):
the two after-norms and their place (on the sub-block's output, before the
residual add), the final norm between passes, a K/V leaf per pass, the
gate's form (one linear output with a bias on the normed hidden state) and
the rule above, no biases in the projections, rotate-half.

Departures from the published code, noted as the guide asks: (1) queries
are walked in blocks of ``ROWS`` rows against all keys behind a mask, so
that a score matrix of 1.2k positions x 16 heads never stands whole beside
the weights: the same sums; (2) weights are dequantized a layer at a time
and may be kept by the caller between passes (``keep``): a pass reads the
same float32 values either way; (3) a token never leaves early: threshold
1.0 is the only one the program serves; (4) text in, logits out: no
sampling.

``emulate`` computes the same function in a lower precision, for the
calibrations of ``compare_ouro.py``: the two inputs of every matmul and of
both attention products are rounded to that dtype (sums stay float32).

The keyword switches are the CONTROLS: each computes a different function
that a comparison with a sound limit must tell from this one
(``tests/test_ouro.py``, ``compare_ouro.py``): ``passes`` (fewer passes
than the file says), ``shared_leaves`` (pass ``t`` attends to the keys and
values pass ``t - 1`` projected: what one cache leaf a LAYER would hold),
``no_pass_norm`` (the final norm after the last pass alone),
``no_post_attn_norm`` / ``no_post_ffn_norm`` (an after-norm left out).
"""

from __future__ import annotations

import numpy as np

from reference import dequantize, read_gguf

ROWS = 256


def open_model(path: str) -> tuple[dict, dict]:
    """(hyper-parameters, {tensor name: (shape, ggml type, raw bytes)})."""
    meta, tensors = read_gguf(path)
    arch = meta["general.architecture"]
    g = lambda key, default=None: meta.get(f"{arch}.{key}", default)  # noqa: E731
    hp = {
        "n_layers": g("block_count"), "n_heads": g("attention.head_count"),
        "n_kv_heads": g("attention.head_count_kv"),
        "eps": g("attention.layer_norm_rms_epsilon"),
        "theta": g("rope.freq_base"), "ut_steps": g("ut_steps", 1),
        "threshold": g("early_exit_threshold", 1.0),
    }
    return hp, tensors


def tensor(tensors: dict, name: str) -> np.ndarray:
    shape, kind, raw = tensors[name]
    return dequantize(kind, raw, shape)


def layer_weights(tensors: dict, l: int, keep: dict | None = None) -> dict:
    """Layer ``l``'s tensors in float32, by their short names; ``keep``
    (departure (2)): a caller's dict that holds them for the next pass."""
    if keep is not None and l in keep:
        return keep[l]
    p = f"blk.{l}."
    w = {name[len(p):].rsplit(".", 1)[0]: tensor(tensors, name)
         for name in tensors if name.startswith(p)}
    if keep is not None:
        keep[l] = w
    return w


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


def _kv(hp: dict, w: dict, n, emulate=None):
    """(rotated keys, values) (S, n_kv_heads, d) of the normed rows ``n``."""
    S, KV = n.shape[0], hp["n_kv_heads"]
    k = _mm(n, w["attn_k"], emulate).reshape(S, KV, -1)
    return _rope(hp, k), _mm(n, w["attn_v"], emulate).reshape(S, KV, -1)


def project_kv(hp: dict, w: dict, x, emulate=None):
    """The rotated keys and the values layer ``w`` projects from its input
    ``x`` (S, dim): what its cache leaf would hold."""
    return _kv(hp, w, norm(x, w["attn_norm"], hp["eps"]), emulate)


def _rope(hp: dict, v):
    """Rotate-half, as published: dimension i pairs with i + d/2."""
    import jax.numpy as jnp

    S, d = v.shape[0], v.shape[-1]
    freqs = hp["theta"] ** (-np.arange(d // 2, dtype=np.float64) / (d // 2))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] \
        * jnp.asarray(freqs.astype(np.float32))[None]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = v[..., :d // 2], v[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def layer(hp: dict, w: dict, x, emulate=None, kv=None,
          no_post_attn_norm=False, no_post_ffn_norm=False):
    """One layer over the whole sequence ``x`` (S, dim) -> (y, (k, v)): the
    keys and values it projected.  ``kv``: attend to these in their place
    (the ``shared_leaves`` control)."""
    import jax
    import jax.numpy as jnp

    S, H, KV, eps = x.shape[0], hp["n_heads"], hp["n_kv_heads"], hp["eps"]
    n = norm(x, w["attn_norm"], eps)
    q = _rope(hp, _mm(n, w["attn_q"], emulate).reshape(S, H, -1))
    own = _kv(hp, w, n, emulate)
    k, v = own if kv is None else kv
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    d = q.shape[-1]
    key_pos = jnp.arange(S)
    out = []
    for lo in range(0, S, ROWS):        # departure (1): query rows in blocks
        qb = q[lo:lo + ROWS]
        s = jnp.einsum("qhd,khd->hqk", _r(qb, emulate), _r(k, emulate)) \
            * d ** -0.5
        mask = key_pos[None, :] <= (lo + jnp.arange(qb.shape[0]))[:, None]
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), -1)
        out.append(jnp.einsum("hqk,khd->qhd", _r(p, emulate), _r(v, emulate)))
    att = _mm(jnp.concatenate(out, 0).reshape(S, H * d), w["attn_output"],
              emulate)
    if not no_post_attn_norm:
        att = norm(att, w["post_attention_norm"], eps)
    a = x + att
    u = norm(a, w["ffn_norm"], eps)
    f = _mm(jax.nn.silu(_mm(u, w["ffn_gate"], emulate))
            * _mm(u, w["ffn_up"], emulate), w["ffn_down"], emulate)
    if not no_post_ffn_norm:
        f = norm(f, w["post_ffw_norm"], eps)
    return a + f, own


def gate(tensors: dict, x):
    """``lam`` (S,): the exit gate on a pass's normed output."""
    import jax
    import jax.numpy as jnp

    w = jnp.asarray(tensor(tensors, "ut_exit_gate.weight")).reshape(-1)
    b = jnp.asarray(tensor(tensors, "ut_exit_gate.bias")).reshape(())
    return jax.nn.sigmoid(x @ w + b)


def exit_mass(lams) -> np.ndarray:
    """(T, S) gate outputs -> (T, S) exit mass: ``p_t = lam_t prod_{j<t}
    (1 - lam_j)``, the last pass takes what is left."""
    lams = np.asarray(lams, np.float64)
    stay = np.concatenate([np.ones_like(lams[:1]),
                           np.cumprod(1.0 - lams[:-1], axis=0)])
    return stay * np.concatenate([lams[:-1], np.ones_like(lams[:1])])


def head(tensors: dict, x, emulate=None):
    """Logits of rows that left a pass through the final norm."""
    return _mm(x, tensor(tensors, "output.weight"), emulate)


def forward(hp: dict, tensors: dict, tokens, emulate=None, passes=None,
            shared_leaves=False, no_pass_norm=False, keep: dict | None = None,
            every_pass=False, **controls):
    """(logits (S, vocab) float32 of the whole sequence ``tokens``, the
    gate's ``lam`` (passes, S)).  ``every_pass``: the logits of each pass's
    output in place of the last's, (passes, S, vocab).  The other switches:
    the module docstring's controls."""
    import jax
    import jax.numpy as jnp

    T = hp["ut_steps"] if passes is None else passes
    g_final = tensor(tensors, "output_norm.weight")
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(tensor(tensors, "token_embd.weight"))[
            jnp.asarray(tokens, jnp.int32)]
        lams, logits, before = [], [], {}
        for t in range(T):
            for l in range(hp["n_layers"]):
                w = layer_weights(tensors, l, keep)
                kv = before.get(l) if shared_leaves else None
                if shared_leaves and t + 1 < T:
                    # what the ONE leaf of layer l holds when pass t + 1
                    # reads it before writing: this pass's projections
                    before[l] = project_kv(hp, w, x, emulate)
                x, _ = layer(hp, w, x, emulate, kv, **controls)
            if not no_pass_norm or t == T - 1:
                x = norm(x, g_final, hp["eps"])
            lams.append(gate(tensors, x))
            if every_pass:
                logits.append(head(tensors, x, emulate))
        out = jnp.stack(logits) if every_pass else head(tensors, x, emulate)
        return out, jnp.stack(lams)
