"""The plain reference of the ``jamba`` block (AI21-Jamba2-3B, ``model_type:
jamba``), beside ``reference.py`` (whose GGUF reader and dequantizers it
uses: ggml's published block layouts, nothing of the program),
``reference_mla.py`` (its rounding helpers) and ``reference_phi4flash.py``
(how a file's ``ssm_a`` / ``ssm_dt.bias`` are read): straightforward
``jax.numpy`` float32 under ``default_matmul_precision("highest")``, the
whole sequence at once, the scan a plain ``lax.scan`` over positions,
attention as a softmax over all earlier positions (query rows in blocks, so
that 34k positions fit), no cache, no carried rows or states, no lanes, no
kernels.  Written from the issue's equations, not from the program.

``x`` the stream (S, D); ``RMS`` an RMSNorm with a weight and no bias, eps
from the file; every layer is ``x += mixer(RMS_in(x)); x += W_down(silu(W_gate
n) * W_up n)``, ``n = RMS_ff(x)``; a final RMSNorm, then the head, which is
the embedding matrix.  No positional encoding anywhere.  Layer ``i`` is an
attention layer where the file's ``attention.head_count_kv[i]`` is not 0:

scan layer (Mamba-1 with inner norms; C = ssm.inner_size, N = ssm.state_size,
L = ssm.conv_kernel, R = ssm.time_step_rank):

    [u, z] = W_in n                         rows of u, then of z
    u_t = silu(sum_j k[:, j] u_(t-(L-1)+j) + b_conv),  u_s = 0 for s < 0
    [dt, B, C] = W_x u                      R, N, N columns
    dt = RMS_dt(dt);  B = RMS_b(B);  C = RMS_c(C)
    dt = softplus(W_dt dt + b_dt);  A (C, N), negative
    s_t = exp(dt_t[c] A[c, n]) s_(t-1) + dt_t[c] B_t[n] u_t[c],  s_(-1) = 0
    y_t[c] = sum_n C_t[n] s_t[c, n] + D[c] u_t[c]
    x + W_out (y * silu(z))

attention layer (H heads on K KV heads of d): ``q = W_q n``, ``k = W_k n``,
``v = W_v n``, no biases, NO rotation, ``softmax(q k^T / sqrt(d))`` causal
over every earlier position, ``x + W_o a``.

ASSUMED (the configuration file's ``assumed`` says the same): all of the
above is the released modelling code as remembered; the catalog row gives
the sizes and the two keys that place the attention layers.  Departure from
the published model: where the file says ``ssm.values = init_offsets`` (the
benchmark's file), ``ssm_a`` and ``ssm_dt.bias`` hold small random OFFSETS
from Mamba's initialisation (``reference_phi4flash.ssm_values`` folds them:
part of READING the file, not of the equations).

``emulate``: a dtype the inputs of every matrix product and of the
attention's two products are rounded to (``bfloat16``: what the program's
kernels take; ``float8_e4m3fn``: the nearest precision below, a control).
The other CONTROLS, each a different function that a comparison with a sound
limit must tell from this one: ``skip_norms`` (a set of ``dt`` / ``b`` /
``c``: those inner norms left out), ``rotate`` (rotate-half RoPE at base
10000 on q and k), ``flip_taps`` (the conv taps newest first),
``state_dtype`` (the state rounded to that dtype at every position).
"""

from __future__ import annotations

from reference import read_gguf
from reference_mla import _mm, _r
from reference_phi4flash import layer_weights, ssm_values, tensor

#: query rows a block of attention scores, and rows a block of a wide
#: matrix product (so that 34k positions x 8192 columns are never whole)
ROWS = 256
WIDE = 4096


def open_model(path: str) -> tuple[dict, dict]:
    """(hyper-parameters, {tensor name: (shape, ggml type, raw bytes)})."""
    meta, tensors = read_gguf(path)
    arch = meta["general.architecture"]
    g = lambda key, default=None: meta.get(f"{arch}.{key}", default)  # noqa: E731
    kv = [int(n) for n in g("attention.head_count_kv")]
    hp = {
        "n_layers": g("block_count"),
        "kinds": ["attn" if n else "ssm" for n in kv],
        "n_heads": g("attention.head_count"), "n_kv": max(kv),
        "d": g("attention.key_length") or g("embedding_length")
        // g("attention.head_count"),
        "eps": g("attention.layer_norm_rms_epsilon"),
        "C": g("ssm.inner_size"), "N": g("ssm.state_size"),
        "L": g("ssm.conv_kernel"), "R": g("ssm.time_step_rank"),
        "values": g("ssm.values", "stored"), "vocab": g("vocab_size"),
    }
    return hp, tensors


def rms(v, w: dict, name: str, eps: float):
    import jax
    import jax.numpy as jnp

    return v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True) + eps) \
        * jnp.asarray(w[name + ".weight"])


def _blocks(f, x, rows: int = WIDE):
    """``f`` over ``x`` in blocks of ``rows`` rows."""
    import jax.numpy as jnp

    return jnp.concatenate([f(x[lo:lo + rows])
                            for lo in range(0, x.shape[0], rows)], 0)


def ssm(hp: dict, w: dict, x, emulate=None, skip_norms=(), flip_taps=False,
        state_dtype=None):
    """A scan layer's mixer branch over the whole sequence: x + branch."""
    import jax
    import jax.numpy as jnp

    S = x.shape[0]
    C, N, L, R = hp["C"], hp["N"], hp["L"], hp["R"]
    n = rms(x, w, "attn_norm", hp["eps"])
    # (the program's stream holds u and z in bfloat16)
    uz = _blocks(lambda b: _r(_mm(b, w["ssm_in.weight"], emulate), emulate),
                 n)
    u, z = uz[:, :C], uz[:, C:]
    k = jnp.asarray(w["ssm_conv1d.weight"])                   # (C, L)
    if flip_taps:
        k = k[:, ::-1]
    run = jnp.concatenate([jnp.zeros((L - 1, C), u.dtype), u])
    u = jax.nn.silu(sum(k[:, j][None, :] * run[j:j + S] for j in range(L))
                    + jnp.asarray(w["ssm_conv1d.bias"]))
    dbc = _blocks(lambda b: _mm(b, w["ssm_x.weight"], emulate), u)
    dt, B, Cm = dbc[:, :R], dbc[:, R:R + N], dbc[:, R + N:]
    if "dt" not in skip_norms:
        dt = rms(dt, w, "ssm_dt_norm", hp["eps"])
    if "b" not in skip_norms:
        B = rms(B, w, "ssm_b_norm", hp["eps"])
    if "c" not in skip_norms:
        Cm = rms(Cm, w, "ssm_c_norm", hp["eps"])
    a, b_dt = ssm_values(hp, w)
    dt = jax.nn.softplus(dt @ jnp.asarray(w["ssm_dt.weight"]).T
                         + jnp.asarray(b_dt))
    a, d = jnp.asarray(a), jnp.asarray(w["ssm_d"])

    def step(s, row):
        ut, dtt, bt, ct = row
        s = jnp.exp(dtt[:, None] * a) * s + (dtt * ut)[:, None] * bt[None, :]
        if state_dtype is not None:
            # (``reduce_precision``, not a pair of casts: the TPU's compiler
            # may keep the excess precision of a float32 cast down and up)
            kind = jnp.finfo(state_dtype)
            s = jax.lax.reduce_precision(s, kind.nexp, kind.nmant)
        return s, s @ ct + d * ut

    _, y = jax.lax.scan(step, jnp.zeros((C, N), jnp.float32),
                        (u, dt, B, Cm))
    return x + _blocks(lambda b: _mm(b, w["ssm_out.weight"], emulate),
                       y * jax.nn.silu(z))


def _rotate_half(v, positions, base: float = 10000.0):
    """(S, H, d) rotated on halves: the ``rotate`` control."""
    import jax.numpy as jnp

    half = v.shape[-1] // 2
    freqs = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[:, None].astype(jnp.float32) * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = v[..., :half], v[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def attention(hp: dict, w: dict, x, emulate=None, rotate=False):
    """An attention layer's mixer branch: x + branch."""
    import jax
    import jax.numpy as jnp

    S, d, H, K = x.shape[0], hp["d"], hp["n_heads"], hp["n_kv"]
    n = rms(x, w, "attn_norm", hp["eps"])
    q = _mm(n, w["attn_q.weight"], emulate).reshape(S, H, d)
    k = _mm(n, w["attn_k.weight"], emulate).reshape(S, K, d)
    v = _mm(n, w["attn_v.weight"], emulate).reshape(S, K, d)
    if rotate:
        at = jnp.arange(S)
        q, k = _rotate_half(q, at), _rotate_half(k, at)
    k, v = (jnp.repeat(t, H // K, axis=1) for t in (k, v))
    out = []
    for lo in range(0, S, ROWS):
        qb = q[lo:lo + ROWS]
        kb, vb = k[:lo + ROWS], v[:lo + ROWS]
        s = jnp.einsum("qhd,thd->hqt", _r(qb, emulate), _r(kb, emulate)) \
            * d ** -0.5
        row = (lo + jnp.arange(qb.shape[0]))[:, None]
        col = jnp.arange(kb.shape[0])[None, :]
        p = jax.nn.softmax(jnp.where((col <= row)[None], s, -jnp.inf), -1)
        out.append(jnp.einsum("hqt,thd->qhd", _r(p, emulate),
                              _r(vb, emulate)))
    a = jnp.concatenate(out, 0).reshape(S, H * d)
    return x + _mm(a, w["attn_output.weight"], emulate)


def ffn(hp: dict, w: dict, x, emulate=None):
    import jax

    def rows(b):
        n = rms(b, w, "ffn_norm", hp["eps"])
        return _mm(jax.nn.silu(_mm(n, w["ffn_gate.weight"], emulate))
                   * _mm(n, w["ffn_up.weight"], emulate),
                   w["ffn_down.weight"], emulate)

    return x + _blocks(rows, x)


def start(hp: dict, tensors: dict, tokens, emulate=None):
    """The stream of the embedded tokens (the program's starts bfloat16)."""
    import jax.numpy as jnp

    x = jnp.asarray(tensor(tensors, "token_embd.weight"))[
        jnp.asarray(tokens, jnp.int32)]
    return _r(x, emulate)


def layer(hp: dict, w: dict, i: int, x, emulate=None, skip_norms=(),
          rotate=False, flip_taps=False, state_dtype=None):
    """Layer ``i`` over the whole sequence: the stream after it."""
    if hp["kinds"][i] == "ssm":
        x = ssm(hp, w, x, emulate, skip_norms, flip_taps, state_dtype)
    else:
        x = attention(hp, w, x, emulate, rotate)
    return ffn(hp, w, x, emulate)


def head(hp: dict, tensors: dict, x, emulate=None):
    """The final RMSNorm and the head, which is the embedding (tied)."""
    final = {"n.weight": tensor(tensors, "output_norm.weight")}
    return _mm(rms(x, final, "n", hp["eps"]),
               tensor(tensors, "token_embd.weight"), emulate)


def forward(hp: dict, tensors: dict, tokens, rows=None, emulate=None,
            **controls):
    """Logits (rows, vocab) float32 of the whole sequence ``tokens``;
    ``rows``: the positions wanted (default all)."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        x = start(hp, tensors, tokens, emulate)
        for i in range(hp["n_layers"]):
            x = layer(hp, layer_weights(tensors, i), i, x, emulate,
                      **controls)
        sel = slice(None) if rows is None else jnp.asarray(rows)
        return head(hp, tensors, x[sel], emulate)
