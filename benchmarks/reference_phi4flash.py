"""The plain reference of the ``phi4flash`` block (Phi-4-mini-flash-reasoning,
``model_type: phi4flash``), beside ``reference.py`` (whose GGUF reader and
dequantizers it uses: ggml's published block layouts, nothing of the
program) and ``reference_mla.py`` (whose rounding helpers it uses):
straightforward ``jax.numpy`` float32 under
``default_matmul_precision("highest")``, the whole sequence at once, the
scan a plain ``lax.scan`` over positions, FOUR softmaxes a pair of heads, no
cache, no carried rows or states, no lanes, no kernels, no packed rows, no
layer skipped at any position.  Written from the issue's equations, not from
the program.

``x`` the stream (S, D); ``LN`` a LayerNorm with weight and bias, eps from
the file; every layer is ``x += mixer(LN1(x)); x += W_down(silu(W_gate n) *
W_up n)``, ``n = LN2(x)``; a final LayerNorm, then the head, which is the
embedding matrix.  No positional encoding anywhere.  The mixer by
``mixer_types[i]``:

``ssm`` (Mamba-1; C = ssm.inner_size, N = ssm.state_size, L =
ssm.conv_kernel, R = ssm.time_step_rank):

    [u, z] = W_in n                         rows of u, then of z
    u_t = silu(sum_j k[:, j] u_(t-(L-1)+j) + b_conv),  u_s = 0 for s < 0
    [dt, B, C] = W_x u                      R, N, N columns
    dt = softplus(W_dt dt + b_dt);  A (C, N), negative
    s_t = exp(dt_t[c] A[c, n]) s_(t-1) + dt_t[c] B_t[n] u_t[c],  s_(-1) = 0
    y_t[c] = sum_n C_t[n] s_t[c, n] + D[c] u_t[c]
    x + W_out (y * silu(z));   the LAST ssm layer's y is ``m``

``window`` / ``full`` (H heads on K KV heads of d; window
attention.sliding_window), differential, heads in pairs by even and odd:

    q = W_q n + b_q, k = W_k n + b_k, v = W_v n + b_v
    q1, q2 = q[even], q[odd]; k1, k2, v1, v2 alike; query pair p reads KV
    pair p // (H / K)
    a1 = softmax(q1 k1^T / sqrt(d)) [v1 | v2];  a2 = softmax(q2 k2^T /
    sqrt(d)) [v1 | v2]      causal (window: the last W positions)
    lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0,  lam0 = 0.8 - 0.6
    exp(-0.3 l), l the layer's depth
    a = RMSNorm_2d(a1 - lam a2; attn_sub_norm) (1 - lam0);   x + W_o a + b_o

``gmu``: ``x + W_out (m * silu(W_in n))``.  ``cross``: ``q = W_q n + b_q``
alone; the keys and values are the ``full`` layer's, causal over all
positions; differential with the layer's own lambdas and sub-norm; ``W_o``.

ASSUMED (the configuration file's ``assumed`` says the same): all of the
above is the released modelling code as remembered; the catalog row gives
the sizes alone.  Departures from the published model: (1) the Hugging Face
checkpoint fuses ``Wqkv`` and ``gate_up``; the file holds them split, as a
llama.cpp conversion would; (2) where the file says ``ssm.values =
init_offsets`` (the benchmark's file), ``ssm_a`` and ``ssm_dt.bias`` hold
small random OFFSETS from Mamba's initialisation and :func:`ssm_values`
folds them, because the benchmark's writer gives a block no say over values
(the fold is part of READING the file, not of the equations).

``emulate``: a dtype the inputs of every matrix product and of the
attention's two products are rounded to (``bfloat16``: what the program's
kernels take; ``float8_e4m3fn``: the nearest precision below, a control).
The other CONTROLS, each a different function that a comparison with a sound
limit must tell from this one: ``no_lam`` (``a1`` alone, without ``- lam
a2``), ``m_after_gate`` (``m = y * silu(z)``), ``flip_taps`` (the conv taps
newest first).  ``state_dtype`` (the state rounded to that dtype at every
position) is a different function too, but on a file of small random B, C
and D it moves ``m`` by 1e-4: printed by the comparison, held by no limit.
"""

from __future__ import annotations

import math

import numpy as np

from reference import dequantize, read_gguf
from reference_mla import _mm, _r

ROWS = 256


def open_model(path: str) -> tuple[dict, dict]:
    """(hyper-parameters, {tensor name: (shape, ggml type, raw bytes)})."""
    meta, tensors = read_gguf(path)
    arch = meta["general.architecture"]
    g = lambda key, default=None: meta.get(f"{arch}.{key}", default)  # noqa: E731
    kinds = str(g("mixer_types")).split(",")
    hp = {
        "n_layers": g("block_count"), "kinds": kinds,
        "n_heads": g("attention.head_count"),
        "n_kv": g("attention.head_count_kv"),
        "d": g("attention.key_length") or g("embedding_length")
        // g("attention.head_count"),
        "window": g("attention.sliding_window"),
        "eps": g("attention.layer_norm_rms_epsilon"),
        "C": g("ssm.inner_size"), "N": g("ssm.state_size"),
        "L": g("ssm.conv_kernel"), "R": g("ssm.time_step_rank"),
        "values": g("ssm.values", "stored"), "vocab": g("vocab_size"),
        "full": kinds.index("full"),
        "last_ssm": max(i for i, k in enumerate(kinds) if k == "ssm"),
    }
    return hp, tensors


def tensor(tensors: dict, name: str) -> np.ndarray:
    shape, kind, raw = tensors[name]
    return dequantize(kind, raw, shape)


def layer_weights(tensors: dict, i: int) -> dict:
    """Layer ``i``'s tensors in float32 under their names after ``blk.i.``
    (``attn_q.weight``, ``attn_q.bias``, ``ssm_a`` ...)."""
    p = f"blk.{i}."
    return {name[len(p):]: tensor(tensors, name)
            for name in tensors if name.startswith(p)}


def ssm_values(hp: dict, w: dict) -> tuple[np.ndarray, np.ndarray]:
    """(A (C, N), b_dt (C,)) of an ssm layer: as stored, or folded from
    offsets (departure (2) above): ``A = -exp(log(n + 1) + ssm_a)``, ``b_dt
    = softplus^-1(dt0) + ssm_dt.bias``, ``dt0[c] = exp(ln 1e-3 + frac(c
    phi) (ln 1e-1 - ln 1e-3))``, phi the golden ratio's fraction."""
    a, b = w["ssm_a"].astype(np.float32), w["ssm_dt.bias"].astype(np.float32)
    if hp["values"] == "init_offsets":
        C, N = a.shape
        a = -np.exp(np.log(np.arange(1, N + 1, dtype=np.float32))[None] + a)
        u = (np.arange(C, dtype=np.float64) * 0.6180339887498949) % 1.0
        dt0 = np.exp(math.log(1e-3) + u * (math.log(1e-1) - math.log(1e-3)))
        b = (dt0 + np.log(-np.expm1(-dt0))).astype(np.float32) + b
    return a, b


def ln(v, w: dict, name: str, eps: float):
    import jax
    import jax.numpy as jnp

    c = v - jnp.mean(v, -1, keepdims=True)
    return c * jax.lax.rsqrt(jnp.mean(c * c, -1, keepdims=True) + eps) \
        * jnp.asarray(w[name + ".weight"]) + jnp.asarray(w[name + ".bias"])


def ssm(hp: dict, w: dict, x, emulate=None, flip_taps=False,
        m_after_gate=False, state_dtype=None):
    """An ssm layer's mixer branch over the whole sequence: (x + branch,
    m)."""
    import jax
    import jax.numpy as jnp

    S = x.shape[0]
    C, N, L, R = hp["C"], hp["N"], hp["L"], hp["R"]
    n = ln(x, w, "attn_norm", hp["eps"])
    uz = _mm(n, w["ssm_in.weight"], emulate)
    # (the program's stream holds u and z in bfloat16)
    u, z = _r(uz[:, :C], emulate), _r(uz[:, C:], emulate)
    k = jnp.asarray(w["ssm_conv1d.weight"])                   # (C, L)
    if flip_taps:
        k = k[:, ::-1]
    run = jnp.concatenate([jnp.zeros((L - 1, C), u.dtype), u])
    u = jax.nn.silu(sum(k[:, j][None, :] * run[j:j + S] for j in range(L))
                    + jnp.asarray(w["ssm_conv1d.bias"]))
    dbc = _mm(u, w["ssm_x.weight"], emulate)
    a, b_dt = ssm_values(hp, w)
    dt = jax.nn.softplus(dbc[:, :R] @ jnp.asarray(w["ssm_dt.weight"]).T
                         + jnp.asarray(b_dt))
    B, Cm = dbc[:, R:R + N], dbc[:, R + N:]
    a, d = jnp.asarray(a), jnp.asarray(w["ssm_d"])

    def step(s, row):
        ut, dtt, bt, ct = row
        s = jnp.exp(dtt[:, None] * a) * s + (dtt * ut)[:, None] * bt[None, :]
        if state_dtype is not None:
            # (``reduce_precision``, not a pair of casts: the TPU's compiler
            # may keep the excess precision of a float32 that is cast down
            # and up again, and did: the control then read 0.0)
            kind = jnp.finfo(state_dtype)
            s = jax.lax.reduce_precision(s, kind.nexp, kind.nmant)
        return s, s @ ct + d * ut

    _, y = jax.lax.scan(step, jnp.zeros((C, N), jnp.float32),
                        (u, dt, B, Cm))
    gated = y * jax.nn.silu(z)
    return x + _mm(gated, w["ssm_out.weight"], emulate), \
        gated if m_after_gate else y


def _pair_softmax(hp, q, k, v, window, emulate):
    """softmax(q k^T / sqrt(d)) v, causal, for (S, P, d) queries on (S, P,
    d) keys and (S, P, 2 d) values, pair by pair, query rows in blocks."""
    import jax
    import jax.numpy as jnp

    S, d = q.shape[0], hp["d"]
    out = []
    for lo in range(0, S, ROWS):
        qb = q[lo:lo + ROWS]
        first = max(lo - window + 1, 0) if window else 0
        kb, vb = k[first:lo + ROWS], v[first:lo + ROWS]
        s = jnp.einsum("qpd,tpd->pqt", _r(qb, emulate), _r(kb, emulate)) \
            * d ** -0.5
        row = (lo + jnp.arange(qb.shape[0]))[:, None]
        col = (first + jnp.arange(kb.shape[0]))[None, :]
        mask = col <= row
        if window:
            mask &= col > row - window
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), -1)
        out.append(jnp.einsum("pqt,tpe->qpe", _r(p, emulate),
                              _r(vb, emulate)))
    return jnp.concatenate(out, 0)


def differential(hp: dict, w: dict, q, k, v, depth: int, window: int,
                 emulate=None, no_lam=False):
    """The pairs' outputs (S, H d): ``q`` (S, H, d), ``k`` / ``v`` (S, K, d)."""
    import jax
    import jax.numpy as jnp

    S, d, H, K = q.shape[0], hp["d"], hp["n_heads"], hp["n_kv"]
    q1, q2 = q[:, 0::2], q[:, 1::2]                        # (S, H / 2, d)
    rep = (H // 2) // (K // 2)
    k1, k2 = (jnp.repeat(k[:, e::2], rep, axis=1) for e in (0, 1))
    vv = jnp.repeat(jnp.concatenate([v[:, 0::2], v[:, 1::2]], -1), rep, 1)
    # four softmaxes a pair: (q1, k1) and (q2, k2), each on v1 and on v2
    a1 = jnp.concatenate([_pair_softmax(hp, q1, k1, vv[..., :d], window,
                                        emulate),
                          _pair_softmax(hp, q1, k1, vv[..., d:], window,
                                        emulate)], -1)
    a2 = jnp.concatenate([_pair_softmax(hp, q2, k2, vv[..., :d], window,
                                        emulate),
                          _pair_softmax(hp, q2, k2, vv[..., d:], window,
                                        emulate)], -1)
    lam0 = 0.8 - 0.6 * math.exp(-0.3 * depth)
    lam = math.exp(float(np.dot(w["attn_lambda_q1"], w["attn_lambda_k1"]))) \
        - math.exp(float(np.dot(w["attn_lambda_q2"], w["attn_lambda_k2"]))) \
        + lam0
    a = a1 if no_lam else a1 - lam * a2
    a = a * jax.lax.rsqrt(jnp.mean(a * a, -1, keepdims=True) + hp["eps"]) \
        * jnp.asarray(w["attn_sub_norm.weight"]) * (1.0 - lam0)
    return a.reshape(S, H * d)


def _proj(n, w, name, emulate):
    import jax.numpy as jnp

    return _mm(n, w[name + ".weight"], emulate) + jnp.asarray(
        w[name + ".bias"])


def attention(hp: dict, w: dict, x, depth: int, window: int, emulate=None,
              no_lam=False):
    """A window or full layer's mixer branch: (x + branch, (k, v))."""
    S, d = x.shape[0], hp["d"]
    n = ln(x, w, "attn_norm", hp["eps"])
    q = _proj(n, w, "attn_q", emulate).reshape(S, hp["n_heads"], d)
    k = _proj(n, w, "attn_k", emulate).reshape(S, hp["n_kv"], d)
    v = _proj(n, w, "attn_v", emulate).reshape(S, hp["n_kv"], d)
    a = differential(hp, w, q, k, v, depth, window, emulate, no_lam)
    return x + _proj(a, w, "attn_output", emulate), (k, v)


def cross(hp: dict, w: dict, x, kv, depth: int, emulate=None, no_lam=False):
    S = x.shape[0]
    n = ln(x, w, "attn_norm", hp["eps"])
    q = _proj(n, w, "attn_q", emulate).reshape(S, hp["n_heads"], hp["d"])
    a = differential(hp, w, q, kv[0], kv[1], depth, 0, emulate, no_lam)
    return x + _proj(a, w, "attn_output", emulate)


def gmu(hp: dict, w: dict, x, m, emulate=None):
    import jax

    n = ln(x, w, "attn_norm", hp["eps"])
    return x + _mm(m * jax.nn.silu(_mm(n, w["gmu_in.weight"], emulate)),
                   w["gmu_out.weight"], emulate)


def ffn(hp: dict, w: dict, x, emulate=None):
    import jax

    n = ln(x, w, "ffn_norm", hp["eps"])
    return x + _mm(jax.nn.silu(_mm(n, w["ffn_gate.weight"], emulate))
                   * _mm(n, w["ffn_up.weight"], emulate),
                   w["ffn_down.weight"], emulate)


def start(hp: dict, tensors: dict, tokens, emulate=None) -> dict:
    """The state :func:`layer` steps: the stream of the embedded tokens."""
    import jax.numpy as jnp

    x = jnp.asarray(tensor(tensors, "token_embd.weight"))[
        jnp.asarray(tokens, jnp.int32)]
    # (the program's stream starts bfloat16)
    return {"x": _r(x, emulate), "m": None, "kv": None, "tap": None}


def layer(hp: dict, w: dict, i: int, st: dict, emulate=None, no_lam=False,
          m_after_gate=False, flip_taps=False, state_dtype=None) -> dict:
    """Layer ``i`` over the whole sequence: the state after it (``tap``: the
    stream after the full layer, ``m``: the last ssm layer's)."""
    kind, x = hp["kinds"][i], st["x"]
    st = dict(st)
    if kind == "ssm":
        x, y = ssm(hp, w, x, emulate, flip_taps, m_after_gate, state_dtype)
        if i == hp["last_ssm"]:
            st["m"] = y
    elif kind in ("window", "full"):
        x, mine = attention(hp, w, x, i,
                            hp["window"] if kind == "window" else 0,
                            emulate, no_lam)
        if kind == "full":
            st["kv"] = mine
    elif kind == "gmu":
        x = gmu(hp, w, x, st["m"], emulate)
    else:
        x = cross(hp, w, x, st["kv"], i, emulate, no_lam)
    st["x"] = ffn(hp, w, x, emulate)
    if i == hp["full"]:
        st["tap"] = st["x"]
    return st


def head(hp: dict, tensors: dict, x, emulate=None):
    """The final LayerNorm and the head, which is the embedding (tied)."""
    final = {"n." + k: tensor(tensors, "output_norm." + k)
             for k in ("weight", "bias")}
    return _mm(ln(x, final, "n", hp["eps"]),
               tensor(tensors, "token_embd.weight"), emulate)


def forward(hp: dict, tensors: dict, tokens, rows=None, emulate=None,
            **controls):
    """(logits (rows, vocab) float32, the stream after the full layer
    (rows, D), m (rows, C)) of the whole sequence ``tokens``; ``rows``: the
    positions wanted (default all)."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        st = start(hp, tensors, tokens, emulate)
        for i in range(hp["n_layers"]):
            st = layer(hp, layer_weights(tensors, i), i, st, emulate,
                       **controls)
        sel = slice(None) if rows is None else jnp.asarray(rows)
        return head(hp, tensors, st["x"][sel], emulate), st["tap"][sel], \
            st["m"][sel]
