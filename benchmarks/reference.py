"""The plain reference of the block every configuration here runs: dense
grouped-query attention + SwiGLU, RMSNorm, no biases, in straightforward
``jax.numpy`` float32 with ``default_matmul_precision("highest")``: no
kernels, no cache, no batching.  It reads the same GGUF file the server
loads, through this directory's own reader and dequantizers (ggml's
published block layouts), so that it shares nothing with the code under
test.

Departure from the published (Hugging Face) description, noted as the
guide asks: rotary embedding rotates the *interleaved* pairs (2i, 2i+1),
ggml's convention, because a GGUF file stores Q/K permuted to it; on the
same file that is the same function as rotate-half on the unpermuted
weights.

The served path returns no logits today (``PERF.md``, open questions), so
``correct`` cannot use this at published widths yet;
``tests/test_reference.py`` holds the program to it on the CPU at a tiny
size.
"""

from __future__ import annotations

import struct

import numpy as np

from ggufgen import ALIGN, GGML, GGUF_MAGIC

_TYPE_BY_ID = {v[0]: k for k, v in GGML.items()}
_SCALARS = {0: "<B", 1: "<b", 2: "<H", 3: "<h", 4: "<I", 5: "<i", 6: "<f",
            7: "<b", 10: "<Q", 11: "<q", 12: "<d"}


# ---------------------------------------------------------------------------
# reading the file
# ---------------------------------------------------------------------------

def read_gguf(path: str) -> tuple[dict, dict]:
    """(metadata, {tensor name: (numpy-order shape, ggml type, raw bytes)})."""
    buf = np.memmap(path, dtype=np.uint8, mode="r")
    mv = memoryview(buf)
    off = 0

    def take(fmt):
        nonlocal off
        (v,) = struct.unpack_from(fmt, mv, off)
        off += struct.calcsize(fmt)
        return v

    def string():
        nonlocal off
        n = take("<Q")
        s = bytes(mv[off:off + n]).decode("utf-8")
        off += n
        return s

    def value(vtype):
        if vtype == 8:
            return string()
        if vtype == 9:
            etype, n = take("<I"), take("<Q")
            return [value(etype) for _ in range(n)]
        return take(_SCALARS[vtype])

    if take("<I") != GGUF_MAGIC:
        raise ValueError(f"{path} is not a GGUF file")
    take("<I")
    n_tensors, n_kv = take("<Q"), take("<Q")
    meta = {}
    for _ in range(n_kv):
        key = string()
        meta[key] = value(take("<I"))
    infos = []
    for _ in range(n_tensors):
        name = string()
        dims = [take("<Q") for _ in range(take("<I"))]
        infos.append((name, tuple(reversed(dims)), _TYPE_BY_ID[take("<I")],
                      take("<Q")))
    data0 = -(-off // ALIGN) * ALIGN
    tensors = {}
    for name, shape, kind, rel in infos:
        n = int(np.prod(shape))
        nbytes = n // GGML[kind][1] * GGML[kind][2]
        tensors[name] = (shape, kind, buf[data0 + rel:data0 + rel + nbytes])
    return meta, tensors


# ---------------------------------------------------------------------------
# ggml block layouts -> float32
# ---------------------------------------------------------------------------

def _scale_min_k4(sc: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """The eight 6-bit (scale, min) pairs of a Q4_K/Q5_K block's 12 bytes."""
    s = np.empty(sc.shape[:-1] + (8,), np.float32)
    m = np.empty_like(s)
    s[..., :4] = sc[..., 0:4] & 63
    m[..., :4] = sc[..., 4:8] & 63
    s[..., 4:] = (sc[..., 8:12] & 0x0F) | ((sc[..., 0:4] >> 6) << 4)
    m[..., 4:] = (sc[..., 8:12] >> 4) | ((sc[..., 4:8] >> 6) << 4)
    return s, m


def dequantize(kind: str, raw: np.ndarray, shape) -> np.ndarray:
    raw = np.asarray(raw)
    if kind == "F32":
        return raw.view(np.float32).reshape(shape).copy()
    if kind == "F16":
        return raw.view(np.float16).astype(np.float32).reshape(shape)
    blk = raw.reshape(-1, GGML[kind][2])
    nb = blk.shape[0]
    if kind == "Q8_0":
        d = blk[:, 0:2].copy().view(np.float16).astype(np.float32)
        q = blk[:, 2:34].view(np.int8).astype(np.float32)
        return (d * q).reshape(shape)
    if kind in ("Q4_K", "Q5_K"):
        d = blk[:, 0:2].copy().view(np.float16).astype(np.float32)
        dmin = blk[:, 2:4].copy().view(np.float16).astype(np.float32)
        s, m = _scale_min_k4(blk[:, 4:16])
        if kind == "Q4_K":
            qs = blk[:, 16:144].reshape(nb, 4, 32)
            q = np.stack([qs & 0x0F, qs >> 4], axis=2)       # (nb,4,2,32)
        else:
            qh = blk[:, 16:48]
            qs = blk[:, 48:176].reshape(nb, 4, 32)
            q = np.stack([qs & 0x0F, qs >> 4], axis=2).astype(np.uint8)
            for j in range(8):
                q[:, j // 2, j % 2] |= ((qh >> j) & 1) << 4
        q = q.reshape(nb, 8, 32).astype(np.float32)
        out = d[:, :, None] * s[:, :, None] * q - dmin[:, :, None] * m[:, :, None]
        return out.reshape(shape)
    if kind == "Q6_K":
        ql = blk[:, 0:128].reshape(nb, 2, 64)
        qh = blk[:, 128:192].reshape(nb, 2, 32)
        sc = blk[:, 192:208].view(np.int8).astype(np.float32).reshape(nb, 2, 8)
        d = blk[:, 208:210].copy().view(np.float16).astype(np.float32)
        q = np.empty((nb, 2, 4, 32), np.int16)
        q[:, :, 0] = (ql[:, :, :32] & 0x0F) | (((qh >> 0) & 3) << 4)
        q[:, :, 1] = (ql[:, :, 32:] & 0x0F) | (((qh >> 2) & 3) << 4)
        q[:, :, 2] = (ql[:, :, :32] >> 4) | (((qh >> 4) & 3) << 4)
        q[:, :, 3] = (ql[:, :, 32:] >> 4) | (((qh >> 6) & 3) << 4)
        q = (q - 32).astype(np.float32).reshape(nb, 2, 4, 2, 16)
        scale = sc.reshape(nb, 2, 4, 2)[..., None]
        return (d[:, :, None, None, None] * scale * q).reshape(shape)
    raise ValueError(f"no dequantizer for {kind}")


def load_weights(path: str) -> tuple[dict, dict]:
    """(hyper-parameters, float32 weights by GGUF tensor name)."""
    meta, tensors = read_gguf(path)
    arch = meta["general.architecture"]
    hp = {
        "n_layers": meta[f"{arch}.block_count"],
        "n_heads": meta[f"{arch}.attention.head_count"],
        "n_kv_heads": meta[f"{arch}.attention.head_count_kv"],
        "eps": meta[f"{arch}.attention.layer_norm_rms_epsilon"],
        "theta": meta[f"{arch}.rope.freq_base"],
    }
    return hp, {name: dequantize(kind, raw, shape)
                for name, (shape, kind, raw) in tensors.items()}


# ---------------------------------------------------------------------------
# the forward pass
# ---------------------------------------------------------------------------

def forward(hp: dict, w: dict, tokens) -> "jax.Array":
    """Logits (S, vocab) in float32 of the whole sequence ``tokens``."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        S = tokens.shape[0]
        H, KV = hp["n_heads"], hp["n_kv_heads"]
        x = jnp.asarray(w["token_embd.weight"])[tokens]
        hd = x.shape[-1] // H
        pos = jnp.arange(S, dtype=jnp.float32)
        freqs = hp["theta"] ** (-jnp.arange(hd // 2, dtype=jnp.float32)
                                / (hd // 2))
        cos = jnp.cos(pos[:, None] * freqs)[:, None, :]
        sin = jnp.sin(pos[:, None] * freqs)[:, None, :]
        causal = jnp.tril(jnp.ones((S, S), bool))

        def norm(v, g):
            return v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True)
                                     + hp["eps"]) * jnp.asarray(g)

        def rope(v):                       # (S, heads, hd), interleaved pairs
            a, b = v[..., 0::2], v[..., 1::2]
            return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                             -1).reshape(v.shape)

        for i in range(hp["n_layers"]):
            p = f"blk.{i}."
            h = norm(x, w[p + "attn_norm.weight"])
            q = rope((h @ jnp.asarray(w[p + "attn_q.weight"]).T).reshape(S, H, hd))
            k = rope((h @ jnp.asarray(w[p + "attn_k.weight"]).T).reshape(S, KV, hd))
            v = (h @ jnp.asarray(w[p + "attn_v.weight"]).T).reshape(S, KV, hd)
            k = jnp.repeat(k, H // KV, axis=1)
            v = jnp.repeat(v, H // KV, axis=1)
            scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(float(hd))
            scores = jnp.where(causal[None], scores, -jnp.inf)
            att = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
            x = x + att.reshape(S, H * hd) @ jnp.asarray(
                w[p + "attn_output.weight"]).T
            h = norm(x, w[p + "ffn_norm.weight"])
            gate = h @ jnp.asarray(w[p + "ffn_gate.weight"]).T
            up = h @ jnp.asarray(w[p + "ffn_up.weight"]).T
            x = x + (jax.nn.silu(gate) * up) @ jnp.asarray(
                w[p + "ffn_down.weight"]).T
        x = norm(x, w["output_norm.weight"])
        return x @ jnp.asarray(w["output.weight"]).T
