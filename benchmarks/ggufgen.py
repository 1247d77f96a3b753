"""Configuration file -> GGUF file: random valid quantized weights and a
synthetic SentencePiece vocabulary, from a seed.  numpy only.

The benchmark owns this writer (GGUF v3 is a public format), so that the
file the server loads does not depend on the program's own writer or on a
kernel's memory layout.  Generalised from ``testing.rand_q4k_blocks`` /
``rand_q6k_blocks`` / ``write_llama3_8b_q4km_gguf``: every block is valid
and zero-mean, with a standard deviation of about ``hidden_size ** -0.5``.

The data is written tensor by tensor as it is made (nothing the size of
the file is held in memory) from 64-bit draws, which numpy makes about
eight times faster than 8-bit ones.

What is shared by every file is here: header, vocabulary, random valid
blocks, alignment.  What one block of layers has and another lacks (its
tensors, its hyperparameter keys) is in ``blocks/<block>.py``, found by the
configuration file's ``block`` key (``block_of``).
"""

from __future__ import annotations

import importlib.util
import os
import struct

import numpy as np

GGUF_MAGIC = 0x46554747
GGUF_VERSION = 3
ALIGN = 32

# gguf metadata value types
_U32, _F32, _BOOL, _STR, _ARR, _I32 = 4, 6, 7, 8, 9, 5

#: ggml tensor types: id, elements per block, bytes per block
GGML = {
    "F32": (0, 1, 4), "F16": (1, 1, 2), "Q8_0": (8, 32, 34),
    "Q4_K": (12, 256, 144), "Q5_K": (13, 256, 176), "Q6_K": (14, 256, 210),
}

#: where a block's file is
BLOCK_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "blocks")
_blocks: dict[str, object] = {}       # loaded block files, by path

SPACE = "▁"
_LETTERS = "abcdefghijklmnopqrstuvwxyz"


def tensor_nbytes(kind: str, n_elem: int) -> int:
    _, per, size = GGML[kind]
    if n_elem % per:
        raise ValueError(f"{n_elem} elements do not fill {kind} blocks of {per}")
    return n_elem // per * size


# ---------------------------------------------------------------------------
# vocabulary
# ---------------------------------------------------------------------------

def synth_spm_vocab(vocab_size: int):
    """A SentencePiece-style vocabulary of ``vocab_size`` entries in which
    every lower-case word of three letters (with its leading space) is ONE
    token: specials, the 256 byte tokens, the space, the letters, and the
    chain ``_a`` -> ``_ab`` -> ``_abc`` that the merge loop climbs, padded
    with four-letter words.  A prompt of N such words is N tokens plus the
    chat template's few, whatever the seed.  Returns (tokens, types,
    scores)."""
    tokens = ["<unk>", "<s>", "</s>"]
    types = [2, 3, 3]                       # UNKNOWN, CONTROL, CONTROL
    for b in range(256):
        tokens.append(f"<0x{b:02X}>")
        types.append(6)                     # BYTE
    normal = [SPACE] + list(_LETTERS)
    normal += [SPACE + a for a in _LETTERS]
    normal += [SPACE + a + b for a in _LETTERS for b in _LETTERS]
    normal += [SPACE + a + b + c
               for a in _LETTERS for b in _LETTERS for c in _LETTERS]
    room = vocab_size - len(tokens) - len(normal)
    if room < 0:
        raise ValueError(f"vocab_size {vocab_size} is too small for the "
                         f"three-letter vocabulary ({-room} short)")
    for a in _LETTERS:
        for b in _LETTERS:
            for c in _LETTERS:
                for d in _LETTERS:
                    if room <= 0:
                        break
                    normal.append(SPACE + a + b + c + d)
                    room -= 1
    tokens += normal
    types += [1] * len(normal)              # NORMAL
    # longer pieces merge first, as in a trained model
    scores = [0.0] * (len(tokens) - len(normal)) + \
        [float(len(t)) for t in normal]
    return tokens, types, scores


def word(i: int) -> str:
    """The i-th three-letter word (0 <= i < 17576): one token each."""
    i %= 26 ** 3
    return _LETTERS[i // 676] + _LETTERS[i // 26 % 26] + _LETTERS[i % 26]


MISTRAL_TEMPLATE = (
    "{{bos_token}}{% for m in messages %}{% if m['role'] == 'user' %}"
    "[INST] {{m['content']}} [/INST]{% else %}{{m['content']}}</s>"
    "{% endif %}{% endfor %}")


# ---------------------------------------------------------------------------
# random blocks
# ---------------------------------------------------------------------------

def _f16_bytes(x: float) -> np.ndarray:
    return np.array([x], np.float16).view(np.uint8)


def _random_bytes(rng, n: int) -> np.ndarray:
    return rng.integers(0, 2 ** 64, size=-(-n // 8), dtype=np.uint64
                        ).view(np.uint8)[:n]


def _kquant_head(blk: np.ndarray, d: float, dmin_over_d: float) -> None:
    """Bytes 0..15 of a Q4_K/Q5_K block: f16 d, f16 dmin, 12 bytes of
    packed 6-bit scales and mins.  Each sub-block's min equals its scale,
    so with ``dmin = mid * d`` a weight is ``d * sc * (q - mid)``."""
    blk[:, 0:2] = _f16_bytes(d)
    blk[:, 2:4] = _f16_bytes(d * dmin_over_d)
    blk[:, 4:8] &= 0x3F                      # scales of sub-blocks 0-3
    blk[:, 8:12] = blk[:, 4:8]               # their mins
    lo = blk[:, 12:16] & 0x0F                # sub-blocks 4-7: scale | min << 4
    blk[:, 12:16] = lo | (lo << 4)


def random_blocks(rng, kind: str, n_elem: int, std: float) -> np.ndarray:
    """``n_elem`` weights of ggml type ``kind`` as raw bytes: valid blocks,
    zero mean, a standard deviation of about ``std``."""
    nbytes = tensor_nbytes(kind, n_elem)
    k = std / (4096 ** -0.5)        # the constants below are for 4096 ** -0.5
    if kind == "F32":
        return (rng.standard_normal(n_elem, dtype=np.float32) * std
                ).view(np.uint8)
    if kind == "F16":
        return (rng.standard_normal(n_elem, dtype=np.float32) * std
                ).astype(np.float16).view(np.uint8)
    raw = _random_bytes(rng, nbytes)
    blk = raw.reshape(-1, GGML[kind][2])
    if kind == "Q4_K":              # d * sc * (q - 7.5), q in 0..15
        _kquant_head(blk, 1.5e-4 * k, 7.5)
    elif kind == "Q5_K":            # d * sc * (q - 15.5), q in 0..31
        _kquant_head(blk, 0.75e-4 * k, 15.5)
    elif kind == "Q6_K":            # d * sc * (q - 32), q in 0..63, sc in 1..3
        blk[:, 192:208] = blk[:, 192:208] % 3 + 1
        blk[:, 208:210] = _f16_bytes(4e-4 * k)
    elif kind == "Q8_0":            # d * q, q in -128..127
        blk[:, 0:2] = _f16_bytes(2.1e-4 * k)
    else:
        raise ValueError(f"no random blocks for {kind}")
    return raw


# ---------------------------------------------------------------------------
# the file
# ---------------------------------------------------------------------------

def _s(s: str) -> bytes:
    raw = s.encode("utf-8")
    return struct.pack("<Q", len(raw)) + raw


def _kv(key: str, vtype: int, payload: bytes) -> bytes:
    return _s(key) + struct.pack("<I", vtype) + payload


def _kv_u32(key, v):
    return _kv(key, _U32, struct.pack("<I", int(v)))


def _kv_f32(key, v):
    return _kv(key, _F32, struct.pack("<f", float(v)))


def _kv_str(key, v):
    return _kv(key, _STR, _s(v))


def _kv_bool(key, v):
    return _kv(key, _BOOL, struct.pack("<b", 1 if v else 0))


def _kv_arr(key, etype, items):
    head = struct.pack("<IQ", etype, len(items))
    if etype == _STR:
        body = b"".join(_s(x) for x in items)
    elif etype == _I32:
        body = np.asarray(items, "<i4").tobytes()
    elif etype == _F32:
        body = np.asarray(items, "<f4").tobytes()
    else:
        raise ValueError(etype)
    return _kv(key, _ARR, head + body)


def block_of(cfg: dict):
    """The module ``blocks/<block>.py`` of the configuration's ``block``
    (absent: ``dense``): the one place that knows that block's tensors,
    metadata keys and costs.  An unknown block is an error, never a
    default."""
    name = cfg.get("block", "dense")
    path = os.path.join(BLOCK_DIR, f"{name}.py")
    if not os.path.exists(path):
        raise KeyError(f"no block {name!r}: no {name}.py in {BLOCK_DIR}")
    if path not in _blocks:
        spec = importlib.util.spec_from_file_location(
            "block_" + name.replace("-", "_").replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _blocks[path] = mod
    return _blocks[path]


def tensor_plan(cfg: dict) -> list[tuple[str, tuple[int, ...], str]]:
    """(name, numpy-order shape of any rank, ggml type) of every tensor of
    the configuration's block, in file order."""
    return block_of(cfg).tensor_plan(cfg)


def transformer_metadata(cfg: dict, arch: str) -> list[tuple[str, str, object]]:
    """The hyperparameter keys of a decoder of attention + feed-forward
    layers as (key, ``u32`` | ``f32`` | ``str`` | ``bool``, value): what a
    block's ``metadata`` starts from where it is such a decoder."""
    head_dim = cfg.get("head_dim") or \
        cfg["hidden_size"] // cfg["num_attention_heads"]
    meta = [
        (f"{arch}.block_count", "u32", cfg["num_hidden_layers"]),
        (f"{arch}.context_length", "u32", cfg["max_position_embeddings"]),
        (f"{arch}.embedding_length", "u32", cfg["hidden_size"]),
        (f"{arch}.feed_forward_length", "u32", cfg["intermediate_size"]),
        (f"{arch}.attention.head_count", "u32", cfg["num_attention_heads"]),
        (f"{arch}.attention.head_count_kv", "u32", cfg["num_key_value_heads"]),
        (f"{arch}.rope.dimension_count", "u32", head_dim),
        (f"{arch}.attention.layer_norm_rms_epsilon", "f32", cfg["rms_norm_eps"]),
        (f"{arch}.rope.freq_base", "f32", cfg["rope_theta"]),
        (f"{arch}.vocab_size", "u32", cfg["vocab_size"]),
    ]
    if cfg.get("sliding_window"):
        meta.append((f"{arch}.attention.sliding_window", "u32",
                     cfg["sliding_window"]))
    return meta


_KV = {"u32": _kv_u32, "f32": _kv_f32, "str": _kv_str, "bool": _kv_bool}


def write_gguf(cfg: dict, path: str) -> int:
    """Write the configuration's GGUF file to ``path`` (through a temporary
    name beside it, renamed when whole).  Returns its size in bytes."""
    arch = cfg["gguf"].get("architecture", "llama")
    block = block_of(cfg)
    tokens, types, scores = synth_spm_vocab(cfg["vocab_size"])
    meta = [
        _kv_str("general.architecture", arch),
        _kv_str("general.name", cfg["name"]),
    ]
    meta += [_KV[kind](key, value)
             for key, kind, value in block.metadata(cfg, arch)]
    meta += [
        _kv_str("tokenizer.ggml.model", "llama"),
        _kv_arr("tokenizer.ggml.tokens", _STR, tokens),
        _kv_arr("tokenizer.ggml.token_type", _I32, types),
        _kv_arr("tokenizer.ggml.scores", _F32, scores),
        _kv_u32("tokenizer.ggml.bos_token_id", 1),
        _kv_u32("tokenizer.ggml.eos_token_id", 2),
        _kv_bool("tokenizer.ggml.add_bos_token", True),
        _kv_str("tokenizer.chat_template", MISTRAL_TEMPLATE),
    ]
    plan = block.tensor_plan(cfg)
    infos, offset = [], 0
    for name, shape, kind in plan:
        n = int(np.prod(shape))
        infos.append(_s(name) + struct.pack("<I", len(shape))
                     + b"".join(struct.pack("<Q", dim) for dim in reversed(shape))
                     + struct.pack("<IQ", GGML[kind][0], offset))
        offset += -(-tensor_nbytes(kind, n) // ALIGN) * ALIGN
    std = cfg["hidden_size"] ** -0.5
    seed = int(cfg["gguf"]["weights_seed"])
    tmp = path + ".part"
    with open(tmp, "wb") as f:
        f.write(struct.pack("<IIQQ", GGUF_MAGIC, GGUF_VERSION,
                            len(plan), len(meta)))
        for m in meta:
            f.write(m)
        for info in infos:
            f.write(info)
        f.write(b"\x00" * (-f.tell() % ALIGN))
        for i, (name, shape, kind) in enumerate(plan):
            n = int(np.prod(shape))
            if name.endswith("_norm.weight"):
                raw = np.ones(n, np.float32).view(np.uint8)
            else:
                raw = random_blocks(np.random.default_rng([seed, i]),
                                    kind, n, std)
            f.write(memoryview(raw))
            f.write(b"\x00" * (-raw.nbytes % ALIGN))
        size = f.tell()
    os.replace(tmp, path)
    return size
