"""Byte-level tokenizer (GGUF ``tokenizer.ggml.model == "bytes"``, this
repo's own value: llama.cpp has no reader for a vocabulary with no pieces).

The vocabulary is a block of control tokens followed by one token for each
of the 256 byte values (``<0xNN>``, type BYTE, contiguous and in order):
EvaByte's 64 + 256.  Text is its UTF-8 bytes plus the block's offset: no
pieces, no merges, no space prefix, no escape, so a space is one token and
comes back a space.  Control tokens are matched by name under
``parse_special`` (the chat templates spell their markers with them).

``decode_bytes`` hands back the raw byte stream, append-only across
incremental decodes; a multi-byte character cut by a stream chunk's edge is
held back, not replaced, by the engines' incremental UTF-8 decoder
(engine/engine.py ``_TextEmitter``).
"""

from __future__ import annotations

from typing import Iterable, Sequence

from .base import Tokenizer, TokenType

N_BYTES = 256


class ByteTokenizer(Tokenizer):
    def __init__(
        self,
        tokens: Sequence[str],
        token_types: Sequence[int] | None,
        bos_id: int | None = None,
        eos_id: int | None = None,
        add_bos: bool = True,
    ):
        super().__init__(tokens, token_types, bos_id, eos_id, add_bos)
        byte_ids = [i for i, t in enumerate(self.token_types)
                    if t == TokenType.BYTE]
        want = [f"<0x{b:02X}>" for b in range(N_BYTES)]
        if not byte_ids or byte_ids != list(
                range(byte_ids[0], byte_ids[0] + N_BYTES)) \
                or [self.tokens[i] for i in byte_ids] != want:
            raise ValueError(
                "a 'bytes' vocabulary holds the 256 byte tokens <0x00>.."
                f"<0xFF> (type BYTE) contiguous and in order; found "
                f"{len(byte_ids)} BYTE tokens")
        #: id of byte 0: the number of tokens before the byte block
        self.byte_offset = byte_ids[0]

    def _encode_fragment(self, text: str) -> list[int]:
        off = self.byte_offset
        return [off + b for b in text.encode("utf-8")]

    def decode_bytes(self, ids: Iterable[int], skip_special: bool = True) -> bytes:
        off = self.byte_offset
        buf = bytearray()
        for tid in ids:
            if off <= tid < off + N_BYTES:
                buf.append(tid - off)
            elif not skip_special or not self.is_control(tid):
                buf.extend(self.tokens[tid].encode("utf-8"))
        return bytes(buf)
