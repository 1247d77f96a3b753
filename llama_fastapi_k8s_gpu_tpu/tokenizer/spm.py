"""SentencePiece-style tokenizer (GGUF ``tokenizer.ggml.model == "llama"``).

The Mistral / Llama-2 family tokenizer: pieces carry scores
(``tokenizer.ggml.scores``); encoding greedily merges the adjacent pair with
the highest-scoring concatenation (ties broken leftmost), with per-byte
``<0xXX>`` fallback for anything outside the vocab.  Whitespace is escaped to
U+2581 and a dummy space prefix is added, matching sentencepiece defaults.

The merge loop is pure Python, about 10 us a token, and runs before a
request's first device dispatch.  Where the vocabulary allows it
(:attr:`SPMTokenizer.cuts_at_spaces`) the escaped text is cut into pieces
that no merge can cross and each piece's ids are remembered.
"""

from __future__ import annotations

import heapq
import re
import threading
from itertools import chain
from typing import Iterable, Sequence

from .base import Tokenizer, TokenType

_SPACE = "▁"  # ▁
#: a piece: a run of ``▁`` and then a run of anything else (``▁▁▁foo\n``,
#: ``▁bar``), so that every cut lies before a ``▁`` that follows another
#: character
_PIECES = re.compile(f"{_SPACE}*[^{_SPACE}]+|{_SPACE}+")
#: the memo of piece -> ids: a longer piece goes through the merge loop
#: every time (a 50 kB run without a space is one piece), and the memo is
#: emptied when it holds this many (a three-letter word's entry is about
#: 150 bytes: some 10 MB full)
MEMO_PIECE_CHARS = 32
MEMO_CAP = 65536


class SPMTokenizer(Tokenizer):
    def __init__(
        self,
        tokens: Sequence[str],
        scores: Sequence[float],
        token_types: Sequence[int] | None = None,
        bos_id: int | None = 1,
        eos_id: int | None = 2,
        add_bos: bool = True,
        add_space_prefix: bool = True,
    ):
        super().__init__(tokens, token_types, bos_id, eos_id, add_bos)
        self.scores = list(scores)
        self.add_space_prefix = add_space_prefix
        self._byte_ids = {}
        for i, t in enumerate(self.tokens):
            if self.token_types[i] == TokenType.BYTE and len(t) == 6 and t.startswith("<0x"):
                self._byte_ids[int(t[3:5], 16)] = i
        #: no entry holds a ``▁`` after another character (past its leading
        #: run of ``▁`` it has none: a model trained with sentencepiece's
        #: default split_by_whitespace), and a merged symbol that spanned a
        #: cut of ``_PIECES`` would be such an entry: the pieces never
        #: interact, the (score, leftmost) order inside a piece is what the
        #: whole-text heap gives it, and the pieces' ids in a row ARE the
        #: whole text's.  A vocabulary that has one keeps the whole-text
        #: loop (/health engine.tokenizer says so)
        self.cuts_at_spaces = not any(
            _SPACE in t.lstrip(_SPACE) for t in self.tokens)
        self._memo: dict[str, tuple[int, ...]] = {}
        # [pieces, memo hits] by thread: a thread adds to its own row only,
        # so nothing is lost without a lock and a caller reads what ITS
        # encode counted (the scheduler thread and server/app.py's
        # to_thread hook tokenize side by side)
        self._tallies: dict[int, list[int]] = {}

    # ------------------------------------------------------------------
    def _encode_fragment(self, text: str) -> list[int]:
        if not text:
            return []
        if self.add_space_prefix:
            text = " " + text
        text = text.replace(" ", _SPACE)
        if not self.cuts_at_spaces:
            return self._merge(text)
        pieces = _PIECES.findall(text)
        memo = self._memo
        found = list(map(memo.get, pieces))
        misses = 0
        if None in found:
            for k, ids in enumerate(found):
                if ids is not None:
                    continue
                # a piece twice in one text is a miss once
                piece = pieces[k]
                ids = memo.get(piece)
                if ids is None:
                    misses += 1
                    ids = tuple(self._merge(piece))
                    if len(piece) <= MEMO_PIECE_CHARS:
                        if len(memo) >= MEMO_CAP:
                            # a new dict, not clear(): a reader beside this
                            # thread keeps the one it holds
                            memo = self._memo = {}
                        memo[piece] = ids
                found[k] = ids
        tally = self._tallies.setdefault(threading.get_ident(), [0, 0])
        tally[0] += len(pieces)
        tally[1] += len(pieces) - misses
        return list(chain.from_iterable(found))

    def piece_counts(self, thread_only: bool = False) -> tuple[int, int]:
        """(pieces encoded, pieces answered from the memo), cumulative: over
        every thread (the /metrics counters), or the calling thread's own
        (the difference across a call is that call's)."""
        if thread_only:
            rows = [self._tallies.get(threading.get_ident(), (0, 0))]
        else:
            rows = list(self._tallies.values())
        return sum(r[0] for r in rows), sum(r[1] for r in rows)

    def _merge(self, text: str) -> list[int]:
        """THE merge loop, over the escaped ``text``: a piece's, or the
        whole text's where the vocabulary does not cut."""
        symbols: list[str] = list(text)  # start from single characters
        # neighbor links: alive[i] is None if merged away
        prev = list(range(-1, len(symbols) - 1))
        nxt = list(range(1, len(symbols) + 1))
        alive = [True] * len(symbols)

        def score_of(s: str):
            tid = self.token_to_id.get(s)
            if tid is None:
                return None
            return self.scores[tid] if tid < len(self.scores) else 0.0

        heap: list[tuple[float, int, int, str]] = []

        def push(i: int):
            j = nxt[i]
            if j >= len(symbols):
                return
            merged = symbols[i] + symbols[j]
            sc = score_of(merged)
            if sc is not None:
                # max score first; ties → leftmost (llama.cpp llm_symbol_bigram)
                heapq.heappush(heap, (-sc, i, j, merged))

        for i in range(len(symbols) - 1):
            push(i)

        while heap:
            _, i, j, merged = heapq.heappop(heap)
            if not alive[i] or not alive[j] or symbols[i] + symbols[j] != merged:
                continue
            symbols[i] = merged
            alive[j] = False
            nxt[i] = nxt[j]
            if nxt[j] < len(symbols):
                prev[nxt[j]] = i
            if prev[i] >= 0:
                push(prev[i])
            push(i)

        ids: list[int] = []
        i = 0
        while i < len(symbols):
            if not alive[i]:
                i = nxt[i]
                continue
            sym = symbols[i]
            tid = self.token_to_id.get(sym)
            if tid is not None:
                ids.append(tid)
            else:
                for b in sym.encode("utf-8"):
                    if b in self._byte_ids:
                        ids.append(self._byte_ids[b])
                    elif self.token_to_id.get("<unk>") is not None:
                        ids.append(self.token_to_id["<unk>"])
            i = nxt[i]
        return ids

    def decode_bytes(self, ids: Iterable[int], skip_special: bool = True) -> bytes:
        buf = bytearray()
        first_real = True
        for tid in ids:
            ttype = self.token_types[tid]
            piece = self.tokens[tid]
            if ttype == TokenType.CONTROL:
                if not skip_special:
                    buf.extend(piece.encode("utf-8"))
                continue
            if ttype == TokenType.BYTE:
                buf.append(int(piece[3:5], 16))
                first_real = False
                continue
            text = piece.replace(_SPACE, " ")
            if first_real and self.add_space_prefix and text.startswith(" "):
                text = text[1:]  # drop the dummy prefix space
            first_real = False
            buf.extend(text.encode("utf-8"))
        return bytes(buf)
