"""EvaByte's vocabulary: 64 control tokens, then one token for each byte
(``vocab_size`` 320), no piece of text at all: ``bytes.py``'s table and
words, under the tokenizer model the program's own byte reader answers to
(``tokenizer.ggml.model = "bytes"``, ``tokenizer/bytes.py``: UTF-8 bytes +
64, control tokens by name, no space prefix, no escape).  A three-letter
word and its space are four tokens, where ``bytes.py``'s ``"llama"`` (a
program with no byte reader: its SentencePiece fallback) pays six; warm-up
measures that, as it measures every vocabulary's.

**No end-of-text token is declared** (no ``tokenizer.ggml.eos_token_id``,
no token by a name the program stops at): a reply runs to its
``max_tokens``, as the other cells' replies do.  There a stop token is one
of 32000 or 50304 entries and seeded random weights never sample it; here
the control tokens are a fifth of the vocabulary, and with ``</s>`` among
them the first chip runs ended a reply after 59 bytes on average (of 260
asked), at random: ``out_tok_s`` 40.6 and 43.7 on two seeds (PERF.md
section 6, PR 35).

ASSUMED: the 64 control names.  The published tokenizer's are not known
here (no network); ``<pad>``, ``<s>`` and placeholders stand in, and the
chat template is the Mistral ``[INST]`` one, spelled in bytes but for
``<s>``.
"""

from ggufgen import vocab_of

_table = vocab_of({"gguf": {"vocabulary": "bytes"}})
word = _table.word
END = "</s>"                     # bytes.py's end-of-text: a placeholder here


def tokenizer_metadata(cfg):
    out = []
    for key, kind, value in _table.tokenizer_metadata(cfg):
        if key == "tokenizer.ggml.eos_token_id":
            continue
        if key == "tokenizer.ggml.model":
            value = "bytes"
        elif key == "tokenizer.ggml.tokens":
            value = [f"<unused_{i}>" if t == END else t
                     for i, t in enumerate(value)]
        out.append((key, kind, value))
    return out
