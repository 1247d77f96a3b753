"""tokenizer: median over the window's requests of the ``tokenize`` span:
chat template + tokenizer, on the thread that then prefills (the serial
engine's worker, the lane scheduler).  program_span."""
from metrics import percentile
from spans import named


def read(run):
    return percentile([s["duration_s"] * 1e3
                       for s in named(run["traces"], "tokenize")], 50)
