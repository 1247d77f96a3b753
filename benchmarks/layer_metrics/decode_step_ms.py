"""model step: median over decode chunks of the chunk's span over the
tokens decoded in it: host time per step, dispatch and fetch included.
program_span."""
from metrics import percentile
from spans import decode_chunks


def read(run):
    return percentile([(end - start) * 1e3 / tokens
                       for start, end, tokens in decode_chunks(run["traces"])],
                      50)
