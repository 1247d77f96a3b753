"""http + admission queue: 90th percentile of the ``queue`` span (enqueue to
the consumer's pickup) over the window's requests.  program_span."""
from metrics import percentile
from spans import named


def read(run):
    waits = [s["duration_s"] * 1e3 for s in named(run["traces"], "queue")]
    return percentile(waits, 90)
