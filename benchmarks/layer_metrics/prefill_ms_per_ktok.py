"""model step: median over requests of the ``prefill`` span (tokenize to
first sampled token) per thousand prompt tokens.  program_span."""
from metrics import percentile
from spans import named


def read(run):
    vals = []
    for s in named(run["traces"], "prefill"):
        n = (s.get("attrs") or {}).get("n_prompt")
        if n:
            vals.append(s["duration_s"] * 1e3 / (n / 1000.0))
    return percentile(vals, 50)
