"""scheduler: median self time of the ``prefill`` span: its duration less
its children ``tokenize``, ``prefill_slice`` (each slice's host dispatch)
and ``first_token``.  What is left is what an admission spent waiting
between slices: each slice goes out in its own scheduler wave, behind that
wave's decode chunk.  None for a program whose prefill has no such
children.  program_span."""
from metrics import percentile
from spans import named

CHILDREN = ("tokenize", "prefill_slice", "first_token")


def read(run):
    vals = []
    for s in named(run["traces"], "prefill"):
        kids = [c for c in s.get("children") or []
                if c.get("name") in CHILDREN and c.get("end") is not None]
        if kids:
            vals.append((s["duration_s"]
                         - sum(c["duration_s"] for c in kids)) * 1e3)
    return percentile(vals, 50)
