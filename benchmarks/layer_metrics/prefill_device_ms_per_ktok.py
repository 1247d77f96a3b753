"""model step: device time of prefill per thousand prompt tokens: the
median duration of the prefill programs on the capture's ``XLA Modules``
line (``jit_prefill_chunk_jit``, one slice; ``jit_prefill_jit``, a prompt
of one slice or less) over the median ``tokens`` of the ``prefill_slice``
spans.  Beside ``prefill_ms_per_ktok`` (the host's span, waiting included)
it says whether prefill is a slow program or a waiting one.  None where
the capture holds no prefill: there is no honest number then, so the
metric is declared (its ``workloads`` list) only for cells whose every 3 s
capture holds one, which leaves out one stream of chat.  device_trace."""
import re

from metrics import percentile
from spans import named

PROGRAM = re.compile(r"prefill")


def read(run):
    p = run.get("profile")
    if not p:
        return None
    durs = [d for n, _, d in p["modules"] if PROGRAM.search(n)]
    tokens = percentile([(s.get("attrs") or {}).get("tokens")
                         for s in named(run["traces"], "prefill_slice")
                         if (s.get("attrs") or {}).get("tokens")], 50)
    if not durs or not tokens:
        return None
    return percentile(durs, 50) * 1e3 / (tokens / 1000.0)
