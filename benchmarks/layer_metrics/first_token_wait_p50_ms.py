"""scheduler: median of the ``first_token`` span: from the return of the
last prefill slice's dispatch to the first sampled token on the host.  The
serial engine fetches it at once (the wait is the device finishing the
slices); the lane engine, with other lanes live, defers the fetch to the
lane's first harvest, one or two decode waves on (the span's ``deferred``
and ``waves``).  Also leaves the whole chain's medians in
``run["notes"]["ttft_chain_ms"]`` (``chain.py``).  program_span."""
import chain
from metrics import percentile
from spans import named


def read(run):
    chain.note(run)
    return percentile([s["duration_s"] * 1e3
                       for s in named(run["traces"], "first_token")], 50)
