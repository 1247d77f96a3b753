"""kernels: self time of a ``deepseek32`` file's sparse attention over
device busy time, in the mid-window capture: the indexer (every XLA
operation on the index-key leaf or a block of it), the selection (the
threshold search, ties and mask over rows of ``n_ctx`` scores) and the
selected read (the decode and slice kernels that take the selection), found
by ``dsa_roofline.py patterns``, which builds them from the cell's own
configuration.  Read through ``opshare`` and not through a ``kernels/*.json``
group: a group is handed to every cell, and these shapes are other things
in a cell without an indexer.  0.0 where the capture holds no such
operation; None without a capture or for a configuration with no indexer.
device_trace."""
from dsa_roofline import patterns
from opshare import busy_share


def read(run):
    pats = patterns(run["config"])
    if not pats:
        return None
    return busy_share(run, "dsa_busy_share",
                      "|".join(f"(?:{p})" for p in pats))
