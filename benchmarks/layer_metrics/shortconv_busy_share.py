"""kernels: self time of the gated short convolutions' own operations over
device busy time, in the mid-window capture: the fusions over the conv
layers' carried rows (the gate, the taps, the rows carried on) and the
``in_proj`` matmul, found by ``kernels/shortconv.json``'s patterns
(``out_proj`` has the attention projections' shape and cannot be told from
them).  Read through ``opshare`` and not through the groups: ``qmatmul.json``
comes before it in name order and takes ``in_proj``.  0.0 where the capture
holds no such operation; None only without a capture, or on a checkout
without the group.  device_trace."""
from opshare import busy_share


def read(run):
    pats = run["kernel_groups"].get("shortconv") or []
    if not pats:
        return None
    return busy_share(run, "shortconv_busy_share",
                      "|".join(f"(?:{p})" for p in pats))
