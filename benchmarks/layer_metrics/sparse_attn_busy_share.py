"""kernels: self time of the block-sparse layers' own attention work over
device busy time, in the mid-window capture: the XLA fusions over the
compressed keys, the last-keys leaf and the gathered blocks (scores,
selection, the read of the selected blocks, the compressed-key write),
found by ``kernels/sparse_attn.json``'s patterns.  Read through ``opshare``
and not through the groups: ``attn.json`` comes before it in name order and
takes the same fusions.  0.0 where the capture holds no such fusion; None
only without a capture.  device_trace."""
from opshare import busy_share


def read(run):
    pats = run["kernel_groups"].get("sparse_attn") or []
    if not pats:
        return None
    return busy_share(run, "sparse_attn_busy_share",
                      "|".join(f"(?:{p})" for p in pats))
