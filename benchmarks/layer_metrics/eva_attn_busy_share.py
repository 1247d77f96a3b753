"""kernels: self time of the evabyte block's attention over device busy
time, in the mid-window capture: the XLA fusions over the window and
summary leaves (a decode step's block read, a prefill slice's scores, the
window close), found by ``kernels/eva_attn.json``'s patterns.  Read through
``opshare`` and not through the groups: ``attn.json`` comes before it in
name order and takes the same fusions.  0.0 where the capture holds no such
fusion (a file of another block); None only without a capture.
device_trace."""
from opshare import busy_share


def read(run):
    pats = run["kernel_groups"].get("eva_attn") or []
    if not pats:
        return None
    return busy_share(run, "eva_attn_busy_share",
                      "|".join(f"(?:{p})" for p in pats))
