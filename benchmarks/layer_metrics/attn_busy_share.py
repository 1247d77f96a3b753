"""kernels: self time of the attention kernels (``kernels/attn.json``) over
device busy time, in the mid-window capture.  device_trace."""
from opshare import group_share


def read(run):
    return group_share(run, "attn_busy_share", "attn")
