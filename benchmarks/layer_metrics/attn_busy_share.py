"""kernels: self time of the attention kernels (``kernels/attn.json``) over
device busy time, in the mid-window capture.  device_trace."""
from xplane import group_busy_share


def read(run):
    return group_busy_share(run.get("profile"), "attn")
