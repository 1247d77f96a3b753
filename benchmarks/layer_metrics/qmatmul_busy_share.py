"""kernels: self time of the fused K-quant matmul kernels (name patterns in
``kernels/qmatmul.json``) over device busy time, in the mid-window
capture.  device_trace."""
from xplane import group_busy_share


def read(run):
    return group_busy_share(run.get("profile"), "qmatmul")
