"""kernels: self time of the fused K-quant matmul kernels (name patterns in
``kernels/qmatmul.json``) over device busy time, in the mid-window
capture.  device_trace."""
from opshare import group_share


def read(run):
    return group_share(run, "qmatmul_busy_share", "qmatmul")
