"""kernels: self time of the fused Q4_K matmuls over device busy time, in
the mid-window capture.  Found by the kernels' own name
(``ops/pallas/qmatmul.py`` ``kernel_name``), which Pallas makes the HLO
instruction's name.  With ``q6k_busy_share`` it splits
``qmatmul_busy_share``.  0.0 where the capture holds no such kernel; None
only without a capture.  device_trace."""
from opshare import busy_share


def read(run):
    return busy_share(run, "q4k_busy_share", r"^%q4k_matmul")
