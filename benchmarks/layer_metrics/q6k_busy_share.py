"""kernels: self time of the fused Q6_K matmuls (both weight layouts) over
device busy time, in the mid-window capture; found by the kernels' own
name, as ``q4k_busy_share``.  In a Q4_K_M file Q6_K holds ``attn_v``,
``ffn_down`` and the head.  0.0 where the capture holds no such kernel (a
file without Q6_K tensors); None only without a capture.  device_trace."""
from opshare import busy_share


def read(run):
    return busy_share(run, "q6k_busy_share", r"^%q6k_(pre_)?matmul")
