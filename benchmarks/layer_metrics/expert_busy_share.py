"""kernels: self time of the grouped expert matmuls (``kernels/
expert_matmul.json``: the kernels' own names) over device busy time, in the
mid-window capture.  0.0 where the capture holds no such kernel (the
parent of the PR that added them; ``notes.no_match`` says so); None only
without a capture.  device_trace."""
from opshare import group_share


def read(run):
    return group_share(run, "expert_busy_share", "expert_matmul")
