"""kernels: the least time a decode step's update of the linear-attention
layers' states could take over the device time it took.  Least: every live
lane's state of every linear layer read and written once (live lanes x 24 x
2 x 2 MB at the published size: ``blocks/sala.py lin_state_bytes_per_step``,
the lanes live from the scheduler's sampled gauge) over the chip's HBM
bandwidth.  Taken: the self time, in the capture, of the decode step's
kernel (``%lin_state``), fusions and copies over the LANES' stacked state leaf
(``kernels/lin_state.json``'s patterns on a shape of rank 5) as a share of
the decode programs' time, times the median decode step.  0.0 where the
capture holds no such operation; None without a capture, or on a block
that brings no ``lin_state_bytes_per_step``.  device_trace."""
from sala_roofline import read as _read


def read(run):
    return _read(run, "lin_state_roofline", "lin_state",
                 "lin_state_bytes_per_step", r"f32\[(\d+,){3}128,128\]")
