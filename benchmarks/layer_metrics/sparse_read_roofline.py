"""kernels: the least time a decode step's read in the block-sparse layers
could take over the device time it took.  Least: per live lane, sparse
layer and KV head the selected blocks' keys and values and the visible
compressed keys, as the program's counters give them over the window
(``sparse_blocks_read_total``, ``sparse_blocks_visible_total`` over the
lane-steps of ``lin_state_updates_total``: ``blocks/sala.py
sparse_read_bytes_per_step``), over the chip's HBM bandwidth.  Taken: the
self time, in the capture, of the decode step's fusions over the LANES'
stacked leaves and gathered blocks (``kernels/sparse_attn.json``'s patterns
on a bf16 shape of the lanes' rank, or a lane's gathered blocks) as a share of the decode
programs' time, times the median decode step.  0.0 where the capture holds
no such fusion; None without a capture, or on a block that brings no
``sparse_read_bytes_per_step``.  device_trace."""
from sala_roofline import read as _read


def read(run):
    return _read(run, "sparse_read_roofline", "sparse_attn",
                 "sparse_read_bytes_per_step",
                 r"bf16\[(\d+,){3,4}(1024|1025|32|64),128\]"
                 r"|bf16\[\d+,\d+,64,128\]")
