"""kv ring: of the ring blocks a causal read would have covered in the
decode steps past ``dense_len``, the share the selection read
(``sparse_blocks_read_total`` over ``sparse_blocks_visible_total``, the
program's counters in the first and last of the 5 Hz ``/metrics`` samples,
summed over live lanes, sparse layers and KV heads): 98 of 192 blocks at
position 12288.  None on a program without the counters, or where no step
took the sparse branch in the window.  program_counter."""
from counters import ratio


def read(run):
    return ratio(run, "sparse_blocks_read_total",
                 "sparse_blocks_visible_total", 100.0)
