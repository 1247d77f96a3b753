"""kernels: the least time a decode step's INDEXER could take over the
device time it took (a ``deepseek32`` file: models/mla.py ``index_scores``).
Least: the larger of every live lane's index keys up to its position, all
layers (``blocks/deepseek32.py index_bytes_per_step``), over the chip's HBM
bandwidth and the indexer's FLOPs (``index_flops_per_step``: per head and
live position a product over 128, a relu, a weight and a sum) over its bf16
peak.  Taken: the self time, in the capture, of the XLA operations that read
or write the LANES' index-key leaf or a block of it (``dsa_roofline.py
leaf_pattern`` on a bf16 shape that the lanes' axis leads: a prefill slice
works on the scratch cache), as ``dsa_roofline.py`` scales it to a step.
0.0 where the capture holds no such operation; None without a capture, on a
block without the two functions, or for a configuration with no indexer.
device_trace."""
from dsa_roofline import lanes_of, leaf_pattern
from dsa_roofline import read as _read


def read(run):
    return _read(run, "index_score_roofline",
                 leaf_pattern(run["config"],
                              r"%d,(?:\d+,)*" % lanes_of(run)),
                 "index_bytes_per_step", "index_flops_per_step")
