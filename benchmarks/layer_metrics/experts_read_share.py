"""kernels: of a routed layer's experts, the share whose weights one decode
step read, averaged over the window: ``experts_read_total`` over
``expert_layer_steps_total`` over the router's experts, the program's
counters in the first and last of the 5 Hz ``/metrics`` samples.  With all
experts held it is what a step's expert bytes are counted from
(``blocks/lfm2_moe.py expert_bytes_per_step``).  None on a program without
the counters.  program_counter."""
from counters import ratio


def read(run):
    return ratio(run, "experts_read_total", "expert_layer_steps_total",
                 100.0 / run["config"]["num_experts"])
