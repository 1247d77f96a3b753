"""experts: distinct experts whose weights one routed layer read in one
decode step, averaged over the window: ``experts_read_total`` over
``expert_layer_steps_total``, the program's counters in the first and last
of the 5 Hz ``/metrics`` samples.  Between one token's picks and all the
experts; it is what a step's expert bytes are counted from
(``blocks/olmoe.py``).  None on a program without the counters.
program_counter."""
from counters import ratio


def read(run):
    return ratio(run, "experts_read_total", "expert_layer_steps_total")
