"""kv ring: reads of the ONE shared K/V leaf a decode step of a lane:
``shared_leaf_reads_total`` (one a reading layer, lane and step) over
``shared_leaf_steps_total`` (lane x step).  8 where the full-attention layer
and each of the seven cross layers reads the leaf once; a program that read
it once for all of them would say 1.  The program's counters in the first
and last of the 5 Hz ``/metrics`` samples.  None on a program without the
counters, or where no step ran.  program_counter."""
from counters import ratio


def read(run):
    return ratio(run, "shared_leaf_reads_total", "shared_leaf_steps_total")
