"""kv ring: of the ring slots the decode steps' attention covered, the share
that was live (at or below the lane's own position):
``ring_slots_live_total`` over ``ring_slots_read_total``, the program's
counters in the first and last of the 5 Hz ``/metrics`` samples.  The
decode kernel reads whole blocks of 128 slots, so a lane at position p reads
``ceil((p + 1) / 128) x 128``: what is read beyond the live context, in
every one of the ring's leaves a step (192 where 48 layers run 4 passes).
None on a program without the counters, or where no step ran in the
window.  program_counter."""
from counters import ratio


def read(run):
    return ratio(run, "ring_slots_live_total", "ring_slots_read_total", 100.0)
