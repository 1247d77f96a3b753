"""kv ring: of the states the decode steps' arithmetic stepped, the share
that a lane which holds a request needed: ``ssm_state_updates_total`` (lane
with an unfinished request x state-space layer x step) over
``ssm_state_steps_total`` (every lane of the batch x layer x step: every
lane runs a step's arithmetic, a lane that holds no request keeps its state).
The mean share of live lanes over the window's steps.  The program's
counters in the first and last of the 5 Hz ``/metrics`` samples.  None on a
program without the counters, or where no step ran.  program_counter."""
from counters import ratio


def read(run):
    return ratio(run, "ssm_state_updates_total", "ssm_state_steps_total",
                 100.0)
