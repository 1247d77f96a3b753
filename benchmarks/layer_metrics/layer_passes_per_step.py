"""model step: layer applications a decoded token took: growth of the decode
part of ``layer_passes_total`` over growth of ``decode_lane_steps_total`` (the
program's counters in the first and last of the 5 Hz ``/metrics`` samples;
both count the lane-steps whose rows were wanted).  ``num_hidden_layers x
total_ut_steps`` where layers run several times (192 at 48 x 4): a change
that skips a pass, or runs one twice, shows at once.  None on a program
without the counters (the parent of the PR that added them), or where no
step ran in the window.  program_counter."""
from counters import ratio


def read(run):
    return ratio(run, 'layer_passes_total{phase="decode"}',
                 "decode_lane_steps_total")
