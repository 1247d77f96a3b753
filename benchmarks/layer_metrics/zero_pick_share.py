"""experts: of the picks the decode chunks' routers made for live rows over
ALL the router's outputs, the share that fell on an identity ("zero-compute")
expert (``expert_picks_zero_total`` over ``expert_picks_routed_total``, the
program's counters in the first and last of the 5 Hz ``/metrics`` samples):
picks that cost a scale and an add of the token's own row and no weight
read.  Even routing reads zero outputs / all outputs (256 / 768 = 33.3 %);
a choice that one bias or one direction decides reads far off it.  None on
a program without the counter.  program_counter."""
from counters import ratio


def read(run):
    return ratio(run, "expert_picks_zero_total",
                 "expert_picks_routed_total", 100.0)
