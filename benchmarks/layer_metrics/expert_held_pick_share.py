"""experts: of the picks the decode chunks' routers made for live rows over
ALL the router's experts, the share that fell on an expert held here
(``expert_picks_held_total`` over ``expert_picks_routed_total``, the
program's counters in the first and last of the 5 Hz ``/metrics``
samples); the rest left the chip in the deployment the file stands for.
Even routing reads held / router experts (32 / 256 = 12.5 %).  None on a
program without the counters.  program_counter."""
from counters import ratio


def read(run):
    return ratio(run, "expert_picks_held_total",
                 "expert_picks_routed_total", 100.0)
