"""experts: of the picks of a REAL expert the decode chunks' routers made
for live rows (``expert_picks_routed_total`` less ``expert_picks_zero_total``),
the share that fell on an expert held here (``expert_picks_held_total``),
the program's counters in the first and last of the 5 Hz ``/metrics``
samples: the share of a token's matrix work in the expert branch that this
chip does; the rest left the chip in the deployment the file stands for.
Even routing reads held / routed experts (64 / 512 = 12.5 %).  None on a
program without the counters, or where no real expert was picked.
program_counter."""
from counters import delta


def read(run):
    held, routed, zero = (delta(run, f"expert_picks_{k}_total")
                          for k in ("held", "routed", "zero"))
    if held is None or routed is None or zero is None or routed <= zero:
        return None
    return 100.0 * held / (routed - zero)
