"""scheduler: prefill slices dispatched per scheduler wave over the window
(growth of ``scheduler_admit_slices`` over growth of ``scheduler_waves``):
how much admission work rides between two decode chunks.
program_counter."""
from counters import ratio


def read(run):
    return ratio(run, "scheduler_admit_slices", "scheduler_waves")
