"""scheduler: share of the waves' wall time the scheduler thread spent
blocked fetching a decode chunk from the device (growth of ``scheduler_
fetch_wait_seconds`` over growth of ``scheduler_wave_seconds``).  100 less
this is the host's own work per wave: dispatch, admission, harvest.
program_counter."""
from counters import ratio


def read(run):
    return ratio(run, "scheduler_fetch_wait_seconds", "scheduler_wave_seconds",
                 100.0)
