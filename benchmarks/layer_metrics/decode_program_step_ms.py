"""model step: device milliseconds of the lane engine's decode-chunk
program per step it ran, over the whole window: growth of
``jit_device_seconds_total{program="lane_decode_chunk"}`` (``stamps.py``)
over growth of ``scheduler_steps_run``.  Beside ``decode_step_ms`` (the
host's span per token, dispatch and fetch included) it says whether a step
is a slow program or a waiting one; admission slices queued between two
chunks are their own programs' seconds, not this one's.  None without
either counter or where no step ran (the serial engine has neither).
program_counter."""
from counters import delta
from stamps import LANE_CHUNK, seconds_of


def read(run):
    secs = seconds_of(run, LANE_CHUNK)
    steps = delta(run, "scheduler_steps_run")
    if secs is None or not steps:
        return None
    return secs * 1e3 / steps
