"""scheduler: median of the ``pending`` span: a request handed to the lane
engine waits here until the scheduler's loop takes it up, which it does
only with a lane free and no other admission in flight.  Absent on the
serial engine.  program_span."""
from metrics import percentile
from spans import named


def read(run):
    return percentile([s["duration_s"] * 1e3
                       for s in named(run["traces"], "pending")], 50)
