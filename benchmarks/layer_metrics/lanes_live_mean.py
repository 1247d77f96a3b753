"""scheduler: mean of the ``scheduler_lanes_live`` gauge over the 5 Hz
samples of ``/metrics`` taken in the window.  Absent on the serial engine,
which has no lanes.  program_counter."""
from server import parse_gauge


def read(run):
    vals = [parse_gauge(text, "scheduler_lanes_live")
            for _, text in run["samples"]]
    vals = [v for v in vals if v is not None]
    return sum(vals) / len(vals) if vals else None
