"""scheduler: lane-seconds occupied over lane-seconds offered, inside
scheduler waves, over the window: the growth of ``scheduler_lane_live_
seconds`` over lanes x the growth of ``scheduler_wave_seconds``.  The
program integrates both once per wave; ``lanes_live_mean`` samples a gauge
at the wave's own frequency.  program_counter."""
from counters import ratio
from server import parse_gauge


def read(run):
    samples = run.get("samples") or []
    lanes = parse_gauge(samples[0][1], "scheduler_batch_size") if samples \
        else None
    if not lanes:
        return None
    return ratio(run, "scheduler_lane_live_seconds", "scheduler_wave_seconds",
                 100.0 / lanes)
