"""device: per cent of the window in which some stamped program's interval
was open: growth of the sum over programs of ``jit_device_seconds_total``
(``stamps.py``) over the seconds between the first and the last 5 Hz
``/metrics`` sample.  The whole window's account beside the 3 s capture's
``100 - device_idle_share``: a capture that misfiles a program shows as a
distance between the two.  Intervals never overlap, so it passes 100 only
by a program that was open across the first sample.  Leaves
``run["notes"]["device_s_by_program"]`` (seconds grown, most first).  None
without the counter.  program_counter."""
from stamps import growth, sampled_seconds


def read(run):
    grown = growth(run)
    seconds = sampled_seconds(run) if grown is not None else 0.0
    if seconds <= 0:
        return None
    run["notes"]["device_s_by_program"] = dict(
        sorted(((k, v) for k, v in grown.items() if v),
               key=lambda kv: -kv[1]))
    return 100.0 * sum(grown.values()) / seconds
