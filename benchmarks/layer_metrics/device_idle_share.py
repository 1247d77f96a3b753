"""device: 1 - (union of the device operations' intervals) / (the traced
window), in the mid-window capture.  device_trace."""


def read(run):
    p = run.get("profile")
    if not p or not p["window_s"]:
        return None
    return 100.0 * (1.0 - p["busy_s"] / p["window_s"])
