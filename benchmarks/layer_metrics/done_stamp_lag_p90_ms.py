"""device: the done stamp's own error.  The jit registry's watcher waits
for each dispatch's result inside ``phase("device_done")``, so the capture
holds every stamp as an ``lfkt.device_done`` host event on the clock the
device's programs are on.  Over the capture, p90 of (the event's end - the
end of the module on the ``XLA Modules`` line that ended last before it),
in milliseconds: how late the host learns that a program is done (the
watcher's wake-up, and its wait for the interpreter lock while another
thread runs Python).  The lag moves time between two neighbouring
programs' intervals and leaves their sum alone.  Leaves the count and the
median in ``run["notes"]["done_stamps"]``.  None without a capture, or on
a program that writes no such event.  device_trace."""
import bisect

import annotations
import xplane
from metrics import percentile

EVENT = annotations.PREFIX + "device_done"


def lags_ms(stamp_ends, module_ends):
    """Per stamp, the milliseconds since the last module end at or before
    it; a stamp that precedes every module is left out."""
    module_ends = sorted(module_ends)
    out = []
    for end in stamp_ends:
        i = bisect.bisect_right(module_ends, end)
        if i:
            out.append((end - module_ends[i - 1]) * 1e3)
    return out


def read(run):
    path = xplane.capture_of(run)
    profile = run.get("profile")
    if not path or not profile or not profile.get("modules"):
        return None
    stamps = [start + dur for _, name, start, dur in annotations.events(path)
              if name == EVENT]
    lags = lags_ms(stamps, [s + d for _, s, d in profile["modules"]])
    if not lags:
        return None
    run["notes"]["done_stamps"] = {"n": len(lags),
                                   "p50_ms": percentile(lags, 50)}
    return percentile(lags, 90)
