"""kv ring: of the cache slots the decode steps' attention covered in both
layer kinds, the share that was live: positions inside the window in a
window layer's leaf, at or below the lane's own position in a global ring
(``window_slots_live_total`` + ``global_slots_live_total`` over the two
``*_read_total``, the program's counters in the first and last of the 5 Hz
``/metrics`` samples).  What finer blocks could still save.  None on a
program without the counters, or where no step ran in the window.
program_counter."""
from counters import delta


def read(run):
    parts = [delta(run, f"{kind}_slots_{what}_total")
             for what in ("live", "read") for kind in ("window", "global")]
    if any(p is None for p in parts) or not parts[2] + parts[3]:
        return None
    return 100.0 * (parts[0] + parts[1]) / (parts[2] + parts[3])
