"""kv ring: of the cache entries the decode steps' attention covered, the
share that was live: window slots at or below the sequence's own fill plus
the summaries of the windows before its own, over whole blocks of window
slots up to the largest live lane's fill plus the summaries up to the lane
with most closed windows (``eva_window_slots_live_total +
eva_summaries_live_total`` over the two ``..._read_total``, the program's
counters in the first and last of the 5 Hz ``/metrics`` samples).  What a
finer block, or a read bounded per lane, could still save.  None on a
program without the counters.  program_counter."""
from counters import delta


def read(run):
    parts = [delta(run, f"eva_{what}_{side}_total")
             for side in ("live", "read")
             for what in ("window_slots", "summaries")]
    if any(x is None for x in parts) or not parts[2] + parts[3]:
        return None
    return 100.0 * (parts[0] + parts[1]) / (parts[2] + parts[3])
