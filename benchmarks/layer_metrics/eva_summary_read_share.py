"""kv ring: of the cache entries the decode steps' attention covered, the
share that were chunk summaries and not window slots
(``eva_summaries_read_total`` over it plus ``eva_window_slots_read_total``,
the program's counters in the first and last of the 5 Hz ``/metrics``
samples): how much of the read the linear part of the attention is.  Grows
with the contexts' length, 128 summaries a closed window against at most
2048 slots.  None on a program without the counters.  program_counter."""
from counters import delta


def read(run):
    s = delta(run, "eva_summaries_read_total")
    w = delta(run, "eva_window_slots_read_total")
    if s is None or w is None or not s + w:
        return None
    return 100.0 * s / (s + w)
