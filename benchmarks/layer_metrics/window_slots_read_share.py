"""kv ring: of the cache slots the decode steps' attention covered, summed
over the layers of both kinds, the share that lay in WINDOW layers' leaves
(``window_slots_read_total`` over it plus ``global_slots_read_total``, the
program's counters in the first and last of the 5 Hz ``/metrics``
samples).  A window leaf is read whole (128 slots) whatever the context, a
global ring in whole blocks up to the position: at 9 window and 3 global
layers 3 % at a context of 11k, a quarter at chat lengths.  With a ring of
``n_ctx`` slots in every layer it would read 75 %.  None on a program
without the counters, or where no step ran in the window.
program_counter."""
from counters import delta


def read(run):
    w = delta(run, "window_slots_read_total")
    g = delta(run, "global_slots_read_total")
    if w is None or g is None or not w + g:
        return None
    return 100.0 * w / (w + g)
