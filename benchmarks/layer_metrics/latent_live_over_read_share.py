"""kv ring: of the cached latent rows the decode steps' attention covered,
the share that was live: rows at or below each live lane's own position
over whole blocks of 512 up to the LARGEST live lane's position
(``latent_positions_live_total`` over ``latent_positions_read_total``, the
program's counters in the first and last of the 5 Hz ``/metrics``
samples).  What a read bounded per lane, or a finer block, could still
save.  None on a program without the counters, or where no step ran in
the window.  program_counter."""
from counters import ratio


def read(run):
    return ratio(run, "latent_positions_live_total",
                 "latent_positions_read_total", 100.0)
