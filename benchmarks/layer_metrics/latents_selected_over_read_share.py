"""kv ring: of the cached latent rows the decode steps' attention FETCHED,
the share the selection had chosen: ``latents_selected_total`` over
``latents_read_total`` at ``phase="decode"`` (the program's counters in the
first and last ``/metrics`` samples).  100 % where the read is sparse in
fact; under a mask on the blocks read, the selected share less the blocks'
overhang: the headroom a read of the selected rows alone starts from.  The
numerator is host arithmetic (what the algorithm prescribes at the tracked
positions), the denominator what the read that served is known to fetch: the
share moves when the READ changes (finer blocks, a gather, a list-walking
kernel) and with nothing else.  None on a program without the counters, or
where no step ran in the window.  program_counter."""
from counters import ratio


def read(run):
    return ratio(run, 'latents_selected_total{phase="decode"}',
                 'latents_read_total{phase="decode"}', 100.0)
