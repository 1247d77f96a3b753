"""kv ring: of the cached positions the decode steps' queries could attend
(every one at or below the query: what the indexer scored), the share the
selection chose: ``latents_selected_total`` over ``index_keys_scored_total``
at ``phase="decode"``, the program's counters in the first and last of the
5 Hz ``/metrics`` samples.  24 % at contexts of 8.6k under ``index_topk``
2048, 100 % below 2048 positions (the mechanism then does nothing).  Both
counters are the program's host arithmetic on tracked positions (what the
algorithm prescribes, not a count taken on the device), so this share is a
property of the cell's TRAFFIC under ``index_topk``: it says how far the
mechanism is engaged, and no change to the program moves it.  None on a
program without the counters, or where no step ran in the window.
program_counter."""
from counters import ratio


def read(run):
    return ratio(run, 'latents_selected_total{phase="decode"}',
                 'index_keys_scored_total{phase="decode"}', 100.0)
