"""kv ring: of the block-sparse layers' queries in the window (every
position of a prompt's prefill, every decode step of a live lane), the
share that took the sparse branch, i.e. stood at ``dense_len`` or beyond
(``sparse_queries_total{branch="sparse"}`` over both branches, the
program's counters in the first and last of the 5 Hz ``/metrics``
samples).  None on a program without the counters.  program_counter."""
from counters import delta


def read(run):
    sp = delta(run, 'sparse_queries_total{branch="sparse"}')
    de = delta(run, 'sparse_queries_total{branch="dense"}')
    if sp is None or de is None or not sp + de:
        return None
    return 100.0 * sp / (sp + de)
