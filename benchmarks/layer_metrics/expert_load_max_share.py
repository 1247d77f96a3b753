"""experts: the most-loaded expert's share of the (token, pick) rows the
decode chunks routed in the window, in per cent: the largest difference of
``expert_picks_total{expert="e"}`` between the first and last ``/metrics``
samples over their sum.  Even routing reads 100 / num_experts.  None on a
program without the counter.  program_counter."""
from counters import delta


def read(run):
    n = (run.get("config") or {}).get("num_experts")
    if not n:
        return None
    rows = [delta(run, 'expert_picks_total{expert="%d"}' % e)
            for e in range(n)]
    if None in rows or not sum(rows):
        return None
    return 100.0 * max(rows) / sum(rows)
