"""scheduler: share of the fetched decode chunks' steps that the chunk
program did not run because none of its lanes had anything left to decode
(growth of ``scheduler_steps_skipped`` over growth of ``scheduler_steps_run``
+ ``scheduler_steps_skipped``): the tail of a request's last chunk and the
whole chunk queued behind it, where no other lane is alive.  About 0 where
some lane always is.  program_counter."""
from counters import delta


def read(run):
    skipped = delta(run, "scheduler_steps_skipped")
    ran = delta(run, "scheduler_steps_run")
    if skipped is None or ran is None or not skipped + ran:
        return None
    return 100.0 * skipped / (skipped + ran)
