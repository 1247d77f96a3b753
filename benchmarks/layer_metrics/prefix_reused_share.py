"""scheduler: of the prompt tokens of the requests that ended in the
window, the share that was NOT prefilled because an admission rode a
lane's claim (``prefix_cache_reused_tokens_total`` over
``tokens_prompt_total{model=<the file's name>}``, the program's counters in
the first and last of the 5 Hz ``/metrics`` samples).  A system line of
8192 tokens before prompts of about 8500, every admission a hit: about
96 %.  0.0 where prompts were counted and none reused; None on a program
without the prompt counter, or where no request ended in the window.
program_counter."""
from counters import delta


def read(run):
    name = (run.get("config") or {}).get("name")
    prompts = delta(run, 'tokens_prompt_total{model="%s"}' % name)
    if not prompts:
        return None
    return 100.0 * (delta(run, "prefix_cache_reused_tokens_total") or 0.0) \
        / prompts
