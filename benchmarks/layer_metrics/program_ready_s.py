"""load path: seconds from the start of the server process to its READY
flip as the program itself stamps them (``/health`` ``engine.startup.
ready_s``: the kernel's start time of the process to
``health.transition(READY)``, on ``time.time()``).  The operator's number;
the diagnostics line's ``ready_s`` is the benchmark's own clock from its
``T_START`` to the READY it polled.  None where the program serves no
timeline.  program_span."""

import startup_doc


def read(run):
    value = (startup_doc.of(run) or {}).get("ready_s")
    return float(value) if value is not None else None
