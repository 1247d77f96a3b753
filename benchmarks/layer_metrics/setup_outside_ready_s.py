"""load path: what the harness itself adds to ``setup_s`` after the server
is READY: its warm-up requests, the measured law of words to tokens and the
opening probes.  ``run`` carries no ``T_START``, so this is ``run["setup_s"]
- run["ready_s"]``, both on the benchmark's own clock (``ready_s`` is the
READY it polled, up to one 0.25 s poll after the program's flip), and not
``setup_s`` less the program's ``engine.startup.ready_unix``.  None where
the program serves no timeline, so that the six metrics of the timeline
appear together.  host_clock."""

import startup_doc


def read(run):
    if startup_doc.of(run) is None:
        return None
    if run.get("setup_s") is None or run.get("ready_s") is None:
        return None
    return float(run["setup_s"]) - float(run["ready_s"])
