"""load path: the share of process start to READY that lies inside a named
top-level phase of the program's timeline: 100 x (1 - ``unnamed_s`` /
``ready_s``) of ``/health`` ``engine.startup``.  None where the program
serves no timeline.  program_span."""

import startup_doc


def read(run):
    doc = startup_doc.of(run) or {}
    if doc.get("unnamed_s") is None or not doc.get("ready_s"):
        return None
    return 100.0 * (1.0 - float(doc["unnamed_s"]) / float(doc["ready_s"]))
