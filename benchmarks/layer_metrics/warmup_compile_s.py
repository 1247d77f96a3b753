"""load path: seconds of the warm-up that went into compiling (or reading
from the persistent cache) the engine's entry programs: the jit
registry's compile seconds over the ``warmup`` phase (``/health``
``engine.startup``, ``warmup.attrs.compile_s``).  Beside ``warmup_s`` it
says whether a warm start still compiles.  None where the program serves
no timeline.  program_counter."""

import startup_doc


def read(run):
    warm = startup_doc.phase(run, "warmup") or {}
    value = (warm.get("attrs") or {}).get("compile_s")
    return float(value) if value is not None else None
