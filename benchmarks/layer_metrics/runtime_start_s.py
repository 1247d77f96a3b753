"""load path: seconds the server process pays before a byte of the model
is read (``/health`` ``engine.startup.phases``): ``before_main`` (the
interpreter and what the launcher imported; under this benchmark
``server_child.py``'s ``import jax`` and its first look at the devices, so
the TPU attach) + ``imports`` (the package, the app module, JAX) +
``backend_init`` + ``compile_cache`` + ``engine_import`` (the load thread's
import of the engine package, which the timeline names apart).  None where
the program serves no timeline.  program_span."""

import startup_doc

PHASES = ("before_main", "imports", "backend_init", "compile_cache",
          "engine_import")


def read(run):
    return startup_doc.seconds(run, PHASES)
