"""load path: seconds of the weight kernels' compile probes, paid by every
start (``/health`` ``engine.startup.phases``, the phase ``probes``): the
fused matmuls of the file's quantised types, and the grouped expert kernels
where the file has experts.  The same stretch as the legacy
``load_phases.probes_s``, at 1 ms.  (The attention side's probes are a
phase of their own, ``attn_probes``, and no part of this number.)  None
where the program serves no timeline or probed nothing.  program_span."""

import startup_doc


def read(run):
    return startup_doc.seconds(run, ("probes",))
