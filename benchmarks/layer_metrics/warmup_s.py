"""load path: seconds of the program's own warm-up, compiles or cache reads
included (``/health`` ``engine.load_phases.warmup_s``).  program_span."""


def read(run):
    phases = (run["health"].get("engine") or {}).get("load_phases") or {}
    return float(phases["warmup_s"]) if "warmup_s" in phases else None
