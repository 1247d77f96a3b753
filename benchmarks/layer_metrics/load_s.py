"""load path: seconds the program spent reading the file and placing the
weights (``/health`` ``engine.load_phases``: tokenizer + params).
program_span."""


def read(run):
    phases = (run["health"].get("engine") or {}).get("load_phases") or {}
    if "params_s" not in phases:
        return None
    return float(phases["params_s"]) + float(phases.get("tokenizer_s", 0.0))
