"""kernels: the least time a decode step's read of the window + summary
cache could take over the device time it took.  Least: the bytes it has to
read (the entries the program counted as live per lane and step, times the
lanes live, times one entry's bytes over all layers: ``blocks/evabyte.py
cache_read_bytes_per_step``) over the chip's HBM bandwidth.  Taken: the
self time, in the capture, of the decode step's fusions over the LANES'
stacked leaves (``kernels/eva_attn.json``'s patterns on an operand of rank
5: the prefill slice reads the scratch cache, rank 4) as a share of the
decode programs' time there (the ``XLA Modules`` line), times the median
decode step (``decode_step_roofline``'s clock).  The window close is among
those fusions: it reads a window too, once in 2048 steps.  0.0 where the
capture holds no such fusion; None without a capture, or on a block that
brings no ``cache_read_bytes_per_step``.  device_trace."""
import re

import costs
from ggufgen import block_of
from metrics import percentile
from opshare import _no_match
from spans import decode_chunks


def read(run):
    p = run.get("profile")
    cfg = run["config"]
    bytes_fn = getattr(block_of(cfg), "cache_read_bytes_per_step", None)
    pats = run["kernel_groups"].get("eva_attn") or []
    if not p or bytes_fn is None or not pats:
        return None
    progs = [re.compile(x)
             for x in run["kernel_groups"].get("decode_program", [])]
    durs = [d for n, _, d in p["modules"] if any(x.search(n) for x in progs)]
    steps = percentile([t for _, _, t in decode_chunks(run["traces"])], 50)
    if not durs or not steps:
        return None
    mine = [re.compile(x) for x in pats]
    lanes_leaf = re.compile(r"bf16\[(\d+,){4}\d+\]")
    secs = sum(s for name, s in p["ops"].items()
               if any(x.search(name) for x in mine)
               and lanes_leaf.search(name))
    if not secs:
        _no_match(run, "eva_read_roofline")
        return 0.0
    step_s = percentile(durs, 50) / steps
    taken = step_s * secs / sum(durs)
    lanes = int(cfg["serve"]["env"].get("LFKT_BATCH_SIZE", 1))
    ctx = [r.prompt_tokens + r.completion_tokens / 2 for r in run["records"]
           if r.prompt_tokens and r.completion_tokens]
    nbytes = bytes_fn(cfg, lanes, sum(ctx) / len(ctx) if ctx else 0, run=run)
    least = nbytes / costs.peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    run["notes"]["eva_read_roofline"] = {
        "bound": "hbm", "least_ms": least * 1e3,
        "device_ms_per_step": taken * 1e3, "cache_bytes_per_step": nbytes}
    return 100.0 * least / taken
