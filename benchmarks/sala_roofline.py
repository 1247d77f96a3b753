"""What ``lin_state_roofline`` and ``sparse_read_roofline`` share: the
least time the bytes a block's function counts could take, over the device
time of the decode step's operations that a kernel group's patterns find
on the LANES' stacked leaves (rank 5: a prefill slice works on the scratch
cache, rank 4), as ``eva_read_roofline`` reads its own.  Taken: those
operations' self time as a share of the decode programs' time in the
capture (the ``XLA Modules`` line), times the median decode step
(``decode_step_roofline``'s clock).  Beside ``opshare.py``: a helper of
readers, no metric of its own."""
import re

import costs
from ggufgen import block_of
from metrics import percentile
from opshare import _no_match
from spans import decode_chunks


def read(run, metric, group, bytes_fn_name, lanes_leaf):
    p = run.get("profile")
    cfg = run["config"]
    bytes_fn = getattr(block_of(cfg), bytes_fn_name, None)
    pats = run["kernel_groups"].get(group) or []
    if not p or bytes_fn is None or not pats:
        return None
    progs = [re.compile(x)
             for x in run["kernel_groups"].get("decode_program", [])]
    durs = [d for n, _, d in p["modules"] if any(x.search(n) for x in progs)]
    steps = percentile([t for _, _, t in decode_chunks(run["traces"])], 50)
    if not durs or not steps:
        return None
    mine = [re.compile(x) for x in pats]
    leaf = re.compile(lanes_leaf)
    secs = sum(s for name, s in p["ops"].items()
               if any(x.search(name) for x in mine) and leaf.search(name))
    if not secs:
        _no_match(run, metric)
        return 0.0
    step_s = percentile(durs, 50) / steps
    taken = step_s * secs / sum(durs)
    lanes = int(cfg["serve"]["env"].get("LFKT_BATCH_SIZE", 1))
    ctx = [r.prompt_tokens + r.completion_tokens / 2 for r in run["records"]
           if r.prompt_tokens and r.completion_tokens]
    context = sum(ctx) / len(ctx) if ctx else 0
    nbytes = bytes_fn(cfg, lanes, context, run=run)
    least = nbytes / costs.peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    run["notes"][metric] = {
        "bound": "hbm", "least_ms": least * 1e3,
        "device_ms_per_step": taken * 1e3, "bytes_per_step": nbytes}
    return 100.0 * least / taken
