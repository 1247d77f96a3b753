"""kernels: the least time a decode step could take on this chip over the
device time it took.  Least time: the bytes a step must read (one pass
over the weights as the file stores them, the live context's keys and
values in every lane, as the configuration's block counts them through
``costs.py``, which hands it this ``run``) over the chip's HBM bandwidth, or
its FLOPs over the bf16 peak where that is larger: at these sizes HBM
bounds it.  Device time per step: the median duration of the decode
programs on the trace's ``XLA Modules`` line (``kernels/decode_program
.json``) over the tokens a decode chunk holds (median over the program's
``decode_chunk`` spans).  device_trace."""
import re

import costs
from metrics import percentile
from spans import decode_chunks, named


def read(run):
    p = run.get("profile")
    if not p:
        return None
    pats = [re.compile(x) for x in run["kernel_groups"].get("decode_program", [])]
    durs = [d for n, _, d in p["modules"] if any(x.search(n) for x in pats)]
    steps = percentile([t for _, _, t in decode_chunks(run["traces"])], 50)
    if not durs or not steps:
        return None
    step_s = percentile(durs, 50) / steps
    cfg = run["config"]
    lanes = int(cfg["serve"]["env"].get("LFKT_BATCH_SIZE", 1))
    ctx = [(s["attrs"].get("n_prompt") or 0) for s in named(run["traces"], "prefill")]
    outs = [r.completion_tokens for r in run["records"] if r.completion_tokens]
    context = (sum(ctx) / len(ctx) if ctx else 0) \
        + (sum(outs) / len(outs) / 2 if outs else 0)
    peak = costs.peaks(run["device"]["kind"])
    least, bound = costs.roofline_seconds(
        costs.decode_step_flops(cfg, lanes, context, run=run),
        costs.decode_step_bytes(cfg, lanes, context, run=run), peak)
    run["notes"]["decode_step_roofline"] = {
        "bound": bound, "least_ms": least * 1e3, "device_step_ms": step_s * 1e3,
        "lanes": lanes, "context_tokens": context}
    return 100.0 * least / step_s
