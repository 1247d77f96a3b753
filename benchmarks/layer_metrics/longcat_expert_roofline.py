"""kernels: the least time the grouped expert matmuls of a decode step
could take over the device time they took, on the ``longcat-flash`` block's
shapes: K 6144 (gate, up) and 2048 (down), 64 experts held, 16 lanes of 12
picks of which most are identity experts or experts held elsewhere, so a
held expert sees a row or two.  Least: the bytes they have to read (the
held experts the program counted per layer and step, times one expert's
stored bytes, every layer; an identity pick reads none:
``blocks/longcat_flash.py expert_bytes_per_step``) over the chip's HBM
bandwidth.  Taken: the few-row expert kernels' self time in the capture as a
share of the decode programs' time there (the ``XLA Modules`` line), times
the median decode step (``decode_step_roofline``'s clock: median program
over a chunk's tokens).  ``routed_matmul_roofline``'s arithmetic on this
block's bytes.  0.0 where the capture holds no such kernel; None without a
capture or on a block without experts.  device_trace."""
import re

import costs
from ggufgen import block_of
from metrics import percentile
from opshare import _no_match
from spans import decode_chunks


def read(run):
    p = run.get("profile")
    cfg = run["config"]
    bytes_fn = getattr(block_of(cfg), "expert_bytes_per_step", None)
    if not p or bytes_fn is None:
        return None
    pats = [re.compile(x)
            for x in run["kernel_groups"].get("decode_program", [])]
    durs = [d for n, _, d in p["modules"] if any(x.search(n) for x in pats)]
    steps = percentile([t for _, _, t in decode_chunks(run["traces"])], 50)
    if not durs or not steps:
        return None
    secs = sum(s for name, s in p["ops"].items()
               if re.search(r"^%q\d\w*_expert_matmul_fewrow", name))
    if not secs:
        _no_match(run, "longcat_expert_roofline")
        return 0.0
    taken = percentile(durs, 50) / steps * secs / sum(durs)
    lanes = int(cfg["serve"]["env"].get("LFKT_BATCH_SIZE", 1))
    nbytes = bytes_fn(cfg, lanes, run)
    least = nbytes / costs.peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    run["notes"]["longcat_expert_roofline"] = {
        "bound": "hbm", "least_ms": least * 1e3,
        "device_ms_per_step": taken * 1e3, "expert_bytes_per_step": nbytes}
    return 100.0 * least / taken
