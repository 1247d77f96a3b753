"""kernels: the least time a decode step's attention could take on a ring
whose leaves are a layer AND a pass, over the device time its kernel took.
Least: every live lane's live keys and values in every leaf
(``blocks/ouro.py ring_bytes_per_step``: 192 x 8192 B a position) over the
chip's HBM bandwidth: a step's attention is one row of queries a head, so
the bytes bound it.  Live lanes: the mean of the scheduler's gauge over the
window's samples that saw a live lane; context: the mean over the window's
requests of prompt + half the answer.  Taken: the self time, in the
capture, of the decode kernel (``flash_attention_decode``: 192 calls a
step), as a share of the decode programs' time, times the median decode
step (``ring_decode_roofline``'s arithmetic).  0.0 where the capture holds
no such kernel; None without a capture or on a block that brings no
``ring_bytes_per_step``.  device_trace."""
import re

import costs
from ggufgen import block_of
from metrics import percentile
from opshare import _no_match
from spans import decode_chunks


def read(run):
    p = run.get("profile")
    cfg = run["config"]
    block = block_of(cfg)
    if not p or not hasattr(block, "ring_bytes_per_step"):
        return None
    progs = [re.compile(x)
             for x in run["kernel_groups"].get("decode_program", [])]
    durs = [d for n, _, d in p["modules"] if any(x.search(n) for x in progs)]
    steps = percentile([t for _, _, t in decode_chunks(run["traces"])], 50)
    if not durs or not steps:
        return None
    mine = re.compile(r"^%flash_attention_decode")
    secs = sum(s for name, s in p["ops"].items() if mine.search(name))
    if not secs:
        _no_match(run, "loop_ring_roofline")
        return 0.0
    taken = percentile(durs, 50) / steps * secs / sum(durs)
    lanes = int(cfg["serve"]["env"].get("LFKT_BATCH_SIZE", 1))
    ctx = [r.prompt_tokens + r.completion_tokens / 2 for r in run["records"]
           if r.prompt_tokens and r.completion_tokens]
    context = sum(ctx) / len(ctx) if ctx else 0
    nbytes = block.ring_bytes_per_step(cfg, lanes, context, run=run)
    least = nbytes / costs.peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    run["notes"]["loop_ring_roofline"] = {
        "least_ms": least * 1e3, "device_ms_per_step": taken * 1e3,
        "bytes_per_step": nbytes, "lanes_live": block.live_lanes(lanes, run),
        "context": context}
    return 100.0 * least / taken
