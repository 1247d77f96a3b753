"""kernels: the least time a decode step's latent attention could take over
the device time it took.  Least: the larger of every live lane's latents
and rotated keys up to its position, all layers, read ONCE a lane
(``blocks/deepseek2.py latent_bytes_per_step``) over the chip's HBM
bandwidth, and the absorbed form's FLOPs (``latent_flops_per_step``: per
head and latent a score over 576 and a weighted sum over 512) over its bf16
peak: 121 FLOP a byte against the chip's 240, so the bytes bound it.  Live
lanes: the mean of the scheduler's gauge over the window's samples.  Taken:
the self time, in the capture, of the operations that
``kernels/mla_attn.json``'s pattern finds and that carry the LANES' axis
(a decode step's: rank 5 on the stacked leaf, or 16 lanes leading a block;
a prefill slice works on the scratch cache), as a share of the decode
programs' time, times the median decode step (``decode_step_roofline``'s
clock).  0.0 where the capture holds no such operation; None without a
capture, on a block that brings no ``latent_bytes_per_step``, or on a
checkout without the group.  device_trace."""
import re

import costs
from ggufgen import block_of
from metrics import percentile
from opshare import _no_match
from server import parse_gauge
from spans import decode_chunks


def read(run):
    p = run.get("profile")
    cfg = run["config"]
    block = block_of(cfg)
    pats = run["kernel_groups"].get("mla_attn") or []
    if not p or not pats or not hasattr(block, "latent_bytes_per_step"):
        return None
    progs = [re.compile(x)
             for x in run["kernel_groups"].get("decode_program", [])]
    durs = [d for n, _, d in p["modules"] if any(x.search(n) for x in progs)]
    steps = percentile([t for _, _, t in decode_chunks(run["traces"])], 50)
    if not durs or not steps:
        return None
    lanes = int(cfg["serve"]["env"].get("LFKT_BATCH_SIZE", 1))
    mine = [re.compile(x) for x in pats]
    # the lanes' axis leads the shape: the stacked leaf, a block of it, a
    # step's rows and queries
    lanes_axis = re.compile(r"bf16\[%d,(\d+,)*640\]" % lanes)
    secs = sum(s for name, s in p["ops"].items()
               if any(x.search(name) for x in mine)
               and lanes_axis.search(name))
    if not secs:
        _no_match(run, "mla_decode_roofline")
        return 0.0
    taken = percentile(durs, 50) / steps * secs / sum(durs)
    live = [parse_gauge(text, "scheduler_lanes_live")
            for _, text in run.get("samples") or []]
    live = [v for v in live if v is not None]
    n_live = sum(live) / len(live) if live else lanes
    ctx = [r.prompt_tokens + r.completion_tokens / 2 for r in run["records"]
           if r.prompt_tokens and r.completion_tokens]
    context = sum(ctx) / len(ctx) if ctx else 0
    peak = costs.peaks(run["device"]["kind"])
    nbytes = block.latent_bytes_per_step(cfg, n_live, context)
    flops = block.latent_flops_per_step(cfg, n_live, context)
    least, bound = costs.roofline_seconds(flops, nbytes, peak)
    run["notes"]["mla_decode_roofline"] = {
        "bound": bound, "least_ms": least * 1e3,
        "device_ms_per_step": taken * 1e3, "bytes_per_step": nbytes,
        "flops_per_step": flops, "lanes_live": n_live, "context": context}
    return 100.0 * least / taken
