"""kernels: the least time the grouped expert matmuls of a prefill slice
could take over the device time they took.  Least, per slice: the larger of
every held expert's stored bytes in every routed layer over the chip's HBM
bandwidth (a slice of 256 rows at 4 picks of 64 touches them all) and the
picked experts' FLOPs on the slice's rows over the chip's bf16 peak
(``blocks/lfm2_moe.py expert_slice_cost``), summed over the slices of the
capture: as many as it holds prefill programs on its ``XLA Modules`` line,
each of the median width of the ``prefill_slice`` spans.  Taken: the
many-row expert kernels' self time in the capture.  0.0 where the capture
holds no such kernel; None without a capture, where it holds no prefill
program, or on a block without ``expert_slice_cost``.  device_trace."""
import re

import costs
from ggufgen import block_of
from metrics import percentile
from opshare import _no_match
from spans import named


def read(run):
    p = run.get("profile")
    cfg = run["config"]
    cost_fn = getattr(block_of(cfg), "expert_slice_cost", None)
    if not p or cost_fn is None:
        return None
    slices = [d for n, _, d in p["modules"] if re.search(r"prefill", n)]
    rows = percentile([(s.get("attrs") or {}).get("tokens")
                       for s in named(run["traces"], "prefill_slice")
                       if (s.get("attrs") or {}).get("tokens")], 50)
    if not slices or not rows:
        return None
    taken = sum(s for name, s in p["ops"].items()
                if re.search(r"^%q\d\w*_expert_matmul_manyrow", name))
    if not taken:
        _no_match(run, "routed_prefill_roofline")
        return 0.0
    peaks = costs.peaks(run["device"]["kind"])
    nbytes, flops = cost_fn(cfg, rows)
    by_bytes, by_flops = nbytes / peaks["hbm_bytes_per_s"], \
        flops / peaks["bf16_flops"]
    least = len(slices) * max(by_bytes, by_flops)
    run["notes"]["routed_prefill_roofline"] = {
        "bound": "hbm" if by_bytes >= by_flops else "mxu",
        "slices": len(slices), "rows": rows,
        "least_ms_per_slice": max(by_bytes, by_flops) * 1e3,
        "device_ms_per_slice": taken / len(slices) * 1e3}
    return 100.0 * least / taken
