"""What ``ssm_scan_roofline`` computes (beside ``sala_roofline.py`` and
``dsa_roofline.py``: a helper of a reader, no metric of its own).

LEAST, a prompt row through all the state-space layers: the larger of

- the bytes the slice kernel must move (``blocks/phi4flash.py
  scan_bytes_per_row``: x and dt in, y out, float32 a channel, B and C) over
  the chip's HBM bandwidth (``peaks.json``), and
- the recurrence's vector work (``scan_ops_per_row``: six multiply-adds and
  one exp a channel and state) over the vector unit's rate.  ``peaks.json``
  states no such rate, so it is DERIVED from what it does state, and the
  derivation is an assumption written here: the MXU peak ``bf16_flops`` = 4
  units x 128 x 128 x 2 x clock gives the clock (1.5 GHz at 197 TFLOP/s);
  a cycle issues up to FOUR vector operations of 8 x 128 float32 lanes and
  ONE transcendental of as many.  If the unit issues fewer, the true least is
  larger and this share is understated: it errs below 100 %, never above.

TAKEN, a prompt row: the ``%ssm_scan`` calls' self time as a share of the
prefill programs' time in the capture (the ``XLA Modules`` line), times the
WHOLE window's prefill device seconds a prompt token
(``jit_device_seconds_total`` of the prefill programs over
``prefill_slice_tokens_total``: a sum over a sum, right at any mix of slice
widths, as ``prefill_program_ms_per_ktok`` has it)."""
import re

import costs
from counters import delta
from ggufgen import block_of
from opshare import _no_match
from stamps import PREFILL, seconds_of

#: vector operations and transcendentals a cycle, lanes of one (assumed)
VALU_SLOTS, EUP_SLOTS, LANES = 4, 1, 8 * 128
#: MXU flops a cycle: 4 units of 128 x 128 multiply-adds
MXU_FLOPS_PER_CYCLE = 4 * 128 * 128 * 2


def vector_rates(peaks: dict) -> tuple[float, float]:
    """(vector operations, transcendentals) a second, float32 elements."""
    clock = peaks["bf16_flops"] / MXU_FLOPS_PER_CYCLE
    return clock * VALU_SLOTS * LANES, clock * EUP_SLOTS * LANES


def least_per_row(cfg: dict, peaks: dict) -> tuple[float, str]:
    """(seconds, bound) of one prompt row through every state-space layer."""
    block = block_of(cfg)
    ops, exps = block.scan_ops_per_row(cfg)
    valu, eup = vector_rates(peaks)
    by = {"hbm": block.scan_bytes_per_row(cfg) / peaks["hbm_bytes_per_s"],
          "vpu": ops / valu, "eup": exps / eup}
    bound = max(by, key=by.get)
    return block.n_kind(cfg, "ssm") * by[bound], bound


def read(run, metric):
    p = run.get("profile")
    cfg = run["config"]
    if not p or not hasattr(block_of(cfg), "scan_ops_per_row"):
        return None
    prefill = [d for n, _, d in p["modules"] if re.search(r"prefill", n)]
    secs = seconds_of(run, PREFILL)
    wide = delta(run, 'prefill_slice_tokens_total{width="wide"}')
    narrow = delta(run, 'prefill_slice_tokens_total{width="narrow"}')
    if not prefill or secs is None or wide is None or narrow is None \
            or not wide + narrow:
        return None
    kernel = sum(s for name, s in p["ops"].items()
                 if re.search(r"^%ssm_scan", name))
    if not kernel:
        _no_match(run, metric)
        return 0.0
    taken = kernel / sum(prefill) * secs / (wide + narrow)
    least, bound = least_per_row(cfg, costs.peaks(run["device"]["kind"]))
    run["notes"][metric] = {
        "bound": bound, "least_us_per_row": least * 1e6,
        "device_us_per_row": taken * 1e6,
        "share_of_prefill_programs": kernel / sum(prefill)}
    return 100.0 * least / taken
