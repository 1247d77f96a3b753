"""scheduler: of the prompt tokens prefilled in the window (padding
included), the share that went through WIDE slices: programs of more than
the narrow width (``LFKT_PREFILL_CHUNK``), which the plan cuts only where
nobody decodes behind the slice (engine/slices.py): growth of
``prefill_slice_tokens_total{width="wide"}`` over the growth of both
widths, the engines' host-side sums in the first and last of the 5 Hz
``/metrics`` samples.  75-95 % where one caller sends long prompts, about 0
where some lane is always live.  None on a program without the counter, or
where nothing was prefilled in the window.  program_counter."""
from counters import delta


def read(run):
    wide = delta(run, 'prefill_slice_tokens_total{width="wide"}')
    narrow = delta(run, 'prefill_slice_tokens_total{width="narrow"}')
    if wide is None or narrow is None or not wide + narrow:
        return None
    return 100.0 * wide / (wide + narrow)
