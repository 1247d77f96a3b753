"""model step: device milliseconds of prefill per thousand prompt tokens
over the WHOLE window: growth of ``jit_device_seconds_total`` of the
prefill programs (``prefill_chunk``, ``prefill``, ``batched_prefill``:
``stamps.py``) over the growth of ``prefill_slice_tokens_total`` of both
widths (padding included).  A sum over a sum, so right at any mix of slice
widths, where ``prefill_device_ms_per_ktok`` (a median program over a
median span of a 3 s capture) is a coin toss.  An upper bound: eager work
queued ahead of a slice (the ids' transfer) lies in its interval.  None
without either counter or where nothing was prefilled.  program_counter."""
from counters import delta
from stamps import PREFILL, seconds_of


def read(run):
    secs = seconds_of(run, PREFILL)
    wide = delta(run, 'prefill_slice_tokens_total{width="wide"}')
    narrow = delta(run, 'prefill_slice_tokens_total{width="narrow"}')
    if secs is None or wide is None or narrow is None or not wide + narrow:
        return None
    return secs * 1e3 / ((wide + narrow) / 1000.0)
