"""kernels: self time of decode attention over device busy time, in the
mid-window capture: the XLA fusions over the KV ring (scores, PV, the ring
write), which ``kernels/attn.json`` counts together with prefill's flash
kernel.  XLA names a fusion itself and a ``jax.named_scope`` reaches only
the HLO's metadata, not the profiler's event, so they are found as
``attn.json`` finds them: fusions that take a bf16 operand of rank 4 or 5.
0.0 where the capture holds no such fusion; None only without a capture.
device_trace."""
from opshare import busy_share


def read(run):
    return busy_share(run, "decode_attn_busy_share",
                      r"= \S+ fusion\(.*bf16\[(\d+,){3,4}\d+\]")
