"""kernels: the least time a decode step's read of the SELECTED latents
could take over the device time its attention took (a ``deepseek32`` file).
Least: the larger of ``min(context, index_topk)`` latents and rotated keys a
live lane and layer (``blocks/deepseek32.py selected_bytes_per_step``) over
the chip's HBM bandwidth and the absorbed form's FLOPs over them
(``selected_flops_per_step``) over its bf16 peak: at 128 heads 242 FLOP a
byte against the chip's 240, so the two bounds meet.  Taken: the self time,
in the capture, of the decode kernel that serves the selection
(``flash_attention_decode_latent_select``: the selection is a MASK on the
blocks it walks, so it fetches and scores every live latent and reads far
under 100 %: the headroom of a read that fetches what was selected), as
``dsa_roofline.py`` scales it to a step.  0.0 where the capture holds no
such kernel (the XLA loop served); None without a capture or on a block
without the two functions.  device_trace."""
from dsa_roofline import read as _read


def read(run):
    return _read(run, "dsa_read_roofline",
                 r"flash_attention_decode_latent_select",
                 "selected_bytes_per_step", "selected_flops_per_step")
