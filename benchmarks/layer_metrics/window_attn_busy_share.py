"""kernels: self time of the window layers' own kernels over device busy
time, in the mid-window capture: the decode kernel on a leaf that wraps
(``flash_attention_decode_window``) and the flash kernel on a window
layer's run of keys in a prefill slice (``flash_attention_window``), found
by ``kernels/window_attn.json``'s patterns.  Read through ``opshare`` and
not through the groups: ``attn.json`` comes before it in name order and
takes every ``flash_attention*`` kernel.  0.0 where the capture holds no
such operation; None only without a capture, or on a checkout without the
group.  device_trace."""
from opshare import busy_share


def read(run):
    pats = run["kernel_groups"].get("window_attn") or []
    if not pats:
        return None
    return busy_share(run, "window_attn_busy_share",
                      "|".join(f"(?:{p})" for p in pats))
