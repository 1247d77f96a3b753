"""kernels: self time of the state-space layers' recurrence over device busy
time, in the mid-window capture: a prefill slice's kernel (``%ssm_scan``) and
the XLA operations over the float32 state leaf (a decode step's update of
the lanes' states), found by ``kernels/ssm_scan.json``'s patterns.  0.0
where the capture holds no such operation; None only without a capture, or
on a checkout without the group.  device_trace."""
from opshare import busy_share


def read(run):
    pats = run["kernel_groups"].get("ssm_scan") or []
    if not pats:
        return None
    return busy_share(run, "ssm_scan_busy_share",
                      "|".join(f"(?:{p})" for p in pats))
