"""kernels: self time of the operations on the linear-attention layers'
state over device busy time, in the mid-window capture: the decode step's
kernel (``ops/pallas/linstate.py``, ``%lin_state`` in a profile) and the XLA
fusions and copies over the float32 state leaf (a prefill slice's chunk form
where it touches the state; the plain XLA recurrence where no kernel
serves), found by
``kernels/lin_state.json``'s patterns.  Read through ``opshare`` and not
through the groups (a group takes an operation from every group after it
in name order).  0.0 where the capture holds no such operation (a file of
another block); None only without a capture.  device_trace."""
from opshare import busy_share


def read(run):
    pats = run["kernel_groups"].get("lin_state") or []
    if not pats:
        return None
    return busy_share(run, "lin_state_busy_share",
                      "|".join(f"(?:{p})" for p in pats))
