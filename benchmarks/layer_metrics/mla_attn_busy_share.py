"""kernels: self time of latent attention's own work over device busy time,
in the mid-window capture: the XLA fusions and dots over cached latents
(the block loop's scores and weighted sums, the row a step or slice writes,
the absorbed query), found by ``kernels/mla_attn.json``'s pattern (a bf16
shape whose last dimension is a latent row's).  Read through ``opshare``
and not through the groups: ``attn.json`` comes before it in name order and
takes the same fusions.  0.0 where the capture holds no such operation;
None only without a capture, or on a checkout without the group.
device_trace."""
from opshare import busy_share


def read(run):
    pats = run["kernel_groups"].get("mla_attn") or []
    if not pats:
        return None
    return busy_share(run, "mla_attn_busy_share",
                      "|".join(f"(?:{p})" for p in pats))
