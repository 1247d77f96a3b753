"""kernels: the least time the slice kernel of the state-space layers' scan
could take over the device time it took, a prompt row.  Least and taken:
``ssm_roofline.py``.  0.0 where the capture holds no ``%ssm_scan`` call;
None without a capture, without the counters, or on a block without
``scan_ops_per_row``.  device_trace."""
from ssm_roofline import read as _read


def read(run):
    return _read(run, "ssm_scan_roofline")
