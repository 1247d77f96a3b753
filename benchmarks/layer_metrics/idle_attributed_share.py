"""device: per cent of the device's idle seconds (the capture's 50 longest
gaps) that lie inside some ``lfkt.`` phase of the program
(``annotations.py``); the rest is host time the program does not name yet.
A capture with no idle second leaves nothing unnamed: 100.0; one with no
phase names nothing: 0.0.  None only without a capture (an unsound run).
Writes ``run["notes"]["idle_by_phase"]``.  device_trace."""
from annotations import idle_share


def read(run):
    return idle_share(run)
