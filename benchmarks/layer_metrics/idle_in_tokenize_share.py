"""device: per cent of the device's idle seconds (the capture's 50 longest
gaps) in whose middle the innermost open phase is ``lfkt.tokenize``: the
chip waiting for the host tokenizer (``annotations.py``).  A capture with
no idle second, or none inside ``lfkt.tokenize``, gives 0.0.  None only
without a capture (an unsound run).  device_trace."""
from annotations import idle_share


def read(run):
    return idle_share(run, "tokenize")
