"""kernels: of the key blocks the prefill attention kernel's grids WALKED in
the window's prompts, the share a slice NEEDED (the fused blocks up to its
own last row's position, a row tile and attention layer): growth of
``prefill_ring_blocks_live_total`` over growth of
``prefill_ring_blocks_walked_total``, which the ``ssm-state+ring`` kind counts
at each prompt from its slice plan and the kernel's static block sizes
(models/jamba.py ``prefill_walk``; ops/pallas/attention.py ``flash_plan``),
in the first and last of the 5 Hz ``/metrics`` samples.  100 % where the
walk ends at the slice's end; about 7 % where a 34k-token prompt's slices
walk a ring of 262144 slots whole.  None on a program without the counters
(the parent of the PR that brought them), or where no prompt was prefilled
through the kernel in the window.  program_counter."""
from counters import ratio


def read(run):
    return ratio(run, "prefill_ring_blocks_live_total",
                 "prefill_ring_blocks_walked_total", 100.0)
