"""model step: of the (layer, prompt row) pairs of the prompts prefilled in
the window, padding included, the share that no program ran:
``prefill_layer_rows_skipped_total`` over it plus
``prefill_layer_rows_run_total``.  A ``phi4flash`` slice that holds no
prompt's last token stops after the full-attention layer (18 of 32 layers:
nothing above it writes a cache), and the one that does runs the layers above
it on that one row: 14 / 32 = 43.75 % of nearly every row.  The program's
counters in the first and last of the 5 Hz ``/metrics`` samples.  None on a
program without the counters, or where nothing was prefilled.
program_counter."""
from counters import delta


def read(run):
    skipped = delta(run, "prefill_layer_rows_skipped_total")
    ran = delta(run, "prefill_layer_rows_run_total")
    if skipped is None or ran is None or not skipped + ran:
        return None
    return 100.0 * skipped / (skipped + ran)
