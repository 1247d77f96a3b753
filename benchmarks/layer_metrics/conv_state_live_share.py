"""kv ring: of the updates of conv layers' carried rows that the decode
steps' arithmetic ran, the share that a lane which holds a request needed:
``conv_state_updates_total`` (live lane x conv layer x step) over lanes x
conv layers x steps.  Every lane of the batch runs a step's arithmetic,
whether it holds a request or not; a lane that holds none keeps its rows.
Steps: ``expert_layer_steps_total`` over the routed layers (the program
counts one a routed layer and step).  The program's counters in the first
and last of the 5 Hz ``/metrics`` samples.  None on a program without the
counters, or where no step ran in the window.  program_counter."""
from counters import delta
from ggufgen import block_of


def read(run):
    cfg = run["config"]
    block = block_of(cfg)
    updates = delta(run, "conv_state_updates_total")
    layer_steps = delta(run, "expert_layer_steps_total")
    if updates is None or not layer_steps or not hasattr(block, "n_moe"):
        return None
    steps = layer_steps / block.n_moe(cfg)
    lanes = int(cfg["serve"]["env"].get("LFKT_BATCH_SIZE", 1))
    return 100.0 * updates / (lanes * block.n_kind(cfg, "conv") * steps)
