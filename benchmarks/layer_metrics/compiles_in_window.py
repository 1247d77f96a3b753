"""scheduler: programs compiled between the window's start and its end
(``/debug/compiles``).  Anything but 0 also makes the run incorrect.
program_counter."""


def read(run):
    return float(run["compiles_in_window"])
