"""compiles_in_window: engine jit traces plus backend compiles inside the
window; 0 when warm-up covered every shape the window used."""


def read(run):
    return run.compiles_in_window
