"""Backend compilations (persistent-cache loads included) that ended
inside the window, from ``jax.monitoring``; the warm-up should leave none."""


def read(run):
    return float(run.compiles_in_window)
