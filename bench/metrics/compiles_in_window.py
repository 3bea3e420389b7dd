"""Programs compiled, or loaded from the persistent cache, inside the
window (``jax.monitoring`` events).  0 once set-up warmed every shape."""


def read(run):
    return run.compiles_in_window
