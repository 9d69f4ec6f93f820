"""Programs lowered inside the measured window (JAX's lowering events,
cache hits included): set-up is meant to leave none."""


def read(run):
    return run.compiles_in_window
