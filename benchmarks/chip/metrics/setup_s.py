"""Process start to the window's arrival clock zero: imports, weights,
engine, compilation or cache loads, and the warm-up of every program the
window uses."""


def read(run):
    return run.setup_s
