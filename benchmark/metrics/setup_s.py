"""Process start to the first instant of the measured window: imports,
compilation or compile-cache loads, the traffic build and the warm-up."""


def read(run):
    return run.setup_s
