"""Process start to the window's start: JAX start-up, inputs, warm-up and
compiles (host clock, s)."""


def read(run):
    return run.setup_s
