"""XLA compiles that ran inside the window: ``jax.monitoring`` backend
compile events that were not persistent-cache reads (count)."""


def read(run):
    return float(sum(1 for t, _ in run.compiles
                     if run.window[0] <= t <= run.window[1]))
