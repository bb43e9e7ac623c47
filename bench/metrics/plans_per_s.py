"""Planning requests answered in the window per second, from the window's
start to the last answer (host clock)."""


def read(run):
    return len(run.requests) / (run.window[1] - run.window[0])
