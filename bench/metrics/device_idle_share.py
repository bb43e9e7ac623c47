"""1 - (union of the device's op intervals / window), from the profile of
the traced window, averaged over the chips (%)."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.trace.idle_share
