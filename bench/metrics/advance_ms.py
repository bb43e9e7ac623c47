"""Mean host time of the ``advance`` span per request in the window: the
session running its plan up to the next release (host clock, ms)."""


def read(run):
    times = [b - a for name, a, b in run.spans
             if name == "advance" and run.in_window(a, b)]
    return sum(times) / len(times) * 1e3 if times else None
