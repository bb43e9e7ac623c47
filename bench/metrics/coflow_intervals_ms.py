"""Host-clock wall of the outermost ``plan.coflow_intervals`` spans
(``FinalSchedule.coflow_intervals``: the lazy build of a plan part's
per-coflow edge intervals, the BNA of every merged interval and its FIFO
walk) in the traced window, per answered request, from the program's
recording (ms); 0 when the window built none, nothing when the program
has no such span."""
from repro.core import spans

NAME = "plan.coflow_intervals"


def read(run):
    rec = run.program
    if rec is None or NAME not in spans.NAMES:
        return None
    seconds = sum(s.seconds for i, s in enumerate(rec.spans)
                  if s.name == NAME and s.end is not None
                  and run.in_window(s.start, s.end) and rec.outermost(i))
    return seconds / len(run.requests) * 1e3
