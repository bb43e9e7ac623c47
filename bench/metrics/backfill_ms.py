"""Host-clock wall of the outermost ``plan.backfill`` spans
(``repro.core.backfill.backfill``: the per-coflow edge intervals of the
plan, then the packet sweep that re-executes them with leftover capacity
filled) in the traced window, per answered request, from the program's
recording (ms); 0 when the window backfilled nothing, nothing when the
program has no such span."""
from repro.core import spans

NAME = "plan.backfill"


def read(run):
    rec = run.program
    if rec is None or NAME not in spans.NAMES:
        return None
    seconds = sum(s.seconds for i, s in enumerate(rec.spans)
                  if s.name == NAME and s.end is not None
                  and run.in_window(s.start, s.end) and rec.outermost(i))
    return seconds / len(run.requests) * 1e3
