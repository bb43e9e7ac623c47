"""Host-clock wall of the outermost ``plan.nested_decompose`` spans
(``repro.core.timeline._decompose``: DMA-RT's BNA of every merged
interval and its FIFO walk) in the traced window, per answered request,
from the program's recording (ms); 0 when the window ran no nested step."""


def read(run):
    rec = run.program
    if rec is None:
        return None
    seconds = sum(s.seconds for i, s in enumerate(rec.spans)
                  if s.name == "plan.nested_decompose" and s.end is not None
                  and run.in_window(s.start, s.end) and rec.outermost(i))
    return seconds / len(run.requests) * 1e3
