"""Host-clock wall of the outermost ``plan.decompose`` spans (the batched
BNA of the plan's coflows: host packing, the device programs, the wait
for their pieces, compiles) in the traced window, per answered request,
from the program's recording (ms); 0 when the window decomposed no
coflow, nothing without a recording.  The interval batches of the nested
step open no such span: ``nested_decompose_ms`` and
``coflow_intervals_ms`` hold them."""
from bench.lib import program_spans


def read(run):
    return program_spans.decompose_ms(run, run.program, None)
