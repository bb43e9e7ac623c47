"""Device time of the Pallas ``coflow_merge`` kernel's program
(``jit_coflow_merge_padded``, called by merge-and-fix) per answered
request, from the profile (ms); 0 when the traced requests never ran it."""


def read(run):
    if run.trace is None:
        return None
    runs_s = run.trace.modules.get("jit_coflow_merge_padded", [0, 0.0])[1]
    return runs_s / len(run.requests) * 1e3
