"""Device time of the jitted BNA decomposition programs (``jit_decompose``,
``repro.core.pipeline``) per answered request, from the profile (ms); 0
when the traced requests decomposed nothing on the device."""


def read(run):
    if run.trace is None:
        return None
    runs_s = run.trace.modules.get("jit_decompose", [0, 0.0])[1]
    return runs_s / len(run.requests) * 1e3
