"""Share of the window's reschedules that the session answered by plan
repair instead of a full replan: ``SessionStats.repairs / reschedules``
as deltas over the window's answered requests (%)."""


def read(run):
    if "repairs" not in run.counters:
        return None
    (r0, r1), (s0, s1) = run.counters["repairs"], run.counters["reschedules"]
    return 100.0 * (r1 - r0) / (s1 - s0) if s1 > s0 else None
