"""device: share of the traced window's device-idle time that no span of
the program accounts for (``perfbench.program_trace``: no work span and
no wait span open on any host thread inside a request). None where the
profile holds none of the program's spans (its profiler sink was off, or
the program records none)."""


def read(run):
    t = run["trace"]
    if t is None or not getattr(t, "program_spans", 0):
        return None
    idle = sum(t.idle_by_span.values())
    if idle <= 0:
        return None
    return 100.0 * t.idle_by_span.get("unattributed", 0.0) / idle
