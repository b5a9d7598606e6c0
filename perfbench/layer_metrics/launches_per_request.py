"""model: launches of compiled programs on the device per request, from
the trace's ``XLA Modules`` line over the whole requests traced."""


def read(run):
    t = run["trace"]
    if t is None or not t.requests or not t.module_runs:
        return None
    return sum(t.module_runs.values()) / t.chips / t.requests
