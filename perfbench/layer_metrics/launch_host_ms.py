"""model: host ms a traced request spends planning, staging and
dispatching its launches, from the program's own spans (``launch.plan``
+ ``launch.stage`` + ``launch.dispatch``, own time). None where the
profile holds none of the program's spans."""

SPANS = ("launch.plan", "launch.stage", "launch.dispatch")


def read(run):
    t = run["trace"]
    if t is None or not getattr(t, "program_spans", 0):
        return None
    return 1e3 * sum(t.span_s.get(name, 0.0) for name in SPANS)
