"""verify seam: host ms a traced request spends reading commits into
columns and packing rows from them, from the program's own spans
(``verify.columns`` + ``verify.pack``, own time, every thread). None
where the profile holds none of the program's spans."""


def read(run):
    t = run["trace"]
    if t is None or not getattr(t, "program_spans", 0):
        return None
    return 1e3 * (t.span_s.get("verify.columns", 0.0) + t.span_s.get("verify.pack", 0.0))
