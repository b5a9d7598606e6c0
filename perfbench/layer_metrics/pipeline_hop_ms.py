"""pipeline: ms a traced request waits on the pipeline (``pipeline.wait``)
while none of its threads works (no ``pipeline.prep``,
``pipeline.execute`` or ``pipeline.resolve`` open): the thread hand-offs
alone. None where the profile holds none of the program's spans or no
caller waited on a pipeline."""


def read(run):
    t = run["trace"]
    if t is None or not getattr(t, "program_spans", 0) or "pipeline.wait" not in t.span_s:
        return None
    return 1e3 * t.hop_s
