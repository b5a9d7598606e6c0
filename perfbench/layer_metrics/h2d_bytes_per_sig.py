"""model: bytes the window's served launches copied host to device per
signature row the device verified, from the program's ``H2D_COUNTS``
(``engine_stats()["counters"]["h2d_bytes"]``). Where the entry's
``engine_stats`` carries no counters (an adapter that builds no
pipeline), the process's totals over the process's device rows at the
window's end: warm-ups and window run the same requests. None where the
program keeps no such count."""


def read(run):
    before, after = run["engine_stats"]
    if "device_rows" not in after:
        return None
    c0, c1 = before.get("counters") or {}, after.get("counters") or {}
    if "h2d_bytes" in c1:
        grew = c1["h2d_bytes"] - c0.get("h2d_bytes", 0)
        rows = after["device_rows"] - before["device_rows"]
    else:
        try:
            from tendermint_tpu.crypto.batch import H2D_COUNTS
        except ImportError:
            return None
        grew, rows = H2D_COUNTS.snapshot()["h2d_bytes"], after["device_rows"]
    if rows <= 0 or grew <= 0:
        return None
    return grew / rows
