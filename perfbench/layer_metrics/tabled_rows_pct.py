"""provider: share of the device's signature rows that the cached key
tables verified (slot order and gathered together), from the program's
own ``TABLED_COUNTS``. 100 when every row rode the tables; 0 when the
generic kernels did the work (decompression and table build on the
device for every row of every call).

Over the window where the entry's ``engine_stats`` carries the counters;
where it does not (an adapter that builds no pipeline and adds none), the
process's totals over the process's device rows at the window's end —
warm-ups and window run the same requests."""


def tabled_counts(run):
    """(counts by name, device rows) of the window, else of the process;
    None where the program keeps no such counts."""
    before, after = run["engine_stats"]
    if "device_rows" not in after:
        return None
    c0, c1 = before.get("counters") or {}, after.get("counters") or {}
    if "tabled_slot_rows" in c1:
        grew = {k: v - c0.get(k, 0) for k, v in c1.items() if k.startswith("tabled_")}
        return grew, after["device_rows"] - before["device_rows"]
    try:
        from tendermint_tpu.crypto.batch import TABLED_COUNTS
    except ImportError:
        return None
    return TABLED_COUNTS.snapshot(), after["device_rows"]


def read(run):
    got = tabled_counts(run)
    if got is None or got[1] <= 0:
        return None
    counts, device_rows = got
    return 100.0 * (counts["tabled_slot_rows"] + counts["tabled_gathered_rows"]) / device_rows
