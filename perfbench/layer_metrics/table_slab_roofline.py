"""kernels: the least time the chip could take to cut the traced
launches' table slabs from the key pool (``rooflines/table_slab``,
against ``peaks.json``) over the device time of the slab program's
launches in the trace. The columns of a slab are the program's own count
over the window (``table_slab_columns`` / ``table_slabs``: every slab of
a cell has one shape). None where no slab program ran: a set that is the
pool as it lies, or a program without a key pool."""

from perfbench.rooflines import table_slab

MODULE_PREFIX = "jit_table_slab"


def read(run):
    t = run["trace"]
    if t is None or run["peaks"] is None:
        return None
    slab_s = sum(s for name, s in t.module_s.items() if name.startswith(MODULE_PREFIX))
    launches = sum(n for name, n in t.module_runs.items() if name.startswith(MODULE_PREFIX))
    before, after = ((st.get("counters") or {}) for st in run["engine_stats"])
    slabs = after.get("table_slabs", 0) - before.get("table_slabs", 0)
    columns = after.get("table_slab_columns", 0) - before.get("table_slab_columns", 0)
    if slab_s <= 0 or not launches or slabs <= 0:
        return None
    least = table_slab.least_seconds(launches * columns / slabs, run["peaks"])
    return 100.0 * least["seconds"] / slab_s
