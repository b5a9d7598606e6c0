"""model: share of the rows launched into the generic verify family that
were padding up to a bucket (a streamed batch's tail, a batch between
two buckets), from the program's ``GENERIC_COUNTS`` over the window: an
empty row costs the device as much as a signed one. None where the
entry's ``engine_stats`` carries no such counters (a program without
them) or nothing ran on the generic family."""


def read(run):
    before, after = ((st.get("counters") or {}) for st in run["engine_stats"])
    if "generic_pad_rows" not in after:
        return None
    pad = after["generic_pad_rows"] - before.get("generic_pad_rows", 0)
    rows = after["generic_rows"] - before.get("generic_rows", 0)
    if rows + pad <= 0:
        return None
    return 100.0 * pad / (rows + pad)
