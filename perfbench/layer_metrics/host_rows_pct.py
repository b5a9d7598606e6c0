"""provider: share of the window's signature rows that the provider's
host path served, from ``RowCounts`` (counted where the work is done).
0 when the device did all of it."""


def read(run):
    before, after = run["engine_stats"]
    if "device_rows" not in after or "host_rows" not in after:
        return None
    host = after["host_rows"] - before["host_rows"]
    total = host + after["device_rows"] - before["device_rows"]
    if total <= 0:
        return None
    return 100.0 * host / total
