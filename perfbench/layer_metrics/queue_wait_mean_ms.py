"""pipeline: mean submit-to-execute wait of a bundle over the window,
``engine_stats()["queue_wait_ms"]``: ``sum_ms`` delta over ``count``
delta (exact; the histogram's buckets are too coarse for a percentile)."""


def read(run):
    before, after = (s.get("queue_wait_ms") for s in run["engine_stats"])
    if not before or not after:
        return None
    n = after["count"] - before["count"]
    if n <= 0:
        return None
    return (after["sum_ms"] - before["sum_ms"]) / n
