"""verify seam: host time of the entry outside the provider's calls —
request time minus time inside provider calls, mean per request, from
the benchmark's own spans (``program_span``)."""


def read(run):
    recs = run["records"]
    if not recs:
        return None
    return 1e3 * sum((r.t1 - r.t0) - r.provider_s for r in recs) / len(recs)
