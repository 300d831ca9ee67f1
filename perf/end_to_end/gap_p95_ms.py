"""95th percentile, pooled over every output token after a request's
first, of the time since the previous token reached the client."""
from perf.stats import percentile, pooled_gaps


def read(run):
    gaps = [g for r in run.window.replies for g in pooled_gaps(r.arrivals)]
    return percentile(gaps, 95) * 1e3 if gaps else None
