"""95th percentile of the time from due to first streamed token."""
from perf.stats import percentile


def read(run):
    ttfts = [r.ttft for r in run.window.replies if r.ttft is not None]
    return percentile(ttfts, 95) * 1e3 if ttfts else None
