"""Median time from when a request was due to its first streamed token."""
from perf.stats import percentile


def read(run):
    ttfts = [r.ttft for r in run.window.replies if r.ttft is not None]
    return percentile(ttfts, 50) * 1e3 if ttfts else None
