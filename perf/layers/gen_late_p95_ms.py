"""How late the load generator sent: send time minus due time. A
starved generator must not read as a fast server."""
from perf.stats import percentile


def read(run):
    late = [r.sent - r.due for r in run.window.replies]
    return percentile(late, 95) * 1e3 if late else None
