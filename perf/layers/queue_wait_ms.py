"""Mean milliseconds from a request's arrival at the async engine to
the round that first scheduled it, over the requests first scheduled
in the window."""
from perf.rounds import ratio


def read(run):
    value = ratio(run, "aphrodite:queue_wait_seconds_total",
                  "aphrodite:requests_first_scheduled_total")
    return None if value is None else value * 1e3
