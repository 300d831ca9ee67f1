"""Mean number of running requests (the decode batch), sampled."""
from perf.stats import mean


def read(run):
    return mean(run.gauge("aphrodite:num_requests_running"))
