"""Mean of the scheduler's waiting queue, sampled four times a second."""
from perf.stats import mean


def read(run):
    return mean(run.gauge("aphrodite:num_requests_waiting"))
