"""Mean share of the device KV pool in use, sampled."""
from perf.stats import mean


def read(run):
    value = mean(run.gauge("aphrodite:gpu_cache_usage_perc"))
    return None if value is None else value * 100
