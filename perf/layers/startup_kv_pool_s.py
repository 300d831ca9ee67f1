"""Seconds sizing the KV pool and the state slots and allocating
their arrays (span `setup.kv_pool`)."""
from perf.startup import at_opening


def read(run):
    return at_opening(run, "aphrodite:setup_kv_pool_seconds_total")
