"""Seconds the server's loader took to put the weights on the device
(span `setup.weights`)."""
from perf.startup import at_opening


def read(run):
    return at_opening(run, "aphrodite:setup_weights_seconds_total")
