"""Milliseconds a round from a step's dispatch entered to its result
on the host (spans `aph.runner.dispatch` and `aph.runner.device_wait`)."""
from perf.rounds import per_round_ms


def read(run):
    return per_round_ms(run, "aphrodite:device_wait_seconds_total")
