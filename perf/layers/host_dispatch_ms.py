"""Milliseconds a round in the jitted calls that enqueue its step
programs, up to their return (span `aph.runner.dispatch`)."""
from perf.rounds import per_round_ms


def read(run):
    return per_round_ms(run, "aphrodite:host_dispatch_seconds_total")
