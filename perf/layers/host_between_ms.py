"""Milliseconds a round the async loop spends between one engine step
returning and the next entering (span `aph.async.between_steps`)."""
from perf.rounds import per_round_ms


def read(run):
    return per_round_ms(run, "aphrodite:host_between_steps_seconds_total")
