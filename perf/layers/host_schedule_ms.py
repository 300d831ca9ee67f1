"""Milliseconds a round in deadline expiry, the scheduler and the
block manager (span `aph.sched.schedule`)."""
from perf.rounds import per_round_ms


def read(run):
    return per_round_ms(run, "aphrodite:host_schedule_seconds_total")
