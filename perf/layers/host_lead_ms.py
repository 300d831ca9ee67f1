"""Milliseconds the step thread blocks pulling the round in flight
after it has dispatched the next one: the device's work still to do
when the host had none left, the result's transfer included (counted
beside `aph.runner.device_wait`; `pull.blocked`). `round_ms` minus it
is the host's whole turn. None on a program without the counters."""
from perf.rounds import ratio


def read(run):
    value = ratio(run, "aphrodite:pull_blocked_seconds_total",
                  "aphrodite:pulls_ahead_total")
    return None if value is None else value * 1e3
