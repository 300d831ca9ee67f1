"""Share of the rounds dispatched with a round in flight whose round
in flight had already finished: the device had nothing queued and
waited for the host (`runner.starved` over `round.ahead`, counted at
the dispatch by `is_ready()` of the handles in flight)."""
from perf.rounds import ratio


def read(run):
    value = ratio(run, "aphrodite:dispatches_starved_total",
                  "aphrodite:rounds_ahead_total")
    return None if value is None else value * 1e2
