"""Share of step programs that were dispatched while the round before
was still on the device, of all step programs dispatched (counted;
`runner.ahead` over `sampler.plan`, one plan a step program)."""
from perf.rounds import ratio


def read(run):
    value = ratio(run, "aphrodite:steps_ahead_total",
                  "aphrodite:sampler_plans_total")
    return None if value is None else value * 1e2
