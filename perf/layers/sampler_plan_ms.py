"""Milliseconds one `Sampler.plan` takes, reused and rebuilt plans
together (span `aph.sampler.plan`, inside `aph.runner.prepare`)."""
from perf.rounds import ratio


def read(run):
    value = ratio(run, "aphrodite:sampler_plan_seconds_total",
                  "aphrodite:sampler_plans_total")
    return None if value is None else value * 1e3
