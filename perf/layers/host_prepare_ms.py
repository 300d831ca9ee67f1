"""Milliseconds a round building the step's host batch, sampling plan
included, up to the dispatch (spans `aph.runner.prepare` and, inside
it, `aph.sampler.plan`)."""
from perf.rounds import per_round_ms


def read(run):
    return per_round_ms(run, "aphrodite:host_prepare_seconds_total")
