"""Milliseconds a round unpacking the sampled results and processing
outputs: detokenise, stop checks, stats (spans `aph.sampler.finalize`
and `aph.engine.process`)."""
from perf.rounds import per_round_ms


def read(run):
    return per_round_ms(run, "aphrodite:host_process_seconds_total")
