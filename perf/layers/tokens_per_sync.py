"""Output tokens per blocking pull of results from the device."""
from perf.rounds import ratio


def read(run):
    return ratio(run, "aphrodite:generation_tokens_total",
                 "aphrodite:host_syncs_total")
