"""Summary pages over all pages the decode rows hold live in a model
whose rows' tables are `[summary pages ; window pages]` (EvaByte),
both summed over the window's decode steps (counted on the host where
the model runner builds a step's work lists:
`aphrodite:kv_pages_live_summary_total` over
`aphrodite:kv_pages_live_window_total`, which counts every page of
such a table). It says how much of the decode attention is the pooled
part, and rises with the context: 16-24 summary pages beside 0-128
window pages read about 20. A program without the counters gives
None."""
from perf.rounds import ratio


def read(run):
    value = ratio(run, "aphrodite:kv_pages_live_summary_total",
                  "aphrodite:kv_pages_live_window_total")
    return None if value is None else value * 1e2
