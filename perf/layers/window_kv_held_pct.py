"""Pages the window page groups hold live for the decode rows, over
the pages the same rows would hold there without a window, both summed
over the window's decode steps (counted on the host where the model
runner builds a step's work lists:
`aphrodite:kv_pages_live_window_total` over
`aphrodite:window_pages_unwindowed_total`). A window of 4,096 under
contexts of 8,192-8,960 reads about 48; 100 says the window lets
nothing go. A program without the counters gives None."""
from perf.rounds import ratio


def read(run):
    value = ratio(run, "aphrodite:kv_pages_live_window_total",
                  "aphrodite:window_pages_unwindowed_total")
    return None if value is None else value * 1e2
