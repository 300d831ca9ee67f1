"""Share of the compile requests before the window opened that the
persistent compilation cache answered: 100 on a warm run, near 0 on
the first of a checkout."""
from perf.startup import at_opening

HITS = "aphrodite:program_cache_hits_total"
MISSES = "aphrodite:program_cache_misses_total"


def read(run):
    hits, asked = at_opening(run, HITS), at_opening(run, HITS, MISSES)
    return hits / asked * 100 if asked else None
