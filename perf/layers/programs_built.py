"""Programs the server's process built before the window opened: every
jitted function and every one-operation program of an eager call
(`aphrodite:programs_built_total`)."""
from perf.startup import at_opening


def read(run):
    return at_opening(run, "aphrodite:programs_built_total")
