"""Seconds the server's process spent lowering traced functions to
MLIR modules before the window opened (`program.lower`)."""
from perf.startup import at_opening


def read(run):
    return at_opening(run, "aphrodite:program_lower_seconds_total")
