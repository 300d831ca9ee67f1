"""Seconds of the backend's compile stage before the window opened:
compiling, or on a hit in the persistent cache loading the executable
(`program.compile`)."""
from perf.startup import at_opening


def read(run):
    return at_opening(run, "aphrodite:program_compile_seconds_total")
