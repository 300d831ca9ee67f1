"""Seconds from the start of the server's process to its being ready
for connections (gauge `aphrodite:startup_seconds`, which the phases
`aphrodite:setup_*_seconds_total` should tile)."""
from perf.startup import at_opening


def read(run):
    return at_opening(run, "aphrodite:startup_seconds")
