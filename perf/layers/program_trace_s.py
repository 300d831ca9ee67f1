"""Seconds the server's process spent tracing jitted functions before
the window opened, outermost traces alone (`program.trace`, from
`jax.monitoring`)."""
from perf.startup import at_opening


def read(run):
    return at_opening(run, "aphrodite:program_trace_seconds_total")
