"""Step programs traced while the window was open; should read 0."""
from perf.server import compile_facts


def read(run):
    return compile_facts(run.log_window)["programs"]
