"""Step programs traced during set-up (JAX_LOG_COMPILES lines)."""
from perf.server import compile_facts


def read(run):
    return compile_facts(run.log_setup)["programs"]
