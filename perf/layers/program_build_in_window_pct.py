"""Share of the window's seconds that some thread of the server spent
building a program (tracing, lowering, compiling or loading), whatever
the function: should read 0."""
STAGES = ("aphrodite:program_trace_seconds_total",
          "aphrodite:program_lower_seconds_total",
          "aphrodite:program_compile_seconds_total")


def read(run):
    rates = [run.rate(name) for name in STAGES]
    return None if None in rates else sum(rates) * 100
