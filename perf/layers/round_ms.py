"""Milliseconds from one engine round to the next over the window:
1 / rate(`engine_rounds_total`), prefill rounds included."""
from perf.rounds import ROUNDS


def read(run):
    rounds = run.rate(ROUNDS)
    return 1e3 / rounds if rounds else None
