"""Arithmetic on the per-stage counters of the engine round
(`aphrodite:*_seconds_total`, `aphrodite:engine_rounds_total`), read
from `/metrics` over the measured window with the profiler off. A
program that has no such counter gives every reader here None."""
from __future__ import annotations

from typing import Optional

ROUNDS = "aphrodite:engine_rounds_total"


def ratio(run, numerator: str, denominator: str) -> Optional[float]:
    """Growth of one counter per unit of growth of another."""
    top, bottom = run.rate(numerator), run.rate(denominator)
    if top is None or not bottom:
        return None
    return top / bottom


def per_round_ms(run, seconds_counter: str) -> Optional[float]:
    """Milliseconds of one stage in an average round of the window."""
    value = ratio(run, seconds_counter, ROUNDS)
    return None if value is None else value * 1e3
