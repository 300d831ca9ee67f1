"""What the server spent before the window: the values its set-up
counters and its start-up gauge (`aphrodite:setup_*_seconds_total`,
`aphrodite:program_*`, `aphrodite:startup_seconds`) have in the first
`/metrics` sample of the window, which holds everything since the
process started. A program that has no such counter gives every
reader here None."""
from __future__ import annotations

from typing import Optional


def at_opening(run, *names: str) -> Optional[float]:
    """The sum of `names` as the window's first sample has them; None
    unless it has every one."""
    if not run.samples:
        return None
    first = run.samples[0][1]
    if any(name not in first for name in names):
        return None
    return sum(first[name] for name in names)
