"""The arithmetic between raw observations and metrics."""
from __future__ import annotations

import math
from typing import Iterable, List, Optional, Sequence, Tuple


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between the
    closest ranks; raises on an empty sample."""
    if not values:
        raise ValueError("percentile of an empty sample")
    xs = sorted(values)
    k = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def pooled_gaps(arrivals: Sequence[Tuple[float, int]]) -> List[float]:
    """Seconds since the previous token reached the client, for every
    output token of one stream after its first. `arrivals` is one
    `(time, tokens)` per chunk. Tokens of one chunk share its arrival
    time: the first of them waited the whole gap since the chunk
    before, the others 0, as the reader of the stream sees it."""
    gaps: List[float] = []
    previous = None
    for t, n in arrivals:
        if n <= 0:
            continue
        if previous is not None:
            gaps.append(t - previous)
        gaps.extend([0.0] * (n - 1))
        previous = t
    return gaps


def mean(values: Iterable[float]) -> Optional[float]:
    xs = list(values)
    return sum(xs) / len(xs) if xs else None


def tokens_inside(replies, start: float, end: float) -> float:
    """Output tokens produced in [start, end) by requests whose replies
    came whole (not streamed): a reply's tokens are spread evenly over
    the time from its send to its end, and the part of that time
    inside the interval counts. Counting a reply's tokens where it
    ends would credit the interval with work done before it and leave
    out the work on requests still open at its end: with replies a
    third as long as the window that is several percent either way."""
    total = 0.0
    for r in replies:
        if r.ok and r.ended > r.sent:
            inside = min(r.ended, end) - max(r.sent, start)
            if inside > 0:
                total += r.tokens * inside / (r.ended - r.sent)
    return total
