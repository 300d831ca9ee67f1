"""Preemptions (recompute and swap) while the window was open."""


def read(run):
    seen = run.gauge("aphrodite:preemptions_total")
    return seen[-1] - seen[0] if seen else None
