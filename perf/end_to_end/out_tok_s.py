"""Output tokens produced inside the window, over its length; each
reply's tokens count by the share of its time, send to end, that lies
inside the window (`stats.tokens_inside`)."""
from perf.stats import tokens_inside


def read(run):
    w = run.window
    tokens = tokens_inside(w.replies, w.t0, w.t0 + w.seconds)
    return tokens / w.seconds if tokens else None
