"""Share of the window in which no dispatched step was waited for:
1 - rate(`device_wait_seconds_total`). The untraced counterpart of
`device_idle_pct`, read with the profiler off: a lower bound of the
device's idle share (the device is also idle inside a wait, until the
dispatched program starts and while its result travels)."""


def read(run):
    waiting = run.rate("aphrodite:device_wait_seconds_total")
    return None if waiting is None else (1.0 - waiting) * 100.0
