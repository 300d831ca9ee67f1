"""1 minus the union of device-operation intervals over the traced
seconds, from the profiler's trace."""


def read(run):
    if run.trace is None:
        return None
    return (1.0 - run.trace["busy_s"] / run.trace["window_s"]) * 100.0
