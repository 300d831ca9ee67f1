"""Seconds of the server's start before its engine had a device: the
interpreter and the imports (span `setup.import`) and the accelerator's
runtime coming up at the first `jax.devices()` (`setup.backend`)."""
from perf.startup import at_opening


def read(run):
    return at_opening(run, "aphrodite:setup_import_seconds_total",
                      "aphrodite:setup_backend_seconds_total")
