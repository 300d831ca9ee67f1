"""`host_lead_ms` over the pulls of a decode-only round behind the
dispatch of a decode-only round (`pull.blocked.decode`): the slack of
an ordinary round under an ordinary step, which a shorter decode step
eats first."""
from perf.rounds import ratio


def read(run):
    value = ratio(run, "aphrodite:pull_blocked_decode_seconds_total",
                  "aphrodite:pulls_ahead_decode_total")
    return None if value is None else value * 1e3
