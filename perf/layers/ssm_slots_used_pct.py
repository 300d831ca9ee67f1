"""Mean share of the state slots that sequences hold, sampled: live
slots over slots (`aphrodite:ssm_slots_live` over
`aphrodite:ssm_slots_total`), the recurrent state's counterpart of
`kv_used_pct`. A program without the gauges, or a model without state
slots (the total reads 0), gives None."""
from perf.stats import mean


def read(run):
    live = run.gauge("aphrodite:ssm_slots_live")
    total = run.gauge("aphrodite:ssm_slots_total")
    shares = [a / b for a, b in zip(live, total) if b]
    value = mean(shares)
    return None if value is None else value * 100
