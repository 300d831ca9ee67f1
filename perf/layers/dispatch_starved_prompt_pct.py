"""`dispatch_starved_pct` over the rounds that carry a prompt step
(`runner.starved.prompt` over `round.ahead.prompt`): such a round is
prepared whole, prompt step included, before its decode step goes
out."""
from perf.rounds import ratio


def read(run):
    value = ratio(run, "aphrodite:dispatches_starved_prompt_total",
                  "aphrodite:rounds_ahead_prompt_total")
    return None if value is None else value * 1e2
