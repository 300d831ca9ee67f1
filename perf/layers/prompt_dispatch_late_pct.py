"""Of the rounds that carry a prompt step and went out with a round in
flight (`round.ahead.prompt`), those whose prompt program was enqueued
after the decode program just ahead of it had already finished
(`runner.prompt_late`): the host prepared the prompt batch for longer
than the device had work. The twin of `dispatch_starved_prompt_pct`,
for the round's second program."""
from perf.rounds import ratio


def read(run):
    value = ratio(run, "aphrodite:dispatches_prompt_late_total",
                  "aphrodite:rounds_ahead_prompt_total")
    return None if value is None else value * 1e2
