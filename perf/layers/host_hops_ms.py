"""Milliseconds a round in the two thread hops, from the event loop
to the step thread and back: the loop's call of `engine.step` (span
`aph.async.step_call`) less the step itself (`aph.engine.step`)."""
from perf.rounds import per_round_ms


def read(run):
    call = per_round_ms(run, "aphrodite:step_call_seconds_total")
    step = per_round_ms(run, "aphrodite:engine_step_seconds_total")
    return None if call is None or step is None else call - step
