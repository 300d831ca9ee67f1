"""Share of the experts that a decode step touches: experts with at
least one token-expert pair, summed over the expert layers of the
window's decode steps, over experts x expert layers a step (counted on
the device in the router and pulled with the step's result:
`aphrodite:moe_decode_experts_touched_total` over
`aphrodite:moe_decode_expert_slots_total`). It says how much of the
experts' weights a decode step has to read: 100 reads them all. A
program without the counters gives None."""
from perf.rounds import ratio


def read(run):
    value = ratio(run, "aphrodite:moe_decode_experts_touched_total",
                  "aphrodite:moe_decode_expert_slots_total")
    return None if value is None else value * 1e2
