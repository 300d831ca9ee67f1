"""Share of their roofline that the expert matmuls reach: the least
time an expert layer's three grouped matmuls could take (the bytes of
the experts touched plus a pair's rows in and out over the chip's
memory bandwidth, or 2 x 3 x hidden x width operations a token-expert
pair over the bf16 peak, whichever is longer;
`perf/rooflines/moe_experts.py`) over the seconds they took in the
trace (the `ragged-dot*` custom calls, every shape together: three a
layer and the metadata call they share), a layer's call against a
layer's call.

Pairs routed and experts touched are counted on the device in the
router, summed over a step's layers and pulled with the step's result
(`aphrodite:moe_tokens_routed_total`,
`aphrodite:moe_experts_touched_total`), decode and prompt steps
together, over the window with the profiler off; a step program is one
`aphrodite:sampler_plans_total`. The trace is the 2 s after the
window under the same callers: the same steady state, not the same
seconds. The longer of the two times is taken of the window's sums,
which is no more than the sum of each call's longer time, so the share
is not overstated by it. A program without the counters, or a trace
without the calls, gives None."""
import os

from perf import cells

KERNEL = "ragged-dot"
#: the grouped matmuls of one expert layer: gate, up, down
CALLS_A_LAYER = 3


def read(run):
    ops = (run.trace or {}).get("ops", {})
    mine = {name: sc for name, sc in ops.items()
            if name.startswith(KERNEL) and sc[1] > 0}
    matmuls = sum(c for name, (_, c) in mine.items()
                  if not name.startswith(KERNEL + "-metadata"))
    pairs = run.rate("aphrodite:moe_tokens_routed_total")
    touched = run.rate("aphrodite:moe_experts_touched_total")
    steps = run.rate("aphrodite:sampler_plans_total")
    if not matmuls or not pairs or not touched or not steps or \
            run.peaks is None:
        return None
    count = cells.load_function(os.path.join(
        run.cell.root, "perf", "rooflines", "moe_experts.py"), "count")
    moved, computed = count(run.cell.config, pairs, touched)
    layer_calls = steps * run.cell.config["num_hidden_layers"]
    least = max(moved / run.peaks["hbm_bytes_per_s"],
                computed / run.peaks["bf16_flops_per_s"]) / layer_calls
    seconds = sum(s for s, _ in mine.values())
    return least / (seconds / (matmuls / CALLS_A_LAYER)) * 100.0
