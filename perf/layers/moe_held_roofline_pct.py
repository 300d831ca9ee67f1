"""Share of their roofline that the expert matmuls reach in a model
that holds a share of its experts: the least time an expert layer's
three grouped matmuls could take for the pairs whose expert is HELD
(the bytes of the held experts touched plus a held pair's rows in and
out over the chip's memory bandwidth, or 2 x 3 x hidden x width
operations a held pair over the bf16 peak, whichever is longer;
`perf/rooflines/moe_held.py`) over the seconds they took in the trace,
a layer's call against a layer's call.

In the trace the grouped matmuls are the custom calls whose name
starts with `ragged-dot` (what `jax.lax.ragged_dot` lowers to on the
chip: three a layer, gate, up and down, and the `ragged-dot-metadata*`
call they share, whose seconds count and whose calls do not), every
shape together: a decode step's and a prompt chunk's. Only the expert
layers call them (`FusedMoE._ragged_ffn`), so matching by name is
matching by the layer's calls; the dense layer's and the shared
expert's matmuls are XLA's own fusions under other names.

Held pairs and held experts touched are counted on the device in the
router, summed over a step's expert layers and pulled with the step's
result (`aphrodite:moe_pairs_held_total`,
`aphrodite:moe_experts_touched_total`), decode and prompt steps
together, over the window with the profiler off; a step program is one
`aphrodite:sampler_plans_total`. The trace is the 2 s after the window
under the same callers: the same steady state, not the same seconds.
The longer of the two times is taken of the window's sums, which is no
more than the sum of each call's longer time, so the share is not
overstated by it. A program without the counter, a configuration
without `mlp_layer_types`, or a trace without the calls gives None."""
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
    held = run.rate("aphrodite:moe_pairs_held_total")
    touched = run.rate("aphrodite:moe_experts_touched_total")
    steps = run.rate("aphrodite:sampler_plans_total")
    if not matmuls or not held or not touched or not steps or \
            run.peaks is None or "mlp_layer_types" not in run.cell.config:
        return None
    module = cells.load_module(os.path.join(
        run.cell.root, "perf", "rooflines", "moe_held.py"))
    moved, computed = module.count(run.cell.config, held, touched)
    layer_calls = steps * module.expert_layers(run.cell.config)
    least = max(moved / run.peaks["hbm_bytes_per_s"],
                computed / run.peaks["bf16_flops_per_s"]) / layer_calls
    seconds = sum(s for s, _ in mine.values())
    return least / (seconds / (matmuls / CALLS_A_LAYER)) * 100.0
