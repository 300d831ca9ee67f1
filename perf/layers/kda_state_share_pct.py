"""Share of a decode step's bytes that is the delta-rule state's: the
KDA layers' matrices of the step's rows, read and written
(`aphrodite:kda_decode_rows_total` x 2 x heads x d x d x 4 B a layer),
over those plus everything else the step touches by the
configuration's own count (`perf/rooflines/kda.py::step_bytes`: the
KDA and MLA projections, the dense MLP, routers, shared experts, the
held experts with a pair as `aphrodite:
moe_decode_experts_touched_total` counts them, the head's held rows,
and the latent rows the MLA layers read,
`aphrodite:mla_latent_tokens_read_total`), all a decode step
(`aphrodite:decode_attn_steps_total`). It says how much of a decode
step is the mechanism's: where it is small the cell measures something
else. A program without the counters, or a configuration without
`linear_attn_config`, gives None."""
import os

from perf import cells


def read(run):
    steps = run.rate("aphrodite:decode_attn_steps_total")
    rows = run.rate("aphrodite:kda_decode_rows_total")
    tokens = run.rate("aphrodite:mla_latent_tokens_read_total")
    touched = run.rate("aphrodite:moe_decode_experts_touched_total")
    if not steps or not rows or tokens is None or touched is None or \
            "linear_attn_config" not in run.cell.config:
        return None
    state, everything = cells.load_function(os.path.join(
        run.cell.root, "perf", "rooflines", "kda.py"), "step_bytes")(
            run.cell.config, rows / steps, tokens / steps, touched / steps)
    return state / everything * 100.0
