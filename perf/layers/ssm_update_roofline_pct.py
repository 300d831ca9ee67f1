"""Share of its roofline that the decode step's state update reaches:
the least time one state layer's call could take for the window's
decode rows (a row's state and convolution tail read and written, its
inputs and output, over the chip's memory bandwidth, or its operations
over the bf16 peak, whichever is longer; `perf/rooflines/ssm_scan.py`)
over the seconds a call took in the trace (`_ssm_update_impl*`, every
shape together).

The rows are counted on the host where the model runner builds a
decode step (`aphrodite:ssm_decode_rows_total`, a step a
`aphrodite:decode_attn_steps_total`), over the window with the
profiler off; the trace is the 2 s after it under the same callers:
the same steady state, not the same seconds. A program without the
counter, or a trace without the calls, gives None."""
import os

from perf import cells

KERNEL = "_ssm_update_impl"


def read(run):
    ops = (run.trace or {}).get("ops", {})
    mine = {name: sc for name, sc in ops.items()
            if name.startswith(KERNEL) and sc[1] > 0}
    rows = run.rate("aphrodite:ssm_decode_rows_total")
    steps = run.rate("aphrodite:decode_attn_steps_total")
    if not mine or not rows or not steps or run.peaks is None:
        return None
    count = cells.load_function(os.path.join(
        run.cell.root, "perf", "rooflines", "ssm_scan.py"), "update_count")
    moved, computed = count(run.cell.config, rows / steps)
    least = max(moved / run.peaks["hbm_bytes_per_s"],
                computed / run.peaks["bf16_flops_per_s"])
    seconds = sum(s for s, _ in mine.values())
    calls = sum(c for _, c in mine.values())
    return least / (seconds / calls) * 100.0
