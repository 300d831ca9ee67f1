"""Share of its roofline that the paged decode-attention kernel
reaches in a model whose rows' tables are `[summary pages ; window
pages]` (EvaByte, 32 KV heads of one query row each): the least time a
decode step's calls could take between them (every live summary page
and every live window page once for each layer, plus the rows, over
the chip's memory bandwidth, or their operations over the bf16 peak,
whichever is longer; `perf/rooflines/paged_decode_summary.py`) over
the seconds a step's calls took in the trace (`_paged_decode_impl*`).

The live pages are counted on the host where the model runner builds a
step's work lists, by the kernel's own rule
(`aphrodite:kv_pages_live_window_total` counts every page of such a
table, `aphrodite:kv_pages_live_summary_total` the summary pages among
them, a step a `aphrodite:decode_attn_steps_total`), over the window
with the profiler off; the trace is the 2 s after it under the same
callers: the same steady state, not the same seconds. A program
without the summary counter, a configuration without `window_size`, or
a trace without the calls gives None."""
import os
import re

from perf import cells

KERNEL = "_paged_decode_impl"


def read(run):
    ops = (run.trace or {}).get("ops", {})
    mine = {name: sc for name, sc in ops.items()
            if name.startswith(KERNEL) and sc[1] > 0}
    steps = run.rate("aphrodite:decode_attn_steps_total")
    pages = run.rate("aphrodite:kv_pages_live_window_total")
    summary = run.rate("aphrodite:kv_pages_live_summary_total")
    if not mine or not steps or not pages or summary is None or \
            run.peaks is None or "window_size" not in run.cell.config:
        return None
    # the rows of a call: the result's leading dimension, of the shape
    # that took most of the time
    most = max(mine, key=lambda name: mine[name][0])
    shape = re.search(r"\[(\d+),", most)
    count = cells.load_function(os.path.join(
        run.cell.root, "perf", "rooflines", "paged_decode_summary.py"),
        "count")
    moved, computed = count(run.cell.config, summary / steps,
                            (pages - summary) / steps,
                            int(shape.group(1)) if shape else 0)
    least = max(moved / run.peaks["hbm_bytes_per_s"],
                computed / run.peaks["bf16_flops_per_s"])
    layers = run.cell.config["num_hidden_layers"]
    seconds = sum(s for s, _ in mine.values())
    calls = sum(c for _, c in mine.values())
    return least / (seconds / calls * layers) * 100.0
