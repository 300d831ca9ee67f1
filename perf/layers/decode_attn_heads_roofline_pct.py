"""Share of its roofline that the paged decode-attention kernel
reaches in a model whose layers lie in page groups and differ in their
query heads: the least time a decode step's calls could take between
them (each group's live pages once, a group being one layer here, plus
the rows at each layer's own head count, over the chip's memory
bandwidth, or their operations over the bf16 peak, whichever is
longer; `perf/rooflines/paged_decode_heads.py`) over the seconds a
step's calls took in the trace (`_paged_decode_impl*`, every shape
together: the result's shape names the rows and the query heads, so
the 48-head and the 72-head layers' calls have two names, and the
share is of a step's calls and not of each kind).

The live pages are counted on the host where the model runner builds a
step's work lists, by the kernel's own rule, by kind of group
(`aphrodite:kv_pages_live_full_total`,
`aphrodite:kv_pages_live_window_total`, a step a
`aphrodite:decode_attn_steps_total`), over the window with the
profiler off; the trace is the 2 s after it under the same callers:
the same steady state, not the same seconds. A program without the
counters, a configuration without `num_attention_heads_per_layer`, or
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
    pages = [run.rate(f"aphrodite:kv_pages_live_{kind}_total")
             for kind in ("full", "window")]
    if not mine or not steps or None in pages or not sum(pages) or \
            run.peaks is None or \
            "num_attention_heads_per_layer" not in run.cell.config:
        return None
    # the rows of a call: the result's leading dimension, of the shape
    # that took most of the time
    most = max(mine, key=lambda name: mine[name][0])
    shape = re.search(r"\[(\d+),", most)
    count = cells.load_function(os.path.join(
        run.cell.root, "perf", "rooflines", "paged_decode_heads.py"),
        "count")
    moved, computed = count(run.cell.config, pages[0] / steps,
                            pages[1] / steps,
                            int(shape.group(1)) if shape else 0)
    least = max(moved / run.peaks["hbm_bytes_per_s"],
                computed / run.peaks["bf16_flops_per_s"])
    layers = run.cell.config["num_hidden_layers"]
    seconds = sum(s for s, _ in mine.values())
    calls = sum(c for _, c in mine.values())
    return least / (seconds / calls * layers) * 100.0
