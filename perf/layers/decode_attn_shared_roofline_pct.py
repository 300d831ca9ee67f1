"""Share of its roofline that the paged decode-attention kernel
reaches in a model whose page groups are read by more layers than
write them: the least time a decode step's calls could take between
them (each group's live pages once for every layer that reads them,
plus the rows, over the chip's memory bandwidth, or their operations
over the bf16 peak, whichever is longer;
`perf/rooflines/paged_decode_shared.py`) over the seconds a step's
calls took in the trace (`_paged_decode_impl*`, every shape together:
the full layer's, the window layers' and the cross layers' calls have
one name, so the share is of a step's calls and not of each kind).

The page reads are counted on the host where the model runner builds a
step's work lists, by the kernel's own rule
(`aphrodite:kv_page_reads_shared_total`, a step a
`aphrodite:decode_attn_steps_total`), over the window with the
profiler off; the trace is the 2 s after it under the same callers:
the same steady state, not the same seconds. A program without the
counter gives None."""
import os
import re

from perf import cells

KERNEL = "_paged_decode_impl"


def read(run):
    ops = (run.trace or {}).get("ops", {})
    mine = {name: sc for name, sc in ops.items()
            if name.startswith(KERNEL) and sc[1] > 0}
    steps = run.rate("aphrodite:decode_attn_steps_total")
    reads = run.rate("aphrodite:kv_page_reads_shared_total")
    if not mine or not steps or not reads or run.peaks is None:
        return None
    # the rows of a call: the result's leading dimension, of the shape
    # that took most of the time
    most = max(mine, key=lambda name: mine[name][0])
    shape = re.search(r"\[(\d+),", most)
    roofline = cells.load_module(os.path.join(
        run.cell.root, "perf", "rooflines", "paged_decode_shared.py"))
    moved, computed = roofline.count(run.cell.config, reads / steps,
                                     int(shape.group(1)) if shape else 0)
    least = max(moved / run.peaks["hbm_bytes_per_s"],
                computed / run.peaks["bf16_flops_per_s"])
    seconds = sum(s for s, _ in mine.values())
    calls = sum(c for _, c in mine.values())
    return least / (seconds / calls *
                    roofline.attention_layers(run.cell.config)) * 100.0
