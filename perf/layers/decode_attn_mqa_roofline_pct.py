"""Share of its roofline that the paged decode-attention kernel
reaches in a model whose page-holding layers follow a rule of period
and offset (two attention layers of 28, one KV head under twenty query
heads: 4 KB page copies): the least time one call could take (its bytes
over the chip's memory bandwidth, or its operations over the bf16 peak,
whichever is longer; `perf/rooflines/paged_decode_mqa.py`) over the
seconds a call took in the trace (`_paged_decode_impl*`, every shape
together). The live K and V are the window's mean (the pool's size
times the mean of the `gpu_cache_usage_perc` gauge) and the trace is
the 2 s after the window, under the same callers: the same steady
state, not the same seconds. A configuration without the rule's keys,
a run without a trace or a trace without the calls gives None."""
import os
import re

from perf import cells
from perf.server import parse_kv_pool
from perf.stats import mean

KERNEL = "_paged_decode_impl"


def read(run):
    ops = (run.trace or {}).get("ops", {})
    mine = {name: sc for name, sc in ops.items()
            if name.startswith(KERNEL) and sc[1] > 0}
    pool, used = parse_kv_pool(run.log_setup), mean(
        run.gauge("aphrodite:gpu_cache_usage_perc"))
    if not mine or pool is None or not used or run.peaks is None or \
            "attn_layer_period" not in run.cell.config:
        return None
    seconds = sum(s for s, _ in mine.values())
    calls = sum(c for _, c in mine.values())
    # the rows of a call: the result's leading dimension, of the shape
    # that took most of the time
    most = max(mine, key=lambda name: mine[name][0])
    shape = re.search(r"\[(\d+),", most)
    count = cells.load_function(os.path.join(
        run.cell.root, "perf", "rooflines", "paged_decode_mqa.py"), "count")
    moved, computed = count(run.cell.config, pool[1] * 2 ** 30 * used,
                            int(shape.group(1)) if shape else 0)
    least = max(moved / run.peaks["hbm_bytes_per_s"],
                computed / run.peaks["bf16_flops_per_s"])
    return least / (seconds / calls) * 100.0
