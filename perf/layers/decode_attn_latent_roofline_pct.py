"""Share of its roofline that the decode-attention kernel reaches over
LATENT pages (multi-head latent attention, absorbed: one array of
pages a layer, one "head" of 640 lanes a token under 64 query rows,
the values its first 512 lanes): the least time a call could take
(every live page ONCE plus the rows, over the chip's memory bandwidth,
or its operations over the bf16 peak, whichever is longer;
`perf/rooflines/paged_decode_latent.py`) over the seconds a call took
in the trace. The calls are found by what the PROGRAM states: the
kernel's calls over latent pages bear a name of their own, the start
of which the kernel's file holds in a constant (`FILE`, `CONSTANT`
below; read with `ast` by `perf/layer_ops.py`, never imported). A
program that states no such constant makes no such call.

The live pages and the keys are counted on the host where the model
runner builds a step's work lists, by the kernel's own rule
(`aphrodite:decode_attn_pages_live_total`,
`aphrodite:mla_latent_tokens_read_total`, a step a
`aphrodite:decode_attn_steps_total`), over the window with the
profiler off; the trace is the 2 s after it under the same callers:
the same steady state, not the same seconds. A program without the
counters or the calls, a configuration without `kv_lora_rank`, or a
run without a trace gives None."""
import os
import re

from perf import cells, layer_ops

#: where the program states what a trace calls the kernel's latent calls
FILE = "aphrodite_tpu/ops/pallas/paged_attention.py"
CONSTANT = "LATENT_DEVICE_OP_PREFIXES"


def _stated(root):
    try:
        names = layer_ops._constant(os.path.join(root, FILE), CONSTANT,
                                    None)
    except (OSError, ValueError, SyntaxError):
        return None
    if not isinstance(names, (tuple, list)) or not names or not all(
            isinstance(n, str) and n for n in names):
        return None
    return tuple(names)


def read(run):
    names = _stated(run.cell.root)
    ops = (run.trace or {}).get("ops", {})
    mine = {} if names is None else {
        op: sc for op, sc in ops.items()
        if op.startswith(names) and sc[0] > 0 and sc[1] > 0}
    steps = run.rate("aphrodite:decode_attn_steps_total")
    pages = run.rate("aphrodite:decode_attn_pages_live_total")
    keys = run.rate("aphrodite:mla_latent_tokens_read_total")
    if not mine or not steps or not pages or not keys or \
            run.peaks is None or "kv_lora_rank" not in run.cell.config:
        return None
    # the rows of a call: the result's leading dimension less the
    # kernel's dummy row, of the shape that took most of the time
    most = max(mine, key=lambda name: mine[name][0])
    shape = re.search(r"\[(\d+),", most)
    count = cells.load_function(os.path.join(
        run.cell.root, "perf", "rooflines", "paged_decode_latent.py"),
        "count")
    moved, computed = count(run.cell.config, pages / steps, keys / steps,
                            max(int(shape.group(1)) - 1, 1) if shape else 0)
    least = max(moved / run.peaks["hbm_bytes_per_s"],
                computed / run.peaks["bf16_flops_per_s"])
    seconds = sum(s for s, _ in mine.values())
    calls = sum(c for _, c in mine.values())
    return least / (seconds / calls) * 100.0
