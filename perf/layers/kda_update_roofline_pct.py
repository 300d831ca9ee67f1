"""Share of its roofline that the decode step's delta-rule update
reaches: the least time one KDA layer's call could take for the
window's decode rows (a row's matrices and convolution tail read and
written, its inputs and output, over the chip's memory bandwidth, or
its operations over the bf16 peak, whichever is longer;
`perf/rooflines/kda.py::update_count`) over the seconds a call took in
the trace. The calls are found by what the PROGRAM states: the
kernel's file holds the start of the name its calls bear in a constant
(`FILE`, `CONSTANT` below; read with `ast` by `perf/layer_ops.py`,
never imported). A program that states no such constant makes no such
call.

The rows are counted on the host where the model runner builds a
decode step (`aphrodite:kda_decode_rows_total`, a step a
`aphrodite:decode_attn_steps_total`), over the window with the
profiler off; the trace is the 2 s after it under the same callers:
the same steady state, not the same seconds. A program without the
counter or the constant, a configuration without `linear_attn_config`,
or a trace without the calls gives None."""
import os

from perf import cells, layer_ops

FILE = "aphrodite_tpu/ops/pallas/kda.py"
CONSTANT = "UPDATE_DEVICE_OP_PREFIXES"


def stated(root, constant):
    """The name prefixes the program's kernel file states under
    `constant`, or None."""
    try:
        names = layer_ops._constant(os.path.join(root, FILE), constant,
                                    None)
    except (OSError, ValueError, SyntaxError):
        return None
    if not isinstance(names, (tuple, list)) or not names or not all(
            isinstance(n, str) and n for n in names):
        return None
    return tuple(names)


def calls_of(run, constant):
    """`(seconds, calls)` of the trace's operations that bear one of
    the names stated under `constant`, or None."""
    names = stated(run.cell.root, constant)
    ops = (run.trace or {}).get("ops", {})
    mine = [] if names is None else [
        sc for op, sc in ops.items()
        if op.startswith(names) and sc[0] > 0 and sc[1] > 0]
    if not mine:
        return None
    return sum(s for s, _ in mine), sum(c for _, c in mine)


def share(run, least_of, found):
    """`least_of(count function) -> (bytes, operations)` of a call over
    the seconds a call took, in percent."""
    moved, computed = least_of
    least = max(moved / run.peaks["hbm_bytes_per_s"],
                computed / run.peaks["bf16_flops_per_s"])
    seconds, calls = found
    return least / (seconds / calls) * 100.0


def counts(run, name):
    return cells.load_function(os.path.join(
        run.cell.root, "perf", "rooflines", "kda.py"), name)


def read(run):
    found = calls_of(run, CONSTANT)
    rows = run.rate("aphrodite:kda_decode_rows_total")
    steps = run.rate("aphrodite:decode_attn_steps_total")
    if found is None or not rows or not steps or run.peaks is None or \
            "linear_attn_config" not in run.cell.config:
        return None
    return share(run, counts(run, "update_count")(
        run.cell.config, rows / steps), found)
