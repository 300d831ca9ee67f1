"""Share of its roofline that the prompt step's delta-rule chunk
kernel reaches: the least time one KDA layer's call could take for a
prompt step's live tokens (their q, k, v, g and output rows and a
row's matrices both ways over the chip's memory bandwidth, or the
products of the chunked form, the triangular solve counted as the
products it is, over the bf16 peak, whichever is longer;
`perf/rooflines/kda.py::chunk_count`) over the seconds a call took in
the trace. The calls are found by what the program states
(`CHUNK_DEVICE_OP_PREFIXES` of `aphrodite_tpu/ops/pallas/kda.py`, as
`kda_update_roofline_pct.py` finds the update's).

The live tokens and the chunks that hold one are counted on the host
where the model runner builds a prompt step
(`aphrodite:kda_prompt_tokens_total`, `aphrodite:
kda_prompt_chunks_total`, the rows that start at position 0
`aphrodite:ssm_state_resets_total`, a step a
`aphrodite:prefill_attn_steps_total`), over the window with the
profiler off; the trace is the 2 s after it under the same callers. A
program without the counters or the constant, a configuration without
`linear_attn_config`, or a trace without the calls gives None."""
import os

from perf import cells

CONSTANT = "CHUNK_DEVICE_OP_PREFIXES"


def read(run):
    update = cells.load_module(os.path.join(
        run.cell.root, "perf", "layers", "kda_update_roofline_pct.py"))
    found = update.calls_of(run, CONSTANT)
    tokens = run.rate("aphrodite:kda_prompt_tokens_total")
    chunks = run.rate("aphrodite:kda_prompt_chunks_total")
    steps = run.rate("aphrodite:prefill_attn_steps_total")
    resets = run.rate("aphrodite:ssm_state_resets_total")
    if found is None or not tokens or not chunks or not steps or \
            run.peaks is None or \
            "linear_attn_config" not in run.cell.config:
        return None
    # a prompt step's rows: those that start at position 0 are counted,
    # those that resume a slot are not, and a step has one at least (a
    # row's state both ways is a twentieth of a 1,024-token row's bytes)
    rows = max((resets or 0.0) / steps, 1.0)
    return update.share(run, update.counts(run, "chunk_count")(
        run.cell.config, tokens / steps, chunks / steps, rows), found)
