"""Share of its roofline that the prompt chunk's selective scan
reaches: the least time one state layer's call could take for the
window's prompt tokens (`u` and `delta` in and `y` out for every
token, a row's state in and out, over the chip's memory bandwidth, or
its operations over the bf16 peak, whichever is longer;
`perf/rooflines/ssm_scan.py`) over the seconds a call took in the
trace (`_ssm_scan_impl*`, every shape together).

Prompt tokens and the rows that started at position 0 are counted on
the host where the model runner builds a prompt step
(`aphrodite:ssm_prefill_tokens_total`,
`aphrodite:ssm_state_resets_total`: this cell's prompts come in one
chunk, so a row is a reset), over the window with the profiler off,
and a call's share of them is a prompt step's: the window's tokens
over its prompt steps (`aphrodite:sampler_plans_total` less the decode
steps). The trace is the 2 s after the window under the same callers:
the same steady state, not the same seconds. A program without the
counters, or a trace without the calls, gives None."""
import os

from perf import cells

KERNEL = "_ssm_scan_impl"


def read(run):
    ops = (run.trace or {}).get("ops", {})
    mine = {name: sc for name, sc in ops.items()
            if name.startswith(KERNEL) and sc[1] > 0}
    tokens = run.rate("aphrodite:ssm_prefill_tokens_total")
    rows = run.rate("aphrodite:ssm_state_resets_total")
    plans = run.rate("aphrodite:sampler_plans_total")
    decodes = run.rate("aphrodite:decode_attn_steps_total")
    if not mine or not tokens or rows is None or None in (plans, decodes) \
            or plans <= decodes or run.peaks is None:
        return None
    prompt_steps = plans - decodes
    count = cells.load_function(os.path.join(
        run.cell.root, "perf", "rooflines", "ssm_scan.py"), "scan_count")
    moved, computed = count(run.cell.config, tokens / prompt_steps,
                            rows / prompt_steps)
    least = max(moved / run.peaks["hbm_bytes_per_s"],
                computed / run.peaks["bf16_flops_per_s"])
    seconds = sum(s for s, _ in mine.values())
    calls = sum(c for _, c in mine.values())
    return least / (seconds / calls) * 100.0
