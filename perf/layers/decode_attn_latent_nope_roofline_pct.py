"""Share of its roofline that the decode-attention kernel reaches over
the LATENT pages of a model whose latent attention has no rotary
embedding and whose other layers hold no pages (Kimi Linear's two MLA
layers among eight): the least time a call could take (every live page
ONCE plus the rows, over the chip's memory bandwidth, or its
operations over the bf16 peak, whichever is longer;
`perf/rooflines/kda.py::latent_count`) over the seconds a call took in
the trace. As `decode_attn_latent_roofline_pct.py`, whose docstring
says how the calls are found (`LATENT_DEVICE_OP_PREFIXES` of
`aphrodite_tpu/ops/pallas/paged_attention.py`) and what is counted on
the host; that reader's count takes a page's row from `head_dim`,
which this family publishes as 72 (the hidden size over the heads)
where the row is `kv_lora_rank + qk_rope_head_dim` = 576 lanes padded
to 640, so this cell has a count of its own and joins neither that
metric nor `mla_cache_read_share_pct.batch`. A program without the
counters or the calls, a configuration without `linear_attn_config`,
or a run without a trace gives None."""
import os
import re

from perf import cells


def read(run):
    other = cells.load_module(os.path.join(
        run.cell.root, "perf", "layers",
        "decode_attn_latent_roofline_pct.py"))
    names = other._stated(run.cell.root)
    ops = (run.trace or {}).get("ops", {})
    mine = {} if names is None else {
        op: sc for op, sc in ops.items()
        if op.startswith(names) and sc[0] > 0 and sc[1] > 0}
    steps = run.rate("aphrodite:decode_attn_steps_total")
    pages = run.rate("aphrodite:decode_attn_pages_live_total")
    keys = run.rate("aphrodite:mla_latent_tokens_read_total")
    if not mine or not steps or not pages or not keys or \
            run.peaks is None or \
            "linear_attn_config" not in run.cell.config:
        return None
    # the rows of a call: the result's leading dimension less the
    # kernel's dummy row, of the shape that took most of the time
    most = max(mine, key=lambda name: mine[name][0])
    shape = re.search(r"\[(\d+),", most)
    moved, computed = cells.load_function(os.path.join(
        run.cell.root, "perf", "rooflines", "kda.py"), "latent_count")(
            run.cell.config, pages / steps, keys / steps,
            max(int(shape.group(1)) - 1, 1) if shape else 0)
    least = max(moved / run.peaks["hbm_bytes_per_s"],
                computed / run.peaks["bf16_flops_per_s"])
    seconds = sum(s for s, _ in mine.values())
    calls = sum(c for _, c in mine.values())
    return least / (seconds / calls) * 100.0
