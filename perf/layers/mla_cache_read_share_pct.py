"""Share of a decode step's bytes that is the latent cache's: the
latent rows the step's absorbed attention reads
(`aphrodite:mla_latent_tokens_read_total` x the bytes a token takes of
the pool, all layers: the gauge `aphrodite:kv_cache_bytes_per_token`)
over those plus the weights the step touches by the configuration's
own count (`perf/rooflines/paged_decode_latent.py::
step_weight_bytes`: attention, dense MLP, routers, shared experts, the
held experts with a pair as `aphrodite:moe_decode_experts_touched_total`
counts them, the head), both a decode step
(`aphrodite:decode_attn_steps_total`). It says how much of a decode
step is the mechanism's: where it is small the cell measures the
weights. A program without the counters or the gauge, or a
configuration without `kv_lora_rank`, gives None."""
import os

from perf import cells
from perf.stats import mean


def read(run):
    steps = run.rate("aphrodite:decode_attn_steps_total")
    tokens = run.rate("aphrodite:mla_latent_tokens_read_total")
    touched = run.rate("aphrodite:moe_decode_experts_touched_total")
    per_token = mean(run.gauge("aphrodite:kv_cache_bytes_per_token"))
    if not steps or not tokens or touched is None or not per_token or \
            "kv_lora_rank" not in run.cell.config:
        return None
    weights = cells.load_function(os.path.join(
        run.cell.root, "perf", "rooflines", "paged_decode_latent.py"),
        "step_weight_bytes")(run.cell.config, touched / steps)
    latent = tokens / steps * per_token
    return latent / (latent + weights) * 100.0
