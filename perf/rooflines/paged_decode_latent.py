"""What one call of the decode-attention kernel over LATENT pages has
to move and to compute (`ops/pallas/paged_attention.py` with `latent`,
`paged-decode-latent*` in the trace; `modeling/layers/mla.py`: the
absorbed decode step of multi-head latent attention), and what a
decode step reads of the weights beside it.

A layer's pages are ONE array: a token's row is `[c | k_r]`
(`kv_lora_rank` + `qk_rope_head_dim` lanes, padded to whole lane
tiles), its key under every query head, and its value is the row's
first `kv_lora_rank` lanes. Bytes of a call: every live page ONCE (the
values are not a second array and not a second copy), plus the query
rows read, the new rows read and written, and the output rows written.
Operations: a multiply and an add for each lane of a key under each
query head (the padded row, as the kernel multiplies it) and for each
lane of a value: 2 x heads x keys x (lanes + value lanes).
"""
from __future__ import annotations

from typing import Tuple

LANE_TILE = 128


def lanes(config: dict) -> int:
    """A row of a latent page: `head_dim` up to whole lane tiles."""
    return -(-config["head_dim"] // LANE_TILE) * LANE_TILE


def count(config: dict, live_pages: float, keys: float, rows: int,
          page_size: int = 16, bytes_per_value: int = 2
          ) -> Tuple[float, float]:
    """`(bytes, operations)` of one call (one layer). `live_pages`: the
    pages below the rows' context lengths, summed over the rows;
    `keys`: the rows' context lengths summed; `rows` the call's batch
    rows."""
    heads, row = config["num_attention_heads"], lanes(config)
    values = config["kv_lora_rank"]
    moved = (live_pages * page_size * row +
             rows * (heads * row + 2 * row + heads * values)) * \
        bytes_per_value
    return moved, 2.0 * heads * keys * (row + values)


def step_weight_bytes(config: dict, experts_touched: float,
                      bytes_per_value: int = 2) -> float:
    """Bytes of the weights a decode step touches, by the
    configuration's own count: every layer's attention projections,
    the dense MLPs, each expert layer's router, shared experts and the
    `experts_touched` held experts with a pair (summed over the expert
    layers), and the head's held rows."""
    hidden, heads = config["hidden_size"], config["num_attention_heads"]
    latent, rope = config["kv_lora_rank"], config["qk_rope_head_dim"]
    nope, v_dim = config["qk_nope_head_dim"], config["v_head_dim"]
    layers, dense = config["num_hidden_layers"], \
        config["first_k_dense_replace"]
    expert = 3 * hidden * config["moe_intermediate_size"]
    routed = config.get("num_routed_experts") or config["num_experts"]
    attention = hidden * heads * (nope + rope) + hidden * (latent + rope) \
        + latent * heads * (nope + v_dim) + heads * v_dim * hidden
    return bytes_per_value * (
        layers * attention + dense * 3 * hidden * config["intermediate_size"]
        + (layers - dense) * (hidden * routed +
                              config["num_shared_experts"] * expert)
        + experts_touched * expert + config["vocab_size"] * hidden)
