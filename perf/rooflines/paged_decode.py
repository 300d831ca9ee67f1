"""What one call of the paged decode-attention kernel has to move and
to compute (`ops/pallas/paged_attention.py`, `_paged_decode_impl*` in
the trace): one layer's attention of every row of a decode batch over
its own context.

Bytes: the keys and values of the pages that are live in one layer
(every live page is the context of some running row, and the kernel
reads a row's pages whole), plus the query rows read and the output
rows written. Operations: a multiply and an add for each of the score
and the weighted sum, for every query head, every dimension of a head
and every live token: 4 x head size x query heads x live tokens.
"""
from __future__ import annotations

from typing import Tuple


def count(config: dict, kv_live_bytes: float, rows: int,
          bytes_per_value: int = 2) -> Tuple[float, float]:
    """`(bytes, operations)` of one call. `kv_live_bytes` is the K and V
    held live in the whole pool (all layers), `rows` the batch rows of
    the call, `bytes_per_value` the width of a cached value and of a
    query or output value (2: bfloat16)."""
    layers = config["num_hidden_layers"]
    heads = config["num_attention_heads"]
    kv_heads = config.get("num_key_value_heads", heads)
    head = config.get("head_dim") or config["hidden_size"] // heads
    kv_bytes = kv_live_bytes / layers
    live_tokens = kv_bytes / (2 * kv_heads * head * bytes_per_value)
    rows_bytes = 2 * rows * heads * head * bytes_per_value
    return kv_bytes + rows_bytes, 4.0 * head * heads * live_tokens
