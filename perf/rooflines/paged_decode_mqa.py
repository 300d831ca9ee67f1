"""What one call of the paged decode-attention kernel has to move and
to compute (`ops/pallas/paged_attention.py`, `_paged_decode_impl*` in
the trace) in a model where only some layers hold pages, by a rule of
period and offset (`attn_layer_period`, `attn_layer_offset`: the other
layers keep recurrent state and no page): one attention layer's
attention of every row of a decode batch over its own context.

`perf/rooflines/paged_decode.py::count` divides the pool's live bytes
by `num_hidden_layers`; here they are divided by the layers that hold
pages. Bytes: the keys and values of the pages that are live in one
such layer, plus the query rows read and the output rows written.
Operations: 4 x head size x query heads x live tokens. With one KV
head under twenty query heads a token's K and V are 512 B and its
operations 10,240: the call is still bound by bytes on a v5e (240
operations a byte at the peaks), but by a factor of 12 and not of 200.
"""
from __future__ import annotations

from typing import Tuple


def page_layers(config: dict) -> int:
    """The layers that hold pages: those whose index is
    `attn_layer_offset` modulo `attn_layer_period`."""
    period, offset = config["attn_layer_period"], config["attn_layer_offset"]
    return sum(1 for l in range(config["num_hidden_layers"])
               if l % period == offset)


def count(config: dict, kv_live_bytes: float, rows: int,
          bytes_per_value: int = 2) -> Tuple[float, float]:
    """`(bytes, operations)` of one call. `kv_live_bytes` is the K and V
    held live in the whole pool (all page-holding layers), `rows` the
    batch rows of the call, `bytes_per_value` the width of a cached
    value and of a query or output value (2: bfloat16)."""
    heads = config["num_attention_heads"]
    kv_heads = config.get("num_key_value_heads", heads)
    head = config.get("head_dim") or config["hidden_size"] // heads
    kv_bytes = kv_live_bytes / page_layers(config)
    live_tokens = kv_bytes / (2 * kv_heads * head * bytes_per_value)
    rows_bytes = 2 * rows * heads * head * bytes_per_value
    return kv_bytes + rows_bytes, 4.0 * head * heads * live_tokens
