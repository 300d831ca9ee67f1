"""What the decode-attention kernel has to move and to compute in one
decode step of a model whose layers are all of the page-group kind
"pooled" (`common/config.py::PageGroups`; EvaByte): a row's table is
`[summary pages ; window pages]`, the summary pages holding one pooled
key and value for each 16-token chunk of the windows behind, and every
layer's call of `_paged_decode_impl*` reads it whole.

All layers are one page group, so a page id holds `page_size` token
rows of every layer, and a step's calls read, between them, every live
page once for each layer: (live summary pages + live window pages) x
layers x the bytes of a page in one layer; beside them the query rows
read and the output rows written, a call a layer. Operations: 4 x head
size x query heads for every live key of a call, a pooled key counting
as one key (it is one row of the table).
"""
from __future__ import annotations

from typing import Tuple


def count(config: dict, live_summary: float, live_window: float,
          rows: int, page_size: int = 16, bytes_per_value: int = 2
          ) -> Tuple[float, float]:
    """`(bytes, operations)` of one decode step's calls, all layers.
    `live_summary` and `live_window`: the step's live summary pages
    and live pages of the rows' current windows, summed over the rows;
    `rows` the batch rows of a call."""
    heads, layers = config["num_attention_heads"], \
        config["num_hidden_layers"]
    head = config["hidden_size"] // heads
    kv_heads = config.get("num_key_value_heads", heads)
    live_keys = (live_summary + live_window) * page_size
    moved = layers * (live_keys * 2 * kv_heads * head * bytes_per_value +
                      2 * rows * heads * head * bytes_per_value)
    computed = layers * 4.0 * head * heads * live_keys
    return moved, computed
