"""What the decode-attention kernel has to move and to compute in one
decode step of a model whose layers lie in page groups
(`common/config.py::PageGroups`): every layer's call of
`_paged_decode_impl*` over the pages its own group holds live.

A page id holds `page_size` tokens of `layers_per_group` layers, one of
each group's places, so a step's calls read, between them, every live
page of every group once for each place: (live pages of the full
groups + live pages of the window groups) x layers a group x the bytes
of a page in one layer. Beside them the query rows read and the output
rows written, a call a layer. Operations: 4 x head size x query heads
for every live token of every call.
"""
from __future__ import annotations

from typing import Tuple


def layers_per_group(config: dict) -> int:
    """Layers of one kind are dealt into groups of gcd(full layers,
    window layers); a model of one kind is one group of all of them."""
    from math import gcd
    layout = config.get("sliding_window_layout")
    layers = config["num_hidden_layers"]
    if not layout:
        return layers
    windowed = sum(1 for x in layout if x)
    return gcd(windowed, layers - windowed) or layers


def count(config: dict, live_pages: float, rows: int, page_size: int = 16,
          bytes_per_value: int = 2) -> Tuple[float, float]:
    """`(bytes, operations)` of one decode step's calls, all layers.
    `live_pages`: the step's live pages summed over the page groups,
    `rows` the batch rows of a call."""
    heads = config["num_attention_heads"]
    kv_heads = config.get("num_key_value_heads", heads)
    head = config.get("head_dim") or config["hidden_size"] // heads
    layers = config["num_hidden_layers"]
    live_tokens = live_pages * page_size * layers_per_group(config)
    kv_bytes = live_tokens * 2 * kv_heads * head * bytes_per_value
    rows_bytes = layers * 2 * rows * heads * head * bytes_per_value
    return kv_bytes + rows_bytes, 4.0 * head * heads * live_tokens
