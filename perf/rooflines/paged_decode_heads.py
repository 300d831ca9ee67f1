"""What the decode-attention kernel has to move and to compute in one
decode step of a model whose layers lie in page groups
(`common/config.py::PageGroups`) AND differ in their query heads
(`num_attention_heads_per_layer` over one count of KV heads, the
layers' kinds in `layer_types`): every layer's call of
`_paged_decode_impl*` over the pages its own group holds live.

A page id holds `page_size` tokens of `layers_per_group` layers, one of
each group's places, so a step's calls read, between them, every live
page of every group once for each place: (live pages of the full
groups + live pages of the window groups) x layers a group x the bytes
of a page in one layer. With two full and three window layers a group
is one layer, and the live pages of a kind's groups are the live pages
of that kind's layers. Beside them the query rows read and the output
rows written, a call a layer at that layer's own head count.
Operations: 4 x head size x the layer's query heads for every live
token of its call; the layers of a kind read equal pages, so a kind's
live tokens meet the mean of its layers' head counts.

`perf/rooflines/paged_decode_groups.py` reads `sliding_window_layout`
and one head count for all layers; without that key it takes all
layers for one group and counts every group's pages five times here.
"""
from __future__ import annotations

from math import gcd
from typing import Tuple

WINDOW = "sliding_attention"


def layers_per_group(config: dict) -> int:
    """Layers of one kind are dealt into groups of gcd(full layers,
    window layers); a model of one kind is one group of all of them."""
    kinds = config["layer_types"]
    windowed = sum(1 for kind in kinds if kind == WINDOW)
    return gcd(windowed, len(kinds) - windowed) or len(kinds)


def count(config: dict, live_full: float, live_window: float, rows: int,
          page_size: int = 16, bytes_per_value: int = 2
          ) -> Tuple[float, float]:
    """`(bytes, operations)` of one decode step's calls, all layers.
    `live_full` and `live_window`: the step's live pages summed over
    the full groups and over the window groups, `rows` the batch rows
    of a call."""
    heads = config["num_attention_heads_per_layer"]
    kinds = config["layer_types"]
    kv_heads, head = config["num_key_value_heads"], config["head_dim"]
    per = layers_per_group(config)
    token_bytes = 2 * kv_heads * head * bytes_per_value
    moved = (live_full + live_window) * page_size * per * token_bytes + \
        sum(2 * rows * h * head * bytes_per_value for h in heads)
    computed = 0.0
    for live, windowed in ((live_full, False), (live_window, True)):
        mine = [h for h, kind in zip(heads, kinds)
                if (kind == WINDOW) == windowed]
        if mine:
            computed += 4.0 * head * sum(mine) / len(mine) * \
                live * page_size * per
    return moved, computed
