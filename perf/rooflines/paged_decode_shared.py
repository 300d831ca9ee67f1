"""What the decode-attention kernel has to move and to compute in one
decode step of a model in which a page group's pages are read by more
layers than write them (`common/config.py::PageGroups`: a layer that
"reads layer k's pages"): every attention layer's call of
`_paged_decode_impl*` over the pages of the group it reads.

The program counts, a decode step, each group's live pages times the
layers that read that group (`aphrodite:kv_page_reads_shared_total`):
that many page reads, each the K and V of `page_size` tokens of ONE
layer's heads. Beside them the query rows read and the output rows
written, a call an attention layer. Operations: 4 x head size x query
heads for every live token of every call.

The heads are the model's own: a differential pair held as one head of
twice the size is the same bytes, and the zero halves of its packed
queries are no operations the algorithm needs.
"""
from __future__ import annotations

from typing import Tuple


def attention_layers(config: dict) -> int:
    """The layers that call the kernel: all of them, but for a model
    whose config states a layer-kind rule (`mb_per_layer`: every other
    layer is a state-space layer or a gated unit)."""
    layers = config["num_hidden_layers"]
    return layers // 2 if config.get("mb_per_layer") else layers


def count(config: dict, page_reads: float, rows: int, page_size: int = 16,
          bytes_per_value: int = 2) -> Tuple[float, float]:
    """`(bytes, operations)` of one decode step's calls, all attention
    layers. `page_reads`: the step's live pages, each counted once for
    every layer that reads it; `rows` the batch rows of a call."""
    heads = config["num_attention_heads"]
    kv_heads = config.get("num_key_value_heads", heads)
    head = config.get("head_dim") or config["hidden_size"] // heads
    live_tokens = page_reads * page_size
    kv_bytes = live_tokens * 2 * kv_heads * head * bytes_per_value
    rows_bytes = attention_layers(config) * 2 * rows * heads * head * \
        bytes_per_value
    return kv_bytes + rows_bytes, 4.0 * head * heads * live_tokens
