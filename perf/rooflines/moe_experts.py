"""What the expert matmuls of one expert layer have to move and to
compute (`modeling/layers/fused_moe.py::FusedMoE._ragged_ffn`, the
three `ragged-dot*` custom calls a layer in the trace: gate, up and
down, each token-expert pair through its expert).

Bytes: the three matrices of every expert that has a pair (an expert
no token chose need not be read), plus a pair's row read on the way in
and written on the way out. The intermediate rows between the matmuls
are left out: a kernel could keep them on the chip. Operations: a
multiply and an add for each weight a pair meets,
2 x 3 x hidden x expert width.
"""
from __future__ import annotations

from typing import Tuple


def count(config: dict, pairs: float, experts_touched: float,
          bytes_per_value: int = 2) -> Tuple[float, float]:
    """`(bytes, operations)` of `pairs` token-expert pairs over
    `experts_touched` experts with a pair (both may be sums over
    layers and steps, or means of a call)."""
    hidden = config["hidden_size"]
    inter = config["moe_ffn_hidden_size"]
    expert = 3 * hidden * inter
    moved = (experts_touched * expert + 2 * pairs * hidden) * bytes_per_value
    return moved, 2.0 * expert * pairs
