"""What the expert matmuls of one expert layer have to move and to
compute where the layer HOLDS a share of the experts its router scores
(`modeling/layers/fused_moe.py::FusedMoE` with `routed_experts` over
`num_experts`: one chip's part of an expert-parallel layer; the three
`ragged-dot*` custom calls a layer in the trace: gate, up and down,
each held token-expert pair through its expert).

Bytes: the three matrices of every HELD expert that has a pair (an
expert no token chose need not be read, and one held elsewhere is not
here to be read), plus a held pair's row read on the way in and
written on the way out. The intermediate rows between the matmuls are
left out: a kernel could keep them on the chip. Operations: a multiply
and an add for each weight a HELD pair meets, 2 x 3 x hidden x expert
width. A pair whose expert is held elsewhere costs nothing by this
count: the time the kernels spend on its row shows as a lower share.

`perf/rooflines/moe_experts.py` reads another model's key names and
takes every layer for an expert layer; this one reads
`moe_intermediate_size` and counts the layers that `mlp_layer_types`
calls sparse.
"""
from __future__ import annotations

from typing import Tuple


def expert_layers(config: dict) -> int:
    """The layers whose MLP is the experts."""
    return sum(1 for kind in config["mlp_layer_types"] if kind == "sparse")


def count(config: dict, pairs_held: float, experts_touched: float,
          bytes_per_value: int = 2) -> Tuple[float, float]:
    """`(bytes, operations)` of `pairs_held` token-expert pairs that
    met a held expert, over `experts_touched` held experts with a pair
    (both may be sums over layers and steps, or means of a call)."""
    hidden = config["hidden_size"]
    expert = 3 * hidden * config["moe_intermediate_size"]
    moved = (experts_touched * expert +
             2 * pairs_held * hidden) * bytes_per_value
    return moved, 2.0 * expert * pairs_held
