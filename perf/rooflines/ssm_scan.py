"""What the two selective-scan kernels have to move and to compute
(`ops/pallas/ssm_scan.py`; `_ssm_update_impl` and `_ssm_scan_impl` in
the trace), one state layer's call each.

The decode step's update, a row: its state read and written (d_state x
d_inner float32, twice), the convolution's tail read and written
(d_conv - 1 rows of d_inner in the model's type, twice), the
convolution's new input in (the model's type), `u` and `delta` in and
`y` out (float32), B and C in; a call also reads A and D once. The
chunk scan, a token: `u` and `delta` in and `y` out (float32 rows of
d_inner), B and C in; a row's state is read and written once a chunk,
which a call's tokens carry (`rows`). Operations, a token and a state
element (d_state x d_inner of them): the exponential, three multiplies
and an add for the state, a multiply and an add for the output; seven,
the exponential counted as one.
"""
from __future__ import annotations

from typing import Tuple

OPS_AN_ELEMENT = 7.0


def _sizes(config: dict) -> Tuple[int, int, int]:
    return (config.get("mamba_expand", 2) * config["hidden_size"],
            config.get("mamba_d_state", 16), config.get("mamba_d_conv", 4))


def update_count(config: dict, rows: float,
                 bytes_per_value: int = 2) -> Tuple[float, float]:
    """`(bytes, operations)` of one layer's `_ssm_update_impl` call over
    `rows` decode rows."""
    d_inner, n, taps = _sizes(config)
    row = 2 * n * d_inner * 4 + 2 * (taps - 1) * d_inner * bytes_per_value \
        + d_inner * bytes_per_value + 3 * d_inner * 4 + 2 * n * 4
    return rows * row + (n + 1) * d_inner * 4, \
        OPS_AN_ELEMENT * rows * n * d_inner


def scan_count(config: dict, tokens: float,
               rows: float) -> Tuple[float, float]:
    """`(bytes, operations)` of one layer's `_ssm_scan_impl` call over
    `tokens` prompt tokens of `rows` prompt rows."""
    d_inner, n, _ = _sizes(config)
    moved = tokens * (3 * d_inner * 4 + 2 * n * 4) + \
        rows * 2 * n * d_inner * 4 + (n + 1) * d_inner * 4
    return moved, OPS_AN_ELEMENT * tokens * n * d_inner
