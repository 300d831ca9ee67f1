"""Selective scan (Mamba, arXiv:2312.00752) over state slots: the
recurrence of a state-space layer,

    s_t[n, c] = exp(delta_t[c] A[n, c]) s_{t-1}[n, c]
                + delta_t[c] B_t[n] u_t[c]
    y_t[c]    = sum_n C_t[n] s_t[n, c] + D[c] u_t[c]

with the state `s` of every sequence in its STATE SLOT: row `slot` of
every layer of the model's one array `[layers, slots + 1, n,
channels]`, float32, state-major so that the channels lie on the lanes
(`common/config.py::StateSpec`; the last slot is the pad rows'
scratch). A call names its layer: its blocks are `(layer, slot)` of the
whole array, which goes into the first layer's call and out of the
last one's in place, never sliced, copied or re-laid-out between them.
The layer reaches the kernel as a prefetched scalar beside the slot
ids, not as a static argument, so that a model's layers share one
trace and one lowered kernel (26 of each cost a 512-token prompt
program 8 s more to trace and lower).

Two entry points, each a dispatcher over a Pallas kernel (one TPU
chip) and a `jax.numpy` side (the CPU, a mesh):

- `selective_scan`: a prompt chunk. Channels in blocks, time inside the
  kernel with the state in VMEM: a time block's inputs stream through,
  the state never leaves the chip between the chunk's first token and
  its last, and nothing of size tokens x channels x n is ever in HBM
  (an associative scan over 2,048 tokens of 5,120 channels and 16
  states would hold 671 MB a sequence a layer). The initial state is
  the slot's (zeros where `fresh`: a sequence's first chunk), the final
  state goes back to it in place.
- `selective_update`: a decode step. One token a row; a row's state is
  read by its slot id (scalar prefetch), updated and written in place
  (`input_output_aliases`), and the tail of the layer's causal
  convolution, the same slot of a second array `[layers, slots + 1,
  kept, channels]` in the model's type, moves on by the row's new
  input with it: the last `kept` inputs, of which the convolution
  reads `d_conv - 1` (`StateSpec.allocated`: `kept` is the power of
  two the device tiles without padding, `d_conv` for Mamba's four
  taps; the oldest row is carried along and read by nothing). A row is
  a grid cell, but the rows' inputs and outputs reach the kernel eight
  rows a block (`_row_blocks`), never as `[rows, 1, channels]`.

A position where `delta` is 0 leaves the state as it is (exp(0) = 1,
no input), which is how a chunk's padding is passed over: the caller
zeroes `delta` there.

**The slot conventions**, stated here once for every kernel over state
slots (`ops/pallas/kda.py`'s two point here): (1) the layer is a
prefetched scalar beside the slot ids, never a static argument, so a
model's state layers share one trace; (2) a call's blocks are `(layer,
slot)` of the model's WHOLE array, which is aliased to the result
(`input_output_aliases`) and updated in place, and no other slot or
layer is touched; (3) a decode step's rows reach the kernel eight a
block (`_row_blocks`), a row a grid cell; (4) pad rows hold the last
slot, the scratch one; (5) a fresh row's slot is never read into the
state (a select on `fresh`, not a multiply).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from aphrodite_tpu.common.utils import note_kernel_path

#: channels a grid cell of the chunk scan carries: the state of a cell
#: is `n x CHANNEL_BLOCK` float32, 8 vector registers at n = 16
CHANNEL_BLOCK = 512
#: tokens a grid cell streams through; the steps of a `TIME_UNROLL`
#: are unrolled, so that B_t and C_t are static columns of a tile
TIME_BLOCK = 256
TIME_UNROLL = 128


# ---------------------------------------------------------------------
# jax.numpy side
# ---------------------------------------------------------------------

def ssm_scan_ref(u, delta, b, c, a, d, state, slots, fresh, layer: int
                 ) -> Tuple[jax.Array, jax.Array]:
    """`selective_scan` in plain `jax.numpy`: a sequential scan over
    time, the state `[rows, n, channels]` carried."""
    s0 = jnp.where(fresh[:, None, None] != 0, 0.0, state[layer, slots])

    def step(s, xs):
        u_t, dl_t, b_t, c_t = xs            # [rows, ch] x2, [rows, n] x2
        s = jnp.exp(dl_t[:, None, :] * a[None]) * s + \
            (dl_t * u_t)[:, None, :] * b_t[:, :, None]
        y = jnp.sum(s * c_t[:, :, None], axis=1) + d[None] * u_t
        return s, y

    s, y = jax.lax.scan(step, s0, tuple(
        jnp.moveaxis(x, 1, 0) for x in (u, delta, b, c)))
    return jnp.moveaxis(y, 0, 1), state.at[layer, slots].set(s)


def ssm_update_ref(x, u, delta, b, c, a, d, state, tail, slots,
                   layer: int
                   ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """`selective_update` in plain `jax.numpy`."""
    s = jnp.exp(delta[:, None, :] * a[None]) * state[layer, slots] + \
        (delta * u)[:, None, :] * b[:, :, None]
    y = jnp.sum(s * c[:, :, None], axis=1) + d[None] * u
    moved = jnp.concatenate(
        [tail[layer, slots][:, 1:], x[:, None, :].astype(tail.dtype)],
        axis=1)
    return y, state.at[layer, slots].set(s), \
        tail.at[layer, slots].set(moved)


# ---------------------------------------------------------------------
# the chunk scan
# ---------------------------------------------------------------------

def _scan_kernel(layer_ref, slots_ref, fresh_ref, u_ref, dl_ref, bt_ref,
                 ct_ref, a_ref, d_ref, s_in_ref, y_ref, s_out_ref, s_scr,
                 *, unroll: int):
    row, t = pl.program_id(0), pl.program_id(2)

    @pl.when(t == 0)
    def _():
        # (a select: what a fresh row's slot holds, NaN or not, is
        # never read into the state)
        s_scr[...] = jnp.where(fresh_ref[row] != 0, 0.0, s_in_ref[0])

    a, d = a_ref[...], d_ref[...]               # [n, ch], [1, ch]
    steps = u_ref.shape[1]

    def block(k, s):
        t0 = pl.multiple_of(k * unroll, unroll)
        bt = bt_ref[0, :, pl.ds(t0, unroll)]    # [n, unroll]
        ct = ct_ref[0, :, pl.ds(t0, unroll)]
        for g in range(unroll // 8):
            at = pl.ds(t0 + g * 8, 8)
            u8, dl8 = u_ref[0, at, :], dl_ref[0, at, :]   # [8, ch]
            rows = []
            for j in range(8):
                i = g * 8 + j
                u_t, dl_t = u8[j:j + 1], dl8[j:j + 1]     # [1, ch]
                s = jnp.exp(dl_t * a) * s + (dl_t * u_t) * bt[:, i:i + 1]
                rows.append(jnp.sum(s * ct[:, i:i + 1], axis=0,
                                    keepdims=True) + d * u_t)
            y_ref[0, at, :] = jnp.concatenate(rows, axis=0)
        return s

    s_scr[...] = jax.lax.fori_loop(0, steps // unroll, block, s_scr[...])

    @pl.when(t == pl.num_programs(2) - 1)
    def _():
        s_out_ref[0] = s_scr[...]


def _scan_blocks(channels: int, tokens: int) -> Tuple[int, int, int]:
    """(channels a cell, tokens a cell, steps unrolled): a whole number
    of time blocks, the block, else the unrolled run, else (a chunk
    under one run) the chunk itself."""
    ch = min(CHANNEL_BLOCK, channels)
    tb = next((t for t in (TIME_BLOCK, TIME_UNROLL) if tokens % t == 0),
              tokens)
    unroll = min(TIME_UNROLL, tb)
    if channels % ch or tokens % tb or tb % unroll or unroll % 8:
        raise ValueError(
            f"ssm scan: {channels} channels in blocks of {ch}, {tokens} "
            f"tokens in blocks of {tb} unrolled by {unroll}")
    return ch, tb, unroll


def _scan_cost(rows: int, tokens: int, n: int, channels: int
               ) -> pl.CostEstimate:
    """What a chunk's call moves and computes, for the compiler that
    schedules the step around it: `u`, `delta` in and `y` out, B and C,
    a row's state in and out; a state element's step is six operations
    and an exponential. The scheduler places the step's async copies
    by it: with no estimate Jamba's prompt step read 1.3% longer on
    the chip and its decode step 2% (`PERF.md` section 6, PR 42)."""
    cells = rows * tokens * n * channels
    return pl.CostEstimate(
        flops=6 * cells, transcendentals=cells,
        bytes_accessed=4 * (rows * tokens * (3 * channels + 2 * n)
                            + 2 * rows * n * channels))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _ssm_scan_impl(u, delta, bt, ct, a, d, state, layer, slots, fresh, *,
                   interpret: bool = False):
    """`u`, `delta` `[rows, tokens, channels]`; `bt`, `ct` `[rows, n,
    tokens]` (time on the lanes: a step's B and C are a column);
    `a` `[n, channels]`, `d` `[1, channels]`, all float32; `state`
    `[layers, slots + 1, n, channels]`, of which this is `layer[0]`."""
    rows, tokens, channels = u.shape
    n = a.shape[0]
    ch, tb, unroll = _scan_blocks(channels, tokens)

    def seq(r, c, t, *_):
        return (r, t, c)

    def coeff(r, c, t, *_):
        return (r, 0, t)

    def slot(r, c, t, layer_ref, slots_ref, fresh_ref):
        return (layer_ref[0], slots_ref[r], 0, c)

    y, state = pl.pallas_call(
        functools.partial(_scan_kernel, unroll=unroll),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(rows, channels // ch, tokens // tb),
            in_specs=[
                pl.BlockSpec((1, tb, ch), seq),
                pl.BlockSpec((1, tb, ch), seq),
                pl.BlockSpec((1, n, tb), coeff),
                pl.BlockSpec((1, n, tb), coeff),
                pl.BlockSpec((n, ch), lambda r, c, t, *_: (0, c)),
                pl.BlockSpec((1, ch), lambda r, c, t, *_: (0, c)),
                pl.BlockSpec((None, 1, n, ch), slot),
            ],
            out_specs=[pl.BlockSpec((1, tb, ch), seq),
                       pl.BlockSpec((None, 1, n, ch), slot)],
            scratch_shapes=[pltpu.VMEM((n, ch), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct(u.shape, jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # (the flattened inputs count the three scalar-prefetch arrays)
        input_output_aliases={9: 1},
        cost_estimate=_scan_cost(rows, tokens, n, channels),
        interpret=interpret,
    )(layer, slots, fresh, u, delta, bt, ct, a, d, state)
    return y, state


def selective_scan(u, delta, b, c, a, d, state, slots, fresh, layer: int
                   ) -> Tuple[jax.Array, jax.Array]:
    """A prompt chunk's scan. `u`, `delta` `[rows, tokens, channels]`
    and `b`, `c` `[rows, tokens, n]`, float32; `a` `[n, channels]`
    (negative), `d` `[channels]`; `state` `[layers, slots + 1, n,
    channels]` and `layer` which of them this is, `slots` `[rows]`
    each row's slot, `fresh` `[rows]` non-zero where the row
    starts from zeros. Returns `y` `[rows, tokens, channels]` and the
    state array with each row's slot of the layer at its last token."""
    if jax.default_backend() == "tpu":
        note_kernel_path("ssm_scan", "pallas",
                         "_ssm_scan_impl, state in VMEM over the chunk")
        tokens = u.shape[1]
        # (a chunk shorter than a lane tile is padded to one: B and C
        # have time on the lanes, and a step with delta 0 is passed
        # over)
        short = max(0, TIME_UNROLL - tokens)
        if short:
            u, delta, b, c = (jnp.pad(x, ((0, 0), (0, short), (0, 0)))
                              for x in (u, delta, b, c))
        y, state = _ssm_scan_impl(
            u, delta, jnp.swapaxes(b, 1, 2), jnp.swapaxes(c, 1, 2), a,
            d[None], state, jnp.full((1,), layer, jnp.int32),
            slots.astype(jnp.int32), fresh.astype(jnp.int32))
        return y[:, :tokens], state
    note_kernel_path("ssm_scan", "reference",
                     f"jnp scan over time: backend={jax.default_backend()}")
    return ssm_scan_ref(u, delta, b, c, a, d, state, slots, fresh, layer)


# ---------------------------------------------------------------------
# the decode step's update
# ---------------------------------------------------------------------

def _update_kernel(layer_ref, slots_ref, x_ref, u_ref, dl_ref, bt_ref,
                   ct_ref, a_ref, d_ref, s_in_ref, tail_in_ref, y_ref,
                   s_out_ref, tail_out_ref):
    row = pl.program_id(0)
    # this row of the block of rows that the step before fetched too
    mine = pl.ds(row % x_ref.shape[1], 1)
    u, dl = u_ref[0, mine, :], dl_ref[0, mine, :]           # [1, ch]
    # this row's B and C: column `row` of the step's `[n, rows]`
    lane = jax.lax.broadcasted_iota(jnp.int32, bt_ref.shape, 1)
    col = lane == row
    b = jnp.sum(jnp.where(col, bt_ref[...], 0.0), axis=1, keepdims=True)
    c = jnp.sum(jnp.where(col, ct_ref[...], 0.0), axis=1, keepdims=True)
    s = jnp.exp(dl * a_ref[...]) * s_in_ref[0] + (dl * u) * b
    s_out_ref[0] = s
    y_ref[0, mine, :] = jnp.sum(s * c, axis=0, keepdims=True) + \
        d_ref[...] * u
    kept = tail_in_ref.shape[1]
    for k in range(kept - 1):
        tail_out_ref[0, k:k + 1, :] = tail_in_ref[0, k + 1:k + 2, :]
    tail_out_ref[0, kept - 1:kept, :] = x_ref[0, mine, :].astype(
        tail_out_ref.dtype)


def _update_cost(rows: int, n: int, channels: int, kept: int, tail_dtype
                 ) -> pl.CostEstimate:
    """What a decode step's call moves and computes (`_scan_cost`): a
    row's state and tail in and out, `x`, `u`, `delta` in and `y`
    out."""
    cells = rows * n * channels
    return pl.CostEstimate(
        flops=6 * cells, transcendentals=cells,
        bytes_accessed=rows * channels * (
            2 * (4 * n + kept * jnp.dtype(tail_dtype).itemsize) + 4 * 4))


def _row_blocks(x: jax.Array) -> jax.Array:
    """`x` `[rows, channels]` as the update kernel takes the rows'
    inputs and gives their outputs: `[rows / g, g, channels]`, `g`
    rows a block. A row is a grid cell (its slot's blocks are its own),
    but a block of ONE row of a `[rows, channels]` array is a tile with
    one sublane in eight used, and as a `[rows, 1, channels]` operand
    it forces that layout on every fusion around the call. Eight rows a
    block (the batch whole where it is not eight's multiple) are
    fetched once and read a row a cell."""
    rows, channels = x.shape
    g = 8 if rows % 8 == 0 else rows
    return x.reshape(rows // g, g, channels)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _ssm_update_impl(x, u, delta, bt, ct, a, d, state, tail, layer, slots,
                     *, interpret: bool = False):
    """`x`, `u`, `delta` `[rows / g, g, channels]` float32
    (`_row_blocks`); `bt`, `ct` `[n, rows]`; `a` `[n, channels]`, `d`
    `[1, channels]`; `state` `[layers, slots + 1, n, channels]`, `tail`
    `[layers, slots + 1, kept, channels]`, of which this is
    `layer[0]`."""
    blocks, g, channels = u.shape
    rows = blocks * g
    n, kept = a.shape[0], tail.shape[2]

    def row(r, *_):
        return (r // g, 0, 0)

    def whole(r, *_):
        return (0, 0)

    def slot(r, layer_ref, slots_ref):
        return (layer_ref[0], slots_ref[r], 0, 0)

    y, state, tail = pl.pallas_call(
        _update_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(rows,),
            in_specs=[
                pl.BlockSpec((1, g, channels), row),
                pl.BlockSpec((1, g, channels), row),
                pl.BlockSpec((1, g, channels), row),
                pl.BlockSpec((n, rows), whole),
                pl.BlockSpec((n, rows), whole),
                pl.BlockSpec((n, channels), whole),
                pl.BlockSpec((1, channels), whole),
                pl.BlockSpec((None, 1, n, channels), slot),
                pl.BlockSpec((None, 1, kept, channels), slot),
            ],
            out_specs=[pl.BlockSpec((1, g, channels), row),
                       pl.BlockSpec((None, 1, n, channels), slot),
                       pl.BlockSpec((None, 1, kept, channels), slot)]),
        out_shape=[jax.ShapeDtypeStruct(u.shape, jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct(tail.shape, tail.dtype)],
        input_output_aliases={9: 1, 10: 2},
        cost_estimate=_update_cost(rows, n, channels, kept, tail.dtype),
        interpret=interpret,
    )(layer, slots, x, u, delta, bt, ct, a, d, state, tail)
    return y, state, tail


def selective_update(x, u, delta, b, c, a, d, state, tail, slots,
                     layer: int
                     ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """A decode step's one-token update. `x` `[rows, channels]` the
    convolution's new input (any float type), `u` its output after the
    activation and `delta`, float32, `b`, `c` `[rows, n]`; `state` and
    `tail` `[layers, slots + 1, n | kept, channels]` and `layer` which
    of them this is, `slots` `[rows]`: live rows hold distinct slots,
    pad rows the last one. Returns `y` `[rows, channels]` and
    both arrays, each row's slot of the layer one token on; no other
    slot and no other layer is touched."""
    if jax.default_backend() == "tpu":
        note_kernel_path("ssm_scan", "pallas",
                         "_ssm_update_impl, state slots in place")
        y, state, tail = _ssm_update_impl(
            _row_blocks(x.astype(jnp.float32)), _row_blocks(u),
            _row_blocks(delta), b.T, c.T, a, d[None], state, tail,
            jnp.full((1,), layer, jnp.int32), slots.astype(jnp.int32))
        return y.reshape(u.shape), state, tail
    note_kernel_path("ssm_scan", "reference",
                     f"jnp update: backend={jax.default_backend()}")
    return ssm_update_ref(x, u, delta, b, c, a, d, state, tail, slots,
                          layer)
