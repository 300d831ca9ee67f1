"""Pallas TPU kernels for the expert layer's grouped matmuls.

What `modeling/layers/fused_moe.py::FusedMoE._ragged_ffn` asks of three
`jax.lax.ragged_dot` calls (gate, up, the gated activation in `jnp`
between them, down), as two kernels over a layout in which EVERY ROW
TILE BELONGS TO ONE EXPERT, so that an expert's matrices cross HBM
once a call:

- The layout (`aligned_layout`, plain `jnp` on the device, the
  reference's `moe_align_block_size`). The pairs sort by expert as they
  always did; an expert's group then starts on a multiple of the row
  tile `t`, `dest` = the group's aligned start + the pair's rank in its
  group. The static row count is `tiles x t` with `tiles` the most that
  `pairs` pairs over `experts` groups can fill (`num_row_tiles`: every
  group ends in at most one tile that is not full). A tile-to-expert
  list and the count of tiles in use are computed from `group_sizes`
  and handed to the kernels as scalar-prefetch operands. A pair with no
  group (its expert is held by another chip) gets NO row: it is in no
  tile, and the caller masks what it reads for it.
- The walk. Grid `(blocks of the matrices' columns, row tiles)`, rows
  the inner axis. A tile's weight block index is its expert's alone, so
  consecutive tiles of one group copy nothing, and a tile past the last
  one in use repeats the last one's indices on every operand and is
  an empty step: neither an expert without a pair nor the padding of
  the list fetches a matrix or a row, and the rows of such a tile are
  never written (nothing reads them). Whole `[hidden, width]` matrices
  ride in VMEM where experts are narrow (SmallThinker's 3.9 MB,
  Laguna's 6.3 MB); wider ones (Mixtral's 4,096 x 14,336) go in blocks
  of columns that fit `WEIGHT_BYTES` twice (the pipeline's two
  buffers), the rows re-read once a block (`column_block`). A shape for
  which no block fits gets `None` there and keeps XLA's call.
- Two kernels, one body. `gate` and `up` share the rows they read and
  the activation is applied to their float32 accumulators, so `act`
  is written once, in the rows' type; `down` is the same body with one
  matrix and no activation. The contraction is whole in both (operands
  go to the MXU in their own type, float32 accumulation), which is the
  arithmetic of the `ragged_dot` path but for `gate` and `up` not being
  rounded to the rows' type before the activation.
- `row_tile`: `t` follows pairs an expert, the bf16 sublane tile (16)
  in a decode step of two or three, up to `MAX_ROW_TILE` in a chunk.
  Nothing is read from a model's name, a flag or the environment.

Both kernels sit behind ONE `jax.jit` (`grouped_ffn`), so a step
program's expert layers share a trace. In a device trace they are
`ragged-dot-aligned-gate-up` and `ragged-dot-aligned-down`
(`DEVICE_OP_PREFIXES` below says why they are called so).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from aphrodite_tpu.common.utils import cdiv

#: What a device trace calls the expert layer's grouped matmuls, gate
#: to down, by the start of an operation's name: XLA's custom calls
#: under `jax.lax.ragged_dot` are `ragged-dot-none` and
#: `ragged-dot-metadata`, and the two kernels here are NAMED to start
#: the same way (`_grouped`'s `name=`), because the benchmark's
#: roofline readers find the layer's seconds by `("ragged-dot",)`
#: (`perf/layer_ops/fused_moe.json`'s `until_stated`) until
#: `modeling/layers/fused_moe.py` states a constant of this name, and
#: `tests/perf/test_perf_expert_share.py` holds that file to NOT
#: stating it yet; neither is a `perf_opt` PR's to edit. The day a
#: `benchmark` PR lifts that pin, this constant moves beside `FusedMoE`
#: and the kernels may bear any name it states. A kernel that does part
#: of gate, up, the activation or down and is not named so is left out
#: of `moe_experts_roofline_pct.batch` and `moe_held_roofline_pct.batch`.
DEVICE_OP_PREFIXES = ("ragged-dot",)

#: rows a tile, least and most: a bfloat16 tile's sublanes, and twice
#: the MXU's 128 rows. A tile pushes its expert's matrices through the
#: MXU once whatever its height (7.7 us for SmallThinker's three, 12.3
#: for Laguna's), so a group in one tile of 256 beats two of 128: a
#: SmallThinker chunk's kernels take 1,355 us at 256 and 1,845 at 128
#: (PERF.md section 6, PR 50). A taller one makes the static layout,
#: `experts x (t - 1)` rows of room, longer for rows nobody reads.
MIN_ROW_TILE = 16
MAX_ROW_TILE = 256
#: bytes of one step's weight blocks, at most (the pipeline holds two
#: steps' worth)
WEIGHT_BYTES = 20 << 20
#: the scoped VMEM the kernels state, of the chip's 128 MiB
VMEM_LIMIT = 100 << 20


def row_tile(pairs: int, experts: int) -> int:
    """Rows a tile for `pairs` pairs that `experts` experts share: the
    power of two at or over the mean group, within `MIN_ROW_TILE` and
    `MAX_ROW_TILE`. A tile taller than its group multiplies padding; a
    shorter one starts more tiles (each pays the MXU's weight load)."""
    mean = cdiv(pairs, experts)
    return min(MAX_ROW_TILE,
               max(MIN_ROW_TILE, 1 << (mean - 1).bit_length()))


def num_row_tiles(pairs: int, experts: int, tile: int) -> int:
    """The most tiles `pairs` pairs in at most `experts` groups can
    fill: every group with a pair ends in at most one tile that is not
    full."""
    return cdiv(pairs + min(experts, pairs) * (tile - 1), tile)


def column_block(contraction: int, columns: int, matrices: int,
                 itemsize: int) -> Optional[int]:
    """Columns a weight block: all of them where `matrices` matrices of
    `[contraction, columns]` fit `WEIGHT_BYTES`, else the largest
    divisor of `columns` that is a multiple of the 128 lanes and fits;
    None where none does."""
    fits = WEIGHT_BYTES // (matrices * contraction * itemsize)
    if columns <= fits:
        return columns
    for block in range(fits // 128 * 128, 0, -128):
        if columns % block == 0:
            return block
    return None


def takes_shapes(hidden: int, width: int, dtype) -> bool:
    """Whether the kernels take an expert layer of these widths: a
    block of columns fits for both of them, and the widths are whole
    lanes."""
    itemsize = jnp.dtype(dtype).itemsize
    return hidden % 128 == 0 and width % 128 == 0 and \
        column_block(hidden, width, 2, itemsize) is not None and \
        column_block(width, hidden, 1, itemsize) is not None


def aligned_layout(pair_expert: jax.Array, group_sizes: jax.Array,
                   tile: int, tokens: int) -> Tuple[jax.Array, ...]:
    """The tile-aligned layout of `pair_expert` `[pairs]` (a pair's
    expert; `experts` for a pair with no group; pair p is token
    `p % tokens`) under `group_sizes` `[experts]`, as `(source, dest,
    tile_expert, tiles_used)`:

    - `source` `[tiles x t]`: the token whose row lies in each aligned
      row (a padding row names some token: what is computed there is
      read by nobody);
    - `dest` `[pairs]`: each pair's aligned row (0 for a pair with no
      group: the caller masks it);
    - `tile_expert` `[tiles]`: each tile's expert, the last used
      tile's for the tiles behind it;
    - `tiles_used`: int32 scalar; `tiles_used x t` rows are walked.

    Pairs keep the order of a stable sort by expert within a group."""
    pairs, experts = pair_expert.shape[0], group_sizes.shape[0]
    tiles = num_row_tiles(pairs, experts, tile)
    group_tiles = (group_sizes + (tile - 1)) // tile
    tile_ends = jnp.cumsum(group_tiles)
    tiles_used = tile_ends[-1]
    # what a group's rows move by from the sorted order to the aligned
    shift = (tile_ends - group_tiles) * tile - \
        (jnp.cumsum(group_sizes) - group_sizes)
    order = jnp.argsort(pair_expert)
    rank = jnp.argsort(order)
    # a pair's row: its sorted place plus its group's shift, the shift
    # picked by comparing with every expert id (a pair with no group
    # matches none; a gather from the table costs the TPU eight times
    # the comparison)
    expert_ids = jnp.arange(experts, dtype=jnp.int32)
    dest = jnp.where(
        pair_expert < experts,
        rank + jnp.sum(jnp.where(pair_expert[:, None] == expert_ids,
                                 shift, 0), axis=1), 0)

    # a tile's expert is the count of groups that end at or before it
    tile_ids = jnp.arange(tiles, dtype=jnp.int32)
    tile_expert = jnp.sum(tile_ids[:, None] >= tile_ends[None, :], axis=1,
                          dtype=jnp.int32)
    last = jnp.max(jnp.where(group_sizes > 0, expert_ids, 0))
    tile_expert = jnp.where(tile_ids < tiles_used, tile_expert, last)
    # a row's place in the sorted order is its tile's first row's plus
    # its row in the tile: arithmetic a tile, then ONE gather of the
    # tokens (a slice a tile, `vmap` of `dynamic_slice`, is a `while`
    # of as many rounds on the TPU: 63 us of a decode step's call where
    # this takes 12; PERF.md section 6, PR 50)
    first = tile_ids * tile - shift.at[tile_expert].get(
        mode="promise_in_bounds")
    place = (first[:, None] +
             jnp.arange(tile, dtype=jnp.int32)[None, :]).reshape(-1)
    source = (order % tokens).at[jnp.clip(place, 0, pairs - 1)].get(
        mode="promise_in_bounds")
    return source, dest, tile_expert, tiles_used


def _kernel(tile_expert_ref, tiles_used_ref, rows_ref, *refs, act):
    """One `(column block, row tile)` step: the tile's rows through its
    expert's block of one matrix (`down`), or of two with the gated
    activation on their float32 accumulators (`gate`, `up`)."""
    del tile_expert_ref                 # the index maps read it
    *w_refs, out_ref = refs

    @pl.when(pl.program_id(1) < tiles_used_ref[0])
    def _():
        rows = rows_ref[...]
        out = jnp.dot(rows, w_refs[0][...],
                      preferred_element_type=jnp.float32)
        if len(w_refs) == 2:
            out = act(out) * jnp.dot(rows, w_refs[1][...],
                                     preferred_element_type=jnp.float32)
        out_ref[...] = out.astype(out_ref.dtype)


def _grouped(rows, weights, tile_expert, tiles_used, *, tile, act,
             interpret):
    """`rows` `[tiles x t, K]` through each tile's expert of `weights`
    (one or two `[experts, K, N]`), as `[tiles x t, N]` in `rows`'
    type."""
    total, k = rows.shape
    experts, _, n = weights[0].shape
    itemsize = jnp.dtype(weights[0].dtype).itemsize
    block = column_block(k, n, len(weights), itemsize)
    if block is None or total % tile:
        raise ValueError(
            f"grouped matmul: {len(weights)} matrices of [{k}, {n}] fit "
            f"no block of columns, or {total} rows are no tiles of {tile}")
    tiles = total // tile

    def last_used(i, tiles_used_ref):
        return jnp.maximum(jnp.minimum(i, tiles_used_ref[0] - 1), 0)

    def row_map(col, i, tile_expert_ref, tiles_used_ref):
        return last_used(i, tiles_used_ref), 0

    def out_map(col, i, tile_expert_ref, tiles_used_ref):
        return last_used(i, tiles_used_ref), col

    def weight_map(col, i, tile_expert_ref, tiles_used_ref):
        return tile_expert_ref[i], 0, col

    return pl.pallas_call(
        functools.partial(_kernel, act=act),
        name=DEVICE_OP_PREFIXES[0] + (
            "-aligned-down" if act is None else "-aligned-gate-up"),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n // block, tiles),
            in_specs=[pl.BlockSpec((tile, k), row_map)] + [
                pl.BlockSpec((None, k, block), weight_map)
                for _ in weights],
            out_specs=pl.BlockSpec((tile, block), out_map)),
        out_shape=jax.ShapeDtypeStruct((total, n), rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        # what the call moves and computes at most (every tile in use,
        # every expert touched), for the compiler that schedules the
        # step around it
        cost_estimate=pl.CostEstimate(
            flops=2 * total * k * n * len(weights), transcendentals=0,
            bytes_accessed=(
                min(experts, total) * len(weights) * k * n * itemsize +
                total * (k * (n // block) + n) * rows.dtype.itemsize)),
        interpret=interpret,
    )(tile_expert, tiles_used.reshape(1), rows, *weights)


@functools.partial(jax.jit,
                   static_argnames=("tile", "act", "interpret"))
def grouped_ffn(rows: jax.Array, w_gate: jax.Array, w_up: jax.Array,
                w_down: jax.Array, tile_expert: jax.Array,
                tiles_used: jax.Array, *, tile: int, act,
                interpret: bool = False) -> jax.Array:
    """`(act(rows @ w_gate[e]) * (rows @ w_up[e])) @ w_down[e]` (`act`
    a function of float32, e.g. `jax.nn.silu`) for the rows of every
    tile in use, `e` the tile's expert: `rows`
    `[tiles x tile, hidden]` in `aligned_layout`'s order, the stacked
    `[experts, hidden, width]`, `[experts, hidden, width]` and
    `[experts, width, hidden]` matrices, `tile_expert` `[tiles]` and
    `tiles_used` of that layout. Returns `[tiles x tile, hidden]` in
    `rows`' type; the rows of a tile not in use are not written."""
    mid = _grouped(rows, (w_gate, w_up), tile_expert, tiles_used,
                   tile=tile, act=act, interpret=interpret)
    return _grouped(mid, (w_down,), tile_expert, tiles_used, tile=tile,
                    act=None, interpret=interpret)
