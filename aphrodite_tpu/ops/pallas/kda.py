"""Kimi Delta Attention (KDA; Kimi Linear, arXiv:2510.26692) over state
slots: a gated delta rule whose decay has a channel of the key each.
A head keeps a matrix `S` `[d_k, d_v]`, float32, and for token `t`

    S'  = diag(exp(g_t)) S_{t-1}
    S_t = S' + b_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t

with `g_t` `[d_k]` the log decay (<= 0), `b_t` in (0, 1) the write
strength, `q_t`, `k_t` `[d_k]` and `v_t` `[d_v]`. A position with
`b_t = 0` and `g_t = 0` leaves `S` as it was, which is how a chunk's
padding is passed over (the caller zeroes both there).

The state of every sequence lives in its STATE SLOT, row `slot` of
every layer of the model's one array `[layers, slots + 1, heads, d_k,
d_v]` (`common/config.py::StateSpec`; the last slot is the pad rows'
scratch), beside the tail of the layer's causal convolutions, the same
slot of `[layers, slots + 1, kept, channels]` in the model's type. The
slot conventions are `ops/pallas/ssm_scan.py`'s, stated there once: a
call names its layer, which reaches the kernel as a prefetched scalar
beside the slot ids so that a model's layers share one trace; the
whole array goes in and comes out in place (`input_output_aliases`);
a decode step's rows reach the kernel eight a block.

Two entry points, each a dispatcher over a Pallas kernel (one TPU
chip) and a `jax.numpy` side (the CPU, a mesh):

- `kda_chunk`: a prompt chunk, from the slot's state (zeros where
  `fresh`), the final state back to the slot. A grid cell is (row,
  head, chunk of `CHUNK` tokens), the chunks innermost with the head's
  state in VMEM across them. Within a chunk, with `G_i` the running
  sum of `g` (inclusive):

      A_ij = b_i (k_i * exp(G_i - G_j)) . k_j            for j < i
      P_ij =     (q_i * exp(G_i - G_j)) . k_j            for j <= i
      (I + A) U~ = diag(b) V - (diag(b) K * exp(G)) S_0
      o   = (Q * exp(G)) S_0 + P U~
      S_C = diag(exp(G_C)) S_0 + (K * exp(G_C - G))^T U~

  which is the recurrence written out (`u~_i` is what token `i` writes
  along `k_i`). Only DIFFERENCES `G_i - G_j <= 0` are exponentiated,
  never `exp(-G_j)` alone, which overflows under a decay of a channel
  each: the chunk goes in sub-blocks of `SUB` tokens; a sub-block's
  rows against the columns of the sub-blocks behind it go through the
  matmul unit with both sides scaled against the sub-block's first
  row (`exp(G_i - G_ref) <= 1` and `exp(G_ref - G_j) <= 1`), and the
  `SUB x SUB` block on the diagonal is computed a column at a time
  with `exp(min(G_i - G_j, 0))` itself. `I + A` is unit lower
  triangular, so `A` is nilpotent and the inverse is a product of
  matmuls, no row-by-row substitution: the `SUB`-wide diagonal blocks
  by `(I - A_d)(I + A_d^2)(I + A_d^4)(I + A_d^8)`, all four at once as
  one block-diagonal matrix, and the rest by `(I - B)(I + B^2)` over
  `B = (I + A_d)^-1 A_off`, nilpotent of order `CHUNK / SUB = 4` by
  blocks: eleven `64 x 64` products. (The powers of a `SUB`-wide block
  grow by binomials, at worst `C(15, 7) = 6,435` where every key of a
  sub-block is the same vector written at full strength with no decay:
  three of float32's seven digits; over the chunk's 64 it would be
  `C(63, 31)`, which is why the blocks are inverted first.)
  NOTHING inside is rounded to bfloat16: every product is
  `Precision.HIGHEST` (float32 operands in three bfloat16 parts, six
  passes), and the cumulative sum of `g` is a triangular matmul at the
  same precision. What IS bfloat16 is what the caller hands in: `q`,
  `k`, `v` come from bfloat16 projections (`modeling/layers/kda.py`).
- `kda_update`: a decode step, one token a row. A row's state (all its
  heads, 2 MiB at 32 heads of 128 x 128) is read by its slot id,
  updated and written in place, and the convolutions' tail moves on by
  the row's new input with it. It is bound by memory: the state both
  ways. The update needs `exp(g)`, `k` and `q` along the state's ROWS
  (`d_k` on the sublanes) and everything else along its lanes; the
  caller hands the three in transposed (`[d_k, 3 x heads]` a row,
  `_columns`), so that the kernel takes a head's column and never
  transposes.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from aphrodite_tpu.common.utils import note_kernel_path
# (a decode step's rows eight a block: the slot conventions' third)
from aphrodite_tpu.ops.pallas.ssm_scan import _row_blocks

#: tokens a grid cell of the chunk kernel takes, and the sub-blocks
#: inside it whose diagonal blocks are computed elementwise
CHUNK = 64
SUB = 16
#: what a trace calls the two kernels (`pallas_call`'s `name=`): the
#: benchmark's readers find the calls by these
CHUNK_DEVICE_OP_PREFIXES = ("kda-chunk",)
UPDATE_DEVICE_OP_PREFIXES = ("kda-update",)

_HIGHEST = jax.lax.Precision.HIGHEST


def _dot(a, b):
    return jnp.dot(a, b, precision=_HIGHEST,
                   preferred_element_type=jnp.float32)


def _dot_nt(a, b):
    """`a @ b.T`"""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               precision=_HIGHEST,
                               preferred_element_type=jnp.float32)


def _dot_tn(a, b):
    """`a.T @ b`"""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               precision=_HIGHEST,
                               preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------
# jax.numpy side
# ---------------------------------------------------------------------

def _step(s, q, k, v, g, b):
    """One token of the recurrence, every row and head at once: `s`
    `[rows, heads, d_k, d_v]`, `q`, `k`, `g` `[rows, heads, d_k]`, `v`
    `[rows, heads, d_v]`, `b` `[rows, heads]`."""
    s = jnp.exp(g)[..., None] * s
    u = b[..., None] * (v - jnp.einsum("rhkv,rhk->rhv", s, k,
                                       precision=_HIGHEST))
    s = s + k[..., None] * u[..., None, :]
    return s, jnp.einsum("rhkv,rhk->rhv", s, q, precision=_HIGHEST)


def kda_chunk_ref(q, k, v, g, b, state, slots, fresh, layer: int
                  ) -> Tuple[jax.Array, jax.Array]:
    """`kda_chunk` in plain `jax.numpy`: the recurrence a token at a
    time, the state `[rows, heads, d_k, d_v]` carried."""
    s0 = jnp.where(fresh[:, None, None, None] != 0, 0.0,
                   state[layer, slots])

    s, o = jax.lax.scan(lambda s, xs: _step(s, *xs), s0, tuple(
        jnp.moveaxis(x.astype(jnp.float32), 1, 0)
        for x in (q, k, v, g, b)))
    return jnp.moveaxis(o, 0, 1), state.at[layer, slots].set(s)


def kda_update_ref(x, q, k, v, g, b, state, tail, slots, layer: int
                   ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """`kda_update` in plain `jax.numpy`."""
    s, o = _step(state[layer, slots], *(
        a.astype(jnp.float32) for a in (q, k, v, g, b)))
    moved = jnp.concatenate(
        [tail[layer, slots][:, 1:], x[:, None, :].astype(tail.dtype)],
        axis=1)
    return o, state.at[layer, slots].set(s), \
        tail.at[layer, slots].set(moved)


# ---------------------------------------------------------------------
# the prompt chunk
# ---------------------------------------------------------------------

def _chunk_kernel(layer_ref, slots_ref, fresh_ref, q_ref, k_ref, v_ref,
                  g_ref, b_ref, s_in_ref, o_ref, s_out_ref, s_scr):
    row, head, c = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(c == 0)
    def _():
        # (a select: what a fresh row's slot holds, NaN or not, is
        # never read into the state)
        s_scr[...] = jnp.where(fresh_ref[row] != 0, 0.0, s_in_ref[0, 0])

    q, k, v, g = (r[0].astype(jnp.float32)
                  for r in (q_ref, k_ref, v_ref, g_ref))   # [C, d]
    n = q.shape[0]
    # this head's write strength: column `head` of the `[C, heads]` block
    heads = jax.lax.broadcasted_iota(jnp.int32, b_ref.shape[1:], 1)
    b = jnp.sum(jnp.where(heads == head, b_ref[0].astype(jnp.float32),
                          0.0), axis=1, keepdims=True)     # [C, 1]
    kb, vb = k * b, v * b

    at_row = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    at_col = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    eye = (at_row == at_col).astype(jnp.float32)
    big = _dot((at_row >= at_col).astype(jnp.float32), g)  # G, [C, d]

    # A (strictly lower) and P (lower), SUB rows at a time
    sub_row = jax.lax.broadcasted_iota(jnp.int32, (SUB, 1), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)
    token = jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)
    a_rows, p_rows = [], []
    for lo in range(0, n, SUB):
        g_i, ref = big[lo:lo + SUB], big[lo:lo + 1]
        scale = jnp.exp(g_i - ref)                          # <= 1
        q_i, k_i, kb_i = q[lo:lo + SUB], k[lo:lo + SUB], kb[lo:lo + SUB]
        if lo:
            # the columns behind this sub-block, scaled against its
            # first row (<= 1: they lie before it); the others zero
            behind = jnp.where(token < lo,
                               k * jnp.exp(jnp.minimum(ref - big, 0.0)),
                               0.0)
            a_i = _dot_nt(kb_i * scale, behind)             # [SUB, C]
            p_i = _dot_nt(q_i * scale, behind)
        else:
            a_i = p_i = jnp.zeros((SUB, n), jnp.float32)
        for j in range(SUB):
            decay = jnp.exp(jnp.minimum(g_i - g_i[j:j + 1], 0.0))
            kd = decay * k_i[j:j + 1]                       # [SUB, d]
            col = lane == lo + j
            a_i = a_i + jnp.where(
                col & (sub_row > j),
                jnp.sum(kb_i * kd, axis=1, keepdims=True), 0.0)
            p_i = p_i + jnp.where(
                col & (sub_row >= j),
                jnp.sum(q_i * kd, axis=1, keepdims=True), 0.0)
        a_rows.append(a_i)
        p_rows.append(p_i)
    a = jnp.concatenate(a_rows, axis=0)                     # [C, C]
    p = jnp.concatenate(p_rows, axis=0)

    # (I + A)^-1: the diagonal blocks, then the blocks below them
    on_diag = (at_row // SUB) == (at_col // SUB)
    a_d = jnp.where(on_diag, a, 0.0)
    inv_d, power = eye - a_d, a_d
    for _ in range(SUB.bit_length() - 2):       # A_d^2, ^4, ^8
        power = _dot(power, power)
        inv_d = _dot(inv_d, eye + power)
    below = _dot(inv_d, a - a_d)                            # B
    inv = _dot(_dot(eye - below, eye + _dot(below, below)), inv_d)

    s0 = s_scr[...]
    decayed = jnp.exp(big)                                  # exp(G) <= 1
    u = _dot(inv, vb - _dot(kb * decayed, s0))              # U~, [C, d_v]
    o_ref[0] = (_dot(q * decayed, s0) + _dot(p, u)).astype(o_ref.dtype)
    # S_C: diag(exp(G_C)) S_0 as one more block of the same product
    last = big[n - 1:n]
    d = s0.shape[0]
    at = jax.lax.broadcasted_iota(jnp.int32, (d, d), 0) == \
        jax.lax.broadcasted_iota(jnp.int32, (d, d), 1)
    s_scr[...] = _dot_tn(
        jnp.concatenate([jnp.where(at, jnp.exp(last), 0.0),
                         k * jnp.exp(last - big)], axis=0),
        jnp.concatenate([s0, u], axis=0))

    @pl.when(c == pl.num_programs(2) - 1)
    def _():
        s_out_ref[0, 0] = s_scr[...]


def chunk_cost(rows: int, tokens: int, heads: int, d_k: int, d_v: int,
               chunk: int = CHUNK, sub: int = SUB) -> Tuple[int, int]:
    """`(operations, bytes)` of a chunk call as the kernel does it
    (`perf/rooflines/kda.py` has the benchmark's own count): a
    multiply and an add each of the products above, a chunk and head."""
    cells = rows * heads * (tokens // chunk)
    mm = 2 * (chunk * chunk * d_k                   # the running sum
              + 2 * chunk * chunk * d_k             # A and P
              + 11 * chunk ** 3                     # the inverse
              + 2 * chunk * d_k * d_v               # K S_0 and Q S_0
              + 2 * chunk * chunk * d_v             # U~ and P U~
              + (d_k + chunk) * d_k * d_v)          # S_C
    moved = 4 * (rows * tokens * heads * (3 * d_k + 2 * d_v + 1)
                 + 2 * rows * heads * d_k * d_v)
    return cells * mm, moved


def _chunks(tokens: int) -> int:
    """Chunks of a call: whole ones (the dispatcher pads)."""
    if tokens % CHUNK or CHUNK % SUB:
        raise ValueError(f"kda chunk: {tokens} tokens in chunks of "
                         f"{CHUNK}, sub-blocks of {SUB}")
    return tokens // CHUNK


@functools.partial(jax.jit, static_argnames=("interpret",))
def _kda_chunk_impl(q, k, v, g, b, state, layer, slots, fresh, *,
                    interpret: bool = False):
    """`q`, `k`, `g` `[rows, tokens, heads * d_k]`, `v` `[rows, tokens,
    heads * d_v]` (a head's lanes side by side, as the projections
    leave them), `b` `[rows, tokens, heads]`; `state` `[layers, slots +
    1, heads, d_k, d_v]`, of which this is `layer[0]`."""
    rows, tokens = q.shape[:2]
    heads, d_k, d_v = state.shape[2:]
    chunks = _chunks(tokens)

    def seq(r, h, c, *_):
        return (r, c, h)

    def slot(r, h, c, layer_ref, slots_ref, fresh_ref):
        return (layer_ref[0], slots_ref[r], h, 0, 0)

    ops, moved = chunk_cost(rows, tokens, heads, d_k, d_v)
    o, state = pl.pallas_call(
        _chunk_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(rows, heads, chunks),
            in_specs=[
                pl.BlockSpec((1, CHUNK, d_k), seq),
                pl.BlockSpec((1, CHUNK, d_k), seq),
                pl.BlockSpec((1, CHUNK, d_v), seq),
                pl.BlockSpec((1, CHUNK, d_k), seq),
                pl.BlockSpec((1, CHUNK, heads),
                             lambda r, h, c, *_: (r, c, 0)),
                pl.BlockSpec((None, 1, 1, d_k, d_v), slot),
            ],
            out_specs=[pl.BlockSpec((1, CHUNK, d_v), seq),
                       pl.BlockSpec((None, 1, 1, d_k, d_v), slot)],
            scratch_shapes=[pltpu.VMEM((d_k, d_v), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct(v.shape, jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # (the flattened inputs count the three scalar-prefetch arrays)
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=ops, transcendentals=rows * tokens * heads * d_k * 8,
            bytes_accessed=moved),
        name=CHUNK_DEVICE_OP_PREFIXES[0],
        interpret=interpret,
    )(layer, slots, fresh, q, k, v, g, b, state)
    return o, state


def kda_chunk(q, k, v, g, b, state, slots, fresh, layer: int
              ) -> Tuple[jax.Array, jax.Array]:
    """A prompt chunk. `q`, `k`, `g` `[rows, tokens, heads, d_k]`, `v`
    `[rows, tokens, heads, d_v]`, `b` `[rows, tokens, heads]` (`g` and
    `b` zero at a row's padding); `state` `[layers, slots + 1, heads,
    d_k, d_v]` float32 and `layer` which of them this is, `slots`
    `[rows]` each row's slot, `fresh` `[rows]` non-zero where the row
    starts from zeros. Returns `o` `[rows, tokens, heads, d_v]`
    float32 and the state array with each row's slot of the layer at
    its last token."""
    if jax.default_backend() == "tpu":
        note_kernel_path("kda_chunk", "pallas",
                         f"_kda_chunk_impl, chunks of {CHUNK}, state in "
                         "VMEM over the chunks")
        rows, tokens, heads, _ = q.shape
        # (a step with g = 0 and b = 0 is passed over)
        short = -tokens % CHUNK
        flat = [x.reshape(rows, tokens, -1) for x in (q, k, v, g, b)]
        if short:
            flat = [jnp.pad(x, ((0, 0), (0, short), (0, 0))) for x in flat]
        o, state = _kda_chunk_impl(
            *flat, state, jnp.full((1,), layer, jnp.int32),
            slots.astype(jnp.int32), fresh.astype(jnp.int32))
        return o[:, :tokens].reshape(v.shape), state
    note_kernel_path("kda_chunk", "reference",
                     f"jnp scan over tokens: backend={jax.default_backend()}")
    return kda_chunk_ref(q, k, v, g, b, state, slots, fresh, layer)


# ---------------------------------------------------------------------
# the decode step's update
# ---------------------------------------------------------------------

def _update_kernel(layer_ref, slots_ref, x_ref, cols_ref, bv_ref, bb_ref,
                   s_in_ref, tail_in_ref, o_ref, s_out_ref, tail_out_ref):
    row = pl.program_id(0)
    # this row of the block of rows that the step before fetched too
    mine = pl.ds(row % x_ref.shape[1], 1)
    heads, _, d_v = s_in_ref.shape[1:]
    cols = cols_ref[0]                          # [d_k, 3 * heads]
    bv, bb = bv_ref[0, mine, :], bb_ref[0, mine, :]     # [1, heads * d_v]
    outs = []
    for h in range(heads):
        decay, k, q = (cols[:, i * heads + h:i * heads + h + 1]
                       for i in range(3))                # [d_k, 1]
        at = slice(h * d_v, (h + 1) * d_v)
        s = s_in_ref[0, h] * decay
        kept = jnp.sum(s * k, axis=0, keepdims=True)     # S'^T k, [1, d_v]
        s = s + k * (bv[:, at] - bb[:, at] * kept)
        s_out_ref[0, h] = s
        outs.append(jnp.sum(s * q, axis=0, keepdims=True))
    o_ref[0, mine, :] = jnp.concatenate(outs, axis=1)
    kept = tail_in_ref.shape[1]
    for i in range(kept - 1):
        tail_out_ref[0, i:i + 1, :] = tail_in_ref[0, i + 1:i + 2, :]
    tail_out_ref[0, kept - 1:kept, :] = x_ref[0, mine, :].astype(
        tail_out_ref.dtype)


def update_cost(rows: int, heads: int, d_k: int, d_v: int, kept: int,
                channels: int, tail_dtype) -> Tuple[int, int]:
    """`(operations, bytes)` of a decode step's call: a state element
    is decayed, read against `k`, written and read against `q` (seven
    operations); a row's state and tail in and out, its inputs in and
    its output out."""
    cells = rows * heads * d_k * d_v
    moved = rows * (
        2 * (4 * heads * d_k * d_v +
             kept * channels * jnp.dtype(tail_dtype).itemsize)
        + 4 * (channels + 3 * heads * d_k + 3 * heads * d_v))
    return 7 * cells, moved


def _columns(g, k, q) -> jax.Array:
    """`[rows, d_k, 3 * heads]`: `exp(g)`, `k` and `q` of every head
    with `d_k` on the SUBLANES, as the state's rows lie: column `i *
    heads + h` is head `h`'s part `i`."""
    cols = jnp.concatenate([jnp.exp(g), k, q], axis=1)   # [rows, 3h, d_k]
    return jnp.swapaxes(cols, 1, 2)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _kda_update_impl(x, cols, bv, bb, state, tail, layer, slots, *,
                     interpret: bool = False):
    """`x` `[rows / g, g, channels]` the convolutions' new input, `bv`,
    `bb` `[rows / g, g, heads * d_v]` (`b v` and `b`, a head's lanes
    side by side), float32 (`_row_blocks`); `cols` `[rows, d_k, 3 *
    heads]` (`_columns`); `state` `[layers, slots + 1, heads, d_k,
    d_v]`, `tail` `[layers, slots + 1, kept, channels]`, of which this
    is `layer[0]`."""
    blocks, group, channels = x.shape
    rows = blocks * group
    heads, d_k, d_v = state.shape[2:]
    kept = tail.shape[2]

    def block(r, *_):
        return (r // group, 0, 0)

    def slot(r, layer_ref, slots_ref):
        return (layer_ref[0], slots_ref[r], 0, 0, 0)

    def tail_slot(r, layer_ref, slots_ref):
        return (layer_ref[0], slots_ref[r], 0, 0)

    ops, moved = update_cost(rows, heads, d_k, d_v, kept, channels,
                             tail.dtype)
    o, state, tail = pl.pallas_call(
        _update_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(rows,),
            in_specs=[
                pl.BlockSpec((1, group, channels), block),
                pl.BlockSpec((1, d_k, 3 * heads), lambda r, *_: (r, 0, 0)),
                pl.BlockSpec((1, group, heads * d_v), block),
                pl.BlockSpec((1, group, heads * d_v), block),
                pl.BlockSpec((None, 1, heads, d_k, d_v), slot),
                pl.BlockSpec((None, 1, kept, channels), tail_slot),
            ],
            out_specs=[pl.BlockSpec((1, group, heads * d_v), block),
                       pl.BlockSpec((None, 1, heads, d_k, d_v), slot),
                       pl.BlockSpec((None, 1, kept, channels), tail_slot)]),
        out_shape=[jax.ShapeDtypeStruct(bv.shape, jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct(tail.shape, tail.dtype)],
        input_output_aliases={6: 1, 7: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # a row's state in and out, both double-buffered: 8 MiB at
            # 32 heads of 128 x 128
            vmem_limit_bytes=max(32 << 20, 5 * 4 * heads * d_k * d_v)),
        cost_estimate=pl.CostEstimate(
            flops=ops, transcendentals=0, bytes_accessed=moved),
        name=UPDATE_DEVICE_OP_PREFIXES[0],
        interpret=interpret,
    )(layer, slots, x, cols, bv, bb, state, tail)
    return o, state, tail


def kda_update(x, q, k, v, g, b, state, tail, slots, layer: int
               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """A decode step's one-token update. `x` `[rows, channels]` the
    convolutions' new input (any float type); `q`, `k`, `g` `[rows,
    heads, d_k]`, `v` `[rows, heads, d_v]`, `b` `[rows, heads]`;
    `state` and `tail` `[layers, slots + 1, heads, d_k, d_v | kept,
    channels]` and `layer` which of them this is, `slots` `[rows]`:
    live rows hold distinct slots, pad rows the last one. Returns `o`
    `[rows, heads, d_v]` float32 and both arrays, each row's slot of
    the layer one token on; no other slot and no other layer is
    touched."""
    if jax.default_backend() == "tpu":
        note_kernel_path("kda_update", "pallas",
                         "_kda_update_impl, state slots in place")
        rows = x.shape[0]
        q, k, v, g, b = (a.astype(jnp.float32) for a in (q, k, v, g, b))
        bb = jnp.broadcast_to(b[..., None], v.shape)
        o, state, tail = _kda_update_impl(
            _row_blocks(x.astype(jnp.float32)), _columns(g, k, q),
            _row_blocks((bb * v).reshape(rows, -1)),
            _row_blocks(bb.reshape(rows, -1)), state, tail,
            jnp.full((1,), layer, jnp.int32), slots.astype(jnp.int32))
        return o.reshape(v.shape), state, tail
    note_kernel_path("kda_update", "reference",
                     f"jnp update: backend={jax.default_backend()}")
    return kda_update_ref(x, q, k, v, g, b, state, tail, slots, layer)
