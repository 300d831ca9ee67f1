"""Pallas TPU flash kernel for the prompt's attention.

What `ops/attention.py::prefill_attention` (the whole float32 score
array through HBM) and `prefill_attention_blocked` (an XLA `while` over
512 x 512 float32 tiles of every head, masked element by element) do in
`jnp`, as one kernel, for every prompt step the chip takes
(`modeling/layers/attention.py::PagedAttention._prefill`):

- Grid (row, KV head, query block, K/V block). A KV head's `group`
  query heads ride together as the rows of one matmul operand,
  `[group x query_block, d]`, packed once a query block into VMEM
  scratch with the score scale folded in, so a key block is read once
  a group. `q` and `k` are seen as `[b, tokens, heads x d]`, `v` and
  the output as `[b, tokens, heads x dv]` (a bitcast of the layouts
  the callers hold): a head, or a KV head's group, is a lane block, and
  the output is written where the next layer reads it. No transposing
  copy before or after. The values' head width `dv` is `v`'s own and
  need not be the keys' `d` (multi-head latent attention's heads are
  192 wide for q and k and 128 for v, `modeling/layers/mla.py`): V's
  blocks, the accumulator and the output are `dv` wide, the packed
  query, the scores and the statistics know nothing of it, and with
  `dv == d` nothing differs.
- The walk. The key sub-blocks a query block visits are `[first,
  stop)` of `ops/attention.py::prefill_tile_ranges`, the rule of the
  `jnp` walk and of the host's tile count, read here for each row by
  itself at the kernel's own block sizes and handed over as
  scalar-prefetch operands. The innermost grid axis walks K/V blocks of
  up to `KEY_MAJOR` keys: its block index is the first visible one
  plus the step, clamped to the last visible one, so the pipeline
  copies a block while the one before is scored and copies nothing
  where the index repeats (a step past the range is an empty step);
  inside a step a `fori_loop` walks the sub-blocks of `key_block` keys
  that lie in the range. A sub-block outside the range is neither
  copied for its own sake nor read.
- The arithmetic is the decode kernel's (`paged_attention.py`):
  operands go to the MXU in the model's type with float32
  accumulation, scores ride the base-2 domain (log2(e) folded into the
  packed query), the weights are cast to the model's type for the
  second matmul, and running maximum, sum and accumulator are float32
  VMEM scratch that lives across the walk. Unlike the decode kernel's
  one query row a head, thousands of rows carry their statistics here,
  so the maximum rides EVERY lane of its row and the sum is a part a
  lane: as `[rows, 1]` columns they cost some seven lane-crossing
  operations a row group a sub-block, three times the kernel's time
  (`PERF.md` section 6, PR 44).
- The mask (causal by absolute position, the row's valid keys, the
  window) is applied only in a sub-block that one of those edges
  crosses, decided from the same scalars; an interior sub-block is two
  matmuls, an exponent and the carry. A query that sees no key gives
  zeros, as both `jnp` functions do.
- Block sizes follow the shapes of the call (`choose_blocks`): `group`,
  the window, what divides the padded lengths. Nothing is read from a
  model's name, a flag or the environment.

ALiBi and pages with a dequantising scale keep the `jnp` functions (the
layer's dispatch); so does everything that is not one TPU.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from aphrodite_tpu.ops.attention import prefill_tile_ranges

_NEG_INF = -2.0**30     # large but finite, as the decode kernel's
_LOG2E = 1.4426950408889634

#: tokens to a multiple of which queries and keys are padded: the lane
#: tile, so that a score sub-block's lanes are whole
TOKEN_TILE = 128
#: keys of one K/V block that the pipeline copies (a grid step costs
#: what it costs whatever it holds, and 2,048 keys of one head are
#: 512 KB in bfloat16)
KEY_MAJOR = 2048
#: rows (`group` x query block) of a matmul operand, at most, and the
#: float32 bytes of a score sub-block: what the kernel keeps in VMEM
#: beside the blocks scales with them (at 4,096 rows x 512 keys:
#: scores, weights and the weights in bfloat16 20 MB, scratch 7, blocks
#: 6)
ROWS = 4096
SCORE_BYTES = 8 << 20
#: keys a sub-block, at most: past 512 a sub-block amortises nothing
#: more and scores more of what the diagonal masks
KEY_BLOCK = 512
#: the scoped VMEM the kernel states, of the chip's 128 MiB
VMEM_LIMIT = 48 << 20


def _pow2_under(value: int) -> int:
    return 1 << (max(int(value), 1).bit_length() - 1)


def choose_blocks(seq_len: int, kv_len: int, group: int,
                  window: Optional[int] = None) -> Tuple[int, int, int]:
    """(queries a block, keys a sub-block, keys a copied block) for a
    call of `seq_len` queries against `kv_len` keys a row (both
    multiples of `TOKEN_TILE`) whose KV heads serve `group` query heads
    each. By what the chip read (`PERF.md` section 6, PR 44: a
    sub-block costs some fixed time a row beside its arithmetic, the
    row's maximum crossing lanes, so long sub-blocks; a key block is
    loaded into the MXU once a query block, so long query blocks): the
    query block is the largest power of two up to 512 that divides the
    chunk and keeps `group` x block rows within `ROWS` (under 128 only
    past 32 query heads a KV head); the key
    sub-block the largest up to `KEY_BLOCK` that divides the keys,
    holds at most half a window (a window of 512 under blocks of 512
    would score 1,024 to 1,536 keys a query for 512 live ones) and
    whose float32 scores fit `SCORE_BYTES`. A copied block is the
    largest multiple of the sub-block that divides the keys and holds
    at most `KEY_MAJOR`."""
    def dividing(n: int, most: int, least: int = TOKEN_TILE) -> int:
        block = min(least, _pow2_under(most))
        while block * 2 <= most and n % (block * 2) == 0:
            block *= 2
        return block
    # (under the lane tile only for some 32 query heads a KV head and
    # more; 16 rows are a bfloat16 tile's)
    query_block = dividing(seq_len, max(min(512, ROWS // group), 16))
    most = min(KEY_BLOCK,
               _pow2_under(SCORE_BYTES // (4 * group * query_block)))
    if window is not None:
        most = min(most, _pow2_under(window // 2))
    key_block = dividing(kv_len, max(most, TOKEN_TILE))
    subs = kv_len // key_block
    major = max(n for n in range(1, subs + 1)
                if subs % n == 0 and n * key_block <= max(KEY_MAJOR,
                                                          key_block))
    return query_block, key_block, major * key_block


def _lanes(x, width: int):
    """`x [rows, lanes]` (a row's value in every lane) at `width`
    lanes: the same vregs side by side, or the first of them."""
    lanes = x.shape[1]
    if width <= lanes:
        return x[:, :width]
    return pltpu.repeat(x, width // lanes, axis=1)


def _flash_kernel(
    # scalar prefetch
    first_ref,      # [b * query blocks] first key sub-block visited
    stop_ref,       # [b * query blocks] one past the last
    ctx_ref,        # [b] position of a row's first query
    valid_ref,      # [b] a row's valid keys
    q_ref,          # [1, query_block, group * d]
    k_ref,          # [1, key_major, d]
    v_ref,          # [1, key_major, dv]
    o_ref,          # [1, query_block, group * dv]
    qp_scr,         # [group * query_block, d], the model's type
    m_scr,          # [rows, lanes] float32 running maximum (base 2),
                    # the same in every lane of a row
    l_scr,          # [rows, lanes] float32 running sum, a part a lane
    acc_scr,        # [rows, dv] float32 weighted values
    *, group: int, head_dim: int, query_block: int, key_block: int,
    scale: float, window: Optional[int],
):
    b, qi, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    d, bq, bk = head_dim, query_block, key_block
    dv = acc_scr.shape[1]                   # the values' own head width
    rows = group * bq
    lanes = m_scr.shape[1]
    subs = k_ref.shape[1] // bk             # sub-blocks of a copied block
    at = b * pl.num_programs(2) + qi
    first, stop = first_ref[at], stop_ref[at]
    sub0 = (first // subs + j) * subs       # this step's first sub-block
    q_first = ctx_ref[b] + qi * bq          # the block's first position
    valid = valid_ref[b]

    @pl.when(j == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        # the group's heads one under the other: row g * bq + i is
        # query i of head g; log2(e) rides with the scale
        for g in range(group):
            qp_scr[g * bq:(g + 1) * bq, :] = (
                q_ref[0, :, g * d:(g + 1) * d].astype(jnp.float32) *
                (scale * _LOG2E)).astype(qp_scr.dtype)

    reps = bk // lanes

    def sub_block(t, masked: bool):
        keys = pl.ds(pl.multiple_of(t * bk, bk), bk)
        s = jax.lax.dot_general(
            qp_scr[...], k_ref[0, keys, :].astype(qp_scr.dtype),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)         # [rows, bk]
        if masked:
            k_pos = (sub0 + t) * bk + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            q_pos = q_first + (row & (bq - 1) if bq & (bq - 1) == 0
                               else jax.lax.rem(row, bq))
            live = (k_pos <= q_pos) & (k_pos < valid)
            if window is not None:
                live &= k_pos > q_pos - window
            s = jnp.where(live, s, _NEG_INF)
        # The running maximum rides every lane of its row and the
        # running sum stays a sum a lane: what crosses lanes in a
        # sub-block is one maximum a row (the sum's lanes meet once,
        # at the end), and `s - m` and `acc * fade` are vreg by vreg.
        m_prev = m_scr[...]                             # [rows, lanes]
        m_lane = s[:, :lanes]
        for c in range(1, reps):
            m_lane = jnp.maximum(m_lane, s[:, c * lanes:(c + 1) * lanes])
        m_new = jnp.maximum(m_prev,
                            jnp.max(m_lane, axis=1, keepdims=True))
        p = jnp.exp2(s - _lanes(m_new, bk))
        if masked:      # (a row with no live key yet: s - m_new = 0)
            p = jnp.where(live, p, 0.0)
        fade = jnp.exp2(m_prev - m_new)
        l_lane = p[:, :lanes]
        for c in range(1, reps):
            l_lane = l_lane + p[:, c * lanes:(c + 1) * lanes]
        l_scr[...] = l_scr[...] * fade + l_lane
        pv = jax.lax.dot_general(
            p.astype(qp_scr.dtype),
            v_ref[0, keys, :].astype(qp_scr.dtype),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)         # [rows, dv]
        acc_scr[...] = acc_scr[...] * _lanes(fade, dv) + pv
        m_scr[...] = m_new

    @pl.when(sub0 < stop)
    def _():
        def visit(t, _):
            lo = (sub0 + t) * bk        # the sub-block's first key
            # an edge crosses it: a key past the first query's own
            # position, past the row's last valid key, or at or before
            # the last query's window
            edge = (lo + bk - 1 > q_first) | (lo + bk > valid)
            if window is not None:
                edge |= lo <= q_first + bq - 1 - window

            @pl.when(edge)
            def _():
                sub_block(t, True)

            @pl.when(jnp.logical_not(edge))
            def _():
                sub_block(t, False)

        jax.lax.fori_loop(jnp.maximum(first - sub0, 0),
                          jnp.minimum(stop - sub0, subs), visit, None)

    @pl.when(j == pl.num_programs(3) - 1)
    def _():
        l = jnp.sum(l_scr[...], axis=1, keepdims=True)
        # (a query that saw no key: l = 0 and acc = 0, so zeros)
        out = acc_scr[...] / jnp.where(l == 0.0, 1.0, l)
        for g in range(group):
            o_ref[0, :, g * dv:(g + 1) * dv] = \
                out[g * bq:(g + 1) * bq].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "sliding_window", "query_block",
                     "key_block", "key_major", "interpret"))
def _prefill_flash_impl(q, k, v, context_lens, kv_valid_lens, *, scale,
                        sliding_window, query_block, key_block,
                        key_major, interpret):
    b, s, num_q_heads, d = q.shape
    kv_len, num_kv_heads, dv = k.shape[1], k.shape[2], v.shape[3]
    group = num_q_heads // num_kv_heads
    bq, bk = query_block, key_block
    subs = key_major // bk
    query_blocks, majors = s // bq, kv_len // key_major
    context_lens = context_lens.astype(jnp.int32)
    kv_valid_lens = kv_valid_lens.astype(jnp.int32)
    # each row's own ranges (the `jnp` walk takes the union over rows)
    first, stop = jax.vmap(lambda ctx, valid: prefill_tile_ranges(
        ctx[None], valid[None], s, kv_len, bk, sliding_window,
        query_block=bq))(context_lens, kv_valid_lens)
    steps = majors
    if sliding_window is not None:
        # a query block's keys span bq + window - 1 positions
        steps = min(majors, (bq + sliding_window - 2) // key_major + 2)

    def q_map(row, head, qi, j, *_):
        return row, qi, head

    def kv_map(row, head, qi, j, first_ref, stop_ref, *_):
        at = row * query_blocks + qi
        last = (stop_ref[at] + subs - 1) // subs - 1
        major = jnp.minimum(first_ref[at] // subs + j, last)
        return row, jnp.clip(major, 0, majors - 1), head

    rows = group * bq
    lanes = min(128, bk)    # (narrower only in the tests' tiny blocks)
    kernel = functools.partial(
        _flash_kernel, group=group, head_dim=d, query_block=bq,
        key_block=bk, scale=scale, window=sliding_window)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b, num_kv_heads, query_blocks, steps),
            in_specs=[pl.BlockSpec((1, bq, group * d), q_map),
                      pl.BlockSpec((1, key_major, d), kv_map),
                      pl.BlockSpec((1, key_major, dv), kv_map)],
            out_specs=pl.BlockSpec((1, bq, group * dv), q_map),
            scratch_shapes=[pltpu.VMEM((rows, d), q.dtype),
                            pltpu.VMEM((rows, lanes), jnp.float32),
                            pltpu.VMEM((rows, lanes), jnp.float32),
                            pltpu.VMEM((rows, dv), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, s, num_q_heads * dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(first.reshape(-1), stop.reshape(-1), context_lens, kv_valid_lens,
      q.reshape(b, s, num_q_heads * d),
      k.reshape(b, kv_len, num_kv_heads * d),
      v.reshape(b, kv_len, num_kv_heads * dv))
    return out.reshape(b, s, num_q_heads, dv)


def prefill_flash_attention(
    q: jax.Array,                 # [batch, seq, num_q_heads, head_dim]
    k: jax.Array,                 # [batch, kv_len, num_kv_heads, head_dim]
    v: jax.Array,                 # [batch, kv_len, num_kv_heads, v_dim]
    context_lens: jax.Array,      # [batch] prefix lengths (0 for plain)
    kv_valid_lens: jax.Array,     # [batch] valid kv entries (rest padded)
    scale: float,
    sliding_window: Optional[int] = None,
    *,
    blocks: Optional[Tuple[int, int, int]] = None,
    interpret: bool = False,
) -> jax.Array:
    """`ops/attention.py::prefill_attention` as the flash kernel of
    this module: the same arguments (but ALiBi, which the kernel does
    not take), the same `[batch, seq, heads, v_dim]` output in `q`'s
    type, zeros at a query that sees no key. `v_dim` is read from `v`
    and may be narrower or wider than `q`'s and `k`'s `head_dim`.
    Compiled, both are multiples of the 128 lanes (the layer pads
    them, as for its decode kernel).

    `blocks` (queries a block, keys a sub-block, keys a copied block)
    are `choose_blocks`' unless given (the tests', in interpret mode,
    and `benchmarks/prefill_ab.py`'s): the first divides the queries,
    the second the third and the third the keys. Left to
    `choose_blocks`, queries and keys are first padded to `TOKEN_TILE`
    (a pad key lies past every row's valid keys; a pad query is sliced
    off)."""
    s, num_q_heads, d = q.shape[1:]
    num_kv_heads = k.shape[2]
    if num_q_heads % num_kv_heads:
        raise ValueError(f"{num_q_heads=} % {num_kv_heads=}")
    dv = v.shape[-1]
    if not interpret and (d % 128 or dv % 128):
        raise ValueError(f"{d=} or {dv=} is no multiple of the 128 lanes")
    if blocks is None:
        def tiled(x):
            pad = -x.shape[1] % TOKEN_TILE
            return jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0))) \
                if pad else x
        q, k, v = tiled(q), tiled(k), tiled(v)
        blocks = choose_blocks(q.shape[1], k.shape[1],
                               num_q_heads // num_kv_heads, sliding_window)
    query_block, key_block, key_major = blocks
    if q.shape[1] % query_block or k.shape[1] % key_major or \
            key_major % key_block:
        raise ValueError(
            f"blocks {blocks} do not divide {q.shape[1]} queries and "
            f"{k.shape[1]} keys")
    out = _prefill_flash_impl(
        q, k, v, context_lens, kv_valid_lens, scale=float(scale),
        sliding_window=sliding_window, query_block=query_block,
        key_block=key_block, key_major=key_major, interpret=interpret)
    return out[:, :s]
