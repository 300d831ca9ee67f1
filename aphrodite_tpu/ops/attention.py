"""Attention ops: prefill (dense causal) and paged decode.

jnp/XLA implementations — correctness baselines that run on CPU and
compile on TPU. The bandwidth-optimal Pallas decode kernel lives in
`ops/pallas/paged_attention.py`; `aphrodite_tpu.modeling.layers.attention`
dispatches between them.

Reference equivalents: xformers prompt path + ALiBi/sliding-window masks
(`modeling/layers/attention.py:104-161`), paged_attention_v1/v2 decode
kernels (`kernels/attention/attention_kernels.cu:717,907`), prefix-prefill
context attention (`triton_kernel/prefix_prefill.py:609`).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_NEG_INF = float("-inf")


def make_causal_mask(
    seq_len: int,
    context_len: jax.Array,      # [batch] tokens already cached (prefix)
    kv_len: int,
    sliding_window: Optional[int] = None,
) -> jax.Array:
    """Boolean [batch, seq_len, kv_len] mask: True = attend.

    Query position i (0-based within the new chunk) has absolute position
    context_len + i; it may attend to kv positions <= its absolute
    position, within the sliding window if set.
    """
    q_pos = jax.lax.broadcasted_iota(jnp.int32, (seq_len, kv_len), 0)
    kv_pos = jax.lax.broadcasted_iota(jnp.int32, (seq_len, kv_len), 1)
    # [batch, seq, kv]
    abs_q = q_pos[None] + context_len[:, None, None]
    mask = kv_pos[None] <= abs_q
    if sliding_window is not None:
        mask &= kv_pos[None] > (abs_q - sliding_window)
    return mask


def make_alibi_bias(alibi_slopes: jax.Array, kv_len: int) -> jax.Array:
    """[num_heads, 1, kv_len] additive bias (reference
    `layers/attention.py:196`): bias depends on kv absolute position."""
    positions = jnp.arange(kv_len, dtype=jnp.float32)
    return alibi_slopes[:, None, None] * positions[None, None, :]


def _grouped_scores(q: jax.Array, k: jax.Array, scale: float) -> jax.Array:
    """q [b, s, Hq, d] x k [b, kv, Hkv, d] -> scores [b, Hq, s, kv]
    with GQA head grouping (Hq = Hkv * group)."""
    b, s, num_q_heads, d = q.shape
    num_kv_heads = k.shape[2]
    group = num_q_heads // num_kv_heads
    qg = q.reshape(b, s, num_kv_heads, group, d)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    return scores.reshape(b, num_q_heads, s, k.shape[1])


def prefill_attention(
    q: jax.Array,                 # [batch, seq, num_q_heads, head_dim]
    k: jax.Array,                 # [batch, kv_len, num_kv_heads, head_dim]
    v: jax.Array,                 # [batch, kv_len, num_kv_heads, head_dim]
    context_lens: jax.Array,      # [batch] prefix lengths (0 for plain)
    kv_valid_lens: jax.Array,     # [batch] valid kv entries (rest padded)
    scale: float,
    sliding_window: Optional[int] = None,
    alibi_slopes: Optional[jax.Array] = None,
) -> jax.Array:
    """Dense causal attention for prompt chunks, GQA-aware.

    Handles both plain prefill (context_lens=0, kv = this chunk's K/V) and
    prefix-cached prefill (kv = [prefix ; chunk], context_lens = prefix
    lengths). Padded kv entries (>= kv_valid_lens) are masked out.
    Softmax accumulates in float32 regardless of input dtype.
    """
    b, s, num_q_heads, d = q.shape
    kv_len = k.shape[1]
    scores = _grouped_scores(q, k, scale)  # [b, H, s, kv] f32

    mask = make_causal_mask(s, context_lens, kv_len, sliding_window)
    kv_pos = jnp.arange(kv_len)[None, None, :]
    mask &= kv_pos < kv_valid_lens[:, None, None]

    if alibi_slopes is not None:
        scores += make_alibi_bias(alibi_slopes, kv_len)[None]

    scores = jnp.where(mask[:, None], scores, _NEG_INF)
    # Fully-masked rows (padding queries) are all -inf -> NaN; zero them.
    weights = jnp.nan_to_num(jax.nn.softmax(scores, axis=-1))

    num_kv_heads = k.shape[2]
    group = num_q_heads // num_kv_heads
    wg = weights.reshape(b, num_kv_heads, group, s, kv_len)
    out = jnp.einsum("bkgst,btkd->bskgd", wg, v.astype(jnp.float32))
    return out.reshape(b, s, num_q_heads, d).astype(q.dtype)


#: `prefill_attention` holds float32 scores `[batch, heads, queries,
#: keys]`. From this many (queries x keys) a row on, queries and keys
#: go by in blocks under an online softmax instead
#: (`prefill_attention_blocked`): at 28 heads, 8,192 queries against
#: 8,192 keys are 7.5 GB of scores, a 2,048-token chunk against them
#: 1.9 GB.
BLOCKED_FROM = 1 << 23
KEY_BLOCK = 512


def _tile_grid(seq_len: int, kv_len: int, key_block: int,
               query_block: Optional[int] = None) -> Tuple[int, int, int]:
    """(queries a block, query blocks, key blocks) of the blocked
    prefill: a query block is as long as a key block (or as the flash
    kernel's `query_block`), or the chunk where that is shorter."""
    query_block = min(query_block or key_block, seq_len)
    return query_block, -(-seq_len // query_block), -(-kv_len // key_block)


def prefill_tile_ranges(context_lens, kv_valid_lens, seq_len: int,
                        kv_len: int, key_block: int,
                        sliding_window: Optional[int] = None, xp=jnp,
                        query_block: Optional[int] = None):
    """The key blocks `[first, stop)` that each query block of
    `prefill_attention_blocked` (and, at its own `query_block`, of
    `ops/pallas/prefill_attention.py`'s kernel) visits: for every row the keys that the
    block's queries can see, from its first query's window (or key 0)
    to its last query's own position or the row's last valid key, and
    over the rows the union. A row that can see nothing (a pad row)
    widens no range, and `stop <= first` where none can. A superset of
    what the mask leaves, which decides every element still. `xp` is
    `jnp` inside the program and `numpy` for the host's count of the
    same tiles (`count_prefill_tiles`)."""
    query_block, query_blocks, key_blocks = _tile_grid(
        seq_len, kv_len, key_block, query_block)
    start = xp.arange(query_blocks, dtype=xp.int32)[:, None] * \
        query_block + context_lens[None, :]             # [blocks, batch]
    hi = xp.minimum(start + query_block, kv_valid_lens[None, :]) - 1
    lo = xp.zeros_like(start) if sliding_window is None else \
        xp.maximum(start - sliding_window + 1, 0)
    sees = hi >= lo
    first = xp.min(xp.where(sees, lo // key_block, key_blocks), axis=1)
    stop = xp.max(xp.where(sees, hi // key_block + 1, 0), axis=1)
    return first, xp.minimum(stop, key_blocks)


def count_prefill_tiles(context_lens, kv_valid_lens, seq_len: int,
                        kv_len: int, sliding_window: Optional[int] = None,
                        key_block: int = KEY_BLOCK) -> Tuple[int, int]:
    """(tiles that `prefill_attention_blocked` visits, tiles of the
    padded rectangle) for one call of these shapes, on the host."""
    _, query_blocks, key_blocks = _tile_grid(seq_len, kv_len, key_block)
    first, stop = prefill_tile_ranges(
        np.asarray(context_lens, np.int32),
        np.asarray(kv_valid_lens, np.int32), seq_len, kv_len, key_block,
        sliding_window, xp=np)
    return (int(np.maximum(stop - first, 0).sum()),
            query_blocks * key_blocks)


def prefill_attention_tiles(
    q: jax.Array, k: jax.Array, v: jax.Array, context_lens: jax.Array,
    kv_valid_lens: jax.Array, scale: float,
    sliding_window: Optional[int] = None,
    alibi_slopes: Optional[jax.Array] = None,
    key_block: int = KEY_BLOCK,
) -> Tuple[jax.Array, jax.Array]:
    """`prefill_attention` a tile of `key_block` queries x `key_block`
    keys at a time, and the number of tiles visited. A query block
    walks the key blocks of `prefill_tile_ranges` alone, read from the
    positions inside the program; the running maximum, sum and
    weighted values of its queries are carried from key block to key
    block (the online softmax), so the transient is `[batch, heads,
    key_block, key_block]` whatever the context. The mask is
    `prefill_attention`'s and decides every element; a tile left out
    is one it masks whole, which would have changed no carry."""
    b, s, num_q_heads, d = q.shape
    kv_len, num_kv_heads = k.shape[1], k.shape[2]
    group = num_q_heads // num_kv_heads
    query_block, query_blocks, blocks = _tile_grid(s, kv_len, key_block)
    first, stop = prefill_tile_ranges(
        context_lens, kv_valid_lens, s, kv_len, key_block, sliding_window)
    pad = blocks * key_block - kv_len
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    pad = query_blocks * query_block - s
    if pad:         # (rows past the chunk: sliced off below)
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qg = q.reshape(b, query_blocks, query_block, num_kv_heads, group,
                   d).astype(jnp.float32).swapaxes(0, 1)
    shape = (b, num_kv_heads, group, query_block)

    def queries(visited, block):
        qb, at_q, lo, hi = block
        abs_q = (at_q + jnp.arange(query_block, dtype=jnp.int32)[None, :] +
                 context_lens[:, None])[:, None, None, :, None]  # b,1,1,s,1

        def tile(j, carry):
            m, l, acc, tiles = carry
            at = j * key_block
            kb = jax.lax.dynamic_slice_in_dim(k, at, key_block, axis=1)
            vb = jax.lax.dynamic_slice_in_dim(v, at, key_block, axis=1)
            scores = jnp.einsum("bskgd,btkd->bkgst", qb,
                                kb.astype(jnp.float32)) * scale
            kv_pos = at + jnp.arange(key_block, dtype=jnp.int32)
            if alibi_slopes is not None:
                scores += (alibi_slopes.reshape(num_kv_heads, group, 1, 1)
                           * kv_pos.astype(jnp.float32))[None]
            mask = (kv_pos <= abs_q) & \
                (kv_pos < kv_valid_lens[:, None, None, None, None])
            if sliding_window is not None:
                mask &= kv_pos > abs_q - sliding_window
            scores = jnp.where(mask, scores, _NEG_INF)
            m_new = jnp.maximum(m, scores.max(axis=-1))
            # a query that has seen no key yet keeps m = -inf: its terms
            # are exp(-inf - 0) = 0, not exp(-inf + inf)
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(scores - m_safe[..., None])
            fade = jnp.exp(m - m_safe)
            l = l * fade + p.sum(axis=-1)
            acc = acc * fade[..., None] + jnp.einsum(
                "bkgst,btkd->bkgsd", p, vb.astype(jnp.float32))
            return m_new, l, acc, tiles + 1

        _, l, acc, visited = jax.lax.fori_loop(
            lo, hi, tile, (jnp.full(shape, _NEG_INF, jnp.float32),
                           jnp.zeros(shape, jnp.float32),
                           jnp.zeros(shape + (d,), jnp.float32), visited))
        # a query with no key at all (padding) gives zeros, as above
        return visited, acc / jnp.where(l == 0.0, 1.0, l)[..., None]

    visited, out = jax.lax.scan(
        queries, jnp.int32(0),
        (qg, jnp.arange(query_blocks, dtype=jnp.int32) * query_block,
         first, stop))
    # [blocks, b, Hkv, g, query_block, d] -> [b, s, Hq, d]
    out = out.transpose(1, 0, 4, 2, 3, 5).reshape(
        b, query_blocks * query_block, num_q_heads, d)[:, :s]
    return out.astype(q.dtype), visited


def prefill_attention_blocked(
    q: jax.Array, k: jax.Array, v: jax.Array, context_lens: jax.Array,
    kv_valid_lens: jax.Array, scale: float,
    sliding_window: Optional[int] = None,
    alibi_slopes: Optional[jax.Array] = None,
    key_block: int = KEY_BLOCK,
) -> jax.Array:
    """`prefill_attention_tiles`' output: what a step program takes
    from `BLOCKED_FROM` queries x keys a row on."""
    return prefill_attention_tiles(
        q, k, v, context_lens, kv_valid_lens, scale, sliding_window,
        alibi_slopes, key_block)[0]


def paged_decode_attention_ref(
    q: jax.Array,              # [batch, num_q_heads, head_dim]
    k_pages: jax.Array,        # [num_pages, page_size, Hkv * head_dim]
    v_pages: jax.Array,
    block_tables: jax.Array,   # [batch, pages_per_seq] int32 (OOB padded)
    context_lens: jax.Array,   # [batch]
    scale: float,
    alibi_slopes: Optional[jax.Array] = None,
    kv_scale: float = 1.0,
    window: Optional[int] = None,
) -> jax.Array:
    """Decode attention over the paged cache — jnp reference path.

    Gathers each sequence's pages then runs masked attention. Correct
    everywhere; materializes the gathered KV (extra HBM traffic) which the
    Pallas kernel avoids. `window`: the newest `window` positions of
    the table only, as the kernel's.
    """
    from aphrodite_tpu.ops.kv_cache import gather_pages
    from aphrodite_tpu.ops.kv_quant import dequant_scale
    b, num_q_heads, d = q.shape
    num_kv_heads = k_pages.shape[2] // d
    group = num_q_heads // num_kv_heads
    kv_s = dequant_scale(k_pages.dtype, kv_scale)  # int8 stores value/S

    k = gather_pages(k_pages, block_tables, num_kv_heads)  # [b,Hkv,ctx,d]
    v = gather_pages(v_pages, block_tables, num_kv_heads)
    ctx = k.shape[2]

    qg = q.reshape(b, num_kv_heads, group, d)
    scores = jnp.einsum("bkgd,bktd->bkgt", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * (scale * kv_s)

    if alibi_slopes is not None:
        # [Hq, 1, ctx] -> [1, Hkv, group, ctx] (q head h = kv*group + g)
        bias = make_alibi_bias(alibi_slopes, ctx)
        scores += bias.reshape(1, num_kv_heads, group, ctx)

    positions = jnp.arange(ctx)[None, None, None, :]
    mask = positions < context_lens[:, None, None, None]
    if window is not None:
        mask &= positions >= context_lens[:, None, None, None] - window
    scores = jnp.where(mask, scores, _NEG_INF)
    weights = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgt,bktd->bkgd", weights,
                     v.astype(jnp.float32)) * kv_s
    return out.reshape(b, num_q_heads, d).astype(q.dtype)
