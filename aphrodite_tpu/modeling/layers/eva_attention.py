"""EVA's pooling: one key and value for a chunk of tokens.

EVA (Zheng et al., "Efficient Attention via Control Variates", ICLR
2023), in the form EvaByte's release uses: a query attends over the
exact keys of its own window and, for every chunk of the windows
behind it, over ONE pooled key and value. For the chunk's roped keys
`k_j`, values `v_j` and a head's two learned vectors `phi`, `mu`:

    p_j  = softmax_j( scale * <k_j, phi> )       over the chunk
    kbar = sum_j p_j k_j + mu
    vbar = sum_j p_j v_j

The chunk is the KV page, so a page of K and V pools to one token row
and a finished window's pages pool to whole pages of the same shape
(`summarise_pages`): the pooled rows are keys and values like any
other to the attention kernels, which read them through the sequence's
one block table `[summary pages ; window pages]`
(`processing/block_manager.py`). Plain `jax.numpy`: a window closes
once in `window_size` steps of a row.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def pool_chunks(k: jax.Array, v: jax.Array, phi: jax.Array,
                mu: jax.Array, scale: float
                ) -> Tuple[jax.Array, jax.Array]:
    """`k`, `v`: `[..., chunk, heads, head]`; `phi`, `mu`: `[heads,
    head]`. Returns float32 `(kbar, vbar)`, each `[..., heads,
    head]`: scores and softmax in float32 whatever the operands."""
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
    scores = jnp.einsum("...jhd,hd->...jh", kf,
                        phi.astype(jnp.float32)) * scale
    weights = jax.nn.softmax(scores, axis=-2)
    kbar = jnp.einsum("...jh,...jhd->...hd", weights, kf) + \
        mu.astype(jnp.float32)
    vbar = jnp.einsum("...jh,...jhd->...hd", weights, vf)
    return kbar, vbar


def summarise_pages(k_pages: jax.Array, v_pages: jax.Array,
                    src: jax.Array, dst: jax.Array, phi: jax.Array,
                    mu: jax.Array, scale: float, num_heads: int
                    ) -> Tuple[jax.Array, jax.Array]:
    """Pool the pages `src[i]` of row `i` (a finished window's, in
    order) into the pages `dst[i]`: page `p` of `src[i]` becomes token
    row `p % page` of page `dst[i][p // page]`. `k_pages`, `v_pages`:
    `[pages, page, heads * padded head]`; `src`: `[rows, window
    pages]`, `dst`: `[rows, window pages / page]`. A pad row's `dst`
    holds the out-of-range page id and its write is dropped."""
    if not jnp.issubdtype(k_pages.dtype, jnp.floating):
        raise NotImplementedError(
            "pooled keys over an integer KV cache: serve this model "
            "with --kv-cache-dtype auto or fp8")
    rows, window_pages = src.shape
    page, lanes = k_pages.shape[1], k_pages.shape[2]
    padded_head = lanes // num_heads
    if window_pages != dst.shape[1] * page:
        raise ValueError(
            f"{window_pages} pages of {page} tokens pool to "
            f"{window_pages} rows, not to {dst.shape[1]} whole pages: "
            "the chunk has to be the KV page (--block-size)")
    short = padded_head - phi.shape[-1]
    if short:       # the pages pad a head to the lane tile with zeros
        phi = jnp.pad(phi, ((0, 0), (0, short)))
        mu = jnp.pad(mu, ((0, 0), (0, short)))
    shape = (window_pages, page, num_heads, padded_head)

    def pooled(row):
        # (a row at a time: a window's pages in float32 are 64 MB a
        # side at 32 heads of 128)
        kbar, vbar = pool_chunks(k_pages[row].reshape(shape),
                                 v_pages[row].reshape(shape), phi, mu,
                                 scale)
        return kbar.astype(k_pages.dtype), vbar.astype(v_pages.dtype)

    kbar, vbar = jax.lax.map(pooled, src)
    into = (rows, dst.shape[1], page, lanes)
    return (k_pages.at[dst].set(kbar.reshape(into), mode="drop"),
            v_pages.at[dst].set(vbar.reshape(into), mode="drop"))
